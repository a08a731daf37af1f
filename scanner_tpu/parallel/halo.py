"""Stencil halo exchange over a device mesh.

Capability parity: the reference's stencil scheduling gives each task the
extra boundary rows its temporal window needs (derive_stencil_requirements,
dag_analysis.cpp:1328; REPEAT_EDGE boundary).  When a sliced stream is
instead mapped across TPU devices (sequence sharding), the same boundary
rows move as a **halo exchange between neighbor shards over ICI** — a pair
of jax.lax.ppermute shifts, exactly the blockwise/ring neighbor pattern
(SURVEY §5 long-context plan).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _halo_exchange_block(x: jnp.ndarray, lo: int, hi: int,
                         axis_name: str) -> jnp.ndarray:
    """Inside shard_map: extend the local block of a sequence-sharded array
    with `lo` trailing rows of the left neighbor and `hi` leading rows of
    the right neighbor.  Edge shards repeat their own edge (REPEAT_EDGE,
    matching the engine's stencil boundary)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    parts = []
    if lo > 0:
        left = jax.lax.ppermute(x[-lo:], axis_name,
                                [(i, (i + 1) % n) for i in range(n)])
        # shard 0 has no left neighbor: repeat its own first rows
        edge = jnp.repeat(x[:1], lo, axis=0)
        parts.append(jnp.where(idx == 0, edge, left))
    parts.append(x)
    if hi > 0:
        right = jax.lax.ppermute(x[:hi], axis_name,
                                 [(i, (i - 1) % n) for i in range(n)])
        edge = jnp.repeat(x[-1:], hi, axis=0)
        parts.append(jnp.where(idx == n - 1, edge, right))
    return jnp.concatenate(parts, axis=0)


def sharded_stencil_map(fn: Callable, stencil: Sequence[int],
                        mesh: Mesh, axis: str = "sp"):
    """Lift a per-window function to a sequence-sharded array.

    fn(window_block) maps a block of shape (m + lo + hi, ...) to outputs
    (m, ...) where lo = -min(stencil), hi = max(stencil); the returned
    callable takes the full sequence sharded over `axis` and computes every
    output row with neighbor halos exchanged over ICI.
    """
    lo = max(0, -min(stencil))
    hi = max(0, max(stencil))
    n = mesh.shape[axis]

    def local(x):
        padded = _halo_exchange_block(x, lo, hi, axis)
        return fn(padded)

    mapped = shard_map(local, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis))

    def wrapper(x):
        block = x.shape[0] // n
        if max(lo, hi) > block:
            raise ValueError(
                f"stencil halo ({lo},{hi}) exceeds the per-shard block of "
                f"{block} rows ({x.shape[0]} rows over {n} '{axis}' shards);"
                f" multi-hop halos are not supported — use fewer shards or "
                f"a narrower stencil")
        return mapped(x)

    return wrapper


@functools.lru_cache(maxsize=32)
def _mapped_halo(mesh: Mesh, lo: int, hi: int, axis: str):
    """The compiled ppermute pair for one (mesh, halo extent) geometry.
    Cached on the MESH, not per call: rebuilding the shard_map closure
    every exchange defeats jax's compile cache (it keys on function
    identity) and re-traces a fresh XLA program per task — ~1s of
    compile inside the gang's stage phase instead of a ~ms collective."""
    return jax.jit(shard_map(
        functools.partial(_halo_exchange_block, lo=lo, hi=hi,
                          axis_name=axis),
        mesh=mesh, in_specs=P(axis), out_specs=P(axis)))


def warm_halo_exchange(mesh: Mesh, shape, dtype, lo: int, hi: int,
                       axis: str = "hosts") -> None:
    """Run one throwaway exchange on zeros of the real block geometry so
    the trace/compile (and the mesh's first-collective channel setup)
    happens OUTSIDE any timed region.  SPMD: every process in the mesh
    must call this together, with identical arguments."""
    import numpy as np

    exchange_row_halo(mesh, np.zeros(shape, dtype), lo, hi, axis)


def exchange_row_halo(mesh: Mesh, local_block, lo: int, hi: int,
                      axis: str = "hosts"):
    """Exchange boundary rows of a host-sharded row block between
    neighbor processes and return (left_halo, right_halo) as host
    ndarrays — THIS process's view of its neighbors' edges.

    `local_block` is this host's (chunk, ...) rows of a sequence laid
    out contiguously over the mesh's `axis` (every host passes the SAME
    chunk count; the gang pads uneven tails before calling).  The
    exchange is the `_halo_exchange_block` ppermute pair run under
    shard_map over the global mesh, so boundary rows move over ICI/DCN
    instead of each host widening its decode (engine/gang.py sharded
    members).  Edge shards see REPEAT_EDGE copies of their own rows in
    the returned halos — callers that own real data beyond the global
    boundary must source those rows themselves.
    """
    import numpy as np

    from .distributed import host_local_array

    local_block = np.ascontiguousarray(local_block)
    chunk = int(local_block.shape[0])
    if max(lo, hi) > chunk:
        raise ValueError(
            f"halo ({lo},{hi}) exceeds the per-shard block of {chunk} "
            f"rows; multi-hop halos are not supported")
    g = host_local_array(mesh, (axis,), local_block)
    out = _mapped_halo(mesh, lo, hi, axis)(g)
    # P(axis) shards only the row dim; every local device holds an
    # identical replica of this host's padded block
    mine = np.asarray(out.addressable_shards[0].data)
    left = mine[:lo]
    right = mine[lo + chunk:lo + chunk + hi]
    return left, right


def temporal_diff(mesh: Mesh, axis: str = "sp"):
    """Example/standard op: frame-to-previous-frame difference over a
    sequence sharded across devices (the shot-detection primitive)."""
    def block(padded):
        # padded has 1 halo row on the left
        return padded[1:] - padded[:-1]

    return sharded_stencil_map(block, stencil=[-1, 0], mesh=mesh, axis=axis)
