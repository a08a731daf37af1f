"""In-program pipeline parallelism (the 'pp' mesh axis).

The engine's task pipeline already gives *inter*-node pipelining
(SURVEY §2.6 strategy 2); this module adds the in-program counterpart for
models whose repeated trunk is too large for one chip's HBM: a GPipe-style
microbatch schedule laid out TPU-natively —

* stage parameters live stacked on a leading axis sharded over 'pp'
  (each pp rank holds exactly its stage — the HBM win),
* a `lax.scan` runs the M + S - 1 schedule steps; every step each rank
  applies its stage and hands its activation to the next rank with a
  single `ppermute` hop over ICI (neighbor traffic only, no all-to-all),
* bubble steps compute on clamped inputs and are masked out of the
  output, so their cotangents are zero and `jax.grad` through the scan +
  ppermute yields exact pipeline-parallel gradients with no custom VJP.

Composes with 'dp' (batch stays sharded across the pipeline).  The stage
function must be collective-free (tp/sp belong inside a stage only via
nested meshes); shapes are static and the schedule is a fixed-length scan
— nothing here blocks XLA from overlapping the ppermute with the next
step's compute.
"""

from __future__ import annotations

from typing import Any, Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(params_list: Sequence[Any]):
    """Stack S per-stage parameter pytrees into one tree whose leaves have
    a leading stage axis (the axis `make_pipeline` shards over 'pp').
    All stages must share a structure (same module repeated)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def make_pipeline(mesh: Mesh, stage_fn: Callable[[Any, Any], Any],
                  num_microbatches: int, axis: str = "pp"):
    """Build `pipe(stacked_params, x) -> y` running `stage_fn`
    sequentially across the mesh's `axis` ranks with a microbatched
    GPipe schedule.

    stage_fn(stage_params, x) must map (mb, ...) -> (mb, ...) with an
    unchanged shape/dtype (a repeated trunk block).  x is (B, ...) with B
    sharded over 'dp' and divisible by num_microbatches on every dp
    shard; the result equals stage_{S-1}(... stage_0(x)) and is
    replicated over `axis`.
    """
    S = int(mesh.shape[axis])
    M = int(num_microbatches)
    if M < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {M}")

    def local_fn(stacked_local, x_loc):
        # each rank's shard of the stacked params is its own stage
        p_loc = jax.tree_util.tree_map(lambda a: a[0], stacked_local)
        rank = jax.lax.axis_index(axis)
        b = x_loc.shape[0]
        if b % M:
            raise ValueError(
                f"per-shard batch {b} not divisible by "
                f"num_microbatches {M}")
        mb = b // M
        xm = x_loc.reshape((M, mb) + x_loc.shape[1:])
        out0 = jnp.zeros_like(xm)
        buf0 = jnp.zeros_like(xm[0])

        def step(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (clamped in bubble steps);
            # later stages consume the shuttle buffer
            x_in = jnp.where(
                rank == 0,
                jax.lax.dynamic_index_in_dim(
                    xm, jnp.clip(t, 0, M - 1), 0, keepdims=False),
                buf)
            y = stage_fn(p_loc, x_in)
            # neighbor hop stage i -> i+1; rank 0's recv slot gets zeros
            # (never read: rank 0 always takes xm)
            buf_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(S - 1)])
            # the last stage retires microbatch t-(S-1) when it's real;
            # clamped writes are masked so bubbles never clobber output
            oidx = t - (S - 1)
            valid = (rank == S - 1) & (oidx >= 0)
            oclamped = jnp.clip(oidx, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(out, oclamped, 0,
                                               keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, y, cur), oclamped, 0)
            return (buf_next, out), None

        (_, out), _ = jax.lax.scan(step, (buf0, out0),
                                   jnp.arange(M + S - 1))
        # results live on the last rank; psum of the masked value
        # replicates them over the pipeline axis
        out = jax.lax.psum(
            jnp.where(rank == S - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(x_loc.shape)

    def full_spec(leaf, lead_axis):
        return P(*((lead_axis,) + (None,) * (leaf.ndim - 1)))

    def pipe(stacked_params, x):
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != S:
                raise ValueError(
                    f"stacked stage axis has {leaf.shape[0]} stages but "
                    f"mesh axis '{axis}' has {S} ranks; they must match "
                    f"(each rank runs exactly one stage)")
        in_specs = (
            jax.tree_util.tree_map(lambda a: full_spec(a, axis),
                                   stacked_params),
            full_spec(x, "dp"),
        )
        fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=full_spec(x, "dp"), check_vma=False)
        return fn(stacked_params, x)

    return pipe
