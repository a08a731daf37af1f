"""Multi-host device meshes (jax.distributed).

The reference scales across nodes with one worker process per node and
NCCL/MPI underneath (scanner/engine/worker.cpp:484 topology,
master.cpp:1558-1607 task sharding).  The TPU equivalent is JAX's
multi-process runtime: every host runs the same program, calls
`jax.distributed.initialize`, and sees the GLOBAL device set; meshes built
over `jax.devices()` then span hosts, and XLA routes collectives over
ICI/DCN automatically.  Engine workers opt in via the `coordinator=`
config (engine/service.py Worker), making a pod slice's hosts one logical
accelerator for in-program dp/sp/tp sharding while the task engine keeps
distributing (job, task) work units between programs.  Gang-scheduled
tasks (engine/gang.py) rendezvous here too — one short-lived runtime per
gang epoch, with `shutdown()` tearing the latch down between epochs so a
surviving member can re-form at a NEW coordinator.

Order matters: `initialize()` must run before the first JAX backend touch
in the process.

Failure classification: a rendezvous that does not complete raises
`RendezvousError` — the engine treats it as TRANSIENT (the peer set
changed under us: a member died, a coordinator moved), so the task
requeues strike-free instead of striking a healthy job
(engine/service.py `_is_transient_failure`).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

from ..common import ScannerException

# default bound on how long initialize() may block in the rendezvous
# when the caller passes no explicit timeout: an unbounded default
# would let one lost peer pin every survivor in
# jax.distributed.initialize forever.  300 s matches jax's own default
# — long-lived pod-slice workers (Worker(coordinator=...), whose hosts
# can legitimately come up minutes apart during a node-pool scale-up)
# keep their full budget; gang members pass the much tighter
# [gang] init_timeout_s per gang instead (engine/gang.py).
DEFAULT_INIT_TIMEOUT_S = 300.0


class RendezvousError(ScannerException):
    """Joining (or re-joining) the multi-process runtime failed: the
    coordinator is unreachable, a peer never arrived, or the bounded
    initialization timeout elapsed.  Classified transient by the engine
    — the gang re-forms on the remaining capacity, no blacklist
    strike."""


@dataclass
class CoordinatorConfig:
    """Multi-process JAX runtime wiring for one engine worker/host.

    address: "host:port" of process 0's coordinator service.
    num_processes: total participating processes (hosts).
    process_id: this process's rank in [0, num_processes).
    local_device_ids: optional explicit local device ids (rarely needed;
        TPU runtimes discover their local chips).
    """

    address: str
    num_processes: int
    process_id: int
    local_device_ids: Optional[Sequence[int]] = None


_init_config: Optional[CoordinatorConfig] = None


def initialize(config: CoordinatorConfig,
               init_timeout: Optional[float] = None) -> None:
    """Join the multi-process JAX runtime (idempotent per process for the
    SAME config; a different config while initialized is an error, not a
    silent no-op — call `shutdown()` first to re-form at a new
    coordinator).

    Must be called before any jax.devices()/computation in this process;
    afterwards `jax.devices()` is the global device list and
    `jax.local_devices()` this host's slice.  Meshes built by
    `make_mesh()` then span all hosts.

    `init_timeout` bounds the rendezvous; None applies
    DEFAULT_INIT_TIMEOUT_S — never unbounded, so one lost peer cannot
    pin the survivors in initialize forever.  A failed or timed-out
    rendezvous raises `RendezvousError` (transient to the engine).
    """
    global _init_config
    if _init_config is not None:
        if _init_config != config:
            raise ScannerException(
                f"jax.distributed already initialized with {_init_config}; "
                f"cannot re-initialize with {config} — call shutdown() "
                f"first to rendezvous at a new coordinator")
        return
    import jax

    # CPU-backend runs (tests, dryruns, chaos drills) need the gloo
    # collectives client or every cross-process computation fails with
    # "Multiprocess computations aren't implemented on the CPU
    # backend".  Selected only when the process is pinned to CPU
    # (JAX_PLATFORMS, as force_cpu_platform/cpu_only_env set) — TPU
    # runtimes keep their native ICI/DCN collectives.
    plats = (os.environ.get("JAX_PLATFORMS") or "").lower()
    if "cpu" in [p.strip() for p in plats.split(",")]:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    kwargs = {}
    if config.local_device_ids is not None:
        kwargs["local_device_ids"] = list(config.local_device_ids)
    if init_timeout is None:
        init_timeout = DEFAULT_INIT_TIMEOUT_S
    kwargs["initialization_timeout"] = int(init_timeout)
    # timestamped rendezvous events onto the caller's current span
    # (engine/gang.py runs this under its gang.rendezvous span):
    # connect -> initialized brackets the actual coordinator wait, so
    # a slow member's join cost is readable off the merged timeline
    from ..util import tracing as _tracing
    _tracing.add_event("rendezvous.connect", address=config.address,
                       process_id=config.process_id,
                       num_processes=config.num_processes)
    try:
        jax.distributed.initialize(
            coordinator_address=config.address,
            num_processes=config.num_processes,
            process_id=config.process_id,
            **kwargs)
    except Exception as e:  # noqa: BLE001 — jax surfaces rendezvous
        # failure as RuntimeError and timeouts as XlaRuntimeError
        # (DEADLINE_EXCEEDED) depending on version; both are the same
        # transient peer-set failure to the engine
        _tracing.add_event("rendezvous.failed",
                           error=f"{type(e).__name__}")
        raise RendezvousError(
            f"jax.distributed.initialize failed for "
            f"process {config.process_id}/{config.num_processes} at "
            f"{config.address}: {e}") from e
    _tracing.add_event("rendezvous.initialized",
                       process_id=config.process_id)
    _init_config = config


def shutdown() -> None:
    """Leave the multi-process runtime and RESET the re-init latch.

    Before this existed, `_init_config` was set once per process and any
    different config raised forever — a surviving gang member could
    never rendezvous at a new coordinator after its gang aborted.  Now
    the distributed client shuts down cleanly, the latch resets, and a
    follow-up `initialize()` with a NEW config (new coordinator, new
    num_processes) is legal.  Backend handles built over the old global
    device set are cleared best-effort; gang members avoid the issue
    entirely by running one process per epoch (engine/gang.py).
    Idempotent; never raises."""
    global _init_config
    if _init_config is None:
        return
    try:
        import jax
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — a dead coordinator must not
        pass           # wedge the teardown path
    try:
        import jax
        # drop cached backends so a later initialize() rebuilds the
        # global device view for the NEW process set (deprecated alias
        # on some versions; best-effort either way)
        jax.clear_backends()
    except Exception:  # noqa: BLE001
        pass
    _init_config = None


def is_initialized() -> bool:
    return _init_config is not None


def current_config() -> Optional[CoordinatorConfig]:
    """The config this process is initialized with, or None."""
    return _init_config


def ceil_chunk(n_rows: int, num_shards: int) -> int:
    """Rows per shard under the ceil-chunk layout (the uneven-staging
    unit: every shard holds `chunk` rows except a short or empty tail)."""
    if num_shards <= 0:
        raise ScannerException(f"num_shards must be > 0, got {num_shards}")
    return -(-max(int(n_rows), 0) // num_shards) if n_rows > 0 else 0


def shard_rows(n_rows: int, rank: int, num_shards: int) -> tuple:
    """Contiguous row shard [lo, hi) of rank `rank` under the ceil-chunk
    layout: equal `ceil(n/num)` chunks with the remainder on the LAST
    non-empty shard (tail shards may be empty).  This is the one row
    layout shared by `shard_range` on the data plane (engine/gang.py)
    and the uneven `host_local_array` staging below — data decoded per
    this split stages with zero re-indexing."""
    chunk = ceil_chunk(n_rows, num_shards)
    lo = min(rank * chunk, n_rows)
    hi = min((rank + 1) * chunk, n_rows)
    return lo, hi


def host_local_array(mesh, spec, local_data, global_rows: Optional[int]
                     = None):
    """Assemble a global jax.Array from THIS process's shard of the data.

    `local_data` is the numpy block this host contributes (its slice along
    the sharded axes); the result is a global array laid out per `spec`
    over `mesh`.  The per-host data-feeding primitive for input pipelines
    (each engine worker decodes only its own rows).

    `global_rows` engages the UNEVEN staging path for row counts not
    divisible by the host axis (the last-shard-remainder case
    `shard_rows` produces): each host passes only its own rows —
    possibly fewer than a full chunk, possibly zero — and the function
    zero-pads every host block to `ceil_chunk` rows so XLA sees an
    evenly divisible global array of `num_hosts * chunk` rows.  Callers
    slice logical rows back out after any gather (`all_gather_rows`
    does this for you); zero padding is also identity-safe under the
    digest-sum collectives.  Requires the LEADING dim sharded over a
    single mesh axis (the gang "hosts" layout).
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    if not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec)
    if global_rows is None:
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), local_data)
    axis = spec[0] if len(spec) else None
    if not isinstance(axis, str):
        raise ScannerException(
            "uneven host_local_array staging requires the leading dim "
            f"sharded over one named mesh axis, got spec {spec}")
    num = int(mesh.shape[axis])
    chunk = ceil_chunk(int(global_rows), num)
    local_data = np.asarray(local_data)
    if len(local_data) > chunk:
        raise ScannerException(
            f"host block of {len(local_data)} rows exceeds the "
            f"ceil-chunk of {chunk} ({global_rows} rows over {num} "
            f"'{axis}' shards)")
    padded = np.zeros((chunk,) + local_data.shape[1:], local_data.dtype)
    if len(local_data):
        padded[:len(local_data)] = local_data
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), padded)


@functools.lru_cache(maxsize=32)
def _replicated_identity(mesh):
    """The jitted replicate-everything identity for one mesh.  Cached on
    the mesh: rebuilding the jit per call keys jax's compile cache on a
    fresh lambda every time, so each gather re-traces — a ~100ms-1s tax
    per collective instead of a one-time compile."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.jit(lambda a: a,
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def all_gather_rows(mesh, axis: str, local_block,
                    global_rows: Optional[int] = None):
    """All-gather per-host row blocks into one full host ndarray on
    EVERY process: stage this host's block via `host_local_array`
    (uneven-aware when `global_rows` is passed) and run one jitted
    identity whose output sharding is fully replicated — XLA lowers the
    resharding to an all-gather over ICI/DCN (gloo on CPU runs).  The
    transport primitive sharded gang members assemble their output
    shards through (engine/gang.py)."""
    import jax
    import numpy as np

    arr = host_local_array(mesh, (axis,), local_block,
                           global_rows=global_rows)
    rep = _replicated_identity(mesh)(arr)
    out = np.asarray(jax.device_get(rep))
    return out[:global_rows] if global_rows is not None else out


def replicate_to_global(mesh, spec, full_data):
    """Place an identical host array (present on every process) as a global
    sharded array — convenient for params/targets in tests and small
    inputs.  Every process must pass the same `full_data`."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec)
    return jax.device_put(full_data, NamedSharding(mesh, spec))
