"""Per-task graph evaluation over batched columns.

Capability parity: reference scanner/engine/evaluate_worker.cpp:408-1328
(EvaluateWorker: row bookkeeping, stencil cache, batching, builtin
sample/space/slice/unslice remapping, per-slice arg rebinding, state reset).

One TaskEvaluator owns the kernel instances of one pipeline instance and
executes tasks end-to-end in column space: {(node_id, column): ColumnBatch}.
A task's frames live in ONE contiguous batch from decode to sink — builtins
are vectorized gathers/relabels on the batch, device kernels receive
on-device slices and chain device-to-device (the reference's pooled
block-allocator + per-call repacking, memory.cpp:269 /
evaluate_worker.cpp:1040-1100, replaced by zero-copy views + a single
host->device transfer per column).

Shape-stable dispatch: XLA compiles one executable per (shape, dtype)
signature, and a TPU compile costs seconds — so device-kernel calls are
routed through a small power-of-two bucket ladder (`bucket_ladder`).  A
tail chunk pads up to the next bucket by edge-repeating its last row
(the REPEAT_EDGE convention stencils already use) and the padding is
sliced off before results are emitted; null-propagated rows ride through
the call at the full chunk shape and are overwritten with NullElement
afterward, so neither task geometry nor null sparsity ever mints a new
executable.  Host/python kernels keep exact shapes (retracing is free
there), and stateful kernels do too (padding rows would advance their
state).  `TaskEvaluator(precompile=...)` warms each device op's ladder
on a background thread — overlapped with the first task's decode — so
steady-state tasks never stall on a compile."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import functools
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import (DeviceType, GraphException, JobException, NullElement,
                      ScannerException, SliceList)
from ..graph import analysis as A
from ..graph import fusion as _fusion
from ..graph import ops as O
from ..util import coststats as _cs
from ..util import memstats as _ms
from ..util import metrics as _mx
from ..util import tracing as _tracing
from ..util.log import get_logger
from ..util.profiler import Profiler
from .batch import (ColumnBatch, concat_batches, is_array_data, rows_at,
                    rows_run)

_log = get_logger("evaluate")

# per-op live row counts, and the evaluator's host seconds by op: the
# `evaluate:<op>` span whole, and inside it what the host spent in the
# op's calls (for a device op the enqueue) and what it waited for the
# chip; each recorded with the span of its name at the same clock reads
_M_OP_ROWS = _mx.registry().counter(
    "scanner_tpu_op_rows_total",
    "Rows evaluated per op (kernel calls, warmup rows included).",
    labels=["op"])
_M_OP_SECONDS = _mx.registry().counter(
    "scanner_tpu_op_seconds_total",
    "Evaluator seconds inside an op's evaluate:<op> span (a task or "
    "chunk a span), by op and where it ran: `host` for a host op and "
    "the builtins (Sample, Space, Slice, Unslice, Output), else the "
    "chip's label, where the seconds are the host's: the enqueue of "
    "the op's calls and any wait for the chip among them.",
    labels=["op", "device"])
_M_OP_INPUT_SECONDS = _mx.registry().counter(
    "scanner_tpu_op_input_seconds_total",
    "Evaluator seconds an op without a stencil spent having its input "
    "columns where it runs before its first call of a task or chunk "
    "(staging's dispatch, the wire conversion's dispatch, the rows "
    "looked up); mirrors the evaluate:inputs span.  A stencilled op's "
    "are scanner_tpu_stencil_window_seconds_total.",
    labels=["op"])
_M_OP_DISPATCH_SECONDS = _mx.registry().counter(
    "scanner_tpu_op_dispatch_seconds_total",
    "Evaluator seconds inside a batched op's execute calls (a fused "
    "chain's program call), a call a span (evaluate:dispatch): for a "
    "device op the host's enqueue, a first call's compile included; "
    "for a host op the work itself.",
    labels=["op"])
_M_DEVICE_WAIT_SECONDS = _mx.registry().counter(
    "scanner_tpu_device_wait_seconds_total",
    "Evaluator seconds held back by the chip (evaluate:device_wait): "
    "the wait for the oldest of the two calls an evaluator keeps in "
    "flight, taken before the dispatch that would make a third, at the "
    "evaluator's release, and the drain after a first call; by the op "
    "whose call was waited for.",
    labels=["op"])
# the window of calls in flight (CallWindow)
_M_OP_CALLS = _mx.registry().counter(
    "scanner_tpu_op_calls_total",
    "Batched device calls an evaluator dispatched, per op (a fused "
    "chain under its id).",
    labels=["op"])
_M_OP_CALLS_DEFERRED = _mx.registry().counter(
    "scanner_tpu_op_calls_deferred_total",
    "Batched device calls whose wait was taken after a later call had "
    "been dispatched, or at the evaluator's release: the host prepared "
    "the next call while the chip ran this one.  What is left of "
    "scanner_tpu_op_calls_total was drained at once: a signature's "
    "first call.",
    labels=["op"])
_M_OP_CALLS_BLOCKED = _mx.registry().counter(
    "scanner_tpu_op_calls_blocked_total",
    "Deferred waits that found the call still running: the chip set "
    "the pace there (and the call was timed for the roofline gauges). "
    "The others found it done: the host did.",
    labels=["op"])
# a device column handed to a host op: the wait for its producer's
# program, the row-major layout where the chip held it otherwise
# (ColumnBatch.prefetch_host, started when the producer's call was
# dispatched) and the copy to the host; the evaluate:handoff span,
# inside the op's evaluate:inputs or evaluate:window
_M_HANDOFF_SECONDS = _mx.registry().counter(
    "scanner_tpu_op_handoff_seconds_total",
    "Evaluator seconds a host op (label) waited for a device column to "
    "reach the host, a task or chunk: mirrors the evaluate:handoff "
    "span.",
    labels=["op"])
_M_HANDOFF_BYTES = _mx.registry().counter(
    "scanner_tpu_op_handoff_bytes_total",
    "Bytes of the device columns brought to the host for a host op "
    "(label).",
    labels=["op"])
_M_HANDOFF_ROWS = _mx.registry().counter(
    "scanner_tpu_op_handoff_rows_total",
    "Rows of the device columns brought to the host for a host op, by "
    "how they came: `relaid` (the chip held the column off row-major, "
    "planar frames, and one program laid it out row-major before the "
    "copy), `asis` (copied as it lay).  Either way the host's array "
    "is C-contiguous and its rows are contiguous views.",
    labels=["op", "layout"])
# a stencilled op's window: what it costs to have the producer's column
# where the op runs (for a host op behind a device op, the wait for the
# producer's kernel and the device->host fetch), and the rows the
# producer evaluated only for the window's reach over a task boundary
_M_WINDOW_SECONDS = _mx.registry().counter(
    "scanner_tpu_stencil_window_seconds_total",
    "Seconds a stencilled op spent having its window ready before its "
    "first call of a task or chunk: its input columns brought to where "
    "it runs (a device producer's column fetched to a host op waits "
    "for the producer's kernel) and the window's rows looked up; "
    "mirrors the evaluate:window span.",
    labels=["op"])
_M_HALO_ROWS = _mx.registry().counter(
    "scanner_tpu_stencil_halo_rows_total",
    "Rows of a producing op (label) that a task or chunk evaluated or "
    "loaded only because a consumer's stencil window reaches outside "
    "the consumer's own rows there: the back-reach over a task "
    "boundary, paid again by each task.",
    labels=["op"])
# the window argument itself: a stencilled op over an array column is
# handed a (k, W, ...) array gathered from it, a copy of every row W
# times over (the evaluate:gather span, inside evaluate:<op>)
_M_GATHER_SECONDS = _mx.registry().counter(
    "scanner_tpu_stencil_gather_seconds_total",
    "Evaluator seconds a stencilled op spent gathering its (k, W, ...) "
    "window argument out of its input column's array, a call (for a "
    "device column the dispatch of the gather program, not its run); "
    "mirrors the evaluate:gather span.",
    labels=["op"])
_M_GATHER_BYTES = _mx.registry().counter(
    "scanner_tpu_stencil_gather_bytes_total",
    "Bytes of the (k, W, ...) window arguments gathered for a "
    "stencilled op: W times its input rows, padding rows included.",
    labels=["op"])
_M_OP_RECOMPILES = _mx.registry().counter(
    "scanner_tpu_op_recompiles_total",
    "New input (device, shape, dtype) signatures seen per kernel "
    "instance — each one is a fresh executable for a jitted kernel "
    "unless a cache serves it; a climbing count means shape churn.  "
    "With bucketed dispatch this is bounded by the op's bucket-ladder "
    "size PER CHIP (evaluator affinity compiles each ladder once per "
    "assigned device).  Compiles that really ran are "
    "scanner_tpu_compile_total.",
    labels=["op", "device"])
_M_OP_PAD_ROWS = _mx.registry().counter(
    "scanner_tpu_op_pad_rows_total",
    "Edge-repeat padding rows added by bucketed dispatch to round tail "
    "chunks up to a bucket shape (padding waste; the price of never "
    "re-tracing), per op and assigned device.",
    labels=["op", "device"])
# a stateful op's price: the resets the engine fires on its kernel, and
# the rows it computes to make state that no consumer asked for
_M_STATE_RESETS = _mx.registry().counter(
    "scanner_tpu_state_resets_total",
    "Resets the engine fired on a stateful kernel inside a stream: at "
    "the first compute row of a task that stands alone (bounded state "
    "with a warm-up) and at every row discontinuity.  The reset that "
    "goes with a stream's binding (new_stream, every kernel) is not "
    "among them.",
    labels=["op"])
_M_STATE_WARMUP_ROWS = _mx.registry().counter(
    "scanner_tpu_state_warmup_rows_total",
    "Rows an op computed that no consumer asked for: a task's or "
    "chunk's compute rows less its valid output rows (a bounded-state "
    "op's warm-up, an unbounded one's replay from row 0).",
    labels=["op"])
_M_OP_PRECOMPILE = _mx.registry().gauge(
    "scanner_tpu_op_precompile_seconds",
    "Seconds the setup-time warm-up spent precompiling this device "
    "op's bucket ladder on its assigned chip (overlapped with the "
    "first task's decode).",
    labels=["op", "device"])
_M_WARMING = _mx.registry().gauge(
    "scanner_tpu_evaluator_warming",
    "Evaluator work in flight that may block on XLA rather than on the "
    "device: kernel construction and set-up, bucket-ladder warm-up "
    "runs, and first dispatches of a new input signature.  While it "
    "is above 0 a full evaluate queue or a busy evaluate stage says "
    "nothing about load; the load-signal alert rules hold quiet on it "
    "(util/health.py `unless`).")


@contextlib.contextmanager
def _warming():
    _M_WARMING.inc()
    try:
        yield
    finally:
        _M_WARMING.dec()


@contextlib.contextmanager
def _first_call(op: str, dev_label: str, bucket: int, signature: str,
                track_cost: bool,
                members: Optional[Sequence[str]] = None):
    """Around the first dispatch of a new (device, shape, dtype)
    signature — the call that may compile: counted as warming, and with
    coststats on any XLA compile inside lands in the compile ledger
    under (op, device, bucket)."""
    with _warming():
        if track_cost:
            with _cs.observe_compiles(op, dev_label, bucket, signature,
                                      members=members):
                yield
        else:
            yield


Elem = Any  # np.ndarray | bytes | arbitrary python object | NullElement
ColKey = Tuple[int, str]  # (node id, column name)


def _is_null(e: Elem) -> bool:
    return isinstance(e, NullElement)


_BACKEND: Optional[str] = None


def _accel_backend() -> bool:
    """True when the default JAX backend is an accelerator.  Device staging
    is pointless (an extra copy) when jax itself runs on host."""
    global _BACKEND
    if _BACKEND is None:
        import jax
        _BACKEND = jax.default_backend()
    return _BACKEND != "cpu"


# ---------------------------------------------------------------------------
# Multi-chip evaluator affinity
# ---------------------------------------------------------------------------
#
# The reference scales by pinning one kernel-group instance per GPU
# (KernelConfig.devices, worker.cpp pipeline instance spin-up); the TPU
# analogue is one pipeline instance per local chip.  Evaluator instance
# *i* owns chip *i mod n_devices*: its stdlib device-kernel calls stage
# inputs to THAT chip (committed jax arrays pull the jitted computation
# onto their device), its bucket-ladder warm-up compiles there, and the
# recompile proxy keys per (device, shape, dtype) so the ladder bound
# holds per chip.  Model kernels keep dp-sharding across the instance's
# device partition (all chips when one instance runs, the reference
# behavior; one chip each when instances == chips).


def _affinity_enabled() -> bool:
    """SCANNER_TPU_DEVICE_AFFINITY=0 restores default-chip dispatch for
    every pipeline instance (the pre-affinity behavior; the multichip
    equivalence tests A/B against it)."""
    return os.environ.get("SCANNER_TPU_DEVICE_AFFINITY", "1") \
        not in ("0", "false")


def kernel_devices() -> Optional[List[Any]]:
    """This host's jax devices, when kernels should see them: always on
    accelerator backends; on the CPU backend only with
    SCANNER_TPU_KERNEL_DEVICES=all, so dryruns/tests exercise the
    multi-chip paths on a virtual multi-device host.  None = kernels run
    wherever jax defaults to (single-device host semantics)."""
    if os.environ.get("SCANNER_TPU_KERNEL_DEVICES") == "all" \
            or _accel_backend():
        import jax
        return list(jax.local_devices())
    return None


def _device_staging_enabled() -> bool:
    """Whether ColumnBatch data is staged onto jax devices for device
    kernels.  On by nature on accelerator backends; the virtual
    multi-device host (SCANNER_TPU_KERNEL_DEVICES=all) opts in so the
    per-chip staging/dispatch paths are testable on CPU."""
    return _accel_backend() \
        or os.environ.get("SCANNER_TPU_KERNEL_DEVICES") == "all"


def assigned_device(instance: int) -> Optional[Any]:
    """The chip pipeline instance `instance` owns — chip `instance mod
    n_devices`, independent of the instance count (instance_devices'
    partitions always lead with this same chip, so the two mappings
    agree for any count) — or None when placement should stay with
    jax's default device (affinity off, host backend without virtual
    devices, or a single chip).  Used by both the evaluator (kernel
    staging/warm-up) and the executor (TaskItem device assignment at
    enqueue time): one mapping, two sides of the handoff."""
    if not _affinity_enabled():
        return None
    devs = kernel_devices()
    if not devs or len(devs) <= 1:
        return None
    return devs[instance % len(devs)]


def instance_devices(instance: int, instances: int = 1
                     ) -> Optional[List[Any]]:
    """Device list instance `instance`'s kernels see (the dp-shard set
    for model kernels).  One instance keeps the whole host's chips
    (today's DataParallelApply behavior); N instances partition them so
    concurrent instances never shard over each other's chips."""
    devs = kernel_devices()
    if not devs:
        return None
    if not _affinity_enabled() or len(devs) <= 1 or instances <= 1:
        return devs
    if instances <= len(devs):
        return devs[instance::instances]
    return [devs[instance % len(devs)]]


def default_pipeline_instances(configured: Optional[int] = None) -> int:
    """Resolve the pipeline-instance count for this node: an explicit
    setting wins — ANY explicit value, including 1 (a user bounding
    memory or serializing evaluation must not be overridden) — and only
    an unset count (None/0) becomes one instance per local chip on
    multi-device hosts (the tentpole default: a v5e-8 worker runs 8
    device-affine instances instead of contending for chip 0), else 1.
    SCANNER_TPU_DEVICE_AFFINITY=0 disables the per-chip resolution."""
    if configured:
        return int(configured)
    devs = kernel_devices() if _affinity_enabled() else None
    if devs and len(devs) > 1:
        return len(devs)
    return 1


def usable_cores() -> int:
    """Cores this process may run on: its affinity mask where the
    platform has one (a container's or taskset's share of the host),
    else the machine's count."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def default_load_workers(configured: Optional[int] = None,
                         instances: int = 1, queues: int = 1,
                         qsize: int = 4, tasks: int = 0,
                         decoder_threads: int = 1,
                         streaming: bool = True) -> int:
    """Resolve the loader-thread count of one pipeline run, under
    default_pipeline_instances' contract: an explicit setting wins as
    given — ANY value, 1 included — and only an unset count (None/0)
    is derived, as the least of what the run can use:

    - the host: usable_cores() less one per evaluator thread, one for
      the savers and one for the main thread; a loader takes
      `decoder_threads` of what is left.
    - the pipeline's depth, where tasks load whole (`streaming` off:
      PerfParams.stream_work_packets): `qsize` loaded tasks per queue
      plus the one each of the `instances` evaluators holds are what
      the run means to keep in memory; a loader beyond that would
      hold one more.  A streaming task takes no place in that bound
      (executor.py _loaded_whole): each loader decodes into its own
      task's two-chunk queue, so the stage is as wide as the host.
    - `tasks`, where the run knows its count (0 = open-ended, a
      cluster worker pulling from the master): a one-task query
      starts one loader.
    """
    if configured:
        return int(configured)
    n = (usable_cores() - instances - 2) // max(1, decoder_threads)
    if not streaming:
        n = min(n, queues * qsize + instances)
    if tasks > 0:
        n = min(n, tasks)
    return max(1, n)


# canonical implementation lives with the memory accountant so metrics,
# ledger entries and trace attrs key devices identically; re-exported
# here because the evaluator/executor are its historical home
device_label = _ms.device_label


# ---------------------------------------------------------------------------
# Shape-stable bucketed dispatch
# ---------------------------------------------------------------------------

# smallest bucket: a ladder of {4, 8, ..., cap} bounds the executable
# count at log2(cap/4)+1 while wasting at most 3 padded rows on the
# tiniest call
_MIN_BUCKET = 4


def bucket_ladder(cap: int) -> List[int]:
    """Batch-size buckets for a kernel whose per-call batch cap is `cap`:
    powers of two from min(4, cap) up, with `cap` itself as the top rung
    (so a full chunk never pads).  Every jitted-kernel call shape is one
    of these, so the op compiles at most len(ladder) executables per
    input dtype."""
    cap = max(1, int(cap))
    if cap <= _MIN_BUCKET:
        return [cap]
    ladder = []
    b = _MIN_BUCKET
    while b < cap:
        ladder.append(b)
        b <<= 1
    ladder.append(cap)
    return ladder


def bucket_for(k: int, ladder: Sequence[int]) -> int:
    """Smallest ladder bucket >= k (k must be <= ladder[-1])."""
    for b in ladder:
        if b >= k:
            return b
    return ladder[-1]


def _bucketing_enabled() -> bool:
    """SCANNER_TPU_BUCKETED=0 opts out (exact call shapes, the
    pre-bucketing behavior; padding-equivalence tests A/B against it)."""
    return os.environ.get("SCANNER_TPU_BUCKETED", "1") not in ("0", "false")


def _precompile_enabled() -> bool:
    """Ladder warm-up default: on for accelerator backends (where a cold
    compile stalls the pipeline for seconds), off on the CPU backend
    (retracing is cheap and tests construct many evaluators).
    SCANNER_TPU_PRECOMPILE=1/0 forces either way."""
    flag = os.environ.get("SCANNER_TPU_PRECOMPILE", "")
    if flag in ("0", "false"):
        return False
    if flag in ("1", "force", "true"):
        return True
    return _accel_backend()


def _source_geometry_inputs(node: O.OpNode) -> bool:
    """True when every FRAME input of `node` reaches an Input node
    through builtins only (gathers never change frame geometry), so the
    ladder warm-up's synthesized frames have the source's shape.  An
    intervening kernel (Resize/CropResize/...) may change geometry; its
    consumers skip warm-up rather than compile a wrong-shape ladder —
    and stall their first real call behind it via ensure_warm."""
    for c in node.input_columns():
        if not c.is_frame:
            continue
        p = c.op
        while p.is_builtin and p.name != O.INPUT_OP:
            cols = p.input_columns()
            if not cols:
                return False
            p = cols[0].op
        if p.name != O.INPUT_OP:
            return False
    return True


def _window_rows(data, p):
    return data[p.reshape(-1)].reshape(p.shape + data.shape[1:])


@functools.lru_cache(maxsize=None)
def _window_gatherer():
    import jax
    return jax.jit(jax.named_scope("stencil_window")(_window_rows))


def _gather_window(data, p: np.ndarray):
    """Rows `p` (k, W) of an array column `data` (n, ...) as the
    (k, W, ...) argument of a stencilled op.  A device column goes
    through one jitted program a (column, window) shape, whose device
    operations carry the scope `stencil_window`; a host column through
    numpy."""
    if isinstance(data, np.ndarray):
        return _window_rows(data, p)
    return _window_gatherer()(data, p.astype(np.int32))


def _strip_pad(res, k: int, n_out: int):
    """Drop bucket-padding rows from a kernel result before emission.
    Accepts every result protocol emit_result does: a single batch, a
    tuple of per-column batches, or a list of per-row results/tuples."""
    if n_out > 1 and isinstance(res, tuple) and len(res) == n_out:
        return tuple(r[:k] for r in res)
    try:
        return res[:k]
    except TypeError:
        return res  # malformed result: let emit_result raise its error


def _state_call_lengths(cap: int, warmup: int, wp: int) -> List[int]:
    """The call lengths of a bounded-state kernel over contiguous tasks
    cut into `wp`-row chunks: a task's first chunk computes its warm-up
    and its own rows in calls of `cap`, then a tail; where the warm-up
    is clipped at row 0 the tail is another.  (Sampled rows make other
    lengths; those compile at their first call.)"""
    runs = {warmup + wp} | {cs + wp for cs in range(0, warmup, wp)}
    return sorted({cap} | {r % cap for r in runs if r % cap},
                  reverse=True)


# One call running and one queued keep a chip that runs its calls in
# order fed whenever the host's work a call is shorter than the chip's;
# a third buys nothing and holds a packet's inputs, temporaries and
# result in HBM.  A constant: nothing observes a reason to change it.
CALLS_IN_FLIGHT = 2


@dataclasses.dataclass(slots=True)
class _Call:
    """One dispatched call of the window."""

    result: Any
    op: str                 # the op's name, a fused chain's id
    dev: str
    bucket: int             # rows called, padding included
    rows: int               # rows asked for
    t_dispatch: float
    desc: Optional["_cs.CostDescriptor"]  # None: not to be timed
    saved: Optional[float]  # a chain's HBM bytes saved, for its gauges
    ki: Any                 # the op's KernelInstance, None for a chain
    owner: Any              # the task that dispatched it


class CallWindow:
    """The batched device calls one evaluator has dispatched and not
    yet waited for: at most CALLS_IN_FLIGHT, of whatever op or chain,
    since the evaluator's chip runs them in the order they came.  The
    wait for a call is taken when the dispatch that would make one too
    many is due (`admit`), so the host prepares call k+1 while the chip
    runs call k; it is the evaluator's back-pressure, with coststats on
    or off, and where coststats is on it is also what times the call
    (util/coststats.py CallClock).  The window lives across chunks,
    tasks and ops and is emptied at the evaluator's release (`drain`).

    A call that failed on the chip raises at its wait.  Taken inside
    the task that dispatched it (`owner`), the failure is that task's;
    taken later it is logged, and the task fails where its results are
    fetched.  Either way the op is named, an OOM is noted and a
    stateful kernel is reset."""

    def __init__(self, profiler: Profiler):
        self.profiler = profiler
        self.owner: Any = None  # the task now dispatching
        self._calls: "collections.deque[_Call]" = collections.deque()
        self._clock = _cs.CallClock()
        # op -> [seconds, flops, bytes] timed since the op's last
        # take_run: its next op.efficiency event
        self._runs: Dict[str, List[float]] = {}

    def __len__(self) -> int:
        return len(self._calls)

    def admit(self) -> None:
        """Before a dispatch: make room for its call."""
        while len(self._calls) >= CALLS_IN_FLIGHT:
            self._wait(self._calls.popleft())

    def put(self, result, op: str, dev: str, bucket: int, rows: int,
            t_dispatch: float, first: bool = False,
            desc: Optional["_cs.CostDescriptor"] = None,
            saved: Optional[float] = None, ki: Any = None) -> None:
        """After a dispatch: its call is in flight.  A signature's
        `first` call is drained at once, and what went before it: its
        seconds hold its compile and never read as the op's."""
        c = _Call(result, op, dev, bucket, rows, t_dispatch, desc, saved,
                  ki, self.owner)
        _M_OP_CALLS.labels(op=op).inc()
        if first:
            self.drain()
            self._wait(c, deferred=False)
        else:
            self._calls.append(c)

    def drain(self) -> None:
        """Wait for every call in flight (the evaluator's release, and
        ahead of a first call)."""
        while self._calls:
            self._wait(self._calls.popleft())

    def abandon(self) -> None:
        """The task now dispatching failed: its calls leave the window,
        waited for and neither timed nor heard."""
        mine = [c for c in self._calls if c.owner == self.owner]
        self._calls = collections.deque(
            c for c in self._calls if c.owner != self.owner)
        for c in mine:
            try:
                _cs.wait_ready(c.result)
            except Exception:  # noqa: BLE001 — the task has failed
                _log.debug("abandoned call of %s failed", c.op,
                           exc_info=True)

    def take_run(self, op: str) -> Optional[Tuple[float, float, float]]:
        """(seconds, flops, bytes) of `op`'s calls timed since it was
        last asked, or None."""
        run = self._runs.pop(op, None)
        return tuple(run) if run else None

    def _wait(self, c: _Call, deferred: bool = True) -> None:
        with self.profiler.span(
                "evaluate:device_wait", op=c.op,
                counter=_M_DEVICE_WAIT_SECONDS.labels(op=c.op)) as span:
            blocked = not _cs.result_ready(c.result)
            span.args["blocked"] = blocked
            try:
                _cs.wait_ready(c.result)
            except Exception as e:  # noqa: BLE001
                self._failed(c, e)  # raises inside the call's own task
                return
            t_done = time.time()
        if deferred:
            _M_OP_CALLS_DEFERRED.labels(op=c.op).inc()
            if blocked:
                _M_OP_CALLS_BLOCKED.labels(op=c.op).inc()
        secs = self._clock.done(c.t_dispatch, t_done,
                                blocked and deferred)
        if secs is None or c.desc is None:
            return
        # the chip's seconds joined with the analytical descriptor
        cls = _cs.record_op_call(c.op, c.dev, c.bucket, c.rows, secs,
                                 c.desc)
        if cls is not None and c.saved is not None:
            _fusion.chain_metrics_for(c.op, c.dev, c.bucket, cls, c.saved)
        run = self._runs.setdefault(c.op, [0.0, 0.0, 0.0])
        run[0] += secs
        run[1] += c.desc.flops or 0.0
        run[2] += c.desc.bytes_total

    def _failed(self, c: _Call, e: Exception) -> None:
        """A call failed on the chip.  Raises for the task that
        dispatched it; another task's evaluation goes on."""
        if c.ki is not None and c.ki.spec.is_stateful:
            # as a failure at the dispatch: the state is partial
            try:
                c.ki.kernel.reset()
            finally:
                c.ki._last_row = None
        _note_call_failure(e, f"op {c.op} on {c.dev}")
        if c.owner == self.owner and c.owner is not None:
            raise e
        _log.error(
            "device call of op %s on %s (task %s, %d rows) failed after "
            "its task had left the evaluator; the task fails where its "
            "results are fetched: %s: %s", c.op, c.dev, c.owner, c.rows,
            type(e).__name__, str(e)[:300])


def _note_call_failure(e: BaseException, detail: str) -> None:
    """What a failed device call gets, at its dispatch or at its
    deferred wait, once: the op's name on the exception, and for an OOM
    the forensics (the report names the ledger entries, and their
    tasks, that held HBM when this op's allocation failed)."""
    if getattr(e, "_scanner_tpu_call_noted", False):
        return
    try:
        e._scanner_tpu_call_noted = True
        e.add_note(f"scanner_tpu: in {detail}")
    except Exception:  # noqa: BLE001 — an exception without a __dict__
        pass
    if _ms.is_oom(e):
        _ms.note_oom(e, site="dispatch", detail=detail)


class StateCarryMiss(Exception):
    """A carry plan's premise failed: the kernel instance's state is not
    positioned at the plan's watermark (task reordering, a failed
    predecessor, or a different instance).  The executor catches this and
    re-runs the task with a self-contained plan — affinity is a pure
    optimization, never a correctness dependency."""


class KernelInstance:
    """One live kernel with its stream/state bookkeeping."""

    def __init__(self, node: O.OpNode, profiler: Profiler,
                 devices: Optional[List[Any]] = None,
                 device: Optional[Any] = None):
        assert node.spec is not None and node.spec.kernel_factory is not None
        self.node = node
        self.spec = node.spec
        cfg = O.KernelConfig(device=node.effective_device(),
                             args=dict(node.init_args),
                             devices=devices or [])
        # canonical class identity: an unpickled job spec can carry a
        # cloudpickle by-value class COPY of a locally-registered op;
        # instantiating the registered original keeps class-level state
        # (and identity-sensitive tests) on one class object
        factory = O.registry.canonical_factory(self.spec)
        self.kernel = factory(cfg, **node.init_args)
        self.profiler = profiler
        # the chip this instance's calls are pinned to (evaluator
        # affinity); None = jax default placement.  Committed inputs on
        # this device pull the shared jitted functions onto it.
        self.device = device
        self.dev_label = device_label(device)
        self._cur_stream: Tuple[int, int] = (-1, -1)  # (job, slice group)
        self._last_row: Optional[int] = None
        # resets fired so far (reset_state): an op span reads its own
        self.resets = 0
        self._did_setup = False
        # input (shape, dtype) signatures already executed (XLA recompile
        # proxy — dtype included: equal shapes with different dtypes are
        # distinct executables)
        self._shape_sigs: set = set()
        # bucket-ladder warm-up state: idle (not scheduled) | pending
        # (scheduled, not started) | running | done
        self._warm_lock = threading.Lock()
        self._warm_state = "idle"
        self._warm_done = threading.Event()
        # what the warm-up rehearses of a stencilled op's window besides
        # the op (TaskEvaluator._warm_targets): the row counts its input
        # column arrives in, chunk by chunk, and whether that column
        # travels as YUV420 wire
        self.window_chunks: Tuple[int, ...] = ()
        self.yuv_wire = False
        # serializes kernel.execute between the evaluation thread and a
        # warm-up/re-warm thread: two concurrent execute() calls on one
        # kernel instance are not guaranteed safe, and the ensure_warm
        # handshake alone cannot cover a MID-RUN rewarm (the
        # recompile_storm remediation).  Uncontended in steady state.
        self._call_lock = threading.Lock()

    def setup(self, fetch: bool = True) -> None:
        if not self._did_setup:
            if fetch:
                self.kernel.fetch_resources()
            self.kernel.setup_with_resources()
            self._did_setup = True

    def stream_args(self, job_idx: int, slice_group: int) -> dict:
        """The per-stream kwargs new_stream would receive for this
        (job, slice group) — also the trace-affecting part of a fused
        chain's program key (e.g. Resize bakes width/height into the
        jitted body at trace time)."""
        args = {}
        for name, per_stream in self.node.job_args.items():
            if name not in self.spec.stream_arg_names:
                continue
            v = per_stream[job_idx]
            if isinstance(v, SliceList):
                v = v[slice_group]
            args[name] = v
        return args

    def bind_stream(self, job_idx: int, slice_group: int) -> None:
        """Call new_stream when the (job, slice group) changes
        (reference evaluate_worker.cpp:640-707 per-slice arg rebinding)."""
        key = (job_idx, slice_group)
        if key == self._cur_stream:
            return
        self.kernel.new_stream(**self.stream_args(job_idx, slice_group))
        self.kernel.reset()
        self._cur_stream = key
        self._last_row = None

    def reset_state(self) -> None:
        """A reset the engine fires on this stateful kernel inside a
        stream (counted; a device kernel's under `evaluate:reset`, since
        dropping its state frees device memory)."""
        if self.device is not None:
            with self.profiler.span("evaluate:reset", op=self.node.name):
                self.kernel.reset()
        else:
            self.kernel.reset()
        self.resets += 1
        _M_STATE_RESETS.labels(op=self.node.name).inc()

    def maybe_reset(self, row: int) -> None:
        """Reset state at row discontinuities (the reference kernel checks
        element indices itself, test_ops.cpp:183-189; we centralize it)."""
        if self._last_row is not None and row != self._last_row + 1 \
                and self.spec.is_stateful:
            self.reset_state()
        self._last_row = row

    # -- bucket-ladder warm-up (precompile) ----------------------------

    def _example_args(self, b: int, h: int, w: int) -> Optional[List[Any]]:
        """Synthesized execute() args at batch size `b` for warm-up:
        frame columns get (b[, W], h, w, 3) uint8 zeros, non-frame
        columns come from the kernel's optional `precompile_input(name)`
        hook.  None = this op is not generically warmable (variadic, or
        a non-frame input without a hook)."""
        if self.spec.variadic:
            return None
        sten = self.node.effective_stencil()
        win = len(sten) if sten != [0] else 0
        args: List[Any] = []
        for name, is_frame in self.spec.input_columns:
            if is_frame:
                shape = (b, win, h, w, 3) if win else (b, h, w, 3)
                args.append(np.zeros(shape, np.uint8))
            else:
                hook = getattr(self.kernel, "precompile_input", None)
                row = hook(name) if hook is not None else None
                if row is None:
                    return None
                args.append([[row] * win for _ in range(b)] if win
                            else [row] * b)
        return args

    def precompile(self, ladder: Sequence[int], h: int, w: int) -> None:
        """Compile this kernel's jitted function at every ladder bucket.
        A warm-up shape that fails is logged at WARNING with its
        exception and the rest of the ladder is abandoned (the job stays
        alive; the real call then compiles — or raises — itself).  Runs
        on the evaluator's warm-up thread; ensure_warm() on the
        evaluation thread claims or waits."""
        with self._warm_lock:
            if self._warm_state != "pending":
                return  # claimed by a real call racing ahead of us
            self._warm_state = "running"
        t0 = time.time()
        _M_WARMING.inc()
        try:
            for b in ladder:
                args = self._example_args(b, h, w)
                if args is None:
                    return
                if self.device is not None:
                    # warm THIS instance's chip: committed example
                    # inputs compile the ladder executable for the
                    # device the real calls will run on (the persistent
                    # compilation cache dedups the XLA work across
                    # same-kind chips)
                    import jax
                    staged = []
                    for a in args:
                        if isinstance(a, np.ndarray):
                            a = jax.device_put(a, self.device)
                            # ledger: warm-up args hold HBM until this
                            # bucket's compile finishes (released when
                            # the arrays are collected at loop exit)
                            _ms.track_array(a, "warmup",
                                            device=self.dev_label)
                        staged.append(a)
                    args = staged
                try:
                    # compile ledger: the warm-up compile of this
                    # ladder rung is attributed to (op, device, bucket)
                    # — with the persistent cache configured, a warmed
                    # restart records it as a `hit`
                    with self._call_lock, \
                            _cs.observe_compiles(self.node.name,
                                                 self.dev_label, b,
                                                 f"warmup:b{b}"):
                        self.kernel.execute(*args)
                except Exception:  # noqa: BLE001 — the job outlives
                    # a failed warm-up, but never silently
                    _log.warning("precompile of %s at batch %d failed; "
                                 "abandoning its ladder warm-up",
                                 self.node.name, b, exc_info=True)
                    return
            self._warm_window(ladder, h, w)
            _M_OP_PRECOMPILE.labels(op=self.node.name,
                                    device=self.dev_label).set(
                time.time() - t0)
        finally:
            if self.spec.is_stateful:
                # the example rows went through the state: drop it before
                # a task can see it
                with self._call_lock:
                    self.kernel.reset()
            _M_WARMING.dec()
            with self._warm_lock:
                self._warm_state = "done"
            self._warm_done.set()

    def _warm_window(self, ladder: Sequence[int], h: int, w: int) -> None:
        """The window's programs in front of a stencilled op, which the
        ladder's synthesized arguments pass by: for every row count a
        streamed chunk's column can have (the work packet plus the
        window's reach, and fewer at a table's edge, where the window
        repeats the edge instead) the column's wire conversion and the
        gather at every rung.  Which of them a task meets depends on
        where its rows lie in the table, so the first request need not
        show them all."""
        if not self.window_chunks or not _device_staging_enabled():
            return
        import jax
        sten = np.asarray(self.node.effective_stencil(), np.int64)
        shape = (h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2),) \
            if self.yuv_wire else (h, w, 3)
        for n in self.window_chunks:
            try:
                col = ColumnBatch(
                    np.arange(n),
                    jax.device_put(np.zeros((n,) + shape, np.uint8),
                                   self.device),
                    convert=("yuv420", h, w) if self.yuv_wire else None
                ).converted()
                for b in ladder:
                    _gather_window(col.data, np.clip(
                        np.arange(b)[:, None] + sten - sten.min(), 0, n - 1))
            except Exception:  # noqa: BLE001 — as the ladder's rungs
                _log.warning("warm-up of %s's window at %d rows failed",
                             self.node.name, n, exc_info=True)
                return

    def ensure_warm(self) -> None:
        """Called before a real execute(): if this kernel's warm-up is
        mid-flight, wait for it (two concurrent execute() calls on one
        kernel instance are not guaranteed safe); if it has not started
        yet, claim it so the warm-up thread skips this kernel."""
        with self._warm_lock:
            if self._warm_state == "pending":
                self._warm_state = "done"
                self._warm_done.set()
                return
            if self._warm_state != "running":
                return
        self._warm_done.wait()

    def close(self) -> None:
        self.kernel.close()


# shared fused-chain programs, keyed on everything that affects the
# trace: member op identity, init args, stream-bound args, and window
# layout.  Evaluators are constructed per task on the non-pipelined
# path, so a per-instance jax.jit closure would recompile the chain
# every task while staged members amortize through their module-level
# @jax.jit impls — this cache gives chains the same amortization.
# Entries own FROZEN kernel objects built from the node spec (never
# the live evaluator's kernels: those rebind stream args, and a
# later retrace through a mutated kernel would poison the entry).
_CHAIN_PROGRAMS: Dict[Tuple, Any] = {}
_CHAIN_PROGRAMS_LOCK = threading.Lock()


def _build_chain_program(nodes: List[O.OpNode],
                         stream_args: List[dict],
                         windows: List[int]):
    """One jitted callable for a chain: cache-owned kernels constructed
    from the canonical factories, stream-bound once, composed
    head->tail inside a single trace."""
    import jax
    kernels = []
    for node, sargs in zip(nodes, stream_args):
        factory = O.registry.canonical_factory(node.spec)
        cfg = O.KernelConfig(device=node.effective_device(),
                             args=dict(node.init_args), devices=[])
        k = factory(cfg, **node.init_args)
        k.fetch_resources()
        k.setup_with_resources()
        if sargs:
            k.new_stream(**sargs)
        k.reset()
        kernels.append(k)

    chain_id = "+".join(n.name for n in nodes)

    def chain_fn(y):
        return _trace_chain(
            chain_id, [(n.name, k, win)
                       for n, k, win in zip(nodes, kernels, windows)], y)

    return jax.jit(chain_fn)


def _trace_chain(chain_id: str, members, y):
    """The body of a fused chain's program: `members` are (op name,
    kernel, window length) head to tail.  Each member is traced under
    the chain's named scope and its own, so the device trace's ops
    carry the program's op names in their `op_name` metadata (trace
    time only)."""
    import jax
    with jax.named_scope(chain_id):
        for name, k, win in members:
            if win:
                y = y.reshape((y.shape[0] // win, win)
                              + tuple(y.shape[1:]))
            with jax.named_scope(name):
                y = k.execute_traced(y)
    return y


class FusedKernelInstance:
    """One planned fusion chain (graph/fusion.py) compiled as a SINGLE
    jitted program: the member kernels' `execute_traced` bodies compose
    inside one trace, so XLA fuses across op boundaries and member
    intermediates never materialize in HBM (they only exist as values
    inside the fused executable).  The chain dispatches at its TAIL
    node with ONE bucket ladder for the whole chain — per (device,
    shape, dtype) signature the chain mints ONE executable where the
    staged path minted len(chain).

    Mirrors KernelInstance's warm-up/call-lock protocol so the
    evaluator's precompile thread, ensure_warm handshake, and the
    recompile_storm rewarm path treat chains and single kernels
    uniformly.  All attribution (recompile proxy, pad rows, compile
    ledger, op rows/seconds, roofline) keys on the stable chain id
    `"a+b+c"` — member names joined head to tail."""

    def __init__(self, chain: "_fusion.FusionChain",
                 members: List[KernelInstance]):
        self.chain = chain
        self.members = members
        self.chain_id = chain.chain_id
        self.member_names = chain.member_names
        self.head = members[0]
        self.tail = members[-1]
        # all members share this evaluator instance's assigned chip
        # (the planner only fuses same-effective-device TPU runs, and
        # the evaluator pins every TPU kernel to its own chip)
        self.device = self.tail.device
        self.dev_label = self.tail.dev_label
        # per member, head->tail: window length (0 = no window axis,
        # matching _example_args' convention for stencil == [0])
        self.windows = chain.windows()
        self.stencils = [np.asarray(s, np.int64) for s in chain.stencils()]
        self.width = chain.width()
        self._jit = None
        # current stream-bound args per member (set by bind_stream);
        # part of the shared-program key — a stream rebind that changes
        # them must map to a different compiled program
        self._stream_args: Optional[List[dict]] = None
        self._shape_sigs: set = set()
        # (shape, dtype) -> (chain CostDescriptor | None, bytes of
        # member intermediates the fusion avoided materializing)
        self._cost_cache: Dict[Tuple, Tuple] = {}
        self._warm_lock = threading.Lock()
        self._warm_state = "idle"
        self._warm_done = threading.Event()
        self._call_lock = threading.Lock()

    # -- the fused program ---------------------------------------------

    def _chain_fn(self, y):
        """The whole chain as one traceable function: (k * width, ...)
        composed-window gather of the head's input in, the tail's raw
        traced result out.  Each windowed member folds its own window
        axis out of the composed leading dimension — the composed
        gather (compose_positions) laid positions out with the HEAD's
        window innermost, so the progressive reshape walks the nesting
        exactly."""
        return _trace_chain(
            self.chain_id, [(ki.node.name, ki.kernel, win)
                            for ki, win in zip(self.members, self.windows)],
            y)

    def _fn(self):
        if self._jit is not None:
            return self._jit
        if self._stream_args is not None:
            key = tuple(
                (ki.spec.name,
                 f"{type(ki.kernel).__module__}."
                 f"{type(ki.kernel).__qualname__}",
                 repr(sorted(ki.node.init_args.items())),
                 repr(sorted(sargs.items())), win)
                for ki, sargs, win in zip(self.members,
                                          self._stream_args,
                                          self.windows))
            with _CHAIN_PROGRAMS_LOCK:
                fn = _CHAIN_PROGRAMS.get(key)
            if fn is None:
                try:
                    fn = _build_chain_program(
                        [ki.node for ki in self.members],
                        self._stream_args, self.windows)
                except Exception:  # noqa: BLE001 — fall back per instance
                    _log.warning("shared program build failed for chain "
                                 "%s; falling back to a per-instance jit",
                                 self.chain_id, exc_info=True)
                    fn = None
                if fn is not None:
                    with _CHAIN_PROGRAMS_LOCK:
                        fn = _CHAIN_PROGRAMS.setdefault(key, fn)
            if fn is not None:
                self._jit = fn
                return fn
        import jax
        self._jit = jax.jit(self._chain_fn)
        return self._jit

    def execute(self, arr):
        """One fused call: jitted chain body, then the tail's host-side
        finish() outside the trace (the staged path's post-jit tail)."""
        return self.tail.kernel.finish(self._fn()(arr))

    def bind_stream(self, job_idx: int, slice_group: int) -> None:
        sargs = []
        for ki in self.members:
            ki.bind_stream(job_idx, slice_group)
            sargs.append(ki.stream_args(job_idx, slice_group))
        if sargs != self._stream_args:
            self._stream_args = sargs
            self._jit = None

    def compose_positions(self, rows: np.ndarray, max_in: int) -> np.ndarray:
        """Head-input read positions for tail compute rows `rows`: the
        member stencils composed tail-first, REPEAT_EDGE-clamped at
        EVERY level — exactly the staged pipeline's transitive backward
        dilation (graph/analysis.py derive_task_streams), so the fused
        gather reads precisely the rows the staged members would have.
        Returns a flat (len(rows) * width,) position array."""
        pos = np.asarray(rows, np.int64)
        for sten, win in zip(reversed(self.stencils),
                             reversed(self.windows)):
            if win:
                pos = np.clip(pos[:, None] + sten[None, :], 0,
                              max_in - 1).reshape(-1)
        return pos

    # -- chain cost model ----------------------------------------------

    def cost_for(self, shape, dtype):
        """Analytical chain descriptor for a head-input signature:
        member costs summed via stepwise shape inference
        (jax.eval_shape walks the chain without running it), with
        bytes_in/bytes_out taken at the chain BOUNDARY — the fused
        program touches HBM only there.  Also returns the member
        intermediate bytes fusion avoided (every non-tail output +
        every non-head input stays on-chip).  Cached per signature;
        (None, 0.0) when any member lacks a cost model."""
        key = (tuple(shape), str(dtype))
        hit = self._cost_cache.get(key)
        if hit is not None:
            return hit
        desc, saved = None, 0.0
        try:
            import jax
            aval = jax.ShapeDtypeStruct(tuple(shape), dtype)
            descs = []
            last = len(self.members) - 1
            for i, (ki, win) in enumerate(zip(self.members, self.windows)):
                shp = tuple(aval.shape)
                if win:
                    shp = (shp[0] // win, win) + shp[1:]
                    aval = jax.ShapeDtypeStruct(shp, aval.dtype)
                d = ki.kernel.cost([shp])
                if isinstance(d, dict):
                    d = _cs.CostDescriptor(**d)
                descs.append(d)
                if i < last:
                    aval = jax.eval_shape(ki.kernel.execute_traced, aval)
            if descs and all(d is not None for d in descs):
                desc = _cs.CostDescriptor(
                    flops=sum(float(d.flops or 0.0) for d in descs),
                    bytes_in=descs[0].bytes_in,
                    bytes_out=descs[-1].bytes_out,
                    source="hook")
                saved = (sum(float(d.bytes_out or 0.0)
                             for d in descs[:-1])
                         + sum(float(d.bytes_in or 0.0)
                               for d in descs[1:]))
        except Exception:  # noqa: BLE001 — cost attribution is optional
            _log.debug("chain cost model failed for %s", self.chain_id,
                       exc_info=True)
        self._cost_cache[key] = (desc, saved)
        return desc, saved

    # -- chain batch cap / warm-up -------------------------------------

    def cap_for(self, wp: Optional[int]) -> int:
        """Per-call batch cap for the CHAIN: walking tail->head, member
        i runs at (tail rows x its downstream window expansion) rows
        per call, so its own cap (the same work-packet derivation as
        _run_kernel) divides down by that expansion.  The chain takes
        the tightest bound — no member ever sees a call larger than it
        would have accepted staged."""
        cap = None
        exp = 1
        for ki, win in zip(reversed(self.members),
                           reversed(self.windows)):
            n = ki.node
            if n.batch is None and wp:
                mcap = max(1, min(n.effective_batch(), int(wp)))
            else:
                mcap = max(1, n.effective_batch())
            c = max(1, mcap // max(1, exp))
            cap = c if cap is None else min(cap, c)
            exp *= max(win, 1)
        return max(1, cap if cap is not None else 1)

    def warmable(self) -> bool:
        """Generic warm-up synthesizes head frames at source geometry:
        needs a frame head input reachable from Input through builtins
        only (same eligibility as single-kernel warm-up)."""
        n = self.head.node
        return bool(n.spec.input_columns
                    and n.spec.input_columns[0][1]
                    and _source_geometry_inputs(n))

    def precompile(self, ladder: Sequence[int], h: int, w: int) -> None:
        """Compile the fused program at every chain-ladder bucket (one
        ladder for the WHOLE chain — this is the warm-up the staged
        path would have run once per member)."""
        with self._warm_lock:
            if self._warm_state != "pending":
                return
            self._warm_state = "running"
        t0 = time.time()
        _M_WARMING.inc()
        try:
            for b in ladder:
                arr = np.zeros((b * self.width, h, w, 3), np.uint8)
                if self.device is not None:
                    import jax
                    arr = jax.device_put(arr, self.device)
                    _ms.track_array(arr, "warmup", device=self.dev_label)
                try:
                    with self._call_lock, \
                            _cs.observe_compiles(self.chain_id,
                                                 self.dev_label, b,
                                                 f"warmup:b{b}",
                                                 members=self.member_names):
                        self.execute(arr)
                except Exception:  # noqa: BLE001 — the job outlives
                    # a failed warm-up, but never silently
                    _log.warning("precompile of chain %s at batch %d "
                                 "failed; abandoning its ladder warm-up",
                                 self.chain_id, b, exc_info=True)
                    return
            _M_OP_PRECOMPILE.labels(op=self.chain_id,
                                    device=self.dev_label).set(
                time.time() - t0)
        finally:
            _M_WARMING.dec()
            with self._warm_lock:
                self._warm_state = "done"
            self._warm_done.set()

    def ensure_warm(self) -> None:
        """Same handshake as KernelInstance.ensure_warm."""
        with self._warm_lock:
            if self._warm_state == "pending":
                self._warm_state = "done"
                self._warm_done.set()
                return
            if self._warm_state != "running":
                return
        self._warm_done.wait()


# every live TaskEvaluator, weakly held: the recompile_storm
# remediation playbook (engine/controller.py) re-warms bucket ladders
# process-wide through rewarm_all() without owning evaluator lifetimes
_LIVE_EVALUATORS: "weakref.WeakSet" = weakref.WeakSet()
# evaluator threads add and discard while another thread lists
_LIVE_LOCK = threading.Lock()


def live_evaluators() -> List["TaskEvaluator"]:
    """The evaluators alive right now (between construction and
    close()): what a run is executing on, for the remediation action
    below and for checks that look at a run's own kernel instances."""
    with _LIVE_LOCK:
        return list(_LIVE_EVALUATORS)


def rewarm_all() -> int:
    """Re-schedule the bucket-ladder warm-up on every live evaluator
    (the recompile_storm -> ladder_rewarm remediation action).
    Returns the total number of kernels scheduled; best-effort — an
    evaluator failing to re-warm never raises out of the actuator."""
    total = 0
    for te in live_evaluators():
        try:
            total += te.rewarm()
        except Exception:  # noqa: BLE001 — remediation is best-effort
            _log.exception("ladder re-warm failed for an evaluator")
    return total


def _value_key(v: Any) -> Any:
    """`v` as a hashable that is equal where the values are: what an op
    was constructed from, for graph_key.  A string that names a file or
    a directory carries its size and mtime_ns, so weights written again
    between two runs are read again.  TypeError: not keyable by value."""
    if isinstance(v, str):
        try:
            st = os.stat(v)
        except (OSError, ValueError):
            return v
        return (v, st.st_size, st.st_mtime_ns)
    if v is None or isinstance(v, (bool, int, float, bytes)):
        return (type(v).__name__, v)
    if isinstance(v, enum.Enum):
        return (type(v).__qualname__, v.name)
    if isinstance(v, (list, tuple)):
        return (type(v).__name__,) + tuple(_value_key(x) for x in v)
    if isinstance(v, dict):
        return ("dict",) + tuple(sorted(
            (str(k), _value_key(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray) and v.dtype != object:
        return ("ndarray", v.shape, str(v.dtype), v.tobytes())
    raise TypeError(f"{type(v).__name__} is not keyed by value")


def graph_key(info: A.GraphInfo) -> Optional[Tuple]:
    """What of a graph its evaluators are made from, and nothing of the
    request: per op in topological order its name, canonical kernel
    class, init args by value, effective device, batch, stencil, what
    decides fusion, and its input edges by position and column.  Node
    ids, tables, samplers and their arguments, per-stream args and
    output names are not in it.  None: the evaluators of this graph are
    not to be kept — an init arg that cannot be keyed by value, or an
    unbounded-state op (its kernels' state is positioned by the run's
    task order; see run_pipeline).  A bounded-state kernel is kept: the
    next run's first task binds its stream anew, which resets it."""
    pos = {n.id: i for i, n in enumerate(info.ops)}
    key = []
    try:
        for n in info.ops:
            if n.spec is not None and n.spec.unbounded_state:
                return None
            key.append((
                n.name,
                None if n.spec is None
                else O.registry.canonical_factory(n.spec),
                _value_key(n.init_args), n.effective_device().name,
                n.effective_batch(), tuple(n.effective_stencil()),
                n.warmup, n.fuse,
                tuple(c.is_frame for c in n.outputs),
                tuple((pos[c.op.id], c.column)
                      for c in n.input_columns())))
    except TypeError:
        return None
    return tuple(key)


class EvaluatorPool:
    """The evaluators of the graph that ran last, kept by whoever owns
    their lifetime (a Client; a cluster Worker for the length of a
    bulk) and handed to the next run with the same key: that run pays
    no kernel construction, no weight restore, no retrace of a
    per-kernel jit and no ladder warm-up.  `key(info)` says which runs
    may share evaluators; None never keeps.

    One key at a time: a take under another key closes what was kept
    before the caller builds its own, so two graphs' weights never sit
    in device memory together.  An evaluator is checked out while a run
    holds it: a second run in flight with the same key finds nothing,
    builds its own, and whichever is given back second is closed."""

    def __init__(self, key=graph_key):
        self._key_of = key
        self._lock = threading.Lock()
        self._key: Any = None
        self._kept: Dict[int, "TaskEvaluator"] = {}

    def take(self, info: A.GraphInfo, profiler: Profiler, idx: int,
             instances: int, precompile: Optional[Tuple[int, int, int]]
             ) -> Tuple[Any, Optional["TaskEvaluator"]]:
        """(key, pipeline instance `idx`'s kept evaluator adopted to
        `info`, or None).  The key goes on the evaluator the caller
        makes on a miss (`pool_key`), for give()."""
        key = self._key_of(info)
        if key is not None:
            # an instance's chip and dp-shard set follow from these
            key = (key, instances, precompile, _fusion.enabled(),
                   tuple(device_label(assigned_device(i))
                         for i in range(instances)))
        with self._lock:
            if key is None or key != self._key:
                self._close_kept()
                self._key = key
            te = self._kept.pop(idx, None)
        if te is not None:
            te.adopt(info, profiler)
        return key, te

    def give(self, te: "TaskEvaluator") -> None:
        """The end of a run's hold on `te`: kept for the next run of
        its key, or closed."""
        te.release()
        with self._lock:
            keep = te.pool_key is not None and te.pool_key == self._key \
                and te.instance not in self._kept
            if keep:
                self._kept[te.instance] = te
        if not keep:
            te.close()

    def _close_kept(self) -> None:
        kept, self._kept = self._kept, {}
        for te in kept.values():
            te.close()

    def close(self) -> None:
        """Close what is kept; what a run still holds is closed when it
        is given back."""
        with self._lock:
            self._close_kept()
            self._key = None


class TaskEvaluator:
    def __init__(self, info: A.GraphInfo, profiler: Profiler,
                 devices: Optional[List[Any]] = None,
                 skip_fetch_resources: bool = False,
                 precompile: Optional[Tuple[int, int, int]] = None,
                 instance: int = 0, instances: int = 1,
                 yuv_wire: bool = False):
        self.info = info
        self.profiler = profiler
        # device affinity: this pipeline instance owns ONE chip (instance
        # i of P -> chip i mod n); all its stdlib device-kernel calls
        # stage and run there.  `devices` (the dp-shard set for model
        # kernels — models/infer.py DataParallelApply) defaults to this
        # instance's partition of the host's chips: the whole host for a
        # single instance (the reference's one-GPU-per-instance pinning,
        # adapted), a disjoint slice each when instances run per chip.
        # SCANNER_TPU_KERNEL_DEVICES=all extends both to the CPU backend
        # so dryruns/tests exercise them on a virtual multi-device host.
        self.instance = instance
        self.device = assigned_device(instance)
        # what an EvaluatorPool keeps this evaluator under; None: not kept
        self.pool_key: Any = None
        if devices is None:
            devices = instance_devices(instance, instances)
        # the batched device calls in flight, of every op and chain of
        # this instance: its chip runs them in order
        self.calls = CallWindow(profiler)
        self.kernels: Dict[int, KernelInstance] = {}
        # kernel construction and set-up load weights and may compile
        # (a model's init): the pipeline waits on this, not on the chip
        with _warming():
            for n in info.ops:
                if n.is_builtin:
                    continue
                # only device-placed kernels get the chip list: a kernel
                # explicitly pinned to CPU must not dp-shard onto TPU
                on_chip = n.effective_device() == DeviceType.TPU
                self.kernels[n.id] = KernelInstance(
                    n, profiler, devices if on_chip else None,
                    device=self.device if on_chip else None)
            for ki in self.kernels.values():
                ki.setup(fetch=not skip_fetch_resources)
        if _cs.enabled() and any(
                ki.node.effective_device() == DeviceType.TPU
                for ki in self.kernels.values()):
            # a device program this evaluator's tasks dispatch outside
            # an observed call (a conversion, a gather, a cache slice)
            # may compile: the listeners stand before the first of them
            _cs.install()
        # whole-pipeline fusion (graph/fusion.py): maximal runs of
        # fusable consecutive device ops execute as ONE jitted program.
        # Non-tail members never dispatch (or materialize an output
        # column) on their own — the tail node runs the whole chain.
        self.chains: Dict[int, "_fusion.FusionChain"] = {}
        self.fused: Dict[int, FusedKernelInstance] = {}
        self._chain_member_ids: set = set()
        if _fusion.enabled():
            for ch in _fusion.plan_chains_once(info, graph_key(info)):
                self.chains[ch.tail.id] = ch
                self.fused[ch.tail.id] = FusedKernelInstance(
                    ch, [self.kernels[m.id] for m in ch.members])
                for m in ch.members[:-1]:
                    self._chain_member_ids.add(m.id)
        # bucket-ladder warm-up: compile every device op's ladder shapes
        # on a background thread so the compiles overlap the first
        # task's decode instead of stalling its evaluation.  `precompile`
        # is a (frame_h, frame_w, work_packet_size) hint from the
        # executor (engine geometry is not knowable from the graph
        # alone); evaluation threads join per-kernel via ensure_warm().
        self._precompile_thread: Optional[threading.Thread] = None
        self._precompile_hint = precompile
        # the executor's word that source frame columns reach this
        # graph's device ops as YUV420 wire (the warm-up converts as the
        # tasks will)
        self._yuv_wire = yuv_wire
        if precompile is not None and _precompile_enabled() \
                and _bucketing_enabled():
            targets = self._warm_targets(precompile)
            for ki, _ladder in targets:
                # bind job-0 stream args HERE, before the warm-up thread
                # exists (binding on that thread would race the first
                # task's own bind): kernels like Resize get their
                # geometry from new_stream, and an unbound warm-up
                # divides by a 0x0 output size.  The real dispatch
                # rebinds only if its (job, slice group) differs, so
                # the warmed executable survives into the first call.
                ki.bind_stream(0, 0)
                ki._warm_state = "pending"
            self._spawn_warm(targets, precompile)
        # live-evaluator registry: the recompile_storm remediation
        # (engine/controller.py -> rewarm_all) re-schedules ladder
        # warm-ups on whatever evaluators currently exist; weak so a
        # closed/forgotten evaluator never pins its kernels alive
        with _LIVE_LOCK:
            _LIVE_EVALUATORS.add(self)

    def _warm_targets(self, precompile: Tuple[int, int, int],
                      rewarm: bool = False
                      ) -> List[Tuple[Any, List[int]]]:
        """The warm-up-eligible kernels and their ladders (shared by
        the constructor warm-up and rewarm).  A bounded-state kernel
        whose tasks stand alone is called at exact lengths, so its
        "ladder" is the lengths its tasks produce; a `rewarm` leaves it
        out: mid-run its example rows would go through a task's state."""
        _h, _w, wp = precompile
        targets: List[Tuple[Any, List[int]]] = []
        for ki in self.kernels.values():
            n = ki.node
            if n.id in self._chain_member_ids or n.id in self.chains:
                continue  # fused members warm as one chain, below
            if n.effective_device() != DeviceType.TPU \
                    or n.effective_batch() <= 1 \
                    or not n.stands_alone() or ki.spec.variadic \
                    or not _source_geometry_inputs(n):
                continue
            # same per-call cap derivation as _run_kernel
            if n.batch is None and wp:
                cap = max(1, min(n.effective_batch(), int(wp)))
            else:
                cap = max(1, n.effective_batch())
            if ki.spec.is_stateful:
                if not rewarm:
                    targets.append((ki, _state_call_lengths(
                        cap, n.bounded_warmup(), int(wp or cap))))
                continue
            sten = n.effective_stencil()
            if sten != [0] and wp:
                reach = max(sten) - min(sten)
                ki.window_chunks = tuple(int(wp) + reach - k
                                         for k in range(reach + 1))
                ki.yuv_wire = self._yuv_wire
            targets.append((ki, bucket_ladder(cap)))
        # fused chains warm their ONE chain ladder (precompile is
        # polymorphic over KernelInstance / FusedKernelInstance)
        for fki in self.fused.values():
            if fki.warmable():
                targets.append((fki, bucket_ladder(fki.cap_for(wp))))
        return targets

    def _spawn_warm(self, targets, precompile) -> None:
        if not targets:
            return
        h, w, _wp = precompile

        def warm() -> None:
            for ki, ladder in targets:
                ki.precompile(ladder, h, w)

        self._precompile_thread = threading.Thread(
            target=warm, name="precompile", daemon=True)
        self._precompile_thread.start()

    def rewarm(self) -> int:
        """Re-schedule the bucket-ladder warm-up (the recompile_storm
        remediation): kernels whose warm-up is idle or done go back to
        pending and a fresh warm-up thread re-executes their ladders —
        with the persistent compilation cache configured this re-pins
        executables at cache-hit cost.  Mid-flight warm-ups and claims
        by racing real calls are respected (the same
        ensure_warm/_call_lock handshake as construction).  Returns
        the number of kernels scheduled."""
        hint = self._precompile_hint
        if hint is None or not _precompile_enabled() \
                or not _bucketing_enabled():
            return 0
        claimed: List[Tuple[Any, List[int]]] = []
        for ki, ladder in self._warm_targets(hint, rewarm=True):
            with ki._warm_lock:
                if ki._warm_state in ("idle", "done"):
                    ki._warm_state = "pending"
                    ki._warm_done.clear()
                    claimed.append((ki, ladder))
        self._spawn_warm(claimed, hint)
        return len(claimed)

    def release(self) -> None:
        """The end of a driver's hold on this evaluator (its task
        stream closed): the calls still in flight are waited for, so no
        result outlives its run unwaited.  Their tasks have left: a
        failure here is logged and fails its task at the fetch."""
        self.calls.owner = None
        self.calls.drain()

    def close(self) -> None:
        self.release()
        with _LIVE_LOCK:
            _LIVE_EVALUATORS.discard(self)
        for ki in self.kernels.values():
            ki.close()

    def adopt(self, info: A.GraphInfo, profiler: Profiler) -> None:
        """Take the next run of the graph this evaluator was made for
        (EvaluatorPool: equal keys, so equal ops at equal positions).
        Node ids come from a global counter, so everything keyed by
        them moves to `info`'s by position; streams are unbound so that
        new_stream and reset() fire on the run's first task as on a
        fresh instance.  Kept: the kernel objects, their parameters on
        their chip, their jitted callables, `_shape_sigs` and the
        warm-up's outcome.  The same `info` again (a Worker's bulk,
        entered again) keeps its streams and state where they are."""
        self.profiler = self.calls.profiler = profiler
        for ki in self.kernels.values():
            ki.profiler = profiler
        if info is self.info:
            return
        # a ladder still warming executes the kernel: let it finish (or
        # never start) before this run's new_stream
        for ki in [*self.kernels.values(), *self.fused.values()]:
            ki.ensure_warm()
        node = {old.id: new for old, new in zip(self.info.ops, info.ops)}
        kernels = {}
        for ki in self.kernels.values():
            ki.node = node[ki.node.id]
            ki._cur_stream = (-1, -1)
            ki._last_row = None
            kernels[ki.node.id] = ki
        chains, fused = {}, {}
        for fki in self.fused.values():
            fki.chain = _fusion.FusionChain(
                [node[m.id] for m in fki.chain.members])
            chains[fki.chain.tail.id] = fki.chain
            fused[fki.chain.tail.id] = fki
        self._chain_member_ids = {node[i].id
                                  for i in self._chain_member_ids}
        self.kernels, self.chains, self.fused = kernels, chains, fused
        self.info = info

    # ------------------------------------------------------------------

    def execute_task(self, jr: A.JobRows, plan: A.TaskPlan,
                     source_batches: Dict[int, ColumnBatch]
                     ) -> Dict[int, ColumnBatch]:
        """Run one task.  source_batches: Input node id -> ColumnBatch.
        Returns sink node id -> ColumnBatch of output rows."""
        # whose the calls dispatched from here on are (a streaming
        # task's chunks are one task)
        self.calls.owner = (plan.job_idx, plan.task_idx)
        try:
            return self._execute_ops(jr, plan, source_batches)
        except BaseException:
            self.calls.abandon()
            raise

    def _execute_ops(self, jr: A.JobRows, plan: A.TaskPlan,
                     source_batches: Dict[int, ColumnBatch]
                     ) -> Dict[int, ColumnBatch]:
        store: Dict[ColKey, ColumnBatch] = {}
        results: Dict[int, ColumnBatch] = {}
        # remaining column-reads per producer: a column is dropped from the
        # store the moment its last consumer has run, so peak host/device
        # memory is the live frontier, not every intermediate of the task
        # (the reference streams work packets through stages instead,
        # worker.cpp stage drivers; with batched columns, freeing eagerly
        # achieves the same bound per io-packet)
        remaining = {nid: len(lst)
                     for nid, lst in self.info.consumers.items()}
        self.last_peak_columns = 0

        for n in self.info.ops:
            if n.id in self._chain_member_ids:
                # fused into a chain: the tail node dispatches the whole
                # chain, this member never materializes an output column
                continue
            ts = plan.streams[n.id]
            if n.name == O.INPUT_OP:
                store[(n.id, "output")] = source_batches[n.id]
            elif n.name in (O.SAMPLE_OP, O.SPACE_OP, O.SLICE_OP,
                            O.UNSLICE_OP, O.OUTPUT_OP):
                # the builtins that move rows of a stored column
                with self._op_span(n.name, len(ts.valid_output_rows),
                                   "host"):
                    if n.name == O.OUTPUT_OP:
                        src = n.input_columns()[0]
                        results[n.id] = store[
                            (src.op.id, src.column)].take_rows(
                                ts.valid_output_rows)
                    elif n.name == O.SLICE_OP:
                        store[(n.id, "output")] = self._run_slice(
                            n, jr, plan, store)
                    elif n.name == O.UNSLICE_OP:
                        store[(n.id, "output")] = self._run_unslice(
                            n, jr, plan, store)
                    else:
                        store[(n.id, "output")] = self._run_sampler(
                            n, jr, plan, store)
            else:
                run = self._run_fused if n.id in self.chains \
                    else self._run_kernel
                outs = run(n, jr, plan, store)
                self._prefetch_for_host_ops(n, outs)
                for col, b in outs.items():
                    store[(n.id, col)] = b
            self.last_peak_columns = max(self.last_peak_columns, len(store))
            if n.id in self.chains:
                # the whole chain's input edges are consumed here: the
                # head's (and every member's) reads happen at tail time,
                # and member columns themselves were never stored
                cons_cols = [c for m in self.chains[n.id].members
                             for c in m.input_columns()]
            else:
                cons_cols = n.input_columns()
            for c in cons_cols:
                pid = c.op.id
                remaining[pid] -= 1
                if remaining[pid] == 0:
                    for key in [k for k in store if k[0] == pid]:
                        del store[key]
        return results

    def _efficiency_event(self, op: str, device: str, **attrs) -> None:
        """ONE op.efficiency event on the op's trace span (per-call
        detail goes to the gauges): the roofline verdict of the op's
        calls whose seconds the window read since its last span, which
        with waits taken late are the calls of two calls back."""
        run = self.calls.take_run(op)
        if run is None:
            return
        secs, flops, nbytes = run
        cls = _cs.classify(device, flops or None, nbytes, secs)
        if cls is not None:
            _tracing.add_event("op.efficiency", op=op, device=device,
                               eff=round(cls["eff"], 6),
                               bound=cls["bound"], **attrs)

    def _op_span(self, op: str, rows: int, device: str):
        """The `evaluate:<op>` span of one op over one task or chunk,
        with its seconds in scanner_tpu_op_seconds_total{op, device}."""
        return self.profiler.span(
            "evaluate:" + op, rows=rows, device=device,
            counter=_M_OP_SECONDS.labels(op=op, device=device))

    # -- builtins (vectorized gathers on the batch) ---------------------

    def _input_batch(self, n: O.OpNode, store) -> ColumnBatch:
        src = n.input_columns()[0]
        return store[(src.op.id, src.column)]

    def _run_sampler(self, n, jr, plan, store) -> ColumnBatch:
        ts = plan.streams[n.id]
        g = plan.slice_group if self.info.slice_level[n.id] > 0 else 0
        sampler = jr.samplers[n.id][g]
        in_b = self._input_batch(n, store)
        up_rows = ts.valid_input_rows
        down_rows, mapping = sampler.downstream_map(up_rows)
        need = np.asarray(ts.valid_output_rows, np.int64)
        pos_in_down = {int(d): i for i, d in enumerate(down_rows.tolist())}
        try:
            sel = np.array([pos_in_down[int(d)] for d in need.tolist()],
                           np.int64)
        except KeyError:
            missing = sorted(set(need.tolist()) - pos_in_down.keys())
            raise JobException(
                f"{n.name}: missing output rows {missing[:5]}...")
        m_sel = np.asarray(mapping, np.int64)[sel] if len(sel) else sel
        if not len(up_rows) or (m_sel < 0).all():
            return ColumnBatch.from_elements(
                need, [NullElement()] * len(need))
        src_rows = up_rows[np.maximum(m_sel, 0)]
        positions = in_b.positions(np.asarray(src_rows, np.int64))
        positions = np.where(m_sel < 0, -1, positions)
        return in_b.take(positions, need)

    def _run_slice(self, n, jr, plan, store) -> ColumnBatch:
        ts = plan.streams[n.id]
        group = jr.partitioners[n.id].group_at(plan.slice_group)
        in_b = self._input_batch(n, store)
        need = np.asarray(ts.valid_output_rows, np.int64)
        src = np.asarray(group, np.int64)[need]
        return in_b.take(in_b.positions(src), need)

    def _run_unslice(self, n, jr, plan, store) -> ColumnBatch:
        ts = plan.streams[n.id]
        inp = n.input_columns()[0].op
        offset = int(np.concatenate(
            [[0], np.cumsum(jr.rows[inp.id])])[plan.slice_group])
        in_b = self._input_batch(n, store)
        need = np.asarray(ts.valid_output_rows, np.int64)
        return in_b.take(in_b.positions(need - offset), need)

    def _hand_off(self, op: str, b: ColumnBatch) -> ColumnBatch:
        """`b` where host op `op` reads it: a device column brought to
        the host, host data as it is."""
        layout = b.sink_layout
        if layout is None:
            return b
        with self.profiler.span(
                "evaluate:handoff", op=op, rows=len(b), layout=layout,
                counter=_M_HANDOFF_SECONDS.labels(op=op)):
            b = b.to_host()
        _M_HANDOFF_BYTES.labels(op=op).inc(b.data.nbytes)
        _M_HANDOFF_ROWS.labels(op=op, layout=layout).inc(len(b))
        return b

    def _prefetch_for_host_ops(self, n: O.OpNode,
                               outs: Dict[str, ColumnBatch]) -> None:
        """Start the copy to the host of what device op (or chain tail)
        `n` just made, where only host ops read it: the sink's own
        mechanism (`ColumnBatch.prefetch_host`: row-major on the chip
        first, then an asynchronous copy), at the producer's dispatch
        and not at the consumer's first look."""
        readers = [self.info.op_at(c) for c in self.info.consumers[n.id]]
        if readers and all(
                not r.is_builtin
                and r.effective_device() != DeviceType.TPU
                for r in readers):
            for b in outs.values():
                b.prefetch_host()

    # -- regular kernels -----------------------------------------------

    def _run_kernel(self, n: O.OpNode, jr: A.JobRows, plan: A.TaskPlan,
                    store) -> Dict[str, ColumnBatch]:
        ts = plan.streams[n.id]
        ki = self.kernels[n.id]
        ki.bind_stream(plan.job_idx, plan.slice_group)

        in_cols = n.input_columns()
        in_batches = [store[(c.op.id, c.column)] for c in in_cols]
        g = plan.slice_group if self.info.slice_level[n.id] > 0 else 0
        in_op = in_cols[0].op
        max_in = jr.rows[in_op.id][g]
        stencil = n.effective_stencil()
        has_stencil = stencil != [0]
        # The batch DECLARATION fixes the calling convention (batched
        # kernels always receive row batches, even 1-row ones) and CAPS
        # the per-call batch (ops declare it as a memory bound); within
        # that cap, PerfParams.work_packet_size sets the chunk — the XLA
        # batch dimension (reference io/work packet split, master.cpp:1421)
        # — unless the op was constructed with an explicit batch= override.
        batched_call = n.effective_batch() > 1
        if batched_call and n.batch is None:
            batch = max(1, min(n.effective_batch(),
                               int(getattr(jr, "work_packet_size",
                                           n.effective_batch()))))
        else:
            batch = max(1, n.effective_batch())

        # Shape-stable dispatch: device-placed batched kernels wrap
        # jitted functions that compile one executable per (shape,
        # dtype), on ANY backend — so their calls are rounded up to a
        # small bucket ladder (pad by edge-repeating the last row, slice
        # the padding off after).  Host/python kernels keep exact shapes
        # (retracing is free), and so do stateful kernels: padding rows
        # would advance their state past the real stream position.
        use_buckets = (batched_call and not n.spec.is_stateful
                       and n.effective_device() == DeviceType.TPU
                       and _bucketing_enabled())
        ladder = bucket_ladder(batch) if use_buckets else None

        # Device staging: a device kernel gets its inputs moved host->device
        # ONCE per task column (async, whole batch); a host kernel gets
        # device inputs fetched once.  Updated in the store so sibling
        # consumers of the same column reuse the placement.  The target is
        # THIS instance's assigned chip: committed inputs pull the shared
        # jitted kernel functions onto it, and a batch the loader
        # pre-staged for this instance is already there (to_device no-ops
        # instead of silently copying cross-chip).
        is_device_kernel = (n.effective_device() == DeviceType.TPU
                            and _device_staging_enabled())
        compute = np.asarray(ts.compute_rows, np.int64)
        sten = np.asarray(stencil, np.int64)
        # a stencilled op's window is one span: the columns brought to
        # where the op runs, and the window's rows looked up in them
        # (evaluate:window); the same of an op without one is
        # evaluate:inputs
        window = self.profiler.span(
            "evaluate:window", op=n.name, rows=len(compute),
            counter=_M_WINDOW_SECONDS.labels(op=n.name)) \
            if has_stencil else self.profiler.span(
                "evaluate:inputs", op=n.name, rows=len(compute),
                counter=_M_OP_INPUT_SECONDS.labels(op=n.name))
        with window:
            for i, (c, b) in enumerate(zip(in_cols, in_batches)):
                if is_device_kernel and isinstance(b.data, np.ndarray) \
                        and b.data.dtype != object:
                    b = b.to_device(ki.device)
                elif not is_device_kernel:
                    b = self._hand_off(n.name, b)
                # resolve a pending wire-format conversion (YUV420 staged
                # at 1.5 B/px) exactly once, where the data now lives: for
                # device kernels a jitted program of its own ahead of the
                # op's (kernels/color.py), on host the bit-identical numpy
                # flavor
                if b.convert is not None:
                    b = b.converted()
                in_batches[i] = b
                store[(c.op.id, c.column)] = b
            # window positions per compute row per input column
            # (REPEAT_EDGE)
            win_rows = np.clip(compute[:, None] + sten[None, :], 0,
                               max_in - 1)
            col_pos = [b.positions(win_rows.reshape(-1)).reshape(
                win_rows.shape) for b in in_batches]
        if has_stencil:
            halo = len(np.setdiff1d(win_rows, compute))
            if halo:
                for c in in_cols:
                    _M_HALO_ROWS.labels(op=c.op.name).inc(halo)

        out_cols = [c for c, _ in n.spec.output_columns]
        valid_out = np.asarray(ts.valid_output_rows, np.int64)
        valid_set = set(valid_out.tolist())

        # A carry plan (unbounded-state node whose recompute starts past
        # row 0) is only sound if THIS kernel instance's state sits
        # exactly at the preceding row of the same stream; anything else
        # (reordered tasks, a failed predecessor, another instance) and
        # maybe_reset would silently reset mid-stream — wrong results.
        # Fail to the self-contained fallback instead.
        # (bind_stream above already rebound+reset on any stream change,
        # nulling _last_row — so the position check alone covers foreign
        # streams, reordering, and failed predecessors)
        if n.spec.unbounded_state and len(compute) and int(compute[0]) > 0:
            if ki._last_row != int(compute[0]) - 1:
                raise StateCarryMiss(
                    f"{n.name}: carry plan expects state at row "
                    f"{int(compute[0]) - 1} of stream "
                    f"({plan.job_idx}, {plan.slice_group}); instance is "
                    f"at {ki._last_row}")

        # null propagation: a row whose inputs (or stencil window) contain a
        # null yields null without running the kernel
        null_in = np.zeros(len(compute), bool)
        for b, pos in zip(in_batches, col_pos):
            if b.nulls is not None:
                null_in |= b.nulls[pos].any(axis=1)

        # Under bucketed dispatch a sparse null must not shrink the call
        # shape (every distinct "live subset" size would mint an
        # executable): run the FULL chunk and overwrite dead rows with
        # NullElement afterward.  Safe only when every nulled input is
        # array data (null positions hold valid zero rows); an object
        # column holds NullElement objects the kernel would choke on, so
        # those rare chunks call on the live subset — still padded up to
        # a bucket below, so shapes stay ladder-bounded either way.
        mask_nulls = use_buckets and all(
            b.nulls is None or is_array_data(b.data) for b in in_batches)

        # contiguous runs of compute rows; reset state between runs
        run_bounds: List[Tuple[int, int]] = []
        start = 0
        for i in range(1, len(compute) + 1):
            if i == len(compute) or compute[i] != compute[i - 1] + 1:
                run_bounds.append((start, i))
                start = i
        out_parts: Dict[str, List[ColumnBatch]] = {c: [] for c in out_cols}

        def emit(col: str, rows: np.ndarray, data, per_row: bool) -> None:
            """Append kernel results, dropping warmup rows."""
            keep = np.isin(rows, valid_out)
            if not keep.any():
                return
            if per_row:
                kept = [d for d, k in zip(data, keep) if k]
                out_parts[col].append(
                    ColumnBatch.from_elements(rows[keep], kept))
            else:
                if keep.all():
                    out_parts[col].append(ColumnBatch(rows, data))
                else:
                    idx = np.flatnonzero(keep)
                    out_parts[col].append(
                        ColumnBatch(rows[keep], data[idx]))

        def emit_result(rows: np.ndarray, res) -> None:
            """Dispatch one kernel call's result to output columns.

            Multi-output batch kernels may return either a tuple of
            per-column batches or a list of per-row tuples (the classic
            protocol) — both are accepted."""
            if len(out_cols) == 1:
                cols_res = (res,)
            elif isinstance(res, tuple) and len(res) == len(out_cols):
                cols_res = res
            elif (isinstance(res, list) and len(res) == len(rows)
                  and all(isinstance(r, tuple) and len(r) == len(out_cols)
                          for r in res)):
                cols_res = tuple(list(col) for col in zip(*res))
            else:
                raise JobException(
                    f"{n.name}: expected {len(out_cols)}-tuple output")
            for col, r in zip(out_cols, cols_res):
                if is_array_data(r) and len(r) == len(rows):
                    emit(col, rows, r, per_row=False)
                else:
                    if r is None or len(r) != len(rows):
                        raise JobException(
                            f"{n.name}: batch kernel returned "
                            f"{0 if r is None else len(r)} results "
                            f"for {len(rows)} inputs")
                    emit(col, rows, list(r), per_row=True)

        null_out_rows: List[int] = []

        def null_rows(rows: np.ndarray) -> None:
            keep = np.isin(rows, valid_out)
            if keep.any():
                null_out_rows.extend(rows[keep].tolist())

        def call_args_for(sel: np.ndarray) -> List[Any]:
            """Kernel arguments for compute positions `sel` (indices into
            the compute/col_pos arrays): per input column either a
            (k, ...) batch slice, a (k, W, ...) stencil gather, or per-row
            python objects."""
            args = []
            for b, pos in zip(in_batches, col_pos):
                p = pos[sel]           # (k, W)
                if is_array_data(b.data):
                    if has_stencil:
                        with self.profiler.span(
                                "evaluate:gather", op=n.name, rows=len(p),
                                counter=_M_GATHER_SECONDS.labels(
                                    op=n.name)):
                            args.append(_gather_window(b.data, p))
                        _M_GATHER_BYTES.labels(op=n.name).inc(
                            args[-1].nbytes)
                    else:
                        q = p[:, 0]
                        if len(q) and np.array_equal(
                                q, np.arange(q[0], q[0] + len(q))):
                            args.append(rows_run(b.data, int(q[0]),
                                                 len(q)))
                        else:
                            args.append(rows_at(b.data, q))
                else:
                    if has_stencil:
                        args.append([[b.data[int(j)] for j in row]
                                     for row in p])
                    else:
                        args.append([b.data[int(j)] for j in p[:, 0]])
            return args

        ki.ensure_warm()
        # a batched device call goes into the evaluator's window of
        # calls in flight (CallWindow); with coststats on its analytical
        # cost descriptor goes with it, to be joined with the chip's
        # seconds at its deferred wait (util/coststats.py)
        in_window = batched_call \
            and n.effective_device() == DeviceType.TPU
        track_cost = in_window and _cs.enabled()
        dispatch_s = _M_OP_DISPATCH_SECONDS.labels(op=n.name)
        # a bounded-state task with a warm-up stands alone: its plan
        # begins with the rows that make its state, so the kernel is
        # reset at its first compute row whatever ran before (a later
        # chunk of a streaming task goes on from the chunk before it)
        fresh_task = n.spec.is_stateful and n.stands_alone() \
            and not plan.resumes
        resets0 = ki.resets
        warm_rows = len(compute) - len(valid_out)
        try:
            with self._op_span(
                    n.name, len(compute),
                    ki.dev_label if is_device_kernel else "host") as span:
                for lo, hi in run_bounds:
                    if lo == 0 and fresh_task:
                        ki.reset_state()
                    else:
                        ki.maybe_reset(int(compute[lo]))
                    ki._last_row = int(compute[hi - 1])
                    i = lo
                    while i < hi:
                        j = min(i + batch, hi)
                        sel = np.arange(i, j)
                        dead = sel[null_in[sel]]
                        if len(dead):
                            null_rows(compute[dead])
                        if mask_nulls and len(dead) < len(sel):
                            # full-chunk call; dead rows' outputs are
                            # overwritten with nulls at assembly time
                            live = sel
                        else:
                            live = sel[~null_in[sel]]
                        if not len(live):
                            i = j
                            continue
                        if batched_call:
                            exec_sel, pad = live, 0
                            if use_buckets:
                                pad = bucket_for(len(live),
                                                 ladder) - len(live)
                                if pad:
                                    exec_sel = np.concatenate(
                                        [live,
                                         np.repeat(live[-1:], pad)])
                                    _M_OP_PAD_ROWS.labels(
                                        op=n.name,
                                        device=ki.dev_label).inc(pad)
                            if in_window:
                                # back-pressure: the oldest call in
                                # flight is waited for before this
                                # one's arguments take their HBM
                                self.calls.admit()
                            args = call_args_for(exec_sel)
                            # a never-seen arg (device, shape, dtype)
                            # signature means XLA compiles a fresh
                            # executable for a jitted kernel — surface it
                            # live.  The device is part of the key: each
                            # assigned chip compiles its own ladder, and
                            # the CI ladder-bound guard holds per chip.
                            sig = (ki.dev_label,) + tuple(
                                (tuple(a.shape), str(a.dtype))
                                if is_array_data(a) else len(a)
                                for a in args)
                            new_sig = sig not in ki._shape_sigs
                            if new_sig:
                                ki._shape_sigs.add(sig)
                                _M_OP_RECOMPILES.labels(
                                    op=n.name,
                                    device=ki.dev_label).inc()
                                # a recompile inside a traced task is a
                                # latency cliff worth pinning to the
                                # exact op span that paid it
                                _tracing.add_event(
                                    "xla.recompile", op=n.name,
                                    device=ki.dev_label)
                            t_call = time.time()
                            with self.profiler.span(
                                    "evaluate:dispatch", op=n.name,
                                    rows=len(exec_sel),
                                    counter=dispatch_s):
                                if new_sig:
                                    # first call of a fresh signature:
                                    # any XLA compile inside lands in
                                    # the compile ledger under this
                                    # (op, device, bucket)
                                    with ki._call_lock, _first_call(
                                            n.name, ki.dev_label,
                                            len(exec_sel), repr(sig[1:]),
                                            track_cost):
                                        res = ki.kernel.execute(*args)
                                else:
                                    with ki._call_lock:
                                        res = ki.kernel.execute(*args)
                            if in_window:
                                # in flight: its wait is taken two
                                # calls on (a first call's at once: the
                                # compile never reads as the op's)
                                self.calls.put(
                                    res, n.name, ki.dev_label,
                                    len(exec_sel), len(live), t_call,
                                    first=new_sig,
                                    desc=None if new_sig or not track_cost
                                    else _cs.descriptor_for(
                                        ki.kernel, n.name, ki.dev_label,
                                        len(exec_sel), args),
                                    ki=ki)
                            if pad:
                                res = _strip_pad(res, len(live),
                                                 len(out_cols))
                            emit_result(compute[live], res)
                        else:
                            args = call_args_for(live)
                            row_args = []
                            for a in args:
                                e = a[0]
                                if has_stencil and is_array_data(a):
                                    e = list(a[0])
                                row_args.append(e)
                            with ki._call_lock:
                                res = ki.kernel.execute(*row_args)
                            emit_result(compute[live], _single(res, n, out_cols))
                        i = j
                if n.spec.is_stateful:
                    span.args.update(warmup_rows=warm_rows,
                                     resets=ki.resets - resets0)
                # straggler attribution: the op span carries its own
                # roofline verdict, so a slow evaluate:<op> stage reads
                # as INEFFICIENT (low eff) vs OVERLOADED (high eff,
                # deep queues) in the master's analytics
                self._efficiency_event(n.name, ki.dev_label)
        except BaseException as e:
            # the kernel died mid-run: its internal state is partial and
            # _last_row may already claim the run's end.  Reset both so a
            # subsequent carry plan MISSES (fallback) instead of silently
            # continuing from half-advanced state, and a self-contained
            # re-run starts from a clean reset.
            if ki.spec.is_stateful:
                try:
                    ki.kernel.reset()
                finally:
                    ki._last_row = None
            _note_call_failure(e, f"op {n.name} on {ki.dev_label}")
            raise
        _M_OP_ROWS.labels(op=n.name).inc(len(compute))
        if warm_rows or n.spec.is_stateful:
            _M_STATE_WARMUP_ROWS.labels(op=n.name).inc(warm_rows)

        # assemble output columns in row order; null-propagated rows (rare)
        # interleave with kernel results, so columns containing them fall
        # back to per-element assembly
        null_set = set(null_out_rows)
        outputs: Dict[str, ColumnBatch] = {}
        for col in out_cols:
            parts = out_parts[col]
            if not parts and not null_set:
                outputs[col] = ColumnBatch(np.zeros(0, np.int64), [])
                continue
            if null_set:
                by_row: Dict[int, Elem] = {}
                for p in parts:
                    for r, e in zip(p.rows.tolist(), p.elements()):
                        by_row[r] = e
                # nulls LAST so they win: bucketed dispatch runs dead
                # rows through the kernel (full-chunk shape) and their
                # outputs must be discarded here
                for r in null_set:
                    by_row[int(r)] = NullElement()
                rows_sorted = np.asarray(sorted(by_row), np.int64)
                outputs[col] = ColumnBatch.from_elements(
                    rows_sorted, [by_row[int(r)] for r in rows_sorted])
            else:
                parts.sort(
                    key=lambda p: int(p.rows[0]) if len(p.rows) else 0)
                outputs[col] = concat_batches(parts)
            got = set(outputs[col].rows.tolist())
            if got != valid_set:
                missing = sorted(valid_set - got)
                raise JobException(
                    f"{n.name}: missing output rows {missing[:5]}...")
        return outputs

    # -- fused chains ---------------------------------------------------

    def _run_fused(self, n: O.OpNode, jr: A.JobRows, plan: A.TaskPlan,
                   store) -> Dict[str, ColumnBatch]:
        """Dispatch one fused chain at its tail node `n`: gather the
        composed stencil window from the HEAD member's input column,
        run the single jitted chain program through the chain's bucket
        ladder, and emit only the tail's outputs — member intermediates
        never materialize.  Chain-level row semantics reproduce the
        staged path exactly: REPEAT_EDGE padding at every member level
        (compose_positions), null propagation over the composed window
        (a tail row is null iff ANY transitively-read input row is
        null), bucketed tail-chunk padding, nulls-last assembly."""
        chain = self.chains[n.id]
        fki = self.fused[n.id]
        ts = plan.streams[n.id]
        fki.bind_stream(plan.job_idx, plan.slice_group)

        head = chain.head
        in_col = head.input_columns()[0]
        in_b = store[(in_col.op.id, in_col.column)]
        g = plan.slice_group if self.info.slice_level[n.id] > 0 else 0
        max_in = jr.rows[in_col.op.id][g]

        # one chain-wide batch cap (see FusedKernelInstance.cap_for)
        wp = int(getattr(jr, "work_packet_size", 0) or 0)
        batch = fki.cap_for(wp)
        use_buckets = _bucketing_enabled()
        ladder = bucket_ladder(batch) if use_buckets else None

        compute = np.asarray(ts.compute_rows, np.int64)
        out_cols = [c for c, _ in n.spec.output_columns]
        valid_out = np.asarray(ts.valid_output_rows, np.int64)
        valid_set = set(valid_out.tolist())
        width = fki.width
        with self.profiler.span(
                "evaluate:inputs", op=fki.chain_id, rows=len(compute),
                counter=_M_OP_INPUT_SECONDS.labels(op=fki.chain_id)):
            # device staging: ONE host->device move for the head column
            # — the only HBM traffic the whole chain pays on the input
            # side
            if _device_staging_enabled() \
                    and isinstance(in_b.data, np.ndarray) \
                    and in_b.data.dtype != object:
                in_b = in_b.to_device(fki.device)
            if in_b.convert is not None:
                in_b = in_b.converted()
            store[(in_col.op.id, in_col.column)] = in_b
            # composed window positions per tail compute row
            # (REPEAT_EDGE at every member level = the staged transitive
            # dilation)
            win_rows = fki.compose_positions(compute, max_in).reshape(
                len(compute), width)
            col_pos = in_b.positions(win_rows.reshape(-1)).reshape(
                win_rows.shape)

        # null propagation across the whole chain in one step
        null_in = np.zeros(len(compute), bool)
        if in_b.nulls is not None:
            null_in |= in_b.nulls[col_pos].any(axis=1)
        mask_nulls = use_buckets and (in_b.nulls is None
                                      or is_array_data(in_b.data))

        out_parts: Dict[str, List[ColumnBatch]] = {c: [] for c in out_cols}

        def emit(col: str, rows: np.ndarray, data, per_row: bool) -> None:
            keep = np.isin(rows, valid_out)
            if not keep.any():
                return
            if per_row:
                kept = [d for d, k in zip(data, keep) if k]
                out_parts[col].append(
                    ColumnBatch.from_elements(rows[keep], kept))
            else:
                if keep.all():
                    out_parts[col].append(ColumnBatch(rows, data))
                else:
                    idx = np.flatnonzero(keep)
                    out_parts[col].append(
                        ColumnBatch(rows[keep], data[idx]))

        def emit_result(rows: np.ndarray, res) -> None:
            if len(out_cols) == 1:
                cols_res = (res,)
            elif isinstance(res, tuple) and len(res) == len(out_cols):
                cols_res = res
            elif (isinstance(res, list) and len(res) == len(rows)
                  and all(isinstance(r, tuple) and len(r) == len(out_cols)
                          for r in res)):
                cols_res = tuple(list(col) for col in zip(*res))
            else:
                raise JobException(
                    f"{fki.chain_id}: expected {len(out_cols)}-tuple "
                    f"output")
            for col, r in zip(out_cols, cols_res):
                if is_array_data(r) and len(r) == len(rows):
                    emit(col, rows, r, per_row=False)
                else:
                    if r is None or len(r) != len(rows):
                        raise JobException(
                            f"{fki.chain_id}: fused chain returned "
                            f"{0 if r is None else len(r)} results "
                            f"for {len(rows)} inputs")
                    emit(col, rows, list(r), per_row=True)

        null_out_rows: List[int] = []

        def null_rows(rows: np.ndarray) -> None:
            keep = np.isin(rows, valid_out)
            if keep.any():
                null_out_rows.extend(rows[keep].tolist())

        def call_data(sel: np.ndarray):
            """The head-input gather for compute positions `sel`: a
            (k * width, ...) array in composed-window order (the chain
            body re-folds the window axes member by member)."""
            p = col_pos[sel].reshape(-1)
            if is_array_data(in_b.data):
                if np.array_equal(p, np.arange(p[0], p[0] + len(p))):
                    return rows_run(in_b.data, int(p[0]), len(p))
                return rows_at(in_b.data, p)
            # object column: stack per-row host data into one array
            return np.stack([np.asarray(in_b.data[int(j)]) for j in p])

        fki.ensure_warm()
        # chains are always batched TPU dispatch by construction
        track_cost = _cs.enabled()
        dispatch_s = _M_OP_DISPATCH_SECONDS.labels(op=fki.chain_id)
        try:
            with self._op_span(fki.chain_id, len(compute),
                               fki.dev_label):
                i = 0
                while i < len(compute):
                    j = min(i + batch, len(compute))
                    sel = np.arange(i, j)
                    dead = sel[null_in[sel]]
                    if len(dead):
                        null_rows(compute[dead])
                    if mask_nulls and len(dead) < len(sel):
                        live = sel
                    else:
                        live = sel[~null_in[sel]]
                    if not len(live):
                        i = j
                        continue
                    exec_sel, pad = live, 0
                    if use_buckets:
                        pad = bucket_for(len(live), ladder) - len(live)
                        if pad:
                            exec_sel = np.concatenate(
                                [live, np.repeat(live[-1:], pad)])
                            _M_OP_PAD_ROWS.labels(
                                op=fki.chain_id,
                                device=fki.dev_label).inc(pad)
                    self.calls.admit()
                    arr = call_data(exec_sel)
                    sig = (fki.dev_label, tuple(arr.shape),
                           str(arr.dtype))
                    new_sig = sig not in fki._shape_sigs
                    if new_sig:
                        fki._shape_sigs.add(sig)
                        _M_OP_RECOMPILES.labels(
                            op=fki.chain_id,
                            device=fki.dev_label).inc()
                        _tracing.add_event("xla.recompile",
                                           op=fki.chain_id,
                                           device=fki.dev_label)
                    t_call = time.time()
                    with self.profiler.span(
                            "evaluate:dispatch", op=fki.chain_id,
                            rows=len(exec_sel), counter=dispatch_s):
                        if new_sig:
                            # fresh signature: ONE ledger entry for the
                            # whole chain, members recorded for
                            # attribution
                            with fki._call_lock, _first_call(
                                    fki.chain_id, fki.dev_label,
                                    len(exec_sel), repr(sig[1:]),
                                    track_cost,
                                    members=fki.member_names):
                                res = fki.execute(arr)
                        else:
                            with fki._call_lock:
                                res = fki.execute(arr)
                    desc, saved = (None, None) if new_sig \
                        or not track_cost \
                        else fki.cost_for(arr.shape, arr.dtype)
                    self.calls.put(res, fki.chain_id, fki.dev_label,
                                   len(exec_sel), len(live), t_call,
                                   first=new_sig, desc=desc, saved=saved)
                    if pad:
                        res = _strip_pad(res, len(live), len(out_cols))
                    emit_result(compute[live], res)
                    i = j
                # straggler attribution for the fused span; the chain
                # attr lets timeline consumers group fusion events
                # without parsing op labels
                self._efficiency_event(fki.chain_id, fki.dev_label,
                                       chain=fki.chain_id)
        except BaseException as e:
            _note_call_failure(
                e, f"chain {fki.chain_id} on {fki.dev_label}")
            raise
        _M_OP_ROWS.labels(op=fki.chain_id).inc(len(compute))

        # assembly: identical to _run_kernel (nulls LAST so they win)
        null_set = set(null_out_rows)
        outputs: Dict[str, ColumnBatch] = {}
        for col in out_cols:
            parts = out_parts[col]
            if not parts and not null_set:
                outputs[col] = ColumnBatch(np.zeros(0, np.int64), [])
                continue
            if null_set:
                by_row: Dict[int, Elem] = {}
                for p in parts:
                    for r, e in zip(p.rows.tolist(), p.elements()):
                        by_row[r] = e
                for r in null_set:
                    by_row[int(r)] = NullElement()
                rows_sorted = np.asarray(sorted(by_row), np.int64)
                outputs[col] = ColumnBatch.from_elements(
                    rows_sorted, [by_row[int(r)] for r in rows_sorted])
            else:
                parts.sort(
                    key=lambda p: int(p.rows[0]) if len(p.rows) else 0)
                outputs[col] = concat_batches(parts)
            got = set(outputs[col].rows.tolist())
            if got != valid_set:
                missing = sorted(valid_set - got)
                raise JobException(
                    f"{fki.chain_id}: missing output rows "
                    f"{missing[:5]}...")
        return outputs


def _single(res, n, out_cols):
    """Wrap a batch=1 result to per-row list form for emit_result."""
    if len(out_cols) == 1:
        return [res]
    if not isinstance(res, tuple) or len(res) != len(out_cols):
        raise JobException(
            f"{n.name}: expected {len(out_cols)}-tuple output")
    return tuple([v] for v in res)
