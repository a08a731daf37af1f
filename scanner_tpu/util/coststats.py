"""Compute-efficiency observability: per-op cost model, roofline
attribution, and an XLA compile ledger.

The time plane (util/tracing.py, util/profiler.py) says an op took
3.1 ms on chip 2; the memory plane (util/memstats.py) says whose bytes
live there; the health plane (util/health.py) says whether that is
normal.  None of them says whether 3.1 ms is *good* — 80% of what the
chip can do, or 4%.  And the recompile proxy counts new signatures
without ever recording what XLA actually compiled, how long it took, or
whether the persistent cache hit.  This module is the missing
efficiency plane, two halves:

  * **The compile ledger** — every jitted-kernel compile observed at
    the engine's dispatch/warm-up sites (engine/evaluate.py) records
    (op, device, bucket, signature, compile seconds, persistent-cache
    hit|miss|uncached, executable size and XLA's own analytical cost
    where the backend provides them) into a bounded per-process ring,
    the ``scanner_tpu_compile_*`` series, and an ``xla.compile`` event
    on the owning task's trace span.  Served over the
    ``GetCompileLedger`` RPC / ``Client.compile_report()``.  Compile
    facts come from two sources: the *supported* ``jax.monitoring``
    event stream (backend compile durations, persistent-cache
    hit/miss), and a best-effort wrap of jax's internal compile entry
    point that hands us the loaded executable for
    ``cost_analysis()`` / ``memory_analysis()`` — guarded so jax
    version drift degrades ledger entries, never the engine.
  * **Roofline attribution** — an analytical per-op cost descriptor
    (FLOPs and bytes in/out as a function of the call shape, declared
    via the ``Kernel.cost(shapes)`` hook with defaults derived from
    XLA's cost analysis of the compiled executable) joined with the
    chip's seconds for the call (``CallClock``: read off the deferred
    waits of the evaluator's window of calls in flight, no wait of its
    own) into
    achieved FLOP/s, achieved bytes/s, and a compute-vs-memory-bound
    classification per (op, device, bucket) — the
    ``scanner_tpu_op_*`` efficiency gauges.  A slow task then reads as
    *inefficient* (low EFF%) or *overloaded* (high EFF%, long queue),
    which is the question straggler analytics could not answer.

Consumers: the /statusz Efficiency panel, ``scanner_top`` EFF%/bound
columns and compile-cache hit rate, and ``tools/scanner_cost.py``.

Knobs: ``SCANNER_TPU_COSTSTATS=0`` disables both halves (the dispatch
sites then skip descriptor/ledger work entirely; the bound on the
calls an evaluator keeps in flight does not hang on it);
``SCANNER_TPU_COMPILE_LEDGER`` sizes the ring (default 1024 entries).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import metrics as _mx
from . import profiler as _profiler
from . import tracing as _tracing
from .log import get_logger

_log = get_logger("coststats")

# -- live series (docs/observability.md §Efficiency & Compilation) ----------

_M_COMPILES = _mx.registry().counter(
    "scanner_tpu_compile_total",
    "XLA backend compiles observed at the engine's dispatch/warm-up "
    "sites, by op, device and persistent-compilation-cache outcome "
    "(hit = executable deserialized from the cache, miss = cache "
    "configured but cold, uncached = no persistent cache configured).",
    labels=["op", "device", "cache"])
_M_COMPILE_SECONDS = _mx.registry().counter(
    "scanner_tpu_compile_seconds_total",
    "Wall seconds spent inside XLA backend compiles (including "
    "persistent-cache retrieval time on hits) per op and device — the "
    "compile bill the recompile counter only counted.",
    labels=["op", "device"])
# a compile that no observe_compiles block was open for on its thread
# (a wire conversion, a window gather, a frame-cache slice first met
# inside a task): series of their own, so scanner_tpu_compile_total
# keeps counting what the dispatch sites observe and nothing else
_M_STRAY_COMPILES = _mx.registry().counter(
    "scanner_tpu_stray_compile_total",
    "XLA backend compiles that fired on a thread with no "
    "observe_compiles block open, by the innermost profiler span open "
    "on that thread (`site`; `none` outside every span) and "
    "persistent-compilation-cache outcome.",
    labels=["site", "cache"])
_M_STRAY_COMPILE_SECONDS = _mx.registry().counter(
    "scanner_tpu_stray_compile_seconds_total",
    "Wall seconds inside the compiles that "
    "scanner_tpu_stray_compile_total counts (cache retrieval time on "
    "hits included), by site; each is also a `compile` interval of "
    "the job's profile.",
    labels=["site"])
_M_OP_FLOPS = _mx.registry().gauge(
    "scanner_tpu_op_achieved_flops",
    "Achieved FLOP/s per (op, device, bucket): analytical FLOPs from "
    "the op's cost descriptor divided by measured kernel-call seconds "
    "(compile-bearing first calls excluded).  0 when the descriptor "
    "declares no FLOPs (pure data movement).",
    labels=["op", "device", "bucket"])
_M_OP_BW = _mx.registry().gauge(
    "scanner_tpu_op_achieved_bandwidth_bytes",
    "Achieved bytes/s per (op, device, bucket): descriptor bytes "
    "in+out over measured kernel-call seconds.",
    labels=["op", "device", "bucket"])
_M_OP_EFF = _mx.registry().gauge(
    "scanner_tpu_op_efficiency_ratio",
    "Roofline efficiency per (op, device, bucket): achieved rate over "
    "the device's peak for the binding resource — FLOP/s over peak "
    "FLOP/s when compute-bound, bytes/s over peak bandwidth when "
    "memory-bound.  1.0 = at the roofline.",
    labels=["op", "device", "bucket"])
_M_OP_BOUND = _mx.registry().gauge(
    "scanner_tpu_op_compute_bound",
    "Roofline classification per (op, device, bucket): 1 = "
    "compute-bound (operational intensity above the device ridge "
    "point), 0 = memory-bound (below it, or FLOPs unknown).",
    labels=["op", "device", "bucket"])

# the series this module owns, in one statically-readable tuple:
# scanner-check SC309 keeps it, the registrations above, and the
# marker-delimited catalog table in docs/observability.md in sync
EFFICIENCY_SERIES = (
    "scanner_tpu_compile_total",
    "scanner_tpu_compile_seconds_total",
    "scanner_tpu_stray_compile_total",
    "scanner_tpu_stray_compile_seconds_total",
    "scanner_tpu_op_achieved_flops",
    "scanner_tpu_op_achieved_bandwidth_bytes",
    "scanner_tpu_op_efficiency_ratio",
    "scanner_tpu_op_compute_bound",
)

# same knob semantics as SCANNER_TPU_TRACING / _MEMSTATS (one parser)
_ENABLED = _tracing._env_on("SCANNER_TPU_COSTSTATS")


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Programmatic override (tests, embedders); the
    SCANNER_TPU_COSTSTATS env var is read at import and is the
    per-process default."""
    global _ENABLED
    _ENABLED = bool(on)


def _env_ring_size() -> int:
    import os
    try:
        return max(16, int(os.environ.get("SCANNER_TPU_COMPILE_LEDGER",
                                          "1024") or 1024))
    except ValueError:
        return 1024


# ---------------------------------------------------------------------------
# Cost descriptors
# ---------------------------------------------------------------------------

@dataclass
class CostDescriptor:
    """Analytical cost of ONE kernel call: floating-point operations
    and bytes moved in/out as the kernel's ``cost(shapes)`` hook
    declared them (``source="hook"``), as XLA's cost analysis of the
    compiled executable measured them (``source="derived"``), or as
    the dispatch site observed from live argument bytes when neither
    exists (``source="observed"``: bytes only, FLOPs unknown)."""

    flops: Optional[float] = None
    bytes_in: Optional[float] = None
    bytes_out: Optional[float] = None
    source: str = "hook"

    @property
    def bytes_total(self) -> float:
        return float(self.bytes_in or 0.0) + float(self.bytes_out or 0.0)


# ---------------------------------------------------------------------------
# Device peaks (the roofline)
# ---------------------------------------------------------------------------

# (device_kind substring, peak dense-bf16 FLOP/s, peak HBM bytes/s) per
# chip generation — public spec-sheet numbers (Google Cloud TPU
# documentation), matched case-insensitively against jax's device_kind.
# The table is a *reference* roofline: EFF% compares kernels against
# each other and across rounds on the same chip; absolute calibration
# rides on these constants.  An accelerator kind that is not in the
# table is an error at the call that asks for a roofline, not a default.
DEVICE_PEAKS = (
    ("v6e", 918e12, 1.64e12),
    ("v5p", 459e12, 2.765e12),
    ("v5e", 197e12, 8.19e11),
    ("v5 lite", 197e12, 8.19e11),
    ("v4", 275e12, 1.228e12),
    ("v3", 123e12, 9.0e11),
    ("v2", 46e12, 7.0e11),
)
# host entry: order-of-magnitude for a few AVX cores — CPU EFF% is
# indicative only (tests pin behavior through set_device_peaks)
_CPU_PEAK = (2e11, 5e10)

_peak_lock = threading.Lock()
_peak_overrides: Dict[str, Tuple[float, float]] = {}
_peak_cache: Dict[str, Tuple[float, float]] = {}


def set_device_peaks(device_label: str, peak_flops: float,
                     peak_bytes_per_s: float) -> None:
    """Override the roofline for one device label (calibration from a
    measured microbench, or a synthetic peak in tests)."""
    with _peak_lock:
        _peak_overrides[device_label] = (float(peak_flops),
                                         float(peak_bytes_per_s))
        _peak_cache.pop(device_label, None)


def _device_kind(device_label: str) -> str:
    """jax's device_kind string for a metrics device label ("tpu:3";
    "default" = the first local device), or "" when the label names no
    local device (a process that never imported jax, a synthetic label
    in tests)."""
    import sys
    if sys.modules.get("jax") is None:
        return ""
    import jax
    from . import memstats as _ms
    devs = jax.local_devices()
    if device_label == "default":
        return str(devs[0].device_kind)
    for d in devs:
        if _ms.device_label(d) == device_label:
            return str(d.device_kind)
    return ""


def peaks_for_kind(kind: str) -> Tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) for a jax ``device_kind`` string: the
    DEVICE_PEAKS generation match, or the host entry for a CPU kind.
    Any other kind raises — a roofline against a guessed peak is worse
    than none."""
    low = kind.lower()
    for sub, f, b in DEVICE_PEAKS:
        if sub in low:
            return (f, b)
    if low == "cpu":
        return _CPU_PEAK
    raise ValueError(
        f"no roofline peaks for device kind {kind!r}: add it to "
        "coststats.DEVICE_PEAKS (with its source) or calibrate with "
        "set_device_peaks()")


def device_peaks(device_label: str) -> Tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) for a device label: explicit
    override > generation match on jax's device_kind.  A label that
    names no local device (synthetic labels in tests) judges against
    the host entry; a local accelerator whose kind is not in
    DEVICE_PEAKS raises (see peaks_for_kind)."""
    with _peak_lock:
        if device_label in _peak_overrides:
            return _peak_overrides[device_label]
        if device_label in _peak_cache:
            return _peak_cache[device_label]
    peak = peaks_for_kind(_device_kind(device_label) or "cpu")
    with _peak_lock:
        _peak_cache[device_label] = peak
    return peak


def result_ready(res: Any) -> bool:
    """Whether the chip has finished a call's result: every leaf that
    can say so (a jax.Array's `is_ready`) says yes.  A result with no
    such leaf (host data, a kernel that fetched inside its execute) is
    ready."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(res)
    except Exception:  # noqa: BLE001 — no jax, nothing to wait for
        return True
    for leaf in leaves:
        ready = getattr(leaf, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


def wait_ready(res: Any) -> Any:
    """Block until the chip has finished a call's result.  What the
    call raised on the chip is raised here: the wait is where an
    asynchronous failure surfaces.  Pass-through for host-only
    results."""
    import jax
    return jax.block_until_ready(res)


class CallClock:
    """The chip's seconds for calls whose waits are taken late
    (engine/evaluate.py CallWindow): one per evaluator, whose chip runs
    its calls in the order they were dispatched.  Call k ran from
    max(t_dispatch(k), t_done(k-1)) to t_done(k), and t_done(k) is seen
    only by a wait that blocked and returned then.  A wait that found
    its result ready saw no completion edge: that call is not timed,
    and all it tells the calls after it is that the chip was busy until
    its own start at the least.  So `_free` is a lower bound of the
    time the chip had finished everything dispatched so far, a timed
    call's start is never later than the true one, and no sample is
    shorter than the chip's time for the call (but for the jitter of
    the wake-up its predecessor's edge was seen with, which the sum of
    consecutive samples, what the gauges divide by, does not have): an
    efficiency gauge fed from here under-reads where the bound is
    loose and never over-reads."""

    def __init__(self) -> None:
        self._free = 0.0

    def done(self, t_dispatch: float, t_done: float,
             blocked: bool) -> Optional[float]:
        """A call's wait returned at `t_done`.  Its seconds on the chip
        where the wait blocked (the completion edge was seen), else
        None (as for a call that is not to be timed at all: a first
        call, whose seconds hold its compile)."""
        start = max(t_dispatch, self._free)
        if not blocked:
            self._free = start
            return None
        self._free = t_done
        return t_done - start


def classify(device_label: str, flops: Optional[float],
             bytes_total: float, seconds: float
             ) -> Optional[Dict[str, Any]]:
    """Roofline verdict for measured work: achieved rates plus the
    binding resource and its efficiency.  None when there is nothing
    to judge (no time, or neither FLOPs nor bytes known)."""
    if seconds <= 0:
        return None
    peak_f, peak_b = device_peaks(device_label)
    f_rate = (flops or 0.0) / seconds
    b_rate = bytes_total / seconds
    if flops and bytes_total:
        # operational intensity vs the ridge point decides the bound
        compute = (flops / bytes_total) >= (peak_f / peak_b)
    elif flops:
        compute = True
    elif bytes_total:
        compute = False
    else:
        return None
    eff = (f_rate / peak_f) if compute else (b_rate / peak_b)
    return {"flops_per_s": f_rate, "bytes_per_s": b_rate,
            "bound": "compute" if compute else "memory",
            "eff": eff}


# ---------------------------------------------------------------------------
# Compile observation
# ---------------------------------------------------------------------------

# jax.monitoring event names (stable across the 0.4.x line)
_EV_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_OUTCOME = {_EV_CACHE_HIT: "hit", _EV_CACHE_MISS: "miss"}

_tls = threading.local()


class _CompileCtx:
    """Per-observation scratch the global listeners write into: one per
    observe_compiles() block, on the observing thread (XLA compiles run
    synchronously on the calling thread, so thread-local is exact)."""

    __slots__ = ("op", "device", "bucket", "signature", "members",
                 "compiles", "pending_cache", "flops", "bytes_accessed",
                 "arg_bytes", "out_bytes", "temp_bytes", "exec_bytes",
                 "analyzed")

    def __init__(self, op: str, device: str, bucket: int, signature: str,
                 members: Optional[Sequence[str]] = None):
        self.op = op
        self.device = device
        self.bucket = int(bucket)
        self.signature = signature
        self.members = list(members) if members is not None else None
        self.compiles: List[Tuple[float, str]] = []  # (seconds, cache)
        self.pending_cache: Optional[str] = None
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.arg_bytes = 0
        self.out_bytes = 0
        self.temp_bytes = 0
        self.exec_bytes = 0
        self.analyzed = 0

    def absorb_executable(self, ex: Any) -> None:
        """Analytical cost from a freshly-compiled executable
        (best-effort: absent methods / drift leave the fields zero)."""
        try:
            ca = ex.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            self.flops += float(ca.get("flops", 0.0) or 0.0)
            self.bytes_accessed += float(
                ca.get("bytes accessed", 0.0) or 0.0)
        except Exception:  # noqa: BLE001
            pass
        try:
            ms = ex.get_compiled_memory_stats()
            self.arg_bytes += int(
                getattr(ms, "argument_size_in_bytes", 0) or 0)
            self.out_bytes += int(
                getattr(ms, "output_size_in_bytes", 0) or 0)
            self.temp_bytes += int(
                getattr(ms, "temp_size_in_bytes", 0) or 0)
            self.exec_bytes += int(
                getattr(ms, "generated_code_size_in_bytes", 0) or 0)
        except Exception:  # noqa: BLE001
            pass
        self.analyzed += 1


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event != _EV_BACKEND_COMPILE:
        return
    ctx = getattr(_tls, "ctx", None)
    # the cache hit/miss event for this compile fired just before the
    # duration lands (observed ordering of jax's compile path); consume
    if ctx is not None:
        ctx.compiles.append((float(duration),
                             ctx.pending_cache or "uncached"))
        ctx.pending_cache = None
        return
    cache, _tls.stray_cache = \
        getattr(_tls, "stray_cache", None) or "uncached", None
    _record_stray(float(duration), cache)


def _on_event(event: str, **_kw: Any) -> None:
    cache = _CACHE_OUTCOME.get(event)
    if cache is None:
        return
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.pending_cache = cache
    else:
        _tls.stray_cache = cache


def _record_stray(seconds: float, cache: str) -> None:
    """A compile nobody observed: charged to the innermost profiler
    span open on this thread, and an interval `compile` of that span's
    profile that ends now (the event fires as the compile returns)."""
    span = _profiler.current_span()
    site = span.name if span is not None else "none"
    _M_STRAY_COMPILES.labels(site=site, cache=cache).inc()
    _M_STRAY_COMPILE_SECONDS.labels(site=site).inc(seconds)
    if span is not None:
        end = time.time()
        span.prof.add_interval("compile", end - seconds, end,
                               site=site, cache=cache)


_install_lock = threading.Lock()
_installed = False


def install() -> None:
    """Register the jax.monitoring listeners (supported API) and wrap
    jax's internal compile entry point for executable capture
    (best-effort).  Idempotent; called lazily from the first
    observe_compiles so importing this module never touches jax.
    Registration happens UNDER the install lock: a second thread
    entering observe_compiles during startup must not proceed to its
    compile before the listeners exist, or that compile would be
    silently missing from the ledger."""
    global _installed
    with _install_lock:
        if _installed:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
        except Exception:  # noqa: BLE001 — no jax, no ledger
            _log.debug("jax.monitoring unavailable; compile ledger off",
                       exc_info=True)
            _installed = True
            return
        # best-effort executable capture: version drift here loses ONLY
        # the analytical-cost fields of entries, never compile timing
        try:
            from jax._src import compiler as _jc
            orig = _jc.compile_or_get_cached
            if not getattr(orig, "_scanner_tpu_coststats", False):
                def _wrapped(*a: Any, **kw: Any):
                    ex = orig(*a, **kw)
                    ctx = getattr(_tls, "ctx", None)
                    if ctx is not None:
                        ctx.absorb_executable(ex)
                    return ex

                _wrapped._scanner_tpu_coststats = True
                _jc.compile_or_get_cached = _wrapped
        except Exception:  # noqa: BLE001
            _log.debug("executable capture unavailable (jax drift); "
                       "ledger entries will lack cost_analysis fields",
                       exc_info=True)
        _installed = True


# ---------------------------------------------------------------------------
# The compile ledger
# ---------------------------------------------------------------------------

_ledger_lock = threading.Lock()
_ledger: deque = deque(maxlen=_env_ring_size())
_ledger_seq = 0
# derived analytical cost per (op, device, bucket), fed by compile
# observations, read by descriptor_for as the hook-less default
_xla_costs: Dict[Tuple[str, str, int], Dict[str, float]] = {}


def set_ring_size(n: int) -> None:
    """Re-bound the ledger ring (tests; production sizes via
    SCANNER_TPU_COMPILE_LEDGER at process start).  Keeps the newest
    entries."""
    global _ledger
    with _ledger_lock:
        _ledger = deque(_ledger, maxlen=max(1, int(n)))


def clear() -> None:
    """Drop ledger + efficiency state (tests)."""
    global _ledger_seq
    with _ledger_lock:
        _ledger.clear()
        _xla_costs.clear()
        _ledger_seq = 0
    with _op_lock:
        _op_stats.clear()


@contextlib.contextmanager
def observe_compiles(op: str, device: str, bucket: int, signature: str,
                     members: Optional[Sequence[str]] = None):
    """Attribute any XLA compile inside the block to (op, device,
    bucket): the engine wraps exactly the calls that can compile — each
    warm-up rung, and the first call of a new (device, shape, dtype)
    signature.  Nothing is recorded when no compile fires.  No-op when
    coststats is disabled.  Fused-chain compiles pass `members` (the
    chain's member op names, graph/fusion.py) so ledger entries under
    the stable chain id stay explainable op by op."""
    if not _ENABLED:
        yield
        return
    install()
    prev = getattr(_tls, "ctx", None)
    ctx = _CompileCtx(op, device, bucket, signature, members=members)
    _tls.ctx = ctx
    try:
        yield
    finally:
        _tls.ctx = prev
        if ctx.compiles:
            _record_compiles(ctx)


def _record_compiles(ctx: _CompileCtx) -> None:
    global _ledger_seq
    total_s = sum(s for s, _c in ctx.compiles)
    caches = [c for _s, c in ctx.compiles]
    # the entry's label: hit only when every compile hit; any cold
    # compile makes the observation a miss; uncached = no cache at all
    cache = ("hit" if all(c == "hit" for c in caches)
             else "miss" if any(c in ("hit", "miss") for c in caches)
             else "uncached")
    task, trace_id = None, None
    attrs = _tracing.current_span_attrs()
    if "task" in attrs:
        task = f"{attrs.get('job')},{attrs.get('task')}"
    cur = _tracing.current_context()
    if cur is not None:
        trace_id = cur.trace_id
    entry = {
        "op": ctx.op, "device": ctx.device, "bucket": ctx.bucket,
        "signature": ctx.signature, "compiles": len(ctx.compiles),
        "compile_s": round(total_s, 6), "cache": cache,
        "exec_bytes": ctx.exec_bytes,
        "flops": ctx.flops or None,
        "bytes_accessed": ctx.bytes_accessed or None,
        "argument_bytes": ctx.arg_bytes or None,
        "output_bytes": ctx.out_bytes or None,
        "temp_bytes": ctx.temp_bytes or None,
        "time": time.time(), "task": task, "trace_id": trace_id,
    }
    if ctx.members is not None:
        entry["members"] = list(ctx.members)
    with _ledger_lock:
        _ledger_seq += 1
        entry["seq"] = _ledger_seq
        _ledger.append(entry)
        if ctx.analyzed:
            # hook-less default descriptor source: XLA's own analysis
            # of what it just compiled for this exact call shape
            _xla_costs[(ctx.op, ctx.device, ctx.bucket)] = {
                "flops": ctx.flops,
                "bytes_in": float(ctx.arg_bytes),
                "bytes_out": float(ctx.out_bytes),
            }
    # metric/tracing work outside the ledger lock (lock-order hygiene,
    # same rule as util/memstats.py)
    for secs, c in ctx.compiles:
        _M_COMPILES.labels(op=ctx.op, device=ctx.device, cache=c).inc()
    _M_COMPILE_SECONDS.labels(op=ctx.op, device=ctx.device).inc(total_s)
    # the compile lands on the span that paid for it (warm-up runs
    # outside any trace; dispatch-site compiles pin to the task's op
    # span next to the existing xla.recompile event)
    _tracing.add_event("xla.compile", op=ctx.op, device=ctx.device,
                       bucket=ctx.bucket, seconds=round(total_s, 4),
                       cache=cache)


def compile_ledger(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """Ledger entries, oldest first (the newest `n` when given)."""
    with _ledger_lock:
        items = list(_ledger)
    return items[-n:] if n else items


def ledger_summary() -> Dict[str, Any]:
    """Aggregate ledger view: totals, per-cache-outcome counts, and the
    persistent-cache hit rate (None when no cache was configured)."""
    with _ledger_lock:
        items = list(_ledger)
        total_seen = _ledger_seq
    by_cache: Dict[str, int] = {}
    secs = 0.0
    compiles = 0
    for e in items:
        by_cache[e["cache"]] = by_cache.get(e["cache"], 0) + 1
        secs += e["compile_s"]
        compiles += e["compiles"]
    hit, miss = by_cache.get("hit", 0), by_cache.get("miss", 0)
    rate = hit / (hit + miss) if (hit + miss) else None
    return {"entries": len(items), "entries_seen": total_seen,
            "compiles": compiles, "compile_seconds": round(secs, 4),
            "by_cache": by_cache, "cache_hit_rate": rate}


# ---------------------------------------------------------------------------
# Per-op cost descriptors at the dispatch site
# ---------------------------------------------------------------------------

def descriptor_for(kernel: Any, op: str, device: str, bucket: int,
                   args: Sequence[Any]) -> Optional[CostDescriptor]:
    """The cost of one kernel call: the kernel's ``cost(shapes)`` hook
    first; else the derived default from XLA's cost analysis of this
    (op, device, bucket)'s compiled executable; else bytes observed
    from the live args (FLOPs unknown).  None when coststats is off."""
    if not _ENABLED:
        return None
    shapes: List[Any] = []
    for a in args:
        shp = getattr(a, "shape", None)
        shapes.append(tuple(shp) if shp is not None else len(a))
    try:
        d = kernel.cost(shapes)
        if d is not None:
            # conversion stays inside the guard: a hook returning a
            # malformed dict is as broken as one that raises
            if isinstance(d, dict):
                d = CostDescriptor(**d)
            d.source = "hook"
            return d
    except Exception:  # noqa: BLE001 — a broken hook must not fail a task
        _log.debug("cost() hook of %s failed", op, exc_info=True)
    with _ledger_lock:
        xla = _xla_costs.get((op, device, int(bucket)))
    if xla:
        return CostDescriptor(flops=xla["flops"] or None,
                              bytes_in=xla["bytes_in"] or None,
                              bytes_out=xla["bytes_out"] or None,
                              source="derived")
    nb = sum(int(getattr(a, "nbytes", 0) or 0) for a in args)
    if not nb:
        return None
    return CostDescriptor(flops=None, bytes_in=float(nb),
                          bytes_out=None, source="observed")


# ---------------------------------------------------------------------------
# Roofline accumulation
# ---------------------------------------------------------------------------

_op_lock = threading.Lock()
# (op, device, bucket) -> [calls, rows, seconds, flops, bytes_in,
#                          bytes_out, source]
_op_stats: Dict[Tuple[str, str, int], List[Any]] = {}


def record_op_call(op: str, device: str, bucket: int, rows: int,
                   seconds: float, desc: Optional[CostDescriptor]
                   ) -> Optional[Dict[str, Any]]:
    """Fold one measured, compile-free kernel call into the (op,
    device, bucket) aggregate and refresh the efficiency gauges.
    Returns the cumulative classification (classify() shape) or None
    when there is nothing to judge."""
    if not _ENABLED or desc is None or seconds <= 0:
        return None
    key = (op, device, int(bucket))
    with _op_lock:
        st = _op_stats.get(key)
        if st is None:
            st = _op_stats[key] = [0, 0, 0.0, 0.0, 0.0, 0.0, desc.source]
        st[0] += 1
        st[1] += int(rows)
        st[2] += float(seconds)
        st[3] += float(desc.flops or 0.0)
        st[4] += float(desc.bytes_in or 0.0)
        st[5] += float(desc.bytes_out or 0.0)
        st[6] = desc.source
        calls, _rows, secs, flops, b_in, b_out, _src = st
    cls = classify(device, flops or None, b_in + b_out, secs)
    if cls is None:
        return None
    b = str(int(bucket))
    _M_OP_FLOPS.labels(op=op, device=device, bucket=b).set(
        cls["flops_per_s"])
    _M_OP_BW.labels(op=op, device=device, bucket=b).set(
        cls["bytes_per_s"])
    _M_OP_EFF.labels(op=op, device=device, bucket=b).set(cls["eff"])
    _M_OP_BOUND.labels(op=op, device=device, bucket=b).set(
        1.0 if cls["bound"] == "compute" else 0.0)
    return cls


def op_efficiency() -> List[Dict[str, Any]]:
    """The roofline table: one row per (op, device, bucket) with
    measured rates, the bound classification and EFF% — what /statusz
    and scanner_cost render."""
    with _op_lock:
        items = sorted(_op_stats.items())
    out = []
    for (op, device, bucket), (calls, rows, secs, flops, b_in, b_out,
                               src) in items:
        cls = classify(device, flops or None, b_in + b_out, secs)
        if cls is None:
            continue
        peak_f, peak_b = device_peaks(device)
        out.append({
            "op": op, "device": device, "bucket": bucket,
            "calls": calls, "rows": rows, "seconds": round(secs, 4),
            "flops_per_s": round(cls["flops_per_s"], 2),
            "bytes_per_s": round(cls["bytes_per_s"], 2),
            "bound": cls["bound"],
            "efficiency": round(cls["eff"], 6),
            "peak_flops": peak_f, "peak_bytes_per_s": peak_b,
            "cost_source": src,
        })
    return out


def status_dict() -> Dict[str, Any]:
    """The /statusz Efficiency panel: the roofline table plus the
    compile-ledger summary (full entries stay on the RPC path)."""
    return {"enabled": _ENABLED,
            "ops": op_efficiency(),
            "compile": ledger_summary()}


def compile_report() -> Dict[str, Any]:
    """One process's full efficiency report — what GetCompileLedger
    ships: the bounded ledger, its summary, and the roofline table."""
    return {"ledger": compile_ledger(),
            "summary": ledger_summary(),
            "op_efficiency": op_efficiency()}
