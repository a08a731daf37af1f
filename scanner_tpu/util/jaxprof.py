"""Device-side (XLA/JAX) trace capture for the engine profiler.

SURVEY §5 tracing row: the reference records host-side interval spans
(scanner/util/profiler.cpp); the TPU equivalent must also see the DEVICE
timeline — XLA op execution, h2d/d2h transfers, compilation — or claims
like "h2d rides under decode" stay inferences from wall clocks.  At
``profiler_level >= 2`` the engine wraps a job's execution in
``jax.profiler.start_trace``/``stop_trace`` and records the trace
directory on the host profiler; ``Profile.write_trace`` then merges the
device timeline into the same Chrome-trace JSON so host stage spans and
device op execution land in ONE perfetto view.

Alignment: the XLA trace's ``ts`` values are microseconds relative to
``start_trace``, so events are shifted by the host wall-clock captured at
start (``t0``).  Device processes are offset into a distinct pid range so
they can never collide with the host profiler's node pids.

JAX allows one active trace per process; concurrent jobs (e.g. several
in-process workers in tests) serialize on a module lock — the first job
gets the device trace, the rest run untraced rather than erroring.
"""

from __future__ import annotations

import atexit
import contextlib
import glob
import gzip
import json
import logging
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

_log = logging.getLogger("scanner_tpu.jaxprof")

# one active jax.profiler trace per process
_ACTIVE = threading.Lock()

# Trace dumps are tens-to-hundreds of MB; auto-created dirs (no explicit
# out_dir) are deleted when this process exits so a long session of
# level-2 jobs cannot fill /tmp.  Callers who want to keep a capture
# (e.g. to open in TensorBoard/XProf) pass out_dir.
_AUTO_DIRS: List[str] = []


def _cleanup_auto_dirs() -> None:
    for d in _AUTO_DIRS:
        shutil.rmtree(d, ignore_errors=True)


atexit.register(_cleanup_auto_dirs)

# pid offset for merged device processes (host profiler pids are 1..N)
DEVICE_PID_BASE = 1000


@contextlib.contextmanager
def device_trace(profiler, out_dir: Optional[str] = None):
    """Capture the XLA device trace around a job when the profiler runs
    at level >= 2; no-op otherwise.  A failing tracer never takes down
    the job: it is logged and counted as the profiler counter
    `device_trace_failed`, so a level-2 profile without device lanes
    says why."""
    if getattr(profiler, "level", 1) < 2:
        yield
        return
    if not _ACTIVE.acquire(blocking=False):
        _log.info("device trace already active in this process; "
                  "running untraced")
        yield
        return
    try:
        trace_dir = None
        auto = out_dir is None
        try:
            import jax
            trace_dir = out_dir or tempfile.mkdtemp(prefix="sc_devtrace_")
            # device planes only: the host tracer's events of the TPU
            # runtime run to hundreds of MB a job and slow the host
            # threefold (PERF.md §6 finding 3); the host side of the
            # merged view is the profiler's own spans
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0
            opts.python_tracer_level = 0
            t0 = time.time()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            if auto:
                _AUTO_DIRS.append(trace_dir)
        except Exception as e:  # noqa: BLE001
            _log.warning("jax.profiler.start_trace failed: %s", e)
            profiler.count("device_trace_failed")
            if auto and trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
            yield
            return
        try:
            yield
        finally:
            try:
                jax.profiler.stop_trace()
                # t0/t1 bound the capture window on the host wall clock;
                # consumers align against THIS window, not the host
                # profiler's first span
                profiler.device_traces.append(
                    {"dir": trace_dir, "t0": t0, "t1": time.time()})
            except Exception as e:  # noqa: BLE001
                _log.warning("jax.profiler.stop_trace failed: %s", e)
                profiler.count("device_trace_failed")
    finally:
        _ACTIVE.release()


def _devtrace_event_cap() -> int:
    try:
        return int(os.environ.get("SCANNER_TPU_DEVTRACE_MAX_EVENTS",
                                  "200000") or 200000)
    except ValueError:
        return 200000


def _read_raw_events(rec: Dict[str, Any],
                     include_python: bool = False) -> List[Dict[str, Any]]:
    """Unshifted device-trace events for one capture record: the
    embedded ``events`` list when present (a profile that crossed
    hosts), else read from the local trace directory."""
    if "events" in rec:
        return rec["events"]
    files = sorted(glob.glob(
        os.path.join(rec["dir"], "**", "*.trace.json.gz"), recursive=True))
    out: List[Dict[str, Any]] = []
    for path in files:
        try:
            with gzip.open(path) as f:
                doc = json.load(f)
        except Exception as e:  # noqa: BLE001
            _log.warning("unreadable device trace %s: %s", path, e)
            continue
        for ev in doc.get("traceEvents", []):
            if not include_python and \
                    str(ev.get("name", "")).startswith("$"):
                continue
            out.append(ev)
    return out


def embed_device_events(rec: Dict[str, Any],
                        max_events: Optional[int] = None
                        ) -> Dict[str, Any]:
    """Serialize the capture's device events INTO the record (mutates
    and returns it) so the profile survives crossing hosts.

    Cross-host fix: only the local trace *directory* path used to
    travel with a shipped profile, so ``load_device_events`` on the
    master returned [] and merged traces silently lost every remote
    device timeline.  Workers call this before ``PostProfile``; bounded
    by SCANNER_TPU_DEVTRACE_MAX_EVENTS (default 200000, longest-first
    truncation recorded in ``events_dropped``) so a verbose capture
    cannot blow the RPC message cap."""
    if "events" in rec:
        return rec
    evs = _read_raw_events(rec)
    cap = _devtrace_event_cap() if max_events is None else max_events
    # Chrome 'M' metadata (process/thread names) is exempt from the
    # cap: dur-less, a handful per capture, and dropping it would
    # render remote device lanes as bare pid numbers
    meta = [e for e in evs if e.get("ph") == "M"]
    rest = [e for e in evs if e.get("ph") != "M"]
    if len(rest) > cap:
        # keep the longest slices: truncation should cost the noise
        # floor, not the dominant kernels
        rest.sort(key=lambda e: -float(e.get("dur", 0.0) or 0.0))
        rec["events_dropped"] = len(rest) - cap
        rest = rest[:cap]
    rec["events"] = meta + rest
    return rec


def load_device_events(rec: Dict[str, Any],
                       pid_base: int = DEVICE_PID_BASE,
                       include_python: bool = False
                       ) -> List[Dict[str, Any]]:
    """Load one recorded device trace as Chrome trace events, shifted to
    the host wall clock and into the device pid range.

    ``rec`` is a ``{"dir": ..., "t0": ...}`` entry from
    ``Profiler.device_traces``; records that crossed hosts carry their
    events inline (``embed_device_events``) and need no filesystem.
    Returns [] when neither embedded events nor a readable local
    directory exist.  The profiler's Python-call spans (names prefixed
    ``$``, tens of thousands per job) drown the device lanes and
    duplicate what the host profiler already records; they are dropped
    unless ``include_python=True``."""
    raw = _read_raw_events(rec, include_python=include_python)
    shift_us = rec["t0"] * 1e6
    out: List[Dict[str, Any]] = []
    for ev in raw:
        if not include_python and str(ev.get("name", "")).startswith("$"):
            continue
        ev = dict(ev)
        if "pid" in ev:
            ev["pid"] = pid_base + int(ev["pid"])
        if "ts" in ev and ev.get("ph") != "M":
            ev["ts"] = float(ev["ts"]) + shift_us
        out.append(ev)
    return out
