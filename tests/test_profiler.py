"""Profiler levels + bounded buffering (reference profiler.h:40-86 levels,
rpc.proto:270-275 profiler_level)."""

import numpy as np

from scanner_tpu.util.profiler import Profiler


def test_level_filtering():
    p = Profiler(level=0)
    with p.span("coarse", level=0):
        pass
    with p.span("detail", level=1):
        pass
    p.add_interval("verbose", 0.0, 1.0, level=2)
    names = [iv.name for iv in p.intervals()]
    assert names == ["coarse"]


def test_serialization_restores_level_and_cap():
    """from_dict must carry level/max_intervals through the worker ->
    master profile round-trip: a merged profile that re-filtered or
    re-capped on the master would silently drop spans the worker
    already admitted."""
    p = Profiler(node="w", level=2, max_intervals=7)
    with p.span("detail", level=2):
        pass
    q = Profiler.from_dict(p.to_dict())
    assert q.level == 2
    assert q.max_intervals == 7
    assert [iv.name for iv in q.intervals()] == ["detail"]
    # a restored profile must admit the same levels the source did:
    # level-2 spans survived the wire, so new level-2 recording (e.g.
    # during a master-side merge) must not be filtered either
    q.add_interval("post", 0.0, 1.0, level=2)
    assert {iv.name for iv in q.intervals()} == {"detail", "post"}
    # legacy payloads without the keys must not re-filter or re-cap
    d = p.to_dict()
    del d["level"], d["max_intervals"]
    legacy = Profiler.from_dict(d)
    assert [iv.name for iv in legacy.intervals()] == ["detail"]
    legacy.add_interval("post2", 0.0, 1.0, level=2)
    assert "post2" in {iv.name for iv in legacy.intervals()}


def test_interval_cap_counts_drops():
    p = Profiler(max_intervals=5)
    for i in range(9):
        with p.span(f"s{i}"):
            pass
    assert len(p.intervals()) == 5
    assert p.counters["profiler_dropped"] == 4


def test_profiler_level_knob(sc=None):
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    import scanner_tpu.kernels
    from scanner_tpu import video as scv
    import tempfile, os
    root = tempfile.mkdtemp(prefix="prof_")
    vid = os.path.join(root, "v.mp4")
    scv.synthesize_video(vid, num_frames=16, width=64, height=48, fps=24)
    c = Client(db_path=os.path.join(root, "db"))
    try:
        def run(level, name):
            frame = c.io.Input([NamedVideoStream(c, "t", path=vid)])
            out = NamedStream(c, name)
            jid = c.run(c.io.Output(c.ops.Histogram(frame=frame), [out]),
                        PerfParams.manual(8, 16, profiler_level=level),
                        cache_mode=CacheMode.Overwrite, show_progress=False)
            return c.get_profile(jid).statistics()

        st0 = run(0, "p0")
        st1 = run(1, "p1")
        # level 0: coarse stage spans only; level 1 adds per-op detail
        assert "load" in st0 and "evaluate" in st0 and "save" in st0
        assert "evaluate:Histogram" not in st0
        assert "evaluate:Histogram" in st1
    finally:
        c.stop()


def test_device_trace_merged_at_level2():
    """profiler_level >= 2 captures the XLA device timeline around the
    job and Profile.write_trace merges it with the host stage spans into
    one Chrome-trace JSON (SURVEY §5 tracing row: jax.profiler hooks)."""
    import json
    import os
    import tempfile

    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    import scanner_tpu.kernels  # noqa: F401
    from scanner_tpu import video as scv
    from scanner_tpu.util.jaxprof import DEVICE_PID_BASE

    root = tempfile.mkdtemp(prefix="devtrace_")
    vid = os.path.join(root, "v.mp4")
    scv.synthesize_video(vid, num_frames=16, width=64, height=48, fps=24)
    c = Client(db_path=os.path.join(root, "db"))
    try:
        frame = c.io.Input([NamedVideoStream(c, "t", path=vid)])
        out = NamedStream(c, "p2")
        jid = c.run(c.io.Output(c.ops.Histogram(frame=frame), [out]),
                    PerfParams.manual(8, 16, profiler_level=2),
                    cache_mode=CacheMode.Overwrite, show_progress=False)
        prof = c.get_profile(jid)
        recs = [r for p in prof.profilers
                for r in getattr(p, "device_traces", [])]
        assert recs, "no device trace captured at level 2"
        trace_path = os.path.join(root, "merged.trace.json")
        prof.write_trace(trace_path)
        doc = json.load(open(trace_path))
        evs = doc["traceEvents"]
        host = [e for e in evs if e.get("pid", 0) < DEVICE_PID_BASE
                and e.get("ph") == "X"]
        dev = [e for e in evs if e.get("pid", 0) >= DEVICE_PID_BASE]
        assert any(e["name"] == "load" for e in host)
        assert dev, "device events missing from merged trace"
        # device planes only (host and python tracers off, PERF.md §6
        # finding 3): on the CPU backend there is no device plane, so
        # the capture holds the process metadata alone and never a
        # python-call span; where a device does report, its events sit
        # inside the CAPTURE window [t0, t1] after the t0 shift
        from scanner_tpu.util.jaxprof import load_device_events
        full = load_device_events(recs[0], include_python=True)
        assert not [e for e in full
                    if str(e.get("name", "")).startswith("$")]
        dev_ts = [e["ts"] for e in full
                  if "ts" in e and e.get("ph") != "M"]
        t0_us, t1_us = recs[0]["t0"] * 1e6, recs[0]["t1"] * 1e6
        assert all(t0_us - 1e6 <= t <= t1_us + 60e6 for t in dev_ts)
        # and the host stage spans sit inside that same window (one
        # merged perfetto timeline, host and device lanes on one clock:
        # the trace wraps the whole pipeline, so every stage span falls
        # between start_trace and stop_trace)
        host_ts = [e["ts"] for e in host]
        assert min(host_ts) >= t0_us - 1e6
        assert max(host_ts) <= t1_us + 60e6
        # level 1 must NOT capture a device trace
        frame = c.io.Input([NamedVideoStream(c, "t", path=vid)])
        out = NamedStream(c, "p1b")
        jid1 = c.run(c.io.Output(c.ops.Histogram(frame=frame), [out]),
                     PerfParams.manual(8, 16, profiler_level=1),
                     cache_mode=CacheMode.Overwrite, show_progress=False)
        assert not [r for p in c.get_profile(jid1).profilers
                    for r in getattr(p, "device_traces", [])]
    finally:
        c.stop()
