"""Memory observability (util/memstats.py + its engine wiring).

Covers the allocation ledger (register/finalizer release, peaks, task/
trace attribution from the tracing context), the `memory.pressure`
fault site driving the full OOM-forensics + transient-requeue path on
an in-process CPU cluster (bit-exact output, report naming the top
ledger entry with its owning task and trace id), the /statusz Memory
panel, scanner_top --json, the leak-guard fixture, historical-bulk
retention/compaction, and the JSON structured-log format.
"""

import gc
import json
import logging
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
from scanner_tpu.common import DeviceOutOfMemory
from scanner_tpu.engine.batch import ColumnBatch
from scanner_tpu.util import faults
from scanner_tpu.util import memstats
from scanner_tpu.util import metrics as _mx
from scanner_tpu.util import tracing as _tr

N_FRAMES = 24


def _counter(name: str, **labels) -> float:
    entry = _mx.registry().snapshot().get(name, {})
    return sum(s["value"] for s in entry.get("samples", [])
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(autouse=True, scope="module")
def _empty_frame_cache():
    """The frame cache is a process singleton, and a worker runs several
    test files: pages that an earlier file's jobs left resident (tens of
    KB each) would crowd this file's own entries out of the ledger's
    top entries.  Their ledger entries go when the pages are collected."""
    from scanner_tpu.engine import framecache
    framecache.cache().clear()
    gc.collect()


# ---------------------------------------------------------------------------
# ledger units
# ---------------------------------------------------------------------------

def test_ledger_register_release_and_peaks():
    base_live = memstats.live_bytes(device="unit:0")
    assert base_live == 0
    e1 = memstats.register(1000, "unit:0", "staging", task="0,1",
                           trace_id="t1")
    e2 = memstats.register(500, "unit:0", "sink")
    assert memstats.live_bytes(device="unit:0") == 1500
    assert memstats.live_bytes(device="unit:0", kind="staging") == 1000
    assert memstats.watermark_bytes(device="unit:0") == 1500
    top = [e for e in memstats.top_entries(5)
           if e["device"] == "unit:0"]
    assert top[0]["bytes"] == 1000 and top[0]["task"] == "0,1" \
        and top[0]["trace_id"] == "t1"
    memstats.release(e1)
    memstats.release(e1)  # double release is idempotent
    memstats.release(e2)
    assert memstats.live_bytes(device="unit:0") == 0
    # the watermark survives release: peak HBM is the point
    assert memstats.watermark_bytes(device="unit:0") == 1500
    summary = {(s["device"], s["kind"]): s
               for s in memstats.ledger_summary()}
    assert summary[("unit:0", "staging")]["peak_bytes"] == 1000
    assert summary[("unit:0", "staging")]["live_bytes"] == 0


def test_track_array_releases_on_collection():
    a = np.zeros((10, 10), np.float32)
    eid = memstats.track_array(a, "staging", device="unit:gc")
    assert eid is not None
    assert memstats.live_bytes(device="unit:gc") == 400
    del a
    gc.collect()
    assert memstats.live_bytes(device="unit:gc") == 0
    assert memstats.watermark_bytes(device="unit:gc") == 400
    # a raw /metrics scrape alone balances the counters: the live-gauge
    # sampler flushes the finalizer-deferred release counts, so
    # allocs - releases = live entries holds on an otherwise-idle
    # process (the documented leak diagnostic)
    snap = _mx.registry().snapshot()

    def val(name):
        return sum(s["value"] for s in snap.get(name, {})["samples"]
                   if s["labels"].get("device") == "unit:gc")

    assert val("scanner_tpu_ledger_allocs_total") == 1
    assert val("scanner_tpu_ledger_releases_total") == 1
    assert val("scanner_tpu_ledger_live_bytes") == 0


def test_to_device_registers_staging_with_owner():
    """The staging hot path: to_device registers the batch against the
    active task span's (job, task) and trace id, and the entry releases
    when the staged batch is collected."""
    tracer = _tr.default_tracer()
    with _tr.start_span(tracer, "task", job=4, task=7) as span:
        staged = ColumnBatch(
            np.arange(4), np.zeros((4, 8, 8, 3), np.uint8)).to_device()
        mine = [e for e in memstats.entries()
                if e["trace_id"] == span.trace_id]
        assert len(mine) == 1
        assert mine[0]["kind"] == "staging"
        assert mine[0]["bytes"] == 4 * 8 * 8 * 3
        assert mine[0]["task"] == "4,7"
        trace_id = span.trace_id
    del staged
    gc.collect()
    assert not [e for e in memstats.entries()
                if e["trace_id"] == trace_id]


def test_device_stats_gracefully_absent_on_cpu():
    # the CPU backend reports no memory_stats: the HBM view is empty,
    # never an error — and the status dict still renders
    assert memstats.device_memory_stats() == {}
    st = memstats.status_dict()
    assert st["enabled"] is True
    assert isinstance(st["ledger"], list)


def test_is_oom_classification():
    assert memstats.is_oom(DeviceOutOfMemory("x"))
    xla_like = type("XlaRuntimeError", (Exception,), {})
    assert memstats.is_oom(
        xla_like("RESOURCE_EXHAUSTED: Out of memory allocating 1GB"))
    assert not memstats.is_oom(xla_like("INVALID_ARGUMENT: shape"))
    assert not memstats.is_oom(ValueError("RESOURCE_EXHAUSTED"))
    from scanner_tpu.engine.service import _is_transient_failure
    assert _is_transient_failure(DeviceOutOfMemory("injected"))


def test_note_oom_builds_one_shot_report():
    pinned = np.zeros((100,), np.uint8)
    memstats.track_array(pinned, "staging", device="unit:oom")
    before = _counter("scanner_tpu_device_oom_events_total",
                      site="unit-test")
    report = memstats.note_oom(DeviceOutOfMemory("RESOURCE_EXHAUSTED"),
                               site="unit-test", detail="d")
    assert _counter("scanner_tpu_device_oom_events_total",
                    site="unit-test") == before + 1
    assert report["site"] == "unit-test"
    assert "DeviceOutOfMemory" in report["reason"]
    assert any(e["device"] == "unit:oom" for e in report["top_entries"])
    last = memstats.last_report()
    assert last is not None and last["seq"] == report["seq"]
    assert report["node"]  # stamped at the source, not by the shipper
    # the global claim-once cursor hands each report out exactly once
    got = memstats.take_unshipped_report()
    assert got is not None and got["seq"] == report["seq"]
    assert memstats.take_unshipped_report() is None
    del pinned
    gc.collect()


def test_memory_report_local_mode(tmp_path):
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        rep = sc.memory_report()
        assert "memory" in rep and "reports" in rep
        assert isinstance(rep["memory"]["ledger"], list)
    finally:
        sc.stop()


# ---------------------------------------------------------------------------
# the full OOM-forensics path on an in-process cluster
# ---------------------------------------------------------------------------

@pytest.fixture()
def mem_cluster(tmp_path, monkeypatch):
    """Master (with /metrics+/statusz) + 1 worker + client over an
    ingested video, with device staging forced on the virtual
    multi-device CPU host so the ledger paths actually run."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    from scanner_tpu import video as scv
    from scanner_tpu.engine.service import Master, Worker

    db_path = str(tmp_path / "db")
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=12)
    seed = Client(db_path=db_path)
    seed.ingest_videos([("mvid", vid)])
    master = Master(db_path=db_path, no_workers_timeout=10.0,
                    metrics_port=0)
    addr = f"localhost:{master.port}"
    worker = Worker(addr, db_path=db_path, pipeline_instances=2)
    sc = Client(db_path=db_path, master=addr)
    yield sc, master, worker, addr
    faults.clear()
    sc.stop()
    worker.stop()
    master.stop()


def _run_histogram(sc, out_name: str):
    import scanner_tpu.kernels  # noqa: F401  (registers Histogram)
    frame = sc.io.Input([NamedVideoStream(sc, "mvid")])
    h = sc.ops.Histogram(frame=frame)
    out = NamedStream(sc, out_name)
    job_id = sc.run(sc.io.Output(h, [out]), PerfParams.manual(4, 8),
                    cache_mode=CacheMode.Overwrite, show_progress=False)
    return job_id, list(out.load())


@pytest.mark.chaos
def test_memory_pressure_requeues_bit_exact_with_report(mem_cluster):
    """The acceptance path: induced memory pressure (memory.pressure on
    CPU) -> one-shot memory report naming the top ledger entry with its
    task and trace id -> strike-free transient requeue -> bit-exact
    completion; /statusz carries the Memory panel and the post-bulk
    straggler/trace queries still answer."""
    sc, master, worker, addr = mem_cluster

    # clean reference run (faults disarmed)
    _job0, expect = _run_histogram(sc, "mem_clean")
    assert expect

    # a pinned co-scheduled buffer: the deterministic "who holds the
    # HBM" answer the OOM report must name (bigger than any task batch)
    tracer = _tr.default_tracer()
    with _tr.start_span(tracer, "task", job=99, task=0) as pin_span:
        pinned = ColumnBatch(
            np.arange(64),
            np.zeros((64, 64, 48, 3), np.uint8)).to_device()
        pin_trace = pin_span.trace_id

    transient_before = _counter("scanner_tpu_transient_retries_total")
    oom_before = _counter("scanner_tpu_device_oom_events_total",
                          site="staging")
    faults.install(faults.NAMED_PLANS["memory-pressure"])
    job_id, got = _run_histogram(sc, "mem_faulted")
    fired = faults.fired("memory.pressure")
    faults.clear()

    # the fault FIRED exactly once, and the output is bit-exact anyway
    assert fired == 1
    assert _counter("scanner_tpu_faults_injected_total",
                    site="memory.pressure", mode="raise") >= 1
    assert len(got) == len(expect)
    assert all(np.array_equal(a, b) for a, b in zip(got, expect))
    # strike-free transient requeue (PR 3 machinery), not a blacklist
    assert _counter("scanner_tpu_transient_retries_total") \
        >= transient_before + 1
    assert _counter("scanner_tpu_device_oom_events_total",
                    site="staging") == oom_before + 1

    # the memory report reached the master and names the pinned entry
    # with its owning task and trace id
    rep = sc.memory_report()
    assert rep["reports"], rep
    # reports accumulate newest-last (earlier tests may have left one)
    r = next(r for r in reversed(rep["reports"])
             if r.get("site") == "staging")
    assert "DeviceOutOfMemory" in r["reason"]
    top = r["top_entries"][0]
    assert top["task"] == "99,0"
    assert top["trace_id"] == pin_trace
    assert top["bytes"] == 64 * 64 * 48 * 3
    assert r["recent_spans"], "flight-recorder tail missing"

    # /statusz Memory panel (master role)
    port = master.metrics_server.port
    st = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statusz", timeout=10).read())
    assert st["memory"]["oom_events"] >= 1
    assert isinstance(st["memory"]["ledger"], list)
    assert st["memory"]["last_oom"]["site"] == "staging"
    assert st["memory"]["worker_reports"] >= 1

    # ledger + HBM series exist on /metrics (device-labeled ledger
    # samples from the staged columns; HBM absent on CPU by design)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert "scanner_tpu_ledger_allocs_total" in text
    assert 'kind="staging"' in text

    # retention: the finished bulk still answers straggler/trace pulls
    stragglers = sc.stragglers(job_id)
    assert stragglers["per_stage"].get("task", {}).get("count", 0) > 0
    trace = sc._cluster.get_trace(sc._cluster.last_bulk_id)
    assert trace["spans"], "span store vanished at bulk completion"

    del pinned
    gc.collect()


@pytest.mark.chaos
def test_scanner_top_json_smoke(mem_cluster):
    """scanner_top --json against a live master: exit 0, parseable
    JSON mirroring --once (status + per-node counters + per-device
    utilization/memory maps) — scripts stop scraping the human table."""
    sc, _master, _worker, addr = mem_cluster
    _run_histogram(sc, "top_json_out")

    from scanner_tpu.util.jaxenv import cpu_only_env
    env = cpu_only_env()
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + \
        env.get("PYTHONPATH", "")
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "scanner_top.py")
    r = subprocess.run(
        [sys.executable, tool, "--master", addr, "--json"],
        env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["master"] == addr
    assert doc["status"]["tasks_done"] == doc["status"]["total_tasks"]
    workers = [n for n in doc["nodes"] if n.startswith("worker")]
    assert workers, doc["nodes"]
    wn = doc["nodes"][workers[0]]
    for key in ("decoded_frames", "eval_rows", "h2d_bytes",
                "eval_queue", "devices"):
        assert key in wn
    # per-device map carries the memory columns (ledger staged on the
    # virtual chips; HBM keys present, zero-valued on CPU)
    assert wn["devices"], wn
    dev = next(iter(wn["devices"].values()))
    assert set(dev) >= {"tasks", "busy_seconds", "hbm_bytes_in_use",
                        "hbm_limit_bytes", "ledger_live_bytes"}

    # the human table grew the memory columns too
    r2 = subprocess.run(
        [sys.executable, tool, "--master", addr, "--once"],
        env=env, capture_output=True, text=True, timeout=180)
    assert r2.returncode == 0, r2.stderr
    assert "HBM MB" in r2.stdout and "LEDG MB" in r2.stdout


def test_local_pipeline_leaves_no_ledger_leaks(tmp_path, monkeypatch,
                                               ledger_leak_guard):
    """The opt-in leak guard over a real local pipeline with device
    staging forced: every buffer the engine registered during the run
    must be released once results are consumed."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    from scanner_tpu import video as scv
    import scanner_tpu.kernels  # noqa: F401

    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=12)
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        sc.ingest_videos([("leak_vid", vid)])
        frame = sc.io.Input([NamedVideoStream(sc, "leak_vid")])
        h = sc.ops.Histogram(frame=frame)
        out = NamedStream(sc, "leak_out")
        sc.run(sc.io.Output(h, [out]), PerfParams.manual(4, 8),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        rows = list(out.load())
        assert len(rows) == N_FRAMES
        # staging actually happened — the guard must not pass vacuously
        assert _counter("scanner_tpu_ledger_allocs_total") > 0
    finally:
        sc.stop()


# ---------------------------------------------------------------------------
# retention / compaction (satellite: last-N-bulks ring)
# ---------------------------------------------------------------------------

def test_history_compaction_keeps_stragglers_and_status(tmp_path):
    """Bulks aging out of the SPAN_HISTORY_BULKS ring drop their span
    stores and per-task scheduling state but keep straggler aggregates
    and a frozen status — GetJobStatus/GetTrace answer for the whole
    history, degrading (spans only) past the ring."""
    from scanner_tpu.engine.service import (SPAN_HISTORY_BULKS, Master,
                                            _BulkJob)

    master = Master(db_path=str(tmp_path / "db"), no_workers_timeout=5.0)
    try:
        n = SPAN_HISTORY_BULKS + 2
        for i in range(n):
            b = _BulkJob(bulk_id=i, spec_blob=b"", task_timeout=0.0,
                         trace_id=f"{i:032x}")
            b.job_tasks[0] = {(0, 0), (0, 1)}
            b.task_rows = {(0, 0): 8, (0, 1): 8}
            b.total_tasks = 2
            b.done = {(0, 0), (0, 1)}
            b.job_done[0] = 2
            b.stage_rows = {"load": 16, "evaluate": 16, "save": 16}
            for t in range(2):
                master._absorb_span_locked(b, {
                    "name": "task", "trace_id": b.trace_id,
                    "span_id": f"{t:016x}", "parent_id": None,
                    "start": 1.0, "end": 2.0 + t, "node": "worker0",
                    "attrs": {"job": 0, "task": t}})
            b.mark_finished()
            with master._lock:
                master._history[i] = b
        with master._lock:
            master._trim_history_locked()
            old = master._history[0]
            recent = master._history[n - 1]
        assert old.compacted and old.spans == [] and old.done == set()
        assert not recent.compacted and len(recent.spans) == 2

        # frozen status still serves, with live worker liveness
        st = master._rpc_job_status({"bulk_id": 0})
        assert st["finished"] and st["tasks_done"] == 2 \
            and st["total_tasks"] == 2
        assert st["num_workers"] == 0
        # straggler aggregates survive compaction; the span store does
        # not (drops are counted, not silent)
        tr = master._rpc_get_trace({"bulk_id": 0})
        assert tr["spans"] == []
        assert tr["stragglers"]["per_stage"]["task"]["count"] == 2
        assert tr["stragglers"]["slowest_tasks"]
        # late-arriving spans for a compacted bulk count as drops but
        # still feed the retained aggregates
        with master._lock:
            master._absorb_span_locked(old, {
                "name": "task", "trace_id": old.trace_id,
                "span_id": "f" * 16, "parent_id": None,
                "start": 1.0, "end": 9.0, "node": "worker0",
                "attrs": {"job": 0, "task": 5}})
        tr2 = master._rpc_get_trace({"bulk_id": 0})
        assert tr2["spans"] == [] and tr2["spans_dropped"] >= 1
        assert tr2["stragglers"]["per_stage"]["task"]["count"] == 3
        # a bulk inside the ring keeps everything
        tr3 = master._rpc_get_trace({"bulk_id": n - 1})
        assert len(tr3["spans"]) == 2
    finally:
        master.stop()


# ---------------------------------------------------------------------------
# structured logging (satellite: SCANNER_TPU_LOG_FORMAT=json)
# ---------------------------------------------------------------------------

def test_json_log_format_carries_trace_context():
    from scanner_tpu.util.log import JsonFormatter

    fmt = JsonFormatter()
    rec = logging.LogRecord("scanner_tpu.worker", logging.WARNING,
                            __file__, 1, "task %d requeued", (7,), None)
    out = json.loads(fmt.format(rec))
    assert out["level"] == "WARNING"
    assert out["logger"] == "scanner_tpu.worker"
    assert out["msg"] == "task 7 requeued"
    assert "trace_id" not in out  # outside any span

    tracer = _tr.default_tracer()
    with _tr.start_span(tracer, "task", job=1, task=2) as span:
        out2 = json.loads(fmt.format(rec))
        assert out2["trace_id"] == span.trace_id
        assert out2["span_id"] == span.span_id

    # exceptions serialize into the object, still one line
    try:
        raise ValueError("boom")
    except ValueError:
        rec_exc = logging.LogRecord("scanner_tpu.engine", logging.ERROR,
                                    __file__, 1, "failed", (),
                                    sys.exc_info())
    out3 = json.loads(fmt.format(rec_exc))
    assert "ValueError: boom" in out3["exc"]
    # newlines in the traceback are escaped: still one object per line
    assert len(fmt.format(rec_exc).splitlines()) == 1


def test_json_log_format_env_selects_handler(monkeypatch):
    """SCANNER_TPU_LOG_FORMAT=json makes the default stderr handler a
    JsonFormatter (fresh-configuration path)."""
    import scanner_tpu.util.log as log_mod

    root = logging.getLogger("scanner_tpu")
    top = logging.getLogger()  # pytest hangs capture handlers here;
    saved_handlers = root.handlers[:]  # _configure_once treats any
    saved_top = top.handlers[:]        # root handler as "app-managed"
    saved_configured = log_mod._configured
    try:
        root.handlers = []
        top.handlers = []
        log_mod._configured = False
        monkeypatch.setenv("SCANNER_TPU_LOG_FORMAT", "json")
        log_mod.get_logger("probe")
        assert root.handlers, "handler not installed"
        assert isinstance(root.handlers[0].formatter,
                          log_mod.JsonFormatter)
    finally:
        root.handlers = saved_handlers
        top.handlers = saved_top
        log_mod._configured = saved_configured
