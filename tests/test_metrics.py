"""Live telemetry subsystem (util/metrics.py + endpoints + scanner-top).

Covers the registry primitives (concurrency, bucket edges, exposition
golden output), the series-name lint that keeps dashboards from drifting,
and the full serving path: /metrics + /healthz + /statusz against a live
in-process master, the master-aggregated Client.metrics() view, and the
scanner_top --once CLI.
"""

import json
import os
import re
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import pytest

from scanner_tpu.util.metrics import (DEFAULT_BUCKETS, MetricsError,
                                      MetricsRegistry, MetricsServer,
                                      merge_snapshots, registry,
                                      render_prometheus)

N_FRAMES = 24


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_counter_concurrency():
    """N threads hammering one counter (and one labeled child) lose no
    increments — the per-thread-cell fast path is race-free."""
    r = MetricsRegistry()
    c = r.counter("scanner_tpu_t_total", "t")
    lc = r.counter("scanner_tpu_tl_total", "t", labels=["k"])
    child = lc.labels(k="x")
    n_threads, per_thread = 8, 20000

    def hammer():
        for _ in range(per_thread):
            c.inc()
            child.inc(2)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c._default.value() == n_threads * per_thread
    assert child.value() == 2 * n_threads * per_thread


def test_counter_timing_accrues_as_it_elapses():
    """timing() makes a busy-seconds counter a true share: a reader
    mid-block sees the seconds so far, overlapping blocks count once,
    and the total never exceeds the wall time that passed."""
    import time
    r = MetricsRegistry()
    child = r.counter("scanner_tpu_t_busy_seconds_total", "t",
                      labels=["device"]).labels(device="tpu:0")
    t0 = time.monotonic()
    inner_done = threading.Event()

    def overlap():
        with child.timing():
            time.sleep(0.1)
        inner_done.set()

    with child.timing():
        th = threading.Thread(target=overlap)
        th.start()
        time.sleep(0.05)
        mid = child.value()
        assert 0.04 <= mid <= time.monotonic() - t0
        th.join()
    end = child.value()
    wall = time.monotonic() - t0
    assert inner_done.is_set()
    assert mid < end <= wall           # the union, not the 0.2 s sum
    time.sleep(0.02)
    assert child.value() == end        # closed: no longer accruing
    child.inc(1.5)                     # inc() still adds on top
    assert child.value() == pytest.approx(end + 1.5)


def test_counter_timing_concurrency():
    """More threads than cores opening and closing timing() blocks on
    one child under a shortened switch interval: no open is lost (the
    child ends closed and stops accruing) and the union never exceeds
    the wall time that passed."""
    import time
    r = MetricsRegistry()
    child = r.counter("scanner_tpu_t_busy2_seconds_total", "t")._default
    n_threads, per_thread = 4 * (os.cpu_count() or 4), 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    t0 = time.monotonic()
    try:
        def work():
            for _ in range(per_thread):
                with child.timing():
                    pass

        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    wall = time.monotonic() - t0
    end = child.value()
    assert child._open == 0
    assert 0 < end <= wall
    time.sleep(0.02)
    assert child.value() == end


def test_histogram_bucket_edges():
    """Prometheus buckets are upper-INCLUSIVE: v == le lands in that
    bucket; above the last upper lands in +Inf."""
    r = MetricsRegistry()
    h = r.histogram("scanner_tpu_t_seconds", "t", buckets=[0.1, 1.0, 5.0])
    for v in (0.1, 1.0, 5.0):     # exactly on the edges
        h.observe(v)
    h.observe(0.0999)             # below first
    h.observe(5.0001)             # above last -> +Inf
    s = h._default.value()
    assert s["buckets"] == [2, 1, 1, 1]
    assert s["count"] == 5
    assert abs(s["sum"] - (0.1 + 1.0 + 5.0 + 0.0999 + 5.0001)) < 1e-9


def test_histogram_concurrency():
    r = MetricsRegistry()
    h = r.histogram("scanner_tpu_t_seconds", "t", buckets=[1.0])

    def hammer():
        for i in range(5000):
            h.observe(0.5 if i % 2 else 2.0)

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = h._default.value()
    assert s["count"] == 30000
    assert s["buckets"] == [15000, 15000]


def test_dead_thread_cells_fold_into_retained_total():
    """Cells of finished threads fold into a retained total at read
    time: a worker spawning fresh stage threads per run leaks neither
    memory nor scrape cost, and no increments are lost."""
    r = MetricsRegistry()
    c = r.counter("scanner_tpu_t_total", "t")
    h = r.histogram("scanner_tpu_t_seconds", "t", buckets=[1.0])
    for _ in range(20):
        t = threading.Thread(target=lambda: (c.inc(5), h.observe(0.5)))
        t.start()
        t.join()
    assert c._default.value() == 100
    assert h._default.value()["count"] == 20
    # dead cells were folded away, not accumulated
    assert len(c._default._cells) == 0
    assert len(h._default._cells) == 0
    c.inc()  # the live (this) thread still counts
    assert c._default.value() == 101


def test_gauge_clear_function_respects_new_owner():
    """A finished pipeline may only detach the queue-depth sampler it
    installed itself — not a newer owner's."""
    r = MetricsRegistry()
    g = r.gauge("scanner_tpu_t_depth", "t")
    mine, theirs = (lambda: 1), (lambda: 2)
    g.set_function(mine)
    g.set_function(theirs)          # a newer pipeline re-binds
    assert g.clear_function(mine) is False
    assert g._default.value() == 2  # still the new owner's sampler
    assert g.clear_function(theirs) is True
    assert g._default.value() == 0.0


def test_remove_labels_drops_child_series():
    """Departed label values (e.g. dead worker ids) can be pruned so a
    long-lived master's scrape output doesn't grow without bound."""
    r = MetricsRegistry()
    g = r.gauge("scanner_tpu_t_age", "t", labels=["worker"])
    g.labels(worker="0").set(1)
    g.labels(worker="1").set(2)
    g.remove_labels(worker="0")
    labels = [s["labels"] for s in
              r.snapshot()["scanner_tpu_t_age"]["samples"]]
    assert labels == [{"worker": "1"}]
    with pytest.raises(MetricsError):
        g.remove_labels(nope="0")


def test_gauge_set_function_and_fallback():
    r = MetricsRegistry()
    g = r.gauge("scanner_tpu_t_depth", "t")
    g.set(3)
    assert g._default.value() == 3
    g.set_function(lambda: 7)
    assert g._default.value() == 7
    g.set_function(lambda: 1 / 0)   # a scrape bug must not raise
    assert g._default.value() == 0.0
    g.set_function(None)
    assert g._default.value() == 3


def test_registry_idempotent_and_mismatch():
    r = MetricsRegistry()
    a = r.counter("scanner_tpu_t_total", "t")
    assert r.counter("scanner_tpu_t_total", "t") is a
    with pytest.raises(MetricsError):
        r.gauge("scanner_tpu_t_total", "t")          # kind mismatch
    with pytest.raises(MetricsError):
        r.counter("scanner_tpu_t_total", "t", labels=["x"])  # labels
    with pytest.raises(MetricsError):
        r.counter("Bad-Name", "t")                   # name pattern
    with pytest.raises(MetricsError):
        r.counter("scanner_tpu_nohelp_total", "  ")  # empty help


def test_prometheus_exposition_golden():
    """Exact text-exposition output: HELP/TYPE lines, label escaping,
    cumulative histogram buckets, _sum/_count."""
    r = MetricsRegistry()
    c = r.counter("scanner_tpu_g_total", "Counter help.", labels=["op"])
    c.labels(op='He said "hi"\n').inc(3)
    g = r.gauge("scanner_tpu_g_depth", "Gauge help.")
    g.set(2.5)
    h = r.histogram("scanner_tpu_g_seconds", "Hist help.",
                    buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    assert render_prometheus(r.snapshot()) == (
        "# HELP scanner_tpu_g_depth Gauge help.\n"
        "# TYPE scanner_tpu_g_depth gauge\n"
        "scanner_tpu_g_depth 2.5\n"
        "# HELP scanner_tpu_g_seconds Hist help.\n"
        "# TYPE scanner_tpu_g_seconds histogram\n"
        'scanner_tpu_g_seconds_bucket{le="0.1"} 1\n'
        'scanner_tpu_g_seconds_bucket{le="1"} 2\n'
        'scanner_tpu_g_seconds_bucket{le="+Inf"} 3\n'
        "scanner_tpu_g_seconds_sum 2.55\n"
        "scanner_tpu_g_seconds_count 3\n"
        "# HELP scanner_tpu_g_total Counter help.\n"
        "# TYPE scanner_tpu_g_total counter\n"
        'scanner_tpu_g_total{op="He said \\"hi\\"\\n"} 3\n')


def test_merge_snapshots_adds_node_labels():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("scanner_tpu_t_total", "t").inc(1)
    r2.counter("scanner_tpu_t_total", "t").inc(5)
    merged = merge_snapshots({"master": r1.snapshot(),
                              "worker0": r2.snapshot()})
    samples = merged["scanner_tpu_t_total"]["samples"]
    by_node = {s["labels"]["node"]: s["value"] for s in samples}
    assert by_node == {"master": 1, "worker0": 5}


# ---------------------------------------------------------------------------
# series-name lint: dashboards break silently on metric-name drift
# ---------------------------------------------------------------------------

def test_registered_series_names_lint():
    """The naming/help/catalog contract now lives in scanner-check's
    contract pass (SC301/SC302, scanner_tpu/analysis/static/) — one
    source of truth, also enforced by the tier-1 gate in
    tests/test_static_analysis.py.  This thin wrapper runs just those
    codes over the package, then keeps the RUNTIME half the static pass
    cannot see: that the series dashboards depend on really register at
    import."""
    from scanner_tpu.analysis.static import run_analysis

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = run_analysis([os.path.join(repo, "scanner_tpu")],
                            root=repo, select=["SC301", "SC302"])
    assert not findings, "metric contract violations:\n" + "\n".join(
        f.format() for f in findings)

    # pull in every instrumented module so their module-level metrics
    # are registered
    import scanner_tpu.engine.batch       # noqa: F401
    import scanner_tpu.engine.evaluate    # noqa: F401
    import scanner_tpu.engine.executor    # noqa: F401
    import scanner_tpu.engine.rpc         # noqa: F401
    import scanner_tpu.engine.service     # noqa: F401
    import scanner_tpu.storage.gcs        # noqa: F401
    import scanner_tpu.storage.items      # noqa: F401
    import scanner_tpu.util.faults        # noqa: F401
    import scanner_tpu.util.profiler      # noqa: F401
    import scanner_tpu.util.retry         # noqa: F401

    metrics = registry().metrics()
    assert len(metrics) >= 20, [m.name for m in metrics]
    # the shape-stability series (docs/observability.md catalog) must
    # exist: padding waste and ladder-precompile time ride alongside the
    # recompile proxy
    names = {m.name for m in metrics}
    assert {"scanner_tpu_op_recompiles_total",
            "scanner_tpu_op_pad_rows_total",
            "scanner_tpu_op_precompile_seconds"} <= names
    # the robustness series (docs/robustness.md): chaos-fire evidence,
    # crc-detected corruption, strike-free transient requeues, drains
    assert {"scanner_tpu_faults_injected_total",
            "scanner_tpu_item_corruptions_total",
            "scanner_tpu_transient_retries_total",
            "scanner_tpu_worker_drains_total"} <= names


# ---------------------------------------------------------------------------
# endpoints against a live in-process cluster
# ---------------------------------------------------------------------------

@pytest.fixture()
def metrics_cluster(tmp_path):
    """Master (with /metrics enabled) + 1 worker + client, plus an
    ingested test video."""
    from scanner_tpu import Client
    from scanner_tpu import video as scv
    from scanner_tpu.engine.service import Master, Worker

    db_path = str(tmp_path / "db")
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=12)
    seed = Client(db_path=db_path)
    seed.ingest_videos([("test1", vid)])
    master = Master(db_path=db_path, no_workers_timeout=10.0,
                    metrics_port=0)
    addr = f"localhost:{master.port}"
    worker = Worker(addr, db_path=db_path)
    sc = Client(db_path=db_path, master=addr)
    yield sc, master, worker, addr
    sc.stop()
    worker.stop()
    master.stop()


def _run_histogram(sc, out_name: str) -> None:
    from scanner_tpu import CacheMode, NamedStream, NamedVideoStream, \
        PerfParams
    import scanner_tpu.kernels  # noqa: F401  (registers Histogram)
    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    h = sc.ops.Histogram(frame=frame)
    out = NamedStream(sc, out_name)
    sc.run(sc.io.Output(h, [out]), PerfParams.manual(4, 8),
           cache_mode=CacheMode.Overwrite, show_progress=False)


def test_metrics_endpoint_end_to_end(metrics_cluster):
    """After a bulk job: GET /metrics returns valid Prometheus text with
    >= 20 distinct scanner_tpu_* series, /healthz and /statusz answer,
    and Client.metrics() returns the master-aggregated cluster view
    including a worker's series."""
    sc, master, worker, _addr = metrics_cluster
    _run_histogram(sc, "mx_out")

    port = master.metrics_server.port
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    # sample lines only (skip # HELP/# TYPE); a series = name+labels
    series = {line.split(" ")[0] for line in text.splitlines()
              if line.startswith("scanner_tpu_")}
    assert len(series) >= 20, sorted(series)
    families = {s.split("{")[0] for s in series}
    # the headline catalog is present
    for fam in ("scanner_tpu_stage_queue_depth",
                "scanner_tpu_stage_seconds_total",
                "scanner_tpu_decoded_frames_total",
                "scanner_tpu_h2d_bytes_total",
                "scanner_tpu_master_workers_active",
                "scanner_tpu_master_tasks_completed_total",
                "scanner_tpu_rpc_latency_seconds_bucket",
                "scanner_tpu_op_rows_total"):
        assert fam in families, f"{fam} missing from /metrics"

    hz = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10).read())
    assert hz["ok"] is True and hz["role"] == "master"

    st = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statusz", timeout=10).read())
    assert st["role"] == "master"
    assert st["bulk"]["tasks_done"] == st["bulk"]["total_tasks"]
    assert set(st["bulk"]["stage_fps"]) == {"load", "evaluate", "save"}
    assert any(w["active"] for w in st["workers"])

    # 404 path
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=10)

    # cluster-wide merged view over the GetMetrics RPC
    snap = sc.metrics()
    nodes = {s["labels"].get("node")
             for e in snap.values() for s in e["samples"]}
    assert "master" in nodes
    assert any(n and n.startswith("worker") for n in nodes), nodes
    assert "scanner_tpu_decoded_frames_total" in snap
    # the merged view renders as valid exposition too
    assert "scanner_tpu_master_workers_active" in render_prometheus(snap)


def test_metrics_server_off_by_default(tmp_path):
    """No metrics_port -> no listener anywhere (the acceptance default:
    telemetry serving must be strictly opt-in)."""
    from scanner_tpu import Client
    from scanner_tpu.engine.service import Master, Worker

    master = Master(db_path=str(tmp_path / "db"), no_workers_timeout=5.0)
    worker = Worker(f"localhost:{master.port}",
                    db_path=str(tmp_path / "db"))
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        assert master.metrics_server is None
        assert worker.metrics_server is None
        assert sc._metrics_server is None
    finally:
        sc.stop()
        worker.stop()
        master.stop()


def test_client_local_metrics_and_endpoint(tmp_path):
    """Local (in-process) mode: Client(metrics_port=0) serves its own
    registry and Client.metrics() returns the node-labeled snapshot."""
    from scanner_tpu import Client

    sc = Client(db_path=str(tmp_path / "db"), metrics_port=0)
    try:
        port = sc._metrics_server.port
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "scanner_tpu_process_start_time_seconds" in text
        snap = sc.metrics()
        nodes = {s["labels"].get("node")
                 for e in snap.values() for s in e["samples"]}
        assert nodes == {"client"}
    finally:
        sc.stop()


def test_scanner_top_once_smoke(metrics_cluster):
    """scanner_top --once against a live master: exits 0 and renders the
    job line + per-node table."""
    sc, _master, _worker, addr = metrics_cluster
    _run_histogram(sc, "top_out")

    from scanner_tpu.util.jaxenv import cpu_only_env
    env = cpu_only_env()
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "scanner_top.py")
    r = subprocess.run(
        [sys.executable, tool, "--master", addr, "--once"],
        env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr
    assert "NODE" in r.stdout
    assert "bulk:" in r.stdout
    assert re.search(r"worker\d", r.stdout), r.stdout

    # unreachable master -> exit code 2, not a hang or traceback
    r2 = subprocess.run(
        [sys.executable, tool, "--master", "localhost:1", "--once"],
        env=env, capture_output=True, text=True, timeout=180)
    assert r2.returncode == 2


def test_profiler_counters_mirror_into_metrics():
    """Profiler.count events appear in the live registry under
    scanner_tpu_profiler_events_total{event=...} — traces and live
    metrics cannot disagree on counts."""
    from scanner_tpu.util.profiler import Profiler

    before = _profiler_event_value("mirror_probe")
    p = Profiler()
    p.count("mirror_probe", 3)
    assert _profiler_event_value("mirror_probe") == before + 3
    assert p.counters["mirror_probe"] == 3


def _profiler_event_value(event: str) -> float:
    snap = registry().snapshot()
    entry = snap.get("scanner_tpu_profiler_events_total", {"samples": []})
    return sum(s["value"] for s in entry["samples"]
               if s["labels"].get("event") == event)


def test_retry_metrics_and_giveup_warning(caplog):
    """util/retry.py routes attempts through the registry and logs the
    final give-up at WARNING with the accumulated wait."""
    import logging

    from scanner_tpu.util.retry import call_with_backoff

    def site_value():
        snap = registry().snapshot()
        entry = snap.get("scanner_tpu_retry_attempts_total",
                         {"samples": []})
        return sum(s["value"] for s in entry["samples"]
                   if s["labels"].get("site") == "unit_test")

    before = site_value()
    sleeps = []
    with caplog.at_level(logging.WARNING, logger="scanner_tpu"):
        with pytest.raises(ConnectionError):
            call_with_backoff(
                _always_fail, is_transient=lambda e: True, retries=3,
                base=0.001, cap=0.002, sleep=sleeps.append,
                label="unit_test")
    assert site_value() == before + 3
    assert len(sleeps) == 3
    assert "giving up" in caplog.text
    assert "unit_test" in caplog.text
    assert "accumulated" in caplog.text

    # retries=0 callers (e.g. wait_for_server poll loops) stay quiet
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="scanner_tpu"):
        with pytest.raises(ConnectionError):
            call_with_backoff(_always_fail, is_transient=lambda e: True,
                              retries=0, label="unit_test")
    assert "giving up" not in caplog.text


def _always_fail():
    raise ConnectionError("nope")
