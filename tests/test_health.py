"""Health & SLO engine suite (scanner_tpu/util/health.py).

Three layers:
  * units — the histogram-quantile estimator, the [alerts] rule clause
    grammar, and every rule form (threshold, rate, quantile,
    multi-window burn, ratio, composite backpressure) driven over a
    private registry with synthetic clocks, so firing/hold-down/resolve
    transitions are deterministic;
  * the serving surface — /healthz roll-up shape + status codes,
    /readyz drain behavior, /alertz;
  * chaos-style e2e (the acceptance test) — an injected pipeline.save
    delay on an in-process cluster fires `stage_backpressure` (visible
    via Client.health(), /alertz and the transitions counter) and
    resolves, while the identical fault-free run stays `ok` with zero
    alerts; heartbeat loss degrades the master's /healthz.
"""

import json
import struct
import sys
import threading
import time
import urllib.error
import urllib.request

import cloudpickle
import pytest

from scanner_tpu import (CacheMode, Client, Kernel, NamedStream,
                         PerfParams, register_op)
from scanner_tpu.engine.service import Master, Worker
from scanner_tpu.util import faults
from scanner_tpu.util import health
from scanner_tpu.util import metrics as _mx
from scanner_tpu.util.metrics import (MetricsRegistry, MetricsServer,
                                      histogram_quantile,
                                      snapshot_histogram_quantiles)

# test kernels travel to worker subprocesses inside the job spec
cloudpickle.register_pickle_by_value(sys.modules[__name__])

N_ROWS = 48


def _pk(v: int) -> bytes:
    return struct.pack("<q", v)


@register_op(name="HealthDouble")
class HealthDouble(Kernel):
    def execute(self, x: bytes) -> bytes:
        return _pk(2 * struct.unpack("<q", x)[0])


EXPECT = [_pk(2 * (100 + i)) for i in range(N_ROWS)]


def _counter(name: str, **labels) -> float:
    entry = _mx.registry().snapshot().get(name, {})
    for s in entry.get("samples", []):
        if s["labels"] == labels:
            return s["value"]
    return 0.0


def _get_json(url: str):
    """(status_code, parsed body) — a 503 is an answer, not an error."""
    try:
        r = urllib.request.urlopen(url, timeout=10)
        return r.getcode(), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# histogram quantile estimation (util/metrics.py — shared helper)
# ---------------------------------------------------------------------------

def test_histogram_quantile_interpolates_within_bucket():
    # 10 observations all inside (1, 2]: p50 lands mid-bucket
    assert histogram_quantile([1, 2, 4], [0, 10, 0, 0], 0.5) == 1.5
    # spread across buckets: p75 of 4+4 obs -> inside the second bucket
    v = histogram_quantile([1, 2], [4, 4, 0], 0.75)
    assert 1.0 < v <= 2.0
    assert v == pytest.approx(1.5)


def test_histogram_quantile_edge_buckets():
    # everything in the FIRST bucket: interpolates from edge 0
    assert histogram_quantile([2, 4], [8, 0, 0], 0.5) == \
        pytest.approx(1.0)
    # everything in the +Inf bucket clamps to the top finite bound
    assert histogram_quantile([1, 2, 4], [0, 0, 0, 5], 0.99) == 4.0
    # q=1.0 stays within the last occupied bucket
    assert histogram_quantile([1, 2], [0, 6, 0], 1.0) == 2.0


def test_histogram_quantile_empty_histogram():
    assert histogram_quantile([1, 2], [0, 0, 0], 0.5) is None
    assert histogram_quantile([], [], 0.5) is None


def test_snapshot_histogram_quantiles_shapes():
    reg = MetricsRegistry()
    h = reg.histogram("scanner_tpu_t_lat_seconds", "x", buckets=(1, 5))
    assert snapshot_histogram_quantiles(reg.snapshot(),
                                        "scanner_tpu_t_lat_seconds") == {}
    assert snapshot_histogram_quantiles(reg.snapshot(), "nosuch") == {}
    for v in (0.2, 0.4, 0.6, 2.0):
        h.observe(v)
    out = snapshot_histogram_quantiles(reg.snapshot(),
                                       "scanner_tpu_t_lat_seconds",
                                       qs=(0.5, 0.99))
    assert out["count"] == 4
    assert out["mean_s"] == pytest.approx(0.8)
    assert 0 < out["p50_s"] <= 1.0
    assert 1.0 < out["p99_s"] <= 5.0


# ---------------------------------------------------------------------------
# rule grammar
# ---------------------------------------------------------------------------

def test_parse_rules_grammar():
    rules = health.parse_rules(
        "evalq:value(scanner_tpu_stage_queue_depth{stage=evaluate})>=8"
        ":for=5:resolve=7.5:severity=critical;"
        "slow_rpc:p99(scanner_tpu_rpc_latency_seconds)>0.5:window=120;"
        "hbm:value(scanner_tpu_device_hbm_bytes_in_use"
        "/scanner_tpu_device_hbm_limit_bytes)>0.9:by=device;"
        "req_slo:burn(scanner_tpu_task_latency_seconds)>2"
        ":objective=5:budget=0.01:short=30:window=300")
    assert [r.name for r in rules] == ["evalq", "slow_rpc", "hbm",
                                      "req_slo"]
    assert rules[0].match == {"stage": "evaluate"}
    assert rules[0].for_seconds == 5 and rules[0].severity == "critical"
    assert rules[0].resolve_seconds == 7.5 and rules[1].resolve_seconds == 0
    assert rules[1].form == "p99" and rules[1].window == 120
    assert rules[2].ratio_to == "scanner_tpu_device_hbm_limit_bytes"
    assert rules[2].by == ("device",)
    assert rules[3].objective == 5 and rules[3].budget == 0.01
    assert rules[3].short_window == 30 and rules[3].window == 300
    assert health.parse_rules("") == []
    for bad in (
            "noexpr",                                      # no clause
            "r:exp!ode(scanner_tpu_x)>1",                  # bad form
            "r:value(not_a_series)>1",                     # bad series
            "r:value(scanner_tpu_x)>1:zz=3",               # unknown opt
            "r:value(scanner_tpu_x)>1:severity=panic",     # bad severity
            "r:value(scanner_tpu_x)>1:window=soon",        # bad number
            "BAD NAME:value(scanner_tpu_x)>1"):            # bad name
        with pytest.raises(health.HealthConfigError):
            health.parse_rules(bad)


def test_default_rules_are_valid_and_quiet_on_empty_registry():
    names = [r.name for r in health.DEFAULT_RULES]
    assert len(names) == len(set(names))
    for r in health.DEFAULT_RULES:
        r.validate()
    eng = health.HealthEngine(reg=MetricsRegistry(),
                              rules=health.default_rules(), interval=0.1)
    assert eng.tick(100.0) == []
    assert eng.tick(105.0) == []
    st = eng.status_dict()
    assert st["status"] == "ok" and st["firing"] == []


# ---------------------------------------------------------------------------
# rule forms (private registry, synthetic clock)
# ---------------------------------------------------------------------------

def test_threshold_hold_down_fire_and_resolve():
    reg = MetricsRegistry()
    g = reg.gauge("scanner_tpu_t_depth", "x", labels=["stage"])
    rule = health.AlertRule(
        name="t_hold", series="scanner_tpu_t_depth", form="value",
        op=">=", value=3, by=("stage",), for_seconds=2.0,
        severity="critical")
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    g.labels(stage="save").set(5)
    assert eng.tick(100.0) == []               # pending, not fired yet
    assert eng.status_dict()["status"] == "ok"
    assert eng.tick(101.0) == []               # still inside hold-down
    trans = eng.tick(102.5)                    # 2.5s >= for
    assert [t["state"] for t in trans] == ["firing"]
    assert trans[0]["labels"] == {"stage": "save"}
    st = eng.status_dict()
    assert st["status"] == "unhealthy"         # critical severity
    assert st["reasons"] == ["t_hold[stage=save]"]
    # transitions counter + firing gauge went live
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="t_hold", state="firing") == 1
    assert _counter("scanner_tpu_alerts_firing",
                    rule="t_hold", severity="critical") == 1
    g.labels(stage="save").set(1)
    trans = eng.tick(103.0)
    assert [t["state"] for t in trans] == ["resolved"]
    assert eng.status_dict()["status"] == "ok"
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="t_hold", state="resolved") == 1
    assert _counter("scanner_tpu_alerts_firing",
                    rule="t_hold", severity="critical") == 0
    # a dip below for_seconds never fires
    g.labels(stage="save").set(5)
    assert eng.tick(104.0) == []
    g.labels(stage="save").set(0)
    assert eng.tick(105.0) == []


@pytest.mark.parametrize("lapse", ["false", "gone"])
def test_a_firing_alert_stands_through_a_lapse_shorter_than_resolve(lapse):
    """Back-to-back runs behind one slow stage: each starts with an
    empty queue (the depth reads 0, or the run's sampler is gone), and
    the alert that fired in the first stands through those seconds
    instead of firing once a run; it resolves once the condition has
    stayed false `resolve_seconds`, counted from the last tick it held."""
    reg = MetricsRegistry()
    g = reg.gauge("scanner_tpu_t_qd", "x", labels=["stage"])
    rule = health.AlertRule(
        name=f"t_stand_{lapse}", series="scanner_tpu_t_qd", form="value",
        op=">=", value=3, by=("stage",), for_seconds=1.5,
        resolve_seconds=5.0)
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)

    def drop():
        if lapse == "false":
            g.labels(stage="evaluate").set(0)
        else:
            for m in reg.metrics():
                if m.name == "scanner_tpu_t_qd":
                    m.remove_labels(stage="evaluate")

    g.labels(stage="evaluate").set(6)
    assert eng.tick(100.0) == [] and eng.tick(101.0) == []
    assert [t["state"] for t in eng.tick(102.0)] == ["firing"]
    for start in (103.0, 110.0, 117.0):      # three more runs
        drop()
        assert eng.tick(start) == [] and eng.tick(start + 1.0) == []
        assert len(eng.firing()) == 1
        g.labels(stage="evaluate").set(4)
        for dt in (2.0, 3.0, 4.0, 5.0, 6.0):
            assert eng.tick(start + dt) == []
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule=f"t_stand_{lapse}", state="firing") == 1
    # the last run ended at 123: false at 124 .. 127 stands, 128 resolves
    drop()
    for t in (124.0, 125.0, 126.0, 127.0, 127.9):
        assert eng.tick(t) == []
    assert [t["state"] for t in eng.tick(128.0)] == ["resolved"]
    assert eng.firing() == []
    # a pending alert gets no such grace: its hold-down starts over
    g.labels(stage="evaluate").set(6)
    assert eng.tick(130.0) == []
    drop()
    assert eng.tick(131.0) == []
    g.labels(stage="evaluate").set(6)
    assert eng.tick(132.0) == [] and eng.tick(133.0) == []
    assert [t["state"] for t in eng.tick(133.5)] == ["firing"]


def test_stage_backpressure_holds_five_seconds_and_the_others_none():
    held = {r.name: r.resolve_seconds for r in health.default_rules()}
    assert held.pop("stage_backpressure") == 5.0
    assert set(held.values()) == {0.0}
    table = {r["name"]: r for r in health.HealthEngine(
        reg=MetricsRegistry()).alertz_dict()["rule_table"]}
    assert table["stage_backpressure"]["resolve"] == 5.0


def test_vanished_series_resolves_firing_alert():
    reg = MetricsRegistry()
    g = reg.gauge("scanner_tpu_t_age", "x", labels=["worker"])
    rule = health.AlertRule(
        name="t_gone", series="scanner_tpu_t_age", form="value",
        op=">", value=4, by=("worker",))
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    g.labels(worker="3").set(9)
    trans = eng.tick(100.0)
    assert [t["state"] for t in trans] == ["firing"]
    # the master drops a deactivated worker's gauge child entirely
    for m in reg.metrics():
        if m.name == "scanner_tpu_t_age":
            m.remove_labels(worker="3")
    trans = eng.tick(101.0)
    assert [t["state"] for t in trans] == ["resolved"]
    assert eng.status_dict()["status"] == "ok"


def test_rate_rule_windowed():
    reg = MetricsRegistry()
    c = reg.counter("scanner_tpu_t_recompiles_total", "x")
    rule = health.AlertRule(
        name="t_rate", series="scanner_tpu_t_recompiles_total",
        form="rate", op=">", value=2.0, window=10.0)
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    assert eng.tick(100.0) == []       # single sample: no rate yet
    c.inc(5)                           # 5 in 5s = 1/s: under threshold
    assert eng.tick(105.0) == []
    c.inc(40)                          # 45 over 10s = 4.5/s: over
    trans = eng.tick(110.0)
    assert [t["state"] for t in trans] == ["firing"]
    # counter stops climbing -> windowed rate decays -> resolves
    trans = eng.tick(121.0)
    assert [t["state"] for t in trans] == ["resolved"]


def test_unless_gate_holds_rule_quiet_inside_window():
    """A rule with `unless` evaluates as not met while the gate series
    read above 0 anywhere inside its window: a pending hold-down
    restarts, a firing alert resolves, and the rule comes back once
    the window holds no gated time."""
    reg = MetricsRegistry()
    depth = reg.gauge("scanner_tpu_t_qdepth", "x")
    warming = reg.gauge("scanner_tpu_t_warming", "x")
    rule = health.AlertRule(
        name="t_gated", series="scanner_tpu_t_qdepth", form="value",
        op=">=", value=3, window=10.0, for_seconds=2.0,
        unless="scanner_tpu_t_warming").validate()
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    depth.set(5)
    warming.set(1)
    for t in range(100, 140):          # 40 s of backlog behind a compile
        assert eng.tick(float(t)) == []
    warming.set(0)
    for t in range(140, 151):          # gate still inside the window
        assert eng.tick(float(t)) == []
    assert eng.tick(151.0) == []       # pending starts only now
    trans = eng.tick(153.5)
    assert [t["state"] for t in trans] == ["firing"]
    warming.set(2)                     # a new graph starts compiling
    trans = eng.tick(154.0)
    assert [t["state"] for t in trans] == ["resolved"]
    with pytest.raises(health.HealthConfigError):
        health.AlertRule(name="t_bad", series="scanner_tpu_t_qdepth",
                         unless="not a series").validate()


def test_negated_label_match():
    """`{cache=!hit}` keeps every sample whose label is not `hit`."""
    reg = MetricsRegistry()
    c = reg.counter("scanner_tpu_t_compile_total", "x",
                    labels=["op", "cache"])
    rule = health.parse_rules(
        "t_miss:rate(scanner_tpu_t_compile_total{cache=!hit})>0.5"
        ":window=10:by=op")[0]
    assert rule.match == {"cache": "!hit"}
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    for cache in ("hit", "miss", "uncached"):
        c.labels(op="A", cache=cache).inc(0)
    c.labels(op="B", cache="hit").inc(0)
    assert eng.tick(100.0) == []
    c.labels(op="A", cache="hit").inc(100)     # 10/s of cache hits
    c.labels(op="B", cache="hit").inc(100)
    assert eng.tick(110.0) == []
    c.labels(op="A", cache="miss").inc(4)      # 0.8/s really compiled
    c.labels(op="A", cache="uncached").inc(4)
    trans = eng.tick(120.0)
    assert [(t["rule"], t["labels"]) for t in trans] == \
        [("t_miss", {"op": "A"})]


def test_default_rules_quiet_through_a_cold_start_on_the_chip():
    """The timeline the v5e bring-up measured (PERF.md): the first run
    of a graph holds the evaluate stage for ~80 s of compile with the
    loader's tasks queued behind it, on every chip at once, each op's
    ladder compiling ~24 executables; then short warm jobs whose fresh
    evaluators meet only cache hits.  No default rule may fire on
    that; sustained saturation afterwards still does."""
    reg = MetricsRegistry()
    busy = reg.counter("scanner_tpu_evaluate_open_seconds_total", "x",
                       labels=["device"])
    depth = reg.gauge("scanner_tpu_stage_queue_depth", "x",
                      labels=["stage"])
    warming = reg.gauge("scanner_tpu_evaluator_warming", "x")
    compiles = reg.counter("scanner_tpu_compile_total", "x",
                           labels=["op", "device", "cache"])
    devs = [f"tpu:{i}" for i in range(4)]
    eng = health.HealthEngine(reg=reg, rules=health.default_rules(),
                              interval=1.0)
    t = 1000.0
    fired = []

    def run(seconds, *, warm, busy_share, qdepth, per_tick=None):
        nonlocal t
        warming.set(warm)
        depth.labels(stage="evaluate").set(qdepth)
        for _ in range(seconds):
            t += 1.0
            for d in devs:
                busy.labels(device=d).inc(busy_share)
            if per_tick is not None:
                per_tick()
            fired.extend(x for x in eng.tick(t)
                         if x["state"] == "firing")

    ticks = [0]

    def cold_ladders():                # 24 compiles per op per chip
        ticks[0] += 1                  # spread over the 80 s
        if ticks[0] % 10 == 0:
            for d in devs:
                compiles.labels(op="Histogram", device=d,
                                cache="miss").inc(3)

    def warm_hits():                   # a fresh evaluator every 2 s,
        for d in devs:                 # every rung a cache hit
            compiles.labels(op="Histogram", device=d,
                            cache="hit").inc(6)

    run(80, warm=4, busy_share=1.0, qdepth=3, per_tick=cold_ladders)
    run(30, warm=0, busy_share=1.0, qdepth=1)      # the job itself
    run(20, warm=0, busy_share=0.0, qdepth=0)
    run(30, warm=0, busy_share=0.9, qdepth=0, per_tick=warm_hits)
    assert fired == [], fired
    run(200, warm=0, busy_share=1.0, qdepth=0)     # minutes of load
    assert {(x["rule"], x["labels"]["device"]) for x in fired} == \
        {("device_saturation", d) for d in devs}


def test_quantile_rule_over_window():
    reg = MetricsRegistry()
    h = reg.histogram("scanner_tpu_t_rpc_seconds", "x",
                      buckets=(0.1, 0.5, 2.0))
    rule = health.AlertRule(
        name="t_p99", series="scanner_tpu_t_rpc_seconds", form="p99",
        op=">", value=0.5, window=30.0)
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    for _ in range(100):
        h.observe(0.05)
    assert eng.tick(100.0) == []       # p99 ~ 0.1: quiet
    assert eng.tick(105.0) == []
    for _ in range(50):
        h.observe(1.5)                 # now a third of the window is slow
    trans = eng.tick(110.0)
    assert [t["state"] for t in trans] == ["firing"]
    # 40s later the slow observations age OUT of the 30s window (no new
    # traffic: the bucket delta is empty, the alert resolves)
    trans = eng.tick(150.0)
    assert [t["state"] for t in trans] == ["resolved"]


def test_burn_rate_multi_window_semantics():
    def mk():
        reg = MetricsRegistry()
        h = reg.histogram("scanner_tpu_t_lat2_seconds", "x",
                          buckets=(0.1, 1.0, 10.0))
        rule = health.AlertRule(
            name="t_burn", series="scanner_tpu_t_lat2_seconds",
            form="burn", op=">", value=2.0, objective=1.0, budget=0.1,
            short_window=10.0, window=60.0, severity="critical")
        return reg, h, health.HealthEngine(reg=reg, rules=[rule],
                                           interval=0.1)

    # sustained burn: 30% of every batch over the objective, in both
    # windows -> fires (30% > 2.0 x 10% budget)
    _reg, h, eng = mk()
    fired = []
    for i in range(15):
        for _ in range(7):
            h.observe(0.05)
        for _ in range(3):
            h.observe(5.0)
        fired += eng.tick(100.0 + 5 * i)
    assert [t["state"] for t in fired] == ["firing"]
    # recovery: traffic goes clean -> the short window empties of bad
    # observations -> resolves
    for i in range(4):
        for _ in range(10):
            h.observe(0.05)
        fired += eng.tick(180.0 + 5 * i)
    assert [t["state"] for t in fired] == ["firing", "resolved"]

    # a short spike does NOT fire: the short window burns but the long
    # window's error share stays under the threshold
    _reg, h, eng = mk()
    out = []
    for i in range(12):                    # 60s of clean traffic
        for _ in range(10):
            h.observe(0.05)
        out += eng.tick(100.0 + 5 * i)
    for _ in range(3):                     # one bad batch
        h.observe(5.0)
    out += eng.tick(160.0)
    out += eng.tick(161.0)
    assert out == []


def test_ratio_rule_hbm_pressure_shape():
    reg = MetricsRegistry()
    use = reg.gauge("scanner_tpu_t_hbm_bytes", "x", labels=["device"])
    lim = reg.gauge("scanner_tpu_t_hbm_limit_bytes", "x",
                    labels=["device"])
    rule = health.AlertRule(
        name="t_hbm", series="scanner_tpu_t_hbm_bytes",
        ratio_to="scanner_tpu_t_hbm_limit_bytes",
        form="value", op=">", value=0.9, by=("device",))
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    lim.labels(device="tpu:0").set(100)
    lim.labels(device="tpu:1").set(100)
    use.labels(device="tpu:0").set(50)
    use.labels(device="tpu:1").set(95)
    trans = eng.tick(100.0)
    assert [(t["state"], t["labels"]) for t in trans] == \
        [("firing", {"device": "tpu:1"})]
    # a device with no limit sample never divides by zero
    use.labels(device="tpu:2").set(99)
    assert eng.tick(101.0) == []


def test_backpressure_watermark_and_imbalance_branches():
    reg = MetricsRegistry()
    q = reg.gauge("scanner_tpu_stage_queue_depth", "x", labels=["stage"])
    tasks = reg.counter("scanner_tpu_stage_tasks_total", "x",
                        labels=["stage"])
    rule = health.AlertRule(
        name="t_bp", series="scanner_tpu_stage_queue_depth",
        form="backpressure", op=">=", value=3, by=("stage",),
        window=10.0, for_seconds=0.0)
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    # watermark branch: deep queue alone fires
    q.labels(stage="save").set(4)
    q.labels(stage="evaluate").set(0)
    trans = eng.tick(100.0)
    assert [(t["state"], t["labels"]) for t in trans] == \
        [("firing", {"stage": "save"})]
    q.labels(stage="save").set(0)
    trans = eng.tick(101.0)
    assert [t["state"] for t in trans] == ["resolved"]
    # imbalance branch: a standing backlog (depth 1 < watermark) plus a
    # producer completing >1.5x faster than the stage
    q.labels(stage="save").set(1)
    tasks.labels(stage="evaluate").inc(0)    # create children
    tasks.labels(stage="save").inc(0)
    eng.tick(102.0)
    tasks.labels(stage="evaluate").inc(100)
    tasks.labels(stage="save").inc(10)
    trans = eng.tick(108.0)
    assert [(t["state"], t["labels"]) for t in trans] == \
        [("firing", {"stage": "save"})]
    # backlog clears -> resolves even though the rate window still
    # remembers the imbalance
    q.labels(stage="save").set(0)
    trans = eng.tick(109.0)
    assert [t["state"] for t in trans] == ["resolved"]


@pytest.mark.parametrize(
    "stage, busy, task_wait, chunk_wait, fires",
    [   # chip readings a window of 10 s (PERF.md §6, PR 42)
        ("evaluate", 9.8, 0.1, 1.1, True),    # pose_dense: sets the pace
        ("evaluate", 9.9, 0.05, 7.2, False),  # hist_dense: starves
        ("evaluate", 9.5, 0.3, 3.2, False),   # walkthrough_dense
        ("evaluate", 3.0, 5.0, 0.0, False),   # waits for tasks, not chunks
        ("save", 18.0, 1.5, 0.0, True),       # two savers, both at work
        ("save", 6.0, 13.0, 9.0, False),      # chunk waits are not theirs
        ("save", 18.0, 1.5, 9.0, True),
    ])
def test_backpressure_holds_quiet_for_a_stage_that_starves(
        stage, busy, task_wait, chunk_wait, fires):
    """A wide load stage finishes a round of tasks together, so a
    sample that falls on a round reads a full queue before an evaluator
    that waits on its loaders most of the time: the depth alone is not
    backpressure.  A stage that spent over a fifth of the window
    waiting on its producer is held quiet, by either branch."""
    reg = MetricsRegistry()
    q = reg.gauge("scanner_tpu_stage_queue_depth", "x", labels=["stage"])
    secs = reg.counter("scanner_tpu_stage_seconds_total", "x",
                       labels=["stage"])
    waits = reg.counter("scanner_tpu_stage_wait_seconds_total", "x",
                        labels=["stage"])
    chunks = reg.counter("scanner_tpu_chunk_wait_seconds_total", "x")
    tasks = reg.counter("scanner_tpu_stage_tasks_total", "x",
                        labels=["stage"])
    rule = health.AlertRule(
        name="t_starved", series="scanner_tpu_stage_queue_depth",
        form="backpressure", op=">=", value=3, by=("stage",),
        window=10.0, for_seconds=0.0)
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    for c in (secs, waits, tasks):
        for st in ("load", "evaluate", "save"):
            c.labels(stage=st).inc(0)
    chunks.inc(0)
    q.labels(stage=stage).set(0)
    assert eng.tick(100.0) == []
    secs.labels(stage=stage).inc(busy)
    waits.labels(stage=stage).inc(task_wait)
    chunks.inc(chunk_wait)
    # the watermark branch ...
    q.labels(stage=stage).set(6)
    assert [t["state"] for t in eng.tick(110.0)] \
        == (["firing"] if fires else [])
    q.labels(stage=stage).set(0)
    eng.tick(110.5)
    # ... and the imbalance branch: a backlog of one behind a producer
    # that completed four times the tasks
    tasks.labels(stage=health._BP_UPSTREAM[stage]).inc(40)
    tasks.labels(stage=stage).inc(10)
    q.labels(stage=stage).set(1)
    assert [t["state"] for t in eng.tick(111.0)] \
        == (["firing"] if fires else [])


def test_rollup_severity_mapping_and_alertz():
    reg = MetricsRegistry()
    g = reg.gauge("scanner_tpu_t_sev", "x", labels=["which"])
    rules = [
        health.AlertRule(name="t_warn", series="scanner_tpu_t_sev",
                         form="value", op=">", value=0,
                         match={"which": "w"}, severity="warning"),
        health.AlertRule(name="t_crit", series="scanner_tpu_t_sev",
                         form="value", op=">", value=0,
                         match={"which": "c"}, severity="critical"),
    ]
    eng = health.HealthEngine(reg=reg, rules=rules, interval=0.1)
    g.labels(which="w").set(0)
    g.labels(which="c").set(0)
    eng.tick(100.0)
    assert eng.status_dict()["status"] == "ok"
    g.labels(which="w").set(1)
    eng.tick(101.0)
    assert eng.status_dict()["status"] == "degraded"
    g.labels(which="c").set(1)
    eng.tick(102.0)
    st = eng.status_dict()
    assert st["status"] == "unhealthy"
    assert {f["rule"] for f in st["firing"]} == {"t_warn", "t_crit"}
    az = eng.alertz_dict()
    assert az["status"] == "unhealthy"
    assert {r["name"] for r in az["rule_table"]} == {"t_warn", "t_crit"}


def test_user_rules_ride_alongside_defaults():
    reg = MetricsRegistry()
    g = reg.gauge("scanner_tpu_t_user", "x")
    eng = health.HealthEngine(reg=reg, rules=health.default_rules(),
                              interval=0.1)
    eng.set_user_rules(health.parse_rules(
        "my_rule:value(scanner_tpu_t_user)>5:severity=critical"))
    assert "my_rule" in [r.name for r in eng.rules()]
    g.set(9)
    trans = eng.tick(100.0)
    assert [(t["rule"], t["state"]) for t in trans] == \
        [("my_rule", "firing")]
    # replacing the user rules resolves the removed rule's firing
    # state on the spot — it must not degrade the roll-up forever
    res_base = _counter("scanner_tpu_alerts_transitions_total",
                        rule="my_rule", state="resolved")
    eng.set_user_rules([])
    assert eng.status_dict()["status"] == "ok"
    assert eng.status_dict()["firing"] == []
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="my_rule", state="resolved") == res_base + 1
    assert _counter("scanner_tpu_alerts_firing",
                    rule="my_rule", severity="critical") == 0


def test_burn_requires_real_long_window_history():
    """A young engine (uptime < the long window) must NOT collapse
    both burn windows onto the same short delta: a spike right after
    startup is not a sustained burn."""
    reg = MetricsRegistry()
    h = reg.histogram("scanner_tpu_t_lat3_seconds", "x",
                      buckets=(0.1, 1.0, 10.0))
    rule = health.AlertRule(
        name="t_young_burn", series="scanner_tpu_t_lat3_seconds",
        form="burn", op=">", value=2.0, objective=1.0, budget=0.1,
        short_window=10.0, window=60.0, severity="critical")
    eng = health.HealthEngine(reg=reg, rules=[rule], interval=0.1)
    eng.tick(100.0)
    for _ in range(7):
        h.observe(0.05)
    for _ in range(3):
        h.observe(5.0)      # 30% bad — would fire if windows collapsed
    assert eng.tick(105.0) == []
    assert eng.tick(115.0) == []     # still < 60s of history
    assert eng.status_dict()["status"] == "ok"


def test_merge_status_worst_of_and_node_prefixes():
    merged = health.merge_status({
        "master": {"status": "ok", "reasons": [], "firing": []},
        "worker0": {"status": "degraded",
                    "reasons": ["stage_backpressure[stage=save]"],
                    "firing": [{"rule": "stage_backpressure",
                                "severity": "warning",
                                "labels": {"stage": "save"}}]},
        "worker1": {"status": "unhealthy",
                    "reasons": ["hbm_pressure[device=tpu:0]"],
                    "firing": [{"rule": "hbm_pressure",
                                "severity": "critical",
                                "labels": {"device": "tpu:0"}}]},
    })
    assert merged["status"] == "unhealthy"
    assert "worker0:stage_backpressure[stage=save]" in merged["reasons"]
    assert "worker1:hbm_pressure[device=tpu:0]" in merged["reasons"]
    assert {(f["node"], f["rule"]) for f in merged["firing"]} == \
        {("worker0", "stage_backpressure"), ("worker1", "hbm_pressure")}


# ---------------------------------------------------------------------------
# serving surface: /healthz roll-up, /readyz drain, /alertz
# ---------------------------------------------------------------------------

def test_healthz_reflects_rollup_and_readyz_drains():
    state = {"status": "ok", "reasons": []}
    draining = {"v": False}
    srv = MetricsServer(port=0, health=lambda: dict(state),
                        ready=lambda: not draining["v"],
                        alertz=lambda: {"status": state["status"],
                                        "firing": [], "rule_table": []},
                        healthz=lambda: {"role": "worker"})
    base = f"http://127.0.0.1:{srv.port}"
    try:
        code, hz = _get_json(base + "/healthz")
        assert code == 200
        # backward-compatible shape PLUS the roll-up
        assert hz["ok"] is True and hz["role"] == "worker"
        assert hz["status"] == "ok" and hz["reasons"] == []
        code, rz = _get_json(base + "/readyz")
        assert code == 200 and rz["ready"] is True

        # degraded: still alive (200), status visible
        state["status"] = "degraded"
        state["reasons"] = ["stage_backpressure[stage=save]"]
        code, hz = _get_json(base + "/healthz")
        assert code == 200 and hz["ok"] is True
        assert hz["status"] == "degraded"
        assert hz["reasons"] == ["stage_backpressure[stage=save]"]

        # unhealthy: /healthz STAYS 200 (liveness — a restart cannot
        # fix a workload alert) with ok False in the body; /readyz is
        # the surface that goes 503 so routing stops
        state["status"] = "unhealthy"
        code, hz = _get_json(base + "/healthz")
        assert code == 200 and hz["ok"] is False
        assert hz["status"] == "unhealthy"
        code, rz = _get_json(base + "/readyz")
        assert code == 503 and rz["ready"] is False

        # draining: NOT ready, still alive — the SIGTERM contract
        state["status"] = "ok"
        draining["v"] = True
        code, hz = _get_json(base + "/healthz")
        assert code == 200 and hz["ok"] is True
        code, rz = _get_json(base + "/readyz")
        assert code == 503 and rz["ready"] is False

        code, az = _get_json(base + "/alertz")
        assert code == 200 and "rule_table" in az
    finally:
        srv.stop()


def test_worker_drain_not_ready_still_alive(tmp_path):
    """The real Worker wiring: drain() flips /readyz to 503 while
    /healthz stays 200 (k8s stops routing, doesn't kill)."""
    db = str(tmp_path / "db")
    master = Master(db_path=db, no_workers_timeout=10.0)
    worker = Worker(f"localhost:{master.port}", db_path=db,
                    metrics_port=0, metrics_host="127.0.0.1")
    base = f"http://127.0.0.1:{worker.metrics_server.port}"
    try:
        code, hz = _get_json(base + "/healthz")
        assert code == 200 and hz["ok"] is True and not hz["draining"]
        code, rz = _get_json(base + "/readyz")
        assert code == 200
        worker.drain()
        code, hz = _get_json(base + "/healthz")
        assert code == 200 and hz["ok"] is True and hz["draining"]
        code, rz = _get_json(base + "/readyz")
        assert code == 503 and rz["ready"] is False
    finally:
        worker.stop()
        master.stop()


# ---------------------------------------------------------------------------
# chaos-style e2e (the acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.fixture()
def health_cluster(tmp_path):
    """Master (with /metrics+/alertz enabled) + 2 in-process workers
    over a packed-int source table, health engine on a fast clock."""
    health.set_interval(0.1)
    # the engine is the process's one: a sample of the last ten seconds
    # in which another test file's evaluator was warming (its first
    # dispatch, a compile) would hold `stage_backpressure` quiet past
    # the end of these short runs (its `unless` gate)
    eng = health.engine()
    with eng._lock:
        eng._samples.clear()
    db_path = str(tmp_path / "db")
    seed = Client(db_path=db_path)
    seed.new_table("health_src", ["output"],
                   [[_pk(100 + i)] for i in range(N_ROWS)])
    master = Master(db_path=db_path, no_workers_timeout=30.0,
                    metrics_port=0, metrics_host="127.0.0.1")
    addr = f"localhost:{master.port}"
    workers = [Worker(addr, db_path=db_path) for _ in range(2)]
    sc = Client(db_path=db_path, master=addr)
    yield sc, master, workers, addr
    faults.clear()
    sc.stop()
    for w in workers:
        w.stop()
    master.stop()
    health.set_interval(1.0)


def _run_golden(sc, out_name: str):
    col = sc.io.Input([NamedStream(sc, "health_src")])
    col = sc.ops.HealthDouble(x=col)
    out = NamedStream(sc, out_name)
    sc.run(sc.io.Output(col, [out]), PerfParams.manual(2, 2),
           cache_mode=CacheMode.Overwrite, show_progress=False)
    return [bytes(r) for r in out.load()]


def _wait_until(pred, timeout=20.0, dt=0.1):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(dt)
    return False


@pytest.mark.chaos
def test_save_delay_fires_backpressure_then_resolves(health_cluster):
    """The acceptance chaos test: a pipeline.save delay fault induces
    stage backpressure -> the `stage_backpressure` alert fires with
    stage=save labels (Client.health(), /alertz, transitions counter)
    and resolves after the backlog drains; output stays bit-exact; the
    identical fault-free run reports ok with zero firing alerts."""
    sc, master, _workers, _addr = health_cluster
    fire_base = _counter("scanner_tpu_alerts_transitions_total",
                         rule="stage_backpressure", state="firing")

    # every save stalls 0.8s: evaluators outrun savers, the save queue
    # hits its watermark and stays there
    faults.install("pipeline.save:delay:seconds=0.8")
    rows_box = []
    t = threading.Thread(
        target=lambda: rows_box.append(_run_golden(sc, "bp_out")))
    t.start()
    saw = {}

    def firing_now():
        h = sc.health()
        for f in h.get("firing", []):
            if f["rule"] == "stage_backpressure" \
                    and (f.get("labels") or {}).get("stage") == "save":
                saw.update(f)
                return True
        return False

    assert _wait_until(firing_now, timeout=30.0), \
        "stage_backpressure[stage=save] never fired under a " \
        "save-delay fault"
    assert saw["labels"] == {"stage": "save"}, saw
    assert saw["severity"] == "warning"
    assert sc.health()["status"] in ("degraded", "unhealthy")

    # visible on /alertz too (the master's endpoint; in-process
    # cluster components share the process engine)
    code, az = _get_json(
        f"http://127.0.0.1:{master.metrics_server.port}/alertz")
    assert code == 200
    assert any(f["rule"] == "stage_backpressure"
               for f in az.get("firing", [])), az

    t.join(timeout=120)
    assert not t.is_alive()
    assert rows_box and rows_box[0] == EXPECT   # bit-exact through it
    assert faults.fired("pipeline.save") > 0    # the fault really fired
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="stage_backpressure",
                    state="firing") > fire_base

    # the fault plan clears; the drained pipeline's queue gauge reads 0
    # and the alert resolves
    faults.clear()
    res_base = _counter("scanner_tpu_alerts_transitions_total",
                        rule="stage_backpressure", state="resolved")

    def resolved():
        h = sc.health()
        return not any(f["rule"] == "stage_backpressure"
                       for f in h.get("firing", []))

    assert _wait_until(resolved, timeout=20.0), \
        "stage_backpressure never resolved after the fault cleared"
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="stage_backpressure",
                    state="resolved") >= res_base

    # clean golden run: zero backpressure alerts fire, health ends ok
    fire_base2 = _counter("scanner_tpu_alerts_transitions_total",
                          rule="stage_backpressure", state="firing")
    rows = _run_golden(sc, "bp_clean_out")
    assert rows == EXPECT
    assert _counter("scanner_tpu_alerts_transitions_total",
                    rule="stage_backpressure",
                    state="firing") == fire_base2
    assert _wait_until(lambda: sc.health()["status"] == "ok",
                       timeout=20.0), sc.health()
    assert sc.health()["firing"] == []


@pytest.mark.chaos
def test_heartbeat_loss_degrades_master_healthz(tmp_path):
    """Worker heartbeat loss -> `worker_heartbeat_stale` fires on the
    master -> /healthz transitions out of ok; the stale scan then
    deactivates the worker (its gauge child is dropped) and health
    recovers."""
    health.set_interval(0.1)
    db = str(tmp_path / "db")
    master = Master(db_path=db, no_workers_timeout=30.0,
                    metrics_port=0, metrics_host="127.0.0.1")
    worker = Worker(f"localhost:{master.port}", db_path=db)
    base = f"http://127.0.0.1:{master.metrics_server.port}"
    try:
        # healthy first: heartbeats land, age stays ~1s
        assert _wait_until(
            lambda: _get_json(base + "/healthz")[1]["status"] == "ok",
            timeout=10.0)
        # now every beat is dropped at the injection site
        faults.install("worker.heartbeat:raise")

        def not_ok():
            code, hz = _get_json(base + "/healthz")
            return hz.get("status") != "ok" and any(
                r.startswith("worker_heartbeat_stale")
                for r in hz.get("reasons", []))

        assert _wait_until(not_ok, timeout=15.0), \
            "heartbeat loss never degraded /healthz"
        # the stale scan deactivates the worker at WORKER_STALE_AFTER;
        # its heartbeat-age gauge child is removed and health recovers
        assert _wait_until(
            lambda: _get_json(base + "/healthz")[1]["status"] == "ok",
            timeout=15.0), "health never recovered after stale removal"
    finally:
        faults.clear()
        worker.stop()
        master.stop()
        health.set_interval(1.0)


# ---------------------------------------------------------------------------
# satellites: GetJobStatus health field, statusz panel, bench history
# ---------------------------------------------------------------------------

def test_job_status_and_statusz_carry_health(health_cluster):
    sc, master, _workers, _addr = health_cluster
    _run_golden(sc, "hs_out")
    st = sc.job_status()
    assert "health" in st and "status" in st["health"]
    code, statusz = _get_json(
        f"http://127.0.0.1:{master.metrics_server.port}/statusz")
    assert code == 200
    assert "health" in statusz and "status" in statusz["health"]
    # the cluster roll-up names its nodes
    h = sc.health()
    assert set(h) >= {"status", "reasons", "firing", "nodes"}
    assert "master" in h["nodes"]
