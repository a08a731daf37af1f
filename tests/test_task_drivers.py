"""A task has three drivers (engine/executor.py): the stage threads of
`run_pipeline`, the one thread of `_run_serial`
(SCANNER_TPU_NO_PIPELINING) and the gang member's `run_single_task`.
All three run one set of stage bodies, so whichever drives a task, it
yields the same rows, records the same spans and counters, and a failed
attempt is left holding nothing.
"""

from typing import Any

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, FrameType, Kernel, NamedStream,
                         NamedVideoStream, PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine.evaluate import device_label
from scanner_tpu.engine.executor import LocalExecutor, TaskItem
from scanner_tpu.util import tracing as _tr
from scanner_tpu.util.metrics import registry
from scanner_tpu.util.profiler import Profiler

N_FRAMES = 64
TASK_ROWS = 16
DRIVERS = ("pipeline", "serial", "single_task")
# what every driver's profile holds of a Histogram task
STAGE_SPANS = {"load", "load:decode", "evaluate:setup", "evaluate",
               "evaluate:Histogram", "save", "save:fetch", "save:write"}


class Boom(Exception):
    pass


@register_op(name="DriverTestBoom")
class DriverTestBoom(Kernel):
    def execute(self, frame: FrameType) -> Any:
        raise Boom("evaluate")


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("drivers")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=16)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("dr", vid)])
    yield client
    client.stop()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot()[series]["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


class _Seen:
    """What a driven run left behind, filled in even when it raises."""
    intervals = tasks = counters = ()


def _drive(driver, sc, monkeypatch, name, op="Histogram", streamed=False,
           seen=None):
    """Run `op` over the clip into stream `name` under `driver`."""
    seen = seen or _Seen()
    frame = sc.io.Input([NamedVideoStream(sc, "dr")])
    outputs = [sc.io.Output(getattr(sc.ops, op)(frame=frame),
                            [NamedStream(sc, name)])]
    # a task of 16 rows streams in packets of 8, or is loaded whole
    perf = PerfParams.manual(8 if streamed else TASK_ROWS, TASK_ROWS)
    prof = Profiler(node=driver)
    ex = LocalExecutor(sc._db, prof, num_load_workers=2)
    root = None
    try:
        if driver == "single_task":
            # as engine/gang.py's member drives it: whole tasks, one at
            # a time, under a span context handed in from outside
            info, jobs = ex.prepare(outputs, perf, CacheMode.Overwrite)
            ex._stream_opt = False
            root = _tr.open_span(ex.tracer, "job")
            for job in jobs:
                for t, rng in enumerate(job.tasks):
                    w = TaskItem(job, t, rng, trace_ctx=root.context())
                    ex.run_single_task(info, w)
            for job in jobs:
                for desc, _c, _k, _e in job.sink_tables.values():
                    sc._db.commit_table(desc.id)
        else:
            if driver == "serial":
                monkeypatch.setenv("SCANNER_TPU_NO_PIPELINING", "1")
            ex.run(outputs, perf, cache_mode=CacheMode.Overwrite)
    finally:
        _tr.close_span(ex.tracer, root)
        trace_id = root.trace_id if root is not None else ex.last_trace_id
        seen.tasks = [s for s in ex.tracer.spans_for_trace(trace_id)
                      if s["name"] == "task"]
        seen.intervals = prof.intervals()
        seen.counters = prof.counters
    return seen


@pytest.fixture(scope="module")
def expected(sc):
    frames = sc.load_frames("dr", list(range(N_FRAMES)))
    return [np.stack([np.bincount(f[..., c].ravel() >> 4, minlength=16)
                      for c in range(3)]) for f in frames]


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["whole", "streamed"])
@pytest.mark.parametrize("driver", DRIVERS)
def test_same_rows_from_a_whole_and_a_streamed_task(sc, monkeypatch,
                                                    expected, driver,
                                                    streamed):
    if driver == "single_task" and streamed:
        pytest.skip("a gang member evaluates whole tasks only "
                    "(engine/gang.py sets _stream_opt False)")
    name = f"rows_{driver}_{int(streamed)}"
    seen = _drive(driver, sc, monkeypatch, name, streamed=streamed)
    ivs = seen.intervals
    assert ("stream_chunks" in seen.counters) == streamed
    assert len([iv for iv in ivs if iv.name == "evaluate"]) \
        == N_FRAMES // TASK_ROWS
    rows = list(NamedStream(sc, name).load())
    assert len(rows) == N_FRAMES
    for got, want in zip(rows, expected):
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("driver", DRIVERS)
def test_same_spans_and_device_label(sc, monkeypatch, driver):
    seen = _drive(driver, sc, monkeypatch, f"spans_{driver}")
    ivs, tasks = seen.intervals, seen.tasks
    names = {iv.name for iv in ivs}
    assert STAGE_SPANS <= names, STAGE_SPANS - names
    n_tasks = N_FRAMES // TASK_ROWS
    for stage in ("evaluate", "save"):
        assert len([iv for iv in ivs if iv.name == stage]) == n_tasks
    # one evaluator a pipeline instance a run; the gang member makes
    # one for its one task
    setups = [iv for iv in ivs if iv.name == "evaluate:setup"]
    assert len(setups) == (n_tasks if driver == "single_task" else 1)
    lbl = device_label(None)
    for iv in ivs:
        if iv.name in ("evaluate", "evaluate:setup"):
            assert iv.args.get("device") == lbl, iv
    if driver != "single_task":
        # LocalExecutor.run reads the drain from where the last save
        # ended, whoever saved
        (drain,) = [iv for iv in ivs if iv.name == "run:drain"]
        assert drain.start == pytest.approx(
            max(iv.end for iv in ivs if iv.name == "save"), abs=1e-3)
    assert len(tasks) == n_tasks
    assert all(s["status"] == "ok" for s in tasks), tasks


@pytest.mark.parametrize("driver", DRIVERS)
def test_stage_counters_count_the_task(sc, monkeypatch, driver):
    series = ("scanner_tpu_stage_tasks_total",
              "scanner_tpu_stage_seconds_total")
    stages = ("load", "evaluate", "save")
    before = {(s, st): _counter(s, stage=st) for s in series for st in stages}
    setups = _counter("scanner_tpu_evaluator_setups_total")
    dev_tasks = _counter("scanner_tpu_device_tasks_total",
                         device=device_label(None))
    ivs = _drive(driver, sc, monkeypatch, f"count_{driver}").intervals
    n_tasks = N_FRAMES // TASK_ROWS
    for st in stages:
        assert _counter(series[0], stage=st) - before[series[0], st] \
            == n_tasks, st
        spent = _counter(series[1], stage=st) - before[series[1], st]
        # the counter and the spans of the stage read the same clock
        in_spans = sum(iv.end - iv.start for iv in ivs if iv.name == st)
        assert spent > 0 and spent == pytest.approx(in_spans, abs=0.05), st
    assert _counter("scanner_tpu_evaluator_setups_total") - setups \
        == (n_tasks if driver == "single_task" else 1)
    assert _counter("scanner_tpu_device_tasks_total",
                    device=device_label(None)) - dev_tasks == n_tasks


class _Lease:
    def __init__(self):
        self.released = 0

    def release(self):
        self.released += 1


@pytest.mark.parametrize("driver", DRIVERS)
def test_failed_evaluate_releases_leases_and_closes_the_span(
        sc, monkeypatch, driver):
    leases = {}
    load = LocalExecutor._load_task

    def load_and_pin(self, info, w, tls):
        out = load(self, info, w, tls)
        leases[w.task_idx] = _Lease()
        w.cache_leases = [leases[w.task_idx]]
        return out

    monkeypatch.setattr(LocalExecutor, "_load_task", load_and_pin)
    seen = _Seen()
    with pytest.raises(Boom, match="evaluate"):
        _drive(driver, sc, monkeypatch, f"boom_{driver}",
               op="DriverTestBoom", seen=seen)
    ivs, tasks = seen.intervals, seen.tasks
    failed = [s for s in tasks if s["status"] == "error"]
    assert failed and len(failed) == len(tasks)
    for s in failed:
        assert [ev["name"] for ev in s["events"]] == ["error"], s
        assert s["events"][0]["attrs"]["type"] == "Boom"
        assert leases[s["attrs"]["task"]].released == 1
    # the stage counted nothing and kept nothing of the attempt
    assert not [iv for iv in ivs if iv.name == "save"]
