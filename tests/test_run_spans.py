"""The job profile names every second of a local `Client.run`
(docs/profiling.md "The span tree"): the run lifecycle on the client
thread, evaluator set-up, the stage threads' waits, and the parts of
load and save; each span's seconds also land in a live counter series
at the same two clock reads.  And the device side: every op's jitted
body is traced under the op's name.
"""

import time
from typing import Any

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, FrameType, Kernel, NamedStream,
                         NamedVideoStream, PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import executor as _executor
from scanner_tpu.util.metrics import registry
from scanner_tpu.util.profiler import Profiler

N_FRAMES = 64
# spans that hold a whole run or all its stage threads name nothing
CONTAINERS = ("run", "run:pipeline")
LIFECYCLE = ("run", "run:prepare", "run:pipeline", "run:drain", "run:commit")


@register_op(name="SpanTestSlowOp")
class SpanTestSlowOp(Kernel):
    """A host op that holds the evaluate stage 20 ms a row."""

    def execute(self, frame: FrameType) -> Any:
        time.sleep(0.02)
        return int(np.asarray(frame).sum() % 251)


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("spans")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=16)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("sp", vid)])
    yield client
    client.stop()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot()[series]["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _run(sc, name, op="Histogram", perf=None, **kw):
    """One run; returns (intervals, call time, return time)."""
    frame = sc.io.Input([NamedVideoStream(sc, "sp")])
    col = getattr(sc.ops, op)(frame=frame)
    out = NamedStream(sc, name)
    t_call = time.time()
    job = sc.run(sc.io.Output(col, [out]), perf or PerfParams.manual(8, 16),
                 cache_mode=CacheMode.Overwrite, show_progress=False, **kw)
    t_done = time.time()
    assert len(list(out.load())) == N_FRAMES
    ivs = [iv for p in sc.get_profile(job).profilers for iv in p.intervals()]
    return ivs, t_call, t_done


def _by_name(ivs):
    by = {}
    for iv in ivs:
        by.setdefault(iv.name, []).append(iv)
    return by


def _uncovered(lo, hi, pairs):
    covered, edge = 0.0, lo
    for s, e in sorted(pairs):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            covered += e - s
            edge = e
    return (hi - lo) - covered


@pytest.mark.parametrize("instances", [1, 2])
def test_run_lifecycle_spans_cover_the_run(sc, instances):
    ivs, t_call, t_done = _run(sc, f"life{instances}",
                               pipeline_instances=instances)
    by = _by_name(ivs)
    for name in LIFECYCLE:
        assert len(by.get(name, [])) == 1, (name, len(by.get(name, [])))
    assert len(by["evaluate:setup"]) == instances
    assert {iv.thread for iv in by["evaluate:setup"]} \
        == {f"eval-{i}" for i in range(instances)}
    run = by["run"][0]
    assert run.args["tasks"] == 4 and run.args["rows"] == N_FRAMES
    assert t_call <= run.start and run.end <= t_done
    # everything lies inside the root, the phases follow one another,
    # and what the stage threads record lies inside run:pipeline
    assert all(run.start <= iv.start and iv.end <= run.end for iv in ivs)
    prepare, pipeline, drain, commit = (
        by[n][0] for n in LIFECYCLE[1:])
    assert prepare.end <= pipeline.start and pipeline.end <= commit.start
    assert pipeline.start <= drain.start and drain.end <= pipeline.end
    for iv in ivs:
        if iv.name.split(":")[0] in ("load", "evaluate", "save"):
            assert pipeline.start <= iv.start and iv.end <= pipeline.end, iv
    # the drain starts where the last save ended
    assert drain.start == pytest.approx(
        max(iv.end for iv in by["save"]), abs=1e-3)
    # children inside parents, on the parent's thread
    for child, parent in (("load:decode", "load"), ("save:fetch", "save"),
                          ("save:write", "save"),
                          ("evaluate:Histogram", "evaluate")):
        assert by.get(child), child
        for c in by[child]:
            assert any(p.thread == c.thread and p.start <= c.start
                       and c.end <= p.end for p in by[parent]), c
    named = [(iv.start, iv.end) for iv in ivs if iv.name not in CONTAINERS]
    # a run of this size takes some 30 ms: the floor is the stage
    # threads' starts, which no span covers
    assert _uncovered(t_call, t_done, named) \
        <= max(0.05 * (t_done - t_call), 0.005)


@pytest.mark.parametrize("savers", [1, 2])
@pytest.mark.parametrize("instances", [1, 2])
def test_run_returns_when_its_last_row_is_saved(sc, monkeypatch, instances,
                                                savers):
    """The stage hand-offs are closed, not polled: nothing timed stands
    between the last save's end and the run's return."""
    monkeypatch.setattr(sc._executor, "num_save_workers", savers)
    ivs, _, t_done = _run(sc, f"prompt{instances}{savers}",
                          pipeline_instances=instances)
    by = _by_name(ivs)
    assert {iv.thread for iv in by["save"]} \
        <= {f"save-{i}" for i in range(savers)}
    (drain,), (commit,) = by["run:drain"], by["run:commit"]
    last_save = max(iv.end for iv in by["save"])
    assert 0.0 < drain.end - drain.start < 0.1
    # the commit is the run's own work after the pipeline; less it, the
    # return follows the last save at once
    assert t_done - last_save - (commit.end - commit.start) < 0.1


def test_counters_count_what_the_spans_cover(sc):
    series = {
        "run:prepare": ("scanner_tpu_run_seconds_total", {"phase": "prepare"}),
        "run:pipeline": ("scanner_tpu_run_seconds_total",
                         {"phase": "pipeline"}),
        "run:drain": ("scanner_tpu_run_seconds_total", {"phase": "drain"}),
        "run:commit": ("scanner_tpu_run_seconds_total", {"phase": "commit"}),
        "evaluate:setup": ("scanner_tpu_evaluator_setup_seconds_total", {}),
        "load:decode": ("scanner_tpu_decode_seconds_total", {}),
    }
    waits = {
        "load:queue_wait": ("scanner_tpu_stage_wait_seconds_total",
                            {"stage": "load"}),
        "evaluate:task_wait": ("scanner_tpu_stage_wait_seconds_total",
                               {"stage": "evaluate"}),
        "save:queue_wait": ("scanner_tpu_stage_wait_seconds_total",
                            {"stage": "save"}),
        "evaluate:chunk_wait": ("scanner_tpu_chunk_wait_seconds_total", {}),
    }
    counts = {"runs": ("scanner_tpu_runs_total", {}),
              "setups": ("scanner_tpu_evaluator_setups_total", {})}
    before = {k: _counter(s, **la)
              for k, (s, la) in {**series, **waits, **counts}.items()}
    ivs, _, _ = _run(sc, "counted", pipeline_instances=2)
    delta = {k: _counter(s, **la) - before[k]
             for k, (s, la) in {**series, **waits, **counts}.items()}
    spans = {}
    for iv in ivs:
        spans.setdefault(iv.name, []).append(iv.end - iv.start)
    assert delta["runs"] == 1 and delta["setups"] == 2
    for name in series:
        assert delta[name] == pytest.approx(sum(spans[name]), rel=0.05,
                                            abs=1e-4), name
    # a wait under 5 ms leaves no interval; its counter still counts it
    n_waits = len([iv for iv in ivs if iv.name == "load"]) + 16
    for name in waits:
        covered = sum(spans.get(name, ()))
        assert covered <= delta[name] + 1e-6, name
        assert delta[name] <= covered * 1.05 + 0.005 * n_waits, name


def test_a_starved_evaluator_waits_in_one_interval(sc, monkeypatch):
    """A loader that takes 0.6 s over a task leaves the evaluator and
    the savers waiting 0.6 s: one interval each."""
    load_task = _executor.LocalExecutor.load_task

    def slow_load(self, info, w, tls):
        time.sleep(0.6)
        return load_task(self, info, w, tls)

    monkeypatch.setattr(_executor.LocalExecutor, "load_task", slow_load)
    ivs, _, _ = _run(sc, "starved", perf=PerfParams.manual(64, 64))
    first_eval = min(iv.start for iv in ivs if iv.name == "evaluate")
    for name, threads in (("evaluate:task_wait", 1), ("save:queue_wait", 2)):
        early = [iv for iv in ivs if iv.name == name
                 and iv.start < first_eval]
        assert len(early) == threads, (name, early)
        assert all(iv.end - iv.start >= 0.5 for iv in early), early
    # a stage thread's last wait ends when its queue is closed: too
    # short for an interval of its own
    waits = [iv for iv in ivs if iv.name == "evaluate:task_wait"]
    assert all(iv.end - iv.start < 0.1 for iv in waits[1:]), waits


def test_a_slow_evaluator_holds_the_loaders(sc):
    before = _counter("scanner_tpu_stage_wait_seconds_total", stage="load")
    ivs, _, _ = _run(sc, "held", op="SpanTestSlowOp",
                     perf=PerfParams.manual(4, 8))
    blocked = [iv for iv in ivs if iv.name == "load:queue_wait"]
    assert blocked and all(iv.thread.startswith("load-") for iv in blocked)
    # 64 rows x 20 ms on one evaluator, two loaders decoding in no time
    assert sum(iv.end - iv.start for iv in blocked) > 0.5
    delta = _counter("scanner_tpu_stage_wait_seconds_total",
                     stage="load") - before
    assert delta >= sum(iv.end - iv.start for iv in blocked) - 1e-6


def test_span_counter_counts_below_the_level():
    c = registry().counter("scanner_tpu_test_span_seconds_total", "test")
    prof = Profiler(level=0)
    v0 = _counter("scanner_tpu_test_span_seconds_total")
    with prof.span("fine", level=1, counter=c):
        time.sleep(0.01)
    with prof.span("coarse", level=0, counter=c):
        time.sleep(0.01)
    assert [iv.name for iv in prof.intervals()] == ["coarse"]
    assert _counter("scanner_tpu_test_span_seconds_total") - v0 >= 0.02


def test_failed_device_trace_is_counted(sc, monkeypatch):
    import jax

    def broken(*a, **k):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    frame = sc.io.Input([NamedVideoStream(sc, "sp")])
    out = NamedStream(sc, "level2")
    job = sc.run(sc.io.Output(sc.ops.Histogram(frame=frame), [out]),
                 PerfParams.manual(8, 16, profiler_level=2),
                 cache_mode=CacheMode.Overwrite, show_progress=False)
    prof = sc.get_profile(job).profilers[0]
    assert prof.counters.get("device_trace_failed") == 1
    assert not prof.device_traces
    assert len(list(out.load())) == N_FRAMES


def _op_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


def test_histogram_program_carries_the_op_name():
    import jax.numpy as jnp

    from scanner_tpu.kernels import imgproc, pallas_ops
    x = jnp.zeros((2, 48, 64, 3), jnp.uint8)
    for fn in (imgproc._histogram_impl, imgproc._histogram_cmp_impl):
        assert f"jit({fn.__name__})/Histogram/" in _op_names(fn.lower(x))
    assert "jit(histogram_frames)/Histogram/" in _op_names(
        pallas_ops.histogram_frames.lower(x, interpret=True))


def test_fused_chain_program_names_chain_and_members(sc):
    import jax.numpy as jnp

    from scanner_tpu.engine import evaluate as _evaluate
    frame = sc.io.Input([NamedVideoStream(sc, "sp")])
    col = sc.ops.Resize(frame=frame, width=[32], height=[24])
    col = sc.ops.Blur(frame=col, kernel_size=3, sigma=1.1)
    col = sc.ops.Histogram(frame=col)
    out = NamedStream(sc, "chain")
    sc.run(sc.io.Output(col, [out]), PerfParams.manual(8, 16),
           cache_mode=CacheMode.Overwrite, show_progress=False)
    chain_id = "Resize+Blur+Histogram"
    programs = [fn for key, fn in _evaluate._CHAIN_PROGRAMS.items()
                if [m[0] for m in key] == chain_id.split("+")]
    assert programs, list(_evaluate._CHAIN_PROGRAMS)
    text = _op_names(programs[-1].lower(
        jnp.zeros((8, 48, 64, 3), jnp.uint8)))
    for member in chain_id.split("+"):
        assert f"/{chain_id}/{member}/" in text, member
