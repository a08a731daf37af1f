"""A bounded-state op on the device, Input -> BackgroundSubtraction
(bounded_state=60, its average image a jax.Array on the chip) -> Output,
through `Client.run` against the benchmark's plain reference
(benchmark/reference/BackgroundSubtraction.py) at the benchmark's cut:
tasks of 32 rows, packets of 16, a warm-up of 60.

A bounded-state task with a warm-up stands alone: its plan begins with
the rows that make its state and the engine resets the kernel at its
first compute row.  So the run keeps its loaders, its pipeline instances
and its evaluators, and a row's value depends on the cut alone: not on
the order the tasks ran in, nor on how many loaders or instances ran
them, nor on what the kernel ran before.
"""

import json
import os
import struct
import sys
from typing import Any, Sequence

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, PerfParams,
                         register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import framecache as fc
from scanner_tpu.engine.executor import LocalExecutor
from scanner_tpu.graph import ops as O
from scanner_tpu.util.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, N_FRAMES, KEYINT = 96, 128, 256, 32
with open(os.path.join(REPO, "benchmark", "configs", "bgsub_1080p.json")) as f:
    CONFIG = json.load(f)
CFG = {"video": {"height": H, "width": W}, "graph": CONFIG["graph"]}
WARMUP, TASK_ROWS = CONFIG["graph"]["warmup"], CONFIG["graph"]["task_rows"]
# the benchmark's cut: what PerfParams.estimate() gives the 1080p video
PERF = PerfParams.manual(16, TASK_ROWS)
# rows a 256-row table's eight tasks compute: 32 + 64 + 6 x 92
PLAN_ROWS = sum(min(s + TASK_ROWS, WARMUP + TASK_ROWS)
                for s in range(0, N_FRAMES, TASK_ROWS))


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator and reference, by their own names
    (they import each other so)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from reference import BackgroundSubtraction
        yield clipgen, BackgroundSubtraction
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def clip(tmp_path_factory, bench):
    path = str(tmp_path_factory.mktemp("bgsub") / "clip.mp4")
    bench[0].encode_clip(path, 7, N_FRAMES, H, W, 30, KEYINT)
    return path


@pytest.fixture()
def sc(tmp_path, monkeypatch, clip):
    """A client on the accelerator path of the CPU mesh: device staging,
    per-chip instances, the YUV420 wire converted on the device (what
    the reference reads), the frame cache."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    client = Client(db_path=str(tmp_path / "db"))
    client.ingest_videos([("movie", clip)])
    yield client
    client.stop()
    fc.set_enabled(was)
    fc.cache().clear()


@pytest.fixture(scope="module")
def wires(clip, tmp_path_factory):
    """The table's wire frames by a decode of their own."""
    client = Client(db_path=str(tmp_path_factory.mktemp("bgsub_w") / "db"))
    client.ingest_videos([("movie", clip)])
    auto = scv.open_automata(client._db, "movie", output_format="yuv420")
    try:
        return list(np.asarray(auto.get_frames(list(range(N_FRAMES)))))
    finally:
        auto.close()
        client.stop()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _run(sc, name, perf=PERF, op="BackgroundSubtraction", **kw):
    col = getattr(sc.ops, op)(
        frame=sc.io.Input([NamedVideoStream(sc, "movie")]),
        **CONFIG["graph"]["args"])
    out = NamedStream(sc, name)
    job = sc.run(sc.io.Output(col, [out]), perf,
                 cache_mode=CacheMode.Overwrite, show_progress=False, **kw)
    return job, list(out.load())


def _intervals(sc, job, name):
    return [iv for p in sc.get_profile(job).profilers
            for iv in p.intervals() if iv.name == name]


def _stated(bench, wires):
    """Every row's stated count, by the reference alone."""
    R = bench[1]
    alpha = np.float32(CONFIG["graph"]["args"]["alpha"])
    level = np.float32(255.0 * CONFIG["graph"]["args"]["threshold"])
    rgb = {}

    def rgb_of(r):
        if r not in rgb:
            rgb[r] = R.wire.to_rgb(wires[r], H, W)
        return rgb[r]

    want = {}
    for t in range(0, N_FRAMES, TASK_ROWS):
        rows = set(range(t, t + TASK_ROWS))
        want.update(R.recurrence(rgb_of, R.task_start(CFG, t), max(rows),
                                 rows, alpha, level))
    return [struct.pack("=q", want[r]) for r in range(N_FRAMES)]


def _assert_stated(got, want):
    """The program's packet is one fused program, the reference's step
    another: where the compiler contracts a multiply and an add
    differently (XLA's CPU backend does), a pixel within a rounding of
    the threshold falls the other way.  One pixel in a row, in few rows."""
    assert len(got) == len(want)
    off = [abs(struct.unpack("=q", g)[0] - struct.unpack("=q", w)[0])
           for g, w in zip(got, want)]
    assert max(off) <= 1 and sum(off) <= len(want) // 32, off


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "whole_task"])
def test_op_agrees_with_the_reference_over_eight_tasks(sc, bench, wires,
                                                       streamed):
    """Every committed row stands within the reference's limits of the
    stated recurrence, each task's first row and the rows whose warm-up
    is clipped at row 0 among them; a task streamed as two packets
    commits what the task loaded whole does."""
    R = bench[1]
    perf = PerfParams.manual(16, TASK_ROWS, stream_work_packets=streamed)
    job, got = _run(sc, f"ref_{streamed}", perf)
    assert len(got) == N_FRAMES
    values = R.compare(CFG, wires, [np.asarray(x) for x in got], seed=7,
                       rows=[list(range(N_FRAMES))], window_wires=[{}])
    # at 128x96 one pixel is 8e-5 of the frame, over a limit made for
    # 1080p: the count is held to a pixel here, the rest to the limits
    assert values.pop("bg_count_gap") * H * W <= 1.5
    assert all(values[k] <= R.LIMITS[k] for k in values), values
    _assert_stated(got, _stated(bench, wires))
    # the counts are not trivially 0: the shapes move
    counts = [struct.unpack("=q", x)[0] for x in got]
    assert counts[0] == 0 and max(counts) > 50


def test_the_state_is_a_device_array_on_the_instance_s_chip(sc):
    import jax
    from scanner_tpu.engine import evaluate as ev
    _run(sc, "placed", pipeline_instances=2)
    kernels = {te.instance: ki for te in ev.live_evaluators()
               for ki in te.kernels.values()
               if ki.node.name == "BackgroundSubtraction"}
    assert sorted(kernels) == [0, 1]
    for i, ki in kernels.items():
        avg = ki.kernel._avg
        assert isinstance(avg, jax.Array) and avg.dtype == np.float32
        assert avg.shape == (H, W, 3)
        assert avg.devices() == {jax.local_devices()[i]}
        ki.kernel.reset()
        assert ki.kernel._avg is None


def test_a_row_depends_on_the_cut_alone(sc, bench, wires, monkeypatch):
    """One loader or five, tasks handed in reversed order, two pipeline
    instances: the same rows, the stated ones."""
    want = _stated(bench, wires)
    monkeypatch.setattr(sc._executor, "num_load_workers", 1)
    job, one = _run(sc, "one_loader", pipeline_instances=1)
    pipeline, = _intervals(sc, job, "run:pipeline")
    assert (pipeline.args["loaders"], pipeline.args["instances"]) == (1, 1)
    monkeypatch.setattr(sc._executor, "num_load_workers", 5)
    job, five = _run(sc, "five_loaders", pipeline_instances=1)
    pipeline, = _intervals(sc, job, "run:pipeline")
    assert (pipeline.args["loaders"], pipeline.args["instances"]) == (5, 1)
    job, two = _run(sc, "two_instances", pipeline_instances=2)
    pipeline, = _intervals(sc, job, "run:pipeline")
    assert pipeline.args["instances"] == 2
    assert len({iv.thread for iv in _intervals(
        sc, job, "evaluate:BackgroundSubtraction")}) == 2

    run_pipeline = LocalExecutor._run_pipeline

    def backwards(self, info, work, *args, **kw):
        return run_pipeline(self, info, list(reversed(work)), *args, **kw)

    monkeypatch.setattr(LocalExecutor, "_run_pipeline", backwards)
    monkeypatch.setattr(sc._executor, "num_load_workers", 1)
    job, reversed_ = _run(sc, "reversed", pipeline_instances=1)
    starts = [iv.args["task"] for iv in _intervals(sc, job, "evaluate")]
    assert starts == sorted(starts, reverse=True)
    assert one == five == two == reversed_
    _assert_stated(one, want)


def test_a_second_run_adopts_the_evaluators_and_resets_every_task(
        sc, bench, wires):
    """The kept evaluator's kernel holds the average the first run left;
    the second run's tasks start from a reset all the same."""
    _, first = _run(sc, "kept_1", pipeline_instances=1)
    reuses = _counter("scanner_tpu_evaluator_reuses_total")
    resets = _counter("scanner_tpu_state_resets_total",
                      op="BackgroundSubtraction")
    job, second = _run(sc, "kept_2", pipeline_instances=1)
    assert _counter("scanner_tpu_evaluator_reuses_total") == reuses + 1
    setup, = _intervals(sc, job, "evaluate:setup")
    assert setup.args["reused"] is True
    assert _counter("scanner_tpu_state_resets_total",
                    op="BackgroundSubtraction") \
        == resets + N_FRAMES // TASK_ROWS
    assert first == second
    _assert_stated(first, _stated(bench, wires))


def test_the_series_count_what_the_plan_says(sc):
    """648 - 256 warm-up rows and 8 resets a 256-row table at the stated
    cut, in the series and on the op's spans; the reset of a device
    kernel has a span of its own."""
    assert PLAN_ROWS == 648
    before = {s: _counter(s, op="BackgroundSubtraction") for s in (
        "scanner_tpu_state_warmup_rows_total",
        "scanner_tpu_state_resets_total", "scanner_tpu_op_rows_total")}
    job, got = _run(sc, "counted", pipeline_instances=1)
    after = {s: _counter(s, op="BackgroundSubtraction") for s in before}
    delta = {s.split("scanner_tpu_")[1]: after[s] - before[s]
             for s in before}
    assert delta == {"state_warmup_rows_total": PLAN_ROWS - N_FRAMES,
                     "state_resets_total": N_FRAMES // TASK_ROWS,
                     "op_rows_total": PLAN_ROWS}
    spans = _intervals(sc, job, "evaluate:BackgroundSubtraction")
    assert len(spans) == 2 * (N_FRAMES // TASK_ROWS)  # two packets a task
    assert sum(iv.args["warmup_rows"] for iv in spans) \
        == PLAN_ROWS - N_FRAMES
    assert sum(iv.args["resets"] for iv in spans) == N_FRAMES // TASK_ROWS
    assert sum(iv.args["rows"] for iv in spans) == PLAN_ROWS
    # a task's second packet goes on from its first: no reset, no warm-up
    assert sorted(iv.args["resets"] for iv in spans) == [0] * 8 + [1] * 8
    resets = _intervals(sc, job, "evaluate:reset")
    assert len(resets) == N_FRAMES // TASK_ROWS
    for r in resets:
        assert any(p.thread == r.thread and p.start <= r.start
                   and r.end <= p.end for p in spans)


def test_the_configuration_states_the_program_s_cut(sc):
    """`graph.task_rows` is PerfParams.estimate()'s io packet for the
    configuration's video, `graph.warmup` the op's registered warm-up,
    and the op's defaults are the configuration's arguments."""
    v = CONFIG["video"]

    class Video1080p:
        is_video = True

        def estimate_geometry(self):
            return v["height"] * v["width"] * 3, v["keyint"]

    from scanner_tpu.graph import analysis as A
    frame = O.OpNode(O.INPUT_OP, {}, extra={"streams": [Video1080p()]})
    info = A.analyze([O.OpNode(O.OUTPUT_OP, {"col": frame.outputs[0]},
                               extra={"streams": [Video1080p()]})])
    perf = sc._executor._estimate_perf(info, PerfParams.estimate())
    assert perf.io_packet_size == CONFIG["graph"]["task_rows"] == 32
    assert perf.work_packet_size == 16
    spec = O.registry.get("BackgroundSubtraction")
    assert spec.bounded_state == CONFIG["graph"]["warmup"] == 60
    assert spec.device == DeviceType.TPU and spec.batch == 16
    import inspect
    from scanner_tpu.kernels.imgproc import BackgroundSubtraction
    params = inspect.signature(BackgroundSubtraction.__init__).parameters
    assert {k: params[k].default for k in ("alpha", "threshold")} \
        == CONFIG["graph"]["args"]


def test_the_op_s_programs_are_compiled_in_warm_up(monkeypatch):
    """The packet lengths the stated cut produces (16, and 12 where a
    task's 76-row first packet ends) are compiled by the evaluator's
    warm-up, on its chip, and the example rows leave no state behind; a
    re-warm mid-run leaves a stateful kernel alone."""
    import jax
    from scanner_tpu.engine import evaluate as ev
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.kernels import imgproc
    from scanner_tpu.util import coststats
    from scanner_tpu.util.profiler import Profiler
    assert ev._state_call_lengths(16, 60, 16) == [16, 12]
    assert ev._state_call_lengths(16, 3, 8) == [16, 11, 8]
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    frame = O.OpNode(O.INPUT_OP, {}, extra={"streams": [object()]})
    node = O.OpNode("BackgroundSubtraction", {"frame": frame.outputs[0]})
    info = A.analyze([O.OpNode(O.OUTPUT_OP, {"col": node.outputs[0]},
                               extra={"streams": [object()]})])
    imgproc._bgsub_impl.clear_cache()
    seen = len(coststats.compile_ledger())
    te = ev.TaskEvaluator(info, Profiler(), precompile=(H, W, 16),
                          instance=1, instances=2)
    try:
        te._precompile_thread.join(timeout=120)
        ki, = te.kernels.values()
        mine = [e for e in coststats.compile_ledger()[seen:]
                if e["op"] == "BackgroundSubtraction"]
        assert [e["signature"] for e in mine] == ["warmup:b16", "warmup:b12"]
        assert {e["device"] for e in mine} \
            == {ev.device_label(jax.local_devices()[1])}
        assert imgproc._bgsub_impl._cache_size() == 2
        assert ki._warm_state == "done" and ki.kernel._avg is None
        assert te.rewarm() == 0
    finally:
        te.close()


@register_op(name="BsTaskCounter", device=DeviceType.TPU, batch=16,
             bounded_state=0)
class BsTaskCounter(Kernel):
    """bounded_state=0: a task's rows continue the last task's."""

    def __init__(self, config, alpha=None, threshold=None):
        super().__init__(config)
        self.n = 0

    def reset(self):
        self.n = 0

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        out = [self.n + i for i in range(len(frame))]
        self.n += len(frame)
        return out


def test_a_warm_up_of_zero_still_runs_in_order_on_one_instance(
        sc, monkeypatch):
    """No warm-up rows, so nothing makes a task's state but the task
    before it: the run serialises as an unbounded one does, and the
    count runs on from task to task."""
    monkeypatch.setattr(sc._executor, "num_load_workers", 5)
    job, got = _run(sc, "continued", op="BsTaskCounter",
                    pipeline_instances=2)
    pipeline, = _intervals(sc, job, "run:pipeline")
    assert (pipeline.args["loaders"], pipeline.args["instances"]) == (1, 1)
    assert got == list(range(N_FRAMES))


@pytest.mark.parametrize("name, stateful, alone, warmup", [
    ("BackgroundSubtraction", True, True, 60),
    ("BsTaskCounter", True, False, 0),
    ("Histogram", False, True, None)])
def test_which_nodes_stand_alone(name, stateful, alone, warmup):
    col = O.OpNode(O.INPUT_OP, {}).outputs[0]
    node = O.OpNode(name, {"frame": col})
    assert node.spec.is_stateful is stateful
    assert node.stands_alone() is alone
    assert node.bounded_warmup() == warmup
    # the node's own bounded_state= wins over the registration's
    over = O.OpNode(name, {"frame": col}, warmup=5)
    assert over.bounded_warmup() == (5 if stateful else None)
    assert over.stands_alone()


@register_op(name="BsRowsSinceReset", bounded_state=3)
class BsRowsSinceReset(Kernel):
    """How many rows the kernel saw before this one since its reset."""

    def __init__(self, config):
        super().__init__(config)
        self.n = 0

    def reset(self):
        self.n = 0

    def execute(self, frame: FrameType) -> Any:
        self.n += 1
        return self.n - 1


@register_op(name="BsPair", stencil=[-1, 0])
class BsPair(Kernel):
    def execute(self, seen: Sequence[Any]) -> Any:
        return (int(seen[0]), int(seen[1]))


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "whole_task"])
def test_a_row_asked_for_again_is_warmed_up_again(sc, streamed):
    """A consumer's stencil reaches back over a packet's edge to a row
    the bounded-state kernel has passed: the packet replays its warm-up
    for it, it does not hand out a row computed from a fresh state."""
    seen = sc.ops.BsRowsSinceReset(
        frame=sc.io.Input([NamedVideoStream(sc, "movie")]))
    out = NamedStream(sc, f"pairs_{streamed}")
    sc.run(sc.io.Output(sc.ops.BsPair(seen=seen), [out]),
           PerfParams.manual(8, 32, stream_work_packets=streamed),
           cache_mode=CacheMode.Overwrite, show_progress=False)
    pairs = list(out.load())
    assert len(pairs) == N_FRAMES
    for row, (before, here) in enumerate(pairs):
        assert here >= min(3, row), (row, before, here)
        assert before >= min(3, max(row - 1, 0)), (row, before, here)
        assert here == before + 1 or row == 0, (row, before, here)
