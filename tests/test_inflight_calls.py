"""The window of device calls an evaluator keeps in flight
(engine/evaluate.py CallWindow, util/coststats.py CallClock): on the CPU,
with a kernel whose results are ready when the test says.

Held here: the wait for call k is taken before the dispatch of call k+2
and never more than two calls are un-waited, with coststats on or off;
the window lives across chunks and tasks and is empty after the
evaluator's release; a signature's first call is drained at once; a
timed sample is never shorter than the chip's time for the call and a
call found ready is counted and not timed; a call that fails on the
chip fails the task that dispatched it, names its op, takes the OOM
note and resets a stateful kernel; outputs do not depend on the depth;
the three series count what docs/observability.md says.
"""

import logging
import time
import traceback
from typing import Any, Sequence

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, PerfParams,
                         register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import evaluate as ev
from scanner_tpu.engine.batch import ColumnBatch
from scanner_tpu.graph import analysis as A
from scanner_tpu.graph import ops as O
from scanner_tpu.graph.streams_dsl import IOGenerator
from scanner_tpu.util import coststats as cs
from scanner_tpu.util import memstats as ms
from scanner_tpu.util.metrics import registry
from scanner_tpu.util.profiler import Profiler

WP = 8            # rows a call
TASK = 4 * WP     # rows a task: four calls


class XlaRuntimeError(Exception):
    """What the chip raises, by the name memstats.is_oom knows."""


class Chip:
    """The test's chip: runs its calls in the order they come.  By hand
    (`busy` None): a call is running until someone waits for it, or
    done as it is dispatched where `instant`.  By the clock: a call
    takes `busy` seconds from when the chip is free."""

    def __init__(self):
        self.log = []        # ("dispatch" | "wait" | "reset", k)
        self.calls = []
        self.instant = False
        self.busy = None
        self.fail = {}       # call number -> exception
        self._free_at = 0.0

    def dispatch(self, op, values):
        c = _ChipCall(self, len(self.calls), op)
        self.calls.append(c)
        self.log.append(("dispatch", c.k))
        if self.busy is not None:
            c.start = max(time.time(), self._free_at)
            c.done_at = self._free_at = c.start + self.busy
        c.done = self.instant
        out = np.asarray(values).view(Lazy)
        out.call = c
        return out

    def in_flight(self):
        """The most calls dispatched and not yet waited for, over the
        log."""
        worst = now = 0
        for what, _k in self.log:
            now += {"dispatch": 1, "wait": -1}.get(what, 0)
            worst = max(worst, now)
        return worst

    def order(self, what):
        return [k for w, k in self.log if w == what]


class _ChipCall:
    def __init__(self, chip, k, op):
        self.chip, self.k, self.op = chip, k, op
        self.done = False
        self.start = self.done_at = None

    def ready(self):
        if self.done_at is not None:
            return time.time() >= self.done_at
        return self.done

    def wait(self):
        self.chip.log.append(("wait", self.k))
        if self.done_at is not None:
            time.sleep(max(0.0, self.done_at - time.time()))
        self.done = True
        if self.k in self.chip.fail:
            raise self.chip.fail[self.k]


class Lazy(np.ndarray):
    """A result as the engine sees a jax.Array: `is_ready`,
    `block_until_ready`.  A view of it (rows taken) is host data."""

    call = None

    def is_ready(self):
        return self.call is None or self.call.ready()

    def block_until_ready(self):
        if self.call is not None:
            self.call.wait()
        return self


CHIP = Chip()


@register_op(name="WinSum", device=DeviceType.TPU, batch=WP)
class _WinSum(Kernel):
    """Per-row pixel sum, a call of the test's chip."""

    def cost(self, shapes):
        return {"flops": 1e6, "bytes_in": 1e6, "bytes_out": 8.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        f = np.asarray(frame, np.int64)
        return CHIP.dispatch("WinSum", f.reshape(len(f), -1).sum(axis=1))


@register_op(name="WinCount", device=DeviceType.TPU, batch=WP,
             bounded_state=0)
class _WinCount(Kernel):
    """Stateful: a running count of rows."""

    def __init__(self, config):
        super().__init__(config)
        self._n = 0

    def reset(self):
        CHIP.log.append(("reset", len(CHIP.calls)))
        self._n = 0

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        out = np.arange(self._n, self._n + len(frame))
        self._n += len(frame)
        return CHIP.dispatch("WinCount", out)


class _Src:
    is_video = False


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _series(op):
    return tuple(_counter(f"scanner_tpu_op_calls{s}_total", op=op)
                 for s in ("", "_deferred", "_blocked"))


class Rig:
    """One evaluator of Input -> `op` -> Output, driven a task or a
    chunk at a time."""

    def __init__(self, op="WinSum", rows=8 * TASK):
        io_g = IOGenerator()
        frame = io_g.Input([_Src()])
        col = getattr(O.OpGenerator(), op)(frame=frame)
        self.info = A.analyze([io_g.Output(col, [_Src()])])
        self.src = self.info.sources[0]
        self.jr = A.job_rows(self.info, 0, {self.src.id: rows})
        self.jr.work_packet_size = WP
        self.profiler = Profiler()
        self.te = ev.TaskEvaluator(self.info, self.profiler)
        self.pixels = np.arange(rows * 12, dtype=np.uint8).reshape(
            rows, 2, 2, 3)

    def run(self, lo, hi, task=0):
        plan = A.derive_task_streams(self.info, self.jr, (lo, hi),
                                     task_idx=task)
        rows = np.asarray(plan.source_rows[self.src.id], np.int64)
        res = self.te.execute_task(
            self.jr, plan,
            {self.src.id: ColumnBatch(rows, self.pixels[rows])})
        (b,) = res.values()
        return b

    def warm(self):
        """The signature's first call, out of the way."""
        self.run(0, WP)
        self.te.release()
        CHIP.__init__()


@pytest.fixture(autouse=True)
def chip(monkeypatch):
    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "0")
    CHIP.__init__()
    was = cs.enabled()
    yield CHIP
    cs.set_enabled(was)


@pytest.fixture
def rig():
    r = Rig()
    r.warm()
    yield r
    r.te.close()


# -- the bound -------------------------------------------------------------

@pytest.mark.parametrize("coststats", [True, False],
                         ids=["coststats_on", "coststats_off"])
def test_the_third_dispatch_waits_for_the_first(rig, chip, coststats):
    cs.set_enabled(coststats)
    out = rig.run(0, TASK + WP)  # five calls
    assert chip.log == [
        ("dispatch", 0), ("dispatch", 1), ("wait", 0), ("dispatch", 2),
        ("wait", 1), ("dispatch", 3), ("wait", 2), ("dispatch", 4)]
    assert chip.in_flight() == ev.CALLS_IN_FLIGHT == 2
    assert len(rig.te.calls) == 2
    assert np.array_equal(
        out.data, rig.pixels[:TASK + WP].reshape(TASK + WP, -1).sum(1))


@pytest.mark.parametrize("edge", ["task", "chunk"])
def test_the_window_lives_across(rig, chip, edge):
    """A task's (a chunk's) last calls are waited for by the next one's
    dispatches, not at its end."""
    rig.run(0, TASK, task=0)
    assert chip.order("wait") == [0, 1] and len(rig.te.calls) == 2
    if edge == "task":
        rig.run(TASK, 2 * TASK, task=1)
    else:
        rig.run(TASK, 2 * TASK, task=0)
    assert chip.order("wait") == [0, 1, 2, 3, 4, 5]
    assert chip.log[6:10] == [("wait", 2), ("dispatch", 4),
                              ("wait", 3), ("dispatch", 5)]
    assert chip.in_flight() == 2 and len(rig.te.calls) == 2


@pytest.mark.parametrize("how", ["release", "give", "close"])
def test_the_window_is_empty_after_the_evaluators_release(rig, chip, how):
    rig.run(0, TASK)
    assert len(rig.te.calls) == 2
    if how == "release":
        rig.te.release()
    elif how == "give":
        pool = ev.EvaluatorPool(key=lambda info: "k")
        rig.te.pool_key = pool._key = "k"
        pool.give(rig.te)
        assert pool._kept == {0: rig.te}
    else:
        rig.te.close()
    assert len(rig.te.calls) == 0
    assert chip.order("wait") == [0, 1, 2, 3]
    ivs = [iv for iv in rig.profiler.intervals()
           if iv.name == "evaluate:device_wait"]
    assert len(ivs) == 1 + 4 and {iv.args["op"] for iv in ivs} == {"WinSum"}


def test_a_signatures_first_call_is_drained_at_once(chip):
    r = Rig()
    calls, deferred, _ = _series("WinSum")
    try:
        r.run(0, 3 * WP)
        # the first call of the 8-row signature, then two in flight
        assert chip.log[:2] == [("dispatch", 0), ("wait", 0)]
        assert chip.order("wait") == [0] and len(r.te.calls) == 2
        # a new signature (a 4-row tail) with calls in flight: they are
        # drained ahead of it, in order, and it after them
        r.run(3 * WP, 3 * WP + 4, task=1)
        assert chip.log[4:] == [("wait", 1), ("dispatch", 3), ("wait", 2),
                                ("wait", 3)]
        assert len(r.te.calls) == 0
    finally:
        r.te.close()
    now = _series("WinSum")
    assert now[0] - calls == 4 and now[1] - deferred == 2


# -- the clock -------------------------------------------------------------

def test_a_timed_sample_is_never_shorter_than_the_calls_device_time(
        rig, chip, monkeypatch):
    timed = []
    record = cs.record_op_call
    monkeypatch.setattr(
        cs, "record_op_call",
        lambda op, dev, bucket, rows, secs, desc:
        timed.append(secs) or record(op, dev, bucket, rows, secs, desc))
    chip.busy = 0.15  # the chip sets the pace: the waits block
    _, deferred, blocked = _series("WinSum")
    rig.run(0, TASK + WP)
    rig.te.release()
    now = _series("WinSum")
    # (a host held up for a call's length by the machine's other work
    # finds one ready: that one is not timed)
    assert now[1] - deferred == 5 and 4 <= now[2] - blocked <= 5
    assert len(timed) == now[2] - blocked
    # a sample runs from its predecessor's edge as seen (a wake-up late:
    # tens of microseconds, milliseconds on a loaded host) to its own,
    # so one sample may give that wake-up to the next; their running
    # sum, which the gauges divide by, telescopes and is never short
    for k in range(1, len(timed) + 1):
        assert sum(timed[:k]) >= k * chip.busy - 1e-6, timed
    # back to back on the chip: no sample holds another call's time
    assert sorted(timed)[len(timed) // 2] < 1.5 * chip.busy, timed
    rows = [o for o in cs.op_efficiency() if o["op"] == "WinSum"]
    assert rows and all(o["cost_source"] == "hook" for o in rows)


def test_a_call_found_ready_is_counted_and_not_timed(rig, chip,
                                                     monkeypatch):
    timed = []
    monkeypatch.setattr(cs, "record_op_call",
                        lambda *a: timed.append(a) and None)
    chip.instant = True  # the host sets the pace: nothing is running
    calls, deferred, blocked = _series("WinSum")
    rig.run(0, TASK + WP)
    rig.te.release()
    now = _series("WinSum")
    assert (now[0] - calls, now[1] - deferred, now[2] - blocked) \
        == (5, 5, 0)
    assert timed == []
    ivs = [iv for iv in rig.profiler.intervals()
           if iv.name == "evaluate:device_wait"][1:]
    assert len(ivs) == 5 and not any(iv.args["blocked"] for iv in ivs)


@pytest.mark.parametrize("seen", [(True, True), (False, True),
                                  (True, False, True)],
                         ids=["edges", "after_ready", "over_ready"])
def test_the_clock_never_starts_a_call_late(seen):
    """CallClock against a chip that runs in order: whatever the waits
    saw, a timed call's seconds are its true ones or more."""
    clock = cs.CallClock()
    free = 0.0
    for k, blocked in enumerate(seen):
        t_dispatch = 1.0 * k
        start = max(t_dispatch, free)
        free = start + 2.5                  # true completion
        t_wait = free if blocked else free + 0.7
        secs = clock.done(t_dispatch, t_wait, blocked)
        if blocked:
            assert secs >= 2.5 - 1e-9, (k, secs)
        else:
            assert secs is None


# -- failures --------------------------------------------------------------

@pytest.fixture
def oom_notes(monkeypatch):
    notes = []
    monkeypatch.setattr(
        ms, "note_oom",
        lambda e, site, detail="": notes.append((site, detail)))
    return notes


@pytest.mark.parametrize("op", ["WinSum", "WinCount"])
def test_a_failing_call_fails_its_own_task(chip, oom_notes, op):
    r = Rig(op)
    try:
        r.warm()
        chip.fail[1] = XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")
        with pytest.raises(XlaRuntimeError) as err:
            r.run(0, TASK, task=3)
        # found at the wait before the fourth dispatch
        assert chip.order("dispatch") == [0, 1, 2]
        assert f"op {op}" in "".join(traceback.format_exception(err.value))
        assert oom_notes == [("dispatch", f"op {op} on default")]
        resets = [k for w, k in chip.log if w == "reset"]
        assert (resets[-1:] == [3]) == (op == "WinCount")
        # the failed task's other call has left the window
        assert len(r.te.calls) == 0 and chip.order("wait") == [0, 1, 2]
        chip.fail.clear()
        again = r.run(0, TASK, task=3)
        assert len(again) == TASK
    finally:
        r.te.close()


@pytest.mark.parametrize("where", ["next_task", "release"])
def test_a_call_that_fails_after_its_task_left_fails_no_other(
        rig, chip, oom_notes, caplog, where):
    chip.fail[3] = XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")
    first = rig.run(0, TASK, task=0)
    with caplog.at_level(logging.ERROR, logger="scanner_tpu"):
        if where == "next_task":
            out = rig.run(TASK, 2 * TASK, task=1)
            assert len(out) == TASK
        else:
            rig.te.release()
    assert oom_notes == [("dispatch", "op WinSum on default")]
    assert any("WinSum" in r.getMessage() and "(0, 0)" in r.getMessage()
               for r in caplog.records)
    # its own task had its rows handed on; it fails where they are
    # fetched (a jax.Array of a failed call raises there)
    assert len(first) == TASK


# -- the depth changes no output -------------------------------------------

@pytest.fixture(scope="module")
def db(tmp_path_factory):
    root = tmp_path_factory.mktemp("inflight")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=48, width=64, height=48, fps=24,
                         keyint=16)
    sc = Client(db_path=str(root / "db"))
    sc.ingest_videos([("w", vid)])
    yield sc
    sc.stop()


def _graph(sc, kind, frame):
    if kind == "kernel":
        return sc.ops.Histogram(frame=frame)
    return sc.ops.Histogram(
        frame=sc.ops.Resize(frame=frame, width=[32], height=[24]))


@pytest.mark.parametrize("kind", ["kernel", "fused"])
def test_outputs_do_not_depend_on_the_depth(db, monkeypatch, kind):
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    got = {}
    for depth in (2, 1):
        monkeypatch.setattr(ev, "CALLS_IN_FLIGHT", depth)
        db._evaluators.close()
        before = [_counter("scanner_tpu_op_rows_total", op=o)
                  for o in ("Histogram", "Resize+Histogram")]
        frame = db.io.Input([NamedVideoStream(db, "w")])
        out = NamedStream(db, f"o_{kind}_{depth}")
        db.run(db.io.Output(_graph(db, kind, frame), [out]),
               PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
               show_progress=False)
        ran = [_counter("scanner_tpu_op_rows_total", op=o) - b
               for o, b in zip(("Histogram", "Resize+Histogram"), before)]
        assert ran == ([48, 0] if kind == "kernel" else [0, 48])
        got[depth] = list(out.load())
    assert len(got[2]) == 48
    assert all(np.array_equal(a, b) for a, b in zip(got[2], got[1]))


# -- a pipeline's evaluator ------------------------------------------------

def test_a_pipelines_evaluator_keeps_two_in_flight_and_releases_none(
        db, chip):
    """Through Client.run: streamed tasks, the evaluator taken from the
    pool and given back."""
    series = _series("WinSum")
    for i in range(2):
        frame = db.io.Input([NamedVideoStream(db, "w")])
        out = NamedStream(db, f"o_pipe{i}")
        db.run(db.io.Output(db.ops.WinSum(frame=frame), [out]),
               PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
               show_progress=False)
        assert len(list(out.load())) == 48
    assert len(chip.calls) == 12
    assert sorted(chip.order("wait")) == list(range(12))
    assert chip.in_flight() == 2
    now = _series("WinSum")
    # all but the signature's first call had their wait taken late
    assert (now[0] - series[0], now[1] - series[1]) == (12, 11)
    assert now[2] - series[2] == 11  # by hand, a call runs until waited for
