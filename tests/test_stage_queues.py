"""The hand-offs between the pipeline's stages (engine/executor.py
`_StageQueue`) end by being closed, and fail by being aborted: no stage
thread sits out a time-out at a run's end, and none is left blocked on a
neighbour that has died.
"""

import sys
import threading
import time
from typing import Any

import pytest

from scanner_tpu import (CacheMode, Client, FrameType, Kernel, NamedStream,
                         NamedVideoStream, PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine.executor import LocalExecutor, TaskItem, _StageQueue

N_FRAMES = 64
STAGE_THREADS = ("load-", "eval-", "save-")


class Boom(Exception):
    pass


# ---------------------------------------------------------------- the queue

def _started(target, *args):
    t = threading.Thread(target=target, args=args, daemon=True)
    t.start()
    return t


def _ended(threads, timeout=5.0):
    for t in threads:
        t.join(timeout)
    return not any(t.is_alive() for t in threads)


def test_close_hands_out_what_is_left_then_none_to_every_consumer():
    q = _StageQueue(4)
    for i in range(3):
        assert q.put(i)
    q.close()
    got = []
    consumers = [_started(lambda: got.append([x for x in iter(q.get, None)]))
                 for _ in range(3)]
    assert _ended(consumers)
    assert sorted(x for part in got for x in part) == [0, 1, 2]
    assert q.get() is None and q.qsize() == 0


def test_close_wakes_a_consumer_that_is_already_waiting():
    q = _StageQueue(1)
    woke = []
    t = _started(lambda: woke.append((q.get(), time.time())))
    time.sleep(0.1)
    assert t.is_alive()
    t_close = time.time()
    q.close()
    assert _ended([t])
    assert woke[0][0] is None and woke[0][1] - t_close < 0.05


def test_put_blocks_while_full_and_a_get_lets_it_through():
    q = _StageQueue(1)
    assert q.put("a")
    placed = []
    t = _started(lambda: placed.append(q.put("b")))
    time.sleep(0.1)
    assert t.is_alive() and q.qsize() == 1
    assert q.get() == "a"
    assert _ended([t])
    assert placed == [True] and q.get() == "b"


def test_abort_wakes_blocked_put_and_get_and_keeps_them_out():
    full, empty = _StageQueue(1), _StageQueue(1)
    assert full.put("kept")
    out = {}
    threads = [
        _started(lambda: out.__setitem__("put", full.put("dropped"))),
        _started(lambda: out.__setitem__("get", empty.get()))]
    time.sleep(0.1)
    assert all(t.is_alive() for t in threads)
    t_abort = time.time()
    full.abort()
    empty.abort()
    assert _ended(threads)
    assert time.time() - t_abort < 0.05
    assert out == {"put": False, "get": None}
    # aborted for good: nothing goes in, nothing comes out
    assert full.qsize() == 1 and full.get() is None
    assert empty.put("late") is False


@pytest.mark.parametrize("bounded", [None, lambda item: item[1] % 3 == 0],
                         ids=["all", "some"])
def test_every_item_arrives_once_under_contention(bounded):
    """More threads than cores on a queue of two slots, the interpreter
    switching threads every 10 us: no item lost or doubled, and every
    consumer ends on close; the same where the bound counts only some
    items and the others are queued past it."""
    n_producers, n_consumers, per_producer = 12, 12, 300
    q = _StageQueue(2, bounded)
    got = [[] for _ in range(n_consumers)]

    def produce(k):
        for i in range(per_producer):
            assert q.put((k, i))

    def consume(k):
        got[k].extend(iter(q.get, None))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        consumers = [_started(consume, k) for k in range(n_consumers)]
        producers = [_started(produce, k) for k in range(n_producers)]
        assert _ended(producers, timeout=60.0)
        q.close()
        assert _ended(consumers, timeout=60.0)
    finally:
        sys.setswitchinterval(interval)
    items = [x for part in got for x in part]
    assert len(items) == n_producers * per_producer
    assert set(items) == {(k, i) for k in range(n_producers)
                          for i in range(per_producer)}
    # each producer's items leave in the order they went in
    for part in got:
        for k in range(n_producers):
            mine = [i for kk, i in part if kk == k]
            assert mine == sorted(mine)


# ------------------------------------------------------------- the pipeline

@register_op(name="StageTestRaises")
class StageTestRaises(Kernel):
    """Holds the evaluate stage 0.3 s, long enough for the loaders to
    fill their queue, then fails."""

    raised_at = None

    def execute(self, frame: FrameType) -> Any:
        time.sleep(0.3)
        StageTestRaises.raised_at = time.time()
        raise Boom("evaluate")


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("stageq")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=16)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("sq", vid)])
    yield client
    client.stop()


@pytest.fixture
def puts(monkeypatch):
    """Every `_StageQueue.put` of the test: (thread, entered, left,
    placed)."""
    calls = []
    put = _StageQueue.put

    def noted(self, item):
        t0 = time.time()
        placed = put(self, item)
        calls.append((threading.current_thread().name, t0, time.time(),
                      placed))
        return placed

    monkeypatch.setattr(_StageQueue, "put", noted)
    return calls


def _stage_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(STAGE_THREADS)]


def _outputs(sc, name, op="Histogram"):
    frame = sc.io.Input([NamedVideoStream(sc, "sq")])
    return [sc.io.Output(getattr(sc.ops, op)(frame=frame),
                         [NamedStream(sc, name)])]


def _caught_in_put(puts, stage, t_err):
    """The puts of `stage` threads that were blocked when the error was
    recorded."""
    return [c for c in puts if c[0].startswith(stage)
            and c[1] < t_err - 0.05 and c[2] >= t_err - 0.005]


def test_evaluate_error_frees_a_loader_blocked_on_a_full_queue(sc, puts):
    ex = LocalExecutor(sc._db, num_load_workers=2, num_save_workers=2)
    StageTestRaises.raised_at = None
    with pytest.raises(Boom, match="evaluate"):
        ex.run(_outputs(sc, "eval_err", op="StageTestRaises"),
               PerfParams.manual(8, 8, queue_size_per_pipeline=1),
               cache_mode=CacheMode.Overwrite)
    t_back = time.time()
    t_err = StageTestRaises.raised_at
    assert t_back - t_err < 1.0
    assert _stage_threads() == []
    # a loader sat in put while the op slept, and abort let it go
    held = _caught_in_put(puts, "load-", t_err)
    assert held, puts
    assert all(not placed and left - t_err < 0.25
               for _, _, left, placed in held), held


def test_save_error_frees_evaluators_blocked_on_save_q(sc, puts,
                                                       monkeypatch):
    raised_at = []

    def failing_save(self, info, w):
        time.sleep(0.3)
        raised_at.append(time.time())
        raise Boom("save")

    monkeypatch.setattr(LocalExecutor, "_save_task", failing_save)
    ex = LocalExecutor(sc._db, num_load_workers=2, num_save_workers=1,
                       pipeline_instances=2)
    with pytest.raises(Boom, match="save"):
        ex.run(_outputs(sc, "save_err"),
               PerfParams.manual(8, 8, queue_size_per_pipeline=1),
               cache_mode=CacheMode.Overwrite)
    t_back = time.time()
    assert len(raised_at) == 1
    assert t_back - raised_at[0] < 1.0
    assert _stage_threads() == []
    held = _caught_in_put(puts, "eval-", raised_at[0])
    assert held, puts
    assert all(not placed and left - raised_at[0] < 0.25
               for _, _, left, placed in held), held


def test_waiting_source_and_a_revoked_task_end_in_a_normal_close(sc):
    """The cluster worker's shape: a source that answers "wait" before
    it is exhausted, and an `on_start` that drops one task."""
    ex = LocalExecutor(sc._db, num_load_workers=2, num_save_workers=2)
    info, jobs = ex.prepare(_outputs(sc, "waited"), PerfParams.manual(8, 8),
                            cache_mode=CacheMode.Overwrite)
    work = [TaskItem(job, t, rng) for job in jobs
            for t, rng in enumerate(job.tasks)]
    assert len(work) == 8
    answers = ["wait"] + work[:4] + ["wait"] + work[4:] + ["wait"]
    lock = threading.Lock()
    none_at, done = [], []

    def source():
        with lock:
            if answers:
                return answers.pop(0)
            none_at.append(time.time())
            return None

    def on_done(w):
        done.append((w.task_idx, time.time()))

    t0 = time.time()
    n = ex.run_pipeline(info, source,
                        on_start=lambda w: w.task_idx != 3,
                        on_done=on_done, queue_size=1)
    t_back = time.time()
    assert n == 7
    assert sorted(t for t, _ in done) == [0, 1, 2, 4, 5, 6, 7]
    assert len(none_at) == 2           # each loader was told once
    # three waits of 0.2 s on two loaders, and nothing timed after them
    assert t_back - max(none_at + [t for _, t in done]) < 0.1
    assert t_back - t0 < 3 * 0.2 + 1.0
    assert _stage_threads() == []
