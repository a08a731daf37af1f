"""The width of the load stage (engine/evaluate.py default_load_workers):
resolved at each run from the cores the process may use, the run's
evaluator instances, its tasks and, where they load whole, its queue
depth; an explicit `num_load_workers` wins as given.  A streaming task
takes no place in the evaluate queue's bound, so every loader decodes.
However many loaders a run starts, its rows come out the same, in the
same order.
"""

import os
import queue
import struct
import threading
import time
import types
from typing import Any

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, FrameType, Kernel, NamedStream,
                         NamedVideoStream, PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import evaluate as _evaluate
from scanner_tpu.engine.evaluate import default_load_workers
from scanner_tpu.util.metrics import registry

N_FRAMES = 128
# 16 tasks of 8 rows, each streamed as two 4-row chunks
STREAMING = PerfParams.manual(4, 8)


def _host(monkeypatch, affinity, cpu_count):
    """A host of `cpu_count` cores whose affinity mask leaves this
    process `affinity` of them (None = a platform without masks)."""
    monkeypatch.setattr(os, "cpu_count", lambda: cpu_count)
    if affinity is None:
        monkeypatch.delattr(os, "sched_getaffinity", raising=False)
    else:
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(affinity)), raising=False)


@pytest.mark.parametrize(
    "affinity, cpu_count, kw, want",
    [
        # an explicit count wins as given: 1, one past every cap, and
        # one above the run's task count
        (13, 13, dict(configured=1, tasks=32), 1),
        (13, 13, dict(configured=7, tasks=32), 7),
        (2, 2, dict(configured=3, tasks=1), 3),
        # the one-chip host: 13 cores less an evaluator, the savers and
        # the main thread are 10, and a streaming run takes them all:
        # each loader decodes into its own task's chunk queue
        (13, 13, dict(tasks=32), 10),
        # the four-chip host: 30 cores less four evaluators and two
        (30, 30, dict(instances=4, queues=4, tasks=128), 24),
        # one shared queue or four: a streaming run's width is the host's
        (30, 30, dict(instances=4, queues=1, tasks=128), 24),
        # tasks that load whole keep the pipeline's depth: the queue of
        # 4 and the evaluator's own task are 5 loaded at once, 20 on
        # four chips, 8 behind one shared queue
        (13, 13, dict(tasks=32, streaming=False), 5),
        (30, 30, dict(instances=4, queues=4, tasks=128, streaming=False),
         20),
        (30, 30, dict(instances=4, queues=1, tasks=128, streaming=False),
         8),
        # a deeper queue is bounded by the cores
        (13, 13, dict(qsize=16, tasks=64, streaming=False), 10),
        # small hosts: never below one
        (2, 2, dict(tasks=32), 1),
        (1, 1, dict(instances=4, queues=4, tasks=32), 1),
        (4, 4, dict(tasks=32), 1),
        # the mask, not the machine: 6 usable cores of 64
        (6, 64, dict(tasks=32), 3),
        # no masks on this platform: the machine's count
        (None, 8, dict(tasks=32), 5),
        (None, None, dict(tasks=32), 1),
        # never more loaders than tasks; 0 = the run cannot know
        (13, 13, dict(tasks=1), 1),
        (13, 13, dict(tasks=3), 3),
        (13, 13, dict(tasks=0), 10),
        (13, 13, dict(tasks=0, streaming=False), 5),
        (13, 13, dict(configured=0, tasks=2), 2),
        # a loader's decoder threads are cores too
        (13, 13, dict(tasks=32, decoder_threads=2), 5),
        (13, 13, dict(tasks=32, decoder_threads=4), 2),
        # an explicit count wins over the depth as well
        (13, 13, dict(configured=7, tasks=32, streaming=False), 7),
    ])
def test_resolver(monkeypatch, affinity, cpu_count, kw, want):
    _host(monkeypatch, affinity, cpu_count)
    assert default_load_workers(**kw) == want


def test_resolver_bounds(monkeypatch):
    """Derived counts lie in [1, the host's spare cores] and never above
    the task count, whatever the host; where tasks load whole, nor above
    instances x (qsize + 1)."""
    for cores in (1, 2, 3, 8, 13, 30, 224):
        _host(monkeypatch, cores, cores)
        for instances in (1, 2, 4, 8):
            for queues in (1, instances):
                for qsize in (1, 4, 8):
                    for tasks in (0, 1, 2, 7, 1000):
                        whole, streaming = (default_load_workers(
                            None, instances=instances, queues=queues,
                            qsize=qsize, tasks=tasks, streaming=s)
                            for s in (False, True))
                        assert 1 <= whole <= instances * (qsize + 1)
                        assert whole <= streaming
                        spare = max(1, cores - instances - 2)
                        assert streaming == min(spare, tasks or spare)


@register_op(name="LoadWidthTracker", unbounded_state=True)
class LoadWidthTracker(Kernel):
    """Stateful: its output is the count of rows it has seen, so a
    reordered or doubly evaluated row would show."""

    def __init__(self, config):
        super().__init__(config)
        self.seen = 0

    def reset(self):
        self.seen = 0

    def execute(self, ignore: FrameType) -> Any:
        self.seen += 1
        return struct.pack("=q", self.seen)


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("loadw")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=16)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("lw", vid)])
    yield client
    client.stop()


GRAPHS = {
    "histogram": lambda sc, f: sc.ops.Histogram(frame=f),
    # Histogram -> HistogramDelta: stencil=[-1, 0], one row of
    # back-reach over every chunk and task boundary
    "shot": lambda sc, f: sc.ops.HistogramDelta(
        hist=sc.ops.Histogram(frame=f)),
    "stride": lambda sc, f: sc.ops.Histogram(
        frame=sc.streams.Stride(f, [{"stride": 3}])),
    "stateful": lambda sc, f: sc.ops.LoadWidthTracker(ignore=f),
}


def _run(sc, monkeypatch, name, graph, loaders, perf=STREAMING):
    """One run with `num_load_workers` = `loaders` (None = derived);
    returns (rows, run:pipeline args, load-* threads started)."""
    monkeypatch.setattr(sc._executor, "num_load_workers", loaders)
    started = []
    start = threading.Thread.start

    def spy(thread):
        started.append(thread.name)
        start(thread)

    frame = sc.io.Input([NamedVideoStream(sc, "lw")])
    out = NamedStream(sc, name)
    with monkeypatch.context() as m:
        m.setattr(threading.Thread, "start", spy)
        job = sc.run(sc.io.Output(GRAPHS[graph](sc, frame), [out]), perf,
                     cache_mode=CacheMode.Overwrite, show_progress=False)
    rows = [np.asarray(r) if not isinstance(r, bytes) else r
            for r in out.load()]
    pipeline, = [iv for p in sc.get_profile(job).profilers
                 for iv in p.intervals() if iv.name == "run:pipeline"]
    return rows, pipeline.args, [n for n in started
                                 if n.startswith("load-")]


def _gauge():
    sample, = registry().snapshot()["scanner_tpu_load_workers"]["samples"]
    return sample["value"]


def _loaders_total():
    return sum(s["value"] for s in registry().snapshot().get(
        "scanner_tpu_run_loaders_total", {}).get("samples", []))


@pytest.mark.parametrize("graph", ["histogram", "shot", "stride"])
def test_six_loaders_give_one_loader_s_rows(sc, monkeypatch, graph):
    one, args1, started1 = _run(sc, monkeypatch, f"{graph}_1", graph, 1)
    six, args6, started6 = _run(sc, monkeypatch, f"{graph}_6", graph, 6)
    assert (args1["loaders"], len(started1)) == (1, 1)
    assert (args6["loaders"], len(started6)) == (6, 6)
    want = (N_FRAMES + 2) // 3 if graph == "stride" else N_FRAMES
    assert len(one) == len(six) == want
    for i, (a, b) in enumerate(zip(one, six)):
        assert np.array_equal(a, b), (graph, i)


def test_stateful_graph_starts_one_loader(sc, monkeypatch):
    """Order is correctness for a stateful op: one loader and one
    evaluator, whatever was asked for and whatever the host has."""
    _host(monkeypatch, 30, 30)
    for name, loaders in (("st_derived", None), ("st_six", 6)):
        rows, args, started = _run(sc, monkeypatch, name, "stateful",
                                   loaders)
        assert started == ["load-0"]
        assert (args["loaders"], args["instances"]) == (1, 1)
        assert _gauge() == 1
        assert [struct.unpack("=q", r)[0] for r in rows] \
            == list(range(1, N_FRAMES + 1))


def test_one_task_run_starts_one_loader(sc, monkeypatch):
    _host(monkeypatch, 30, 30)
    rows, args, started = _run(sc, monkeypatch, "one_task", "histogram",
                               None, perf=PerfParams.manual(8, N_FRAMES))
    assert args["tasks"] == 1 and len(rows) == N_FRAMES
    assert started == ["load-0"] and args["loaders"] == 1
    assert _gauge() == 1


@pytest.mark.parametrize("cores, tasks_rows, stream, want",
                         [(13, 8, True, 10), (3, 8, True, 1),
                          (13, 64, True, 2), (13, 8, False, 5)])
def test_gauge_and_span_read_the_resolved_count(sc, monkeypatch, cores,
                                                tasks_rows, stream, want):
    """An unset count is derived at the run, and the run says what it
    started: the gauge, the run:pipeline span's args, the threads, and
    the counter a benchmark divides by the runs."""
    _host(monkeypatch, cores, cores)
    before = _loaders_total()
    _, args, started = _run(
        sc, monkeypatch, f"derived_{cores}_{tasks_rows}_{stream}",
        "histogram", None,
        perf=PerfParams.manual(4, tasks_rows, stream_work_packets=stream))
    assert args["tasks"] == N_FRAMES // tasks_rows
    assert args["instances"] == 1
    assert args["loaders"] == len(started) == _gauge() == want
    assert _loaders_total() - before == want
    assert want == default_load_workers(
        None, instances=1, queues=1, qsize=STREAMING.queue_size_per_pipeline,
        tasks=args["tasks"], streaming=stream)


def test_default_is_unset():
    """The literal 2 is gone: Client, LocalExecutor and the cluster
    Worker all leave the count to the run."""
    import inspect
    from scanner_tpu.engine.executor import LocalExecutor
    from scanner_tpu.engine.service import Worker
    for cls in (Client, LocalExecutor, Worker):
        p = inspect.signature(cls.__init__).parameters["num_load_workers"]
        assert p.default is None, cls


def test_usable_cores_is_this_process_s_share():
    n = _evaluate.usable_cores()
    assert 1 <= n <= (os.cpu_count() or n)
    if hasattr(os, "sched_getaffinity"):
        assert n == len(os.sched_getaffinity(0))


@pytest.mark.parametrize("tasks, workers, window, want",
                         [(6, 2, 6, 3),      # a small bulk is shared
                          (7, 2, 24, 4),
                          (6, 1, 6, 6),      # nobody to share with
                          (100, 2, 6, 6),    # a large one: the window
                          # ten loaders and an evaluator; 24 and four
                          (32, 2, 11, 11),
                          (16, 2, 11, 8),
                          (128, 2, 28, 28),
                          (32, 4, 28, 8)])
def test_master_holds_a_window_to_the_worker_s_share(tmp_path, tasks,
                                                     workers, window, want):
    """A worker derives its NextWork window from its own host; the
    master keeps one worker from taking a whole small bulk while a
    sibling idles."""
    from collections import deque
    from scanner_tpu.engine.service import Master, _BulkJob
    master = Master(db_path=str(tmp_path / "db"), no_workers_timeout=60.0)
    try:
        bulk = _BulkJob(bulk_id=0, spec_blob=b"", task_timeout=0.0)
        bulk.job_tasks[0] = {(0, t) for t in range(tasks)}
        bulk.job_sink_names[0] = []
        bulk.job_custom_sinks[0] = []
        bulk.job_output_rows[0] = 0
        bulk.queue[0] = deque(range(tasks))
        bulk.job_rr.append(0)
        bulk.total_tasks = tasks
        with master._lock:
            master._bulk = bulk
            master._history[0] = bulk
        wids = [master._rpc_register_worker({"address": f"w{i}"})
                ["worker_id"] for i in range(workers)]
        got = 0
        while master._rpc_next_work({"worker_id": wids[0], "bulk_id": 0,
                                     "window": window})["status"] == "task":
            got += 1
        assert got == want
        if workers > 1:
            # and the sibling finds its share still queued
            assert master._rpc_next_work(
                {"worker_id": wids[1], "bulk_id": 0,
                 "window": window})["status"] == "task"
    finally:
        master.stop()


def test_evaluate_depth_counts_tasks_that_wait_on_the_evaluator():
    """A wide load stage keeps the evaluate queue full of streaming
    tasks that are still being decoded; `stage_backpressure` reads the
    depth gauge, so it counts only the tasks whose loader can do no
    more for them."""
    from scanner_tpu.engine.executor import (TaskItem, _StageQueue,
                                             _awaits_evaluator,
                                             _loaded_whole)

    def task(chunks_ready=None):
        w = TaskItem(job=None, task_idx=0, output_range=(0, 32))
        if chunks_ready is not None:
            w.chunk_q = queue.Queue(maxsize=2)
            for c in range(chunks_ready):
                w.chunk_q.put(c)
        return w

    q = _StageQueue(4)
    for w in (task(), task(0), task(1), task(2)):
        assert q.put(w)
    # loaded whole; nothing decoded yet; one chunk of two; both, and
    # the loader blocked putting its end marker
    assert [_awaits_evaluator(w) for w in q._items] \
        == [True, False, False, True]
    assert q.qsize() == 4 and q.count(_awaits_evaluator) == 2
    # ten loaders' streaming tasks stand in the queue at once, past its
    # bound; the depth is still those whose loader can do no more
    q = _StageQueue(4, _loaded_whole)
    for k in range(10):
        assert q.put(task(k % 3))
    assert q.qsize() == 10 and q.count(_awaits_evaluator) == 3


# ------------------------------------------------- a streaming task's place

def test_the_bound_counts_tasks_loaded_whole_and_no_others():
    """A streaming task is queued past the bound, in its turn; a task
    loaded whole waits for one of `qsize` places among its kind, and
    abort() wakes it."""
    from scanner_tpu.engine.executor import (TaskItem, _StageQueue,
                                             _loaded_whole)

    def task(idx, streaming):
        w = TaskItem(job=None, task_idx=idx, output_range=(0, 32))
        if streaming:
            w.chunk_q = queue.Queue(maxsize=2)
        return w

    q = _StageQueue(2, _loaded_whole)
    for i in range(6):
        assert q.put(task(i, streaming=True))
    assert q.put(task(6, False)) and q.put(task(7, False))
    assert q.put(task(8, streaming=True))
    blocked = []
    t = threading.Thread(
        target=lambda: blocked.append(q.put(task(9, False))), daemon=True)
    t.start()
    t.join(0.2)
    assert t.is_alive() and q.qsize() == 9
    # the streaming tasks before it leave: still two loaded whole
    for i in range(6):
        assert q.get().task_idx == i
    t.join(0.2)
    assert t.is_alive()
    assert q.get().task_idx == 6
    t.join(5.0)
    assert blocked == [True]
    # queued in its turn: behind the streaming task that came before it
    assert [q.get().task_idx for _ in range(3)] == [7, 8, 9]
    held = _StageQueue(1, _loaded_whole)
    assert held.put(task(0, False))
    out = []
    t = threading.Thread(
        target=lambda: out.append(held.put(task(1, False))), daemon=True)
    t.start()
    t.join(0.2)
    assert t.is_alive()
    held.abort()
    t.join(5.0)
    assert out == [False] and held.put(task(2, True)) is False


# 16 tasks of 8 rows, each streamed as four 2-row chunks: a loader whose
# evaluator takes nothing decodes three of them (two in the task's chunk
# queue, one in its hand) and waits
WIDE = PerfParams.manual(2, 8)
N_WIDE = 10


class _Leases:
    """Stand-ins for tasks' frame-cache pages: counts each one's
    releases."""

    def __init__(self):
        self.released = {}
        self.lock = threading.Lock()

    def lease(self, task_idx):
        def release():
            with self.lock:
                self.released[task_idx] += 1
        with self.lock:
            self.released[task_idx] = 0
        return types.SimpleNamespace(release=release)


class _WidePipeline:
    """`run_pipeline` over the fixture's video on a host of 13 cores,
    with what the tests below watch: the chunks each task's loader has
    decoded, the order tasks were queued in and evaluated in, and a
    stand-in lease a task taken at its first chunk."""

    def __init__(self, sc, monkeypatch, name):
        from scanner_tpu.engine.executor import (LocalExecutor, TaskItem,
                                                 _StageQueue, _loaded_whole)
        _host(monkeypatch, 13, 13)
        self.ex = LocalExecutor(sc._db, num_save_workers=1)
        frame = sc.io.Input([NamedVideoStream(sc, "lw")])
        outputs = [sc.io.Output(sc.ops.Histogram(frame=frame),
                                [NamedStream(sc, name)])]
        self.info, jobs = self.ex.prepare(outputs, WIDE,
                                          cache_mode=CacheMode.Overwrite)
        self.work = [TaskItem(job, t, rng) for job in jobs
                     for t, rng in enumerate(job.tasks)]
        assert len(self.work) == N_FRAMES // 8
        self.made, self.queued, self.evaluated = {}, [], []
        self.queues = []
        self.leases = _Leases()
        self.lock = threading.Lock()
        probe = self

        iter_chunks = LocalExecutor._iter_chunk_items

        def counted_chunks(ex, info, w, tls):
            w.cache_leases = (w.cache_leases or []) \
                + [probe.leases.lease(w.task_idx)]
            for item in iter_chunks(ex, info, w, tls):
                with probe.lock:
                    probe.made[w.task_idx] = \
                        probe.made.get(w.task_idx, 0) + 1
                yield item

        put = _StageQueue.put

        def ordered_put(q, item):
            if q._bounded is not _loaded_whole:   # the save queue
                return put(q, item)
            with probe.lock:   # a streaming task's put never blocks
                if q not in probe.queues:
                    probe.queues.append(q)
                placed = put(q, item)
                if placed:
                    probe.queued.append(item.task_idx)
                return placed

        monkeypatch.setattr(LocalExecutor, "_iter_chunk_items",
                            counted_chunks)
        monkeypatch.setattr(_StageQueue, "put", ordered_put)

    def all_blocked(self, timeout=60.0):
        """Waits until ten loaders have decoded all they may."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self.lock:
                if sum(self.made.values()) >= 3 * N_WIDE:
                    break
            time.sleep(0.01)
        time.sleep(0.3)   # anything decoded past the bound shows now
        with self.lock:
            return dict(self.made)

    def run(self, **hooks):
        pending = list(self.work)
        lock = threading.Lock()

        def source():
            with lock:
                return pending.pop(0) if pending else None

        return self.ex.run_pipeline(self.info, source, total=len(self.work),
                                    queue_size=4, **hooks)


def _stage_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("load-", "eval-", "save-"))]


@pytest.fixture(scope="module")
def stalled(sc):
    """A run whose evaluator takes its first task and then nothing until
    every loader has decoded all it may; one run for the tests that
    read it."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        yield _stalled_run(sc, monkeypatch)


def _stalled_run(sc, monkeypatch):
    from scanner_tpu.engine.executor import LocalExecutor
    p = _WidePipeline(sc, monkeypatch, "stalled")
    evaluate = LocalExecutor._evaluate_stage
    seen = {}

    def stalling(ex, info, te, w, *args, **kw):
        with p.lock:
            first = not p.evaluated
            p.evaluated.append(w.task_idx)
        if first:
            seen["made"] = p.all_blocked()
            seen["queue"] = [x.task_idx for q in p.queues
                             for x in q._items]
            seen["threads"] = _stage_threads()
            with p.lock:
                seen["queued"] = list(p.queued)
        return evaluate(ex, info, te, w, *args, **kw)

    monkeypatch.setattr(LocalExecutor, "_evaluate_stage", stalling)
    seen["done"] = p.run()
    return p, seen


def test_ten_loaders_all_decode_before_a_stalled_evaluator_takes_a_chunk(
        stalled):
    p, seen = stalled
    assert p.ex.stage_widths == (N_WIDE, 1, 1)
    assert len([t for t in seen["threads"] if t.startswith("load-")]) \
        == N_WIDE
    # ten tasks, twice the queue's four places and the evaluator's one,
    # were being decoded with no chunk taken
    assert sorted(seen["made"]) == list(range(N_WIDE))
    # nine of them stood in the queue, past its bound, as queued
    assert sorted(seen["queued"]) == list(range(N_WIDE))
    assert seen["queue"] == seen["queued"][1:]
    assert seen["done"] == len(p.work)


def test_a_loader_holds_two_queued_chunks_and_the_one_in_its_hand(stalled):
    p, seen = stalled
    assert set(seen["made"].values()) == {3}
    # and once the evaluator moves, every task's four chunks are made
    assert p.made == {t: 4 for t in range(len(p.work))}


def test_tasks_reach_the_evaluator_in_the_order_they_were_queued(stalled):
    p, seen = stalled
    assert sorted(p.queued) == list(range(len(p.work)))
    assert p.evaluated == p.queued
    # every lease went back with its task's evaluation
    assert p.leases.released == {t: 1 for t in range(len(p.work))}


def test_stop_wakes_ten_blocked_loaders_and_their_leases_go_back(
        sc, monkeypatch):
    """The evaluator fails with ten loaders blocked on full chunk
    queues: the run raises at once, no stage thread is left, and every
    task that had begun to decode lets its pages go."""
    from scanner_tpu.engine.executor import LocalExecutor
    p = _WidePipeline(sc, monkeypatch, "stopped")
    failed_at = []

    def failing(ex, info, te, w, *args, **kw):
        p.all_blocked()
        failed_at.append(time.time())
        raise RuntimeError("evaluate failed")

    monkeypatch.setattr(LocalExecutor, "_evaluate_stage", failing)
    with pytest.raises(RuntimeError, match="evaluate failed"):
        p.run()
    assert time.time() - failed_at[0] < 2.0
    assert _stage_threads() == []
    assert sorted(p.leases.released) == list(range(N_WIDE))
    assert all(n >= 1 for n in p.leases.released.values()), \
        p.leases.released
    assert set(p.made.values()) == {3}


def test_chunk_abort_wakes_ten_blocked_loaders_one_task_at_a_time(
        sc, monkeypatch):
    """The cluster's revocation: `on_start` drops every task, the first
    with ten loaders blocked.  Each dropped task's loader wakes on its
    `chunk_abort`, lets its pages go and takes the next task; the
    pipeline ends in a normal close with nothing saved."""
    p = _WidePipeline(sc, monkeypatch, "revoked")
    first = []

    def on_start(w):
        if not first:
            first.append(p.all_blocked())
        return False

    t0 = time.time()
    assert p.run(on_start=on_start) == 0
    assert time.time() - t0 < 60.0
    assert _stage_threads() == []
    assert set(first[0].values()) == {3} and len(first[0]) == N_WIDE
    assert p.leases.released == {t: 1 for t in range(len(p.work))}
    # a revoked task's loader stops where it was: no chunk past the bound
    assert max(p.made.values()) <= 3
