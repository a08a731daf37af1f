"""The width of the load stage (engine/evaluate.py default_load_workers):
resolved at each run from the cores the process may use, the run's
evaluator instances, its queue depth and its tasks; an explicit
`num_load_workers` wins as given.  However many loaders a run starts,
its rows come out the same, in the same order.
"""

import os
import struct
import threading
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
        # the main thread are 10; the queue of 4 and the evaluator's
        # own task let 5 decode at once
        (13, 13, dict(tasks=32), 5),
        # the four-chip host: 30 cores, a queue per chip
        (30, 30, dict(instances=4, queues=4, tasks=128), 20),
        # four instances behind one shared queue: 4 queued + 4 held
        (30, 30, dict(instances=4, queues=1, tasks=128), 8),
        # a deeper queue is bounded by the cores
        (13, 13, dict(qsize=16, tasks=64), 10),
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
        (13, 13, dict(tasks=0), 5),
        (13, 13, dict(configured=0, tasks=2), 2),
        # a loader's decoder threads are cores too
        (13, 13, dict(tasks=32, decoder_threads=2), 5),
        (13, 13, dict(tasks=32, decoder_threads=4), 2),
    ])
def test_resolver(monkeypatch, affinity, cpu_count, kw, want):
    _host(monkeypatch, affinity, cpu_count)
    assert default_load_workers(**kw) == want


def test_resolver_bounds(monkeypatch):
    """Derived counts lie in [1, instances x (qsize + 1)] and never
    above the task count, whatever the host."""
    for cores in (1, 2, 3, 8, 13, 30, 224):
        _host(monkeypatch, cores, cores)
        for instances in (1, 2, 4, 8):
            for queues in (1, instances):
                for qsize in (1, 4, 8):
                    for tasks in (0, 1, 2, 7, 1000):
                        n = default_load_workers(
                            None, instances=instances, queues=queues,
                            qsize=qsize, tasks=tasks)
                        assert 1 <= n <= instances * (qsize + 1)
                        assert not tasks or n <= tasks
                        assert n <= max(1, cores - instances - 2)


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


@pytest.mark.parametrize("cores, tasks_rows, want",
                         [(13, 8, 5), (3, 8, 1), (13, 64, 2)])
def test_gauge_and_span_read_the_resolved_count(sc, monkeypatch, cores,
                                                tasks_rows, want):
    """An unset count is derived at the run, and the run says what it
    started: the gauge, the run:pipeline span's args, the threads."""
    _host(monkeypatch, cores, cores)
    _, args, started = _run(
        sc, monkeypatch, f"derived_{cores}_{tasks_rows}", "histogram", None,
        perf=PerfParams.manual(4, tasks_rows))
    assert args["tasks"] == N_FRAMES // tasks_rows
    assert args["instances"] == 1
    assert args["loaders"] == len(started) == _gauge() == want
    assert want == default_load_workers(
        None, instances=1, queues=1, qsize=STREAMING.queue_size_per_pipeline,
        tasks=args["tasks"])


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
                          (100, 2, 6, 6)])   # a large one: the window
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
    import queue
    from scanner_tpu.engine.executor import (TaskItem, _StageQueue,
                                             _awaits_evaluator)

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
