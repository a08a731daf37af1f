"""A Client keeps the evaluators of the graph it ran last
(engine/evaluate.py EvaluatorPool) and hands them to the next run of the
same graph: kernels are constructed once per Client and graph, bound to
each run's streams anew, and closed once — at `Client.stop()`, when
another graph takes their place, or when a run raises.
"""

import os
import threading
from typing import Any, Sequence

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, PerfParams,
                         register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.util.metrics import registry

N_FRAMES = 32
TASK_ROWS = 8

# what the probe kernels did, in order: (event, kernel serial, detail)
EVENTS = []
_SERIAL = [0]
_LOCK = threading.Lock()


def _note(event, kernel, detail=None):
    with _LOCK:
        EVENTS.append((event, kernel.serial, detail))


def _events(event):
    with _LOCK:
        return [e for e in EVENTS if e[0] == event]


class _Probe(Kernel):
    def __init__(self, config, scale: int = 1, weights: str = None,
                 fail: bool = False, blob: Any = None):
        super().__init__(config)
        with _LOCK:
            self.serial = _SERIAL[0]
            _SERIAL[0] += 1
        self.scale, self.fail, self.offset = scale, fail, 0
        self.weight = 0
        if weights is not None:
            with open(weights) as f:
                self.weight = int(f.read())
        _note("init", self, scale)

    def new_stream(self, offset: int = 0):
        self.offset = offset
        _note("new_stream", self, offset)

    def reset(self):
        _note("reset", self)

    def close(self):
        _note("close", self)

    def _row(self, f):
        return int(f[0, 0, 0]) * self.scale + self.offset + self.weight


# set by a test: the first execute() of each thread waits here, so that
# two runs are inside their evaluate stage together
MEET = [None]
_MET = set()


@register_op(name="PoolProbe")
class PoolProbe(_Probe):
    def execute(self, frame: FrameType) -> Any:
        if self.fail:
            raise RuntimeError("probe told to fail")
        me = threading.get_ident()
        if MEET[0] is not None and me not in _MET:
            _MET.add(me)
            MEET[0].wait(timeout=60)
        _note("execute", self, me)
        return self._row(frame)


@register_op(name="PoolProbeStateful", unbounded_state=True)
class PoolProbeStateful(_Probe):
    def execute(self, frame: FrameType) -> Any:
        return self._row(frame)


@register_op(name="PoolProbeDevice", device=DeviceType.TPU, batch=4)
class PoolProbeDevice(_Probe):
    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        _note("execute", self, threading.get_ident())
        return [self._row(np.asarray(f)) for f in frame]


@pytest.fixture(scope="module")
def clip(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pool_clip") / "v.mp4")
    scv.synthesize_video(path, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=8)
    return path


@pytest.fixture()
def sc(tmp_path, clip):
    client = Client(db_path=str(tmp_path / "db"))
    client.ingest_videos([("a", clip), ("b", clip)])
    del EVENTS[:]
    yield client
    client.stop()


def _run(sc, out, op="PoolProbe", tables=("a",), perf=None, job_args=None,
         **init):
    """One `Client.run` of Input -> op -> Output; the rows it committed."""
    frame = sc.io.Input([NamedVideoStream(sc, t) for t in tables])
    col = getattr(sc.ops, op)(frame=frame, **(job_args or {}), **init)
    names = [f"{out}_{t}" for t in tables]
    job = sc.run(sc.io.Output(col, [NamedStream(sc, n) for n in names]),
                 perf or PerfParams.manual(TASK_ROWS, TASK_ROWS),
                 cache_mode=CacheMode.Overwrite, show_progress=False)
    return job, [list(NamedStream(sc, n).load()) for n in names]


def _counter(series):
    return sum(s["value"]
               for s in registry().snapshot()[series]["samples"])


@pytest.mark.parametrize("driver", ["pipeline", "serial"])
def test_second_run_of_a_graph_constructs_nothing(sc, tmp_path, clip,
                                                  monkeypatch, driver):
    """Two runs of one graph on one Client: each kernel constructed
    once, bound to the second run's stream anew, and the rows are what
    two fresh Clients give.  The serial driver hits as the threaded one
    does."""
    if driver == "serial":
        monkeypatch.setenv("SCANNER_TPU_NO_PIPELINING", "1")
    reuses = _counter("scanner_tpu_evaluator_reuses_total")
    setups = _counter("scanner_tpu_evaluator_setups_total")
    _, first = _run(sc, "one", scale=3)
    _, second = _run(sc, "two", scale=3)
    assert len(_events("init")) == 1
    assert len(_events("new_stream")) == 2 and len(_events("reset")) == 2
    assert not _events("close")
    assert _counter("scanner_tpu_evaluator_reuses_total") == reuses + 1
    assert _counter("scanner_tpu_evaluator_setups_total") == setups + 2
    fresh = []
    for i in range(2):
        with Client(db_path=str(tmp_path / f"fresh{i}")) as other:
            other.ingest_videos([("a", clip)])
            fresh.append(_run(other, "out", scale=3)[1])
    assert len(first[0]) == N_FRAMES
    assert [first, second] == fresh


@pytest.mark.parametrize("what", ["init_args", "file_rewritten",
                                  "unkeyable_arg", "other_op"])
def test_another_graph_misses_and_closes_the_kept_first(sc, tmp_path, what):
    """What a kernel was constructed from has changed: a miss, and the
    old kernel is closed before the new one is constructed."""
    weights = str(tmp_path / "weights.txt")
    with open(weights, "w") as f:
        f.write("5")
    first = {"init_args": dict(scale=2),
             "file_rewritten": dict(weights=weights),
             "unkeyable_arg": dict(blob=object()),
             "other_op": dict(scale=2)}[what]
    _, rows = _run(sc, "one", **first)
    if what == "init_args":
        second = dict(scale=4)
    elif what == "file_rewritten":
        with open(weights, "w") as f:
            f.write("70")
        st = os.stat(weights)
        os.utime(weights, ns=(st.st_atime_ns, st.st_mtime_ns + 10**9))
        second = first
    elif what == "unkeyable_arg":
        # equal by identity only: not keyed by value, never kept
        second = first
    else:
        second = dict(scale=2, op="PoolProbeDevice")
    _, rows2 = _run(sc, "two", **second)
    order = [e[0] for e in EVENTS if e[0] in ("init", "close")]
    # what cannot be keyed is closed with its run, like the first here
    assert order == ["init", "close", "init"] + \
        ["close"] * (what == "unkeyable_arg")
    assert _events("close")[0][1] == _events("init")[0][1]
    if what == "file_rewritten":
        assert [b - a for a, b in zip(rows[0], rows2[0])] == [65] * N_FRAMES
    if what == "init_args":
        assert rows2[0] == [2 * r for r in rows[0]]


def test_stream_args_of_the_second_run_reach_new_stream(sc):
    """Per-stream args are the run's, not the kept kernel's."""
    _, first = _run(sc, "one", tables=("a", "b"),
                    job_args=dict(offset=[100, 200]))
    n_first = len(_events("new_stream"))
    _, second = _run(sc, "two", tables=("a", "b"),
                     job_args=dict(offset=[7, 9]))
    assert len(_events("init")) == 1
    assert {e[2] for e in _events("new_stream")[:n_first]} == {100, 200}
    assert {e[2] for e in _events("new_stream")[n_first:]} == {7, 9}
    assert [r - 100 for r in first[0]] == [r - 7 for r in second[0]]
    assert [r - 200 for r in first[1]] == [r - 9 for r in second[1]]


def test_a_run_that_raises_leaves_nothing_kept(sc):
    _run(sc, "one", scale=2)
    with pytest.raises(RuntimeError, match="told to fail"):
        _run(sc, "bad", scale=2, fail=True)
    inits = {e[1] for e in _events("init")}
    assert len(inits) == 2
    assert sorted(e[1] for e in _events("close")) == sorted(inits)
    # and the next good run starts from nothing
    _, rows = _run(sc, "three", scale=2)
    assert len(_events("init")) == 3 and len(rows[0]) == N_FRAMES


def test_two_runs_in_flight_share_no_evaluator(sc):
    """Check-out and check-in: a second run in flight with the same key
    builds its own evaluator; one of the two is kept, the other closed."""
    _run(sc, "warm", scale=2)
    del EVENTS[:]
    rows, errors = {}, []

    def go(name):
        try:
            rows[name] = _run(sc, name, scale=2)[1]
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    MEET[0] = threading.Barrier(2)
    try:
        threads = [threading.Thread(target=go, args=(n,))
                   for n in ("x", "y")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        MEET[0] = None
    assert not errors
    assert rows["x"] == rows["y"]
    # the run that found the kept evaluator checked out built its own,
    # and each run's evaluator thread executed on its own kernel
    assert len(_events("init")) == 1
    kernels_of = {}
    for _e, serial, thread in _events("execute"):
        kernels_of.setdefault(thread, set()).add(serial)
    assert len(kernels_of) == 2
    assert all(len(k) == 1 for k in kernels_of.values())
    assert len(set.union(*kernels_of.values())) == 2
    assert len(_events("close")) == 1
    sc.stop()
    assert sorted(e[1] for e in _events("close")) == \
        sorted(set.union(*kernels_of.values()))


def test_stop_closes_each_kept_kernel_once(sc):
    _run(sc, "one", scale=2)
    _run(sc, "two", scale=2)
    assert not _events("close")
    sc.stop()
    sc.stop()
    assert len(_events("close")) == 1


def test_intervals_land_in_the_profile_of_the_run_that_made_them(sc):
    """Each Client.run has its own Profiler; a kept evaluator records
    into the one of the run that holds it."""
    job1, _ = _run(sc, "one", scale=2)
    n1 = len([iv for p in sc.get_profile(job1).profilers
              for iv in p.intervals()])
    job2, _ = _run(sc, "two", scale=2)

    def named(job, prefix):
        return [iv for p in sc.get_profile(job).profilers
                for iv in p.intervals() if iv.name.startswith(prefix)]

    for job, reused in ((job1, False), (job2, True)):
        setup = named(job, "evaluate:setup")
        assert [iv.args["reused"] for iv in setup] == [reused]
        assert len(named(job, "evaluate:PoolProbe")) == N_FRAMES // TASK_ROWS
    # the second run added nothing to the first run's profile
    assert len([iv for p in sc.get_profile(job1).profilers
                for iv in p.intervals()]) == n1
    lo = min(iv.start for iv in named(job2, "evaluate"))
    assert all(iv.end <= lo for iv in named(job1, "evaluate"))


def test_a_stateful_graph_is_constructed_every_run(sc):
    _, first = _run(sc, "one", op="PoolProbeStateful", scale=2)
    _, second = _run(sc, "two", op="PoolProbeStateful", scale=2)
    assert first == second
    assert len(_events("init")) == 2
    assert len(_events("close")) == 2


def test_each_instance_gets_back_the_evaluator_of_its_own_device(
        sc, monkeypatch):
    """Four device-affine instances on four virtual devices: the second
    run's instance i runs on the kernel the first run made for chip i."""
    import jax
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    devs = jax.local_devices()[:4]

    def run(name):
        frame = sc.io.Input([NamedVideoStream(sc, "a")])
        col = sc.ops.PoolProbeDevice(frame=frame, scale=2)
        out = sc.io.Output(col, [NamedStream(sc, name)])
        job = sc.run(out, PerfParams.manual(4, 4),
                     cache_mode=CacheMode.Overwrite, show_progress=False,
                     pipeline_instances=4)
        return job, list(NamedStream(sc, name).load())

    from scanner_tpu.engine import evaluate as ev
    _, first = run("one")
    made = {te.instance: (te, te.device,
                          next(iter(te.kernels.values())).kernel)
            for te in ev.live_evaluators()
            if any(ki.node.name == "PoolProbeDevice"
                   for ki in te.kernels.values())}
    assert sorted(made) == [0, 1, 2, 3]
    assert [made[i][1] for i in range(4)] == devs
    job2, second = run("two")
    assert first == second and len(first) == N_FRAMES
    assert len(_events("init")) == 4 and not _events("close")
    for i, (te, dev, kernel) in made.items():
        ki = next(iter(te.kernels.values()))
        assert te.device == dev and ki.kernel is kernel
        assert ki.device == dev
        # its node is the second run's
        assert ki.node in te.info.ops
    setups = [iv for p in sc.get_profile(job2).profilers
              for iv in p.intervals() if iv.name == "evaluate:setup"]
    assert sorted(iv.args["device"] for iv in setups) == \
        sorted(ev.device_label(d) for d in devs)
    assert all(iv.args["reused"] for iv in setups)


@pytest.mark.parametrize("precompile", ["0", "1"],
                         ids=["cold", "ladder_warmed"])
def test_a_fused_chain_is_adopted_whole(sc, tmp_path, clip, monkeypatch,
                                        precompile):
    """Resize+Blur+Histogram runs as one fused program and HistDiff
    staged behind it: the kept evaluator's chains follow the next run's
    nodes, a sampler or a stream arg that differs is no miss, and no
    second ladder warm-up is started."""
    from scanner_tpu.engine import evaluate as ev
    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", precompile)

    def run(client, name, rows, size):
        frame = client.io.Input([NamedVideoStream(client, "a")])
        frame = client.streams.Range(frame, [rows])
        r = client.ops.Resize(frame=frame, width=[size[0]],
                              height=[size[1]])
        b = client.ops.Blur(frame=r, kernel_size=3, sigma=1.1)
        d = client.ops.HistDiff(frame=client.ops.Histogram(frame=b))
        out = NamedStream(client, name)
        client.run(client.io.Output(d, [out]), PerfParams.manual(8, 8),
                   cache_mode=CacheMode.Overwrite, show_progress=False)
        return [np.asarray(x) for x in out.load()]

    requests = [((0, N_FRAMES), (32, 24)), ((4, 25), (32, 24)),
                ((0, N_FRAMES), (48, 36))]
    reuses = _counter("scanner_tpu_evaluator_reuses_total")
    got, threads = [], []
    for i, (rows, size) in enumerate(requests):
        got.append(run(sc, f"chain{i}", rows, size))
        (te,) = [t for t in ev.live_evaluators()
                 if any(f.chain_id == "Resize+Blur+Histogram"
                        for f in t.fused.values())]
        threads.append(te._precompile_thread)
        assert all(m in te.info.ops for f in te.fused.values()
                   for m in f.chain.members)
    assert _counter("scanner_tpu_evaluator_reuses_total") == reuses + 2
    assert (threads[0] is not None) == (precompile == "1")
    assert threads[1] is threads[0] and threads[2] is threads[0]
    for i, (rows, size) in enumerate(requests):
        with Client(db_path=str(tmp_path / f"fresh{i}")) as other:
            other.ingest_videos([("a", clip)])
            want = run(other, "out", rows, size)
        assert len(want) == rows[1] - rows[0]
        assert all(np.array_equal(a, b) for a, b in zip(got[i], want))
        assert len(got[i]) == len(want)
