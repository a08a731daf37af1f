"""Every stage span has its parts (docs/profiling.md "The span tree"):
the child spans of `run:prepare`, `run:commit`, `load`, `evaluate`,
`evaluate:<op>` and `save:raw` lie inside their parents
on the parent's thread, cover no more than it, and each feeds a counter
at its own two clock reads; the frame cache's and the column batch's
device programs are traced under a scope; and a compile that no
dispatch site observes is counted where it fired.
"""

import threading
import time

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import batch as _batch
from scanner_tpu.engine import framecache as fc
from scanner_tpu.util import coststats as cs
from scanner_tpu.util.metrics import registry
from scanner_tpu.util.profiler import Profiler, current_span

N_FRAMES = 64
RUN_PART = "scanner_tpu_run_part_seconds_total"
LOAD_PART = "scanner_tpu_load_part_seconds_total"
EVAL_PART = "scanner_tpu_evaluate_part_seconds_total"
RAW_PART = "scanner_tpu_raw_frame_part_seconds_total"
# graph -> (child span, parent span, counter series, its labels)
PARTS = {
    "Histogram": [
        ("prepare:analyze", "run:prepare", RUN_PART, {"part": "analyze"}),
        ("prepare:jobs", "run:prepare", RUN_PART, {"part": "jobs"}),
        ("commit:tables", "run:commit", RUN_PART, {"part": "tables"}),
        ("commit:sinks", "run:commit", RUN_PART, {"part": "sinks"}),
        ("commit:megafile", "run:commit", RUN_PART, {"part": "megafile"}),
        ("load:open", "load", LOAD_PART, {"part": "open"}),
        ("load:assemble", "load", LOAD_PART, {"part": "assemble"}),
        ("load:stage", "load", LOAD_PART, {"part": "stage"}),
        ("load:prestage", "load", LOAD_PART, {"part": "prestage"}),
        ("evaluate:inputs", "evaluate",
         "scanner_tpu_op_input_seconds_total", {"op": "Histogram"}),
        ("evaluate:Histogram", "evaluate",
         "scanner_tpu_op_seconds_total", {"op": "Histogram"}),
        ("evaluate:Output", "evaluate",
         "scanner_tpu_op_seconds_total", {"op": "Output"}),
        ("evaluate:dispatch", "evaluate:Histogram",
         "scanner_tpu_op_dispatch_seconds_total", {"op": "Histogram"}),
        ("evaluate:device_wait", "evaluate:Histogram",
         "scanner_tpu_device_wait_seconds_total", {"op": "Histogram"}),
        ("evaluate:merge", "evaluate", EVAL_PART, {"part": "merge"}),
        ("evaluate:prefetch", "evaluate", EVAL_PART, {"part": "prefetch"}),
    ],
    "OpticalFlow": [
        ("raw:pickle", "save:raw", RAW_PART, {"part": "pickle"}),
        ("raw:build", "save:raw", RAW_PART, {"part": "build"}),
        ("raw:write", "save:raw", RAW_PART, {"part": "write"}),
    ],
}
CASES = [(graph,) + part for graph, parts in PARTS.items()
         for part in parts]
CONTIGUOUS = ("scanner_tpu_save_contiguous_seconds_total", ())
# parents whose direct children may not cover more than they do
WHOLES = [("Histogram", "run:prepare"), ("Histogram", "run:commit"),
          ("Histogram", "load"), ("Histogram", "evaluate:Histogram"),
          ("OpticalFlow", "save:raw")]


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One run of each graph on the accelerator path of the CPU mesh
    (device staging, the YUV420 wire, the frame cache): its intervals,
    and what every series of PARTS counted over it."""
    root = tmp_path_factory.mktemp("parts")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=64, height=48,
                         fps=24, keyint=16)
    mp = pytest.MonkeyPatch()
    mp.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    mp.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    sc = Client(db_path=str(root / "db"))
    sc.ingest_videos([("sp", vid)])
    out = {}
    try:
        for graph in (*PARTS, "Blur"):
            before = {(s, tuple(la.items())): _counter(s, **la)
                      for _g, _c, _p, s, la in CASES}
            before[CONTIGUOUS] = _counter(CONTIGUOUS[0])
            frame = sc.io.Input([NamedVideoStream(sc, "sp")])
            col = getattr(sc.ops, graph)(frame=frame)
            job = sc.run(sc.io.Output(col, [NamedStream(sc, "o" + graph)]),
                         PerfParams.manual(8, 16),
                         cache_mode=CacheMode.Overwrite,
                         show_progress=False)
            delta = {k: _counter(k[0], **dict(k[1])) - v
                     for k, v in before.items()}
            ivs = [iv for p in sc.get_profile(job).profilers
                   for iv in p.intervals()]
            out[graph] = (ivs, delta)
    finally:
        sc.stop()
        fc.set_enabled(was)
        fc.cache().clear()
        mp.undo()
    return out


@pytest.mark.parametrize("graph,child,parent,series,labels", CASES,
                         ids=[c[1] for c in CASES])
def test_a_part_lies_inside_its_stage_and_feeds_its_counter(
        runs, graph, child, parent, series, labels):
    ivs, delta = runs[graph]
    kids = [iv for iv in ivs if iv.name == child]
    parents = [iv for iv in ivs if iv.name == parent]
    assert kids, f"no {child} interval"
    out = [c for c in kids
           if not any(p.thread == c.thread and p.start <= c.start
                      and c.end <= p.end for p in parents)]
    if child == "evaluate:device_wait":
        # a call's wait is taken two calls on, inside the op's span of
        # that later call; an evaluator's last two at its release,
        # after its last task
        for t in {c.thread for c in kids}:
            last = max(iv.end for iv in ivs
                       if iv.name == "evaluate" and iv.thread == t)
            late = [c for c in out if c.thread == t]
            assert len(late) <= 2 and all(c.start >= last for c in late)
        assert len(kids) > len(out)
    else:
        assert not out, out
    # the counter took the spans' seconds at the spans' own clock reads
    assert delta[(series, tuple(labels.items()))] == pytest.approx(
        sum(iv.end - iv.start for iv in kids), rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("graph,parent", WHOLES,
                         ids=[w[1] for w in WHOLES])
def test_the_parts_cover_no_more_than_the_whole(runs, graph, parent):
    ivs, _ = runs[graph]
    children = {c for g, parts in PARTS.items() for c, p, _s, _l in parts
                if p == parent} | ({"load:decode"} if parent == "load"
                                   else set())
    for p in (iv for iv in ivs if iv.name == parent):
        inside = [iv for iv in ivs if iv.name in children
                  and iv.thread == p.thread
                  and p.start <= iv.start and iv.end <= p.end]
        assert sum(iv.end - iv.start for iv in inside) \
            <= (p.end - p.start) + 1e-6, p
        # siblings on one thread follow one another
        inside.sort(key=lambda iv: iv.start)
        assert all(a.end <= b.start + 1e-9
                   for a, b in zip(inside, inside[1:])), inside


def test_the_megafile_span_says_what_it_packed(runs):
    ivs, _ = runs["Histogram"]
    (mega,) = [iv for iv in ivs if iv.name == "commit:megafile"]
    (tables,) = [iv for iv in ivs if iv.name == "commit:tables"]
    (jobs,) = [iv for iv in ivs if iv.name == "prepare:jobs"]
    assert tables.args["tables"] == 1
    assert jobs.args == {"jobs": 1, "tasks": 4}
    # the run's own table and the ingested one, at the least
    assert mega.args["tables"] >= 2 and mega.args["bytes"] > 0


def test_the_frames_copies_are_counted_inside_the_encode(runs):
    """A video item's frames are made contiguous one by one between
    the feeds: one number an item, on the `save:encode` span and in a
    counter, inside the encode's own seconds."""
    ivs, delta = runs["Blur"]
    encodes = [iv for iv in ivs if iv.name == "save:encode"]
    assert len(encodes) == 4
    copied = sum(iv.args["contiguous_s"] for iv in encodes)
    assert delta[CONTIGUOUS] == pytest.approx(copied, abs=1e-5)
    assert 0 <= copied <= sum(iv.end - iv.start for iv in encodes)


def test_current_span_is_the_innermost_open_one():
    prof = Profiler()
    assert current_span() is None
    with prof.span("outer"):
        assert current_span().name == "outer"
        with prof.span("inner") as inner:
            assert current_span() is inner
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(current_span()))
            t.start()
            t.join()
            assert seen == [None]  # a thread's own
        assert current_span().name == "outer"
    assert current_span() is None


def test_a_compile_nobody_observes_is_counted_where_it_fired():
    """A fresh shape compiled on a thread with no observe_compiles
    block open: in scanner_tpu_stray_compile_total under the open span,
    an interval of that span's profile, and not in
    scanner_tpu_compile_total."""
    import jax
    if not cs.enabled():
        pytest.skip("coststats off")
    cs.install()
    prof = Profiler()
    site = "test:stray_site"
    observed = _counter("scanner_tpu_compile_total")
    strays = _counter("scanner_tpu_stray_compile_total", site=site)
    seconds = _counter("scanner_tpu_stray_compile_seconds_total", site=site)

    def fresh(x):  # a closure: no jit cache holds it
        return (x * 3 + 1).sum()

    x, y = (jax.device_put(np.arange(n, dtype=np.float32))
            for n in (37, 41))
    with prof.span(site):
        t0 = time.time()
        jax.jit(fresh)(x).block_until_ready()
        t1 = time.time()
    assert _counter("scanner_tpu_stray_compile_total", site=site) \
        == strays + 1
    assert _counter("scanner_tpu_compile_total") == observed
    spent = _counter("scanner_tpu_stray_compile_seconds_total",
                     site=site) - seconds
    (iv,) = [iv for iv in prof.intervals() if iv.name == "compile"]
    assert iv.args["site"] == site
    assert iv.args["cache"] in ("hit", "miss", "uncached")
    assert iv.end - iv.start == pytest.approx(spent)
    assert t0 <= iv.start + 1e-3 and iv.end <= t1 + 1e-3
    # the same compile inside an observed block goes to the ledger's
    # series and leaves the stray one alone
    with prof.span(site), cs.observe_compiles("TestOp", "cpu:0", 1, "sig"):
        jax.jit(lambda x: (x * 5 - 2).sum())(y).block_until_ready()
    assert _counter("scanner_tpu_compile_total") == observed + 1
    assert _counter("scanner_tpu_stray_compile_total", site=site) \
        == strays + 1


def _lowered_names(fn, *args):
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("program,args", [
    ("slice", (4, 8)), ("copy", (0, 32, np.zeros((), np.uint8))),
    ("gather", (np.array([1, 9]),)),
    ("concat", ())])
def test_the_cache_programs_carry_their_scope(program, args):
    """Each device program the frame cache dispatches is traced under
    `framecache`: its operations carry the scope in their op_name."""
    import jax
    page = jax.device_put(np.arange(32 * 6, dtype=np.uint8).reshape(32, 6))
    fn = getattr(fc._programs(), program)
    host = np.asarray(page)
    if program == "concat":
        args, want = (page,), np.concatenate([host, host])
    elif program == "gather":
        want = host[args[0]]
    else:
        want = host[args[0]:args[0] + args[1]]
    assert "/framecache/" in _lowered_names(fn, page, *args)
    got = fn(page, *args)
    np.testing.assert_array_equal(np.asarray(got), want)
    if program == "copy":
        # a fill fragment never shares its block's buffer
        assert got.unsafe_buffer_pointer() != page.unsafe_buffer_pointer()


def test_a_cache_hit_path_runs_under_its_scope(monkeypatch):
    """A hit served from a page (a run of rows, then rows picked out)
    goes through the scoped programs and nothing eager."""
    import jax
    cache = fc.FrameCache()
    rows = np.arange(32)
    frames = np.arange(32 * 6, dtype=np.uint8).reshape(32, 6)
    plan = cache.plan(None, ("db", 1), "frame", 0, "rgb24", rows,
                      total_rows=32)
    cache.assemble(plan, rows, frames, hw=(2, 1))  # fills the page
    plan.lease.release()
    calls = []
    real = _batch.row_programs("framecache")
    for name in ("slice", "gather", "concat"):
        monkeypatch.setattr(
            real, name, lambda *a, _n=name, _f=getattr(real, name):
            (calls.append(_n), _f(*a))[1])
    for want in (np.arange(4, 12), np.array([1, 9, 30])):
        plan = cache.plan(None, ("db", 1), "frame", 0, "rgb24", want,
                          total_rows=32)
        assert plan.hit_mask.all()
        got = cache.assemble(plan, np.zeros(0, np.int64),
                             np.zeros((0, 6), np.uint8))
        plan.lease.release()
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), frames[want])
    assert calls == ["slice", "gather"]


def test_column_batch_rows_go_through_scoped_programs():
    import jax
    data = jax.device_put(np.arange(16 * 3, dtype=np.int32).reshape(16, 3))
    b = _batch.ColumnBatch(np.arange(16), data)
    assert _batch.rows_run(data, 0, 16) is data  # all of it: no program
    np.testing.assert_array_equal(
        np.asarray(b.take_rows(np.arange(4, 9)).data),
        np.asarray(data)[4:9])
    np.testing.assert_array_equal(
        np.asarray(b.take(np.array([3, 1, 15]), np.arange(3)).data),
        np.asarray(data)[[3, 1, 15]])
    joined = _batch.concat_batches([
        _batch.ColumnBatch(np.arange(16), data),
        _batch.ColumnBatch(np.arange(16, 32), data)])
    assert len(joined) == 32
    programs = _batch.row_programs("columnbatch")
    assert "/columnbatch/" in _lowered_names(programs.slice, data, 4, 5)
    # a host column stays a view
    host = np.arange(12).reshape(4, 3)
    assert np.shares_memory(_batch.rows_run(host, 1, 2), host)
