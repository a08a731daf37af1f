"""The shot-detection graph, Input -> Histogram (device) ->
HistogramDelta (host, stencil [-1, 0]) -> Output, through `Client.run`
against the benchmark's plain reference, over task and packet
boundaries; and the counters that say what a sampled or stencilled
graph costs: the codec's frames beside the delivered ones, and the rows
a producer evaluated only for a window's reach over a boundary.
"""

import os
import sys

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import framecache as fc
from scanner_tpu.util.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, N_FRAMES, KEYINT = 96, 128, 64, 16
CFG = {"video": {"height": H, "width": W}}
# what the traffic generator's samplers ask of a 64-row table
SAMPLED = {
    "All": ("All", None, list(range(N_FRAMES))),
    "Range": ("Range", [(8, 56)], list(range(8, 56))),
    "Stride": ("Stride", [3], list(range(0, N_FRAMES, 3))),
    "Stride30": ("Stride", [30], [0, 30, 60]),
    "Gather": ("Gather", [[5, 6, 40, 63]], [5, 6, 40, 63]),
}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator and references, by their own
    names (they import each other so)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from reference import Histogram, HistogramDelta
        yield clipgen, Histogram, HistogramDelta
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def clip(tmp_path_factory, bench):
    path = str(tmp_path_factory.mktemp("shot") / "clip.mp4")
    bench[0].encode_clip(path, 7, N_FRAMES, H, W, 24, KEYINT)
    return path


@pytest.fixture()
def sc(tmp_path, monkeypatch, clip):
    """A client on the accelerator path of the CPU mesh: device staging,
    the YUV420 wire converted on the device (what the reference reads),
    the frame cache."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    client = Client(db_path=str(tmp_path / "db"))
    client.ingest_videos([("movie", clip)])
    yield client
    client.stop()
    fc.set_enabled(was)
    # the pool is a process singleton: leave no page for the next file
    fc.cache().clear()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _wire(sc, table, rows):
    auto = scv.open_automata(sc._db, table, output_format="yuv420")
    try:
        return list(np.asarray(auto.get_frames(list(rows))))
    finally:
        auto.close()


def _run(sc, name, sampler, perf, ops=("Histogram", "HistogramDelta"),
         table="movie"):
    op, args, rows = SAMPLED[sampler]
    node = sc.io.Input([NamedVideoStream(sc, table)])
    if args is not None:
        node = getattr(sc.streams, op)(node, args)
    node = sc.ops.Histogram(frame=node)
    if "HistogramDelta" in ops:
        node = sc.ops.HistogramDelta(hist=node)
    out = NamedStream(sc, name)
    sc.run(sc.io.Output(node, [out]), perf, cache_mode=CacheMode.Overwrite,
           show_progress=False)
    return rows, [np.asarray(x) for x in out.load()]


@pytest.mark.parametrize("perf", [(8, 16), (16, 16), (4, 32)],
                         ids=["streamed", "whole_task", "eight_packets"])
@pytest.mark.parametrize("sampler", ["All", "Range", "Stride"])
def test_shot_graph_agrees_with_the_reference_on_every_row(
        sc, bench, sampler, perf):
    """A 64-row table in tasks of 16 or 32 rows and packets of 4, 8 or
    16: every row's distance, each task's and packet's first among them,
    is the reference's, exactly; row 0 of the (sampled) stream reads 0."""
    _, Histogram, R = bench
    rows, got = _run(sc, f"shot_{sampler}", sampler,
                     PerfParams.manual(*perf))
    wires = _wire(sc, "movie", rows)
    want = R.stream_deltas([Histogram.expected(f, H, W) for f in wires])
    assert len(got) == len(rows)
    assert [g.shape for g in got] == [()] * len(rows)
    assert [float(g) for g in got] == want
    assert want[0] == 0.0 and all(v > 0 for v in want[1:])
    if sampler != "Stride":
        # the comparison as the benchmark's harness makes it: only a
        # run that starts past row 0 leaves its first row uncompared
        assert R.compare(CFG, wires, got) == {
            "delta_rows_differ": 0,
            "delta_uncompared_share": (rows[0] > 0) / len(rows)}


def _codec_frames(rows, through=16):
    """Frames the codec has to decode for `rows` of the clip, from the
    keyframe index (a keyframe every KEYINT, no reordering): runs from
    the governing keyframe to the last wanted row, decoded through to
    the next row where its keyframe lies within `through` packets."""
    total, start, end = 0, None, None
    for r in sorted(set(rows)):
        kf = r - r % KEYINT
        if end is not None and kf <= end + through:
            end = max(end, r)
            continue
        if end is not None:
            total += end - start + 1
        start, end = kf, r
    return total + end - start + 1


@pytest.mark.parametrize("path,perf,cached", [
    ("streaming", (8, 16), False), ("whole_task", (16, 16), False),
    ("cached", (16, 16), True), ("streaming_cached", (8, 16), True)])
@pytest.mark.parametrize("sampler", ["All", "Stride30", "Gather"])
def test_codec_frames_counts_what_the_codec_decoded(
        sc, clip, monkeypatch, sampler, path, perf, cached):
    """`scanner_tpu_codec_frames_total` against the keyframe index, task
    by task, on every load path; `scanner_tpu_decoded_frames_total`
    keeps counting the rows delivered."""
    if not cached:
        fc.set_enabled(False)
    # a table of its own: no page of an earlier case is a hit
    table = f"movie_{sampler}_{path}"
    sc.ingest_videos([(table, clip)])
    before = [_counter("scanner_tpu_codec_frames_total"),
              _counter("scanner_tpu_decoded_frames_total")]
    rows, got = _run(sc, f"codec_{sampler}_{path}", sampler,
                     PerfParams.manual(*perf), ops=("Histogram",),
                     table=table)
    assert len(got) == len(rows)
    codec = _counter("scanner_tpu_codec_frames_total") - before[0]
    delivered = _counter("scanner_tpu_decoded_frames_total") - before[1]
    tasks = [rows[i:i + perf[1]] for i in range(0, len(rows), perf[1])]
    assert delivered == len(rows)
    assert codec == sum(_codec_frames(t) for t in tasks)
    # 0-30 (row 30's keyframe, 16, lies within reach of row 0) and
    # 48-60; 0-6 and 32-63
    assert codec == {"All": 64, "Stride30": 31 + 13,
                     "Gather": 7 + 32}[sampler]


@pytest.mark.parametrize("perf,units", [((16, 16), 4), ((8, 16), 8),
                                        ((32, 32), 2)],
                         ids=["tasks", "packets_of_streamed_tasks",
                              "two_tasks"])
def test_halo_rows_are_one_per_boundary(sc, perf, units):
    """A [-1, 0] window reaches one row back over the start of every
    unit the evaluator is handed but the stream's first: a task, or a
    work packet of a streamed task.  The producer evaluates that row
    again, and the source loads it again."""
    before = {k: _counter(*k[:1], **dict(k[1:])) for k in [
        ("scanner_tpu_stencil_halo_rows_total", ("op", "Histogram")),
        ("scanner_tpu_op_rows_total", ("op", "Histogram")),
        ("scanner_tpu_op_rows_total", ("op", "HistogramDelta")),
        ("scanner_tpu_stencil_window_seconds_total",
         ("op", "HistogramDelta"))]}
    rows, got = _run(sc, f"halo_{perf[0]}_{perf[1]}", "All",
                     PerfParams.manual(*perf))
    assert len(got) == N_FRAMES
    halo, hist_rows, delta_rows, window_s = (
        _counter(*k[:1], **dict(k[1:])) - v for k, v in before.items())
    assert halo == units - 1
    assert hist_rows == N_FRAMES + halo
    assert delta_rows == N_FRAMES
    assert window_s > 0


def test_window_and_host_op_spans(sc):
    """`evaluate:window` once per stencilled call of `_run_kernel`, with
    the op's name; `evaluate:<op>` says where the op ran."""
    node = sc.ops.HistogramDelta(hist=sc.ops.Histogram(
        frame=sc.io.Input([NamedVideoStream(sc, "movie")])))
    out = NamedStream(sc, "spans")
    job = sc.run(sc.io.Output(node, [out]), PerfParams.manual(16, 16),
                 cache_mode=CacheMode.Overwrite, show_progress=False)
    ivs = [iv for p in sc.get_profile(job).profilers
           for iv in p.intervals()]
    by = {}
    for iv in ivs:
        by.setdefault(iv.name, []).append(iv)
    assert len(by["evaluate:window"]) == 4
    assert {iv.args["op"] for iv in by["evaluate:window"]} \
        == {"HistogramDelta"}
    assert {iv.args["device"] for iv in by["evaluate:HistogramDelta"]} \
        == {"host"}
    assert "host" not in {iv.args["device"]
                          for iv in by["evaluate:Histogram"]}
    # the window is ready before the op's first call of the task
    for win, op in zip(by["evaluate:window"],
                       by["evaluate:HistogramDelta"]):
        assert win.end <= op.start
