"""The frame-output graph, Input -> Blur -> a video column, through
`Client.run` against the benchmark's plain reference
(benchmark/reference/Blur.py): the committed frames are the reference's
own round trip through the stated encode, the filter in bfloat16, a
coarser quantiser and a missing filter do not pass, the op alone is the
reference's mathematics within one level, the configuration's stated
encode and item length are the program's defaults, and the save stage's
spans and counters say what it did.
"""

import inspect
import json
import os
import re
import sys
import time

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import executor as _executor
from scanner_tpu.engine import framecache as fc
from scanner_tpu.graph.ops import OpColumn
from scanner_tpu.storage import FilesStream
from scanner_tpu.util.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_FRAMES, KEYINT = 64, 32
SIZES = {"128x96": (96, 128), "320x240": (240, 320)}
# the configuration's PSNR floor is the 1080p clip's; these clips' own,
# as far under the stated encode's reading as over crf 26's (the stated
# encode reads 34.6 and 37.1 dB here, crf 26 31.4 and 34.4)
FLOORS_DB = {"128x96": 33.0, "320x240": 35.7}
with open(os.path.join(REPO, "benchmark", "configs", "blur_1080p.json")) as f:
    CONFIG = json.load(f)
ARGS = CONFIG["graph"]["args"]


def cfg_at(size):
    h, w = SIZES[size]
    return dict(CONFIG,
                video=dict(CONFIG["video"], height=h, width=w,
                           frames=N_FRAMES),
                output=dict(CONFIG["output"], psnr_floor_db=FLOORS_DB[size]))


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator and references, by their own
    names (they import each other so)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from reference import Blur, wire
        yield clipgen, Blur, wire
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def sc(tmp_path_factory, bench):
    """A client on the accelerator path of the CPU mesh (device staging,
    the YUV420 wire converted on the device: what the reference reads),
    with the seeded clip at both sizes."""
    root = tmp_path_factory.mktemp("frames")
    mp = pytest.MonkeyPatch()
    mp.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    mp.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    client = Client(db_path=str(root / "db"))
    for name, (h, w) in SIZES.items():
        path = str(root / f"{name}.mp4")
        bench[0].encode_clip(path, 11, N_FRAMES, h, w, CONFIG["video"]["fps"],
                             KEYINT)
        client.ingest_videos([(name, path)])
    yield client
    client.stop()
    fc.set_enabled(was)
    # the pool is a process singleton: leave no page for the next file
    fc.cache().clear()
    mp.undo()


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


def _blur_run(sc, table, name, perf=None, column=None, op="Blur", **kw):
    """Blur over `table` into the video column `name`, in two items of
    32 rows; returns the job's profiler intervals."""
    frame = sc.io.Input([NamedVideoStream(sc, table)])
    col = sc.ops.Blur(frame=frame, **ARGS) if op == "Blur" \
        else getattr(sc.ops, op)(frame=frame)
    col = column(col) if column else col
    job = sc.run(sc.io.Output(col, [NamedStream(sc, name)]),
                 perf or PerfParams.manual(16, 32),
                 cache_mode=CacheMode.Overwrite, show_progress=False, **kw)
    return [iv for p in sc.get_profile(job).profilers
            for iv in p.intervals()]


@pytest.fixture(scope="module")
def committed(sc):
    """The default column of both sizes, once: {size: (wires, frames)}."""
    out = {}
    for name in SIZES:
        _blur_run(sc, name, f"blurred_{name}")
        out[name] = (_wire(sc, name, range(N_FRAMES)),
                     [np.asarray(f) for f in
                      NamedStream(sc, f"blurred_{name}").load()])
    return out


def _not_correct(Blur, values):
    return [k for k, limit in Blur.LIMITS.items() if values[k] > limit]


@pytest.mark.parametrize("size", list(SIZES))
def test_default_column_is_the_references_own_round_trip(
        sc, bench, committed, size):
    _, Blur, _ = bench
    h, w = SIZES[size]
    wires, got = committed[size]
    assert len(got) == N_FRAMES
    assert {f.shape for f in got} == {(h, w, 3)}
    assert sc.table(f"blurred_{size}").committed()
    values = Blur.compare(cfg_at(size), wires, got)
    assert not _not_correct(Blur, values), values
    # both items are whole and x264 is deterministic: what is left is
    # the few pixels the compiler's order of sums rounds the other way
    assert abs(values["psnr_deficit_db"]) < Blur.LIMITS["psnr_deficit_db"] / 3
    assert values["psnr_under_floor_db"] < -1.0
    assert values["blur_response_missing"] < 0.0


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("control,fails_by", [
    ("bf16", ["psnr_deficit_db"]),
    ("crf26", ["psnr_under_floor_db", "psnr_deficit_db"]),
    ("no_blur", ["blur_response_missing"])])
def test_controls_are_not_correct(bench, committed, size, control, fails_by):
    """The reference's own round trip of its filter in bfloat16, at
    crf 26, and of the unfiltered frames, in the program's place: the
    lower precision fails by the deficit against the exact round trip
    alone, the coarser quantiser by the floor too, the missing filter by
    the share of the filter's change (at these sizes the unfiltered
    frames also stand a dB further off)."""
    _, Blur, _ = bench
    assert Blur.CONTROL == "bf16" and control in Blur.CONTROLS
    wires, _ = committed[size]
    values = Blur.compare(cfg_at(size), wires, [None] * len(wires),
                          control=control)
    failed = _not_correct(Blur, values)
    assert set(fails_by) <= set(failed), values
    assert values[fails_by[0]] - Blur.LIMITS[fails_by[0]] \
        > {"bf16": 0.2, "crf26": 1.0, "no_blur": 0.3}[control]
    if control != "no_blur":
        assert failed == fails_by, values


def test_a_coarser_column_of_the_program_is_not_correct(sc, bench,
                                                        committed):
    """`.compress(crf=26)` through the program itself, not the reference's
    stand-in."""
    _, Blur, _ = bench
    wires, _ = committed["128x96"]
    _blur_run(sc, "128x96", "blurred_crf26",
              column=lambda c: c.compress("video", crf=26))
    got = list(NamedStream(sc, "blurred_crf26").load())
    assert _not_correct(Blur, Blur.compare(cfg_at("128x96"), wires, got)) \
        == ["psnr_under_floor_db", "psnr_deficit_db"]


def test_only_whole_items_are_held_to_the_exact_round_trip(bench, committed):
    """x264 looks ahead, so a part of an item does not encode as the
    whole does: of rows 16-63 only the item 32-63 takes part, and a
    sample with no whole item reads 0 there and is held by the other
    numbers."""
    _, Blur, _ = bench
    wires, got = committed["128x96"]
    cfg = cfg_at("128x96")
    values = Blur.compare(cfg, wires[16:], got[16:])
    assert not _not_correct(Blur, values), values
    spoiled = [g if i >= 32 else np.roll(g, 1, axis=1)
               for i, g in enumerate(got)]
    assert Blur.compare(cfg, wires[16:], spoiled[16:])["psnr_deficit_db"] \
        == values["psnr_deficit_db"]
    part = Blur.compare(cfg, wires[8:24], spoiled[8:24])
    assert part["psnr_deficit_db"] == 0.0
    assert "psnr_under_floor_db" in _not_correct(Blur, part)


@pytest.mark.parametrize("size", list(SIZES))
def test_the_op_alone_is_the_references_mathematics(sc, bench, size):
    """`_blur_impl` on the reference's own RGB: within one level on
    every pixel, equal on at least 99 % (the order of the float32 sums
    is the compiler's)."""
    import jax.numpy as jnp
    from scanner_tpu.kernels.imgproc import _blur_impl, _gaussian_kernel1d
    _, Blur, wire = bench
    h, w = SIZES[size]
    rgb = np.stack([wire.to_rgb(f, h, w) for f in _wire(sc, size, range(16))])
    want = np.stack([Blur.blur(f, ARGS["kernel_size"], ARGS["sigma"])
                     for f in rgb])
    kern = _gaussian_kernel1d(ARGS["kernel_size"], ARGS["sigma"])
    np.testing.assert_allclose(
        kern, Blur.taps(ARGS["kernel_size"], ARGS["sigma"]), rtol=1e-6)
    got = np.asarray(_blur_impl(jnp.asarray(rgb), jnp.asarray(kern),
                                ARGS["kernel_size"]))
    assert got.shape == want.shape and got.dtype == np.uint8
    diff = np.abs(got.astype(np.int16) - want)
    assert diff.max() <= 1
    assert (diff == 0).mean() >= 0.99
    # and the filter does something: the reference differs from its input
    assert (want != rgb).mean() > 0.2


def test_stated_encode_settings_are_the_programs_defaults(sc, monkeypatch):
    """benchmark/configs/blur_1080p.json states what a frame column gets
    when nothing is asked for; it may not drift from what runs."""
    stated = CONFIG["output"]
    defaults = {k: p.default for k, p in
                inspect.signature(OpColumn.compress).parameters.items()}
    assert (defaults["codec"], defaults["bitrate"]) == ("video", 0)
    assert defaults["crf"] == stated["crf"]
    assert defaults["keyint"] == stated["keyint"]
    from scanner_tpu.video import lib
    made = []
    real = lib.Encoder

    def spy(*args, **kw):
        made.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(lib, "Encoder", spy)
    _blur_run(sc, "128x96", "blurred_spied")
    assert len(made) == 2  # one encoder an item
    for args, kw in made:
        assert args == (128, 96)
        assert kw == {"fps": float(CONFIG["video"]["fps"]),
                      "codec": stated["codec"], "bitrate": 0,
                      "crf": stated["crf"], "keyint": stated["keyint"]}
    enc = inspect.signature(real).parameters
    assert enc["bframes"].default == stated["bframes"] == 0
    assert (stated["width"], stated["height"]) \
        == (CONFIG["video"]["width"], CONFIG["video"]["height"])
    with open(os.path.join(REPO, "cpp", "scvid.cpp")) as f:
        presets = set(re.findall(r'"preset", "(\w+)"', f.read()))
    assert presets == {stated["preset"]}


def test_stated_item_length_is_what_estimate_gives_a_1080p_stream(sc):
    """`output.item_rows` is no setting: it is what PerfParams.estimate()
    makes of 1080p frames and the clip's keyframe interval, and the
    reference cuts its own encode there."""
    from types import SimpleNamespace
    v = CONFIG["video"]
    stream = SimpleNamespace(
        _sc=sc, is_video=True,
        estimate_geometry=lambda: (v["width"] * v["height"] * 3, v["keyint"]))
    info = SimpleNamespace(sources=[SimpleNamespace(
        extra={"streams": [stream]})])
    perf = sc._executor._estimate_perf(info, PerfParams.estimate())
    assert perf.io_packet_size == CONFIG["output"]["item_rows"] == 32
    assert perf.work_packet_size == 16


def test_a_run_across_two_items_loads_in_row_order(sc, bench, committed):
    clipgen, Blur, _ = bench
    rows = list(range(24, 44))  # item 0 ends at row 31
    desc = sc._db.table_descriptor("blurred_128x96")
    assert list(desc.end_rows) == [32, 64]
    got = list(NamedStream(sc, "blurred_128x96").load(rows=rows))
    assert [clipgen.read_barcode(Blur.luma(np.asarray(f))) for f in got] \
        == rows
    # every item starts on a keyframe and holds one every 16 rows
    from scanner_tpu.storage import metadata as md
    for item in range(2):
        vd = md.VideoDescriptor.deserialize(sc._db.backend.read(
            md.video_meta_path(desc.id, "frame", item)))
        assert list(vd.keyframe_indices) == [0, 16]


def _by_name(ivs):
    by = {}
    for iv in ivs:
        by.setdefault(iv.name, []).append(iv)
    return by


SAVE_SERIES = ("scanner_tpu_encode_seconds_total",
               "scanner_tpu_encoded_frames_total",
               "scanner_tpu_encoded_bytes_total",
               "scanner_tpu_sink_fetch_seconds_total",
               "scanner_tpu_sink_fetch_bytes_total")


def test_save_spans_nest_and_their_counters_move_with_them(sc):
    before = {s: _counter(s) for s in SAVE_SERIES}
    by = _by_name(_blur_run(sc, "128x96", "blurred_spans"))
    moved = {s.replace("scanner_tpu_", ""): _counter(s) - before[s]
             for s in SAVE_SERIES}
    assert len(by["save"]) == len(by["save:encode"]) == 2
    for child, parent in (("save:encode", "save:write"),
                          ("save:write", "save"), ("save:fetch", "save")):
        for c in by[child]:
            assert any(p.thread == c.thread and p.start <= c.start
                       and c.end <= p.end for p in by[parent]), c
    # fetch, then write: the encode is no part of the fetch
    for f in by["save:fetch"]:
        assert all(f.end <= e.start for e in by["save:encode"]
                   if e.thread == f.thread and e.start >= f.start)
    assert [iv.args["frames"] for iv in by["save:encode"]] == [32, 32]
    # a span and its counter are taken at the same two clock reads
    assert moved["encode_seconds_total"] == pytest.approx(
        sum(iv.end - iv.start for iv in by["save:encode"]), abs=1e-6)
    assert moved["sink_fetch_seconds_total"] == pytest.approx(
        sum(iv.end - iv.start for iv in by["save:fetch"]), abs=1e-6)
    assert moved["encoded_frames_total"] == N_FRAMES
    assert moved["sink_fetch_bytes_total"] == N_FRAMES * 96 * 128 * 3
    desc = sc._db.table_descriptor("blurred_spans")
    from scanner_tpu.storage import metadata as md
    on_disk = sum(len(sc._db.backend.read(
        md.column_item_path(desc.id, "frame", item))) for item in range(2))
    assert moved["encoded_bytes_total"] == on_disk > 0
    (pipeline,) = by["run:pipeline"]
    assert pipeline.args["savers"] == sc._executor.num_save_workers == 2
    assert pipeline.args["loaders"] >= 1


def _planar_views(host):
    """`host`'s frames as a fetch handed them over before sink batches
    were laid out row-major on the device: views into planes."""
    return np.ascontiguousarray(host.transpose(0, 3, 1, 2)) \
        .transpose(0, 2, 3, 1)


def test_an_item_encodes_the_same_from_planar_views_as_from_frames(
        sc, monkeypatch):
    """The encoder is fed the same pixels whether a frame reaches it as
    a strided view of planes (its copy to a contiguous array is made
    before the feed) or contiguous (the copy returns its argument): the
    item on disk is the same, byte for byte."""
    from scanner_tpu.engine.batch import ColumnBatch
    from scanner_tpu.storage import metadata as md
    real, handed = ColumnBatch.to_host, []

    def planar(self):
        got = real(self)
        if got is not self and got.data.ndim == 4:
            got.data = _planar_views(got.data)
            handed.append(got.data[0].flags.c_contiguous)
        return got

    def items(name):
        desc = sc._db.table_descriptor(name)
        return [sc._db.backend.read(
            md.column_item_path(desc.id, "frame", item)) for item in range(2)]

    copied = "scanner_tpu_save_contiguous_seconds_total"
    _blur_run(sc, "128x96", "blurred_contiguous")
    monkeypatch.setattr(ColumnBatch, "to_host", planar)
    before = _counter(copied)
    _blur_run(sc, "128x96", "blurred_planar")
    assert handed == [False, False] and _counter(copied) > before
    assert items("blurred_planar") == items("blurred_contiguous")
    assert all(len(item) > 0 for item in items("blurred_planar"))


def test_a_saved_task_lets_go_of_its_results(sc, monkeypatch):
    """The run keeps every TaskItem until it returns; what a task holds
    of the device (6.2 MB a 1080p row of a frame column) goes when it is
    saved, not when the bulk ends."""
    real = _executor.LocalExecutor._save_task
    seen, held = [], []

    def spy(self, info, w):
        held.append([x.task_idx for x in seen if x.results is not None])
        assert w.results
        seen.append(w)
        return real(self, info, w)

    monkeypatch.setattr(_executor.LocalExecutor, "_save_task", spy)
    monkeypatch.setattr(sc._executor, "num_save_workers", 1)
    _blur_run(sc, "128x96", "blurred_let_go", perf=PerfParams.manual(16, 16))
    assert len(seen) == 4 and held == [[], [], [], []]
    assert all(w.results is None for w in seen)
    assert len(list(NamedStream(sc, "blurred_let_go").load())) == N_FRAMES


def test_a_sink_that_is_no_table_counts_its_fetch_too(sc, tmp_path):
    """The custom-sink branch of the save stage: Histogram rows into one
    file a row."""
    before = {s: _counter(s) for s in SAVE_SERIES}
    frame = sc.io.Input([NamedVideoStream(sc, "128x96")])
    out = FilesStream("hists", str(tmp_path / "files"), codec="pickle")
    job = sc.run(sc.io.Output(sc.ops.Histogram(frame=frame), [out]),
                 PerfParams.manual(16, 32), cache_mode=CacheMode.Overwrite,
                 show_progress=False)
    by = _by_name([iv for p in sc.get_profile(job).profilers
                   for iv in p.intervals()])
    assert len(by["save:fetch"]) == len(by["save:write"]) == 2
    assert "save:encode" not in by
    assert _counter(SAVE_SERIES[3]) - before[SAVE_SERIES[3]] \
        == pytest.approx(sum(iv.end - iv.start for iv in by["save:fetch"]),
                         abs=1e-6)
    # 64 histograms of (3, 16) int32 came down; nothing was encoded
    assert _counter(SAVE_SERIES[4]) - before[SAVE_SERIES[4]] \
        == N_FRAMES * 3 * 16 * 4
    assert _counter(SAVE_SERIES[1]) == before[SAVE_SERIES[1]]


def test_save_wait_records_an_evaluator_held_by_a_full_save_queue(
        sc, monkeypatch):
    """One saver, slowed to 60 ms a task, behind a queue of one: the
    evaluator's hand-off blocks, in a span and a counter of its own."""
    real = _executor.LocalExecutor._save_task

    def slow(self, info, w):
        time.sleep(0.06)
        return real(self, info, w)

    # a run makes its own executor from the client's settings
    monkeypatch.setattr(_executor.LocalExecutor, "_save_task", slow)
    monkeypatch.setattr(sc._executor, "num_save_workers", 1)
    wait = "scanner_tpu_stage_wait_seconds_total"
    before = _counter(wait, stage="evaluate_out")
    by = _by_name(_blur_run(
        sc, "128x96", "blurred_slow", op="Histogram",
        perf=PerfParams.manual(8, 8, queue_size_per_pipeline=1),
        pipeline_instances=1))
    waited = _counter(wait, stage="evaluate_out") - before
    assert len(by["save"]) == 8
    spans = by.get("evaluate:save_wait", [])
    assert len(spans) >= 4
    assert {iv.thread for iv in spans} == {"eval-0"}
    # every wait counts, those too short for a span too
    total = sum(iv.end - iv.start for iv in spans)
    assert 0.2 <= total <= waited <= total + 8 * _executor._WAIT_SPAN_MIN_S
    # the waits lie outside the evaluate spans, on the same thread
    for iv in spans:
        assert not any(e.thread == iv.thread and e.start < iv.end
                       and iv.start < e.end for e in by["evaluate"])
    (pipeline,) = by["run:pipeline"]
    assert pipeline.args["savers"] == 1


def test_an_unhindered_evaluator_waits_next_to_nothing(sc):
    wait = "scanner_tpu_stage_wait_seconds_total"
    before = _counter(wait, stage="evaluate_out")
    by = _by_name(_blur_run(sc, "128x96", "blurred_free", op="Histogram",
                            perf=PerfParams.manual(8, 8),
                            pipeline_instances=1))
    assert _counter(wait, stage="evaluate_out") - before < 0.05
    assert len(by.get("evaluate:save_wait", [])) <= 1

