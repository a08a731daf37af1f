"""A sink batch reaches the host row-major (engine/batch.py
`prefetch_host`, `to_host`, `row_programs(...).rowmajor`): a device
batch the backend holds off row-major (a frame column on the chip is
planar) is laid out row-major by one device program before its copy, so
the host's array is C-contiguous and its rows contiguous views; a batch
that is row-major already, host data and a convert-marked batch
dispatch nothing; and `scanner_tpu_sink_rows_total{layout}` with the
`evaluate:prefetch` span's args say which it was.  The CPU backend
keeps the layout a `Format` asks for, so the engaged branch is driven
with real planar arrays.
"""

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.common import NullElement
from scanner_tpu.engine import batch as _batch
from scanner_tpu.engine import framecache as fc
from scanner_tpu.util.metrics import registry

PLANAR = (0, 3, 1, 2)  # major to minor: N, C, H, W
COLUMNS = {"frames": ((6, 12, 16, 3), np.uint8),
           "fields": ((6, 12, 16, 2), np.float32)}
SINK_ROWS = "scanner_tpu_sink_rows_total"


def _planar(host):
    """`host` on the first device, laid out as the chip lays a frame
    column out."""
    import jax
    from jax.experimental.layout import Format, Layout
    return jax.device_put(host, Format(
        Layout(major_to_minor=PLANAR),
        jax.sharding.SingleDeviceSharding(jax.devices()[0])))


def _column(name):
    shape, dtype = COLUMNS[name]
    values = np.random.default_rng(7).integers(0, 200, shape)
    return values.astype(dtype)


@pytest.fixture()
def dispatched(monkeypatch):
    """The merged shapes the `rowmajor` program was dispatched for."""
    programs = _batch.row_programs("columnbatch")
    calls, real = [], programs.rowmajor
    monkeypatch.setattr(programs, "rowmajor", lambda data, shape:
                        (calls.append(shape), real(data, shape))[1])
    return calls


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetched", "to_host_alone"])
@pytest.mark.parametrize("column", sorted(COLUMNS))
def test_a_planar_batch_comes_back_row_major(dispatched, column, prefetch):
    host = _column(column)
    device = _planar(host)
    # what the parent's fetch handed the savers: strided as laid out
    assert not np.asarray(device).flags.c_contiguous
    b = _batch.ColumnBatch(np.arange(10, 16), device)
    assert b.sink_layout == "relaid"
    if prefetch:
        assert b.prefetch_host() is b
        assert b.data is not device and b.sink_layout == "relaid"
        assert not _batch.off_row_major(b.data)
        b.prefetch_host()  # a second start lays nothing out again
    got = b.to_host()
    assert got.data.dtype == host.dtype and got.data.shape == host.shape
    assert got.data.flags.c_contiguous
    np.testing.assert_array_equal(got.data, host)
    rows = got.elements()
    assert all(r.flags.c_contiguous and np.shares_memory(r, got.data)
               for r in rows)
    assert np.ascontiguousarray(rows[3]) is rows[3]
    merged = _batch.merged_row_shape(host.shape)
    assert dispatched == [merged] and merged == (6, 12 * 16 * host.shape[3])
    if prefetch:
        # fetched again, it is the same array: nothing is laid out twice
        np.testing.assert_array_equal(b.to_host().data, host)
        assert dispatched == [merged]
    else:
        # a fetch alone leaves the batch as it was
        assert b.data is device


def _host_batch():
    return _batch.ColumnBatch(np.arange(6), _column("frames"))


def _row_major_batch():
    import jax
    return _batch.ColumnBatch(np.arange(6),
                              jax.device_put(_column("frames")))


def _wire_batch():
    wire = np.arange(6 * 12 * 16 * 3 // 2, dtype=np.uint8).reshape(6, -1)
    return _batch.ColumnBatch(np.arange(6), _planar(
        wire.reshape(6, 1, 1, -1)), convert=("yuv420", 12, 16))


@pytest.mark.parametrize("make,layout", [
    (_host_batch, None), (_row_major_batch, "asis"), (_wire_batch, "asis")],
    ids=["host_data", "row_major", "convert_marked"])
def test_what_is_not_off_row_major_dispatches_nothing(dispatched, make,
                                                      layout):
    b = make()
    data = b.data
    assert b.sink_layout == layout
    assert b.prefetch_host() is b and b.data is data
    got = b.to_host()
    assert (got is b) == (layout is None)
    assert got.convert == b.convert
    np.testing.assert_array_equal(got.data, np.asarray(data))
    assert dispatched == []


@pytest.mark.parametrize("shape,merged", [
    ((32, 1080, 1920, 3), (32, 1080, 5760)),
    ((32, 1080, 1920, 2), (32, 1080, 3840)),
    ((4, 6, 8, 3), (4, 144)), ((16, 3, 16), (16, 48)),
    ((8, 5, 256), (8, 5, 256)), ((32, 17), (32, 17)), ((32,), (32,))])
def test_trailing_dimensions_merge_until_the_minor_one_is_long(shape, merged):
    assert _batch.merged_row_shape(shape) == merged


@pytest.mark.parametrize("column", sorted(COLUMNS))
def test_the_program_is_a_reshape_under_the_batch_scope(column):
    host = _column(column)
    program = _batch.row_programs("columnbatch").rowmajor
    merged = _batch.merged_row_shape(host.shape)
    lowered = program.lower(_planar(host), merged).as_text(debug_info=True)
    assert "/columnbatch/" in lowered
    got = program(_planar(host), merged)
    np.testing.assert_array_equal(np.asarray(got), host.reshape(merged))
    assert not _batch.off_row_major(got)


def test_a_batch_without_trailing_dimensions_goes_as_it_is(dispatched):
    """Two dimensions leave nothing to merge: whatever the layout, the
    fetch is the plain one."""
    import jax
    from jax.experimental.layout import Format, Layout
    host = np.arange(6 * 9, dtype=np.int32).reshape(6, 9)
    device = jax.device_put(host, Format(
        Layout(major_to_minor=(1, 0)),
        jax.sharding.SingleDeviceSharding(jax.devices()[0])))
    b = _batch.ColumnBatch(np.arange(6), device)
    assert _batch.off_row_major(device) and b.sink_layout == "asis"
    np.testing.assert_array_equal(b.prefetch_host().to_host().data, host)
    assert dispatched == []


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetched", "to_host_alone"])
def test_nulls_and_row_ranges_after_the_fetch_behave_as_before(prefetch):
    host = _column("fields")
    nulls = np.array([False, True, False, False, True, False])
    b = _batch.ColumnBatch(np.arange(20, 26), _planar(host), nulls)
    if prefetch:
        b.prefetch_host()
    got = b.to_host()
    np.testing.assert_array_equal(got.nulls, nulls)
    part = got.take_range(22, 25)
    assert np.shares_memory(part.data, got.data)
    rows = part.elements()
    assert isinstance(rows[2], NullElement)
    np.testing.assert_array_equal(rows[0], host[2])
    np.testing.assert_array_equal(rows[1], host[3])
    if not prefetch:
        # rows taken on the device, before any fetch, come back whole too
        np.testing.assert_array_equal(
            b.take_range(21, 24).to_host().data, host[1:4])
        np.testing.assert_array_equal(b.get_row(23), host[3])


def _counted():
    return {layout: sum(
        s["value"] for s in registry().snapshot().get(
            SINK_ROWS, {"samples": []})["samples"]
        if s["labels"].get("layout") == layout)
        for layout in ("relaid", "asis")}


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    """A client on the accelerator path of the CPU mesh: the sinks'
    batches are device arrays."""
    root = tmp_path_factory.mktemp("sink_layout")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=48, width=64, height=48, fps=24,
                         keyint=16)
    mp = pytest.MonkeyPatch()
    mp.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    mp.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("clip", vid)])
    yield client
    client.stop()
    fc.set_enabled(was)
    fc.cache().clear()
    mp.undo()


@pytest.mark.parametrize("planar,async_fetch", [
    (False, "1"), (True, "1"), (True, "0")],
    ids=["row_major", "planar", "planar_fetched_by_the_saver"])
def test_sink_rows_are_counted_by_layout_and_the_span_says_the_same(
        sc, monkeypatch, planar, async_fetch):
    """`relaid` + `asis` = the rows of the device sink batches; where
    the copy starts at eval-done the `evaluate:prefetch` spans carry the
    same counts.  A frame column counts as off row-major here by the one
    predicate patched: the CPU backend's own results never are."""
    monkeypatch.setenv("SCANNER_TPU_ASYNC_SINK_FETCH", async_fetch)
    if planar:
        monkeypatch.setattr(_batch, "off_row_major",
                            lambda data: data.ndim == 4)
    before = _counted()
    name = f"blurred_{planar}_{async_fetch}"
    frame = sc.io.Input([NamedVideoStream(sc, "clip")])
    job = sc.run(sc.io.Output(sc.ops.Blur(frame=frame),
                              [NamedStream(sc, name)]),
                 PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
                 show_progress=False)
    moved = {k: v - before[k] for k, v in _counted().items()}
    assert moved == ({"relaid": 48, "asis": 0} if planar
                     else {"relaid": 0, "asis": 48})
    spans = [iv for p in sc.get_profile(job).profilers
             for iv in p.intervals() if iv.name == "evaluate:prefetch"]
    assert len(spans) == 3
    for layout in ("relaid", "asis"):
        assert sum(iv.args.get(layout, 0) for iv in spans) \
            == (moved[layout] if async_fetch == "1" else 0)
    frames = [np.asarray(f) for f in NamedStream(sc, name).load()]
    assert len(frames) == 48 and frames[0].shape == (48, 64, 3)
    if planar and async_fetch == "1":
        want = [np.asarray(f) for f in
                NamedStream(sc, "blurred_False_1").load()]
        assert all(np.array_equal(a, b) for a, b in zip(frames, want))
