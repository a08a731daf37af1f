"""YUV420 wire format: decode at 1.5 B/px, convert to RGB on the device.

The h2d halving of PERF.md §5 (reference analog: NV12 shipped to the GPU
and converted by scanner/util/image.cu:22).  Pinned here:
  - device and host converters are bit-identical (integer fixed point)
  - YUV-decoded + converted frames agree with the swscale RGB24 decode
    within chroma-interpolation tolerance and carry the same semantics
  - the ENGINE path (SCANNER_TPU_YUV_DEVICE=force on the CPU mesh) is
    bit-identical to the host-converted reference, including through
    samplers/gathers operating on the flat wire rows
"""

import os
import tempfile

import numpy as np
import pytest

from scanner_tpu import video as scv
from scanner_tpu.kernels.color import yuv420_to_rgb_device, yuv420_to_rgb_host
from scanner_tpu.video.lib import yuv420_frame_bytes


@pytest.fixture(scope="module")
def clip(tmp_path_factory):
    p = str(tmp_path_factory.mktemp("yuvclip") / "clip.mp4")
    scv.synthesize_video(p, num_frames=48, width=128, height=96, fps=24,
                         keyint=8)
    return p


def test_converters_bit_exact_all_geometries():
    rng = np.random.RandomState(7)
    for h, w in [(96, 128), (97, 129), (33, 31), (480, 640)]:
        flat = rng.randint(0, 256, (3, yuv420_frame_bytes(h, w)), np.uint8)
        host = yuv420_to_rgb_host(flat, h, w)
        dev = np.asarray(yuv420_to_rgb_device(flat, h, w))
        assert host.shape == (3, h, w, 3)
        assert (host == dev).all(), f"device/host mismatch at {h}x{w}"


# the studio swing's ends, the chroma ceiling and both ends of a byte
EXTREMES = np.array([0, 16, 235, 240, 255], np.uint8)


@pytest.mark.parametrize("rows", [1, 2, 6, 16, 17])
def test_device_converter_bit_exact_at_1080p(rows):
    """The kernel at the benchmark's geometry and every chunk length its
    cells meet: nine row blocks a frame, the last ragged (56 of 128
    rows), chroma lines padded 270 -> 272.  Row 0 holds only the
    extremes, drawn independently in each plane; the rest is noise."""
    h, w = 1080, 1920
    rng = np.random.RandomState(rows)
    flat = rng.randint(0, 256, (rows, yuv420_frame_bytes(h, w)), np.uint8)
    flat[0] = rng.choice(EXTREMES, flat.shape[1])
    dev = np.asarray(yuv420_to_rgb_device(flat, h, w))
    assert dev.shape == (rows, h, w, 3) and dev.dtype == np.uint8
    for i in range(rows):  # a frame at a time: int32 planes of one only
        assert (dev[i] == yuv420_to_rgb_host(flat[i], h, w)).all(), i


def test_device_converter_keeps_leading_dims():
    rng = np.random.RandomState(3)
    flat = rng.randint(0, 256, (2, 3, yuv420_frame_bytes(36, 52)), np.uint8)
    host = yuv420_to_rgb_host(flat, 36, 52)
    assert host.shape == (2, 3, 36, 52, 3)
    assert (np.asarray(yuv420_to_rgb_device(flat, 36, 52)) == host).all()
    assert (np.asarray(yuv420_to_rgb_device(flat[0, 0], 36, 52))
            == host[0, 0]).all()


def test_yuv_decode_matches_sws_decode(tmp_db, clip):
    """Same frames decoded both ways: planar YUV + our fixed-point
    conversion vs swscale's packed RGB24.  The two conversions differ in
    chroma interpolation (nearest vs bilinear) and rounding, so equality
    is tolerance-based; the per-frame pattern id must survive exactly."""
    _, failed = scv.ingest_videos(tmp_db, [("c", clip)])
    assert not failed
    rows = [0, 7, 8, 23, 47]
    rgb = scv.load_frames(tmp_db, "c", rows)

    from scanner_tpu.storage import metadata as md
    from scanner_tpu.video.automata import DecoderAutomata
    desc = tmp_db.table_descriptor("c")
    vd = scv.load_video_meta(tmp_db, "c")
    a = DecoderAutomata(tmp_db.backend, vd,
                        md.column_item_path(desc.id, "frame", 0),
                        output_format="yuv420")
    try:
        flat = a.get_frames(rows)
    finally:
        a.close()
    assert flat.shape == (len(rows), yuv420_frame_bytes(96, 128))
    conv = yuv420_to_rgb_host(flat, 96, 128)
    diff = np.abs(conv.astype(int) - rgb.astype(int))
    assert diff.mean() < 3.0, f"mean diff {diff.mean():.2f}"
    assert np.percentile(diff, 99) <= 12, \
        f"p99 diff {np.percentile(diff, 99)}"
    for f, r in zip(conv, rows):
        assert scv.frame_pattern_id(f) == r % 14


def test_full_range_stream_not_plane_copied(tmp_db, tmp_path):
    """mjpeg decodes to FULL-range 4:2:0 (yuvj420p); a verbatim plane
    copy would feed full-range values into the limited-range on-device
    converter and stretch every tone.  The C layer must route full-range
    frames through swscale's range compression, keeping the YUV wire
    within tolerance of the RGB24 decode."""
    from scanner_tpu.storage import metadata as md
    from scanner_tpu.video.automata import DecoderAutomata

    from scanner_tpu.video.ingest import encode_frames_mp4

    p = str(tmp_path / "mj.mp4")
    try:
        encode_frames_mp4(
            p, (scv.frame_pattern(i, 96, 128) for i in range(8)),
            128, 96, codec="mjpeg")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"mjpeg encoder unavailable: {e}")
    _, failed = scv.ingest_videos(tmp_db, [("mj", p)])
    assert not failed
    rows = list(range(8))
    rgb = scv.load_frames(tmp_db, "mj", rows)
    desc = tmp_db.table_descriptor("mj")
    vd = scv.load_video_meta(tmp_db, "mj")
    a = DecoderAutomata(tmp_db.backend, vd,
                        md.column_item_path(desc.id, "frame", 0),
                        output_format="yuv420")
    try:
        flat = a.get_frames(rows)
    finally:
        a.close()
    conv = yuv420_to_rgb_host(flat, 96, 128)
    diff = np.abs(conv.astype(int) - rgb.astype(int))
    # an unconverted full-range plane copy shows mean diff > 10 here
    assert diff.mean() < 4.0, f"full-range handling broken: {diff.mean()}"


def test_engine_yuv_wire_bit_exact(monkeypatch, tmp_path):
    """Engine run with the YUV wire forced on the CPU mesh: results are
    bit-identical to numpy histograms over host-converted YUV frames —
    the wire format changes bytes-on-the-link, never results."""
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    import scanner_tpu.kernels  # noqa: F401
    from scanner_tpu.storage import metadata as md
    from scanner_tpu.video.automata import DecoderAutomata

    monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    root = tempfile.mkdtemp(prefix="yuvwire_")
    vid = os.path.join(root, "v.mp4")
    scv.synthesize_video(vid, num_frames=40, width=128, height=96, fps=24,
                         keyint=8)
    sc = Client(db_path=os.path.join(root, "db"))
    try:
        sc.ingest_videos([("t", vid)])
        # stride sampler exercises row gathers on the FLAT wire rows
        frames = sc.io.Input([NamedVideoStream(sc, "t")])
        strided = sc.streams.Stride(frames, [2])
        out = NamedStream(sc, "h")
        sc.run(sc.io.Output(sc.ops.Histogram(frame=strided), [out]),
               PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
               show_progress=False)
        got = np.stack(list(out.load()))

        desc = sc._db.table_descriptor("t")
        vd = scv.load_video_meta(sc._db, "t")
        a = DecoderAutomata(sc._db.backend, vd,
                            md.column_item_path(desc.id, "frame", 0),
                            output_format="yuv420")
        try:
            flat = a.get_frames(list(range(0, 40, 2)))
        finally:
            a.close()
        ref_frames = yuv420_to_rgb_host(flat, 96, 128)
        v = (ref_frames >> 4).astype(np.int32)
        expect = np.stack([
            np.stack([np.bincount(v[i, :, :, c].ravel(), minlength=16)
                      for c in range(3)])
            for i in range(v.shape[0])]).astype(got.dtype)
        assert got.shape == expect.shape
        assert (got == expect).all(), "engine YUV path altered results"
    finally:
        sc.stop()


def test_engine_yuv_off_uses_sws(monkeypatch, tmp_path):
    """SCANNER_TPU_YUV_DEVICE=0 keeps the classic RGB24 decode: results
    match numpy histograms over swscale-decoded frames."""
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    import scanner_tpu.kernels  # noqa: F401

    monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "0")
    root = tempfile.mkdtemp(prefix="yuvoff_")
    vid = os.path.join(root, "v.mp4")
    scv.synthesize_video(vid, num_frames=16, width=64, height=48, fps=24)
    sc = Client(db_path=os.path.join(root, "db"))
    try:
        sc.ingest_videos([("t", vid)])
        frames = sc.io.Input([NamedVideoStream(sc, "t")])
        out = NamedStream(sc, "h")
        sc.run(sc.io.Output(sc.ops.Histogram(frame=frames), [out]),
               PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
               show_progress=False)
        got = np.stack(list(out.load()))
        rgb = scv.load_frames(sc._db, "t", list(range(16)))
        v = (rgb >> 4).astype(np.int32)
        expect = np.stack([
            np.stack([np.bincount(v[i, :, :, c].ravel(), minlength=16)
                      for c in range(3)])
            for i in range(v.shape[0])]).astype(got.dtype)
        assert (got == expect).all()
    finally:
        sc.stop()
