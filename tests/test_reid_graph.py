"""Upstream's re-identification cascade (open-reid's extract_features.py,
the port examples/reid_features.py): Input -> ObjectDetect -> the user's
Python op TopBox (host) -> CropResize over the frames and that box ->
FaceEmbedding, through `Client.run` on the accelerator path of the CPU
mesh, at 128x96 frames, networks of width 8, crops of 32x16 and
embeddings of 16: against the benchmark's plain reference
(benchmark/reference/ReidFeatures.py) with a clear top box and in a
forced tie; the host op's column put on the device op's chip
(`evaluate:upload`, its counters, the box batch where CropResize runs,
rows bit for bit the per-row path's); the `nms` scope; ObjectDetect's
weights on its instance's chip; and the reference's controls, each
failing.

At this size the embedder pools 2 x 1 cells of a 32x16 crop and the
width-8 detector's log-odds lie further from the reference's than the
cell's: the tie and the limits of box and vector are this size's,
`SMALL`.  Read here (benchmark/tests/reid_controls_on_chip.py at this
size, two seeds): the program's box 0.0013-0.0025 and vector 0.039-0.040;
`fp8` 0.32-0.46 and 0.24-0.49; `no_crop` and `other_box` vectors
0.54-1.10.
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
import scanner_tpu.models  # noqa: F401
from scanner_tpu import video as scv
from scanner_tpu.engine import framecache as fc
from scanner_tpu.kernels import imgproc
from scanner_tpu.util.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, N_FRAMES, KEYINT, SEED = 96, 128, 48, 16, 2 ** 31 + 45
CH, CW = 32, 16
# this size's tie (log-odds) and limits, between the readings of the
# module docstring
SMALL = {"DET_TIE": 0.5, "reid_box_err": 0.03, "reid_embed_gap": 0.1}
CONTROL_FAILS = {"fp8": ["reid_box_err", "reid_embed_gap", "reid_norm_err"],
                 "no_crop": ["reid_embed_gap"],
                 "other_box": ["reid_embed_gap"]}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator, the cell's builder (it registers
    `TopBox`) and its reference, by their own names."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from graphs import reid
        from reference import ReidFeatures
        yield SimpleNamespace(clipgen=clipgen, builder=reid, ref=ReidFeatures)
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture()
def ref(bench, monkeypatch):
    """The reference with this size's tie and limits."""
    monkeypatch.setattr(bench.ref, "DET_TIE", SMALL["DET_TIE"])
    for k in ("reid_box_err", "reid_embed_gap"):
        monkeypatch.setitem(bench.ref.LIMITS, k, SMALL[k])
    return bench.ref


@pytest.fixture(scope="module")
def cfg():
    """The configuration as the cell states it, at the test's sizes."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "reid_1080p.json")) as f:
        c = json.load(f)
    c["video"].update(height=H, width=W, frames=N_FRAMES, keyint=KEYINT)
    ops = {o["op"]: o for o in c["graph"]["ops"]}
    ops["ObjectDetect"]["args"]["width"] = 8
    ops["CropResize"]["args"].update(height=CH, width=CW)
    ops["FaceEmbedding"]["args"].update(width=8, dim=16)
    return c


@pytest.fixture(scope="module")
def clip(tmp_path_factory, bench):
    path = str(tmp_path_factory.mktemp("reid") / "clip.mp4")
    bench.clipgen.encode_clip(path, 13, N_FRAMES, H, W, 24, KEYINT)
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
    fc.cache().clear()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _wire(sc, rows):
    auto = scv.open_automata(sc._db, "movie", output_format="yuv420")
    try:
        return list(np.asarray(auto.get_frames(list(rows))))
    finally:
        auto.close()


def _weights(ref, cfg, tmp_path, params=None):
    """The seeded weights (or `params`) where the ops read them."""
    params = params or ref.init_params(SEED, cfg)
    out = {}
    for op, net in (("ObjectDetect", "det"), ("FaceEmbedding", "emb")):
        path = str(tmp_path / f"{net}.npz")
        np.savez(path, **params[net])
        out[op] = {"checkpoint_dir": path}
    return out


def _run(sc, bench, cfg, op_args, name):
    """The cell's graph over every row; returns (the job, the committed
    rows)."""
    request = [{"table": "movie", "rows": list(range(N_FRAMES)),
                "sampler": "All"}]
    node = bench.builder.build(sc, request, cfg["graph"], op_args)
    out = NamedStream(sc, name)
    job = sc.run(sc.io.Output(node, [out]), PerfParams.manual(8, 16),
                 cache_mode=CacheMode.Overwrite, show_progress=False)
    return job, [np.asarray(r) for r in out.load()]


def _tied(ref, cfg):
    """Seeded weights whose class head scores every anchor of one shape
    (scale 0.25, ratio 1) alike, over every other: its kernel 0, the
    object logit of that shape 3, the rest 0."""
    params = ref.init_params(SEED, cfg)
    kern = params["det"]["params/SSDHead_0/cls/kernel"]
    bias = params["det"]["params/SSDHead_0/cls/bias"]
    kern[:] = 0.0
    classes = cfg["graph"]["ops"][0]["args"]["num_classes"]
    bias[4 * classes + 1] = 3.0
    return params


@pytest.mark.parametrize("case", ["clear", "tie"])
def test_the_cascade_is_the_references(sc, bench, ref, cfg, tmp_path, case):
    """The rows against the reference's comparison: each row's box
    against the reference's candidates, its vector against the
    reference's crop and embedding of that box."""
    import jax
    params = ref.init_params(SEED, cfg) if case == "clear" \
        else _tied(ref, cfg)
    op_args = _weights(ref, cfg, tmp_path, params)
    _, rows = _run(sc, bench, cfg, op_args, f"rows_{case}")
    assert len(rows) == N_FRAMES
    assert all(r.shape == (4 + 16,) and r.dtype == np.float32 for r in rows)
    wires = _wire(sc, range(N_FRAMES))
    values = ref.compare(cfg, wires, rows, params=params)
    assert values["reid_shape_errors"] == 0
    assert values["reid_norm_err"] < 1e-5
    for k in ("reid_box_err", "reid_embed_gap"):
        assert 0 < values[k] <= ref.LIMITS[k], values

    det = cfg["graph"]["detector"]
    detector, _, _ = ref._nets()
    rgb = np.stack([ref.wire.to_rgb(f, H, W) for f in wires])
    with jax.default_matmul_precision("highest"):
        sc_, odds, bx = (np.asarray(a) for a in detector(
            params["det"], rgb, ref.anchors(cfg, H, W), 2,
            det["delta_clip"]))
    ties = [len(ref.candidates(sc_[i], odds[i], bx[i], det))
            for i in range(N_FRAMES)]
    if case == "tie":
        # every non-degenerate anchor of the shape is a candidate
        assert min(ties) > 10


def test_a_host_column_is_put_on_the_device_op_once_a_task(
        sc, bench, ref, cfg, tmp_path, monkeypatch):
    """TopBox's boxes reach CropResize as one (k, 4) float32 array on the
    chip its frames are on; one `evaluate:upload` span a task, inside the
    op's `evaluate:inputs` (a streaming task's chunk a span), and its two
    counters."""
    seen = []
    real = imgproc.CropResize.execute

    def execute(self, frame, box):
        seen.append((type(box).__module__, box.shape, str(box.dtype),
                     set(box.devices()), set(frame.devices())))
        return real(self, frame, box)

    monkeypatch.setattr(imgproc.CropResize, "execute", execute)
    before = {k: _counter(f"scanner_tpu_op_upload_{k}_total", op="CropResize")
              for k in ("seconds", "bytes")}
    job, rows = _run(sc, bench, cfg, _weights(ref, cfg, tmp_path), "upload")
    assert len(rows) == N_FRAMES
    assert seen and all(m.startswith("jax") and dt == "float32"
                        and s[1:] == (4,) and len(bd) == 1 and bd == fd
                        for m, s, dt, bd, fd in seen)
    after = {k: _counter(f"scanner_tpu_op_upload_{k}_total", op="CropResize")
             for k in ("seconds", "bytes")}
    assert after["bytes"] - before["bytes"] == N_FRAMES * 4 * 4
    assert after["seconds"] > before["seconds"]
    ivs = [iv for p in sc.get_profile(job).profilers for iv in p.intervals()]
    up = [iv for iv in ivs if iv.name == "evaluate:upload"]
    inputs = [iv for iv in ivs if iv.name == "evaluate:inputs"
              and iv.args["op"] == "CropResize"]
    # a task's chunk a span: its inputs are made ready once a chunk
    assert len(up) == len(inputs) >= N_FRAMES // 16
    assert {iv.args["op"] for iv in up} == {"CropResize"}
    assert sum(iv.args["rows"] for iv in up) == N_FRAMES
    assert all(any(o.start <= iv.start and iv.end <= o.end for o in inputs)
               for iv in up)


def test_the_staged_boxes_give_the_per_row_paths_rows_bit_for_bit(
        sc, bench, ref, cfg, tmp_path, monkeypatch):
    """The staged batch against the boxes stacked a row at a time and put
    on the default device, as CropResize took them before."""
    import jax.numpy as jnp
    op_args = _weights(ref, cfg, tmp_path)
    _, staged = _run(sc, bench, cfg, op_args, "staged")

    def per_row(self, frame, box):
        boxes = jnp.asarray(np.stack([np.asarray(b, np.float32)
                                      for b in box]))
        return imgproc._crop_resize_impl(jnp.asarray(frame), boxes,
                                         self.height, self.width)

    monkeypatch.setattr(imgproc.CropResize, "execute", per_row)
    _, rows = _run(sc, bench, cfg, op_args, "per_row")
    assert all(np.array_equal(a, b) for a, b in zip(staged, rows))
    # the op itself: the boxes on the host and on the device, alike
    frames = np.random.default_rng(2).integers(0, 255, (5, H, W, 3),
                                               np.uint8)
    boxes = np.asarray([[0.1, 0.2, 0.6, 0.5]] * 5, np.float32) \
        + np.linspace(0, 0.3, 5, dtype=np.float32)[:, None]
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    k = imgproc.CropResize(O.KernelConfig(device=DeviceType.TPU, args={}),
                           height=CH, width=CW)
    assert np.array_equal(np.asarray(k.execute(frames, boxes)),
                          np.asarray(k.execute(frames, jnp.asarray(boxes))))


def test_the_post_head_decode_and_nms_are_under_the_nms_scope():
    import jax
    import jax.numpy as jnp
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    from scanner_tpu.models.detection import ObjectDetect
    k = ObjectDetect(O.KernelConfig(device=DeviceType.TPU, args={}),
                     width=8, pretrained=False)
    images = jax.ShapeDtypeStruct((2, H, W, 3), jnp.uint8)
    anchors = jax.ShapeDtypeStruct((6 * 8 * 9, 4), jnp.float32)
    hlo = k._infer.lower(k.params, images, anchors).as_text(debug_info=True)
    assert "ObjectDetect/nms/" in hlo and "while" in hlo


def test_a_one_chip_instance_keeps_the_detectors_weights_on_its_chip():
    import jax
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    from scanner_tpu.models.detection import ObjectDetect
    chip = jax.devices()[1]
    k = ObjectDetect(O.KernelConfig(device=DeviceType.TPU, args={},
                                    devices=[chip]),
                     width=8, pretrained=False)
    leaves = jax.tree_util.tree_leaves(k.params)
    assert all(leaf.devices() == {chip} and leaf.committed
               for leaf in leaves)
    frames = np.zeros((2, H, W, 3), np.uint8)
    out = k.execute(jax.device_put(frames, chip))
    assert out.devices() == {chip} and out.shape == (2, 32, 6)
    assert all(a.devices() == {chip} for a in k._anchors.values())
    # an instance over several chips leaves them where they were made
    k = ObjectDetect(O.KernelConfig(device=DeviceType.TPU, args={},
                                    devices=jax.devices()[:2]),
                     width=8, pretrained=False)
    assert not any(leaf.committed for leaf in jax.tree_util.tree_leaves(
        k.params))


@pytest.fixture(scope="module")
def control_sample(tmp_path_factory, clip):
    """The wires of the test clip's every row."""
    root = tmp_path_factory.mktemp("reid_controls")
    with Client(db_path=str(root / "db")) as client:
        client.ingest_videos([("movie", clip)])
        return _wire(client, range(N_FRAMES))


@pytest.mark.parametrize("control", ["fp8", "no_crop", "other_box"])
def test_each_control_fails(ref, cfg, control_sample, control):
    """On the frames and weights of the cascade's test above: `fp8` by its
    box, its vector and its norm; the two that crop elsewhere than the
    box they commit, by the vector."""
    assert control in ref.CONTROLS
    values = ref.compare(cfg, control_sample, [None] * len(control_sample),
                         control=control, seed=SEED)
    over = [k for k, limit in ref.LIMITS.items() if values[k] > limit]
    assert over == CONTROL_FAILS[control], values


def test_a_row_is_held_to_a_tied_candidate_and_no_other(ref, cfg,
                                                        control_sample):
    """Rows whose box is the runner-up's (the box of area with the
    highest log-odds apart from TopBox's, more than 0.05 from it) and
    whose vector is the reference's embedding of that box: a box error
    of 0 where every runner-up lies within DET_TIE of its top box, the
    distance to the top box where the tie is narrowed below their leads;
    a vector that is its own box's, whatever the tie."""
    import jax
    params = ref.init_params(SEED, cfg)
    det = cfg["graph"]["detector"]
    detector, embedder, crop = ref._nets()
    wires = control_sample[:4]
    rgb = np.stack([ref.wire.to_rgb(f, H, W) for f in wires])
    with jax.default_matmul_precision("highest"):
        sc_, odds, bx = (np.asarray(a) for a in detector(
            params["det"], rgb, ref.anchors(cfg, H, W), 2,
            det["delta_clip"]))
    tops, picks = [], []
    for i in range(len(rgb)):
        box, j = ref.top_box(sc_[i], bx[i], det)
        area = (bx[i][:, 2] - bx[i][:, 0]) * (bx[i][:, 3] - bx[i][:, 1])
        order = [k for k in np.argsort(-odds[i]) if area[k] > ref.MIN_AREA
                 and np.abs(bx[i][k] - box).max() > 0.05]
        tops.append(box)
        picks.append((bx[i][order[0]], odds[i][j] - odds[i][order[0]]))
    boxes = np.stack([b for b, _ in picks]).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        vecs = np.asarray(embedder(params["emb"], crop(rgb, boxes, CH, CW)))
    rows = [np.concatenate([b, v]) for b, v in zip(boxes, vecs)]
    ref.DET_TIE = max(d for _, d in picks) * 1.01
    wide = ref.compare(cfg, wires, rows, params=params)
    assert wide["reid_box_err"] < 1e-6 and wide["reid_embed_gap"] < 1e-6
    ref.DET_TIE = min(d for _, d in picks) * 0.99
    apart = float(np.abs(boxes - np.stack(tops)).max(1).max())
    assert apart > 0.05
    narrow = ref.compare(cfg, wires, rows, params=params)
    assert narrow["reid_box_err"] == pytest.approx(apart, abs=1e-6)
    assert narrow["reid_embed_gap"] < 1e-6
    # a row that is no (4 + dim,) vector is counted and has no distance
    short = ref.compare(cfg, wires, [rows[0][:8]] + rows[1:], params=params)
    assert short["reid_shape_errors"] == 1
    assert short["reid_box_err"] <= apart + 1e-6


def test_a_saturated_score_ties_every_anchor_at_one(ref, cfg):
    """float32 scores stand at exactly 1.0 past log-odds ~17, and TopBox
    orders such a tie no way in particular: the reference's pick is the
    first NMS keeps (log-odds 30 here), the program's may be another at
    1.0 (18).  Every anchor at 1.0, and one within DET_TIE below the
    weakest of them, is a candidate; one further below is not."""
    det = cfg["graph"]["detector"]
    t = ref.DET_TIE
    boxes = np.array([[0.0, 0.0, 0.2, 0.2], [0.5, 0.5, 0.7, 0.7],
                      [0.3, 0.0, 0.45, 0.15], [0.8, 0.8, 0.95, 0.95]],
                     np.float32)
    odds = np.array([30.0, 18.0, 18.0 - t / 2, 18.0 - 2 * t], np.float32)
    scores = np.array([1.0, 1.0, 1.0 - 2 ** -24, 1.0 - 2 ** -20], np.float32)
    box, j = ref.top_box(scores, boxes, det)
    assert j == 0
    got = ref.candidates(scores, odds, boxes, det)
    assert [int(np.abs(boxes - c).max(1).argmin()) for c in got] == [0, 1, 2]


@pytest.mark.parametrize("side", ["program_has_area", "reference_has_area"])
def test_a_sliver_on_the_area_tests_edge_is_a_candidate(ref, cfg, side):
    """A box clipped to a sliver at the frame's edge passes TopBox's area
    test on one side of the rounding and fails it on the other: 0 high
    in the reference and a hair over in the program (which commits the
    sliver), or the other way round (the program commits the next box of
    sure area).  Both are candidates either way; a box DET_TIE under the
    box of sure area is not."""
    det = cfg["graph"]["detector"]
    t = ref.DET_TIE
    high = 0.0 if side == "program_has_area" else 4e-4
    boxes = np.array([[0.0, 0.0, high, 0.05], [0.2, 0.2, 0.4, 0.4],
                      [0.6, 0.6, 0.8, 0.8]], np.float32)
    odds = np.array([9.0, 8.0, 8.0 - 2 * t], np.float32)
    scores = (1.0 / (1.0 + np.exp(-odds))).astype(np.float32)
    got = ref.candidates(scores, odds, boxes, det)
    first = 1 if side == "program_has_area" else 0
    assert [int(np.abs(boxes - c).max(1).argmin()) for c in got] \
        == [first, 1 - first]
