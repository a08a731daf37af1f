"""Model family tests: ops through the engine + sharded train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401
import scanner_tpu.models   # registers model ops
from scanner_tpu import video as scv
from scanner_tpu.models import make_sharded_train_step
from scanner_tpu.models.pose import heatmaps_to_keypoints
from scanner_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("models")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=32, width=128, height=128, fps=24,
                         keyint=8)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("test1", vid)])
    yield client
    client.stop()


def _run(sc, col, name):
    out = NamedStream(sc, name)
    sc.run(sc.io.Output(col, [out]), PerfParams.manual(8, 16),
           cache_mode=CacheMode.Overwrite, show_progress=False)
    return list(out.load())


def test_pose_detect_e2e(sc):
    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 8)])
    pose = sc.ops.PoseDetect(frame=sampled)
    rows = _run(sc, pose, "pose_out")
    assert len(rows) == 8
    assert rows[0].shape == (17, 3)


def test_object_and_face_detect_e2e(sc):
    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 4)])
    det = sc.ops.ObjectDetect(frame=sampled)
    rows = _run(sc, det, "det_out")
    assert len(rows) == 4
    # packed (top_k, 6) rows [y1,x1,y2,x2,score,valid]
    from scanner_tpu.models import unpack_detections
    d0 = unpack_detections(rows[0])
    assert np.asarray(rows[0]).shape[1] == 6
    assert "boxes" in d0 and d0["boxes"].shape[1:] == (4,)

    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 4)])
    fd = sc.ops.FaceDetect(frame=sampled)
    rows = _run(sc, fd, "face_out")
    assert len(rows) == 4


def test_face_embedding_e2e(sc):
    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 6)])
    emb = sc.ops.FaceEmbedding(frame=sampled)
    rows = _run(sc, emb, "emb_out")
    assert len(rows) == 6
    assert rows[0].shape == (128,)
    np.testing.assert_allclose(np.linalg.norm(rows[0]), 1.0, rtol=1e-4)


def test_shot_detection_e2e(sc):
    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    d = sc.ops.HistDiff(frame=frame)
    rows = _run(sc, d, "shots_out")
    assert len(rows) == 32
    assert all(isinstance(r, float) for r in rows)
    from scanner_tpu.kernels.shot import detect_shots
    detect_shots(np.asarray(rows))


def test_heatmaps_to_keypoints():
    heat = np.zeros((16, 16, 17), np.float32)
    heat[3, 7, 0] = 5.0
    kp = heatmaps_to_keypoints(heat)
    assert tuple(kp[0][:2]) == (7.0, 3.0)
    assert kp[0][2] == 5.0


@pytest.mark.slow  # multi-minute XLA compile of the full multi-chip train step on CPU
def test_sharded_train_step_dp_sp_tp():
    """Full multi-chip training step on the virtual 8-device mesh:
    dp=2 (batch) x sp=2 (ring-attention time) x tp=2 (channels+experts)."""
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    step, params, opt_state, (clip, target) = make_sharded_train_step(
        mesh, clip_shape=(4, 4, 64, 64, 3), width=32)
    params, opt_state, loss = step(params, opt_state, clip, target)
    params, opt_state, loss = step(params, opt_state, clip, target)
    assert np.isfinite(float(loss))


@pytest.mark.slow  # multi-minute XLA compile of the full multi-chip train step on CPU
def test_train_checkpoint_roundtrip(tmp_path):
    import jax
    from scanner_tpu.models.checkpoint import TrainCheckpointer
    from scanner_tpu.models import make_sharded_train_step
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    step, params, opt_state, (clip, target) = make_sharded_train_step(
        mesh, clip_shape=(2, 4, 32, 32, 3), width=32)
    params, opt_state, loss1 = step(params, opt_state, clip, target)
    ck = TrainCheckpointer(str(tmp_path / "ckpt"))
    ck.save(1, params, opt_state)
    assert ck.latest_step() == 1
    # restore onto the same shardings and take another step
    p2, o2, s = ck.restore(params, opt_state)
    p2, o2, loss2 = step(p2, o2, clip, target)
    assert s == 1 and float(loss2) <= float(loss1) * 1.5
    ck.close()


def test_params_npz_roundtrip(tmp_path):
    import jax
    from scanner_tpu.models import init_params
    from scanner_tpu.models.checkpoint import (export_params_npz,
                                               import_params_npz)
    _, params = init_params(jax.random.PRNGKey(3),
                            clip_shape=(1, 2, 32, 32, 3), width=8)
    p = str(tmp_path / "w.npz")
    export_params_npz(params, p)
    restored = import_params_npz(p, params)
    flat1 = jax.tree_util.tree_leaves(params)
    flat2 = jax.tree_util.tree_leaves(restored)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(flat1, flat2))
    # width mismatch fails loudly, not silently
    _, wrong = init_params(jax.random.PRNGKey(3),
                           clip_shape=(1, 2, 32, 32, 3), width=16)
    with pytest.raises((ValueError, KeyError)):
        import_params_npz(p, wrong)


def test_pose_shipped_weights_localize(tmp_path):
    """E2E: PoseDetect restoring the SHIPPED weights localizes the blob in
    an encoded clip far better than chance (reference pose app semantics —
    real trained weights, not random init)."""
    import os
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    from scanner_tpu.models.pose_train import (SIZE, WIDTH,
                                               synth_blob_video)

    weights = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scanner_tpu", "models", "weights", "pose_blobnet_w8.npz")
    assert os.path.exists(weights), "shipped weights missing"

    vid = str(tmp_path / "blob.mp4")
    centers = synth_blob_video(vid, num_frames=16)
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        movie = NamedVideoStream(sc, "blob", path=vid)
        poses = sc.ops.PoseDetect(frame=sc.io.Input([movie]), width=WIDTH,
                                  checkpoint_dir=weights)
        out = NamedStream(sc, "poses_out")
        sc.run(sc.io.Output(poses, [out]), PerfParams.estimate(),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        errs = []
        for i, kp in enumerate(out.load()):
            x, y = kp[0, 0] * 4, kp[0, 1] * 4
            errs.append(float(np.hypot(x - centers[i, 0],
                                       y - centers[i, 1])))
        assert len(errs) == 16
        # chance (uniform argmax over the heatmap) averages ~SIZE/2*0.76
        # ~= 18px here; the trained weights must be several times better
        assert np.mean(errs) < 5.0, f"mean error {np.mean(errs):.1f}px"
    finally:
        sc.stop()


def test_model_ops_checkpoint_restore(tmp_path):
    """Every model op restores exported weights (uniform weight path)."""
    import jax
    import jax.numpy as jnp
    from scanner_tpu.graph.ops import KernelConfig, registry
    from scanner_tpu.common import DeviceType
    from scanner_tpu.models.checkpoint import export_params_npz

    cfg = KernelConfig(device=DeviceType.TPU)
    for op_name, kw in [("ObjectDetect", dict(width=8)),
                        ("FaceDetect", dict(width=8)),
                        ("FaceEmbedding", dict(width=8, dim=16))]:
        spec = registry.get(op_name)
        k1 = spec.kernel_factory(cfg, **kw)
        p = str(tmp_path / f"{op_name}.npz")
        export_params_npz(k1.params, p)
        k2 = spec.kernel_factory(cfg, checkpoint_dir=p, **kw)
        leaves1 = jax.tree_util.tree_leaves(k1.params)
        leaves2 = jax.tree_util.tree_leaves(k2.params)
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(leaves1, leaves2)), op_name
        # restored kernel runs
        frames = np.random.RandomState(0).randint(
            0, 255, (2, 64, 64, 3), np.uint8)
        out = k2.execute(frames)
        assert len(out) == 2


def test_data_parallel_inference_multichip():
    """Model kernels dp-shard inference across the devices the engine
    hands them; results match single-device exactly."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (virtual CPU mesh)")
    from scanner_tpu.common import DeviceType
    from scanner_tpu.graph.ops import KernelConfig, registry

    frames = np.random.RandomState(0).randint(
        0, 255, (8, 64, 64, 3), np.uint8)
    spec = registry.get("FaceEmbedding")
    k1 = spec.kernel_factory(
        KernelConfig(device=DeviceType.TPU), width=8, dim=16)
    k4 = spec.kernel_factory(
        KernelConfig(device=DeviceType.TPU,
                     devices=list(jax.devices()[:4])), width=8, dim=16)
    out1 = np.stack(k1.execute(frames))
    out4 = np.stack(k4.execute(frames))
    np.testing.assert_allclose(out1, out4, rtol=1e-5, atol=1e-6)
    # the sharded path really spans the chips
    sharded = jax.device_put(jnp.asarray(frames), k4._dp._data_sharding)
    assert len({s.device for s in sharded.addressable_shards}) == 4
    # odd batch pads to the device multiple and slices (still correct)
    odd = frames[:5]
    np.testing.assert_allclose(np.stack(k1.execute(odd)),
                               np.stack(k4.execute(odd)),
                               rtol=1e-5, atol=1e-6)


def test_detect_shipped_weights_localize(tmp_path):
    """E2E: ObjectDetect with the SHIPPED weights (restored by default at
    width 8) localizes synthetic scenes through the video codec path —
    reference object-detection app semantics (trained model by default,
    object_detection_tensorflow/main.py:16-23)."""
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    from scanner_tpu.models.detect_train import (WIDTH, box_iou,
                                                 synth_scene_video)
    from scanner_tpu.models.checkpoint import shipped_weights

    assert shipped_weights("detect_ssd_w8.npz"), "shipped weights missing"
    vid = str(tmp_path / "scenes.mp4")
    truth = synth_scene_video(vid, num_frames=12, seed=21)
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        movie = NamedVideoStream(sc, "scenes", path=vid)
        dets = sc.ops.ObjectDetect(frame=sc.io.Input([movie]), width=WIDTH,
                                   score_thresh=0.3)
        out = NamedStream(sc, "dets_out")
        sc.run(sc.io.Output(dets, [out]), PerfParams.estimate(),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        hits = total = 0
        from scanner_tpu.models import unpack_detections
        for i, det in enumerate(out.load()):
            boxes = unpack_detections(det)["boxes"]
            for gt in truth[i]:
                total += 1
                if any(box_iou(gt, b) >= 0.3 for b in boxes):
                    hits += 1
        assert total >= 12
        assert hits >= 0.7 * total, f"recall {hits}/{total}"
    finally:
        sc.stop()


def test_face_shipped_weights_localize(tmp_path):
    """E2E: FaceDetect's shipped face-task weights localize face scenes
    (reference face_detection app semantics)."""
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    from scanner_tpu.models.detect_train import (WIDTH, box_iou,
                                                 render_face_scene,
                                                 synth_scene_video)
    from scanner_tpu.models.checkpoint import shipped_weights

    assert shipped_weights("face_ssd_w8.npz"), "shipped weights missing"
    vid = str(tmp_path / "faces.mp4")
    truth = synth_scene_video(vid, renderer=render_face_scene,
                              num_frames=12, seed=22)
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        movie = NamedVideoStream(sc, "faces", path=vid)
        dets = sc.ops.FaceDetect(frame=sc.io.Input([movie]), width=WIDTH,
                                 score_thresh=0.3)
        out = NamedStream(sc, "faces_out")
        sc.run(sc.io.Output(dets, [out]), PerfParams.estimate(),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        hits = total = 0
        from scanner_tpu.models import unpack_detections
        for i, det in enumerate(out.load()):
            boxes = unpack_detections(det)["boxes"]
            for gt in truth[i]:
                total += 1
                if any(box_iou(gt, b) >= 0.3 for b in boxes):
                    hits += 1
        assert total >= 12
        assert hits >= 0.7 * total, f"recall {hits}/{total}"
    finally:
        sc.stop()


def test_embedding_shipped_weights_recall():
    """The shipped embedding separates identities: probe views match
    gallery views of the same procedural identity (recall@1) well above
    chance (1/8)."""
    import jax.numpy as jnp

    from scanner_tpu.graph.ops import KernelConfig
    from scanner_tpu.common import DeviceType
    from scanner_tpu.models.detect_train import WIDTH, render_identity
    from scanner_tpu.models.face import FaceEmbedding
    from scanner_tpu.models.checkpoint import shipped_weights

    assert shipped_weights("embed_w8.npz"), "shipped weights missing"
    k = FaceEmbedding(KernelConfig(device=DeviceType.CPU), width=WIDTH)
    rng = np.random.RandomState(99)
    idents = list(range(8))
    gallery = np.stack([render_identity(i, rng) for i in idents])
    probe = np.stack([render_identity(i, rng) for i in idents])
    g = np.stack(k.execute(gallery))
    p = np.stack(k.execute(probe))
    sim = p @ g.T                      # cosine (embeddings normalized)
    pred = sim.argmax(1)
    recall = float((pred == np.arange(8)).mean())
    assert recall >= 0.75, f"recall@1 {recall:.2f}"


@pytest.mark.slow  # multi-minute XLA compile of the full multi-chip train step on CPU
def test_attention_scheme_selection():
    """attn_scheme (or SCANNER_TPU_ATTN) selects the sequence-parallel
    attention for the sharded train step; all three schemes (XLA ring,
    pallas-flash ring, Ulysses all-to-all) train to the SAME losses over
    TWO steps from the same seed — the second step's loss depends on the
    first step's gradients, so this pins the backward pass too (incl.
    the pallas custom_vjp)."""
    from scanner_tpu.models import make_sharded_train_step
    from scanner_tpu.parallel import auto_axes, make_mesh

    schemes = ["ring", "ulysses", "pallas"]
    losses = {}
    for scheme in schemes:
        mesh = make_mesh(auto_axes(8))
        step, params, opt_state, (clip, target) = make_sharded_train_step(
            mesh, clip_shape=(2, 8, 32, 32, 3), width=8,
            attn_scheme=scheme)
        params, opt_state, l1 = step(params, opt_state, clip, target)
        params, opt_state, l2 = step(params, opt_state, clip, target)
        losses[scheme] = (float(l1), float(l2))
        assert np.isfinite(losses[scheme]).all(), (scheme, losses[scheme])
        assert losses[scheme][1] < losses[scheme][0], \
            f"{scheme}: loss did not decrease {losses[scheme]}"
    # rel 1e-3: schemes reduce in different orders (ppermute chain vs
    # all-to-all vs pallas tiles), so f32 losses agree to ~1e-4 but not
    # bitwise; a broken backward diverges by orders of magnitude more
    for scheme in schemes[1:]:
        assert losses[scheme][0] == pytest.approx(losses["ring"][0],
                                                  rel=1e-3)
        assert losses[scheme][1] == pytest.approx(losses["ring"][1],
                                                  rel=1e-3)
    # unknown scheme fails loudly, not silently-ring
    with pytest.raises(ValueError, match="unknown attention scheme"):
        make_sharded_train_step(make_mesh(auto_axes(8)),
                                clip_shape=(2, 8, 32, 32, 3), width=8,
                                attn_scheme="flash")


def test_roi_align_matches_numpy_reference():
    """roi_align's bilinear samples agree with a direct numpy evaluation
    for identity, sub-region and out-of-range (clamped) boxes."""
    from scanner_tpu.models.segmentation import roi_align

    rng = np.random.RandomState(0)
    feat = rng.randn(1, 6, 5, 3).astype(np.float32)
    boxes = np.asarray([[[0.0, 0.0, 1.0, 1.0],
                         [0.2, 0.1, 0.7, 0.9],
                         [-0.2, 0.5, 1.3, 1.5]]], np.float32)
    S = 4
    got = np.asarray(roi_align(jnp.asarray(feat), jnp.asarray(boxes), S))

    fh, fw = feat.shape[1], feat.shape[2]
    for k, box in enumerate(boxes[0]):
        y1, x1, y2, x2 = box
        for i in range(S):
            for j in range(S):
                fy = (y1 + (y2 - y1) * (i + 0.5) / S) * fh - 0.5
                fx = (x1 + (x2 - x1) * (j + 0.5) / S) * fw - 0.5
                y0, x0 = int(np.floor(fy)), int(np.floor(fx))
                wy, wx = fy - y0, fx - x0
                c = lambda y, x: feat[0, min(max(y, 0), fh - 1),
                                      min(max(x, 0), fw - 1)]
                want = (c(y0, x0) * (1 - wy) * (1 - wx) +
                        c(y0, x0 + 1) * (1 - wy) * wx +
                        c(y0 + 1, x0) * wy * (1 - wx) +
                        c(y0 + 1, x0 + 1) * wy * wx)
                np.testing.assert_allclose(got[0, k, i, j], want,
                                           rtol=1e-5, atol=1e-5)


def test_instance_segment_e2e(sc):
    """InstanceSegment rows are packed (top_k, 6 + M*M) and unpack to
    boxes + boolean roi masks (reference detectron app shape contract)."""
    from scanner_tpu.models.segmentation import MASK_SIZE, TOP_K
    from scanner_tpu.models import unpack_instances

    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 4)])
    inst = sc.ops.InstanceSegment(frame=sampled)
    rows = _run(sc, inst, "seg_out")
    assert len(rows) == 4
    a = np.asarray(rows[0])
    assert a.shape == (TOP_K, 6 + MASK_SIZE * MASK_SIZE)
    r = unpack_instances(rows[0])
    assert r["masks"].shape[1:] == (MASK_SIZE, MASK_SIZE)
    assert r["masks"].dtype == bool


def test_seg_shipped_weights_segment(tmp_path):
    """E2E: InstanceSegment with the SHIPPED weights localizes synthetic
    shapes AND recovers their silhouettes — predicted masks must match
    the correct shape kind better than the wrong kind (a full-box mask
    cannot pass: IoU(box, inscribed ellipse) = pi/4).  Reference
    detectron app semantics (trained Mask R-CNN by default)."""
    from scanner_tpu.models import paste_masks, unpack_instances
    from scanner_tpu.models.checkpoint import shipped_weights
    from scanner_tpu.models.detect_train import WIDTH, box_iou
    from scanner_tpu.models.seg_train import (SIZE, full_gt_mask,
                                              synth_shape_video)

    assert shipped_weights("seg_w8.npz"), "shipped weights missing"
    vid = str(tmp_path / "shapes.mp4")
    truth = synth_shape_video(vid, num_frames=12, seed=31)
    sc2 = Client(db_path=str(tmp_path / "db"))
    try:
        movie = NamedVideoStream(sc2, "shapes", path=vid)
        inst = sc2.ops.InstanceSegment(frame=sc2.io.Input([movie]),
                                       width=WIDTH, score_thresh=0.3)
        out = NamedStream(sc2, "inst_out")
        sc2.run(sc2.io.Output(inst, [out]), PerfParams.estimate(),
                cache_mode=CacheMode.Overwrite, show_progress=False)
        matched = total = 0
        iou_correct, iou_wrong = [], []
        for i, row in enumerate(out.load()):
            r = unpack_instances(row)
            boxes, masks = r["boxes"], r["masks"]
            full = paste_masks(boxes, masks, SIZE, SIZE)
            gt_boxes, gt_kinds = truth[i]
            for gt_box, gt_kind in zip(gt_boxes, gt_kinds):
                total += 1
                cand = [j for j, b in enumerate(boxes)
                        if box_iou(gt_box, b) >= 0.3]
                if not cand:
                    continue
                matched += 1

                def iou_with(kind):
                    gm = full_gt_mask(gt_box, kind, SIZE, SIZE)
                    return max((full[j] & gm).sum() /
                               max((full[j] | gm).sum(), 1) for j in cand)

                iou_correct.append(iou_with(int(gt_kind)))
                iou_wrong.append(iou_with(1 - int(gt_kind)))
        assert total >= 12
        assert matched >= 0.7 * total, f"recall {matched}/{total}"
        mean_c = float(np.mean(iou_correct))
        mean_w = float(np.mean(iou_wrong))
        assert mean_c >= 0.55, f"mask IoU too low: {mean_c:.2f}"
        assert mean_c > mean_w + 0.05, (
            f"masks don't discriminate shape: correct {mean_c:.2f} "
            f"vs wrong-kind {mean_w:.2f}")
    finally:
        sc2.stop()


@pytest.mark.slow  # multi-minute XLA compile of the full multi-chip train step on CPU
def test_remat_train_step_matches():
    """remat=True (jax.checkpoint on backbone + temporal blocks) is the
    same math: first-step loss and the second-step loss after one update
    match the unremat'd model to f32 tolerance — only activation storage
    changes."""
    from scanner_tpu.parallel import auto_axes, make_mesh

    losses = {}
    for remat in (False, True):
        mesh = make_mesh(auto_axes(8))
        step, params, opt_state, (clip, target) = make_sharded_train_step(
            mesh, clip_shape=(2, 8, 32, 32, 3), width=8, remat=remat)
        params, opt_state, l1 = step(params, opt_state, clip, target)
        params, opt_state, l2 = step(params, opt_state, clip, target)
        losses[remat] = (float(l1), float(l2))
    # step-1 loss: same params, same forward -> identical
    assert losses[True][0] == pytest.approx(losses[False][0], rel=1e-5)
    # step-2 loss: grads recompute through bf16 blocks, so f32
    # accumulation order differs slightly (measured ~4e-4 rel); a broken
    # remat (wrong params/rng threading) diverges by orders more
    assert losses[True][1] == pytest.approx(losses[False][1], rel=1e-2)


def test_pp_params_convert_to_plain_serving():
    """Params trained on a pipeline mesh convert to the plain serving
    layout (and back) with BIT-IDENTICAL outputs in f32 — train with pp,
    serve with the engine kernels (pp_params_to_plain), or continue
    training shipped plain weights on a pp mesh (plain_params_to_pp)."""
    from scanner_tpu.models.pose import (VideoPoseNet, init_params,
                                         pp_params_to_plain,
                                         plain_params_to_pp)
    from scanner_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 2, "pp": 2})
    pp_model, pp_params = init_params(
        jax.random.PRNGKey(3), clip_shape=(1, 4, 32, 32, 3), width=8,
        pipeline_mesh=mesh, temporal_layers=2, dtype=jnp.float32)
    clip = (np.arange(np.prod((4, 4, 32, 32, 3))) % 251) \
        .astype(np.uint8).reshape(4, 4, 32, 32, 3)
    pp_out = np.asarray(jax.jit(pp_model.apply)(pp_params, clip))

    plain_model = VideoPoseNet(width=8, temporal_layers=2,
                               dtype=jnp.float32)
    plain_params = pp_params_to_plain(pp_params)
    plain_out = np.asarray(jax.jit(plain_model.apply)(plain_params, clip))
    np.testing.assert_array_equal(pp_out, plain_out)

    back = plain_params_to_pp(plain_params)
    back_out = np.asarray(jax.jit(pp_model.apply)(back, clip))
    np.testing.assert_array_equal(back_out, plain_out)
    # conversion is lossless both ways on the leaves too
    again = pp_params_to_plain(back)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), again,
        plain_params)


@pytest.mark.slow
def test_pp_trained_weights_serve_through_engine(tmp_path, sc):
    """The full pp workflow: one training step on a pipeline mesh ->
    convert the stacked stages to the plain layout -> export portable
    .npz -> PoseDetect(checkpoint_dir=...) serves it through the engine.
    Pins that pipeline-trained weights are first-class citizens of the
    kernel weight path."""
    from scanner_tpu.models import pp_params_to_plain
    from scanner_tpu.models.checkpoint import export_params_npz

    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 2, "pp": 2})
    step, params, opt_state, (clip, target) = make_sharded_train_step(
        mesh, clip_shape=(4, 4, 64, 64, 3), width=8)
    params, opt_state, loss = step(params, opt_state, clip, target)
    assert np.isfinite(float(loss))

    npz = str(tmp_path / "pp_trained_w8.npz")
    export_params_npz(pp_params_to_plain(params), npz)

    frame = sc.io.Input([NamedVideoStream(sc, "test1")])
    sampled = sc.streams.Range(frame, [(0, 4)])
    pose = sc.ops.PoseDetect(frame=sampled, width=8, checkpoint_dir=npz)
    rows = _run(sc, pose, "pp_pose_out")
    assert len(rows) == 4 and rows[0].shape == (17, 3)
    assert all(np.isfinite(np.asarray(r)).all() for r in rows)


def test_unpack_and_paste_edge_cases():
    """Host-side mask utilities on degenerate inputs: all-invalid rows
    unpack to empty arrays, zero boxes paste to an empty stack, and a
    sub-pixel box still paints at least one pixel without crashing."""
    from scanner_tpu.models import paste_masks, unpack_instances
    from scanner_tpu.models.segmentation import MASK_SIZE, TOP_K

    row = np.zeros((TOP_K, 6 + MASK_SIZE * MASK_SIZE), np.float32)
    r = unpack_instances(row)  # every valid flag is 0
    assert r["boxes"].shape == (0, 4)
    assert r["scores"].shape == (0,)
    assert r["masks"].shape == (0, MASK_SIZE, MASK_SIZE)

    empty = paste_masks(r["boxes"], r["masks"], 32, 32)
    assert empty.shape == (0, 32, 32)

    boxes = np.asarray([[0.5, 0.5, 0.5001, 0.5001],   # sub-pixel
                        [-0.2, -0.2, 1.4, 1.4]],      # out of range
                       np.float32)
    masks = np.ones((2, MASK_SIZE, MASK_SIZE), bool)
    full = paste_masks(boxes, masks, 32, 32)
    assert full.shape == (2, 32, 32)
    assert full[0].sum() >= 1          # degenerate box still paints
    assert full[1].all()               # clipped full-frame box covers all
