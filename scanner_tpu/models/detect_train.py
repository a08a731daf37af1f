"""Train the SSD detector + embedding net on synthetic tasks and ship
checkpoints.

The reference apps load externally-trained models (object detection:
examples/apps/object_detection_tensorflow/main.py:16-23 downloads SSD
mobilenet; face detection: examples/apps/face_detection/main.py).  This
framework trains its own: fully reproducible weight provenance, the same
story as the flagship pose model (models/pose_train.py).  Three tasks:

* **ObjectDetect** — localize 1-3 bright rectangles on a noisy dark
  background (anchor-matched SSD loss).
* **FaceDetect**  — same machinery, face-like targets (bright ellipse
  with two dark "eyes"), separate weights.
* **FaceEmbedding** — identity metric learning: K procedural-texture
  identities under crop/brightness/noise augmentation, trained with a
  classification head; the shipped embedding is the L2-normalized
  projection (recall@1 asserted in tests/test_models.py).

`python -m scanner_tpu.models.detect_train <out_dir>` trains all three
and exports portable .npz weight files (models/weights/ ships them).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

# shared synthetic-task geometry (training, tests and examples agree)
SIZE = 64
WIDTH = 8
EMBED_DIM = 128
EMBED_IDENTITIES = 16


# ---------------------------------------------------------------------------
# Synthetic scenes
# ---------------------------------------------------------------------------

def render_rect_scene(rng: np.random.RandomState, size: int = SIZE,
                      max_objects: int = 3
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Noisy dark frame with 1..max_objects bright axis-aligned
    rectangles.  Returns (frame uint8 (S,S,3), boxes (N,4) unit
    [y1,x1,y2,x2])."""
    frame = rng.randint(0, 40, (size, size, 3)).astype(np.uint8)
    n = rng.randint(1, max_objects + 1)
    boxes = []
    for _ in range(n):
        h = rng.randint(10, 28)
        w = rng.randint(10, 28)
        y = rng.randint(0, size - h)
        x = rng.randint(0, size - w)
        color = rng.randint(170, 255, 3)
        frame[y:y + h, x:x + w] = color
        boxes.append([y / size, x / size, (y + h) / size, (x + w) / size])
    return frame, np.asarray(boxes, np.float32)


def render_face_scene(rng: np.random.RandomState, size: int = SIZE,
                      max_objects: int = 2
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Face-like targets: bright ellipse with two dark eye dots."""
    frame = rng.randint(0, 40, (size, size, 3)).astype(np.uint8)
    n = rng.randint(1, max_objects + 1)
    ys, xs = np.mgrid[0:size, 0:size]
    boxes = []
    for _ in range(n):
        h = rng.randint(14, 30)
        w = int(h * rng.uniform(0.7, 0.9))
        cy = rng.randint(h // 2, size - h // 2)
        cx = rng.randint(w // 2, size - w // 2)
        mask = (((ys - cy) / (h / 2)) ** 2 + ((xs - cx) / (w / 2)) ** 2) <= 1
        tone = np.array([rng.randint(190, 250), rng.randint(150, 210),
                         rng.randint(120, 180)])
        frame[mask] = tone
        for ex in (-w // 5, w // 5):  # eyes
            ey, exx = cy - h // 6, cx + ex
            frame[max(ey - 1, 0):ey + 2, max(exx - 1, 0):exx + 2] = 15
        boxes.append([(cy - h / 2) / size, (cx - w / 2) / size,
                      (cy + h / 2) / size, (cx + w / 2) / size])
    return frame, np.asarray(boxes, np.float32)


def render_identity(rng_id: int, view_rng: np.random.RandomState,
                    size: int = SIZE) -> np.ndarray:
    """One augmented view of a procedural-texture identity: the identity
    seed fixes an 8x8 color tile; views vary by shift, brightness and
    noise."""
    base_rng = np.random.RandomState(1000 + rng_id)
    tile = base_rng.randint(0, 255, (8, 8, 3)).astype(np.float32)
    img = np.kron(tile, np.ones((size // 8, size // 8, 1), np.float32))
    # augment: circular shift, brightness scale, additive noise
    sy, sx = view_rng.randint(0, size, 2)
    img = np.roll(np.roll(img, sy, axis=0), sx, axis=1)
    img = img * view_rng.uniform(0.6, 1.4)
    img = img + view_rng.normal(0, 18, img.shape)
    return np.clip(img, 0, 255).astype(np.uint8)


# ---------------------------------------------------------------------------
# SSD anchor matching (host-side numpy; targets feed the jitted loss)
# ---------------------------------------------------------------------------

def _anchor_corners(anchors: np.ndarray) -> np.ndarray:
    cy, cx, h, w = anchors.T
    return np.stack([cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2], 1)


def _iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(N,4) x (M,4) corner boxes -> (N,M) IoU."""
    y1 = np.maximum(a[:, None, 0], b[None, :, 0])
    x1 = np.maximum(a[:, None, 1], b[None, :, 1])
    y2 = np.minimum(a[:, None, 2], b[None, :, 2])
    x2 = np.minimum(a[:, None, 3], b[None, :, 3])
    inter = np.clip(y2 - y1, 0, None) * np.clip(x2 - x1, 0, None)
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    return inter / np.maximum(area_a[:, None] + area_b[None, :] - inter,
                              1e-9)


def match_anchors(anchors: np.ndarray, gt: np.ndarray,
                  pos_iou: float = 0.5, neg_iou: float = 0.4
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """SSD target assignment.  anchors (N,4) [cy,cx,h,w]; gt (M,4)
    corners.  Returns (cls (N,) int32: 1 pos / 0 neg / -1 ignore,
    deltas (N,4) f32, zero outside positives)."""
    N = anchors.shape[0]
    cls = np.zeros((N,), np.int32)
    deltas = np.zeros((N, 4), np.float32)
    if gt.shape[0] == 0:
        return cls, deltas
    iou = _iou_matrix(_anchor_corners(anchors), gt)
    best_gt = iou.argmax(1)
    best_iou = iou.max(1)
    cls[(best_iou >= neg_iou) & (best_iou < pos_iou)] = -1
    pos = best_iou >= pos_iou
    # every gt claims its best anchor even below the threshold
    forced = iou.argmax(0)
    pos[forced] = True
    best_gt[forced] = np.arange(gt.shape[0])
    cls[pos] = 1
    g = gt[best_gt[pos]]
    gcy = (g[:, 0] + g[:, 2]) / 2
    gcx = (g[:, 1] + g[:, 3]) / 2
    gh = g[:, 2] - g[:, 0]
    gw = g[:, 3] - g[:, 1]
    a = anchors[pos]
    deltas[pos] = np.stack([
        (gcy - a[:, 0]) / a[:, 2], (gcx - a[:, 1]) / a[:, 3],
        np.log(np.maximum(gh, 1e-4) / a[:, 2]),
        np.log(np.maximum(gw, 1e-4) / a[:, 3])], 1)
    return cls, deltas


def synth_scene_video(path: str, renderer: Callable = None,
                      num_frames: int = 24, size: int = SIZE,
                      fps: float = 24.0, seed: int = 11):
    """Encode a clip of independent synthetic scenes to mp4; returns the
    per-frame ground-truth box lists.  The e2e counterpart of
    detection_batch: the exact task the shipped detector weights were
    trained on, but through the video codec path (crf 14 keeps the
    rectangles crisp enough for IoU checks)."""
    from ..video.ingest import encode_frames_mp4

    renderer = renderer or render_rect_scene
    rng = np.random.RandomState(seed)
    frames, gts = [], []
    for _ in range(num_frames):
        f, gt = renderer(rng, size)
        frames.append(f)
        gts.append(gt)
    encode_frames_mp4(path, frames, size, size, fps=fps, keyint=8, crf=14)
    return gts


def box_iou(a, b) -> float:
    """IoU of two corner boxes [y1,x1,y2,x2] (unit coords)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(_iou_matrix(a[None], b[None])[0, 0])


def detection_batch(rng: np.random.RandomState, batch: int,
                    anchors: np.ndarray, renderer: Callable,
                    size: int = SIZE):
    """(frames (B,S,S,3) u8, cls (B,N) i32, deltas (B,N,4) f32)."""
    frames = np.zeros((batch, size, size, 3), np.uint8)
    N = anchors.shape[0]
    cls = np.zeros((batch, N), np.int32)
    deltas = np.zeros((batch, N, 4), np.float32)
    for b in range(batch):
        frames[b], gt = renderer(rng, size)
        cls[b], deltas[b] = match_anchors(anchors, gt)
    return frames, cls, deltas


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def train_detector(checkpoint_dir: str, renderer: Callable = None,
                   steps: int = 300, batch: int = 4, size: int = SIZE,
                   width: int = WIDTH, seed: int = 0,
                   export_npz: Optional[str] = None,
                   log_every: int = 50) -> float:
    """Train SSDDetector on the synthetic scene task; orbax checkpoint +
    optional portable .npz export.  Returns final loss."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..util.log import get_logger
    from .checkpoint import TrainCheckpointer, export_params_npz
    from .detection import SSDDetector, make_anchors

    log = get_logger("train")
    renderer = renderer or render_rect_scene
    fh = fw = -(-size // 16)
    anchors_np = make_anchors(fh, fw)
    anchors = jnp.asarray(anchors_np)

    model = SSDDetector(num_classes=2, width=width)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, size, size, 3), jnp.uint8))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, frames, cls_t, box_t):
        logits, deltas = model.apply(p, frames)           # (B,N,2),(B,N,4)
        valid = (cls_t >= 0)
        pos = (cls_t == 1)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(cls_t, 0))
        # balance: positives are rare among N anchors — weight them up
        w = jnp.where(pos, 10.0, 1.0) * valid
        cls_loss = (ce * w).sum() / jnp.maximum(w.sum(), 1.0)
        hub = optax.huber_loss(deltas, box_t).sum(-1)
        box_loss = (hub * pos).sum() / jnp.maximum(pos.sum(), 1.0)
        return cls_loss + box_loss

    @jax.jit
    def step_fn(p, s, frames, cls_t, box_t):
        loss, grads = jax.value_and_grad(loss_fn)(p, frames, cls_t, box_t)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    rng = np.random.RandomState(seed)
    loss = float("nan")
    for i in range(steps):
        frames, cls_t, box_t = detection_batch(rng, batch, anchors_np,
                                               renderer, size)
        params, opt_state, loss = step_fn(params, opt_state, frames,
                                          cls_t, box_t)
        if log_every and (i + 1) % log_every == 0:
            log.info("detect_train step %d/%d loss=%.5f", i + 1, steps,
                     float(loss))
    ckpt = TrainCheckpointer(checkpoint_dir)
    try:
        ckpt.save(steps, params, opt_state)
    finally:
        ckpt.close()
    if export_npz:
        export_params_npz(params, export_npz)
    return float(loss)


def train_embedding(checkpoint_dir: str, steps: int = 300, batch: int = 16,
                    size: int = SIZE, width: int = WIDTH,
                    dim: int = EMBED_DIM,
                    identities: int = EMBED_IDENTITIES, seed: int = 0,
                    export_npz: Optional[str] = None,
                    log_every: int = 50) -> float:
    """Train EmbeddingNet: identity classification over procedural
    textures; the shipped weights are the backbone+projection (the
    classifier head is training-only scaffolding)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..util.log import get_logger
    from .checkpoint import TrainCheckpointer, export_params_npz
    from .face import EmbeddingNet

    log = get_logger("train")
    model = EmbeddingNet(dim=dim, width=width)
    rng_key = jax.random.PRNGKey(seed)
    params = model.init(rng_key, jnp.zeros((1, size, size, 3), jnp.uint8))
    # training-only linear classifier on the normalized embedding
    k1, _ = jax.random.split(rng_key)
    w_cls = jax.random.normal(k1, (dim, identities)) * 0.05
    opt = optax.adam(1e-3)
    opt_state = opt.init((params, w_cls))

    def loss_fn(state, frames, labels):
        p, w = state
        emb = model.apply(p, frames)                  # (B, dim) normalized
        logits = emb @ w * 10.0                       # temperature
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    @jax.jit
    def step_fn(state, s, frames, labels):
        loss, grads = jax.value_and_grad(loss_fn)(state, frames, labels)
        updates, s = opt.update(grads, s, state)
        return optax.apply_updates(state, updates), s, loss

    rng = np.random.RandomState(seed)
    state = (params, w_cls)
    loss = float("nan")
    for i in range(steps):
        labels = rng.randint(0, identities, batch)
        frames = np.stack([render_identity(l, rng, size) for l in labels])
        state, opt_state, loss = step_fn(state, opt_state, frames,
                                         labels.astype(np.int32))
        if log_every and (i + 1) % log_every == 0:
            log.info("embed_train step %d/%d loss=%.5f", i + 1, steps,
                     float(loss))
    params = state[0]
    ckpt = TrainCheckpointer(checkpoint_dir)
    try:
        ckpt.save(steps, params, opt_state)
    finally:
        ckpt.close()
    if export_npz:
        export_params_npz(params, export_npz)
    return float(loss)


def main(argv: Optional[list] = None) -> None:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("out_dir")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--which", default="all",
                    choices=["all", "detect", "face", "embed"])
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend, before the first "
                    "backend touch")
    args = ap.parse_args(argv)
    if args.cpu:
        from ..util.jaxenv import force_cpu_platform
        force_cpu_platform()
    os.makedirs(args.out_dir, exist_ok=True)
    if args.which in ("all", "detect"):
        loss = train_detector(
            os.path.join(args.out_dir, "detect_ckpt"),
            render_rect_scene, steps=args.steps, seed=0,
            export_npz=os.path.join(args.out_dir,
                                    f"detect_ssd_w{WIDTH}.npz"))
        print(f"detect: final loss {loss:.5f}")
    if args.which in ("all", "face"):
        loss = train_detector(
            os.path.join(args.out_dir, "face_ckpt"),
            render_face_scene, steps=args.steps, seed=1,
            export_npz=os.path.join(args.out_dir,
                                    f"face_ssd_w{WIDTH}.npz"))
        print(f"face: final loss {loss:.5f}")
    if args.which in ("all", "embed"):
        loss = train_embedding(
            os.path.join(args.out_dir, "embed_ckpt"), steps=args.steps,
            seed=2,
            export_npz=os.path.join(args.out_dir,
                                    f"embed_w{WIDTH}.npz"))
        print(f"embed: final loss {loss:.5f}")


if __name__ == "__main__":
    main()
