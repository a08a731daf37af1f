"""Single-shot object detection.

Capability parity: reference examples/apps/object_detection_tensorflow
(SSD mobilenet TF kernel) — rebuilt as an anchor-based SSD head over the
shared JAX backbone, with jit-compiled box decode and a vectorized NMS that
runs as a fixed-iteration lax loop (no data-dependent shapes on device).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import DeviceType, FrameType
from ..graph.ops import Kernel, register_op
from .nets import Backbone


def make_anchors(fh: int, fw: int, scales=(0.1, 0.25, 0.45),
                 ratios=(0.5, 1.0, 2.0)) -> np.ndarray:
    """(fh*fw*A, 4) anchors as [cy, cx, h, w] in unit coords."""
    ys = (np.arange(fh) + 0.5) / fh
    xs = (np.arange(fw) + 0.5) / fw
    anchors = []
    for y in ys:
        for x in xs:
            for s in scales:
                for r in ratios:
                    anchors.append([y, x, s * np.sqrt(r), s / np.sqrt(r)])
    return np.asarray(anchors, np.float32)


class SSDHead(nn.Module):
    num_classes: int = 2  # background + object
    anchors_per_cell: int = 9
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, feat):
        A = self.anchors_per_cell
        cls = nn.Conv(A * self.num_classes, (3, 3), dtype=jnp.float32,
                      padding="SAME", name="cls")(feat)
        box = nn.Conv(A * 4, (3, 3), dtype=jnp.float32, padding="SAME",
                      name="box")(feat)
        B, fh, fw, _ = cls.shape
        return (cls.reshape(B, fh * fw * A, self.num_classes),
                box.reshape(B, fh * fw * A, 4))


class SSDDetector(nn.Module):
    num_classes: int = 2
    width: int = 32
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        feat = Backbone(width=self.width, dtype=self.dtype)(images)
        return SSDHead(num_classes=self.num_classes,
                       dtype=self.dtype)(feat)


def decode_boxes(anchors: jnp.ndarray, deltas: jnp.ndarray) -> jnp.ndarray:
    """Standard SSD box decode -> [y1, x1, y2, x2] unit coords, clipped to
    the image (downstream crops must never sample fully out of frame)."""
    cy = anchors[:, 0] + deltas[..., 0] * anchors[:, 2]
    cx = anchors[:, 1] + deltas[..., 1] * anchors[:, 3]
    h = anchors[:, 2] * jnp.exp(jnp.clip(deltas[..., 2], -4, 4))
    w = anchors[:, 3] * jnp.exp(jnp.clip(deltas[..., 3], -4, 4))
    boxes = jnp.stack([cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2],
                      axis=-1)
    return jnp.clip(boxes, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=("top_k",))
def batched_nms(boxes, scores, top_k: int = 32, iou_thresh: float = 0.5):
    """Greedy NMS with a fixed iteration count: selects up to top_k boxes
    per image; returns (idx, keep_scores) with -1/0 padding.  Fixed shapes
    keep the whole postprocess on-device (no host sync per frame)."""
    def one_image(b, s):
        def area(bb):
            return jnp.maximum(bb[..., 2] - bb[..., 0], 0) * \
                jnp.maximum(bb[..., 3] - bb[..., 1], 0)

        def iou(b1, b2):
            y1 = jnp.maximum(b1[0], b2[..., 0])
            x1 = jnp.maximum(b1[1], b2[..., 1])
            y2 = jnp.minimum(b1[2], b2[..., 2])
            x2 = jnp.minimum(b1[3], b2[..., 3])
            inter = jnp.maximum(y2 - y1, 0) * jnp.maximum(x2 - x1, 0)
            return inter / jnp.maximum(area(b1) + area(b2) - inter, 1e-9)

        def step(carry, _):
            sc, sel_idx, sel_sc, i = carry
            j = jnp.argmax(sc)
            best = sc[j]
            sel_idx = sel_idx.at[i].set(jnp.where(best > 0, j, -1))
            sel_sc = sel_sc.at[i].set(jnp.maximum(best, 0))
            overl = iou(b[j], b)
            sc = jnp.where(overl > iou_thresh, -1.0, sc)
            sc = sc.at[j].set(-1.0)
            return (sc, sel_idx, sel_sc, i + 1), None

        init = (s, jnp.full((top_k,), -1, jnp.int32),
                jnp.zeros((top_k,), jnp.float32), 0)
        final, _ = jax.lax.scan(step, init, None, length=top_k)
        _sc, idx, ssc, _i = final
        return idx, ssc

    return jax.vmap(one_image)(boxes, scores)


def pack_detections(cls, deltas, anchors, score_thresh: float,
                    top_k: int = 32):
    """The shared post-head decode contract: class logits + box deltas ->
    packed (B, top_k, 6) rows [y1, x1, y2, x2, score, valid] plus the
    selected boxes (B, top_k, 4).  Every detection-family kernel
    (ObjectDetect, FaceDetect, InstanceSegment) packs through here so the
    row layout and NMS policy cannot diverge between them."""
    probs = jax.nn.softmax(cls, axis=-1)[..., 1:]  # drop background
    scores = probs.max(axis=-1)
    boxes = decode_boxes(anchors, deltas)
    idx, ssc = batched_nms(boxes, scores, top_k=top_k)
    sel = jnp.take_along_axis(boxes, jnp.maximum(idx, 0)[..., None],
                              axis=1)
    valid = ((idx >= 0) & (ssc > score_thresh)).astype(jnp.float32)
    # packed fixed shape end to end so results stay on device
    # (variable-length filtering happens at the consumer)
    packed = jnp.concatenate([sel, ssc[..., None], valid[..., None]],
                             axis=-1)
    return packed, sel


def unpack_detections(row) -> Dict[str, np.ndarray]:
    """Unpack one stored ObjectDetect/FaceDetect row — a (top_k, 6) array
    [y1, x1, y2, x2, score, valid] — into the classic
    {"boxes": (n, 4), "scores": (n,)} dict, dropping padding rows.
    Rows from tables written before the packed format (per-row dicts)
    pass through unchanged, so old committed tables stay readable."""
    if isinstance(row, dict):
        return {"boxes": np.asarray(row["boxes"], np.float32),
                "scores": np.asarray(row["scores"], np.float32)}
    a = np.asarray(row, np.float32)
    keep = a[:, 5] > 0.5
    return {"boxes": a[keep, :4], "scores": a[keep, 4]}


def anchored_cost_flops(kern, batch):
    """Shared MFU probe for the anchors-based detection family
    (ObjectDetect / FaceDetect / InstanceSegment): resolve the batch's
    stride-16 anchor grid like execute() does, then ask XLA's cost
    analysis for the jitted inference's FLOPs (infer.lowered_flops)."""
    from .infer import lowered_flops
    images = jnp.asarray(batch)
    fh = -(-images.shape[1] // 16)
    fw = -(-images.shape[2] // 16)
    if (fh, fw) not in kern._anchors:
        kern._anchors[(fh, fw)] = jnp.asarray(make_anchors(fh, fw))
    return lowered_flops(kern._infer, kern.params, images,
                         kern._anchors[(fh, fw)])


@register_op(device=DeviceType.TPU, batch=8)
class ObjectDetect(Kernel):
    """Per-frame object detections as packed (top_k, 6) rows
    [y1, x1, y2, x2, score, valid] in unit coordinates — decode with
    `unpack_detections` (reference TF SSD app equivalent).

    With no `checkpoint_dir`, width-8 instances restore the shipped
    synthetic-task weights (models/weights/detect_ssd_w8.npz, provenance
    models/detect_train.py) — like the reference app downloading SSD
    mobilenet by default; pass `pretrained=False` for random init."""

    _shipped = "detect_ssd_w8.npz"
    _shipped_width = 8

    def __init__(self, config, width: int = 32, num_classes: int = 2,
                 score_thresh: float = 0.05, seed: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 pretrained: bool = True):
        super().__init__(config)
        self.model = SSDDetector(num_classes=num_classes, width=width)
        from .checkpoint import init_or_restore, shipped_weights
        if checkpoint_dir is None and pretrained \
                and width == self._shipped_width and num_classes == 2:
            checkpoint_dir = shipped_weights(self._shipped)
        self.params = init_or_restore(
            self.model, jax.random.PRNGKey(seed),
            jnp.zeros((1, 128, 128, 3), jnp.uint8), checkpoint_dir)
        self.score_thresh = float(score_thresh)
        self._anchors = {}  # (fh, fw) -> anchor tensor, per resolution

        thresh = self.score_thresh

        @jax.jit
        @jax.named_scope(type(self).__name__)
        def infer(params, images, anchors):
            cls, deltas = self.model.apply(params, images)
            packed, _sel = pack_detections(cls, deltas, anchors, thresh)
            return packed

        self._infer = infer

    def infer_cost_flops(self, batch):
        """XLA-reported FLOPs for one inference call on `batch` (for
        the bench's MFU accounting); None when unavailable."""
        return anchored_cost_flops(self, batch)

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        """Returns a (B, top_k, 6) float32 batch — per row a (top_k, 6)
        array [y1, x1, y2, x2, score, valid] in unit coordinates,
        `valid`-padded (see unpack_detections).  Returned WITHOUT a host
        sync: device arrays chain through the column store and the sink
        fetches once per task (a per-packet fetch would serialize the
        pipeline on d2h latency, PERF.md §5)."""
        images = jnp.asarray(frame)
        # SAME-padded stride-16 backbone -> ceil-divided feature map
        fh = -(-images.shape[1] // 16)
        fw = -(-images.shape[2] // 16)
        if (fh, fw) not in self._anchors:
            self._anchors[(fh, fw)] = jnp.asarray(make_anchors(fh, fw))
        return self._infer(self.params, images, self._anchors[(fh, fw)])
