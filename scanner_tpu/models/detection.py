"""Single-shot object detection.

Capability parity: reference examples/apps/object_detection_tensorflow
(SSD mobilenet TF kernel) — rebuilt as an anchor-based SSD head over the
shared JAX backbone, with jit-compiled box decode and a vectorized NMS that
runs as a fixed-iteration lax loop (no data-dependent shapes on device).
The boxes are decoded into four lane-dense (B, N) coordinate planes, and
the loop reads them and the boxes' areas as loop-invariant operands over
the whole batch at once; only the kept boxes are stacked, after it.
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


def decode_boxes(anchors: jnp.ndarray, deltas: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, ...]:
    """Standard SSD box decode -> the four coordinate planes (y1, x1, y2,
    x2), each (B, N) in unit coords and clipped to the image (downstream
    crops must never sample fully out of frame).  Planes and not a
    (B, N, 4) stack: on the TPU a 4-wide minor dimension is padded to the
    128 lanes, and the NMS loop reads each coordinate across all N."""
    cy = anchors[:, 0] + deltas[..., 0] * anchors[:, 2]
    cx = anchors[:, 1] + deltas[..., 1] * anchors[:, 3]
    h = anchors[:, 2] * jnp.exp(jnp.clip(deltas[..., 2], -4, 4))
    w = anchors[:, 3] * jnp.exp(jnp.clip(deltas[..., 3], -4, 4))
    return tuple(jnp.clip(c, 0.0, 1.0)
                 for c in (cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2))


@functools.partial(jax.jit, static_argnames=("top_k",))
def batched_nms(planes, scores, top_k: int = 32, iou_thresh: float = 0.5):
    """Greedy NMS with a fixed iteration count over a batch: `planes` are
    the (y1, x1, y2, x2) coordinate planes and `scores` the scores, each
    (B, N).  Selects up to top_k boxes a row; returns (idx, keep_scores),
    each (B, top_k), with -1/0 padding.  Fixed shapes keep the whole
    postprocess on-device (no host sync per frame).

    The planes and every box's area (computed once, before the loop) are
    loop-invariant and lane-dense; the loop carries only the (B, N)
    scores.  A step takes each row's argmax (first index on ties), reads
    the chosen box's coordinates and area from the planes, and suppresses
    by IoU against all N boxes at once."""
    y1, x1, y2, x2 = planes
    areas = jnp.maximum(y2 - y1, 0) * jnp.maximum(x2 - x1, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    def pick(a, j):
        return jnp.take_along_axis(a, j, axis=1)

    def step(sc, _):
        j = jnp.argmax(sc, axis=1)[:, None].astype(jnp.int32)
        best = pick(sc, j)
        iy1 = jnp.maximum(pick(y1, j), y1)
        ix1 = jnp.maximum(pick(x1, j), x1)
        iy2 = jnp.minimum(pick(y2, j), y2)
        ix2 = jnp.minimum(pick(x2, j), x2)
        inter = jnp.maximum(iy2 - iy1, 0) * jnp.maximum(ix2 - ix1, 0)
        overl = inter / jnp.maximum(pick(areas, j) + areas - inter, 1e-9)
        sc = jnp.where(overl > iou_thresh, -1.0, sc)
        sc = jnp.where(lanes == j, -1.0, sc)
        return sc, (jnp.where(best > 0, j, -1)[:, 0],
                    jnp.maximum(best, 0)[:, 0])

    _sc, (idx, ssc) = jax.lax.scan(step, scores, None, length=top_k)
    return idx.T, ssc.T


def pack_detections(cls, deltas, anchors, score_thresh: float,
                    top_k: int = 32):
    """The shared post-head decode contract: class logits + box deltas ->
    packed (B, top_k, 6) rows [y1, x1, y2, x2, score, valid] plus the
    selected boxes (B, top_k, 4).  Every detection-family kernel
    (ObjectDetect, FaceDetect, InstanceSegment) packs through here so the
    row layout and NMS policy cannot diverge between them.  The boxes
    stay four (B, N) planes through the NMS; only the top_k kept ones
    are gathered and stacked, after the loop.  Traced under the scope
    `nms`: the device trace tells its time (the scan's top_k steps) apart
    from the network's."""
    with jax.named_scope("nms"):
        probs = jax.nn.softmax(cls, axis=-1)[..., 1:]  # drop background
        scores = probs.max(axis=-1)
        planes = decode_boxes(anchors, deltas)
        idx, ssc = batched_nms(planes, scores, top_k=top_k)
        kept = jnp.maximum(idx, 0)
        sel = jnp.stack([jnp.take_along_axis(c, kept, axis=1)
                         for c in planes], axis=-1)
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


def anchors_for(cache, shape, device=None) -> jnp.ndarray:
    """The anchors of a (B, H, W, 3) batch's SAME-padded stride-16
    feature map (ceil-divided), made once a resolution into `cache`;
    committed to `device` where one is given."""
    fh, fw = -(-shape[1] // 16), -(-shape[2] // 16)
    if (fh, fw) not in cache:
        a = make_anchors(fh, fw)
        cache[(fh, fw)] = jnp.asarray(a) if device is None \
            else jax.device_put(a, device)
    return cache[(fh, fw)]


def anchored_cost_flops(kern, batch):
    """Shared MFU probe for the anchors-based detection family
    (ObjectDetect / FaceDetect / InstanceSegment): resolve the batch's
    stride-16 anchor grid like execute() does, then ask XLA's cost
    analysis for the jitted inference's FLOPs (infer.lowered_flops)."""
    from .infer import lowered_flops
    images = jnp.asarray(batch)
    return lowered_flops(kern._infer, kern.params, images,
                         anchors_for(kern._anchors, images.shape,
                                     getattr(kern, "_device", None)))


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
        params = init_or_restore(
            self.model, jax.random.PRNGKey(seed),
            jnp.zeros((1, 128, 128, 3), jnp.uint8), checkpoint_dir)
        # an instance that owns one chip commits its weights and anchors
        # there (as DataParallelApply does for the other model ops):
        # init_or_restore leaves them uncommitted on the default chip
        devices = list(config.devices or [])
        self._device = devices[0] if len(devices) == 1 else None
        self.params = params if self._device is None \
            else jax.device_put(params, self._device)
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
        return self._infer(self.params, images,
                           anchors_for(self._anchors, images.shape,
                                       self._device))
