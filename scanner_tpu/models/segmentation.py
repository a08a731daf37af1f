"""Instance segmentation: detection + per-instance masks.

Capability parity: reference examples/apps/detectron (Mask R-CNN via the
Caffe2 detectron kernels, detectron_kernels.py) — rebuilt TPU-first:

* **Fixed shapes end to end.**  Mask R-CNN's dynamic proposal lists don't
  map to XLA; here detection keeps the packed (top_k, 6) contract of
  ObjectDetect and masks are a fixed (top_k, M, M) tensor — padding
  instances carry valid=0 instead of changing shapes.
* **ROI align as a vectorized bilinear gather** (`roi_align`): a K-roi
  S×S sampling grid evaluated with 4 clamped gathers + lerp, vmapped
  over rois and batch — no dynamic slicing, no host sync.
* **Two-level features.**  The SSD detection head reads the shared
  stride-16 backbone; masks read a dedicated stride-2 trunk (FPN-lite)
  so an object 16 px wide still spans 8 mask-feature cells.

The whole forward (backbone → head → decode → NMS → ROI align → mask
head) is ONE jitted function; results stay device-resident and are
fetched once per task at the sink, like the other model ops.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import DeviceType, FrameType
from ..graph.ops import Kernel, register_op
from .detection import SSDHead, anchors_for, pack_detections
from .nets import Backbone

ROI_SIZE = 8          # roi-align grid (mask head upsamples 2x)
MASK_SIZE = 2 * ROI_SIZE
TOP_K = 8             # fixed instance budget per frame


def roi_align(feat: jnp.ndarray, boxes: jnp.ndarray,
              out_size: int) -> jnp.ndarray:
    """Bilinear ROI align with fixed shapes.

    feat (B, fh, fw, C) float; boxes (B, K, 4) unit-coordinate corners
    [y1, x1, y2, x2] -> (B, K, S, S, C).  Each output cell samples the
    feature map at its roi-grid center with bilinear interpolation
    (4 clamped gathers); degenerate boxes just sample a point.
    """
    fh, fw = feat.shape[1], feat.shape[2]
    S = out_size
    cell = (jnp.arange(S, dtype=jnp.float32) + 0.5) / S

    def one_roi(fmap, box):
        ys = box[0] + (box[2] - box[0]) * cell          # unit coords
        xs = box[1] + (box[3] - box[1]) * cell
        fy = ys * fh - 0.5                              # pixel-center grid
        fx = xs * fw - 0.5
        yf = jnp.floor(fy)
        xf = jnp.floor(fx)
        wy = fy - yf
        wx = fx - xf
        # clamp each corner from the UNCLIPPED floor so out-of-range
        # samples degenerate to the edge value (both corners hit the same
        # edge row/col) instead of extrapolating inward
        y0 = jnp.clip(yf.astype(jnp.int32), 0, fh - 1)
        y1 = jnp.clip(yf.astype(jnp.int32) + 1, 0, fh - 1)
        x0 = jnp.clip(xf.astype(jnp.int32), 0, fw - 1)
        x1 = jnp.clip(xf.astype(jnp.int32) + 1, 0, fw - 1)
        f00 = fmap[y0[:, None], x0[None, :]]            # (S, S, C)
        f01 = fmap[y0[:, None], x1[None, :]]
        f10 = fmap[y1[:, None], x0[None, :]]
        f11 = fmap[y1[:, None], x1[None, :]]
        wy = wy[:, None, None]
        wx = wx[None, :, None]
        return (f00 * (1 - wy) * (1 - wx) + f01 * (1 - wy) * wx +
                f10 * wy * (1 - wx) + f11 * wy * wx)

    per_image = jax.vmap(one_roi, in_axes=(None, 0))     # over K rois
    return jax.vmap(per_image)(feat, boxes)              # over batch


class MaskTrunk(nn.Module):
    """Stride-2 mask feature extractor (FPN-lite level for ROI align) —
    high-resolution on purpose: a 16 px object still spans 8 mask-feature
    cells, so silhouette boundaries survive to the roi grid."""

    width: int = 32
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        x = images.astype(self.dtype) / 255.0
        x = nn.Conv(self.width, (5, 5), strides=(2, 2), dtype=self.dtype,
                    padding="SAME")(x)
        x = nn.GroupNorm(num_groups=min(8, self.width), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Conv(self.width, (3, 3), dtype=self.dtype,
                    padding="SAME")(x)
        x = nn.GroupNorm(num_groups=min(8, self.width), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Conv(self.width, (3, 3), dtype=self.dtype,
                    padding="SAME")(x)
        return nn.relu(x)


class MaskHead(nn.Module):
    """(…, S, S, C) roi features -> (…, 2S, 2S) mask logits."""

    width: int = 32
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, roi_feat):
        h = roi_feat.astype(self.dtype)
        h = nn.Conv(2 * self.width, (3, 3), dtype=self.dtype,
                    padding="SAME")(h)
        h = nn.relu(h)
        h = nn.Conv(2 * self.width, (3, 3), dtype=self.dtype,
                    padding="SAME")(h)
        h = nn.relu(h)
        h = nn.ConvTranspose(self.width, (2, 2), strides=(2, 2),
                             dtype=self.dtype)(h)
        h = nn.relu(h)
        return nn.Conv(1, (1, 1), dtype=jnp.float32)(h)[..., 0]


class InstanceSegmentor(nn.Module):
    """SSD detection + per-roi mask prediction over shared inputs."""

    num_classes: int = 2
    width: int = 32
    roi_size: int = ROI_SIZE
    dtype: Any = jnp.bfloat16

    def setup(self):
        self.backbone = Backbone(width=self.width, dtype=self.dtype)
        self.det_head = SSDHead(num_classes=self.num_classes,
                                dtype=self.dtype)
        self.mask_trunk = MaskTrunk(width=self.width, dtype=self.dtype)
        self.mask_head = MaskHead(width=self.width, dtype=self.dtype)

    def detect(self, images):
        return self.det_head(self.backbone(images))

    def roi_masks(self, images, rois):
        """rois (B, K, 4) unit corners -> (B, K, 2*roi_size, 2*roi_size)
        mask logits."""
        mf = self.mask_trunk(images).astype(jnp.float32)
        return self.mask_head(roi_align(mf, rois, self.roi_size))

    def __call__(self, images, rois=None):
        if rois is None:  # init-time shape probe: any fixed-K roi set
            rois = jnp.zeros((images.shape[0], TOP_K, 4), jnp.float32)
        cls, deltas = self.detect(images)
        return cls, deltas, self.roi_masks(images, rois)


def unpack_instances(row, mask_thresh: float = 0.5,
                     mask_size: int = MASK_SIZE) -> Dict[str, np.ndarray]:
    """Unpack one stored InstanceSegment row — a (top_k, 6 + M*M) array
    [y1, x1, y2, x2, score, valid, mask probs…] — into
    {"boxes": (n, 4), "scores": (n,), "masks": (n, M, M) bool},
    dropping padding instances."""
    a = np.asarray(row, np.float32)
    keep = a[:, 5] > 0.5
    a = a[keep]
    masks = a[:, 6:].reshape(-1, mask_size, mask_size) > mask_thresh
    return {"boxes": a[:, :4], "scores": a[:, 4], "masks": masks}


def paste_masks(boxes: np.ndarray, masks: np.ndarray, height: int,
                width: int) -> np.ndarray:
    """Paste per-roi boolean masks (n, M, M) into full-frame boolean masks
    (n, H, W) by nearest-neighbor resampling inside each box (the
    detectron visualization step, host-side numpy)."""
    n = len(boxes)
    M = masks.shape[1] if n else 0
    out = np.zeros((n, height, width), bool)
    for i in range(n):
        y1, x1, y2, x2 = boxes[i]
        py1 = int(np.clip(round(y1 * height), 0, height - 1))
        px1 = int(np.clip(round(x1 * width), 0, width - 1))
        py2 = int(np.clip(round(y2 * height), py1 + 1, height))
        px2 = int(np.clip(round(x2 * width), px1 + 1, width))
        h, w = py2 - py1, px2 - px1
        yy = np.clip(((np.arange(h) + 0.5) * M / h - 0.5).round(),
                     0, M - 1).astype(int)
        xx = np.clip(((np.arange(w) + 0.5) * M / w - 0.5).round(),
                     0, M - 1).astype(int)
        out[i, py1:py2, px1:px2] = masks[i][yy[:, None], xx[None, :]]
    return out


@register_op(device=DeviceType.TPU, batch=4)
class InstanceSegment(Kernel):
    """Per-frame instance segmentation as packed (top_k, 6 + M*M) rows —
    [y1, x1, y2, x2, score, valid] + an M×M mask probability grid per
    instance, unit coordinates — decode with `unpack_instances` /
    `paste_masks` (reference detectron app equivalent).

    With no `checkpoint_dir`, width-8 instances restore the shipped
    synthetic-shape-task weights (models/weights/seg_w8.npz, provenance
    models/seg_train.py); pass `pretrained=False` for random init."""

    _shipped = "seg_w8.npz"
    _shipped_width = 8

    def __init__(self, config, width: int = 32, num_classes: int = 2,
                 score_thresh: float = 0.05, seed: int = 3,
                 checkpoint_dir: Optional[str] = None,
                 pretrained: bool = True):
        super().__init__(config)
        self.model = InstanceSegmentor(num_classes=num_classes, width=width)
        from .checkpoint import init_or_restore, shipped_weights
        if checkpoint_dir is None and pretrained \
                and width == self._shipped_width and num_classes == 2:
            checkpoint_dir = shipped_weights(self._shipped)
        self.params = init_or_restore(
            self.model, jax.random.PRNGKey(seed),
            jnp.zeros((1, 128, 128, 3), jnp.uint8), checkpoint_dir)
        self.score_thresh = float(score_thresh)
        self._anchors = {}

        thresh = self.score_thresh
        model = self.model

        @jax.jit
        @jax.named_scope("InstanceSegment")
        def infer(params, images, anchors):
            def fwd(mdl, images):
                cls, deltas = mdl.detect(images)
                packed, sel = pack_detections(cls, deltas, anchors, thresh,
                                              top_k=TOP_K)
                mask_p = jax.nn.sigmoid(mdl.roi_masks(images, sel))
                B = sel.shape[0]
                return jnp.concatenate(
                    [packed,
                     mask_p.reshape(B, TOP_K, MASK_SIZE * MASK_SIZE)],
                    axis=-1)

            return model.apply(params, images, method=fwd)

        self._infer = infer

    def infer_cost_flops(self, batch):
        """XLA-reported FLOPs for one inference call on `batch` (for
        the bench's MFU accounting); None when unavailable."""
        from .detection import anchored_cost_flops
        return anchored_cost_flops(self, batch)

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        """Returns a (B, top_k, 6 + M*M) float32 batch, device-resident
        (single fetch per task at the sink, PERF.md §5)."""
        images = jnp.asarray(frame)
        return self._infer(self.params, images,
                           anchors_for(self._anchors, images.shape))
