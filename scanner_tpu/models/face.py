"""Face detection and embedding.

Capability parity: reference examples/apps/face_detection (MTCNN-style
kernel) and the multi-worker face-embedding baseline config
(BASELINE.json config 5).  Detection reuses the SSD family with a
face-tuned anchor set; embeddings come from a compact backbone + projection
head with L2-normalized output.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import DeviceType, FrameType
from ..graph.ops import Kernel, register_op
from .detection import ObjectDetect
from .nets import Backbone


@register_op(name="FaceDetect", device=DeviceType.TPU, batch=8)
class FaceDetect(ObjectDetect):
    """SSD detector with face-tuned defaults (reference face_detection
    app).  Width-8 instances restore the shipped face-task weights
    (models/weights/face_ssd_w8.npz, models/detect_train.py) unless a
    checkpoint is given or pretrained=False."""

    _shipped = "face_ssd_w8.npz"
    _shipped_width = 8

    def __init__(self, config, width: int = 32, score_thresh: float = 0.1,
                 seed: int = 1, checkpoint_dir: Optional[str] = None,
                 pretrained: bool = True):
        super().__init__(config, width=width, num_classes=2,
                         score_thresh=score_thresh, seed=seed,
                         checkpoint_dir=checkpoint_dir,
                         pretrained=pretrained)


class EmbeddingNet(nn.Module):
    dim: int = 128
    width: int = 32
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        feat = Backbone(width=self.width, dtype=self.dtype)(images)
        pooled = feat.mean(axis=(1, 2))
        emb = nn.Dense(self.dim, dtype=jnp.float32)(pooled)
        # zero inputs (e.g. a crop that fell outside the frame) must yield
        # a zero vector, not 0/0 = NaN
        norm = jnp.linalg.norm(emb, axis=-1, keepdims=True)
        return emb / jnp.maximum(norm, 1e-12)


@register_op(device=DeviceType.TPU, batch=16)
class FaceEmbedding(Kernel):
    """L2-normalized face/crop embedding vectors (reference face-embedding
    pipeline, BASELINE config 5).  Width-8/dim-128 instances restore the
    shipped identity-metric weights (models/weights/embed_w8.npz,
    models/detect_train.py) unless a checkpoint is given or
    pretrained=False."""

    _shipped = "embed_w8.npz"
    _shipped_width = 8

    def __init__(self, config, dim: int = 128, width: int = 32,
                 seed: int = 2, checkpoint_dir: Optional[str] = None,
                 pretrained: bool = True):
        super().__init__(config)
        self.model = EmbeddingNet(dim=dim, width=width)
        from .checkpoint import init_or_restore, shipped_weights
        from .infer import DataParallelApply
        if checkpoint_dir is None and pretrained \
                and width == self._shipped_width and dim == 128:
            checkpoint_dir = shipped_weights(self._shipped)
        params = init_or_restore(
            self.model, jax.random.PRNGKey(seed),
            jnp.zeros((1, 128, 128, 3), jnp.uint8), checkpoint_dir)
        # dp-shard batches over every chip the engine handed this kernel
        self._dp = DataParallelApply(
            jax.jit(jax.named_scope("FaceEmbedding")(self.model.apply)),
            params, config.devices)
        self.params = self._dp.params

    def infer_cost_flops(self, batch):
        """XLA-reported FLOPs for one inference call on `batch` (for
        the bench's MFU accounting); None when unavailable."""
        return self._dp.cost_flops(jnp.asarray(batch))

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        # (B, dim) embeddings returned without a host sync (device arrays
        # chain through the column store; the sink fetches once per task)
        return self._dp(jnp.asarray(frame))
