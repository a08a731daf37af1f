"""Neural building blocks (flax.linen) shared by the model families.

Capability parity: the reference delegates model compute to Caffe2/TF GPU
kernels inside ops (OpenPose pose app, TF SSD detection app — SURVEY §2.4);
here models are first-class JAX modules the kernel stdlib wraps.  bfloat16
activations by default: matmuls/convs land on the MXU.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.linen.dtypes import promote_dtype

from ..util import metrics as _mx

_M_STEM = _mx.registry().counter(
    "scanner_tpu_backbone_stem_total",
    "Backbone stems traced, by the form the input's shape chose: s2d "
    "(H and W multiples of 4: the 7x7 stride-4 convolution over "
    "12-channel column blocks) or plain (over the 3-channel frame).",
    labels=["path"])


class ResBlock(nn.Module):
    ch: int
    stride: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.Conv(self.ch, (3, 3), strides=(self.stride, self.stride),
                    dtype=self.dtype, padding="SAME")(x)
        h = nn.GroupNorm(num_groups=8, dtype=self.dtype)(h)
        h = nn.relu(h)
        h = nn.Conv(self.ch, (3, 3), dtype=self.dtype, padding="SAME")(h)
        h = nn.GroupNorm(num_groups=8, dtype=self.dtype)(h)
        if x.shape[-1] != self.ch or self.stride != 1:
            x = nn.Conv(self.ch, (1, 1),
                        strides=(self.stride, self.stride),
                        dtype=self.dtype)(x)
        return nn.relu(x + h)


class Stem(nn.Module):
    """The backbone's 7x7 stride-4 SAME convolution, with `nn.Conv`'s
    parameters: a (7, 7, Cin, C) `lecun_normal` kernel, a (C,) bias.

    Where H and W are multiples of 4 it reads the frame as column
    blocks: (B, H, W, Cin) -> (B, H, W/4, 4 Cin) is a reshape that moves
    nothing, the kernel gains zero columns to 12 taps (3 before, 2 after)
    regrouped to (7, 3, 4 Cin, C), and the convolution strides (4, 1)
    with SAME's own padding, (1, 2) rows and one block each side.  The
    products are the same and the added ones exact zeros.  A 3-channel
    batch is what the TPU compiler would otherwise relay into a copy
    with the batch on lanes and the channels on sublanes, padded ~21
    times.  Other shapes take the plain convolution."""

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, H, W, cin = x.shape
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, cin, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        dn = ("NHWC", "HWIO", "NHWC")
        if H % 4 == 0 and W % 4 == 0:
            _M_STEM.labels(path="s2d").inc()
            x = x.reshape(B, H, W // 4, 4 * cin)
            kernel = jnp.pad(kernel, ((0, 0), (3, 2), (0, 0), (0, 0)))
            kernel = kernel.reshape(7, 3, 4 * cin, self.features)
            y = jax.lax.conv_general_dilated(
                x, kernel, (4, 1), ((1, 2), (1, 1)), dimension_numbers=dn)
        else:
            _M_STEM.labels(path="plain").inc()
            y = jax.lax.conv_general_dilated(
                x, kernel, (4, 4), "SAME", dimension_numbers=dn)
        return y + bias


class Backbone(nn.Module):
    """ResNet-lite feature extractor: (B, H, W, 3) -> (B, H/16, W/16, C).

    Stands in for the reference apps' ResNet/VGG backbones (pose app
    Caffe model, SSD mobilenet) in a TPU-native dress.
    """

    width: int = 64
    depths: Sequence[int] = (2, 2, 2)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype) / 255.0
        x = Stem(self.width, dtype=self.dtype, name="Conv_0")(x)
        x = nn.GroupNorm(num_groups=8, dtype=self.dtype)(x)
        x = nn.relu(x)
        ch = self.width
        for stage, depth in enumerate(self.depths):
            for i in range(depth):
                stride = 2 if (i == 0 and stage > 0) else 1
                x = ResBlock(ch, stride=stride, dtype=self.dtype)(x)
            ch *= 2
        return x  # (B, H/16, W/16, width * 2^(len(depths)-1))


class MoEMlp(nn.Module):
    """Top-1 routed mixture-of-experts MLP over tokens (B, T, C).

    Experts evaluate densely and the router's one-hot selects — compiler
    friendly (no dynamic gather), fine for small expert counts; gives the
    framework a real expert-parallel surface (experts shard over 'tp').
    """

    num_experts: int = 4
    hidden: int = 256
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        gate = nn.Dense(self.num_experts, dtype=self.dtype, name="router")(x)
        probs = jax.nn.softmax(gate.astype(jnp.float32), axis=-1)
        sel = jax.nn.one_hot(jnp.argmax(probs, -1), self.num_experts,
                             dtype=x.dtype)
        # experts as one batched params tensor: (E, C, H) and (E, H, C)
        C = x.shape[-1]
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (self.num_experts, C, self.hidden)).astype(self.dtype)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (self.num_experts, self.hidden, C)).astype(self.dtype)
        h = jnp.einsum("btc,ech->bteh", x, w1)
        h = nn.relu(h)
        y = jnp.einsum("bteh,ehc->btec", h, w2)
        return jnp.einsum("btec,bte->btc", y, sel)


class TemporalBlock(nn.Module):
    """Pre-norm MHA + MoE-MLP over the time axis of (B, T, C) tokens.

    attn_fn lets callers swap in ring attention (sequence sharded over the
    'sp' mesh axis) without changing the module."""

    heads: int = 4
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        D = C // self.heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * C, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv.reshape(B, T, 3 * self.heads, D), 3, axis=2)
        if self.attn_fn is not None:
            att = self.attn_fn(q, k, v)
        else:
            from ..parallel.ring_attention import reference_attention
            att = reference_attention(q, k, v)
        att = att.reshape(B, T, C)
        x = x + nn.Dense(C, dtype=self.dtype, name="proj")(att)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        return x + MoEMlp(dtype=self.dtype)(h)


class DeconvHead(nn.Module):
    """SimpleBaseline-style upsampling head producing K heatmaps."""

    keypoints: int = 17
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        for _ in range(2):
            x = nn.ConvTranspose(128, (4, 4), strides=(2, 2),
                                 dtype=self.dtype)(x)
            x = nn.relu(x)
        return nn.Conv(self.keypoints, (1, 1), dtype=jnp.float32)(x)
