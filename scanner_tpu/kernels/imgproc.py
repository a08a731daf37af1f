"""Image-processing kernel stdlib (JAX).

Capability parity: the scannertools kernel stdlib the reference tutorials
import (examples/tutorials/00_basic.py `import scannertools.imgproc`:
Histogram, Resize, Blur, OpticalFlow) and tests/test_ops.cpp (Histogram:13,
Resize:114, Blur:239, OpticalFlow:63).

All kernels are batched: XLA sees (batch, H, W, C) uint8 arrays, the natural
TPU layout.  jit caches compile per (shape, dtype), and the engine's
bucketed dispatch (engine/evaluate.py) rounds every call up a small
power-of-two ladder capped at the declared batch= — so each op compiles a
bounded executable set however ragged the task geometry is.  The batch
declaration is a memory cap, not a promise of exact call sizes.
"""

from __future__ import annotations

import functools
import struct
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import DeviceType, FrameType
from ..graph.ops import Kernel, register_op
from ..util.coststats import CostDescriptor

HISTOGRAM_BINS = 16


def _frame_shape(shapes, idx: int = 0):
    """The idx-th input's array shape, or None when the engine handed a
    per-row list (host path) — cost hooks then fall back to the derived
    default rather than guess."""
    if idx < len(shapes) and isinstance(shapes[idx], tuple):
        return shapes[idx]
    return None


@functools.partial(jax.jit, static_argnames=("bins",))
@jax.named_scope("Histogram")
def _histogram_impl(frames: jnp.ndarray, bins: int = HISTOGRAM_BINS):
    """(batch, H, W, C) uint8 -> (batch, C, bins) int32 counts.

    vmapped bincount: lowers to a segment reduction — good on CPU/GPU
    XLA; no TPU path runs it (a scatter there; its speed on a TPU: not
    measured)."""
    b, c = frames.shape[0], frames.shape[-1]
    vals = (frames.astype(jnp.int32) * bins) // 256
    vals = vals.reshape(b, -1, c).transpose(0, 2, 1).reshape(b * c, -1)
    counts = jax.vmap(lambda v: jnp.bincount(v, length=bins))(vals)
    return counts.reshape(b, c, bins)


def _histogram_seq_impl(frames: jnp.ndarray, bins: int = HISTOGRAM_BINS):
    """(batch, H, W, C) uint8 -> (batch, C, bins) int32 via one
    compare+sum pass per bin inside a lax.scan: no scatter and no
    materialized (B, P, C, bins) one-hot (that bool tensor costs ~5x on
    XLA CPU — 80 ms vs 16 ms at 8x240x320, measured 2026-08).  The scan
    over bin ids — rather than an unrolled python loop — is load-bearing
    for FUSION chains: `vals` becomes a loop invariant XLA must
    materialize ONCE, where an unrolled loop leaves 16 sibling
    compare+reduce consumers and XLA CPU re-fuses the whole upstream
    producer (e.g. a composed Blur) into every one of them — it also
    deletes optimization_barrier, so this loop structure is the only
    reliable fence.  This is the lowering fused chains trace on
    host-only backends, where Histogram's numpy bincount fast path is
    unreachable inside a jit.

    The per-bin reduce is hierarchical: uint8 partial sums over 128-wide
    chunks (128 matches fit uint8), then an int32 reduce over the tiny
    partials.  A direct int32 reduce converts every compare result to 4
    bytes first, quadrupling accumulate traffic — 14.4 ms vs 4.4 ms at
    8x240x320 on XLA CPU (measured 2026-08).  Assumes bins < 255 (the
    chunk padding uses 255 as a never-matches bin id)."""
    b, c = frames.shape[0], frames.shape[-1]
    vals = ((frames.astype(jnp.int32) * bins) // 256).astype(jnp.uint8)
    vals = vals.reshape(b, -1, c).transpose(0, 2, 1)    # (B, C, P)
    chunk = 128
    pad = (-vals.shape[-1]) % chunk
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, pad)),
                       constant_values=255)
    vals = vals.reshape(b, c, -1, chunk)
    ids = jnp.arange(bins, dtype=jnp.uint8)

    def _bin(carry, i):
        part = (vals == i).sum(3, dtype=jnp.uint8)
        return carry, part.astype(jnp.int32).sum(2)

    _, cols = jax.lax.scan(_bin, 0, ids)
    return jnp.moveaxis(cols, 0, -1)


@functools.partial(jax.jit, static_argnames=("bins",))
@jax.named_scope("Histogram")
def _histogram_cmp_impl(frames: jnp.ndarray, bins: int = HISTOGRAM_BINS):
    """(batch, H, W, C) uint8 -> (batch, C, bins) int32 via one-hot
    compare + reduce: pure VPU work, no scatter — the lowering fused
    chains trace on TPU.  Alone, one jit from a uint8 16 x 1080p packet
    on a v5e: 4.64 ms, against the pallas kernel's 1.21 (PERF.md §6,
    PR 32), which is why a staged call does not take it."""
    b, c = frames.shape[0], frames.shape[-1]
    vals = (frames.astype(jnp.int32) * bins) // 256
    vals = vals.reshape(b, -1, c)                       # (B, P, C)
    ids = jnp.arange(bins, dtype=jnp.int32)
    onehot = (vals[..., None] == ids)                   # (B, P, C, bins)
    return onehot.sum(1, dtype=jnp.int32)               # (B, C, bins)


@register_op(device=DeviceType.TPU, batch=16)
class Histogram(Kernel):
    """Per-channel 16-bin color histogram; returns [r, g, b] int32 arrays
    per frame (matching scannertools' UniformList(Histogram, parts=3)).

    Backend selection, by what was measured and by nothing a user
    sets: a TPU runs the uint8 pallas compare+reduce kernel
    (kernels/pallas_ops.py; PERF.md §6, PR 32 has the two packet timings
    that chose it over _histogram_cmp_impl) — a kernel that does not
    compile raises, there is no silent XLA fallback; a host-only backend
    uses numpy's C bincount; other accelerators the vmapped-bincount XLA
    path."""

    def __init__(self, config):
        super().__init__(config)
        from . import pallas_ops
        self._on_tpu = pallas_ops.on_tpu()
        # on a host-only backend numpy's C bincount beats the XLA-CPU
        # scatter lowering; accelerators take the XLA/pallas path
        self._use_numpy = jax.default_backend() == "cpu"

    @staticmethod
    def _histogram_np(frames: np.ndarray) -> np.ndarray:
        b, c = frames.shape[0], frames.shape[-1]
        bins = HISTOGRAM_BINS
        assert bins == 16, "np fast path assumes 16 bins (uint8 >> 4)"
        v = (frames >> 4).astype(np.int32)
        v += np.arange(c, dtype=np.int32) * bins
        flat = v.reshape(b, -1)
        # int32, matching the XLA/pallas paths so stored output dtype does
        # not depend on which backend ran the job
        out = np.empty((b, c, bins), np.int32)
        for i in range(b):
            out[i] = np.bincount(flat[i], minlength=c * bins).reshape(c,
                                                                      bins)
        return out

    def cost(self, shapes):
        """Compare+reduce histogram: per pixel-channel, one fixed-point
        binning (2 ops) plus `bins` compares and `bins` accumulates.
        Reads the uint8 frames once, writes (b, C, bins) int32."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4:
            return None
        b, h, w, c = s
        px = b * h * w * c
        return CostDescriptor(
            flops=float(px * (HISTOGRAM_BINS + 2)),
            bytes_in=float(px),
            bytes_out=float(b * c * HISTOGRAM_BINS * 4))

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        """Returns the (batch, C, bins) int32 counts as ONE batch array.

        Device paths return it WITHOUT materializing on host: jax arrays
        chain asynchronously through the column store and the sink
        fetches once per task — a blocking np.asarray per work packet
        would serialize the pipeline on d2h latency.  Each stored row
        is a (C, bins) array;
        row[c] indexes channel c's histogram (scannertools parity:
        UniformList(Histogram, parts=3))."""
        if self._use_numpy and isinstance(frame, np.ndarray):
            return self._histogram_np(frame)
        if self._on_tpu:
            from .pallas_ops import histogram_frames
            return histogram_frames(jnp.asarray(frame))
        return _histogram_impl(jnp.asarray(frame))

    def execute_traced(self, frame):
        """Fusion-chain core: inside a composed trace the numpy fast
        path is unreachable (the input is a tracer), and the bincount
        lowering serializes on scatter on every backend.  TPU traces
        the one-hot compare+sum, which XLA can fuse with the chain's
        producer; hosts and other accelerators the per-bin compare+sum
        (see _histogram_seq_impl)."""
        frame = jnp.asarray(frame)
        if self._on_tpu:
            return _histogram_cmp_impl(frame)
        return _histogram_seq_impl(frame)


def _resize_band(in_size: int, out_size: int):
    """Contiguous tap indices + normalized triangle weights for one
    axis of a separable bilinear resize (half-pixel centers, antialias
    width max(scale, 1) — the jax.image.resize bilinear kernel).  Every
    output row reads the same small tap count k, so the resize lowers
    to k weighted gathers per axis instead of a dense contraction."""
    scale = in_size / out_size
    centers = (np.arange(out_size) + 0.5) * scale - 0.5
    idx = np.arange(in_size)
    wts = 1.0 - np.abs(centers[:, None] - idx[None, :]) / max(scale, 1.0)
    wts = np.clip(wts, 0.0, None)
    nz = wts > 0
    k = int(nz.sum(1).max())
    start = np.where(nz.any(1), nz.argmax(1), 0)
    start = np.minimum(start, in_size - k)
    taps = start[:, None] + np.arange(k)[None, :]
    tw = np.take_along_axis(wts, taps, 1)
    tw = (tw / tw.sum(1, keepdims=True)).astype(np.float32)
    return jnp.asarray(taps), jnp.asarray(tw), k


@functools.partial(jax.jit, static_argnames=("h", "w"))
@jax.named_scope("Resize")
def _resize_impl(frames: jnp.ndarray, h: int, w: int):
    """Separable gather-based bilinear resize.  The triangle kernel is
    sparse — k taps per output row (k=4 for a 2x downscale) — but
    jax.image.resize materializes it as a dense [in, out] contraction,
    which XLA CPU executes in full: 91.7 ms vs 12.9 ms for the tap form
    at 8x480x640 -> 240x320 (measured 2026-08).  h/w are static, so the
    tap tables are concrete numpy at trace time.

    Structure matters as much as the tap count.  The h-pass gathers
    uint8 rows and converts AFTER the gather (converting the whole
    input first makes XLA materialize a 4x-bigger f32 copy), and the
    w-pass runs in a lax.scan over output row blocks with the h-pass
    result as a loop invariant: left to itself, XLA CPU merges the two
    passes into one 2-D gather of hk*wk taps per output element,
    discarding separability — the loop invariant pins the h-pass to
    one materialization (8.4 ms -> 6.4 ms alone, and it is what keeps
    fused chains from re-fusing the resize into downstream taps)."""
    b, c = frames.shape[0], frames.shape[-1]
    hi, hw_, hk = _resize_band(frames.shape[1], h)
    wi, ww_, wk = _resize_band(frames.shape[2], w)
    y = sum(hw_[:, j, None, None] * frames[:, hi[:, j], :, :]
            .astype(jnp.float32) for j in range(hk))
    rb = min(48, h)
    nb = -(-h // rb)
    y = jnp.pad(y, ((0, 0), (0, nb * rb - h), (0, 0), (0, 0)))

    def _block(carry, s):
        ys = jax.lax.dynamic_slice_in_dim(y, s, rb, 1)
        o = sum(ww_[:, j, None] * ys[:, :, wi[:, j], :] for j in range(wk))
        return carry, jnp.clip(jnp.round(o), 0, 255).astype(jnp.uint8)

    _, blocks = jax.lax.scan(_block, 0, jnp.arange(nb) * rb)
    return jnp.moveaxis(blocks, 0, 1).reshape(b, nb * rb, w, c)[:, :h]


@register_op(device=DeviceType.TPU, batch=16)
class Resize(Kernel):
    """Bilinear resize to (width, height) — per-stream args like the
    reference Resize op (test_ops.cpp:114, stream-protobuf args)."""

    def __init__(self, config, width: int = 0, height: int = 0):
        super().__init__(config)
        self.width, self.height = int(width), int(height)

    def new_stream(self, width: int = None, height: int = None):
        if width is not None:
            self.width = int(width)
        if height is not None:
            self.height = int(height)

    def cost(self, shapes):
        """Separable bilinear resample: 4 taps (mul+add) per output
        pixel-channel = 8 flops.  Reads the source frames, writes the
        (b, H, W, c) uint8 result."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4 or not (self.height and self.width):
            return None
        b, h, w, c = s
        out_px = b * self.height * self.width * c
        return CostDescriptor(flops=float(out_px * 8),
                              bytes_in=float(b * h * w * c),
                              bytes_out=float(out_px))

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        # device in -> device out: chained TPU ops never bounce to host
        return _resize_impl(jnp.asarray(frame), self.height, self.width)


@functools.partial(jax.jit, static_argnames=("oh", "ow"))
@jax.named_scope("CropResize")
def _crop_resize_impl(frames: jnp.ndarray, boxes: jnp.ndarray, oh: int,
                      ow: int):
    """Crop unit-coordinate boxes [y1,x1,y2,x2] out of (b,H,W,C) frames
    and resample each to (oh, ow).  scale_and_translate keeps the output
    shape static whatever the box is — no dynamic shapes on device."""
    H, W = frames.shape[1], frames.shape[2]

    def one(frame, box):
        y1, x1, y2, x2 = box[0] * H, box[1] * W, box[2] * H, box[3] * W
        h = jnp.maximum(y2 - y1, 1.0)
        w = jnp.maximum(x2 - x1, 1.0)
        scale = jnp.asarray([oh / h, ow / w], jnp.float32)
        # output pixel o maps to input o/scale + translate/..; translate
        # is in OUTPUT units: shift so input y1 lands on output 0
        translate = jnp.asarray([-y1 * oh / h, -x1 * ow / w], jnp.float32)
        out = jax.image.scale_and_translate(
            frame.astype(jnp.float32), (oh, ow, frame.shape[-1]),
            (0, 1), scale, translate, method="linear")
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)

    return jax.vmap(one)(frames, boxes)


@register_op(device=DeviceType.TPU, batch=16)
class CropResize(Kernel):
    """Crop a per-row box (unit coords [y1, x1, y2, x2]) out of each frame
    and resize to (height, width) — the region-extraction step of the
    reference's re-id/feature apps (open-reid extract_features.py resamples
    person crops to 256x128), with static output shapes so the whole op
    stays on device.  `size` sets a square output; height/width override
    per axis."""

    def __init__(self, config, size: int = 64, height: int = 0,
                 width: int = 0):
        super().__init__(config)
        self.height = int(height) or int(size)
        self.width = int(width) or int(size)

    def precompile_input(self, name: str):
        # boxes are unit coords, so a full-frame box warms the exact
        # executable the real calls hit (engine bucket-ladder warm-up)
        if name == "box":
            return np.asarray([0.0, 0.0, 1.0, 1.0], np.float32)
        return None

    def cost(self, shapes):
        """Crop + bilinear resample to (height, width): like Resize, 4
        taps (mul+add) per output pixel-channel = 8 flops; the per-box
        scale/translate arithmetic is O(b) and ignored.  Reads the
        frames and the (b, 4) float32 boxes, writes the crops."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4:
            return None
        b, h, w, c = s
        out_px = b * self.height * self.width * c
        return CostDescriptor(flops=float(out_px * 8),
                              bytes_in=float(b * h * w * c + b * 4 * 4),
                              bytes_out=float(out_px))

    def execute(self, frame: Sequence[FrameType],
                box: Sequence[Any]) -> Sequence[FrameType]:
        # the box column arrives as one (b, 4) array, on the instance's
        # chip where the engine stages (evaluate:upload)
        return _crop_resize_impl(jnp.asarray(frame),
                                 jnp.asarray(box, jnp.float32), self.height,
                                 self.width)


def _gaussian_kernel1d(ksize: int, sigma: float) -> np.ndarray:
    r = (ksize - 1) / 2.0
    x = np.arange(ksize, dtype=np.float32) - r
    k = np.exp(-(x ** 2) / (2.0 * max(sigma, 1e-6) ** 2))
    return (k / k.sum()).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("ksize",))
@jax.named_scope("Blur")
def _blur_impl(frames: jnp.ndarray, kern: jnp.ndarray, ksize: int):
    """Separable gaussian as shift-add: per tap, one scaled slice of the
    edge-padded image, summed — pure elementwise VPU work.  The previous
    depthwise conv_general_dilated lowering (batch*channel images of ONE
    feature each) hits XLA CPU's scalar conv path and ran 28x slower at
    the 8x240x320 bench geometry (195 ms vs 7 ms, measured 2026-08);
    one-feature convs are equally hostile to the TPU MXU.

    The shift-add runs inside a lax.scan over output ROW BLOCKS with the
    padded input as a loop invariant.  That structure is load-bearing
    for fusion chains: XLA CPU's loop fusion duplicates a producer into
    every sibling consumer (it also deletes optimization_barrier), so a
    composed upstream member would be recomputed once per tap slice —
    the loop invariant pins it to ONE materialization while the taps
    stay fully fused inside the block body.  Per-element arithmetic is
    identical to the unfenced form (bit-exact; block rows past `h` are
    computed on zero padding and cropped)."""
    b, h, w, c = frames.shape
    pad = ksize // 2
    x = jnp.pad(frames.astype(jnp.float32),
                ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="edge")
    rb = min(48, h)
    nb = -(-h // rb)
    # out-of-bounds zero pad so the last block's slice never clamps
    # (dynamic_slice clamps starts, which would silently shift rows)
    x = jnp.pad(x, ((0, 0), (0, nb * rb - h), (0, 0), (0, 0)))

    def _block(carry, s):
        xs = jax.lax.dynamic_slice_in_dim(x, s, rb + 2 * pad, 1)
        v = sum(kern[i] * xs[:, i:i + rb, :, :] for i in range(ksize))
        o = sum(kern[j] * v[:, :, j:j + w, :] for j in range(ksize))
        return carry, jnp.clip(jnp.round(o), 0, 255).astype(jnp.uint8)

    _, blocks = jax.lax.scan(_block, 0, jnp.arange(nb) * rb)
    return jnp.moveaxis(blocks, 0, 1).reshape(b, nb * rb, w, c)[:, :h]


@register_op(device=DeviceType.TPU, batch=16)
class Blur(Kernel):
    """Gaussian blur (reference tests/test_ops.cpp:239 Blur)."""

    def __init__(self, config, kernel_size: int = 3, sigma: float = 0.5):
        super().__init__(config)
        self.ksize = int(kernel_size) | 1  # odd
        self.kern = jnp.asarray(_gaussian_kernel1d(self.ksize, float(sigma)))

    def cost(self, shapes):
        """Separable gaussian: two 1-D passes of `ksize` taps each —
        2 * ksize * 2 flops per pixel-channel.  uint8 in, uint8 out,
        same geometry."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4:
            return None
        b, h, w, c = s
        px = b * h * w * c
        return CostDescriptor(flops=float(px * 4 * self.ksize),
                              bytes_in=float(px), bytes_out=float(px))

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        # device in -> device out: chained TPU ops never bounce to host
        return _blur_impl(jnp.asarray(frame), self.kern, self.ksize)


# 0.299, 0.587, 0.114 in 16-bit fixed point; they sum to 2**16, so a
# grey pixel (v, v, v) reads v
GRAY_WEIGHTS = (19595, 38470, 7471)


def gray3(frames):
    """(..., 3) uint8 RGB -> (..., 3) uint8, each channel the luma
    (19595 R + 38470 G + 7471 B) >> 16: integer arithmetic, so a numpy
    batch on the host, a jax batch on a device and a tracer inside a
    fused chain give the same bytes.  (In float32 they do not: XLA's
    CPU backend contracts a multiply into an add, and 1001 of the 2**24
    colours then truncate to another level than numpy's.)"""
    xp = np if isinstance(frames, np.ndarray) else jnp
    f = frames.astype(xp.int32)
    wr, wg, wb = GRAY_WEIGHTS
    g = ((wr * f[..., 0] + wg * f[..., 1] + wb * f[..., 2]) >> 16) \
        .astype(xp.uint8)
    return xp.stack([g, g, g], axis=-1)


_gray3_impl = jax.jit(jax.named_scope("Grayscale")(gray3))


@register_op(device=DeviceType.TPU, batch=16)
class Grayscale(Kernel):
    """RGB frame -> its luma in all three channels (the walkthrough's
    colour op, reference examples/apps/walkthroughs): a device op, so
    it fuses with a `Resize` or a `Blur` before it."""

    def cost(self, shapes):
        """Three multiplies, two adds and a shift a pixel.  uint8 in,
        uint8 out, same geometry."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4:
            return None
        px = float(np.prod(s))
        return CostDescriptor(flops=2.0 * px, bytes_in=px, bytes_out=px)

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        # a host batch (no device staging) stays on the host; device in
        # -> device out
        if isinstance(frame, np.ndarray):
            return gray3(frame)
        return _gray3_impl(jnp.asarray(frame))


@jax.jit
@jax.named_scope("OpticalFlow")
def _grayscale(frames: jnp.ndarray) -> jnp.ndarray:
    w = jnp.asarray([0.299, 0.587, 0.114], jnp.float32)
    return (frames.astype(jnp.float32) * w).sum(-1)


HS_ITERS = 16  # fixed Horn-Schunck iteration count (cost model reads it)


@functools.partial(jax.jit, static_argnames=("iters",))
@jax.named_scope("OpticalFlow")
def _horn_schunck(prev: jnp.ndarray, nxt: jnp.ndarray, iters: int = HS_ITERS,
                  alpha: float = 15.0):
    """Horn-Schunck optical flow, batched; (b,h,w) float32 grayscale in,
    (b,h,w,2) float32 flow out, float32 throughout on every backend.  The
    fixed iteration count is unrolled: one XLA program with no loop and
    no data-dependent control flow (as a lax.scan the solve read 9 %
    slower on the v5e, 24.3 against 22.1 ms for four 1080p rows, and a
    device trace counts a loop's time twice, once as the `while` and once
    as its body's operations).

    The neighbourhood average is eight shifted adds over the edge-padded
    array (edge neighbours 1/6, corners 1/12), on the VPU.  The one-channel
    3x3 conv_general_dilated that stood here compiled to bfloat16 operands
    on the TPU (default matmul precision), so sixteen iterations each
    rounded the running flow to 8 bits of mantissa (62.7 ms for the four
    rows); at precision HIGHEST it is float32 and takes 168 ms: an MXU
    pass for a 1 -> 1 channel 3x3 buys nothing."""
    Ix = (jnp.roll(prev, -1, 2) - jnp.roll(prev, 1, 2)) * 0.5
    Iy = (jnp.roll(prev, -1, 1) - jnp.roll(prev, 1, 1)) * 0.5
    It = nxt - prev

    def avg(x):
        p = jnp.pad(x, ((0, 0), (1, 1), (1, 1)), mode="edge")
        edges = (p[:, :-2, 1:-1] + p[:, 2:, 1:-1]
                 + p[:, 1:-1, :-2] + p[:, 1:-1, 2:])
        corners = (p[:, :-2, :-2] + p[:, :-2, 2:]
                   + p[:, 2:, :-2] + p[:, 2:, 2:])
        return edges * jnp.float32(1 / 6) + corners * jnp.float32(1 / 12)

    denom = alpha ** 2 + Ix ** 2 + Iy ** 2
    u, v = jnp.zeros_like(Ix), jnp.zeros_like(Iy)
    for _ in range(iters):
        ub, vb = avg(u), avg(v)
        t = (Ix * ub + Iy * vb + It) / denom
        u, v = ub - Ix * t, vb - Iy * t
    return jnp.stack([u, v], axis=-1)


@register_op(device=DeviceType.TPU, stencil=[-1, 0], batch=4)
class OpticalFlow(Kernel):
    """Dense optical flow between consecutive frames (reference scannertools
    OpticalFlow / test_ops.cpp:63, StenciledKernel; upstream's op is
    OpenCV's Farneback, this one is Horn-Schunck at a fixed iteration
    count: another algorithm under the same name, stencil and column
    type).  Output per row: float32 (H, W, 2) flow (u, v) from the
    previous frame to the current; row 0 (REPEAT_EDGE: its own
    predecessor) reads exactly 0.

    The equations, float32 throughout: E = 0.299 R + 0.587 G + 0.114 B of
    both frames; Ix, Iy central differences of the PREVIOUS frame by
    jnp.roll (they wrap at the borders), It = E(cur) - E(prev); from
    u = v = 0, HS_ITERS Jacobi iterations of
        t = (Ix * avg(u) + Iy * avg(v) + It) / (alpha^2 + Ix^2 + Iy^2)
        u, v = avg(u) - Ix * t, avg(v) - Iy * t
    with alpha = 15 and avg the 3x3 neighbourhood mean (edge neighbours
    1/6, corners 1/12, centre 0) over the edge-replicated field."""

    def cost(self, shapes):
        """Horn-Schunck: grayscale both frames (~5 flops/px each),
        gradients (~6/px), then HS_ITERS solver iterations of two
        neighbourhood averages (7 adds + 2 multiplies each: 18 flops/px)
        plus ~12 arithmetic ops/px.  Reads the (b, 2, H, W, C) uint8
        stencil window, writes (b, H, W, 2) float32 flow."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 5:
            return None
        b, win, h, w, c = s
        px = b * h * w
        flops = px * (win * 5 + 6 + HS_ITERS * (18 + 12))
        return CostDescriptor(flops=float(flops),
                              bytes_in=float(b * win * h * w * c),
                              bytes_out=float(px * 2 * 4))

    def execute(self, frame: Sequence[Sequence[FrameType]]
                ) -> Sequence[FrameType]:
        from ..engine.batch import is_array_data
        if is_array_data(frame):
            # engine-gathered (batch, window, H, W, C) array: slice, don't
            # restack
            arr = jnp.asarray(frame)
            prev, nxt = arr[:, 0], arr[:, 1]
        else:
            prev = jnp.asarray(np.stack([w[0] for w in frame]))
            nxt = jnp.asarray(np.stack([w[1] for w in frame]))
        return _horn_schunck(_grayscale(prev), _grayscale(nxt))


@functools.partial(jax.jit, donate_argnums=(1,))
@jax.named_scope("BackgroundSubtraction")
def _bgsub_impl(frames: jnp.ndarray, avg: jnp.ndarray, fresh: jnp.ndarray,
                alpha: jnp.ndarray, level: jnp.ndarray):
    """One packet of the running-average recurrence, float32 throughout:
    (b, H, W, C) uint8 rows, the (H, W, C) float32 average `avg` and
    whether the state was just reset -> ((b,) int32 foreground counts,
    the average after the packet's last row).  `fresh` is a traced
    scalar: a reset costs no second executable.  `avg` is donated: the
    state is updated where it lies on the chip.

    The scan stays a loop.  Unrolled it ran twice as fast on the v5e
    (0.076 ms a 1080p row against 0.143; the traffic's floor is 0.068:
    as a loop XLA moves the average to another memory space and back
    every row), but nothing waits for it, and XLA's CPU backend then
    contracts the update's multiplies and adds step by step differently
    from the reference's one step, so that flat regions within a
    rounding of the threshold fall the other way together (up to 9e-5 of
    a frame in the CPU rehearsal, over `bg_count_gap`'s limit).  A
    device trace counts a loop's time twice (PERF.md sec. 7)."""
    x0 = frames[0].astype(jnp.float32)
    avg = jnp.where(fresh, x0, avg)
    keep = jnp.float32(1) - alpha

    def row(a, f):
        x = f.astype(jnp.float32)
        fg = jnp.all(jnp.abs(x - a) >= level, axis=-1)
        return a * keep + x * alpha, jnp.sum(fg, dtype=jnp.int32)

    avg, counts = jax.lax.scan(row, avg, frames)
    return counts, avg


@register_op(device=DeviceType.TPU, batch=16, bounded_state=60)
class BackgroundSubtraction(Kernel):
    """Running-average background subtraction (upstream
    examples/tutorials/02_op_attributes.py, its bounded-state example):
    the kernel holds an average image A; after a reset the first row
    seen sets A = F.  For a row's frame F (float32 of the uint8 RGB): a
    pixel is foreground where |F - A| >= 255 * threshold in EVERY
    channel, c = the count of foreground pixels, then
    A = A * (1 - alpha) + F * alpha.  Output per row:
    struct.pack("=q", c).  Upstream returns the masked frame; this op
    commits the mask's size.

    The state is a float32 (H, W, C) jax.Array on the chip the frames
    are on; a packet is one jitted scan over its rows that carries it;
    only the packet's counts come back to the host.  bounded_state=60:
    a task replays the 60 rows before its own from a reset state
    (0.95^60 = 4.6 % of what a reset forgot is left)."""

    def __init__(self, config, alpha: float = 0.05,
                 threshold: float = 0.05):
        super().__init__(config)
        self.alpha = np.float32(alpha)
        self.level = np.float32(255.0 * float(threshold))
        self._avg = None

    def reset(self) -> None:
        self._avg = None

    def cost(self, shapes):
        """A row reads its uint8 frame and reads and writes the float32
        average; per pixel-channel a subtract, an abs, a compare, two
        multiplies and an add, and the count's add."""
        s = _frame_shape(shapes)
        if s is None or len(s) != 4:
            return None
        b, h, w, c = s
        px = b * h * w * c
        return CostDescriptor(flops=float(7 * px),
                              bytes_in=float(px + 4 * px),
                              bytes_out=float(4 * px + 4 * b))

    def execute(self, frame: Sequence[FrameType]) -> Sequence[bytes]:
        frames = jnp.asarray(frame)
        fresh = self._avg is None
        if fresh:
            # a placeholder the program overwrites with the first row:
            # made where the frames are, by a fill, not by a transfer
            self._avg = jnp.zeros(frames.shape[1:], jnp.float32,
                                  device=frames.sharding)
        counts, self._avg = _bgsub_impl(frames, self._avg, fresh,
                                        self.alpha, self.level)
        return [struct.pack("=q", int(c)) for c in np.asarray(counts)]
