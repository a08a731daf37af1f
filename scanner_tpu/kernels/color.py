"""YUV420 -> RGB conversion, device (jnp) and host (numpy) flavors.

The decode pipeline can ship planar I420 (1.5 B/px) to the accelerator
instead of packed RGB24 (3 B/px) and convert there — halving host->device
bytes, the first-order term of every device pipeline (PERF.md §5).  The
reference did the same on GPU: NV12 frames converted by a CUDA kernel
(reference scanner/util/image.cu:22 nv12_to_rgb); here the conversion is
a jitted program of its own, the first of every packet: one uint8
relayout of the wire's planes, then a Pallas kernel
(`pallas_ops.yuv420_planes`) that widens, upsamples, combines and
narrows a block of rows in VMEM and writes the three uint8 planes once.
Nothing wider than uint8 touches HBM (PERF.md §6, PR 35).

Both flavors implement the SAME arithmetic — BT.601 limited range with
nearest-neighbor chroma upsampling in 8-bit integer fixed point — so
device and host pipelines are bit-identical on every backend
(test_yuv_wire.py pins this).  Note
swscale's own yuv420p->RGB24 path (the decoder's "rgb24" output) uses
fixed-point coefficients and bilinear chroma; the two conversions agree
closely but not bit-for-bit, which is why a pipeline picks ONE decode
format end-to-end rather than mixing per stage.
"""

from __future__ import annotations

import functools

import numpy as np

# ITU-R BT.601 studio swing (the default signaled range of the h264/hevc
# streams the engine ingests), in the classic 8-bit fixed-point form:
#   R = (298(Y-16)           + 409(V-128) + 128) >> 8
#   G = (298(Y-16) - 100(U-128) - 208(V-128) + 128) >> 8
#   B = (298(Y-16) + 516(U-128)            + 128) >> 8
# Integer arithmetic is EXACT on every backend — float fma/reassociation
# under XLA fusion would cost odd one-count rounding differences between
# host and device at some geometries.


def _split_planes(flat, h: int, w: int):
    """Slice flat I420 rows into Y/U/V planes; works identically on
    numpy and jax arrays (shared so the two flavors cannot drift)."""
    ch, cw = (h + 1) // 2, (w + 1) // 2
    y = flat[..., : h * w].reshape(*flat.shape[:-1], h, w)
    u = flat[..., h * w: h * w + ch * cw].reshape(*flat.shape[:-1], ch, cw)
    v = flat[..., h * w + ch * cw:].reshape(*flat.shape[:-1], ch, cw)
    return y, u, v


def _combine(y, u, v, xp):
    """The fixed-point arithmetic on int32 planes already at full
    resolution; returns int32 0..255.  The statement of it: the device's
    kernel (`pallas_ops._yuv_kernel`) is held to these bits."""
    yy = 298 * (y - 16)
    uu = u - 128
    vv = v - 128
    r = (yy + 409 * vv + 128) >> 8
    g = (yy - 100 * uu - 208 * vv + 128) >> 8
    b = (yy + 516 * uu + 128) >> 8
    rgb = xp.stack([r, g, b], axis=-1)
    return xp.clip(rgb, 0, 255)


def yuv420_to_rgb_host(flat: np.ndarray, h: int, w: int) -> np.ndarray:
    """(..., yuv420_frame_bytes) uint8 -> (..., h, w, 3) uint8 on host."""
    y, u, v = _split_planes(np.asarray(flat), h, w)
    up = np.repeat(np.repeat(u, 2, axis=-2), 2, axis=-1)[..., :h, :w]
    vp = np.repeat(np.repeat(v, 2, axis=-2), 2, axis=-1)[..., :h, :w]
    out = _combine(y.astype(np.int32), up.astype(np.int32),
                   vp.astype(np.int32), np)
    return out.astype(np.uint8)


@functools.lru_cache(maxsize=16)
def _device_converter(h: int, w: int):
    import jax
    import jax.numpy as jnp

    from .pallas_ops import LANES, SUBLANES, yuv420_planes

    # the kernel's geometry: chroma rows pair up along lanes, so H a
    # multiple of 4 and W of 128; anything else is padded to that and
    # cropped (1080p is neither)
    hp, wp = -(-h // 4) * 4, -(-w // LANES) * LANES
    lines = -(-(hp // 4) // SUBLANES) * SUBLANES

    def pad(plane, *shape):
        """Zeros after every dim but the first, up to `shape`."""
        return jnp.pad(plane, [(0, 0)] + [
            (0, to - at) for at, to in zip(plane.shape[1:], shape)])

    def paired(plane):
        # (n, hp / 2, wp / 2) chroma is (n, hp / 4, wp) in the same
        # bytes.  Padded to whole 8-line tiles while still flat, the
        # reshape compiles to the relayout copy the luma's does; cut at
        # 1080p's 270 lines it took the compiler 10 s a chunk length
        flat = pad(plane, hp // 2, wp // 2).reshape(len(plane), -1)
        return pad(flat, lines * wp).reshape(len(plane), lines, wp)

    @jax.named_scope("yuv420_to_rgb")
    def convert(flat):
        lead = flat.shape[:-1]
        y, u, v = _split_planes(flat.reshape(-1, flat.shape[-1]), h, w)
        planes = yuv420_planes(pad(y, hp, wp), paired(u), paired(v))
        # (n, 3, h, w) row-major is the device's own layout of
        # (n, h, w, 3) uint8: the transpose moves nothing
        return planes[:, :, :h, :w].transpose(0, 2, 3, 1).reshape(
            *lead, h, w, 3)

    return jax.jit(convert)


def yuv420_to_rgb_device(flat, h: int, w: int):
    """(..., yuv420_frame_bytes) uint8 -> (..., h, w, 3) uint8 as a
    jit-compiled device op (cached per geometry)."""
    return _device_converter(int(h), int(w))(flat)
