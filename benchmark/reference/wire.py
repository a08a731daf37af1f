"""The YUV420 wire, converted the plain way.

A decoded frame travels to the device as planar I420: h*w luma bytes,
then the two (h/2)*(w/2) chroma planes.  Pipelines compute on the RGB
that the device makes of it: ITU-R BT.601 studio swing in the classic
8-bit fixed-point form, each chroma sample shared by its 2x2 luma block

    C = Y - 16, D = U - 128, E = V - 128
    R = clip((298 C         + 409 E + 128) >> 8)
    G = clip((298 C - 100 D - 208 E + 128) >> 8)
    B = clip((298 C + 516 D         + 128) >> 8)

Written from the standard's integer form; imports nothing of the program.
"""

import numpy as np


def wire_bytes(h, w):
    return h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2)


def planes(flat, h, w):
    ch, cw = (h + 1) // 2, (w + 1) // 2
    flat = np.asarray(flat)
    y = flat[:h * w].reshape(h, w)
    u = flat[h * w:h * w + ch * cw].reshape(ch, cw)
    v = flat[h * w + ch * cw:h * w + 2 * ch * cw].reshape(ch, cw)
    return y, u, v


def _up(p, h, w):
    return np.repeat(np.repeat(p, 2, axis=0), 2, axis=1)[:h, :w]


def to_rgb(flat, h, w, dtype=np.int32):
    """One flat I420 frame -> (h, w, 3) uint8.  `dtype` float16-like
    types are the lower-precision control: the same equations in
    floating point at that precision, rounded to nearest."""
    y, u, v = planes(flat, h, w)
    if np.issubdtype(dtype, np.integer):
        c = 298 * (y.astype(np.int32) - 16) + 128
        d = u.astype(np.int32) - 128
        e = v.astype(np.int32) - 128
        r = (c + _up(409 * e, h, w)) >> 8
        g = (c - _up(100 * d + 208 * e, h, w)) >> 8
        b = (c + _up(516 * d, h, w)) >> 8
    else:
        f = dtype
        c = (y.astype(f) - f(16)) * f(298 / 256)
        d = _up(u, h, w).astype(f) - f(128)
        e = _up(v, h, w).astype(f) - f(128)
        r = np.rint(c + f(409 / 256) * e)
        g = np.rint(c - f(100 / 256) * d - f(208 / 256) * e)
        b = np.rint(c + f(516 / 256) * d)
    return np.clip(np.stack([r, g, b], -1), 0, 255).astype(np.uint8)
