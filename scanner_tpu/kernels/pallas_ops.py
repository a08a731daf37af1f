"""Pallas TPU kernels for hot ops.

The stdlib ops default to plain XLA; a hand-written kernel stays only
where a measurement on the chip says it beats XLA's own lowering.  The
histogram is that case (PERF.md §6, PR 32: one 16 x 1080p packet on a
v5e), and the wire conversion (PR 35: 0.57 ms a packet against 2.34 for
XLA's best and 7.09 for the int32 planes it replaced).

`histogram_frames` runs under `interpret=True` on CPU (tests) and
compiles natively on TPU; `yuv420_planes` picks by the platform it is
lowered for.  Operands stay uint8 in HBM, channel-planar: the converter
writes the layout the histogram reads.  A grid step widens one block of
rows in VMEM, works there, and narrows what it writes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_PIXELS = 128 * 1024  # uint8 pixels a grid step (lanes padded to 128)
YUV_BLOCK_ROWS = 128  # luma rows a grid step: 32 chroma lines, a uint8 tile


def _block_rows(h: int, w: int) -> int:
    """Rows of one grid step: about BLOCK_PIXELS pixels, a multiple of
    the uint8 tile's 32 rows, or the whole plane where it is smaller."""
    lanes = -(-w // LANES) * LANES
    rows = max(32, BLOCK_PIXELS // lanes // 32 * 32)
    return h if h <= rows else rows


def _hist_kernel(x_ref, out_ref, *, bins: int, h: int, rows: int):
    """One grid step: x_ref (rows, W) uint8, one block of one channel
    plane of one frame; out_ref (SUBLANES, LANES) int32, bin b's count
    in lane b of every sublane.

    Grid dim 2 walks the plane's rows revisiting the same out block:
    zero it on the first visit, accumulate after.  Where `rows` does not
    divide `h` the last block reads past the plane; its rows are masked
    to bin id `bins`, which counts nowhere."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = x_ref[...].astype(jnp.int32)
    # unsigned binning: a shift where bins divides 256, never a signed //
    if 256 % bins == 0:
        vals = vals >> (8 - (bins.bit_length() - 1))
    else:
        vals = (vals * bins) >> 8

    def _accumulate(vals):
        # compare+reduce per bin on the VPU; the static loop unrolls
        # into `bins` vectorized passes, no scatter
        lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        counts = jnp.zeros(out_ref.shape, jnp.int32)
        for b in range(bins):
            n = jnp.sum((vals == b).astype(jnp.int32))
            counts = jnp.where(lane == b, n, counts)
        out_ref[...] += counts

    if h % rows == 0:
        _accumulate(vals)
        return
    last = pl.num_programs(2) - 1

    @pl.when(k != last)
    def _full():
        _accumulate(vals)

    @pl.when(k == last)
    def _ragged():
        row = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0) + k * rows
        _accumulate(jnp.where(row < h, vals, bins))


@functools.partial(jax.jit, static_argnames=("bins", "interpret"))
@jax.named_scope("Histogram")
def histogram_frames(frames: jnp.ndarray, bins: int = 16,
                     interpret: bool = False) -> jnp.ndarray:
    """(B, H, W, C) uint8 -> (B, C, bins) int32: one program a batch
    rung, in which nothing of the packet's size is wider than uint8
    outside VMEM.

    The kernel walks (B, C, H, W): on a TPU that is the layout the wire
    converter's output already has, so the transpose moves nothing
    (a uint8 copy otherwise).  Frame, channel and row block are grid
    axes: the executable is the same size for every B."""
    if not 0 < bins <= LANES:
        raise ValueError(f"bins must be in 1..{LANES}")
    b, h, w, c = frames.shape
    rows = _block_rows(h, w)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bins=bins, h=h, rows=rows),
        out_shape=jax.ShapeDtypeStruct((b, c, SUBLANES, LANES), jnp.int32),
        grid=(b, c, pl.cdiv(h, rows)),
        in_specs=[pl.BlockSpec((None, None, rows, w),
                               lambda i, j, k: (i, j, k, 0))],
        out_specs=pl.BlockSpec((None, None, SUBLANES, LANES),
                               lambda i, j, k: (i, j, 0, 0)),
        interpret=interpret,
    )(frames.transpose(0, 3, 1, 2))
    return out[:, :, 0, :bins]


def _yuv_kernel(y_ref, u_ref, v_ref, out_ref):
    """One grid step: y_ref (rows, W) uint8, a block of one frame's
    luma; u_ref, v_ref (rows / 4, W) uint8, the chroma rows that block
    shares, two to a line (chroma row 2k in lanes [0, W/2), row 2k + 1
    beside it); out_ref (3, rows, W) uint8, the R, G and B planes.

    BT.601 limited range in 8-bit fixed point, the arithmetic at the top
    of `color.py`, carried in float32: every term is an integer under
    2**24, so each product and sum is exact, and clamping before the
    division by 256 truncates to what the arithmetic shift floors to.
    The 2x nearest upsample is two 0/1 selections on the MXU, exact in
    bfloat16 for chroma - 128 in [-128, 127]: `sel` doubles 128 lanes
    into 256 (which also carries the second chroma row of a line from
    lane W/2 to lane W, a vreg boundary where W/2 is none), `dup` hands
    luma row i its chroma row i // 2."""
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    rows, w = y_ref.shape
    quarter = u_ref.shape[0]
    iota = jax.lax.broadcasted_iota
    sel = (iota(i32, (LANES, 2 * LANES), 1) >> 1
           == iota(i32, (LANES, 2 * LANES), 0)).astype(bf16)
    i = iota(i32, (rows, 2 * quarter), 0)
    dup = ((i >> 2) + quarter * ((i >> 1) & 1)
           == iota(i32, (rows, 2 * quarter), 1)).astype(bf16)

    def upsample(c_ref):
        c = (c_ref[...].astype(i32) - 128).astype(f32).astype(bf16)
        wide = jnp.concatenate(
            [jnp.dot(c[:, k:k + LANES], sel, preferred_element_type=f32)
             for k in range(0, w, LANES)], axis=1)
        pair = jnp.concatenate([wide[:, :w], wide[:, w:]], axis=0)
        return jnp.dot(dup, pair.astype(bf16), preferred_element_type=f32)

    uu, vv = upsample(u_ref), upsample(v_ref)
    yy = y_ref[...].astype(i32).astype(f32) * 298.0 - (298.0 * 16 - 128)

    def narrow(x):
        x = jnp.minimum(jnp.maximum(x, 0.0), 65535.0) * (1.0 / 256.0)
        return x.astype(i32).astype(jnp.uint8)

    out_ref[0] = narrow(yy + 409.0 * vv)
    out_ref[1] = narrow(yy - 100.0 * uu - 208.0 * vv)
    out_ref[2] = narrow(yy + 516.0 * uu)


def yuv420_planes(y: jnp.ndarray, u: jnp.ndarray,
                  v: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W) luma and (B, >= H / 4, W) paired chroma, uint8 -> (B, 3,
    H, W) uint8 RGB planes; H a multiple of 4, W of 128 (`color.py` pads
    other geometries to that).  Frame and row block are grid axes: a
    packet costs what its rows cost, whatever their number.  Native
    where the program is lowered for a TPU, interpreted elsewhere (the
    CPU's tests run the same kernel)."""
    b, h, w = y.shape
    rows = YUV_BLOCK_ROWS

    def call(interpret):
        return pl.pallas_call(
            _yuv_kernel,
            out_shape=jax.ShapeDtypeStruct((b, 3, h, w), jnp.uint8),
            grid=(b, pl.cdiv(h, rows)),
            in_specs=[
                pl.BlockSpec((None, rows, w), lambda i, k: (i, k, 0)),
                pl.BlockSpec((None, rows // 4, w), lambda i, k: (i, k, 0)),
                pl.BlockSpec((None, rows // 4, w), lambda i, k: (i, k, 0))],
            out_specs=pl.BlockSpec((None, 3, rows, w),
                                   lambda i, k: (i, 0, k, 0)),
            interpret=interpret, name="yuv420_planes")

    return jax.lax.platform_dependent(y, u, v, tpu=call(False),
                                      default=call(True))


def on_tpu() -> bool:
    # default_backend, not devices()[0]: a platform probe must not
    # look like a chip pin (scanner-check SC106 device-affinity lint)
    return jax.default_backend() == "tpu"
