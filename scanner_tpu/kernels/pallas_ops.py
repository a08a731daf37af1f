"""Pallas TPU kernels for hot ops.

The stdlib ops default to plain XLA; a hand-written kernel stays only
where a measurement on the chip says it beats XLA's own lowering.  The
histogram is that case (PERF.md §6, PR 32: one 16 x 1080p packet on a
v5e).

Kernels run under `interpret=True` on CPU (tests) and compile natively on
TPU.  The histogram's operand stays uint8 in HBM, channel-planar as the
wire converter leaves it; a grid step widens, bins, compares and counts
one block of rows in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_PIXELS = 128 * 1024  # uint8 pixels a grid step (lanes padded to 128)


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


def on_tpu() -> bool:
    # default_backend, not devices()[0]: a platform probe must not
    # look like a chip pin (scanner-check SC106 device-affinity lint)
    return jax.default_backend() == "tpu"
