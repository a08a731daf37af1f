"""Pallas TPU kernels for hot ops.

The stdlib ops default to plain XLA (which fuses well); these hand-written
kernels exist where XLA's lowering leaves throughput on the table.  The
histogram is the flagship case: bincount lowers to sort/segment machinery,
while the VPU can do compare+reduce entirely in VMEM.

Kernels run under `interpret=True` on CPU (tests) and compile natively on
TPU.  Layout follows the pallas guide: last dim 128 lanes, f32/i32 tiles
(8, 128), grid accumulation over the pixel axis with @pl.when init.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
PIX_BLOCK = 16384  # int32 pixels per grid step: 8*16384*4 = 512 KB VMEM


def _hist_kernel(vals_ref, out_ref, *, bins: int):
    """One grid step: vals_ref (SUBLANES, PIX_BLOCK) int32 bin indices,
    out_ref (SUBLANES, LANES) int32 counts (bins <= LANES, rest padding).

    Grid dim 1 walks the pixel axis revisiting the same out block;
    accumulate with an explicit zero-init on the first visit."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    vals = vals_ref[:, :]
    # compare+reduce per bin on the VPU; static Python loop unrolls into
    # `bins` vectorized passes, no scatter
    cols = []
    for b in range(bins):
        cols.append(jnp.sum((vals == b).astype(jnp.int32), axis=1))
    counts = jnp.stack(cols, axis=1)  # (SUBLANES, bins)
    pad = jnp.zeros((counts.shape[0], LANES - bins), jnp.int32)
    out_ref[:, :] += jnp.concatenate([counts, pad], axis=1)


@functools.partial(jax.jit, static_argnames=("bins", "interpret"))
@jax.named_scope("Histogram")
def pallas_histogram(vals: jnp.ndarray, bins: int = 16,
                     interpret: bool = False) -> jnp.ndarray:
    """(R, P) int32 bin indices -> (R, bins) int32 counts.

    Rows are padded to a SUBLANES multiple and pixels to PIX_BLOCK; padding
    pixels carry bin id `bins` (out of range) so they count nowhere.
    """
    if bins > LANES:
        raise ValueError(f"bins must be <= {LANES}")
    R, P = vals.shape
    Rp = -(-R // SUBLANES) * SUBLANES
    Pp = -(-P // PIX_BLOCK) * PIX_BLOCK
    padded = jnp.full((Rp, Pp), bins, jnp.int32)
    padded = padded.at[:R, :P].set(vals)
    grid = (Rp // SUBLANES, Pp // PIX_BLOCK)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bins=bins),
        out_shape=jax.ShapeDtypeStruct((Rp, LANES), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((SUBLANES, PIX_BLOCK),
                               lambda r, p: (r, p))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda r, p: (r, 0)),
        interpret=interpret,
    )(padded)
    return out[:R, :bins]


def histogram_frames(frames: jnp.ndarray, bins: int = 16,
                     interpret: bool = False) -> jnp.ndarray:
    """(B, H, W, C) uint8 -> (B, C, bins) int32, pallas path."""
    b, c = frames.shape[0], frames.shape[-1]
    vals = (frames.astype(jnp.int32) * bins) // 256
    vals = vals.reshape(b, -1, c).transpose(0, 2, 1).reshape(b * c, -1)
    return pallas_histogram(vals, bins=bins,
                            interpret=interpret).reshape(b, c, bins)


def on_tpu() -> bool:
    # default_backend, not devices()[0]: a platform probe must not
    # look like a chip pin (scanner-check SC106 device-affinity lint)
    return jax.default_backend() == "tpu"
