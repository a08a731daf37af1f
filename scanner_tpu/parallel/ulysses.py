"""Ulysses-style sequence parallelism: all-to-all head-sharded attention.

The complement of ring attention (`ring_attention.py`) for sequences
sharded across devices (the reference has neither — SURVEY §5: its
long-context machinery is stencil/warmup/slice scheduling; attention
enters with this framework's model kernels).  Where the ring rotates K/V
blocks around the `sp` axis (n steps of neighbor ICI traffic, memory
O(T/n)), Ulysses re-shards ONCE: an all-to-all converts the layout from
time-sharded/full-heads to head-sharded/full-time, each device runs
plain full attention for its head group, and a reverse all-to-all
restores the time sharding (DeepSpeed Ulysses, Jacobs et al. 2023).

Trade-offs, mapped to TPU:
* two all-to-alls per call (ICI-friendly single collective each) vs the
  ring's n ppermute steps — fewer, larger transfers;
* requires heads % axis_size == 0 and materializes the full (T, T)
  attention for H/n heads — the right regime is moderate T with spare
  head parallelism; ring wins at extreme T.

Both share the (B, T, H, D) contract and in/out shardings, so model code
(`TemporalBlock(attn_fn=...)`) can swap them freely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import reference_attention


def _ulysses_block(q, k, v, axis_name: str, causal: bool,
                   scale: Optional[float]):
    """Local computation: q,k,v are (B, Tl, H, D) time-blocks of a
    sequence sharded over axis_name."""
    n = jax.lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses attention needs heads ({H}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring attention otherwise")

    def to_heads(x):
        # (B, Tl, H, D) -> (B, T, H/n, D): give away head groups, gather
        # every device's time block — one tiled all-to-all over ICI
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    # full-T plain attention on the local head group — shared math with
    # the single-device path so masking/scaling can never diverge
    out = reference_attention(to_heads(q), to_heads(k), to_heads(v),
                              causal=causal, scale=scale)
    # reverse all-to-all: hand back time blocks, regather all heads
    return jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def make_ulysses_attention(mesh: Mesh, axis: str = "sp",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Returns attn(q, k, v) over (B, T, H, D) arrays with T sharded on
    `axis` — the same contract as make_ring_attention, interchangeable in
    TemporalBlock(attn_fn=...)."""
    fn = functools.partial(_ulysses_block, axis_name=axis, causal=causal,
                           scale=scale)
    return shard_map(fn, mesh=mesh,
                     in_specs=(P(None, axis), P(None, axis), P(None, axis)),
                     out_specs=P(None, axis))
