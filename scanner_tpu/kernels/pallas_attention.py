"""Pallas TPU flash-attention block kernel for ring attention.

The ring rotation (parallel/ring_attention.py) consumes one arriving K/V
block per step, updating online-softmax accumulators (m, l, acc) for the
local queries.  The XLA path tiles that update with fori_loop +
dynamic_slice; this kernel fuses one whole (q-block x kv-block) update
into a single pallas_call so logits never leave VMEM and the
exp/correction arithmetic fuses with the two MXU matmuls.

Layout (pallas guide): grid (BH, q_tiles, kv_tiles) with kv innermost;
q/k/v tiles (block, D) f32 in VMEM; m/l carries (1, block_q) — lane-major
vectors; acc (1, block_q, D).  The kv axis revisits the same output
block, initializing from the carry refs at kv==0 (flash accumulation).
Global q/k positions for causal masking arrive via scalar prefetch, so
the same compiled kernel serves every ring step (the k offset is a
traced value — the block's origin device changes per step).

No reference counterpart: the reference has no in-engine attention
(SURVEY §5); this is TPU-native long-context machinery.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(offs_ref,                      # SMEM (2,): q_off, k_off
                  q_ref, k_ref, v_ref,           # VMEM tiles
                  m_in_ref, l_in_ref, acc_in_ref,  # carries (previous step)
                  m_out_ref, l_out_ref, acc_out_ref,
                  *, causal: bool, block_q: int, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_out_ref[...] = m_in_ref[...]
        l_out_ref[...] = l_in_ref[...]
        acc_out_ref[...] = acc_in_ref[...]

    q = q_ref[0]                                  # (block_q, D) pre-scaled
    k = k_ref[0]                                  # (block_k, D)
    v = v_ref[0]                                  # (block_k, D)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (block_q, block_k)

    if causal:
        q_pos = offs_ref[0] + pl.program_id(1) * block_q \
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = offs_ref[1] + ki * block_k \
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)

    m_prev = m_out_ref[0, 0, :]                   # (block_q,)
    l_prev = l_out_ref[0, 0, :]
    m_blk = jnp.max(logits, axis=1)               # (block_q,)
    m_new = jnp.maximum(m_prev, m_blk)
    # fully-masked rows keep m == NEG_INF; exp against a zero pivot and
    # zero correction so they contribute nothing and produce no NaN/inf
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(logits - m_safe[:, None])         # (block_q, block_k)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                     jnp.exp(m_prev - m_safe))    # (block_q,)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (block_q, D)
    m_out_ref[0, 0, :] = m_new
    l_out_ref[0, 0, :] = l_new
    acc_out_ref[0, 0] = acc_out_ref[0, 0] * corr[:, None] + pv


def _block_size(tl: int, want: int) -> int:
    """Largest divisor of tl that is <= want."""
    if want < 1:
        raise ValueError(f"block size must be >= 1, got {want}")
    b = min(tl, want)
    while tl % b:
        b -= 1
    return b


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "vma"))
def flash_block_update(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       m: jnp.ndarray, l: jnp.ndarray, acc: jnp.ndarray,
                       q_off, k_off, *, causal: bool = False,
                       block_q: int = 256, block_k: int = 256,
                       interpret: bool = False,
                       vma: Optional[Tuple[str, ...]] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One flash update of online-softmax state with a K/V block.

    q: (BH, Tq, D) queries, ALREADY scaled by 1/sqrt(D).
    k, v: (BH, Tk, D) the arriving block.
    m, l: (BH, Tq) running max / normalizer;  acc: (BH, Tq, D).
    q_off, k_off: global positions of q[.,0] / k[.,0] (for causal masks);
    may be traced values (ring step index).
    vma: mesh axes the outputs vary over — required when called inside
    shard_map with vma checking (the ring path passes its sequence axis).
    Returns updated (m, l, acc) in float32.
    """
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = _block_size(Tq, block_q)
    bk = _block_size(Tk, block_k)
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    vkw = {} if vma is None else {"vma": frozenset(vma)}
    grid = (BH, Tq // bq, Tk // bk)
    kern = functools.partial(_flash_kernel, causal=causal,
                             block_q=bq, block_k=bk)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    m3 = m[:, None, :]        # (BH, 1, Tq): lane-major carry blocks
    l3 = l[:, None, :]
    acc4 = acc[:, None, :, :]  # (BH, 1, Tq, D)
    # index maps receive the scalar-prefetch ref as a trailing arg
    m_o, l_o, acc_o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, qi, ki, s: (b, qi, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, qi, ki, s: (b, ki, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, qi, ki, s: (b, ki, 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, qi, ki, s: (b, 0, qi)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, qi, ki, s: (b, 0, qi)),
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, qi, ki, s: (b, 0, qi, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq),
                             lambda b, qi, ki, s: (b, 0, qi)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, qi, ki, s: (b, 0, qi)),
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, qi, ki, s: (b, 0, qi, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32, **vkw),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32, **vkw),
            jax.ShapeDtypeStruct((BH, 1, Tq, D), jnp.float32, **vkw),
        ],
        interpret=interpret,
    )(offs, qf, kf, vf, m3, l3, acc4)
    return m_o[:, 0], l_o[:, 0], acc_o[:, 0]
