"""Ring attention: exact attention over sequences sharded across devices.

The reference has no in-engine attention (SURVEY §5: "no ring attention /
Ulysses — no tensor compute exists in-engine"); its long-context machinery is
stencil/warmup/slice scheduling.  The TPU build adds model kernels, so
long-sequence attention becomes first-class: K/V blocks rotate around the
`sp` mesh axis via jax.lax.ppermute (ICI neighbor exchange) while each
device keeps flash-style online-softmax accumulators for its local queries —
memory O(T/n) per device, exact results (Liu et al., Ring Attention with
Blockwise Transformers).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# single source of truth: the pallas kernel's masked-row guards compare
# the m carry this module initializes against the same sentinel
from ..kernels.pallas_attention import NEG_INF


def _flash_block_k(tl: int, block_k: Optional[int]) -> int:
    """Largest divisor of the local block length ≤ the requested tile."""
    if block_k is not None and block_k < 1:
        raise ValueError(f"block_k must be >= 1, got {block_k}")
    want = min(tl, block_k or 512)
    while tl % want:
        want -= 1
    return want


def _ring_attention_block(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float],
                          block_k: Optional[int] = None):
    """Local computation: q,k,v are (B, Tl, H, D) blocks of a sequence
    sharded over axis_name.

    Flash-style tiling inside the ring rotation: each arriving K/V block
    is consumed in `block_k`-wide tiles, so the logits intermediate is
    (B, H, Tl, block_k) instead of (B, Tl, Tl) per step — the long-T
    memory bound that makes ring attention worthwhile in the first
    place."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    s = scale if scale is not None else (D ** -0.5)
    qf = q.astype(jnp.float32) * s
    bk = _flash_block_k(Tl, block_k)
    n_tiles = Tl // bk

    # accumulators: running max m, normalizer l, weighted value sum acc.
    # pcast marks them device-varying over the ring axis so the fori_loop
    # carry types match (shard_map vma tracking).
    vary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    m0 = vary(jnp.full((B, H, Tl), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((B, H, Tl), jnp.float32))
    acc0 = vary(jnp.zeros((B, H, Tl, D), jnp.float32))

    q_pos = idx * Tl + jnp.arange(Tl)

    def tile_update(m, l, acc, ks, vs, k_pos):
        """Online-softmax update for one (B, bk, H, D) K/V tile."""
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, ks.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # guard fully-masked rows (m_new == NEG_INF) against NaNs
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        correction = jnp.where(m <= NEG_INF / 2, 0.0,
                               jnp.exp(m - m_safe))
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vs.astype(jnp.float32))
        return m_new, l_new, acc_new

    def step(i, carry):
        m, l, acc, kb, vb = carry
        # the block arriving at step i originated on device (idx + i) % n
        src = (idx + i) % n
        # double-buffer: issue the rotation FIRST — the tile loop only
        # reads the current buffers, so XLA can run the ICI transfer
        # concurrently with this step's compute
        perm = [(j, (j - 1) % n) for j in range(n)]
        kb_next = jax.lax.ppermute(kb, axis_name, perm)
        vb_next = jax.lax.ppermute(vb, axis_name, perm)

        def tile(j, inner):
            m, l, acc = inner
            ks = jax.lax.dynamic_slice_in_dim(kb, j * bk, bk, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vb, j * bk, bk, axis=1)
            k_pos = src * Tl + j * bk + jnp.arange(bk)
            return tile_update(m, l, acc, ks, vs, k_pos)

        m, l, acc = jax.lax.fori_loop(0, n_tiles, tile, (m, l, acc))
        return m, l, acc, kb_next, vb_next

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,Tl,H,D)


def _ring_attention_block_pallas(q, k, v, axis_name: str, causal: bool,
                                 scale: Optional[float],
                                 block_q: Optional[int] = None,
                                 block_k: Optional[int] = None,
                                 interpret: bool = False):
    """Pallas variant of the local ring step: each arriving K/V block is
    consumed by ONE fused flash kernel (kernels/pallas_attention.py) —
    logits stay in VMEM, the online-softmax update fuses with both MXU
    matmuls.  Exactness is identical to the XLA path."""
    from ..kernels.pallas_attention import flash_block_update
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    s = scale if scale is not None else (D ** -0.5)
    # (B, Tl, H, D) -> (B*H, Tl, D): per-head rows for the kernel grid
    qf = jnp.transpose(q.astype(jnp.float32) * s, (0, 2, 1, 3)) \
        .reshape(B * H, Tl, D)

    vary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    m0 = vary(jnp.full((B * H, Tl), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((B * H, Tl), jnp.float32))
    acc0 = vary(jnp.zeros((B * H, Tl, D), jnp.float32))
    q_off = idx * Tl
    bq = block_q or 256
    bk = block_k or 256

    # the ring is unrolled (n is a static mesh size): each iteration is
    # one pallas call + one ppermute, and unrolling sidesteps a jax
    # lowering-cache bug with interpret-mode pallas inside fori_loop
    m, l, acc, kb, vb = m0, l0, acc0, k, v
    perm = [(j, (j - 1) % n) for j in range(n)]
    for i in range(n):
        src = (idx + i) % n
        kb_next = jax.lax.ppermute(kb, axis_name, perm) if i < n - 1 \
            else kb
        vb_next = jax.lax.ppermute(vb, axis_name, perm) if i < n - 1 \
            else vb
        kf = jnp.transpose(kb, (0, 2, 1, 3)).reshape(B * H, Tl, D)
        vf = jnp.transpose(vb, (0, 2, 1, 3)).reshape(B * H, Tl, D)
        m, l, acc = flash_block_update(
            qf, kf, vf, m, l, acc, q_off, src * Tl, causal=causal,
            block_q=bq, block_k=bk, interpret=interpret,
            vma=(axis_name,))
        kb, vb = kb_next, vb_next
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(B, H, Tl, D)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,Tl,H,D)


def make_ring_attention(mesh: Mesh, axis: str = "sp", causal: bool = False,
                        scale: Optional[float] = None,
                        block_k: Optional[int] = None,
                        impl: str = "xla",
                        block_q: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Returns attn(q, k, v) over arrays (B, T, H, D) with T sharded on
    `axis` (batch replicated or dp-sharded orthogonally).  `block_k`
    bounds the flash tile width (default 512, clipped to the local
    block).

    impl="pallas" runs each ring step through the fused pallas flash
    kernel (forward only — the backward pass recomputes through the XLA
    path via custom_vjp, so gradients work identically); its tiles
    default to 256x256 (`block_q`/`block_k`), clipped to divisors of the
    local block.  `interpret` defaults to auto: native on TPU,
    interpreter elsewhere (tests)."""
    fn = functools.partial(_ring_attention_block, axis_name=axis,
                           causal=causal, scale=scale, block_k=block_k)
    specs = dict(in_specs=(P(None, axis), P(None, axis), P(None, axis)),
                 out_specs=P(None, axis))
    xla_sm = shard_map(fn, mesh=mesh, **specs)
    if impl == "xla":
        return xla_sm
    if impl != "pallas":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    for name, b in (("block_q", block_q), ("block_k", block_k)):
        if b is not None and b < 1:
            raise ValueError(f"{name} must be >= 1, got {b}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pfn = functools.partial(_ring_attention_block_pallas, axis_name=axis,
                            causal=causal, scale=scale, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    # check_vma=False: the pallas interpreter's internal dynamic_slices
    # don't propagate varying-axis types (jax asks for exactly this
    # workaround in its error); the XLA path keeps full vma checking
    pal_sm = shard_map(pfn, mesh=mesh, check_vma=False, **specs)

    @jax.custom_vjp
    def attn(q, k, v):
        return pal_sm(q, k, v)

    def fwd(q, k, v):
        return pal_sm(q, k, v), (q, k, v)

    def bwd(res, g):
        _, vjp = jax.vjp(xla_sm, *res)
        return vjp(g)

    attn.defvjp(fwd, bwd)
    return attn


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None):
    """Single-device exact attention for testing ring equivalence."""
    B, T, H, D = q.shape
    s = scale if scale is not None else (D ** -0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * s,
                        k.astype(jnp.float32))
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
