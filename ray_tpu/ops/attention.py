"""Attention ops.

Single source of truth for the dense (fully local) attention used by the
transformer, by ulysses_attention's inner computation, and by tests.
Accumulates scores and the probs@V contraction in f32 regardless of the
compute dtype (bf16 on TPU) via preferred_element_type.
"""

from __future__ import annotations

from typing import Optional


def gqa_scores(q, k, scale):
    """Scores [B, Hq, Tq, Tk] (f32) for MHA or GQA inputs.

    q [B,Tq,Hq,D], k [B,Tk,Hkv,D] with Hkv | Hq. GQA contracts via a
    grouped einsum — K is never materialized at Hq width. Head order
    convention: q head h attends to kv head h // (Hq//Hkv), i.e. query
    heads are contiguous per kv group (same as jnp.repeat on axis 2).
    """
    import jax.numpy as jnp

    b, tq, hq, d = q.shape
    hkv, tk = k.shape[2], k.shape[1]
    if hq == hkv:
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32) * scale
    if hq % hkv:
        raise ValueError(
            f"GQA needs kv heads ({hkv}) to divide query heads ({hq})")
    rep = hq // hkv
    qg = q.reshape(b, tq, hkv, rep, d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    return s.reshape(b, hq, tq, tk)


def gqa_pv(p, v):
    """probs [B, Hq, Tq, Tk] @ v [B, Tk, Hkv, D] -> [B, Tq, Hq, D] (f32
    accumulation), grouped for GQA like gqa_scores."""
    import jax.numpy as jnp

    b, hq, tq, tk = p.shape
    hkv, d = v.shape[2], v.shape[3]
    if hq == hkv:
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32)
    rep = hq // hkv
    pg = p.reshape(b, hkv, rep, tq, tk)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", pg, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, tq, hq, d)


def dense_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None):
    """Multi-head / grouped-query attention on [batch, seq, heads,
    head_dim] arrays; k/v may carry fewer (kv) heads than q."""
    import jax
    import jax.numpy as jnp

    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = gqa_scores(q, k, scale)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return gqa_pv(p, v).astype(q.dtype)


def flash_shape_ok(t: int, head_dim: int) -> bool:
    """Whether the pallas TPU flash kernel can tile this shape: seq in
    blocks of >=128, head_dim on the lane dim."""
    return t >= 128 and t % 128 == 0 and head_dim % 64 == 0


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None):
    """Fused flash attention on [batch, seq, heads, head_dim]: the
    pallas TPU flash kernel (O(T) memory — never materializes the
    [B,H,T,T] score matrix), f32 accumulation inside the kernel.

    Raises ValueError for a shape the kernel cannot tile; off the TPU
    the pallas lowering itself refuses. There is no dense fallback —
    callers that want one say attention_impl="auto" or "dense".
    """
    import jax.numpy as jnp

    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, t, h, d = q.shape
    if not flash_shape_ok(t, d):
        raise ValueError(
            f"flash attention needs seq a multiple of 128 and head_dim a "
            f"multiple of 64, got seq={t}, head_dim={d}; use "
            f"attention_impl='auto' or 'dense' for this shape")
    if k.shape[2] != h:
        # the pallas kernel wants equal head counts; materialize the
        # GQA repeat only on this (single-device-local) path
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as _pallas_flash)

    # largest block <=512 that divides t (the kernel requires exact
    # divisibility; flash_shape_ok guarantees t % 128 == 0)
    blk = next(b for b in (512, 256, 128) if t % b == 0)
    sizes = BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
    # kernel layout is [B, H, T, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _pallas_flash(qt, kt, vt, causal=causal, sm_scale=scale,
                      block_sizes=sizes)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)
