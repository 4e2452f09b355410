"""Attention ops.

Single source of truth for the dense (fully local) attention used by the
transformer, by ulysses_attention's inner computation, and by tests.
Accumulates scores and the probs@V contraction in f32 regardless of the
compute dtype (bf16 on TPU) via preferred_element_type.

`flash_attention` is the fused path on the TPU: JAX's pallas splash
attention (`jax.experimental.pallas.ops.tpu.splash_attention`) with a
causal mask the kernel knows, K and V kept at their kv-head width, and a
block geometry derived from the shape at trace time. One kernel library,
no option: `dense_attention` is the reference the tests hold it to.

Three masks, the same on both paths: causal, a causal window, and the
block-diffusion mask (`block_length` > 0; `block_visible` is its
predicate), the one that is not causal in the stream's order. Over a plain
stream it is causal by block: a position sees every block up to its own,
its own in both directions. Over a doubled stream (`noised` = L > 0: L
noised positions, then their L clean copies; arXiv 2503.09573) a noised
block sees itself in both directions and the clean blocks strictly before
it, a clean block the clean blocks up to itself, and nothing clean sees
anything noised: L^2 + L*block_length pairs of the 4 L^2.

Packed documents: `segment_ids` `[batch, seq]` int32 (one id a position,
each document one run of equal ids) join the causal mask or a window on
both paths: a query sees the keys of its own document and no others, so a
document's outputs are those of the document attended alone. They are
data: `dense_attention` compares them, `flash_attention` hands them to
the kernels as splash's `SegmentIds`, which mask inside a block and skip
none (`causal_block_pairs` counts what is computed all the same). None
hands the kernels no operand; the block-diffusion mask refuses them, and
ring and ulysses attention (parallel/) take none.

`block_visible` is that mask's definition: `dense_attention`'s predicate
and the tests' oracle. The splash kernels get another form of it. The
library calls a computed mask's function on every `[block_q,
block_kv_compute]` tile of every block the kernels run, full blocks too
(only a mask that comes as an array is exempt there), so each operation of
the predicate is VPU time an element beside the exponential.
`block_visible` is 44 of them with two divisions; the kernels' form
(`_visible_from_bounds`) is a few compares on what `_query_bounds` worked
out a query on the host and handed over as `q_sequence`, and
`tests/test_block_mask_predicate.py` holds it to the definition pair for
pair.
"""

from __future__ import annotations

import functools
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


def block_visible(q_ids, kv_ids, block_length: int, noised: int = 0):
    """The block-diffusion mask as a predicate of position ids (numpy or
    jax integer arrays that broadcast): whether query `q_ids` sees key
    `kv_ids`. The first `noised` positions of the stream are the noised
    copy of the ones behind them (0: a plain stream, every position
    clean); a position's block is its place in its own copy over
    `block_length`.

        noised query, noised key   the same block
        noised query, clean key    a block strictly before the query's
        clean query, clean key     a block up to the query's own
        clean query, noised key    never
    """
    q_clean, kv_clean = q_ids >= noised, kv_ids >= noised
    q_block = (q_ids - noised * q_clean) // block_length
    kv_block = (kv_ids - noised * kv_clean) // block_length
    return (q_clean & kv_clean & (kv_block <= q_block)) \
        | (~q_clean & kv_clean & (kv_block < q_block)) \
        | (~q_clean & ~kv_clean & (kv_block == q_block))


def _check_block_mask(t: int, block_length: int, noised: int, window: int):
    if block_length < 0 or noised and not block_length:
        raise ValueError("a noised copy needs a block_length above 0")
    if block_length and window:
        raise ValueError("the block-diffusion mask takes no window")
    if noised and (t != 2 * noised or noised % block_length):
        raise ValueError(
            f"a doubled stream is {noised} noised positions and their "
            f"{noised} clean copies in whole blocks of {block_length}, got "
            f"{t} positions")


def _check_segments(segment_ids, q, k, block_length: int):
    if segment_ids is None:
        return
    if block_length:
        raise ValueError("the block-diffusion mask takes no segment_ids: "
                         "packed documents under it are not taught yet")
    if segment_ids.shape != q.shape[:2] or k.shape[1] != q.shape[1]:
        raise ValueError(
            f"segment_ids {segment_ids.shape} are one id a position of "
            f"self-attention over {q.shape[:2]}")


def dense_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, window: int = 0,
                    block_length: int = 0, noised: int = 0,
                    segment_ids=None):
    """Multi-head / grouped-query attention on [batch, seq, heads,
    head_dim] arrays; k/v may carry fewer (kv) heads than q, and v
    another width than k. `window` > 0 (with `causal`): query i sees the
    keys j with i - j < window. `block_length` > 0: the block-diffusion
    mask (`block_visible`) in place of the causal one, over a doubled
    stream where `noised` > 0. `segment_ids` `[batch, seq]` int32 (packed
    documents): besides, a query sees the keys of its own document
    only."""
    import jax
    import jax.numpy as jnp

    if window and not causal:
        raise ValueError("a window is a causal window")
    _check_block_mask(q.shape[1], block_length, noised, window)
    _check_segments(segment_ids, q, k, block_length)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = gqa_scores(q, k, scale)
    if block_length:
        mask = block_visible(jnp.arange(q.shape[1])[:, None],
                             jnp.arange(k.shape[1])[None, :], block_length,
                             noised)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    elif causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        if window:
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return gqa_pv(p, v).astype(q.dtype)


def flash_shape_ok(t: int, head_dim: int) -> bool:
    """Whether `flash_attention`'s kernel (JAX's pallas splash attention)
    tiles this shape: the sequence in blocks of 128 or more, head_dim a
    multiple of 64. `tests/test_chip_compile.py` holds this against what
    the v5e compiler takes, T 128 to 8192 and head_dim 64, 128 and 256."""
    return t >= 128 and t % 128 == 0 and head_dim % 64 == 0


def _splash_block_sizes(t: int, head_dim: int):
    """The kernels' block geometry, a function of the shape seen at trace
    time and of nothing else. Fetched blocks (q and kv, both kernels): the
    largest of 1024 / 512 / 256 / 128 that divides T, from 512 down where
    head_dim is above 192 (a 1024-row block of 256 columns overruns the
    v5e's 16 MiB of scoped VMEM in the backward kernel, one of 192 with
    values of 128 fits). Compute sub-blocks: up to 512 columns of scores
    at a time in the forward kernel; the whole fetched block in the one
    backward kernel, which makes dK, dV and dQ in one pass over the scores
    (`use_fused_bwd_kernel`). The best of a sweep on a v5e at T = 4096,
    D = 128, heads 32/8 and 16/16 (PERF.md section 6, PR 32). A window
    keeps these blocks: smaller ones would skip more of a 512 window's
    keys, and the fused backward's dQ partials, one `[H, T, D]` f32 a kv
    block, would double (5.4 GB at T = 16,384, 40 heads of 64: PERF.md
    section 6, PR 43)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    largest = 1024 if head_dim <= 192 else 512
    block = next(b for b in (1024, 512, 256, 128)
                 if b <= largest and t % b == 0)
    return BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=min(block, 512),
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)


# What the forward kernel hands its backward, by `checkpoint_name`: the
# output [H, T, Dv] in the compute dtype and the logsumexp [H, T] in f32,
# one sequence each. A `jax.checkpoint` whose policy saves this name keeps
# both, and its backward pass does not run the forward kernel again
# (`Transformer._remat`).
FLASH_RESIDUALS = "flash_residuals"


_CLEAN = -2 ** 31   # int32's sign bit


def _query_bounds(t: int, block_length: int, noised: int):
    """What each of `t` queries sees under `block_visible`, one int32 a
    query (numpy, made once a shape on the host): everything in the
    predicate that is the query's alone. A query sees at most two runs of
    keys: the clean keys `[noised, noised + c)`, c = (b + 1) block_length
    for a clean query of block b and b block_length for a noised one, and,
    a noised query alone, its own noised block `[c, c + block_length)`:
    for it c is that block's start too. So c is all a query carries, with
    the sign bit set on a clean one (any `t` below 2^31 - block_length;
    no field of bits to overrun)."""
    import numpy as np

    ids = np.arange(t)
    clean = ids >= noised
    c = ((ids - noised * clean) // block_length + clean) * block_length
    return np.where(clean, c + _CLEAN, c).astype(np.int32)


def _visible_from_bounds(bounds, kv_ids, block_length: int, noised: int):
    """`block_visible` of the queries whose `_query_bounds` are `bounds`
    and the keys `kv_ids` (numpy or jax integer arrays that broadcast):
    the form the kernels evaluate, a tile at a time. An interval test is
    one unsigned compare, `kv - lo` as uint32 below the width. The clean
    run reads c under the sign bit; the noised block's test takes `bounds`
    as it is: with the sign bit set `kv - bounds` is 2^31 off every key,
    so a clean query fails it without a flag being looked at. No division,
    remainder or multiplication, six operations an element."""
    def u32(x):   # the same bits: no operation in the kernel
        return x.astype("uint32")

    return (u32(kv_ids - noised) < u32(bounds & ~_CLEAN)) \
        | (u32(kv_ids - bounds) < block_length)


@functools.lru_cache(maxsize=None)
def _block_diffusion_mask(t: int, block_length: int, noised: int):
    """`block_visible` over `t` positions as a splash mask the kernel
    computes: no `[t, t]` array on the host or the device (268 MB of
    booleans at 16,384), a block's emptiness read off the predicate over
    that block alone. The library evaluates a computed mask on every block
    it runs and not on the partial ones alone, so what it is handed is
    `_visible_from_bounds` with `_query_bounds` as the mask's
    `q_sequence`, which the library's block tables, `block_table` and the
    kernel all read through the one `mask_function`."""
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib)

    class BlockDiffusionMask(mask_lib._ComputableMask):
        def __init__(self):
            self.key = (t, block_length, noised)
            super().__init__(
                shape=(t, t), shard_count=1,
                mask_function=lambda bounds, kv_ids: _visible_from_bounds(
                    bounds, kv_ids, block_length, noised))
            self.q_sequence = _query_bounds(t, block_length, noised)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.key == other.key \
                and np.array_equal(self.q_sequence, other.q_sequence)

        def __hash__(self):
            return hash((type(self).__name__, self.key))

    return BlockDiffusionMask()


@functools.lru_cache(maxsize=None)
def block_table(t: int, head_dim: int, block_length: int, noised: int = 0):
    """What the block-diffusion mask leaves of `flash_attention`'s grid at
    this shape: the kernel blocks (`_splash_block_sizes`' q x kv) that
    hold a visible pair, how many of those hold a hidden one too (the
    partial blocks, computed whole and masked), a block's area in pairs,
    and the pairs the mask needs. The same reading of the mask the
    kernel's own table takes, block by block."""
    sizes = _splash_block_sizes(t, head_dim)
    mask = _block_diffusion_mask(t, block_length, noised)
    non_empty = partial = pairs = 0
    for qs in range(0, t, sizes.block_q):
        for ks in range(0, t, sizes.block_kv):
            seen = int(mask[slice(qs, qs + sizes.block_q),
                            slice(ks, ks + sizes.block_kv)].sum())
            pairs += seen
            non_empty += seen > 0
            partial += 0 < seen < sizes.block_q * sizes.block_kv
    return {"blocks": (t // sizes.block_q) * (t // sizes.block_kv),
            "non_empty": non_empty, "partial": partial,
            "block_pairs": sizes.block_q * sizes.block_kv,
            "pairs_needed": pairs}


def _splash_attention(q, k, v, *, causal: bool, scale: float,
                      window: int = 0, block_length: int = 0,
                      noised: int = 0, segment_ids=None,
                      interpret: bool = False):
    """`flash_attention`'s body; `interpret` runs the kernels in pallas
    interpret mode, which is how the CPU tests read their numerics."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask, FullMask, LocalMask, MultiHeadMask, SegmentIds,
        make_splash_mha)

    t, h, d = q.shape[1:]
    if not flash_shape_ok(t, d):
        raise ValueError(
            f"flash attention needs seq a multiple of 128 and head_dim a "
            f"multiple of 64, got seq={t}, head_dim={d}; use "
            f"attention_impl='auto' or 'dense' for this shape")
    if h % k.shape[2]:
        raise ValueError(f"GQA needs kv heads ({k.shape[2]}) to divide "
                         f"query heads ({h})")
    # built once per trace: the mask's block table is numpy, and the layer
    # stack that calls this is one lax.scan
    if window and not causal:
        raise ValueError("a window is a causal window")
    _check_block_mask(t, block_length, noised, window)
    _check_segments(segment_ids, q, k, block_length)
    if block_length:   # computed from indices; empty blocks are skipped
        head_mask = _block_diffusion_mask(t, block_length, noised)
    elif window:   # i - j < window and j <= i; blocks outside are skipped
        head_mask = LocalMask((t, t), (window - 1, 0), 0)
    else:
        head_mask = (CausalMask if causal else FullMask)((t, t))
    kernel = make_splash_mha(
        MultiHeadMask([head_mask] * h),
        block_sizes=_splash_block_sizes(t, d), head_shards=1,
        q_seq_shards=1, interpret=interpret,
        residual_checkpoint_name=FLASH_RESIDUALS)
    # the kernel takes no scale (folded into q, rounded once) and one
    # sequence [H, T, D] at a time; K and V keep their own head count,
    # q head i reading kv head i // (H // Hkv) as gqa_scores does
    scaled = (q.astype(jnp.float32) * scale).astype(q.dtype)
    operands = (jnp.swapaxes(scaled, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2))
    if segment_ids is None:
        o = jax.vmap(kernel)(*operands)
    else:
        # the kernels' own operand: a pair of one document passes, every
        # block the static mask keeps is still computed
        # (`causal_block_pairs`)
        ids = segment_ids.astype(jnp.int32)
        o = jax.vmap(lambda q, k, v, ids: kernel(
            q, k, v, segment_ids=SegmentIds(q=ids, kv=ids)))(*operands, ids)
    return jnp.swapaxes(o, 1, 2)


def causal_block_pairs(t: int, head_dim: int) -> int:
    """The (query, key) pairs `flash_attention`'s kernels compute a head
    under the causal mask at this shape: the area of the blocks
    (`_splash_block_sizes`) on and under the diagonal. Packed documents do
    not lessen it: `SegmentIds` mask inside a block and skip none, so what
    a document mask needs (the sum over documents of n (n + 1) / 2) over
    this is the share of the kernels' work that is read."""
    sizes = _splash_block_sizes(t, head_dim)
    blocks = t // sizes.block_q
    return blocks * (blocks + 1) // 2 * sizes.block_q * sizes.block_kv


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, window: int = 0,
                    block_length: int = 0, noised: int = 0,
                    segment_ids=None):
    """Fused attention on [batch, seq, heads, head_dim] (k, v may carry
    fewer heads, v another width than k): JAX's pallas splash attention
    kernels. O(T) memory (the
    [B, H, T, T] scores never exist), bf16 operands with f32 accumulation
    inside a kernel, blocks above the diagonal of a causal mask (and,
    with `window` > 0, blocks wholly before the window: `LocalMask`)
    skipped rather than computed and masked, K and V read at their own
    head count
    (no GQA repeat in HBM), one backward kernel for dQ, dK and dV. Block
    sizes follow from (T, head_dim): `_splash_block_sizes`. With
    `block_length` > 0 the block-diffusion mask (`block_visible`; a
    doubled stream where `noised` > 0), computed in the kernel: the
    blocks it leaves empty are skipped, those it cuts are computed whole
    (`block_table` counts both), and the kernel evaluates the predicate
    on every block it runs, a full one too (the library exempts none from
    a computed mask). So it is handed each query's bounds as `q_sequence`
    and a predicate of a few compares (`_block_diffusion_mask`), held to
    `block_visible`, the definition. `segment_ids` `[batch, seq]` int32
    (packed documents) reach the kernels as splash's `SegmentIds`: data,
    not shape, so other boundaries compile nothing; None hands the
    kernels no such operand.

    Raises ValueError for a shape the kernel cannot tile; off the TPU
    the pallas lowering itself refuses. There is no dense fallback:
    callers that want one say attention_impl="auto" or "dense".
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _splash_attention(q, k, v, causal=causal, scale=scale,
                             window=window, block_length=block_length,
                             noised=noised, segment_ids=segment_ids)
