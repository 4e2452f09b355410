"""Linear attention whose state is corrected by a delta rule behind a
decay: Kimi Delta Attention (KDA, arXiv 2510.26692), a decay per channel,
and Gated DeltaNet (arXiv 2412.06464), one decay a head. One chunked
delta rule (`gated_delta_rule`) serves both: the gate's shape says which.

The reference has no linear attention of any kind; this fills that row
beside `ops/attention.py`, `ops/ssm.py` and `ops/moe.py`. A KDA sublayer
(`kda_mixer`), H heads of width D (keys and values alike), per token t and
head:

    q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
                                 depthwise, causal, no bias; then q and k
                                 L2-normed over the head's D
    log a_t = lower * sigmoid(exp(A) * (h W_a + b_a))   per channel, f32:
                                 a_t in (e^lower, 1)^D, `lower` = -5
    beta_t  = sigmoid(h w_beta)                          one scalar a head
    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = D^-1/2 S_t^T q_t                               S is D x D, f32
    y_t = rmsnorm(o_t) * gain * sigmoid(h w_g)           gate: a scalar a head
    out = concat_h(y_t) W_o

Mamba-2's decay is one scalar a head and Mamba-1's state has no key-key
interaction; here the state update is a rank-one correction behind a
diagonal decay, so a chunk does not unroll into one `[C, C]` block product.

A Gated DeltaNet sublayer (`gdn_mixer`) is the same recurrence with the
decay constant over a head's channels, keys of width D_k beside values of
another width D_v (the state `[D_k, D_v]`), beta up to 2 and a gate a
channel:

    q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
                                 q and k L2-normed over the head's D_k
    log a_t = -exp(A) * softplus(h w_a + dt)   ONE scalar a head, f32,
                                 at or below 0 and NOT bounded below
    beta_t  = 2 sigmoid(h w_beta)              in (0, 2) (`allow_neg_eigval`)
    S_t = (I - beta_t k_t k_t^T) a_t S_{t-1} + beta_t k_t v_t^T
    o_t = D_k^-1/2 S_t^T q_t
    y_t = rmsnorm(o_t) * gain * silu(h W_g)    gate: a factor a channel
    out = concat_h(y_t) W_o

**The delta rule in chunks** (`gated_delta_rule`; the WY / UT form). With
`G_r` the running sum of `log a` inside a chunk of C steps (the step r
included) and `S_0` the state entering it, `S_r = diag(e^{G_r}) S_0 +
sum_{i<=r} diag(e^{G_r - G_i}) k_i u_i^T` where the corrected values
`u_r = beta_r (v_r - S_{r-1}^T (a_r * k_r))` solve

    (I + diag(beta) tril(A, -1)) U = diag(beta) (V - (K * e^G) S_0),
    A_ri = sum_c k_rc k_ic e^{G_rc - G_ic}                    (i < r)

so a chunk needs the inverse of one unit lower-triangular `[C, C]` matrix
a head, `T`, made here by doubling: the strictly lower part N is
nilpotent, `(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...`, log2(C) - 1
squarings, all matmuls. `T diag(beta) V` and `T diag(beta) (K * e^G)` are
made for all chunks at once; a `lax.scan` over the chunks carries the
state and does four small products a chunk (a `jax.checkpoint` each: the
backward pass keeps a chunk's entering state, not its corrected values):

    U = T beta V - (T beta (K * e^G)) S_0
    O = D^-1/2 ((Q * e^G) S_0 + tril(B) U),   B_ri = sum_c q_rc k_ic e^{G_rc - G_ic}
    S_C = diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U

**The decays inside a chunk cannot be factored naively**: `(k * e^G)
(k * e^-G)^T` overflows float32 once a chunk's running log-decay passes
-88, and at the gate's bound of -5 a step that is the 18th step. A and B
are formed relative to sub-blocks of `SUB` = 16 steps: the row factor is
`e^{G_r - Gm}` with Gm the running sum at the MIDDLE of the row's own
sub-block, the column factor `e^{Gm - G_i}`: both exponents lie within
+-40 for a row and a column of one sub-block (8 x 5: the published bound
of -5 a step is there for this), the column's is below 0 for a column of
an earlier sub-block, and a later one is masked to nothing before the
exponential. The middle and not the start, where the range would be
(-80, 0] and [0, 80): a factor of e^-80 = 2e-35 times a cotangent of a
loss's size (1e-6) is flushed to zero, and with it a gradient whose true
weight `e^{G_r - G_i}` is of order 1; at +-40 a cotangent of 1e-20 still
passes. Every factor is finite, and so is every cotangent autodiff forms
from them.

**A decay a head needs none of that** (`_head_blocks`): the decays leave
the sums over the channels, `A = (K K^T) * L` and `B = (Q K^T) * L` with
`L_ri = e^{G_r - G_i}` one `[C, C]` matrix a head and chunk, masked to
i < r BEFORE the exponential and 1 on the diagonal (as
`ops/ssm.ssd_scan`'s): every exponent is at or below 0 whatever the gate,
so a gate of -30 a step is as safe as one of -0.01, and none of the
channel path's `[H, T/C, C/16, C, D]` float32 factors goes through HBM.
**Beta up to 2 changes the inverse** (`_unit_lower_inverse_by_halves`): the
strictly lower block the inverse amplifies is twice as large, and on keys
that share a direction (silu leaves them one) the power series' terms
grow like `C(C, j) |N|^j` before they cancel: in float32 it misses the
inverse by the inverse's own size, at any matmul precision. The gate a
head's path inverts by halves, `[[A, 0], [X, B]]^-1 = [[A^-1, 0],
[-B^-1 X A^-1, B^-1]]` six times for C = 64, which sums nothing larger
than the result (1e-7 of it in float32). What that costs: two batched
products a level of blocks 1 to 32 wide, 0.09M multiply-adds a chunk and
head where the series' ten `[64, 64]` products are 2.6M, but in twelve
small float32 products at the highest precision (six passes of the MXU
each) where the series has ten large ones; A, B and the inverse stay
float32 at the highest precision under either gate.

The decays, their running sums, A, B, the inverse and the state are
float32 (A, B and the inverse at the highest matmul precision: an error
there is amplified by the inverse); the products with `S`, `U` and `T`
take their operands in the compute dtype and accumulate in float32. A T
that is no whole chunks is padded at its end with steps that change
nothing (k = v = 0, beta = 0, no decay).

**Two implementations of the one algorithm**, chosen at trace time by
`kda_delta_impl` (no option, as `ops/ssm.ssd_scan_impl` chooses a scan's):

- `"xla"` (`gated_delta_rule`): the factors, blocks, inverses and the six
  per-chunk tensors go through HBM between every two passes (the column
  factors alone `[H, T/C, C/16, C, D]` float32), a `lax.scan` chains the
  chunks, autodiff makes the backward. What that backward is handed: the
  chain's six per-chunk tensors (`T beta V` float32, `T beta (K e^G)`,
  `Q e^G`, `tril(B)` and `K e^{G_C - G}` in the compute dtype, `e^{G_C}`),
  the state entering each chunk, and the chunked q, k, v, G and beta. The
  batched stage between those operands and the chain (A and B, the two
  masks, the chunks' inverses, the solve) is under one `jax.checkpoint`
  and made again in the backward, the same ops on the same operands.
  Kept, it is most of what autodiff holds (nine float32 `[H, T/C, C, C]`
  blocks, every level of the inverse five times, under a decay a channel
  the column factors three times: 1,190 MB a layer at Olmo-Hybrid's 8,192
  tokens of 15 heads against 562 without), and it is held across the rest
  of the layer at the step's peak, where that cell has under 1 GB of the
  chip's 16.91 to spare: the compiler then runs the layer's gate/up matmul
  and q/k/v projection a third time to fit (PERF.md section 6, PR 70). It
  runs on the CPU, on a mesh above one device (GSPMD cannot partition a
  pallas call) and for every shape the kernels do not tile, and it is the
  oracle of the kernels' tests.
- `"pallas"` (`gated_delta_rule_pallas`): on one TPU device, under a decay
  a channel, where the shapes tile (`delta_shape_ok`: chunks of 64, keys
  and values one lane tile wide, the heads in pairs; a decay a head, keys
  of 96 beside values of 192 or 15 heads take the XLA path). Grid
  `(batch, head blocks, chunks)`, the chunk axis sequential; a grid step
  takes one chunk of `DELTA_HEADS` heads. It reads q, k, v (compute dtype) and g (float32) as lane-dense
  `[64, heads·128]` blocks of the `[B, T, H·D]` layout the convolution
  and the gates leave (no `[B, H, T/C, C, D]` copy of any of them; beta
  `[B, T, H]`, 0.5 MB, is turned by XLA to two columns a pair of heads)
  and writes o `[B, T, H·D]` float32. Two heads share every tile: a
  pair's steps lie one above the other as `[128, 128]`, a head's
  `[64, 64]` blocks are the two diagonal blocks of a `[128, 128]` matrix
  (the MXU is that wide anyway, so a pair's key-key block, inverse and
  products cost what one head's would), and what falls between the heads
  is masked. In VMEM and never in HBM: the L2 norms, G (a float32 product
  with a 0/1 matrix at the highest precision), the row and column factors
  relative to the middles of the rows' sub-blocks (a column of a later
  sub-block masked before the exponential), A and B (four products of a
  sub-block's rows of q and k with k under its column factors, at the
  highest precision), the inverse by doubling (ten float32 products at
  the highest precision, a `fori_loop`), `T beta V`, `T beta (K e^G)`, U,
  and the carried state, transposed `[Dv, D]` float32 a head (a decay lies
  along its lanes) in a scratch that persists over the chunk axis. The
  forward (`kda_delta_fwd`) also writes the state entering each chunk,
  `[T/C, H·Dv, D]` float32, and each chunk's inverse, float32 as made, a
  pair's two `[64, 64]` diagonal blocks side by side: `[T/C, H/2·64,
  128]`. These two and o are what the rule's forward hands its backward,
  under one `checkpoint_name`, `DELTA_RESIDUALS`: a layer under
  `jax.checkpoint` whose policy saves that name (`Transformer._remat`'s)
  keeps all three from its forward pass, 201 + 33.5 MB a layer at 16,384
  tokens of 8 heads, and its backward pass runs neither the forward
  kernel a second time (the head norm's derivative reads the kept o) nor
  the doubling a third; under a policy that does not, remat's forward
  runs the kernel again and they live between it and the backward of
  the same layer. The backward (`kda_delta_bwd`, under `jax.custom_vjp`)
  walks the chunks in reverse with the state's cotangent carried in
  VMEM, reads a chunk's inverse where the forward made it (the same
  float32 array: the same bits), makes its factors, blocks and U again,
  and returns dq, dk, dv (compute dtype, through the L2 norms), dg and
  dbeta; the inverse's cotangent is
  `T^T dT T^T` on the strictly lower part (two products, no second
  solve), what reaches G through the factors' reference rows is added
  to those rows, and dg is the transpose of the running sum (one more
  product with the 0/1 matrix). Roundings as above, the same operands of
  the same products in the compute dtype; G's sums are added in the
  MXU's order and not `cumsum`'s, and the cotangents of the products with
  `S`, `U` and `T` are rounded to the compute dtype where autodiff leaves
  them float32 beside a rounded operand (the MXU rounds both either way).

**A share of the heads.** The sublayer is told its heads by the weights it
is given: the columns of the projections, the channels of the
convolutions and the rows of `W_o` that belong to some heads give that
share's part of the output projection's sum; the norm is per head, so it
stays local. No code stands in for absent heads.

Scopes (PERF.md section 3): `kda/qkv_proj`, `kda/conv`, `kda/gates`
(log a, beta and the output gate), `kda/delta` (the L2 norms, the chunk
inverses, the state), `kda/out_norm`, `kda/out_proj`; a Gated DeltaNet
sublayer's are the same six under `gdn/`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

from ray_tpu.ops.ssm import _one_tpu_device

# steps of a sub-block: SUB x |lower| stays under float32's e^88
SUB = 16
L2_EPS = 1e-6


def l2norm(x):
    """x / ||x|| over the last axis, in float32."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, w):
    """Depthwise causal convolution over time without a bias: x
    `[B, T, C]`, w `[C, K]` -> `y_t = sum_j w[:, j] x_{t-K+1+j}`."""
    import jax.numpy as jnp

    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = padded[:, :t] * w[:, 0].astype(x.dtype)
    for j in range(1, k):
        y = y + padded[:, j:j + t] * w[:, j].astype(x.dtype)
    return y


def log_decay(a, a_log, bias, lower: float):
    """The bounded gate: a `[B, T, H, D]` or `[B, T, H·D]`, a_log `[H]`,
    bias `[H, D]` -> `lower * sigmoid(exp(a_log) * (a + bias))`, float32,
    in (lower, 0), in a's layout."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rate = jnp.broadcast_to(jnp.exp(a_log.astype(f32))[:, None], bias.shape)
    return lower * jax.nn.sigmoid(
        rate.reshape(a.shape[2:])
        * (a.astype(f32) + bias.astype(f32).reshape(a.shape[2:])))


def head_norm(o, gate, gain, eps: float):
    """`rmsnorm(o_h) * gain * gate_h` a head: o `[B, T, H·D]`, gain `[D]`,
    gate `[B, T, H]` (a scalar a head, KDA's) or `[B, T, H·D]` (a gate a
    channel, Gated DeltaNet's), in float32. A head is a slice of the last
    axis, not a `[.., H, D]` reshape: on the TPU that shape has another
    tiling, and the compiler copied `[B, T, H·D]` into it and back in
    every pass (`ops/ssm.gated_norm` met the same; PERF.md section 6,
    PR 51)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d = gain.shape[-1]
    heads = o.shape[-1] // d
    per = gate.shape[-1] // heads        # 1: a head's scalar; d: a channel's
    gain = gain.astype(f32)
    runs = [o[..., h * d:(h + 1) * d].astype(f32) for h in range(heads)]
    return jnp.concatenate(
        [run * jax.lax.rsqrt(jnp.mean(run * run, axis=-1, keepdims=True)
                             + eps) * gain * gate[..., h * per:(h + 1) * per]
         for h, run in enumerate(runs)], axis=-1)


def _unit_lower_inverse(n):
    """`(I - n)^-1` of a strictly lower-triangular `[.., C, C]` n, by
    doubling: n^C = 0."""
    import jax
    import jax.numpy as jnp

    c = n.shape[-1]
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    inv = jnp.eye(c, dtype=n.dtype) + n
    power, reach = n, 2            # inv holds the powers below `reach`
    while reach < c:
        power = jnp.einsum("...ij,...jk->...ik", power, power, **exact)
        inv = inv + jnp.einsum("...ij,...jk->...ik", inv, power, **exact)
        reach *= 2
    return inv


def _unit_lower_inverse_by_halves(n):
    """`(I - n)^-1` of a strictly lower-triangular `[.., C, C]` n, C a
    power of two, by halves: `[[A, 0], [X, B]]^-1 = [[A^-1, 0], [-B^-1 X
    A^-1, B^-1]]`, the diagonal blocks' inverses doubled in size log2(C)
    times, two batched products a level. Every product is with an inverse
    already formed, as in a substitution, so nothing larger than the
    result's own entries is summed: `_unit_lower_inverse`'s powers of n
    grow like `C(C, j) |n|^j` before they cancel, which float32 survives
    for beta in (0, 1) and not for beta up to 2 on keys that share a
    direction (|n| about 0.3 a pair: the series misses the inverse by its
    whole size, this form by 1e-7)."""
    import jax
    import jax.numpy as jnp

    c = n.shape[-1]
    lead = n.shape[:-2]
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    inv = jnp.ones(lead + (c, 1, 1), n.dtype)     # the 1 x 1 blocks'
    size = 1
    while size < c:
        pairs = c // (2 * size)
        # the block under the diagonal of every pair of blocks: -X
        under = jnp.moveaxis(jnp.diagonal(
            n.reshape(lead + (pairs, 2 * size, pairs, 2 * size)),
            axis1=-4, axis2=-2), -1, -3)[..., size:, :size]
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = jnp.einsum("...ij,...jk->...ik", jnp.einsum(
            "...ij,...jk->...ik", b, under, **exact), a, **exact)
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([low, b], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _channel_blocks(qc, kc, cum):
    """A and B of every chunk under a decay a CHANNEL: `A_ri = sum_c k_rc
    k_ic e^{G_rc - G_ic}`, `B_ri` with q's row, both `[.., c, c]` float32
    and unmasked above the diagonal's sub-block; qc, kc, cum `[B, H, nc, c,
    D]`. The decays are formed relative to the middles of the rows'
    sub-blocks (module docstring)."""
    import jax
    import jax.numpy as jnp

    bsz, h, nc, c, d = cum.shape
    ns = c // SUB
    by_sub = cum.reshape(bsz, h, nc, ns, SUB, d)
    middle = by_sub[:, :, :, :, SUB // 2 - 1]        # Gm [B, H, nc, ns, D]
    row = jnp.exp(by_sub - middle[..., None, :])     # exponents in +-40
    rows = jnp.concatenate(                          # [.., ns, 2 SUB, D]
        [qc.reshape(by_sub.shape) * row, kc.reshape(by_sub.shape) * row],
        axis=4)
    # a column no later than the row's sub-block; masked before the
    # exponential
    reached = jnp.arange(c) < (jnp.arange(ns)[:, None] + 1) * SUB
    to_row = middle[..., None, :] - cum[:, :, :, None]   # [.., ns, c, D]
    cols = kc[:, :, :, None] * jnp.exp(
        jnp.where(reached[..., None], to_row, -jnp.inf))
    scores = jnp.einsum("...srd,...sid->...sri", rows, cols,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    return (scores[..., SUB:, :].reshape(bsz, h, nc, c, c),
            scores[..., :SUB, :].reshape(bsz, h, nc, c, c))


def _head_blocks(qc, kc, cum):
    """A and B of every chunk under ONE decay a head: the decays leave the
    sums, `A = (K K^T) * L`, `B = (Q K^T) * L` with `L_ri = e^{G_r - G_i}`
    one `[c, c]` matrix a head and chunk, masked to i < r BEFORE the
    exponential and 1 on the diagonal (as `ops/ssm.ssd_scan`'s masks
    first): never `e^G` times `e^-G`, whose
    second factor overflows float32 at the third step of a gate of -30 a
    step. None of the channel path's `[H, T/C, C/16, C, D]` factors is
    made. qc, kc `[B, H, nc, c, D]`, cum `[B, H, nc, c, 1]`."""
    import jax
    import jax.numpy as jnp

    c = cum.shape[3]
    since = cum - jnp.swapaxes(cum, -1, -2)          # G_r - G_i [.., c, c]
    # the diagonal is 1 whatever G: left out of the differences, or its
    # O(1) cotangent enters G's twice with unlike signs and what is left
    # of a gradient of e^-30 beside it is rounding
    decays = jnp.exp(jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), since,
                               -jnp.inf)) + jnp.eye(c, dtype=cum.dtype)
    scores = jnp.einsum("...rd,...id->...ri",
                        jnp.concatenate([qc, kc], axis=3), kc,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)  # [.., 2c, c]
    return scores[..., c:, :] * decays, scores[..., :c, :] * decays


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """`S_t = (I - beta_t k_t k_t^T) diag(e^{g_t}) S_{t-1} + beta_t k_t
    v_t^T`, `o_t = D^-1/2 S_t^T q_t` from a zero state, in chunks (module
    docstring): q, k `[B, T, H, D]` as the convolutions leave them (L2-
    normed here), v `[B, T, H, Dv]` (its dtype is the compute dtype; Dv
    need not be D), beta `[B, T, H]` in [0, 2], and g the float32
    log-decays, whose shape says which gate: `[B, T, H, D]` a decay a
    channel in (-5, 0] (KDA), `[B, T, H]` one decay a head, any value at
    or below 0 (Gated DeltaNet) -> o `[B, T, H, Dv]` float32."""
    import jax
    import jax.numpy as jnp

    f32, cdt = jnp.float32, v.dtype
    bsz, t, h, d = q.shape
    per_head = g.ndim == 3
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} steps is no whole sub-blocks "
                         f"of {SUB}")
    pad = -t % chunk
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g, beta = g.astype(f32), beta.astype(f32)
    if per_head:
        g = g[..., None]       # one channel, broadcast over the head's D
    if pad:   # steps that change nothing
        q, k, v, g, beta = (jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc, c = (t + pad) // chunk, chunk

    def chunks(a):   # [B, T, H, W] -> [B, H, nc, c, W]
        return jnp.moveaxis(a.reshape(bsz, nc, c, h, -1), 3, 1)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta[..., None])                     # [B, H, nc, c, 1]
    cum = jnp.cumsum(gc, axis=3)                     # G, the step included
    # ---- the batched stage, made again in the backward --------------
    @jax.checkpoint
    def batched(qc, kc, vc, cum, bc):
        # A and B under the chunk's decays
        a_mat, b_mat = (_head_blocks if per_head else _channel_blocks)(
            qc, kc, cum)
        lower = jnp.tril(jnp.ones((c, c), bool))
        b_mat = jnp.where(lower, b_mat, 0.0)
        a_mat = jnp.where(lower & ~jnp.eye(c, dtype=bool), a_mat, 0.0)
        # the chunk inverses, and what they make of V and K; beta up to 2
        # (the gate a head's model) outgrows the power series
        inv = (_unit_lower_inverse_by_halves if per_head
               else _unit_lower_inverse)(-bc * a_mat).astype(cdt)
        decayed = jnp.exp(cum)
        rhs = jnp.concatenate([vc.astype(f32), kc * decayed], -1) * bc
        solved = jnp.einsum("...ri,...iw->...rw", inv, rhs.astype(cdt),
                            preferred_element_type=f32)
        return b_mat.astype(cdt), solved, decayed

    b_mat, solved, decayed = batched(qc, kc, vc, cum, bc)
    dv = vc.shape[-1]
    to_end = jnp.exp(cum[:, :, :, -1:] - cum)        # e^{G_C - G}, <= 1
    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in (
        solved[..., :dv], solved[..., dv:].astype(cdt),
        (qc * decayed).astype(cdt), b_mat,
        (kc * to_end).astype(cdt), decayed[:, :, :, -1]))

    # ---- the chunks chained: the state carried ----------------------
    @jax.checkpoint
    def one_chunk(state, inp):
        t_v, t_k, q_in, b_in, k_out, decay = inp
        u = (t_v - jnp.einsum("bhck,bhkv->bhcv", t_k, state.astype(cdt),
                              preferred_element_type=f32)).astype(cdt)
        o = jnp.einsum("bhck,bhkv->bhcv", q_in, state.astype(cdt),
                       preferred_element_type=f32) \
            + jnp.einsum("bhci,bhiv->bhcv", b_in, u,
                         preferred_element_type=f32)
        state = decay[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, u, preferred_element_type=f32)
        return state, o

    _, o = jax.lax.scan(one_chunk, jnp.zeros((bsz, h, d, dv), f32),
                        per_chunk)                   # [nc, B, H, c, Dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(bsz, nc * c, h, dv)
    return o[:, :t] if pad else o


# ---- the delta rule as pallas TPU kernels -----------------------------------
# One grid step takes one chunk of DELTA_CHUNK steps and a block of heads,
# two heads to a `[128, 128]` tile: a pair's steps one above the other
# (head A's 64 rows, then head B's), every `[64, 64]` block of a head one
# of the two diagonal blocks of a `[128, 128]` matrix. The MXU is that wide
# anyway: a pair's products cost what one head's would.
DELTA_CHUNK = 64
DELTA_LANES = 128
DELTA_HEADS = 4          # heads a grid step takes (PERF.md section 6, PR 51)
# The `checkpoint_name` of what the forward kernel hands the backward one
# (`_delta_calls.rule_fwd`; module docstring), for a `jax.checkpoint`
# policy to save, as `ops.attention.FLASH_RESIDUALS` is. Only the kernels
# name anything: `gated_delta_rule` keeps what autodiff keeps.
DELTA_RESIDUALS = "delta_residuals"
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def delta_shape_ok(seq_len: int, heads: int, d_k: int, d_v: int,
                   chunk: int) -> bool:
    """Whether the kernels tile the delta rule: whole chunks of 64 steps,
    keys and values one lane tile wide, the heads in pairs."""
    return (chunk == DELTA_CHUNK and seq_len % chunk == 0
            and d_k == DELTA_LANES and d_v == DELTA_LANES
            and heads % 2 == 0)


def kda_delta_impl(mesh, seq_len: int, heads: int, d_k: int, d_v: int,
                   chunk: int, per_head: bool = False) -> str:
    """`"pallas"` (`gated_delta_rule_pallas`) where the program runs on one
    TPU device and the kernels tile the shapes (`delta_shape_ok`) under a
    decay a channel, else `"xla"` (`gated_delta_rule`: any platform, any
    length, either gate (`per_head`: one decay a head, which the kernels
    do not take), and GSPMD can partition it). Decided at trace time, as
    `ops/ssm.ssd_scan_impl` decides for a Mamba-2 scan."""
    return "pallas" if not per_head and _one_tpu_device(mesh) \
        and delta_shape_ok(seq_len, heads, d_k, d_v, chunk) else "xla"


def _dot(a, b, dims, exact: bool = False):
    """A product accumulated in float32; `exact`: float32 operands at the
    highest precision (A, B, the inverse, the running sums)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _pair(ref, p: int):
    """A pair's `[64, 2·128]` block of a `[B, T, H·D]` operand -> `[128,
    128]`, head A's steps above head B's."""
    import jax.numpy as jnp

    lo = 2 * p * DELTA_LANES
    return jnp.concatenate([ref[0, :, lo:lo + DELTA_LANES],
                            ref[0, :, lo + DELTA_LANES:lo + 2 * DELTA_LANES]],
                           axis=0)


def _unpair(ref, p: int, value):
    """`_pair` back: `[128, 128]` into a pair's block of an output."""
    lo, c = 2 * p * DELTA_LANES, DELTA_CHUNK
    ref[0, :, lo:lo + DELTA_LANES] = value[:c].astype(ref.dtype)
    ref[0, :, lo + DELTA_LANES:lo + 2 * DELTA_LANES] = \
        value[c:].astype(ref.dtype)


def _per_head(fn):
    """`fn(h, rows)` of a pair's two heads, one above the other: `rows`
    head h's steps of a `[128, ..]` value."""
    import jax.numpy as jnp

    c = DELTA_CHUNK
    return jnp.concatenate(
        [fn(h, slice(h * c, (h + 1) * c)) for h in range(2)], axis=0)


def _states_at(p: int):
    """Where the states `[Dv, D]` of pair p's two heads lie in a grid
    step's `[hb·Dv, D]`."""
    w = DELTA_LANES
    return [slice((2 * p + h) * w, (2 * p + h + 1) * w) for h in range(2)]


def _by_sub(of_head):
    """A pair's `[128, 128]` from its sub-blocks' rows: `of_head(h)` lists
    head h's `[SUB, 128]` pieces, first sub-block first."""
    import jax.numpy as jnp

    return jnp.concatenate(of_head(0) + of_head(1), axis=0)


def _per_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, g_scr, p: int,
               inv=None):
    """The part of a chunk that is independent of the state, for the pair
    p of a grid step's heads: the normed q and k, the running sums G (and
    the rows of them the factors refer to, read off `g_scr`), A and B
    relative to the middles of the rows' sub-blocks, the unit lower
    triangular inverse by doubling (or `inv`, the `[128, 128]` float32
    one the forward kernel made of these operands: the powers are then
    not formed), `T beta V` and `T beta (K e^G)`: a namespace of
    `[128, 128]` values of the pair."""
    import types

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    c, n, ns = DELTA_CHUNK, 2 * DELTA_CHUNK, DELTA_CHUNK // SUB
    cdt = v_ref.dtype
    ch = types.SimpleNamespace()
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    same = (row >= c) == (col >= c)          # both of one head
    ch.lower, ch.strict = same & (col <= row), same & (col < row)
    ch.beta = jnp.concatenate(
        [beta_ref[0, p, :, 0:1], beta_ref[0, p, :, 1:2]], axis=0)   # [128, 1]

    def unit(ref):
        x = _pair(ref, p).astype(f32)
        r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
        return x * r, r

    ch.q_unit, ch.q_r = unit(q_ref)
    ch.k, ch.k_r = unit(k_ref)
    ch.scale = DELTA_LANES ** -0.5
    ch.q = ch.q_unit * ch.scale
    ch.v = _pair(v_ref, p)
    # G: the running sum as a float32 product with a 0/1 matrix
    ch.cum = _dot(ch.lower.astype(f32), _pair(g_ref, p), _NN, exact=True)
    g_scr[...] = ch.cum
    ch.middle = [[g_scr[h * c + s * SUB + SUB // 2 - 1:
                        h * c + s * SUB + SUB // 2, :]
                  for s in range(ns)] for h in range(2)]             # [1, 128]
    last = [g_scr[h * c + c - 1:h * c + c, :] for h in range(2)]
    ch.decay = [jnp.exp(v) for v in last]        # a chunk's whole decay

    def over_heads(rows):    # a `[1, 128]` row a head, over its steps
        return _per_head(lambda h, _: jnp.broadcast_to(
            rows[h], (c, DELTA_LANES)))

    middles = _by_sub(lambda h: [
        jnp.broadcast_to(ch.middle[h][s], (SUB, DELTA_LANES))
        for s in range(ns)])
    ch.row = jnp.exp(ch.cum - middles)           # exponents in +-40
    ch.decayed = jnp.exp(ch.cum)
    ch.to_end = jnp.exp(over_heads(last) - ch.cum)                   # <= 1
    rq, rk = ch.q * ch.row, ch.k * ch.row
    step = row & (c - 1)                         # the step inside its head

    def columns(s):
        """The column factors of the rows' sub-block s and k under them:
        a column of a later sub-block is masked before the exponential."""
        factor = jnp.exp(jnp.where(
            step < (s + 1) * SUB,
            over_heads([ch.middle[h][s] for h in range(2)]) - ch.cum,
            -jnp.inf))
        return factor, ch.k * factor

    def rows_of(s):
        """Sub-block s's rows of q and k, both heads: `[4 SUB, 128]`."""
        return jnp.concatenate(
            [v[h * c + s * SUB:h * c + (s + 1) * SUB]
             for h in range(2) for v in (rq, rk)], axis=0)

    ch.columns, ch.rows_of = columns, rows_of
    scores = [_dot(rows_of(s), columns(s)[1], _NT, exact=True)
              for s in range(ns)]                # [4 SUB, 128] each
    ch.b_mat = jnp.where(ch.lower, _by_sub(lambda h: [
        sc[2 * h * SUB:(2 * h + 1) * SUB] for sc in scores]), 0.0)
    ch.a_mat = jnp.where(ch.strict, _by_sub(lambda h: [
        sc[(2 * h + 1) * SUB:(2 * h + 2) * SUB] for sc in scores]), 0.0)
    if inv is None:
        # (I - N)^-1 by doubling, N = -beta A: N^64 = 0
        nil = -ch.beta * ch.a_mat

        def double(_, carry):
            inv, power = carry       # the powers below `reach`, N^reach
            return (inv + _dot(inv, power, _NN, exact=True),
                    _dot(power, power, _NN, exact=True))

        inv, power = jax.lax.fori_loop(
            0, 4, double, ((row == col).astype(f32) + nil,
                           _dot(nil, nil, _NN, exact=True)))
        inv = inv + _dot(inv, power, _NN, exact=True)
    ch.inv = inv
    ch.inv_c = ch.inv.astype(cdt)
    ch.rhs = jnp.concatenate(
        [ch.v.astype(f32) * ch.beta, ch.k * ch.decayed * ch.beta],
        axis=1).astype(cdt)                                      # [128, 256]
    solved = _dot(ch.inv_c, ch.rhs, _NN)
    ch.t_v, ch.t_k = solved[:, :DELTA_LANES], \
        solved[:, DELTA_LANES:].astype(cdt)
    ch.q_in = (ch.q * ch.decayed).astype(cdt)
    ch.b_in = ch.b_mat.astype(cdt)
    ch.k_out = (ch.k * ch.to_end).astype(cdt)
    return ch


def _diagonal_blocks(inv):
    """A pair's `[128, 128]` inverse -> its heads' two `[64, 64]` blocks
    side by side, `[64, 128]`: what lies between the heads is zero."""
    import jax
    import jax.numpy as jnp

    c = DELTA_CHUNK
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    return jnp.where(lane < c, inv[:c], inv[c:])


def _block_diagonal(blocks):
    """`_diagonal_blocks` back: `[64, 128]` -> the pair's `[128, 128]`."""
    import jax
    import jax.numpy as jnp

    c = DELTA_CHUNK
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    return jnp.concatenate([jnp.where(lane < c, blocks, 0.0),
                            jnp.where(lane < c, 0.0, blocks)], axis=0)


def _corrected(ch, states):
    """U = T beta V - (T beta (K e^G)) S_0 of a pair, in the compute
    dtype: `states` the two heads' entering states `[Dv, D]`, rounded."""
    return _per_head(lambda h, rows: ch.t_v[rows] - _dot(
        ch.t_k[rows], states[h], _NT)).astype(states[0].dtype)


def _delta_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref,
                      inv_ref, state, g_scr, *, pairs: int):
    """One chunk of one block of heads: q, k, v, g `[64, hb·128]`, beta
    `[hb/2, 64, 2]` -> o `[64, hb·128]` float32, the states that entered
    the chunk, transposed `[hb·Dv, D]` (a decay lies along the lanes), and
    the chunk's inverses, float32 as made, a pair's two `[64, 64]` blocks
    side by side: `[hb/2·64, 128]`; `state` carries the states over the
    chunks."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    c, cdt = DELTA_CHUNK, v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for p in range(pairs):
        ch = _per_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, g_scr, p)
        inv_ref[0, 0, p * c:(p + 1) * c, :] = _diagonal_blocks(ch.inv)
        at = _states_at(p)
        entering = [state[at[h], :] for h in range(2)]
        for h in range(2):
            st_ref[0, 0, at[h], :] = entering[h]
        rounded = [s.astype(cdt) for s in entering]
        u = _corrected(ch, rounded)
        intra = _dot(ch.b_in, u, _NN)
        _unpair(o_ref, p, intra + _per_head(
            lambda h, rows: _dot(ch.q_in[rows], rounded[h], _NT)))
        for h in range(2):
            rows = slice(h * c, (h + 1) * c)
            state[at[h], :] = entering[h] * ch.decay[h] + _dot(
                u[rows], ch.k_out[rows], _TN)


def _delta_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, inv_ref,
                      do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      dstate, g_scr, *, pairs: int):
    """The same chunk going backward (the chunks last to first): besides
    the forward's operands the entering states, the inverses as the
    forward wrote them and do `[64, hb·128]` float32 -> dq, dk, dv, dg
    and dbeta `[hb/2, 64, 2]`. A chunk's factors and blocks are made
    again, its inverse is read; `dstate` carries the cotangent of the
    state a chunk hands on. The inverse's cotangent is `T^T dT T^T` on
    the strictly lower part."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    c, w, ns = DELTA_CHUNK, DELTA_LANES, DELTA_CHUNK // SUB
    cdt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for p in range(pairs):
        ch = _per_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, g_scr, p,
                        _block_diagonal(inv_ref[0, 0, p * c:(p + 1) * c, :]))
        at = _states_at(p)
        entering = [st_ref[0, 0, at[h], :] for h in range(2)]
        rounded = [s.astype(cdt) for s in entering]
        u = _corrected(ch, rounded)
        d_o = _pair(do_ref, p).astype(cdt)
        d_out = [dstate[at[h], :] for h in range(2)]
        d_out_c = [d.astype(cdt) for d in d_out]
        # ---- the four products of the chain -------------------------
        d_u = _dot(ch.b_in, d_o, _TN) + _per_head(
            lambda h, rows: _dot(ch.k_out[rows], d_out_c[h], _NT))
        d_u_c = d_u.astype(cdt)
        d_b = jnp.where(ch.lower, _dot(d_o, u, _NT), 0.0)
        d_q_in = _per_head(lambda h, rows: _dot(d_o[rows], rounded[h], _NN))
        d_t_k = -_per_head(lambda h, rows: _dot(d_u_c[rows], rounded[h], _NN))
        d_k_out = _per_head(lambda h, rows: _dot(u[rows], d_out_c[h], _NN))
        d_last = []
        for h in range(2):
            rows = slice(h * c, (h + 1) * c)
            d_decay = jnp.sum(d_out[h] * entering[h], axis=0, keepdims=True)
            d_last.append(d_decay * ch.decay[h])
            dstate[at[h], :] = d_out[h] * ch.decay[h] \
                + _dot(d_o[rows], ch.q_in[rows], _TN) \
                - _dot(d_u_c[rows], ch.t_k[rows], _TN)
        # ---- T beta [V | K e^G], and the inverse --------------------
        d_solved = jnp.concatenate([d_u_c, d_t_k.astype(cdt)], axis=1)
        d_rhs = _dot(ch.inv_c, d_solved, _TN)                    # [128, 256]
        d_inv = _dot(d_solved, ch.rhs, _NT)
        d_nil = jnp.where(ch.strict, _dot(
            _dot(ch.inv, d_inv, _TN, exact=True), ch.inv, _NT, exact=True),
            0.0)
        d_a = -ch.beta * d_nil
        d_rhs_v, d_rhs_k = d_rhs[:, :w], d_rhs[:, w:]
        d_beta = jnp.sum(
            d_rhs_v * ch.v.astype(f32) + d_rhs_k * ch.k * ch.decayed
            - d_nil * ch.a_mat, axis=1, keepdims=True)
        # ---- A and B: the rows' and the columns' factors ------------
        d_k = d_rhs_k * ch.decayed * ch.beta + d_k_out * ch.to_end
        through_end = d_k_out * ch.k * ch.to_end
        d_cum = (d_q_in * ch.q + d_rhs_k * ch.k * ch.beta) * ch.decayed \
            - through_end
        d_middle = [[None] * ns for _ in range(2)]
        d_rows = []
        for s in range(ns):
            d_scores = jnp.concatenate(
                [m[h * c + s * SUB:h * c + (s + 1) * SUB]
                 for h in range(2) for m in (d_b, d_a)], axis=0)
            factor, under = ch.columns(s)
            d_rows.append(_dot(d_scores, under, _NN, exact=True))
            d_under = _dot(d_scores, ch.rows_of(s), _TN, exact=True)
            d_k = d_k + d_under * factor
            through = d_under * under
            d_cum = d_cum - through
            for h in range(2):
                d_middle[h][s] = jnp.sum(through[h * c:(h + 1) * c], axis=0,
                                         keepdims=True)
        d_rq = _by_sub(lambda h: [
            d[2 * h * SUB:(2 * h + 1) * SUB] for d in d_rows])
        d_rk = _by_sub(lambda h: [
            d[(2 * h + 1) * SUB:(2 * h + 2) * SUB] for d in d_rows])
        through_row = (d_rq * ch.q + d_rk * ch.k) * ch.row
        d_cum = d_cum + through_row
        d_q = d_rq * ch.row + d_q_in * ch.decayed
        d_k = d_k + d_rk * ch.row
        # what reaches G through the rows the factors refer to
        g_scr[...] = d_cum
        for h in range(2):
            for s in range(ns):
                lo = h * c + s * SUB
                at_mid = slice(lo + SUB // 2 - 1, lo + SUB // 2)
                g_scr[at_mid, :] += d_middle[h][s] - jnp.sum(
                    through_row[lo:lo + SUB], axis=0, keepdims=True)
            g_scr[h * c + c - 1:h * c + c, :] += d_last[h] + jnp.sum(
                through_end[h * c:(h + 1) * c], axis=0, keepdims=True)
        # the transpose of the running sum
        _unpair(dg_ref, p, _dot(ch.lower.astype(f32), g_scr[...], _TN,
                                exact=True))

        def through_norm(d_unit, unit, r):
            return r * (d_unit - unit * jnp.sum(d_unit * unit, axis=1,
                                                keepdims=True))

        _unpair(dq_ref, p, through_norm(d_q * ch.scale, ch.q_unit, ch.q_r))
        _unpair(dk_ref, p, through_norm(d_k, ch.k, ch.k_r))
        _unpair(dv_ref, p, d_rhs_v * ch.beta)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2), 1)
        dbeta_ref[0, p] = jnp.where(lane == 0, d_beta[:c], d_beta[c:])


@functools.lru_cache(maxsize=None)
def _delta_calls(bsz: int, t: int, heads: int, hb: int, interpret: bool):
    """The delta rule of one set of shapes under `jax.custom_vjp`, built
    once a process with each `pallas_call` behind a `jax.jit` of its own,
    for the reason of `ops/ssm._scan_calls`: a pallas kernel's body is
    traced anew by every call, in every program of a job."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, w = DELTA_CHUNK, DELTA_LANES
    nc, blocks, pairs = t // c, heads // hb, hb // 2
    f32 = jnp.float32
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)

    def specs(reverse):
        """The block specs of a walk over the chunks, first to last or
        last to first: q-wide, beta-like pairs of columns, the entering
        states, the inverses."""
        def at(ci):
            return nc - 1 - ci if reverse else ci
        return (
            pl.BlockSpec((1, c, hb * w), lambda bi, hi, ci: (bi, at(ci), hi)),
            pl.BlockSpec((1, pairs, c, 2),
                         lambda bi, hi, ci: (bi, hi, at(ci), 0)),
            pl.BlockSpec((1, 1, hb * w, w),
                         lambda bi, hi, ci: (bi, at(ci), hi, 0)),
            pl.BlockSpec((1, 1, pairs * c, 2 * c),
                         lambda bi, hi, ci: (bi, at(ci), hi, 0)))

    scratch = [pltpu.VMEM((hb * w, w), f32), pltpu.VMEM((2 * c, w), f32)]

    @functools.partial(jax.jit, inline=True)
    def forward(q, k, v, g, beta):
        wide, cols, states, inverses = specs(False)
        return pl.pallas_call(
            functools.partial(_delta_fwd_kernel, pairs=pairs),
            grid=(bsz, blocks, nc),
            in_specs=[wide, wide, wide, wide, cols],
            out_specs=[wide, states, inverses],
            out_shape=[jax.ShapeDtypeStruct((bsz, t, heads * w), f32),
                       jax.ShapeDtypeStruct((bsz, nc, heads * w, w), f32),
                       jax.ShapeDtypeStruct(
                           (bsz, nc, heads // 2 * c, 2 * c), f32)],
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret, name="kda_delta_fwd")(q, k, v, g, beta)

    @functools.partial(jax.jit, inline=True)
    def backward(q, k, v, g, beta, entering, inverses, d_o):
        wide, cols, states, inverse_blocks = specs(True)
        return pl.pallas_call(
            functools.partial(_delta_bwd_kernel, pairs=pairs),
            grid=(bsz, blocks, nc),
            in_specs=[wide, wide, wide, wide, cols, states, inverse_blocks,
                      wide],
            out_specs=[wide, wide, wide, wide, cols],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(g.shape, f32),
                       jax.ShapeDtypeStruct(beta.shape, f32)],
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret, name="kda_delta_bwd")(
                q, k, v, g, beta, entering, inverses, d_o)

    @jax.custom_vjp
    def rule(q, k, v, g, beta):
        return forward(q, k, v, g, beta)[0]

    def rule_fwd(*operands):
        # named, the output too: under a policy that saves the name the
        # layer's backward pass reads all three and not the kernel again
        o, entering, inverses = checkpoint_name(forward(*operands),
                                                DELTA_RESIDUALS)
        return o, (operands, entering, inverses)

    def rule_bwd(res, d_o):
        operands, entering, inverses = res
        return tuple(backward(*operands, entering, inverses, d_o))

    rule.defvjp(rule_fwd, rule_bwd)
    return rule


def gated_delta_rule_pallas(q, k, v, g, beta, *, chunk: int = DELTA_CHUNK,
                            head_block=None, interpret: bool = False):
    """`gated_delta_rule` as pallas TPU kernels with a backward kernel of
    their own (module docstring), on the layouts the convolution and the
    gates leave: q, k, v `[B, T, H·D]` (L2-normed in the kernels; v's
    dtype is the compute dtype), g `[B, T, H·D]` float32, beta
    `[B, T, H]` -> o `[B, T, H·D]` float32. The shapes are
    `delta_shape_ok`'s to vouch for; `head_block` and `interpret` are the
    tests' (fewer heads a grid step, the kernels on the CPU)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, t, heads = beta.shape
    if not delta_shape_ok(t, heads, q.shape[2] // heads,
                          v.shape[2] // heads, chunk):
        raise ValueError(f"the kernels do not tile {q.shape} in chunks of "
                         f"{chunk} with {heads} heads")
    hb = head_block or next(b for b in (DELTA_HEADS, 2) if heads % b == 0)
    rule = _delta_calls(bsz, t, heads, hb, interpret)

    def pairs(a):    # [B, T, H] <-> [B, H/2, T, 2]: 0.5 MB, turned by XLA
        return jnp.swapaxes(a.reshape(bsz, t, heads // 2, 2), 1, 2)

    return rule(q, k, v, g.astype(f32), pairs(beta.astype(f32)))


def kda_mixer(h, lp: Dict[str, Any], *, chunk: int, lower: float,
              eps: float, mesh=None):
    """h `[B, T, d]` (normed, compute dtype) -> the sublayer's output
    before the residual, `[B, T, d]`. lp: `w_kda_qkv [d, 3, H, D]`,
    `w_kda_a [d, H, D]`, `w_kda_bg [d, 2, H]` (beta, the output gate) and
    `w_kda_out [H, D, d]` in the compute dtype; `kda_conv [3, H*D, K]`,
    `kda_A_log [H]`, `kda_a_bias [H, D]`, `kda_out_norm [D]`. The heads
    are read off the leaves (module docstring); `mesh` is what the program
    runs on, for `kda_delta_impl`'s choice."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, t, _ = h.shape
    heads, d = lp["kda_a_bias"].shape
    with jax.named_scope("kda/qkv_proj"):
        qkv = jnp.einsum("btd,dghk->btghk", h, lp["w_kda_qkv"])
    with jax.named_scope("kda/conv"):
        # the three depthwise convolutions as one over q's, k's and v's
        # channels side by side
        qkv = jax.nn.silu(causal_conv(
            qkv.reshape(bsz, t, 3 * heads * d),
            lp["kda_conv"].reshape(3 * heads * d, -1)))
        # `[B, T, H·D]` each: a run of the convolution's lanes
        q, k, v = (qkv[..., i * heads * d:(i + 1) * heads * d]
                   for i in range(3))
    with jax.named_scope("kda/gates"):
        # in the convolution's `[B, T, H·D]` layout, as the kernels and
        # `head_norm` read it
        g = log_decay(jnp.einsum("btd,de->bte", h,
                                 lp["w_kda_a"].reshape(-1, heads * d)),
                      lp["kda_A_log"], lp["kda_a_bias"], lower)
        bg = jax.nn.sigmoid(jnp.einsum(
            "btd,dgh->btgh", h, lp["w_kda_bg"]).astype(f32))
        beta, gate = bg[:, :, 0], bg[:, :, 1]
    with jax.named_scope("kda/delta"):
        if kda_delta_impl(mesh, t, heads, d, d, chunk) == "pallas":
            # the kernels read the convolution's own layout
            o = gated_delta_rule_pallas(q, k, v, g, beta, chunk=chunk)
        else:
            o = gated_delta_rule(
                *(a.reshape(bsz, t, heads, d) for a in (q, k, v, g)), beta,
                chunk=chunk).reshape(bsz, t, heads * d)
    with jax.named_scope("kda/out_norm"):
        y = head_norm(o, gate, lp["kda_out_norm"], eps).astype(h.dtype)
    with jax.named_scope("kda/out_proj"):
        return jnp.einsum("bte,ed->btd", y,
                          lp["w_kda_out"].reshape(heads * d, -1))


# ---- Gated DeltaNet: the same rule behind one decay a head ------------------


def head_log_decay(a, a_log, dt_bias):
    """Gated DeltaNet's gate: a `[B, T, H]`, a_log and dt_bias `[H]` ->
    `-exp(a_log) * softplus(a + dt_bias)`, float32, one scalar a head and
    step, at or below 0 and NOT bounded below."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))


def gdn_mixer(h, lp: Dict[str, Any], *, chunk: int, beta_scale: float,
              eps: float):
    """A Gated DeltaNet sublayer (arXiv 2412.06464; module docstring): h
    `[B, T, d]` (compute dtype; the stream itself under the reordered
    norm) -> the sublayer's output before its norm and residual,
    `[B, T, d]`. lp: `w_gdn_qkv [d, H, 2 Dk + Dv]` (a head's q, k and v
    columns side by side), `w_gdn_g [d, H, Dv]` (the output gate, a
    channel each), `w_gdn_ab [d, 2, H]` (the decay's input, beta's) and
    `w_gdn_out [H, Dv, d]` in the compute dtype; `gdn_conv [H, 2 Dk + Dv,
    K]`, `gdn_A_log [H]`, `gdn_dt_bias [H]`, `gdn_out_norm [Dv]`. The
    heads and both widths are read off the leaves, so a share of the heads
    is a slice of every leaf's head axis; `beta_scale` is 2 where the
    model allows negative eigenvalues (`allow_neg_eigval`), else 1."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, t, _ = h.shape
    heads, wide, _ = lp["gdn_conv"].shape
    d_v = lp["w_gdn_g"].shape[-1]
    d_k = (wide - d_v) // 2
    with jax.named_scope("gdn/qkv_proj"):
        qkv = jnp.einsum("btd,dhw->bthw", h, lp["w_gdn_qkv"])
    with jax.named_scope("gdn/conv"):
        # the three depthwise convolutions as one over every head's q, k
        # and v channels
        qkv = jax.nn.silu(causal_conv(
            qkv.reshape(bsz, t, heads * wide),
            lp["gdn_conv"].reshape(heads * wide, -1))).reshape(
                bsz, t, heads, wide)
        q, k, v = (qkv[..., :d_k], qkv[..., d_k:2 * d_k],
                   qkv[..., 2 * d_k:])
    with jax.named_scope("gdn/gates"):
        ab = jnp.einsum("btd,dgh->btgh", h, lp["w_gdn_ab"]).astype(f32)
        g = head_log_decay(ab[:, :, 0], lp["gdn_A_log"], lp["gdn_dt_bias"])
        beta = beta_scale * jax.nn.sigmoid(ab[:, :, 1])
        gate = jax.nn.silu(jnp.einsum(
            "btd,de->bte", h,
            lp["w_gdn_g"].reshape(-1, heads * d_v)).astype(f32))
    with jax.named_scope("gdn/delta"):
        # the XLA path, as `kda_delta_impl(..., per_head=True)` says: the
        # kernels take a decay a channel at keys and values 128 wide
        o = gated_delta_rule(q, k, v, g, beta, chunk=chunk).reshape(
            bsz, t, heads * d_v)
    with jax.named_scope("gdn/out_norm"):
        y = head_norm(o, gate, lp["gdn_out_norm"], eps).astype(h.dtype)
    with jax.named_scope("gdn/out_proj"):
        return jnp.einsum("bte,ed->btd", y,
                          lp["w_gdn_out"].reshape(heads * d_v, -1))
