"""Kimi Delta Attention (KDA, arXiv 2510.26692): linear attention whose
state is corrected by a delta rule behind a decay per channel.

The reference has no linear attention of any kind; this fills that row
beside `ops/attention.py`, `ops/ssm.py` and `ops/moe.py`. One sublayer, H
heads of width D (keys and values alike), per token t and head:

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

The decays, their running sums, A, B, the inverse and the state are
float32 (A, B and the inverse at the highest matmul precision: an error
there is amplified by the inverse); the products with `S`, `U` and `T`
take their operands in the compute dtype and accumulate in float32. A T
that is no whole chunks is padded at its end with steps that change
nothing (k = v = 0, beta = 0, no decay).

**A share of the heads.** The sublayer is told its heads by the weights it
is given: the columns of the projections, the channels of the
convolutions and the rows of `W_o` that belong to some heads give that
share's part of the output projection's sum; the norm is per head, so it
stays local. No code stands in for absent heads.

Scopes (PERF.md section 3): `kda/qkv_proj`, `kda/conv`, `kda/gates`
(log a, beta and the output gate), `kda/delta` (the L2 norms, the chunk
inverses, the state), `kda/out_norm`, `kda/out_proj`.
"""

from __future__ import annotations

from typing import Any, Dict

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
    """The bounded gate: a `[B, T, H, D]`, a_log `[H]`, bias `[H, D]` ->
    `lower * sigmoid(exp(a_log) * (a + bias))`, float32, in (lower, 0)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    return lower * jax.nn.sigmoid(
        jnp.exp(a_log.astype(f32))[:, None]
        * (a.astype(f32) + bias.astype(f32)))


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


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """`S_t = (I - beta_t k_t k_t^T) diag(e^{g_t}) S_{t-1} + beta_t k_t
    v_t^T`, `o_t = D^-1/2 S_t^T q_t` from a zero state, in chunks (module
    docstring): q, k `[B, T, H, D]` as the convolutions leave them (L2-
    normed here), v `[B, T, H, Dv]` (its dtype is the compute dtype), g
    `[B, T, H, D]` float32 log-decays in (-5, 0], beta `[B, T, H]` -> o
    `[B, T, H, Dv]` float32."""
    import jax
    import jax.numpy as jnp

    f32, cdt = jnp.float32, v.dtype
    bsz, t, h, d = q.shape
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} steps is no whole sub-blocks "
                         f"of {SUB}")
    pad = -t % chunk
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g, beta = g.astype(f32), beta.astype(f32)
    if pad:   # steps that change nothing
        q, k, v, g, beta = (jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc, c, ns = (t + pad) // chunk, chunk, chunk // SUB

    def chunks(a):   # [B, T, H, W] -> [B, H, nc, c, W]
        return jnp.moveaxis(a.reshape(bsz, nc, c, h, -1), 3, 1)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta[..., None])                     # [B, H, nc, c, 1]
    cum = jnp.cumsum(gc, axis=3)                     # G, the step included
    # ---- A and B, relative to the rows' sub-blocks ------------------
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
                        preferred_element_type=f32)  # [.., ns, 2 SUB, c]
    lower = jnp.tril(jnp.ones((c, c), bool))
    b_mat = jnp.where(lower, scores[..., :SUB, :].reshape(
        bsz, h, nc, c, c), 0.0)
    a_mat = jnp.where(lower & ~jnp.eye(c, dtype=bool),
                      scores[..., SUB:, :].reshape(bsz, h, nc, c, c), 0.0)
    # ---- the chunk inverses, and what they make of V and K ----------
    inv = _unit_lower_inverse(-bc * a_mat).astype(cdt)
    decayed = jnp.exp(cum)
    rhs = jnp.concatenate([vc.astype(f32), kc * decayed], -1) * bc
    solved = jnp.einsum("...ri,...iw->...rw", inv, rhs.astype(cdt),
                        preferred_element_type=f32)
    dv = vc.shape[-1]
    to_end = jnp.exp(cum[:, :, :, -1:] - cum)        # e^{G_C - G}, <= 1
    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in (
        solved[..., :dv], solved[..., dv:].astype(cdt),
        (qc * decayed).astype(cdt), b_mat.astype(cdt),
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


def kda_mixer(h, lp: Dict[str, Any], *, chunk: int, lower: float,
              eps: float):
    """h `[B, T, d]` (normed, compute dtype) -> the sublayer's output
    before the residual, `[B, T, d]`. lp: `w_kda_qkv [d, 3, H, D]`,
    `w_kda_a [d, H, D]`, `w_kda_bg [d, 2, H]` (beta, the output gate) and
    `w_kda_out [H, D, d]` in the compute dtype; `kda_conv [3, H*D, K]`,
    `kda_A_log [H]`, `kda_a_bias [H, D]`, `kda_out_norm [D]`. The heads
    are read off the leaves (module docstring)."""
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
            lp["kda_conv"].reshape(3 * heads * d, -1))
            ).reshape(bsz, t, 3, heads, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    with jax.named_scope("kda/gates"):
        g = log_decay(jnp.einsum("btd,dhk->bthk", h, lp["w_kda_a"]),
                      lp["kda_A_log"], lp["kda_a_bias"], lower)
        bg = jax.nn.sigmoid(jnp.einsum(
            "btd,dgh->btgh", h, lp["w_kda_bg"]).astype(f32))
        beta, gate = bg[:, :, 0], bg[:, :, 1]
    with jax.named_scope("kda/delta"):
        o = gated_delta_rule(q, k, v, g, beta, chunk=chunk)
    with jax.named_scope("kda/out_norm"):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        y = (y * lp["kda_out_norm"].astype(f32)
             * gate[..., None]).astype(h.dtype)
    with jax.named_scope("kda/out_proj"):
        return jnp.einsum("bthk,hkd->btd", y, lp["w_kda_out"])
