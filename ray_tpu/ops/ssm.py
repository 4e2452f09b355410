"""Mamba-2 mixer: a selective state-space layer in its chunked (SSD) form.

The reference has no state-space layer of any kind; this fills that row
beside `ops/attention.py` and `ops/moe.py`. One mixer, H heads of width P
over G groups of state N (Dao & Gu, "Transformers are SSMs", 2024, as
`nemotron_h` and `mamba2` publish it):

    [z | x | B | C | dt] = h W_in          widths H·P, H·P, G·N, G·N, H
    [x | B | C] = silu(conv1d([x | B | C]))  depthwise, causal, with bias
    dt = softplus(dt + dt_bias),  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               S is P x N
    y_t = S_t C_t + D x_t            head h reads its group's B and C
    y = grouped_rmsnorm(y * silu(z)) * gain   groups of H·P / G channels
    out = y W_out

**The scan in chunks** (`ssd_scan`): within a chunk of Q steps the
recurrence unrolls into the quadratic form `y_i = sum_{j<=i} L_ij (C_i.B_j)
dt_j x_j` with `L_ij = exp(sum_{j<m<=i} dt_m A)`, three einsums over
`[chunks, Q, Q]` blocks; each chunk's contribution to the state is one
more einsum, and the T/Q chunk states are chained by a `lax.scan`. The
decays are float32; the products take their operands in the compute dtype
and accumulate in float32. Nothing is `[T, T]`: at T = 8,192 and Q = 128
the largest temporaries are the `[T/Q, H, Q, Q]` decay blocks and the
`[T/Q, H, P, N]` chunk states, and autodiff's residuals are those, so the
backward pass holds in the same memory. T must be a multiple of the
chunk: any other T is refused, not padded. Plain XLA; a pallas kernel is
later work (ROADMAP R4).

**A share of the heads.** The mixer is told its heads and groups by the
weights it is given (`A_log` has H entries, the convolution H·P + 2·G·N
channels): given the columns of `W_in`, the channels of the convolution
and the norm, and the rows of `W_out` that belong to some of the groups
with their heads, it computes that share's part of the output
projection's sum. The gated norm's groups are the B/C groups, so it stays
local to a share. No code stands in for absent heads.

Scopes (PERF.md section 3): `ssm/in_proj`, `ssm/conv`, `ssm/scan`,
`ssm/gate_norm`, `ssm/out_proj`.
"""

from __future__ import annotations

from typing import Any, Dict


def causal_conv(x, w, b):
    """Depthwise causal convolution over time: x `[B, T, C]`, w `[C, K]`,
    b `[C]` -> `y_t = b + sum_j w[:, j] x_{t-K+1+j}` (zeros before t = 0).
    K shifted multiply-adds: K is 4."""
    import jax.numpy as jnp

    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = b.astype(x.dtype)
    for j in range(k):
        y = y + padded[:, j:j + t] * w[:, j].astype(x.dtype)
    return y


def ssd_scan(x, dt, a, b, c, chunk: int):
    """The selective scan `S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T`,
    `y_t = S_t c_t` in chunks: x `[B, T, H, P]`, dt `[B, T, H]` (after
    softplus, float32), a `[H]` (negative, float32), b and c
    `[B, T, G, N]` with head h in group h // (H / G) -> y `[B, T, H, P]`
    float32. T % chunk != 0 is refused."""
    import jax
    import jax.numpy as jnp

    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if t % chunk:
        raise ValueError(f"the scan takes whole chunks: {t} steps are no "
                         f"multiple of {chunk}")
    nc, q, r = t // chunk, chunk, h // g
    f32 = jnp.float32
    xc = x.reshape(bsz, nc, q, g, r, p)
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)
    dtc = dt.astype(f32).reshape(bsz, nc, q, g, r)
    # log-decay up to and including each step of its chunk
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(g, r), axis=2)
    # ---- within a chunk: the quadratic form ------------------------
    lower = jnp.tril(jnp.ones((q, q), bool))
    diff = cum[:, :, :, None] - cum[:, :, None, :]        # [.., i, j, g, r]
    # masked before the exponential: above the diagonal the sum is
    # positive and would overflow
    decay = jnp.exp(jnp.where(lower[:, :, None, None], diff, -jnp.inf))
    scores = jnp.einsum("zcign,zcjgn->zcijg", cc, bc,
                        preferred_element_type=f32)
    weights = (scores[..., None] * decay
               * dtc[:, :, None, :]).astype(x.dtype)      # [z, c, i, j, g, r]
    y = jnp.einsum("zcijgr,zcjgrp->zcigrp", weights, xc,
                   preferred_element_type=f32)
    # ---- each chunk's own contribution to the state at its end ------
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc           # [z, c, j, g, r]
    states = jnp.einsum("zcjgn,zcjgrp->zcgrpn", bc,
                        xc * to_end[..., None].astype(x.dtype),
                        preferred_element_type=f32)
    # ---- the chunk states chained: T/Q steps of a scan --------------
    chunk_decay = jnp.exp(cum[:, :, -1])                   # [z, c, g, r]

    def step(carry, inp):
        state, decay_c = inp
        return carry * decay_c[..., None, None] + state, carry

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # [z, c, g, r, p, n]
    # ---- what the state entering a chunk adds to its steps ----------
    y = y + jnp.einsum("zcign,zcgrpn->zcigrp", cc,
                       entering.astype(x.dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(bsz, t, h, p)


def gated_norm(y, z, gain, groups: int, eps: float):
    """`grouped_rmsnorm(y * silu(z)) * gain`: y, z `[..., C]`, the RMS
    over each of `groups` runs of C / groups channels, in float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    grouped = v.reshape(v.shape[:-1] + (groups, -1))
    scale = jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped * scale).reshape(v.shape) * gain.astype(f32)


def mamba2_mixer(h, lp: Dict[str, Any], *, head_dim: int, state: int,
                 chunk: int, eps: float):
    """h `[B, T, d]` (normed, compute dtype) -> the mixer's output before
    the residual, `[B, T, d]`. lp: `w_in [d, 2·H·P + 2·G·N + H]` and
    `w_out [H·P, d]` in the compute dtype; `conv_w [H·P + 2·G·N, K]`,
    `conv_b`, `dt_bias [H]`, `A_log [H]`, `D [H]`, `gate_norm [H·P]`.
    Heads and groups are read off the leaves (module docstring)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads = lp["A_log"].shape[0]
    inner = heads * head_dim
    conv_dim = lp["conv_w"].shape[0]
    groups = (conv_dim - inner) // (2 * state)
    bsz, t, _ = h.shape
    with jax.named_scope("ssm/in_proj"):
        zxbcdt = jnp.einsum("btd,de->bte", h, lp["w_in"])
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = zxbcdt[..., inner + conv_dim:]
    with jax.named_scope("ssm/conv"):
        xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
        x = xbc[..., :inner].reshape(bsz, t, heads, head_dim)
        b = xbc[..., inner:inner + groups * state].reshape(
            bsz, t, groups, state)
        c = xbc[..., inner + groups * state:].reshape(
            bsz, t, groups, state)
    with jax.named_scope("ssm/scan"):
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        y = ssd_scan(x, dt, -jnp.exp(lp["A_log"].astype(f32)), b, c, chunk)
        y = y + x.astype(f32) * lp["D"].astype(f32)[:, None]
    with jax.named_scope("ssm/gate_norm"):
        y = gated_norm(y.reshape(bsz, t, inner), z, lp["gate_norm"], groups,
                       eps).astype(h.dtype)
    with jax.named_scope("ssm/out_proj"):
        return jnp.einsum("bte,ed->btd", y, lp["w_out"])
