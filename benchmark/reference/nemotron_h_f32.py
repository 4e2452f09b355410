"""Plain reference for Nemotron-3-Super (`model_type: nemotron_h`),
float32, jax.numpy.

Written from the published config.json keys and the equations of the
`nemotron_h` modelling code they name (Mamba-2 mixers, GQA attention
without position, a latent expert layer with a `noaux_tc`-style sigmoid
router), importing nothing from `ray_tpu`. A layer is ONE of three
sublayers, by its character in `hybrid_override_pattern`, each

    h <- h + f(RMSNorm(h; g)),   RMSNorm(x) = g * x / sqrt(mean(x^2) + eps)

`M`, a Mamba-2 mixer, H heads of P = `mamba_head_dim`, G = `n_groups`
groups of state N = `ssm_state_size`, inner width H·P, n the normed input:

    [z | xBC | dt] = W_in n                 widths H·P, H·P + 2·G·N, H
    xBC = silu(conv1d(xBC))                 depthwise, causal, kernel
                                            `conv_kernel`, with bias
    [x | B | C] = xBC                       widths H·P, G·N, G·N
    dt = softplus(dt + dt_bias),  A = -exp(A_log)          per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     per head, S is P x N,
                                                   B, C of the head's group
    y_t = S_t C_t + D x_t
    y = RMSNorm_grouped(y * silu(z); g_norm)       over G runs of H·P / G
    f = W_out y                                    no bias on W_in, W_out

  The recurrence runs step by step (`jax.lax.scan` over the T steps, one
  state per head), not in chunks.

`*`, attention: `num_attention_heads` query heads over
`num_key_value_heads` key/value heads of `head_dim`, causal, scale
head_dim^-1/2, NO rotary embedding (the mixers carry position), no bias:
`f = W_o . CausalAttn(W_q n, W_k n, W_v n)`.

`E`, a latent expert layer:

    s      = sigmoid(W_r n) over the E routed experts, float32
    S      = the k experts of largest s + b      (b: the correction bias, a
             buffer; n_group = topk_group = 1: no group limit)
    g_e    = s_e / sum_{e in S} s_e  (norm_topk_prob), times
             routed_scaling_factor               -- s WITHOUT b
    u      = W_down n                            hidden -> moe_latent_size,
                                                 once a token
    expert_e(u) = W2_e relu(W1_e u)^2            latent -> width -> latent,
                                                 no gate (`relu2`)
    f      = W_up (sum_{e in S} g_e . expert_e(u))
             + V2 relu(V1 n)^2                   the shared expert, on the
                                                 stream itself

logits = W_head . RMSNorm(h_L; g_final); loss = next-token cross-entropy
(no aux loss). Every product is float32: on a TPU a float32 matmul runs in
lower precision unless `jax.default_matmul_precision("highest")` is set,
which the functions here do themselves.

**A share.** The reference is told what is held by the weights it is
given, as the program is. `lw["experts"]` maps an expert's id to its
weights and holds the resident experts: a token-slot routed to an absent
expert adds nothing (the router still scores all E and picks k). A mixer
given the columns of `in_proj`, the channels of the convolution and the
norm and the columns of `out_proj` that belong to some of the B/C groups
with their heads computes those heads' part of `out_proj`'s sum; the
grouped norm's groups are the B/C groups, so it stays local. Attention
given some key/value heads with their query heads likewise. What the
absent heads would add is left out. A sliced vocabulary is a smaller
vocabulary: `embed_tokens` and `lm_head` simply have fewer rows.

Weights arrive in the published layout (`y = x W^T`, W of shape
[out, in]), one dict per layer with `norm` and: a mixer `in_proj`,
`conv1d` [channels, kernel], `conv1d_bias`, `dt_bias`, `A_log`, `D`,
`mixer_norm`, `out_proj`; attention `q_proj`, `k_proj`, `v_proj`,
`o_proj`; an expert layer `gate` ([E, hidden]),
`e_score_correction_bias` [E], `fc1_latent_proj`, `fc2_latent_proj`,
`experts` {id: {`up_proj` [width, latent], `down_proj` [latent, width]}},
`shared_experts` {`up_proj`, `down_proj`}. Whoever calls this converts the
system's parameters.

Departures from the HF modelling code, each marked `# HF:` where it is:
every resident expert runs on every token and a 0/weight mask picks the
chosen ones (HF gathers each expert's tokens: same sum); attention is
computed for a block of queries at a time against the keys up to the
block's end, and the mixer's recurrence for a block of heads at a time,
so that one 8,192-token sequence fits beside the weights on a chip (same
arithmetic, row by row); HF's `torch_forward` computes the recurrence in
chunks, here the plain recurrence it is equal to; `time_step_min/max/
floor` only shape `dt_bias`'s initial values and `dt` is not clamped
(`time_step_limit` is (0, inf) in the published config); the multi-token
prediction module (`num_nextn_predict_layers`) is absent; no attention
mask, padding or cache; group-limited routing is refused unless absent
(the published values).

No kernels, no sort, no chunked scan, no fused weights, no sharding.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

QUERY_BLOCK = 1024
HEAD_BLOCK = 32


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def relu2(x):
    import jax.numpy as jnp
    return jnp.square(jnp.maximum(x, 0.0))


# ---- M: the Mamba-2 mixer ------------------------------------------------


def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution: x [B, T, C], weight [C, K], bias [C]:
    y_t = bias + sum_j weight[:, j] * x_{t - (K-1) + j}."""
    import jax.numpy as jnp
    k, t = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = jnp.zeros_like(x) + bias
    for j in range(k):
        y = y + padded[:, j:j + t] * weight[:, j]
    return y


def selective_scan(x, dt, a, b, c, d_skip):
    """The recurrence, step by step: x [B, T, H, P], dt [B, T, H], a [H],
    b and c [B, T, H, N] (each head's group's), d_skip [H] -> y
    [B, T, H, P]."""
    import jax
    import jax.numpy as jnp

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp            # [B,H,P] [B,H] [B,H,N] [B,H,N]
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    bsz, _, h, p = x.shape
    init = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, init, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x


@functools.lru_cache(maxsize=None)
def _compiled_scan():
    import jax
    return jax.jit(selective_scan)


def mamba2_mixer(n, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """n [B, T, hidden] (normed) -> the mixer's output before the
    residual. Heads and groups are what the weights hold."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    p, ns = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    heads = lw["A_log"].shape[0]
    inner = heads * p
    conv_dim = lw["conv1d"].shape[0]
    groups = (conv_dim - inner) // (2 * ns)
    bsz, t, _ = n.shape

    zxbcdt = linear(n, lw["in_proj"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:inner + conv_dim]
    dt = zxbcdt[..., inner + conv_dim:]
    xbc = jax.nn.silu(causal_conv1d(xbc, lw["conv1d"].astype(f32),
                                    lw["conv1d_bias"].astype(f32)))
    x = xbc[..., :inner].reshape(bsz, t, heads, p)
    b = xbc[..., inner:inner + groups * ns].reshape(bsz, t, groups, ns)
    c = xbc[..., inner + groups * ns:].reshape(bsz, t, groups, ns)
    # a head reads the B and C of its group
    b = jnp.repeat(b, heads // groups, axis=2)
    c = jnp.repeat(c, heads // groups, axis=2)
    # HF: time_step_limit (0, inf): no clamp
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(f32))
    a = -jnp.exp(lw["A_log"].astype(f32))
    d_skip = lw["D"].astype(f32)
    # HF: torch_forward's chunked form; here the recurrence itself, a
    # block of heads at a time
    y = jnp.concatenate([
        _compiled_scan()(x[:, :, lo:lo + HEAD_BLOCK],
                         dt[:, :, lo:lo + HEAD_BLOCK],
                         a[lo:lo + HEAD_BLOCK],
                         b[:, :, lo:lo + HEAD_BLOCK],
                         c[:, :, lo:lo + HEAD_BLOCK],
                         d_skip[lo:lo + HEAD_BLOCK])
        for lo in range(0, heads, HEAD_BLOCK)], axis=2)
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(bsz, t, groups, -1),
                 lw["mixer_norm"].astype(f32).reshape(groups, -1),
                 cfg["layer_norm_epsilon"])
    return linear(y.reshape(bsz, t, inner), lw["out_proj"])


# ---- *: attention ----------------------------------------------------------


def causal_attention(q, k, v, scale: float, block: int = QUERY_BLOCK):
    """q, k, v [B, H, T, D] -> [B, H, T, D]."""
    import jax
    import jax.numpy as jnp
    t = q.shape[2]
    out = []
    # HF: one [T, T] score matrix; here a block of queries at a time
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, lo:hi],
                            k[:, :, :hi]) * scale
        visible = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1), v[:, :, :hi]))
    return jnp.concatenate(out, axis=2)


def attention(n, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """n [B, T, hidden] (normed) -> attention's output before the
    residual; no rotary embedding."""
    import jax.numpy as jnp
    bsz, t, _ = n.shape
    hd = cfg["head_dim"]

    def heads(w):
        return linear(n, w).reshape(bsz, t, -1, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(lw["q_proj"]), heads(lw["k_proj"]), heads(lw["v_proj"])
    rep = q.shape[1] // k.shape[1]
    # HF: NemotronHAttention applies no rotary embedding
    o = causal_attention(q, jnp.repeat(k, rep, axis=1),
                         jnp.repeat(v, rep, axis=1), float(hd) ** -0.5)
    return linear(o.transpose(0, 2, 1, 3).reshape(bsz, t, -1), lw["o_proj"])


# ---- E: the latent expert layer -------------------------------------------


def plain_mlp(m, up_proj, down_proj):
    """W_2 relu(W_1 m)^2 on every row of m: no gate."""
    return linear(relu2(linear(m, up_proj)), down_proj)


@functools.lru_cache(maxsize=None)
def _compiled_mlp():
    """`plain_mlp` under `jax.jit`: called op by op the loop over the
    experts then compiles one expert once. Same arithmetic."""
    import jax
    return jax.jit(plain_mlp)


def route(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (weights [N, k], expert ids [N, k], scores
    [N, E]): sigmoid scores, the choice by score + bias, the weights the
    chosen scores without it, normalised and scaled."""
    import jax
    import jax.numpy as jnp

    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not in this reference")
    scores = jax.nn.sigmoid(linear(m, lw["gate"]).astype(jnp.float32))
    choice = scores + lw["e_score_correction_bias"].astype(jnp.float32)
    _, top_e = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    return top_w * cfg["routed_scaling_factor"], top_e, scores


def latent_experts(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (the expert layer's output before the residual
    [N, hidden], expert ids [N, k])."""
    import jax.numpy as jnp

    top_w, top_e, _ = route(m, lw, cfg)      # the router reads the stream
    u = linear(m, lw["fc1_latent_proj"])     # once a token
    y = jnp.zeros_like(u)
    for e, ew in sorted(lw["experts"].items()):
        # HF: gathers the tokens that chose e; here every token runs
        # through e and those that did not choose it get weight 0
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * _compiled_mlp()(
            u, ew["up_proj"], ew["down_proj"])
    sw = lw["shared_experts"]                # on the stream itself
    return (linear(y, lw["fc2_latent_proj"])
            + _compiled_mlp()(m, sw["up_proj"], sw["down_proj"])), top_e


# ---- the model ---------------------------------------------------------------


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_routing: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every expert layer's chosen ids [B*T, k])."""
    import jax
    import jax.numpy as jnp

    if cfg.get("mlp_hidden_act", "relu2") != "relu2" \
            or cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("the published activations are relu2 and silu")
    if cfg.get("use_bias") or cfg.get("mamba_proj_bias") \
            or cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("the published projections have no bias")
    if not cfg.get("use_conv_bias", True):
        raise ValueError("the published convolution has a bias")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != len(weights["layers"]) or set(pattern) - set("ME*"):
        raise ValueError(f"pattern {pattern!r} against "
                         f"{len(weights['layers'])} layers")
    eps = cfg["layer_norm_epsilon"]
    f32 = jnp.float32
    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        bsz, t = tokens.shape
        h = weights["embed_tokens"].astype(f32)[tokens]
        for kind, lw in zip(pattern, weights["layers"]):
            n = rms_norm(h, lw["norm"].astype(f32), eps)
            if kind == "M":
                h = h + mamba2_mixer(n, lw, cfg)
            elif kind == "*":
                h = h + attention(n, lw, cfg)
            else:
                y, top_e = latent_experts(n.reshape(bsz * t, -1), lw, cfg)
                chosen.append(top_e)
                h = h + y.reshape(bsz, t, -1)
        h = rms_norm(h, weights["norm_f"].astype(f32), eps)
        logits = linear(h, weights["lm_head"])
    return (logits, chosen) if with_routing else logits


def next_token_loss(logits, targets):
    """Mean cross-entropy of logits [B, T, V] against targets [B, T]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def tokens_per_expert(chosen: List[Any], n_experts: int):
    """[L, E] int32: how many of an expert layer's N*k token-slots chose
    each of the E routed experts."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sum(jax.nn.one_hot(
        top_e.reshape(-1), n_experts, dtype=jnp.int32), axis=0)
        for top_e in chosen])


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any]):
    """batch_tokens [B, T+1] -> next-token cross-entropy (no aux term)."""
    logits = forward(weights, batch_tokens[:, :-1], cfg)
    return next_token_loss(logits, batch_tokens[:, 1:])


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`; the bias only
    enters the choice: its gradient is zero."""
    import jax
    return jax.value_and_grad(lambda w: loss(w, batch_tokens, cfg))(weights)
