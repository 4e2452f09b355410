"""Plain reference for Olmo-Hybrid-7B (the catalog row `Olmo-Hybrid-7B`,
`config.json`, `model_type: olmo_hybrid`), float32, jax.numpy.

Written from the published config.json keys and the equations ISSUE 59
writes down from them (Gated DeltaNet, arXiv 2412.06464; the OLMo-2/3
family's block with the norm on a sublayer's OUTPUT), importing nothing
from `ray_tpu`. Every sublayer is `x + RMSNorm(f(x); g)`: no norm before
it (`sublayer`):

    h_0 = E[tokens]
    a `linear_attention` layer (`q_conv1d` among its weights), per head
    of d_k = 96 keys and d_v = 192 values:
      q~, k~, v = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
                   depthwise causal convolutions of 4 taps, no bias
      q_t = q~_t / |q~_t|,  k_t = k~_t / |k~_t|     (L2 over the head's 96)
      beta_t  = 2 sigmoid(w_b x)        one scalar a head, in (0, 2)
                   (`linear_allow_neg_eigval`; without it sigmoid alone)
      log a_t = -exp(A_h) softplus(w_a x + dt_h)    one scalar a head,
                   NOT bounded below
      S_t = a_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
      o_t = 96^-1/2 S_t q_t             STEP BY STEP: one `lax.scan` over
                   the tokens, no chunks, no WY form
      y_t = RMSNorm(o_t; g_o over the head's 192) * silu(W_g x)
                   a gate a CHANNEL
      f(x) = W_o concat_h(y_t)          no rotary embedding
    a `full_attention` layer (`q_norm` among its weights):
      q = RMSNorm(W_q x; g_q),  k = RMSNorm(W_k x; g_k)   each over the
                   WHOLE projection (all heads together), v = W_v x
      f(x) = W_o CausalAttn(q, k, v), heads of 128, scale 128^-1/2,
                   no rotary embedding (`rope_theta` null), no biases
    then in both: x = x + RMSNorm(W_down (silu(W_gate x) * (W_up x)); g_ff)
    logits = W_head RMSNorm(h_L; g_final)
    loss   = next-token cross-entropy

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); every product is float32 under
`jax.default_matmul_precision("highest")`, which the functions here set
themselves.

**A share.** The heads a layer holds are read off its weights (`A_log` has
one entry a mixer head; `q_proj` has 128 rows an attention head): given the
rows of the projections, the channels of the convolutions and the columns
of `o_proj` that belong to some heads, the layer computes that share's
part of `o_proj`'s sum. Two things then differ from the whole layer, and
the system is given the same share so both sides do the same: the
QK-norm's mean of squares runs over the held columns, and the norm on the
sublayer's output sees this share's part of `o_proj`'s sum. A sliced
vocabulary is a smaller vocabulary.

Weights arrive in a published-style layout (`y = x W^T`, W of shape
[out, in]), one dict per layer. Mixer: `q_proj`, `k_proj` [H*96, hidden],
`v_proj`, `g_proj` [H*192, hidden], `q_conv1d`, `k_conv1d` [H*96, 4],
`v_conv1d` [H*192, 4], `a_proj`, `b_proj` [H, hidden], `A_log`, `dt_bias`
[H], `o_norm` [192], `o_proj` [hidden, H*192]. Attention: `q_proj`,
`k_proj`, `v_proj` [H*128, hidden], `q_norm`, `k_norm` [H*128], `o_proj`.
Both: `post_attention_layernorm`, `gate_proj`, `up_proj`, `down_proj`,
`post_feedforward_layernorm`.

Departures from a modelling file, each marked `# dep:` where it is:
attention is computed for a block of queries at a time; `rotary` is the
identity (the place a rotary embedding would stand); no mask, padding or
cache.

No kernels, no chunks, no fused weights, no sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

QUERY_BLOCK = 1024
L2_EPS = 1e-6


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def l2_norm(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def sublayer(x, f, gain, eps):
    """The family's block: the norm stands on the sublayer's output."""
    return x + rms_norm(f(x), gain, eps)


# ---- Gated DeltaNet ------------------------------------------------------


def short_conv(x, w):
    """Depthwise causal convolution: x [B, T, C], w [C, K], no bias:
    y_t = sum_j w[:, j] x_{t - K + 1 + j}, then silu."""
    import jax
    import jax.numpy as jnp
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for j in range(taps):
        y = y + padded[:, j:j + t] * w[:, j]
    return jax.nn.silu(y)


def decay_gate(a, a_log, dt_bias):
    """a [B, T, H] -> log a_t = -exp(A_h) softplus(a + dt_h), one scalar a
    head and step."""
    import jax
    import jax.numpy as jnp
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)


def beta_gate(b, allow_neg_eigval: bool):
    """b [B, T, H] -> beta in (0, 2) where the state's transition may have
    negative eigenvalues, else in (0, 1)."""
    import jax
    return jax.nn.sigmoid(b) * (2.0 if allow_neg_eigval else 1.0)


def out_gate(g):
    """The gate on the normed output: a factor a channel."""
    import jax
    return jax.nn.silu(g)


def delta_rule(q, k, v, log_a, beta):
    """The recurrence step by step: q, k [B, T, H, Dk], v [B, T, H, Dv],
    log_a, beta [B, T, H] -> o [B, T, H, Dv], from a zero state."""
    import jax
    import jax.numpy as jnp

    def step(state, inp):            # state S^T [B, H, Dk, Dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = state * jnp.exp(a_t)[..., None, None]    # a S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)   # S k
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, (v_t - seen) * b_t[..., None])
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    b, _, h, d = q.shape
    steps = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_a, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, v.shape[-1]),
                                        jnp.float32), steps)
    return jnp.moveaxis(o, 0, 1) * d ** -0.5


@functools.lru_cache(maxsize=None)
def _compiled_delta_rule():
    """`delta_rule` under `jax.jit`: op by op the scan would compile with
    every call."""
    import jax
    return jax.jit(delta_rule)


def gated_delta_net(x, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """x [B, T, hidden] (the stream itself) -> the mixer's output before
    its norm and residual; the heads are those of the weights."""
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = x.shape
    heads = lw["A_log"].shape[0]

    def heads_of(y):
        return y.reshape(b, t, heads, -1)

    q, k, v = (heads_of(short_conv(linear(x, lw[p + "_proj"]),
                                   lw[p + "_conv1d"].astype(f32)))
               for p in "qkv")
    q, k = l2_norm(q), l2_norm(k)
    log_a = decay_gate(linear(x, lw["a_proj"]), lw["A_log"].astype(f32),
                       lw["dt_bias"].astype(f32))
    beta = beta_gate(linear(x, lw["b_proj"]),
                     bool(cfg["linear_allow_neg_eigval"]))
    o = _compiled_delta_rule()(q, k, v, log_a, beta)
    y = rms_norm(o, lw["o_norm"].astype(f32), cfg["rms_norm_eps"])
    y = y * out_gate(heads_of(linear(x, lw["g_proj"])))
    return linear(y.reshape(b, t, -1), lw["o_proj"])


# ---- full attention ------------------------------------------------------


def causal_attention(q, k, v, scale: float, block: int = QUERY_BLOCK):
    """q, k, v [B, H, T, D] -> [B, H, T, D]."""
    import jax
    import jax.numpy as jnp
    t = q.shape[2]
    out = []
    # dep: one [T, T] score matrix; here a block of queries at a time
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, lo:hi],
                            k[:, :, :hi]) * scale
        visible = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1), v[:, :, :hi]))
    return jnp.concatenate(out, axis=2)


def qk_norm(x, gain, eps):
    """The family's QK-norm: the whole projection, all heads together."""
    return rms_norm(x, gain, eps)


def rotary(q, k):
    """q, k [B, H, T, D]: `rope_theta` is null, nothing turns."""
    # dep: the place a rotary embedding would stand (docstring)
    return q, k


def full_attention(x, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """x [B, T, hidden] -> the attention block's output before its norm
    and residual; the heads are those of the weights."""
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = x.shape
    # `head_dim` is null in the published file: hidden_size over the
    # PUBLISHED head count, which a share of the heads does not change
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = qk_norm(linear(x, lw["q_proj"]), lw["q_norm"].astype(f32), eps)
    k = qk_norm(linear(x, lw["k_proj"]), lw["k_norm"].astype(f32), eps)
    v = linear(x, lw["v_proj"])
    q, k, v = (a.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    q, k = rotary(q, k)
    o = causal_attention(q, k, v, float(hd) ** -0.5)
    return linear(o.transpose(0, 2, 1, 3).reshape(b, t, -1), lw["o_proj"])


# ---- the MLP, the model ----------------------------------------------------


def gated_mlp(m, gate_proj, up_proj, down_proj):
    """W_down (silu(W_gate m) * (W_up m)) on every row of m."""
    import jax
    gate = jax.nn.silu(linear(m, gate_proj))
    return linear(gate * linear(m, up_proj), down_proj)


def refuse(cfg: Dict[str, Any]) -> None:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published MLPs are SiLU-gated")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rope_theta is null in the published config: this "
                         "reference has no rotary embedding")
    if cfg.get("attention_bias"):
        raise ValueError("the published projections have no bias")


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    import jax
    import jax.numpy as jnp

    refuse(cfg)
    eps, f32 = cfg["rms_norm_eps"], jnp.float32
    with jax.default_matmul_precision("highest"):
        h = weights["embed_tokens"].astype(f32)[tokens]
        for lw in weights["layers"]:
            mixer = gated_delta_net if "q_conv1d" in lw else full_attention
            h = sublayer(h, lambda x: mixer(x, lw, cfg),
                         lw["post_attention_layernorm"].astype(f32), eps)
            h = sublayer(h, lambda x: gated_mlp(
                x, lw["gate_proj"], lw["up_proj"], lw["down_proj"]),
                lw["post_feedforward_layernorm"].astype(f32), eps)
        h = rms_norm(h, weights["norm"].astype(f32), eps)
        return linear(h, weights["lm_head"])


def next_token_loss(logits, targets):
    """Mean cross-entropy of logits [B, T, V] against targets [B, T]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any]):
    """batch_tokens [B, T+1] -> next-token cross-entropy."""
    logits = forward(weights, batch_tokens[:, :-1], cfg)
    return next_token_loss(logits, batch_tokens[:, 1:])


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(lambda w: loss(w, batch_tokens, cfg))(weights)
