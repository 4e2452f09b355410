"""Plain reference for Xing4.0-29B-A4B (`model_type: xing4_0`), float32,
jax.numpy.

Written from the published config.json keys (the catalog row
`Xing4.0-29B-A4B`), the equations of the DeepSeek-V3 modelling code its
attention, router and YaRN keys name, and, for the keys `hc_mult`,
`hc_sinkhorn_iters`, `hc_eps` and `mhc_h_res_clamp_min/max`, the equations
of manifold-constrained hyper-connections (mHC, arXiv 2512.24880, after
hyper-connections, arXiv 2409.19606); it imports nothing from `ray_tpu`.
n = hc_mult streams of width C = hidden_size:

    X_0[i] = E[token]                for i = 1 .. n      the embedding,
                                                         repeated
    each layer, each of its two sublayers s (attention, then the dense MLP
    or the experts), with s's own phi [n*n + 2n, n*C], b, alpha:
      u      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   one statistic
               over all n*C values of a token, no gain
      m      = phi u                                     [n*n + 2n]
      H_pre  = sigmoid(alpha_0 m[0:n] + b[0:n])          [n]
      H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])      [n]
      A      = clamp(alpha_2 mat(m[2n:]) + b[2n:], clamp_min, clamp_max)
               [n, n], row-major
      M      = exp(A); hc_sinkhorn_iters times:
               M = M / (rowsum(M) + hc_eps); M = M / (colsum(M) + hc_eps)
      H_res  = M                                          doubly stochastic
      h      = sum_i H_pre[i] X[i]                        [C]
      y      = F_s(RMSNorm_s(h))          the sublayer, under its own norm
      X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y
    x_out  = sum_i X_L[i];  logits = W_head . RMSNorm(x_out; g_final)
    loss   = next-token cross-entropy (no aux loss: `noaux_tc`)

    F_attn (latent attention, as `glm4_moe_lite_f32` has it at other
    widths):
      c_q    = RMSNorm(W_qa n; g_qa);  q = W_qb c_q -> per head
               [q_nope (128) ; q_r (64)]
      [c_kv ; k_r] = W_kva n,  c_kv = RMSNorm(c_kv; g_kva),  k_r ONE head
      [k_nope ; v] = W_kvb c_kv -> per head [128 ; 128]
      q_h = [q_nope_h ; RoPE(q_r_h)],  k_h = [k_nope_h ; RoPE(k_r)]
      RoPE under YaRN (`rope_scaling`): the pair frequencies
      theta^(-2i/64) kept where a pair turns more than beta_fast times
      over original_max_position_embeddings positions, divided by factor
      where it turns fewer than beta_slow times, blended linearly between;
      cos and sin times mscale(factor, mscale) / mscale(factor,
      mscale_all_dim), which is 1 at the published mscale = mscale_all_dim
      causal softmax at (128 + 64)^-1/2 * mscale(factor, mscale_all_dim)^2
      over ALL 192 columns, mscale(f, m) = 0.1 m ln f + 1; then W_o
    F_mlp (the leading dense layers): W_down (silu(W_gate m) * (W_up m))
    F_moe: s = sigmoid(W_r m) over the E routed experts, float32; S = the
      k experts of largest s + b (e_score_correction_bias, a buffer;
      n_group = topk_group = 1); g_e = s_e / sum_{e in S} s_e
      (norm_topk_prob) times routed_scaling_factor, s WITHOUT b;
      y = shared(m) + sum_{e in S} g_e . expert_e(m)

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); every product is float32: on a
TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set, which the functions here
do themselves.

**A share.** `lw["experts"]` maps an expert's id to its weights and holds
the experts that are resident: a token-slot routed to an absent expert
adds nothing, the router still scores all E and picks k. The heads that
are resident are the rows of `q_b_proj` and `kv_b_proj` and the columns of
`o_proj` that the weights hold (`num_attention_heads` of the config given
says how many): what the absent heads would add to `o_proj`'s sum is left
out. A sliced vocabulary is a smaller vocabulary.

Weights arrive in the published layout (`y = x W^T`, W [out, in]), one
dict per layer: `input_layernorm`, `q_a_proj`, `q_a_layernorm`,
`q_b_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj`, `o_proj`,
`post_attention_layernorm`, then either `mlp` (a dense layer) or
`mlp.gate`, `e_score_correction_bias`, `experts` {id: ...} and
`shared_experts`; and the two sublayers' hyper-connections `attn_hc` and
`mlp_hc`, each {`phi` [n*n + 2n, n*C], `b` [n*n + 2n], `alpha` [3]}.
Whoever calls this converts the system's parameters.

What the row does not give (the form of the maps, where hc_eps and the
clamp sit, rows before columns, the entry and the exit, the sublayer's own
norm kept, YaRN read as DeepSeek-V3's family reads it, the rotary pairing)
is listed in the configuration file's `assumed`. Departures from the
DeepSeek-V3 modelling code, each marked `# HF:` where it is: RoPE pairs
column i with column i + 32 (rotate-half) where `rope_interleave` pairs
(2i, 2i+1); every resident expert runs on every token and a 0/weight mask
picks the chosen ones; attention is computed for a block of queries at a
time; the multi-token prediction module is absent; no mask, padding or
cache; group-limited routing is refused unless absent.

The Sinkhorn rounds and the layers are Python loops. No scan, no kernels,
no sort, no fused weights, no sharding annotations.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

QUERY_BLOCK = 1024


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def yarn_mscale(factor: float, mscale: float) -> float:
    """`yarn_get_mscale`."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rope_dim: int, theta: float, scaling: Dict[str, Any]):
    """The rotary pairs' frequencies under YaRN
    (`DeepseekV3YarnRotaryEmbedding`): pair i turns
    original * theta^(-2i/dim) / 2 pi times over the original context."""
    import jax.numpy as jnp

    original = scaling["original_max_position_embeddings"]

    def pair_that_turns(times):   # `yarn_find_correction_dim`
        return rope_dim * math.log(original / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scaling["beta_slow"])),
               rope_dim - 1)
    if low == high:
        high += 0.001
    exponents = jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim
    kept = 1.0 / theta ** exponents
    stretched = kept / scaling["factor"]
    ramp = jnp.clip((jnp.arange(rope_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return stretched * ramp + kept * (1.0 - ramp)


def rope_tables(seq: int, rope_dim: int, theta: float, scaling=None):
    """cos and sin [T, rope_dim], the angles repeated over both halves:
    x*cos + rotate_half(x)*sin. `scaling`: the config's `rope_scaling`
    (type yarn) or None."""
    import jax.numpy as jnp
    if scaling is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim))
        table_scale = 1.0
    else:
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling {scaling!r}: yarn or null")
        inv_freq = yarn_inv_freq(rope_dim, theta, scaling)
        table_scale = yarn_mscale(scaling["factor"], scaling["mscale"]) \
            / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, rope_dim]
    return jnp.cos(emb) * table_scale, jnp.sin(emb) * table_scale


def softmax_scale(cfg: Dict[str, Any]) -> float:
    """q_head_dim^-1/2, under YaRN times mscale(factor, mscale_all_dim)^2
    (`DeepseekV3Attention.__init__`)."""
    scale = float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling is not None and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"],
                             scaling["mscale_all_dim"]) ** 2
    return scale


def apply_rope(x, cos, sin):
    """x [B, H, T, rope_dim]."""
    # HF: rope_interleave de-interleaves the columns first (docstring)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def causal_attention(q, k, v, scale: float, block: int = QUERY_BLOCK):
    """q, k [B, H, T, D], v [B, H, T, Dv] -> [B, H, T, Dv]."""
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


def latent_attention(n, lw: Dict[str, Any], cfg: Dict[str, Any], cos, sin):
    """n [B, T, hidden] (normed) -> the attention block's output before
    the residual, [B, T, hidden], from the heads the weights hold."""
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = n.shape
    nh = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kvr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]

    c_q = rms_norm(linear(n, lw["q_a_proj"]),
                   lw["q_a_layernorm"].astype(f32), eps)
    q = linear(c_q, lw["q_b_proj"]).reshape(b, t, nh, nope + rope)
    q = q.transpose(0, 2, 1, 3)                           # [B, H, T, .]
    q_nope, q_r = q[..., :nope], q[..., nope:]

    ckv = linear(n, lw["kv_a_proj_with_mqa"])             # [B, T, kvr+rope]
    c_kv = rms_norm(ckv[..., :kvr], lw["kv_a_layernorm"].astype(f32), eps)
    k_r = ckv[..., kvr:][:, None]                         # [B, 1, T, rope]
    kv = linear(c_kv, lw["kv_b_proj"]).reshape(b, t, nh, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    q_r = apply_rope(q_r, cos, sin)
    k_r = jnp.broadcast_to(apply_rope(k_r, cos, sin), (b, nh, t, rope))
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate([k_nope, k_r], axis=-1)
    o = causal_attention(q, k, v, softmax_scale(cfg))
    return linear(o.transpose(0, 2, 1, 3).reshape(b, t, nh * vd),
                  lw["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    """W_down (silu(W_gate m) * (W_up m)) on every row of m."""
    import jax
    gate = jax.nn.silu(linear(m, gate_proj))
    return linear(gate * linear(m, up_proj), down_proj)


@functools.lru_cache(maxsize=None)
def _compiled_gated_mlp():
    """`gated_mlp` under `jax.jit`: called op by op (as the benchmark's
    job does on the chip) the loop over the experts then compiles one
    expert once. Same arithmetic."""
    import jax
    return jax.jit(gated_mlp)


def route(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (weights [N, k], expert ids [N, k], scores
    [N, E]): sigmoid scores, the choice by score + bias, the weights the
    chosen scores without it, normalised and scaled."""
    import jax
    import jax.numpy as jnp

    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not in this reference")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc" \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the published router is sigmoid, noaux_tc")
    scores = jax.nn.sigmoid(linear(m, lw["mlp.gate"]).astype(jnp.float32))
    choice = scores + lw["e_score_correction_bias"].astype(jnp.float32)
    _, top_e = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    return top_w * cfg["routed_scaling_factor"], top_e, scores


def routed_experts(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (the resident experts' part of the routed sum
    [N, hidden], expert ids [N, k])."""
    import jax.numpy as jnp

    top_w, top_e, _ = route(m, lw, cfg)
    y = jnp.zeros_like(m)
    for e, ew in sorted(lw["experts"].items()):
        # HF: gathers the tokens that chose e; here every token runs
        # through e and those that did not choose it get weight 0
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * _compiled_gated_mlp()(
            m, ew["gate_proj"], ew["up_proj"], ew["down_proj"])
    return y, top_e


def shared_experts(m, lw: Dict[str, Any]):
    sw = lw["shared_experts"]
    return _compiled_gated_mlp()(m, sw["gate_proj"], sw["up_proj"],
                                 sw["down_proj"])


# ---- the streams ---------------------------------------------------------


def sinkhorn(m, rounds: int, eps: float):
    """m [..., n, n] positive -> `rounds` times: every row divided by its
    sum + eps, then every column by its sum + eps."""
    for _ in range(rounds):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def normed_stream(x, eps: float):
    """u [B, T, n*C]: the flattened stream over its RMS, ONE statistic a
    token over all n*C values, no gain."""
    import jax.numpy as jnp
    b, t, n, c = x.shape
    flat = x.reshape(b, t, n * c)
    return flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + eps)


def enter_streams(e, n: int):
    """The embedding e [B, T, C] repeated into n streams."""
    import jax.numpy as jnp
    return jnp.broadcast_to(e[:, :, None, :],
                            e.shape[:2] + (n, e.shape[-1]))


def leave_streams(x):
    """The streams [B, T, n, C] summed."""
    import jax.numpy as jnp
    return jnp.sum(x, axis=2)


def stream_maps(x, hw: Dict[str, Any], cfg: Dict[str, Any]):
    """The stream x [B, T, n, C] and a sublayer's hyper-connection weights
    -> (H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    b, t, n, _ = x.shape
    m = linear(normed_stream(x, cfg["rms_norm_eps"]), hw["phi"])
    alpha, bias = hw["alpha"].astype(f32), hw["b"].astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + bias[n:2 * n])
    a = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    a = jnp.clip(a, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(a), cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    return pre, post, res


def hyper_connected(x, hw: Dict[str, Any], gain, cfg: Dict[str, Any],
                    sublayer):
    """One sublayer on the streams x [B, T, n, C]: `sublayer` maps the
    normed mix [B, T, C] to y [B, T, C] -> (X', the three maps as one
    record [B, T, n*n + 2n]: H_pre, H_post, H_res row by row)."""
    import jax.numpy as jnp

    b, t, n, _ = x.shape
    pre, post, res = stream_maps(x, hw, cfg)
    h = jnp.einsum("bti,btic->btc", pre, x)
    y = sublayer(rms_norm(h, gain.astype(jnp.float32), cfg["rms_norm_eps"]))
    out = jnp.einsum("btij,btjc->btic", res, x) \
        + post[..., None] * y[:, :, None, :]
    return out, jnp.concatenate([pre, post, res.reshape(b, t, n * n)],
                                axis=-1)


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_routing: bool = False, with_maps: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every expert layer's chosen ids [B*T, k], and every
    sublayer's maps [sublayers, B, T, n*n + 2n], attention's before the
    MLP's or the experts' layer by layer)."""
    import jax
    import jax.numpy as jnp

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published MLPs are SiLU-gated")
    if cfg.get("attention_bias"):
        raise ValueError("attention_bias is false in the published config")
    theta, n = float(cfg["rope_theta"]), cfg["hc_mult"]
    f32 = jnp.float32
    chosen: List[Any] = []
    maps: List[Any] = []
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        cos, sin = rope_tables(t, cfg["qk_rope_head_dim"], theta,
                               cfg.get("rope_scaling"))
        x = enter_streams(weights["embed_tokens"].astype(f32)[tokens], n)
        for lw in weights["layers"]:
            x, found = hyper_connected(
                x, lw["attn_hc"], lw["input_layernorm"], cfg,
                lambda m: latent_attention(m, lw, cfg, cos, sin))
            maps.append(found)

            def ffn(m):
                if "mlp" in lw:     # a leading dense layer
                    mw = lw["mlp"]
                    return gated_mlp(m, mw["gate_proj"], mw["up_proj"],
                                     mw["down_proj"])
                flat = m.reshape(b * t, -1)
                y, top_e = routed_experts(flat, lw, cfg)
                chosen.append(top_e)
                return (y + shared_experts(flat, lw)).reshape(b, t, -1)

            x, found = hyper_connected(
                x, lw["mlp_hc"], lw["post_attention_layernorm"], cfg, ffn)
            maps.append(found)
        h = rms_norm(leave_streams(x), weights["norm"].astype(f32),
                     cfg["rms_norm_eps"])
        logits = linear(h, weights["lm_head"])
    out = (logits,)
    if with_routing:
        out += (chosen,)
    if with_maps:
        out += (jnp.stack(maps),)
    return out if len(out) > 1 else logits


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
