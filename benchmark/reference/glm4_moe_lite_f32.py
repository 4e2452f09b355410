"""Plain reference for GLM-4.7-Flash (`model_type: glm4_moe_lite`),
float32, jax.numpy.

Written from the published config.json keys and the equations of the
`Glm4MoeLiteForCausalLM` modelling code they name (DeepSeek-V3's
multi-head latent attention and `noaux_tc` router), importing nothing from
`ray_tpu`:

    h_0    = E[tokens]
    n      = RMSNorm(h_l; g_in)
    c_q    = RMSNorm(W_qa n; g_qa)                          [q_lora_rank]
    q      = W_qb c_q  -> per head [q_nope (192) ; q_r (64)]
    [c_kv ; k_r] = W_kva n         [kv_lora_rank ; 64], k_r ONE head
    c_kv   = RMSNorm(c_kv; g_kva)
    [k_nope ; v] = W_kvb c_kv  -> per head [192 ; 256]
    q_h    = [q_nope_h ; RoPE(q_r_h)],  k_h = [k_nope_h ; RoPE(k_r)]
              (the rotary key head is shared by every head; RoPE touches
              the 64 rotary columns only)
    a_l    = h_l + W_o . CausalAttn(q, k, v),  scale (192 + 64)^-1/2
    m      = RMSNorm(a_l; g_post)
    layers 0 .. first_k_dense_replace-1 (width intermediate_size):
      h_l+1 = a_l + W_down (silu(W_gate m) * (W_up m))
    the layers after them (experts of width moe_intermediate_size):
      s      = sigmoid(W_r m) over the E routed experts, float32
      S      = the k experts of largest s + b   (b: e_score_correction_bias,
               a buffer; n_group = topk_group = 1, so no group limit)
      g_e    = s_e / sum_{e in S} s_e  (norm_topk_prob), times
               routed_scaling_factor        -- s WITHOUT b
      h_l+1  = a_l + shared(m) + sum_{e in S} g_e . expert_e(m)
    logits = W_head . RMSNorm(h_L; g_final)
    loss   = next-token cross-entropy (no aux loss: `noaux_tc`)

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); every product is float32: on a
TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set, which the functions here
do themselves.

**A share.** `lw["experts"]` maps an expert's id to its weights and holds
the experts that are resident: with all E of them this is the whole
model; with a share it leaves out what the absent experts would add (a
token-slot routed to an absent expert adds nothing; the router still
scores all E and picks k). A sliced vocabulary is a smaller vocabulary:
`embed_tokens` and `lm_head` simply have fewer rows.

Weights arrive in the published layout (`y = x W^T`, W of shape
[out, in]), one dict per layer: `input_layernorm`, `q_a_proj`,
`q_a_layernorm`, `q_b_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`,
`kv_b_proj`, `o_proj`, `post_attention_layernorm`, then either `mlp` (a
dense layer: `gate_proj`, `up_proj`, `down_proj`) or `mlp.gate` (the
router, [E, hidden]) with `e_score_correction_bias` [E], `experts` {id:
{`gate_proj`, `up_proj` [width, hidden], `down_proj` [hidden, width]}} and
`shared_experts` (one gated MLP of width n_shared_experts x the expert
width). Whoever calls this converts the system's parameters.

Departures from the HF modelling code, each marked `# HF:` where it is:
RoPE pairs column i with column i + 32 of the rotary part (rotate-half),
where HF's `rope_interleave` first de-interleaves pairs (2i, 2i+1): the
same function up to a fixed permutation of the rotary columns of `q_b_proj`
and `kv_a_proj_with_mqa`, immaterial under seeded weights; every resident
expert runs on every token and a 0/weight mask picks the chosen ones (HF
gathers each expert's tokens and adds them back with `index_add_`: same
sum); attention is computed for a block of queries at a time against the
keys up to the block's end, so that one 8,192-token sequence fits beside
the weights on a chip (same softmax, row by row); the multi-token
prediction layer (`num_nextn_predict_layers`) is absent, as HF drops its
weights on load; no attention mask, padding or cache; `rope_scaling` and
group-limited routing are refused unless absent (the published values).

No kernels, no sort, no fused weights, no sharding annotations.
"""

from __future__ import annotations

import functools
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


def rope_tables(seq: int, rope_dim: int, theta: float):
    """angle_i(p) = p * theta^(-2i/rope_dim), i < rope_dim/2, the cos/sin
    tables repeated over both halves: x*cos + rotate_half(x)*sin."""
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, rope_dim]
    return jnp.cos(emb), jnp.sin(emb)


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
    the residual, [B, T, hidden]."""
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
    o = causal_attention(q, k, v, float(nope + rope) ** -0.5)
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
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("the published router is noaux_tc")
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


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_routing: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every expert layer's chosen ids [B*T, k])."""
    import jax
    import jax.numpy as jnp

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published MLPs are SiLU-gated")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is null in the published config")
    if cfg.get("attention_bias"):
        raise ValueError("attention_bias is false in the published config")
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    f32 = jnp.float32
    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        cos, sin = rope_tables(t, cfg["qk_rope_head_dim"], theta)
        h = weights["embed_tokens"].astype(f32)[tokens]
        for lw in weights["layers"]:
            n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)
            h = h + latent_attention(n, lw, cfg, cos, sin)
            m = rms_norm(h, lw["post_attention_layernorm"].astype(f32), eps)
            if "mlp" in lw:     # a leading dense layer
                mw = lw["mlp"]
                h = h + gated_mlp(m, mw["gate_proj"], mw["up_proj"],
                                  mw["down_proj"])
                continue
            flat = m.reshape(b * t, -1)
            y, top_e = routed_experts(flat, lw, cfg)
            chosen.append(top_e)
            h = h + (y + shared_experts(flat, lw)).reshape(b, t, -1)
        h = rms_norm(h, weights["norm"].astype(f32), eps)
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
    each of the E routed experts (the system's counter holds the resident
    experts' columns of it)."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sum(jax.nn.one_hot(
        top_e.reshape(-1), n_experts, dtype=jnp.int32), axis=0)
        for top_e in chosen])


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any]):
    """batch_tokens [B, T+1] -> next-token cross-entropy, as
    `Glm4MoeLiteForCausalLM.forward` with labels (no aux term)."""
    logits = forward(weights, batch_tokens[:, :-1], cfg)
    return next_token_loss(logits, batch_tokens[:, 1:])


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`; the bias is
    integer-like to autodiff (it only enters the choice): its gradient is
    zero."""
    import jax
    return jax.value_and_grad(lambda w: loss(w, batch_tokens, cfg))(weights)
