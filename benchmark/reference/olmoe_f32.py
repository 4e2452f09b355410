"""Plain reference for OLMoE (`model_type: olmoe`), float32, jax.numpy.

Written from the published equations (OLMoE, arXiv:2409.02060) and the
`OlmoeForCausalLM` modelling code that the published config.json names,
importing nothing from `ray_tpu`:

    h_0   = E[tokens]
    n     = RMSNorm(h_l; g_in)
    q     = RMSNorm(W_q n; g_q),  k = RMSNorm(W_k n; g_k),  v = W_v n
              (QK-norm over the WHOLE projection, all heads together,
              before the split into heads and before RoPE)
    a_l   = h_l + W_o . CausalAttn(RoPE(q), RoPE(k), v)
    m     = RMSNorm(a_l; g_post)
    p     = softmax(W_r m) over the E experts, float32
    S     = the k experts of largest p;  weights p_e for e in S,
              renormalised to sum to 1 only if `norm_topk_prob`
    h_l+1 = a_l + sum_{e in S} p_e . W_down^e (silu(W_gate^e m) * (W_up^e m))
    logits = W_head . RMSNorm(h_L; g_final)
    loss  = next-token CE + router_aux_loss_coef . aux
    aux   = E . sum_e f_e . P_e    (`load_balancing_loss`, below)

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); RoPE is the rotate-half form
(see `rope_tables`); attention is multi-head or grouped-query, causal,
scaled by 1/sqrt(d_head), softmax in float32. Every product is float32:
on a TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set, which the functions
here do themselves.

Weights arrive in the published layout (`y = x W^T`, W of shape
[out, in]), one dict per layer: `input_layernorm`, `q_proj`, `k_proj`,
`v_proj`, `o_proj`, `q_norm`, `k_norm`, `post_attention_layernorm`,
`mlp.gate` (the router, [E, hidden]) and `experts`, a list of E dicts
`gate_proj`, `up_proj` [width, hidden], `down_proj` [hidden, width].
Whoever calls this converts the system's parameters.

Departures from the HF modelling code, each marked `# HF:` where it is:
every expert runs on every token and a 0/1 mask picks the chosen ones
(HF gathers each expert's tokens with `torch.where` and adds them back
with `index_add_`: same sum, no gather, no scatter, no capacity); the
routing weights stay float32 (HF casts them to the hidden dtype, which is
float32 here anyway); no attention mask, no padding, no cache; `clip_qkv`
is refused unless null (the published value).

No kernels, no sort, no batching tricks, no sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_tables(seq: int, head_dim: int, theta: float):
    """angle_i(p) = p * theta^(-2i/d_head), i < d_head/2, the cos/sin
    tables repeated over both halves: x*cos + rotate_half(x)*sin."""
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, head_dim]
    return jnp.cos(emb), jnp.sin(emb)


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def causal_attention(q, k, v):
    """q, k, v [B, H, T, D] (k, v already repeated) -> [B, H, T, D]."""
    import jax
    import jax.numpy as jnp
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def expert_mlp(m, gate_proj, up_proj, down_proj):
    """One expert on every row of m: W_down (silu(W_gate m) * (W_up m))."""
    import jax
    gate = jax.nn.silu(linear(m, gate_proj))
    return linear(gate * linear(m, up_proj), down_proj)


@functools.lru_cache(maxsize=None)
def _compiled_expert_mlp():
    """`expert_mlp` under `jax.jit`: called op by op (as the benchmark's
    job does on the chip) the loop below then compiles one expert once
    and runs it E times, where the whole loop unrolled into one program
    takes the chip's compiler a minute and a half. Same arithmetic."""
    import jax
    return jax.jit(expert_mlp)


def sparse_moe(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (y [N, hidden], router logits [N, E])."""
    import jax
    import jax.numpy as jnp

    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    router_logits = linear(m, lw["mlp.gate"])
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # [N, k]
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(m)
    for e in range(n_experts):
        ew = lw["experts"][e]
        # HF: gathers the tokens that chose e; here every token runs
        # through e and those that did not choose it get weight 0
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * _compiled_expert_mlp()(
            m, ew["gate_proj"], ew["up_proj"], ew["down_proj"])
    return y, router_logits


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_router_logits: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every layer's router logits [B*T, E])."""
    import jax
    import jax.numpy as jnp

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published expert is SiLU-gated")
    if cfg.get("clip_qkv") is not None:
        raise ValueError("clip_qkv is null in the published config")
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    f32 = jnp.float32
    router_logits: List[Any] = []
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        cos, sin = rope_tables(t, hd, theta)
        h = weights["embed_tokens"].astype(f32)[tokens]
        for lw in weights["layers"]:
            n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)
            q = rms_norm(linear(n, lw["q_proj"]),
                         lw["q_norm"].astype(f32), eps)
            k = rms_norm(linear(n, lw["k_proj"]),
                         lw["k_norm"].astype(f32), eps)
            v = linear(n, lw["v_proj"])

            def heads(x, n_heads):
                return x.reshape(b, t, n_heads, hd).transpose(0, 2, 1, 3)

            q, k, v = heads(q, nh), heads(k, nkv), heads(v, nkv)
            q = q * cos + rotate_half(q) * sin
            k = k * cos + rotate_half(k) * sin
            k = jnp.repeat(k, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            o = causal_attention(q, k, v)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
            h = h + linear(o, lw["o_proj"])
            m = rms_norm(h, lw["post_attention_layernorm"].astype(f32), eps)
            y, logits_l = sparse_moe(m.reshape(b * t, -1), lw, cfg)
            router_logits.append(logits_l)
            h = h + y.reshape(b, t, -1)
        h = rms_norm(h, weights["norm"].astype(f32), eps)
        logits = linear(h, weights["lm_head"])
    return (logits, router_logits) if with_router_logits else logits


def next_token_loss(logits, targets):
    """Mean cross-entropy of logits [B, T, V] against targets [B, T]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def load_balancing_loss(router_logits: List[Any], cfg: Dict[str, Any]):
    """HF `load_balancing_loss_func` without an attention mask: the
    layers' router logits concatenated into [L*N, E]; P_e the mean over
    those rows of softmax(logits)_e; the one-hot of the top-k choices
    [L*N, k, E] averaged over the rows into [k, E] and multiplied with P
    broadcast over k, summed over k and E, times E. So f_e, summed over
    the k slots, is the share of tokens that chose e and sums to k over
    the experts: the loss is k (not 1) when routing is uniform."""
    import jax
    import jax.numpy as jnp

    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = jnp.concatenate(router_logits, axis=0).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32)
    tokens_per_expert = jnp.mean(chosen, axis=0)            # [k, E]
    router_prob_per_expert = jnp.mean(probs, axis=0)        # [E]
    return n_experts * jnp.sum(
        tokens_per_expert * router_prob_per_expert[None, :])


def tokens_per_expert(router_logits: List[Any], cfg: Dict[str, Any]):
    """[L, E] int32: how many of a layer's N*k token-slots chose each
    expert (what the system's counter must equal)."""
    import jax
    import jax.numpy as jnp
    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    rows = []
    for logits in router_logits:
        _, top_e = jax.lax.top_k(jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1), k)
        rows.append(jnp.sum(jax.nn.one_hot(
            top_e.reshape(-1), n_experts, dtype=jnp.int32), axis=0))
    return jnp.stack(rows)


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any],
         aux_coef: Optional[float] = None):
    """batch_tokens [B, T+1] -> (CE + aux_coef * aux, CE, aux), as
    `OlmoeForCausalLM.forward` with `output_router_logits=True`."""
    coef = cfg["router_aux_loss_coef"] if aux_coef is None else aux_coef
    logits, router_logits = forward(
        weights, batch_tokens[:, :-1], cfg, with_router_logits=True)
    ce = next_token_loss(logits, batch_tokens[:, 1:])
    aux = load_balancing_loss(router_logits, cfg)
    return ce + coef * aux, ce, aux


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any], aux_coef: Optional[float] = None):
    """((total, CE, aux), d total / d weights) by `jax.grad` of `loss`."""
    import jax

    def total(w):
        out = loss(w, batch_tokens, cfg, aux_coef)
        return out[0], out

    (_, parts), grads = jax.value_and_grad(total, has_aux=True)(weights)
    return parts, grads
