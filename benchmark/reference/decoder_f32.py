"""Plain reference for a Llama/Mistral-style decoder, float32, jax.numpy.

Written from the published equations of the architecture (Mistral 7B,
arXiv:2310.06825, and the `MistralForCausalLM` modelling code the
published config.json names), importing nothing from `ray_tpu.models`:

    h_0   = E[tokens]
    a_l   = h_l + W_o . Attn(RoPE(W_q n), RoPE(W_k n), W_v n),
                                  n = RMSNorm(h_l; g_in, eps)
    h_l+1 = a_l + W_down . (silu(W_gate m) * (W_up m)),
                                  m = RMSNorm(a_l; g_post, eps)
    logits = W_head . RMSNorm(h_L; g_final, eps)

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps). RoPE is the rotate-half form:
with angle_i(p) = p * theta^(-2i/d_head), i < d_head/2, and the cos/sin
tables repeated over both halves, x*cos + rotate_half(x)*sin where
rotate_half(x) = concat(-x[half:], x[:half]). Grouped-query attention:
query head h reads key/value head h // (n_heads // n_kv_heads). The mask
is causal and windowed: query i sees key j iff 0 <= i - j < sliding_window
(full causal attention when the window is absent or >= the length).
Softmax and every product in float32; on a TPU a float32 matmul runs in
lower precision unless the caller sets
`jax.default_matmul_precision("highest")`, which `forward` does itself.

Weights arrive in the published layout (`y = x W^T`, W of shape
[out, in]); whoever calls this converts the system's parameters.
No kernels, no cache, no batching tricks, no sharding annotations.
"""

from __future__ import annotations

from typing import Any, Dict


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_tables(seq: int, head_dim: int, theta: float):
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, head_dim]
    return jnp.cos(emb), jnp.sin(emb)


def attention(q, k, v, window):
    """q [B, H, T, D], k/v [B, H, T, D] (already repeated) -> [B, H, T, D]."""
    import jax
    import jax.numpy as jnp
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    visible = (j <= i)
    if window:
        visible = visible & (i - j < window)
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    import jax
    import jax.numpy as jnp

    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window")
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        cos, sin = rope_tables(t, hd, theta)
        h = weights["embed_tokens"].astype(f32)[tokens]
        for lw in weights["layers"]:
            n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)

            def heads(x, w, n_heads):
                y = x @ w.astype(f32).T
                return y.reshape(b, t, n_heads, hd).transpose(0, 2, 1, 3)

            q = heads(n, lw["q_proj"], nh)
            k = heads(n, lw["k_proj"], nkv)
            v = heads(n, lw["v_proj"], nkv)
            q = q * cos + rotate_half(q) * sin
            k = k * cos + rotate_half(k) * sin
            k = jnp.repeat(k, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            o = attention(q, k, v, window)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
            h = h + o @ lw["o_proj"].astype(f32).T
            m = rms_norm(h, lw["post_attention_layernorm"].astype(f32), eps)
            gate = jax.nn.silu(m @ lw["gate_proj"].astype(f32).T)
            up = m @ lw["up_proj"].astype(f32).T
            h = h + (gate * up) @ lw["down_proj"].astype(f32).T
        h = rms_norm(h, weights["norm"].astype(f32), eps)
        return h @ weights["lm_head"].astype(f32).T


def next_token_loss(logits, targets):
    """Mean cross-entropy of logits [B, T, V] against targets [B, T]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
