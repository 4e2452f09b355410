"""Plain reference for Ouro-2.6B (the catalog row `Ouro-2.6B`, `config.json`,
`model_type: ouro`; "Scaling Latent Reasoning via Looped Language Models",
arXiv 2510.25741), float32, jax.numpy.

Written from the published config.json keys and the equations ISSUE 63
writes down from them, importing nothing from `ray_tpu`. R =
`total_ut_steps` passes through ONE set of weights, a Python loop over the
passes and over the layers:

    x = E[tokens]
    for t = 1 .. R:
      for l = 1 .. n:                       the sandwich norm: four gains
        x = x + RMSNorm_l,2(Attn_l(RMSNorm_l,1(x)))
              16 / 16 heads of 128, RoPE (rotate-half, theta 1e6) on q and
              k with the SAME position ids in every pass, causal softmax at
              128^-1/2, no bias, no QK-norm
        x = x + RMSNorm_l,4(W_down (silu(W_gate h) * (W_up h))),
              h = RMSNorm_l,3(x)
      h_t      = RMSNorm_f(x);  x = h_t     the final norm closes every
                                            pass; the next pass reads h_t
      logits_t = W_head h_t                 the one untied head
      z_t      = h_t . w_g + b_g            the exit gate

    log p_t = log sigmoid(z_t) + sum_{j<t} log sigmoid(-z_j)     t < R
    log p_R =                    sum_{j<R} log sigmoid(-z_j)
    loss = mean_i [ sum_t p_t(i) nll_t(i) - beta H(p(i)) ],
           H(p) = -sum_t p_t log p_t,  nll_t(i) the next-token
           cross-entropy of logits_t(i);  beta = `exit_entropy_coeff`

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); every product is float32 under
`jax.default_matmul_precision("highest")`, which the functions here set
themselves.

Weights arrive in the family's layout (`y = x W^T`, W of shape [out, in]),
one dict per layer: `input_layernorm` (before attention),
`input_layernorm_2` (on attention's output), `post_attention_layernorm`
(before the MLP), `post_attention_layernorm_2` (on the MLP's output),
`q_proj`, `k_proj`, `v_proj`, `o_proj`, `gate_proj`, `up_proj`,
`down_proj`; beside them `embed_tokens`, `norm`, `lm_head` and
`early_exit_gate` = {`weight` [1, hidden], `bias` [1]}.

The gate's gradient needs no backward pass through the layers: h_t does
not depend on the gate, so d loss / d (w_g, b_g) is the gradient of
`exit_loss` through z alone, with h_t and nll_t as the forward pass gave
them (`gate_gradient`).

Departures from a modelling file, each marked `# dep:` where it is:
attention is computed for a block of queries at a time; no mask, padding
or cache; `early_exit_threshold` is read by nothing (no token leaves
early in training).

No kernels, no scan, no fused weights, no sharding annotations.
"""

from __future__ import annotations

from typing import Any, Dict, List

QUERY_BLOCK = 1024


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_tables(positions, head_dim: int, theta: float):
    """positions [T] -> cos, sin [T, head_dim], the angles repeated over
    both halves."""
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def pass_positions(t: int, seq: int):
    """The position ids of pass t (0-based): the same in every pass."""
    import jax.numpy as jnp
    return jnp.arange(seq)


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


def sublayer(x, f, before, after, eps):
    """The sandwich norm: a norm before the sublayer and one on its
    output."""
    return x + rms_norm(f(rms_norm(x, before, eps)), after, eps)


def attention(h, lw: Dict[str, Any], cfg: Dict[str, Any], cos, sin):
    """h [B, T, hidden] (normed) -> W_o Attn(...)."""
    b, t, _ = h.shape
    hd = cfg["head_dim"]
    q, k, v = (linear(h, lw[p + "_proj"]).reshape(b, t, -1, hd)
               .transpose(0, 2, 1, 3) for p in "qkv")
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    o = causal_attention(q, k, v, float(hd) ** -0.5)
    return linear(o.transpose(0, 2, 1, 3).reshape(b, t, -1), lw["o_proj"])


def gated_mlp(m, lw: Dict[str, Any]):
    """W_down (silu(W_gate m) * (W_up m)) on every row of m."""
    import jax
    gate = jax.nn.silu(linear(m, lw["gate_proj"]))
    return linear(gate * linear(m, lw["up_proj"]), lw["down_proj"])


def layer(x, lw: Dict[str, Any], cfg: Dict[str, Any], cos, sin):
    import jax.numpy as jnp
    f32, eps = jnp.float32, cfg["rms_norm_eps"]
    x = sublayer(x, lambda h: attention(h, lw, cfg, cos, sin),
                 lw["input_layernorm"].astype(f32),
                 lw["input_layernorm_2"].astype(f32), eps)
    return sublayer(x, lambda h: gated_mlp(h, lw),
                    lw["post_attention_layernorm"].astype(f32),
                    lw["post_attention_layernorm_2"].astype(f32), eps)


def close_pass(x, weights: Dict[str, Any], cfg: Dict[str, Any]):
    """(h_t, what the next pass reads): the final norm closes the pass
    and the next pass reads the normed stream."""
    import jax.numpy as jnp
    h = rms_norm(x, weights["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return h, h


def exit_gate(h, x, weights: Dict[str, Any]):
    """z_t [B, T] from the pass's normed hidden state h (`x` is the
    stream before the final norm, which the gate does not read)."""
    import jax.numpy as jnp
    f32, gate = jnp.float32, weights["early_exit_gate"]
    # one scalar a token: a product by elements and a sum, float32
    return jnp.sum(h.astype(f32) * gate["weight"].astype(f32)[0], axis=-1) \
        + gate["bias"].astype(f32)[0]


def refuse(cfg: Dict[str, Any]) -> None:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published MLPs are SiLU-gated")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the published attention has one key/value head "
                         "a query head")
    if cfg.get("rope_scaling") or cfg.get("use_sliding_window"):
        raise ValueError("the published config scales no RoPE and has no "
                         "window")
    if set(cfg.get("layer_types", ["full_attention"])) != {"full_attention"}:
        raise ValueError("every published layer is full_attention")


def passes(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, T] int32 -> (the R passes' hidden states, each
    [B, T, hidden], the R gates' z, each [B, T], and the R streams before
    the final norm, which `gate_gradient` hands `exit_gate` again),
    float32."""
    import jax
    import jax.numpy as jnp

    refuse(cfg)
    hs: List[Any] = []
    zs: List[Any] = []
    streams: List[Any] = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed_tokens"].astype(jnp.float32)[tokens]
        for t in range(cfg["total_ut_steps"]):
            cos, sin = rope_tables(pass_positions(t, tokens.shape[1]),
                                   cfg["head_dim"], cfg["rope_theta"])
            for lw in weights["layers"]:
                x = layer(x, lw, cfg, cos, sin)
            stream = x
            h, x = close_pass(stream, weights, cfg)
            hs.append(h)
            streams.append(stream)
            zs.append(exit_gate(h, stream, weights))
    return hs, zs, streams


def logits_of(weights: Dict[str, Any], h):
    """A pass's hidden state [B, T, hidden] -> logits [B, T, vocab]."""
    import jax
    with jax.default_matmul_precision("highest"):
        return linear(h, weights["lm_head"])


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, T] -> every pass's logits [R, B, T, vocab]."""
    import jax.numpy as jnp
    hs, _, _ = passes(weights, tokens, cfg)
    return jnp.stack([logits_of(weights, h) for h in hs])


def token_nll(logits, targets):
    """logits [B, T, V], targets [B, T] -> the cross-entropy [B, T]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def exit_distribution(zs):
    """The R gates' z -> the R log-probabilities of leaving after each
    pass, from log-sigmoids: the last pass takes what is left."""
    import jax
    import jax.numpy as jnp
    log_p, stayed = [], jnp.zeros_like(zs[0])
    for z in zs[:-1]:
        log_p.append(jax.nn.log_sigmoid(z) + stayed)
        stayed = stayed + jax.nn.log_sigmoid(-z)
    return log_p + [stayed]


def exit_loss(log_p, nll, beta: float):
    """mean_i [ sum_t p_t nll_t - beta H(p) ] from the R passes' log p
    and cross-entropies, each [B, T]."""
    import jax.numpy as jnp
    expected = sum(jnp.exp(lp) * n for lp, n in zip(log_p, nll))
    entropy = -sum(jnp.exp(lp) * lp for lp in log_p)
    return jnp.mean(expected - beta * entropy)


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any]):
    """batch_tokens [B, T+1] -> the loss."""
    tokens, targets = batch_tokens[:, :-1], batch_tokens[:, 1:]
    hs, zs, _ = passes(weights, tokens, cfg)
    nll = [token_nll(logits_of(weights, h), targets) for h in hs]
    return exit_loss(exit_distribution(zs), nll, cfg["exit_entropy_coeff"])


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(lambda w: loss(w, batch_tokens, cfg))(weights)


def gate_gradient(weights: Dict[str, Any], hs, streams, nll,
                  cfg: Dict[str, Any]):
    """d loss / d early_exit_gate from the forward pass alone: the passes'
    hidden states `hs` (and `streams`), and cross-entropies `nll` as
    `passes` and `token_nll` gave them, the loss a function of the gate
    through z."""
    import jax

    def of_gate(gate):
        with jax.default_matmul_precision("highest"):
            zs = [exit_gate(h, x, {"early_exit_gate": gate})
                  for h, x in zip(hs, streams)]
        return exit_loss(exit_distribution(zs), nll,
                         cfg["exit_entropy_coeff"])

    return jax.grad(of_gate)(weights["early_exit_gate"])
