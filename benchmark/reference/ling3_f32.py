"""Plain reference for Ling-3.0-flash's language model (the catalog row
`Ling-3.0-flash-VL`, `config.json`'s language model), float32, jax.numpy.

Written from the published config.json keys and the equations ISSUE 50
writes down from them (Kimi Delta Attention, arXiv 2510.26692; DeepSeek-V2's
latent attention without a query latent; DeepSeek-V3's group-limited
`noaux_tc` router), importing nothing from `ray_tpu`. x~ = RMSNorm(x; g),
every sublayer is `x + f(x~)`:

    h_0 = E[tokens]
    a KDA layer (`q_conv1d` among its weights), per head of D = 128:
      q^, k^, v^ = silu(conv4(W_q x~)), silu(conv4(W_k x~)), silu(conv4(W_v x~))
                   depthwise causal convolutions of 4 taps, no bias
      q_t = q^_t / |q^_t|,  k_t = k^_t / |k^_t|          (L2 over the head)
      log a_t = lower * sigmoid(exp(A_h) * (W_f x~ + b_f))    per channel,
                   lower = kda_lower_bound = -5: a_t in (e^-5, 1)^D
      beta_t  = sigmoid(w_b x~)                          a scalar a head
      S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
      o_t = D^-1/2 S_t^T q_t              STEP BY STEP: one `lax.scan`
                   over the tokens, no chunks, no WY form
      y_t = RMSNorm(o_t; g_o) * sigmoid(w_g x~)          gate: a scalar a head
      x   = x + W_o concat_h(y_t)                        no rotary embedding
    an MLA layer (`kv_a_proj_with_mqa` among its weights):
      q = W_q x~ -> per head [q_nope (128) ; q_r (64)]   NO query latent
      [c ; k_r] = W_kva x~   [kv_lora_rank ; 64], k_r ONE head
      [k_nope ; v] = W_kvb RMSNorm(c; g_kva)  -> per head [128 ; 128]
      q_h = RMSNorm(q_h; g_q),  k_h = RMSNorm([k_nope_h ; k_r]; g_k)
                   over the head's 192 columns, before RoPE
      RoPE on the last 64 columns of q_h and k_h (rotate-half)
      x = x + W_o CausalAttn(q, k, v),  scale 192^-1/2
    then, m = RMSNorm(x; g_post):
      a dense layer (`mlp`):  x = x + W_down (silu(W_gate m) * (W_up m))
      an expert layer:
        s   = sigmoid(W_r m) over the E routed experts, float32
        c   = s + b (e_score_correction_bias, a buffer), in n_group groups
        a group's rank = the sum of its 2 largest c; the topk_group best
        groups stay (ties to the lower index); S = the k largest c among
        the experts of those groups
        g_e = s_e / sum_{e in S} s_e (norm_topk_prob), times
              routed_scaling_factor                      -- s WITHOUT b
        x   = x + shared(m) + sum_{e in S} g_e . expert_e(m)
    logits = W_head RMSNorm(h_L; g_final)
    loss   = next-token cross-entropy (no aux loss)

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); every product is float32 under
`jax.default_matmul_precision("highest")`, which the functions here set
themselves.

**A share.** The heads a layer holds are read off its weights (`A_log`
has one entry a KDA head; `q_proj` has 192 rows an MLA head): given the
rows of the projections, the channels of the convolutions and the columns
of `o_proj` that belong to some heads, the layer computes that share's
part of `o_proj`'s sum. `lw["experts"]` maps an expert's id to its weights
and holds the resident ones (a token-slot routed to an absent expert adds
nothing; the router still scores all E). A sliced vocabulary is a smaller
vocabulary.

Weights arrive in a published-style layout (`y = x W^T`, W of shape
[out, in]), one dict per layer. KDA: `input_layernorm`, `q_proj`,
`k_proj`, `v_proj` [H*D, hidden], `q_conv1d`, `k_conv1d`, `v_conv1d`
[H*D, 4], `f_proj` [H*D, hidden], `A_log` [H], `dt_bias` [H*D], `b_proj`,
`g_proj` [H, hidden], `o_norm` [D], `o_proj` [hidden, H*D]. MLA:
`input_layernorm`, `q_proj` [H*192, hidden], `kv_a_proj_with_mqa`,
`kv_a_layernorm`, `kv_b_proj`, `q_layernorm`, `k_layernorm` [192],
`o_proj`. Both: `post_attention_layernorm`, then `mlp` or `mlp.gate`
[E, hidden], `e_score_correction_bias`, `experts`, `shared_experts`.

Departures from a modelling file, each marked `# dep:` where it is: RoPE
pairs column i with column i + 32 (rotate-half; an interleaved layout is
the same function up to a fixed permutation of the rotary columns);
every resident expert runs on every token and a 0/weight mask picks the
chosen ones; attention is computed for a block of queries at a time; the
experts outside the chosen groups are set to -inf before the choice (a
fill of 0 would differ only where a chosen score + bias is negative); no
MTP module, no vision tower, no mask, padding or cache; a non-zero
swiglu clamp is refused.

No kernels, no chunks, no sort, no fused weights, no sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

QUERY_BLOCK = 1024
L2_EPS = 1e-6


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def l2_norm(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_tables(seq: int, rope_dim: int, theta: float):
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, rope_dim]
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x, cos, sin):
    """x [B, H, T, rope_dim]."""
    # dep: rotate-half, not interleaved pairs (docstring)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


# ---- Kimi Delta Attention -----------------------------------------------


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


def decay_gate(f, a_log, dt_bias, lower: float):
    """f [B, T, H, D] -> log a in (lower, 0): the bounded gate
    (`kda_safe_gate`)."""
    import jax
    import jax.numpy as jnp
    return lower * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f + dt_bias.reshape(f.shape[2:])))


def delta_rule(q, k, v, log_a, beta):
    """The recurrence step by step: q, k, log_a [B, T, H, D], v
    [B, T, H, Dv], beta [B, T, H] -> o [B, T, H, Dv], from a zero state."""
    import jax
    import jax.numpy as jnp

    def step(state, inp):            # state [B, H, D, Dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = state * jnp.exp(a_t)[..., None]          # diag(a) S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)   # S^T k
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


def kda_attention(n, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """n [B, T, hidden] (normed) -> the KDA block's output before the
    residual, [B, T, hidden]; the heads are those of the weights."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = n.shape
    heads = lw["A_log"].shape[0]
    d = lw["q_proj"].shape[0] // heads

    def heads_of(x):
        return x.reshape(b, t, heads, d)

    q, k, v = (heads_of(short_conv(linear(n, lw[p + "_proj"]),
                                   lw[p + "_conv1d"].astype(f32)))
               for p in "qkv")
    q, k = l2_norm(q), l2_norm(k)
    log_a = decay_gate(heads_of(linear(n, lw["f_proj"])),
                       lw["A_log"].astype(f32), lw["dt_bias"].astype(f32),
                       float(cfg["kda_lower_bound"]))
    beta = jax.nn.sigmoid(linear(n, lw["b_proj"]))       # [B, T, H]
    o = _compiled_delta_rule()(q, k, v, log_a, beta)
    y = rms_norm(o, lw["o_norm"].astype(f32), cfg["rms_norm_eps"])
    y = y * jax.nn.sigmoid(linear(n, lw["g_proj"]))[..., None]
    return linear(y.reshape(b, t, heads * d), lw["o_proj"])


# ---- latent attention ----------------------------------------------------


def causal_attention(q, k, v, scale: float, block: int = QUERY_BLOCK):
    """q, k [B, H, T, D], v [B, H, T, Dv] -> [B, H, T, Dv]."""
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
    """The family's QK-norm: each head over its own columns."""
    return rms_norm(x, gain, eps)


def latent_attention(n, lw: Dict[str, Any], cfg: Dict[str, Any], cos, sin):
    """n [B, T, hidden] (normed) -> the MLA block's output before the
    residual; the heads are those of the weights."""
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = n.shape
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kvr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    nh = lw["q_proj"].shape[0] // (nope + rope)

    q = linear(n, lw["q_proj"]).reshape(b, t, nh, nope + rope)
    q = q.transpose(0, 2, 1, 3)                           # [B, H, T, .]
    ckv = linear(n, lw["kv_a_proj_with_mqa"])             # [B, T, kvr+rope]
    c_kv = rms_norm(ckv[..., :kvr], lw["kv_a_layernorm"].astype(f32), eps)
    k_r = jnp.broadcast_to(ckv[..., kvr:][:, None], (b, nh, t, rope))
    kv = linear(c_kv, lw["kv_b_proj"]).reshape(b, t, nh, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    v = kv[..., nope:]
    if cfg.get("use_qk_norm"):
        q = qk_norm(q, lw["q_layernorm"].astype(f32), eps)
        k = qk_norm(k, lw["k_layernorm"].astype(f32), eps)
    q = jnp.concatenate([q[..., :nope],
                         apply_rope(q[..., nope:], cos, sin)], axis=-1)
    k = jnp.concatenate([k[..., :nope],
                         apply_rope(k[..., nope:], cos, sin)], axis=-1)
    o = causal_attention(q, k, v, float(nope + rope) ** -0.5)
    return linear(o.transpose(0, 2, 1, 3).reshape(b, t, nh * vd),
                  lw["o_proj"])


# ---- MLPs and the router ---------------------------------------------------


def gated_mlp(m, gate_proj, up_proj, down_proj):
    """W_down (silu(W_gate m) * (W_up m)) on every row of m."""
    import jax
    gate = jax.nn.silu(linear(m, gate_proj))
    return linear(gate * linear(m, up_proj), down_proj)


@functools.lru_cache(maxsize=None)
def _compiled_gated_mlp():
    import jax
    return jax.jit(gated_mlp)


def group_limited(choice, n_group: int, topk_group: int):
    """choice [N, E] with every expert outside a token's `topk_group`
    best groups at -inf; a group's rank is the sum of its two largest
    entries, ties to the lower index."""
    import jax.numpy as jnp
    n, e = choice.shape
    grouped = choice.reshape(n, n_group, e // n_group)
    rank = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)      # [N, G]
    order = jnp.argsort(-rank, axis=-1, stable=True)         # best first
    place = jnp.argsort(order, axis=-1, stable=True)         # a group's place
    # dep: -inf, not 0, for the experts of the other groups (docstring)
    return jnp.where((place < topk_group)[..., None], grouped,
                     -jnp.inf).reshape(n, e)


def route(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (weights [N, k], expert ids [N, k], scores
    [N, E])."""
    import jax
    import jax.numpy as jnp

    if cfg.get("score_function", "sigmoid") != "sigmoid":
        raise ValueError("the published router scores with a sigmoid")
    scores = jax.nn.sigmoid(linear(m, lw["mlp.gate"]).astype(jnp.float32))
    choice = scores + lw["e_score_correction_bias"].astype(jnp.float32)
    if cfg.get("n_group", 1) > 1:
        choice = group_limited(choice, cfg["n_group"], cfg["topk_group"])
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
        # dep: every token runs through e; the others get weight 0
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * _compiled_gated_mlp()(
            m, ew["gate_proj"], ew["up_proj"], ew["down_proj"])
    return y, top_e


def shared_experts(m, lw: Dict[str, Any]):
    sw = lw["shared_experts"]
    return _compiled_gated_mlp()(m, sw["gate_proj"], sw["up_proj"],
                                 sw["down_proj"])


def refuse(cfg: Dict[str, Any]) -> None:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published MLPs are SiLU-gated")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is null in the published config")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key) or ()):
            raise ValueError(f"{key}: a swiglu clamp is not in this "
                             f"reference")


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_routing: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every expert layer's chosen ids [B*T, k])."""
    import jax
    import jax.numpy as jnp

    refuse(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    f32 = jnp.float32
    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        cos, sin = rope_tables(t, cfg["qk_rope_head_dim"], theta)
        h = weights["embed_tokens"].astype(f32)[tokens]
        for lw in weights["layers"]:
            n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)
            if "q_conv1d" in lw:
                h = h + kda_attention(n, lw, cfg)
            else:
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
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(lambda w: loss(w, batch_tokens, cfg))(weights)
