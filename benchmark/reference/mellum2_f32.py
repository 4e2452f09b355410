"""Plain reference for Mellum 2's sparse-expert decoder (`model_type:
mellum`), float32, jax.numpy.

Written from the published `config.json` keys and the family's modelling
code (Qwen3-MoE's layer, whose keys the row carries; the rotary
embedding's `_compute_yarn_parameters`), importing nothing from
`ray_tpu`:

    n     = RMSNorm(h_l; g_in)
    q     = RMSNorm_head(W_q n; g_q),  k = RMSNorm_head(W_k n; g_k),
    v     = W_v n        (QK-norm per head: each head of 128 normed over
                          its own columns, one gain a side shared by the
                          heads, before RoPE: a reading, the row has no
                          key for it)
    a_l   = h_l + W_o . Attn(RoPE_kind(q), RoPE_kind(k), v; mask_kind)
    m     = RMSNorm(a_l; g_post)
    s     = softmax(W_r m) over the E experts, float32
    S     = the k experts of largest s; weights s_e / sum_S s
              (`norm_topk_prob`)
    h_l+1 = a_l + sum_{e in S} w_e . W_down^e (silu(W_gate^e m) * (W_up^e m))
    logits = W_head . RMSNorm(h_L; g_final)

**The layer's kind** (`layer_types[l]`) decides the mask and the table:

- `sliding_attention`: query i sees key j iff `0 <= i - j < sliding_window`
  (`sliding_mask`: the window's keys, the query's own among them, the
  family's `kv > q - sliding_window`); RoPE plain, `inv_freq_p =
  theta^(-p / (d/2))`, p = 0..d/2-1 (`rope_parameters.sliding_attention`).
- `full_attention`: causal; RoPE under YaRN
  (`rope_parameters.full_attention`, `yarn_inv_freq`): with d the head
  width, L0 the original length, `c(r) = d ln(L0 / (2 pi r)) / (2 ln
  theta)`, `low = max(floor(c(beta_fast)), 0)`, `high =
  min(ceil(c(beta_slow)), d - 1)` (`truncate` at its default, true),
  `ramp_p = clip((p - low) / (high - low), 0, 1)`, `inv_freq_p =
  theta^(-p / (d/2)) ((1 - ramp_p) + ramp_p / factor)`; cos and sin are
  both multiplied by `attention_factor`, on q and on k, so a score carries
  its square.

Positions are 0..T-1. RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); RoPE is
the rotate-half form (pairs (p, p + d/2)); attention is grouped-query
(query head h reads key head h div (H / H_kv)), scaled by 1/sqrt(d_head),
softmax in float32, the mask a dense boolean array. `query_block` runs
attention's rows in blocks of that many queries so that the [H, rows, T]
scores fit a device: the same arithmetic row by row. Every product is
float32 under `jax.default_matmul_precision("highest")`.

**The experts** are a loop: every resident expert runs on every token and
a 0/1 mask picks the chosen ones. `lw["experts"]` is a dict `expert id ->
weights` of the experts resident here (all E in the benchmark's cell: the
reference knows no chips and no exchange, it is the uncut layer; a test
may hand it a share, and what the absent experts would add is left out).

Weights arrive in the published layout (`y = x W^T`), one dict per layer:
`input_layernorm`, `q_proj`, `k_proj`, `v_proj`, `o_proj`, `q_norm`,
`k_norm` ([head_dim]), `post_attention_layernorm`, `mlp.gate` ([E,
hidden]) and `experts`. Departures from the HF modelling code, each
marked `# HF:`: the loop over the experts; the routing weights stay
float32; no padding, no cache. No auxiliary loss (`router_aux_loss_coef`
is not in the row) and no multi-token-prediction head (the config has no
key for it). `intermediate_size`, `max_window_layers` and
`use_sliding_window` are read by nothing: `layer_types` governs. A
`dense` entry in `mlp_layer_types` of the layers kept, a `rope_type`
other than `default` and `yarn` and a `layer_types` entry other than the
two are refused.

No kernels, no sort, no batching tricks, no sharding annotations, no mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

KINDS = ("sliding_attention", "full_attention")


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def qk_norm(x, gain, eps):
    """x [B, T, heads, head_dim]: every head over its own columns."""
    return rms_norm(x, gain, eps)


def rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def plain_inv_freq(head_dim: int, theta: float):
    import jax.numpy as jnp
    return 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_bounds(head_dim: int, rope: Dict[str, Any]):
    """(low, high): the pairs YaRN's ramp runs between."""
    theta, length = float(rope["rope_theta"]), \
        rope["original_max_position_embeddings"]

    def pair_that_turns(times):
        return head_dim * math.log(length / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = pair_that_turns(rope.get("beta_fast", 32)), \
        pair_that_turns(rope.get("beta_slow", 1))
    if rope.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, head_dim - 1)


def yarn_inv_freq(head_dim: int, rope: Dict[str, Any]):
    """YaRN's per-pair frequencies (module docstring)."""
    import jax.numpy as jnp
    low, high = yarn_bounds(head_dim, rope)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    plain = plain_inv_freq(head_dim, float(rope["rope_theta"]))
    return plain * (1.0 - ramp) + plain / rope["factor"] * ramp


def attention_factor(rope: Dict[str, Any]) -> float:
    """What YaRN multiplies cos and sin by: as published, or the
    family's default `0.1 ln(factor) + 1`."""
    given = rope.get("attention_factor")
    return float(given) if given else 0.1 * math.log(rope["factor"]) + 1.0


def rope_tables(positions, head_dim: int, rope: Dict[str, Any]):
    """cos and sin `[T, head_dim]` of a layer kind's `rope_parameters`
    entry for the position ids `positions` [T], the tables repeated over
    both halves."""
    import jax.numpy as jnp
    kind = rope.get("rope_type", "default")
    if kind == "default":
        inv_freq, scale = plain_inv_freq(head_dim,
                                         float(rope["rope_theta"])), 1.0
    elif kind == "yarn":
        inv_freq, scale = yarn_inv_freq(head_dim, rope), \
            attention_factor(rope)
    else:
        raise ValueError(f"rope_type {kind!r}: default and yarn are here")
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def causal_mask(t: int):
    import jax.numpy as jnp
    i = jnp.arange(t)
    return i[None, :] <= i[:, None]


def sliding_mask(t: int, window: int):
    """[t, t] bool: query i sees key j iff 0 <= i - j < window."""
    import jax.numpy as jnp
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (back >= 0) & (back < window)


def masked_attention(q, k, v, mask, query_block: Optional[int] = None):
    """q, k, v [B, H, T, D] (k, v already repeated), mask [T, T] bool
    -> [B, H, T, D]; the rows in blocks of `query_block` queries."""
    import jax
    import jax.numpy as jnp

    def rows(q_rows, mask_rows):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        scores = jnp.where(mask_rows[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(scores, axis=-1), v)

    t = q.shape[2]
    step = query_block or t
    return jnp.concatenate([rows(q[:, :, s:s + step], mask[s:s + step])
                            for s in range(0, t, step)], axis=2)


def expert_mlp(m, gate_proj, up_proj, down_proj):
    """One expert on every row of m: W_down (silu(W_gate m) * (W_up m))."""
    import jax
    gate = jax.nn.silu(linear(m, gate_proj))
    return linear(gate * linear(m, up_proj), down_proj)


@functools.lru_cache(maxsize=None)
def _compiled_expert_mlp():
    """`expert_mlp` under `jax.jit`: called op by op (as the benchmark's
    job does on the chip) the loop over the experts then compiles one
    expert once. Same arithmetic."""
    import jax
    return jax.jit(expert_mlp)


def route(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (weights [N, k], expert ids [N, k]): softmax
    over all E experts in float32, the k largest, normalised over the
    chosen where `norm_topk_prob`."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.softmax(
        linear(m, lw["mlp.gate"]).astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    return top_w, top_e


def sparse_moe(m, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """m [N, hidden] -> (the resident experts' routed sum [N, hidden],
    expert ids [N, k])."""
    import jax.numpy as jnp
    top_w, top_e = route(m, lw, cfg)
    y = jnp.zeros_like(m)
    for e, ew in sorted(lw["experts"].items()):
        # HF: gathers the tokens that chose e; here every token runs
        # through e and those that did not choose it get weight 0
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * _compiled_expert_mlp()(
            m, ew["gate_proj"], ew["up_proj"], ew["down_proj"])
    return y, top_e


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """The kinds of the layers kept, refusing what is not here."""
    n = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"][:n])
    if len(kinds) != n or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {kinds}: {n} entries of {KINDS}")
    if set(cfg.get("mlp_layer_types", ["sparse"] * n)[:n]) != {"sparse"}:
        raise ValueError("a dense MLP layer among the layers kept is not "
                         "in this reference")
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias"):
        raise ValueError("the published expert is SiLU-gated and the "
                         "projections have no bias")
    return kinds


def hidden(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
           chosen: Optional[List[Any]] = None,
           query_block: Optional[int] = None):
    """tokens [B, T] -> the stream after the last layer [B, T, hidden],
    before the final norm; `chosen` collects every layer's expert ids
    [B*T, k]."""
    import jax.numpy as jnp

    kinds = layer_kinds(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32
    b, t = tokens.shape
    positions = jnp.arange(t)
    tables = {kind: rope_tables(positions, hd, cfg["rope_parameters"][kind])
              for kind in set(kinds)}
    masks = {"full_attention": causal_mask(t),
             "sliding_attention": sliding_mask(t, cfg["sliding_window"])}
    h = weights["embed_tokens"].astype(f32)[tokens]
    for kind, lw in zip(kinds, weights["layers"]):
        cos, sin = tables[kind]
        n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)
        q = qk_norm(linear(n, lw["q_proj"]).reshape(b, t, nh, hd),
                    lw["q_norm"].astype(f32), eps).transpose(0, 2, 1, 3)
        k = qk_norm(linear(n, lw["k_proj"]).reshape(b, t, nkv, hd),
                    lw["k_norm"].astype(f32), eps).transpose(0, 2, 1, 3)
        v = linear(n, lw["v_proj"]).reshape(b, t, nkv, hd).transpose(
            0, 2, 1, 3)
        q = q * cos + rotate_half(q) * sin
        k = k * cos + rotate_half(k) * sin
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        o = masked_attention(q, k, v, masks[kind], query_block)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
        h = h + linear(o, lw["o_proj"])
        m = rms_norm(h, lw["post_attention_layernorm"].astype(f32), eps)
        y, top_e = sparse_moe(m.reshape(b * t, -1), lw, cfg)
        if chosen is not None:
            chosen.append(top_e)
        h = h + y.reshape(b, t, -1)
    return h


def head(weights: Dict[str, Any], h, cfg: Dict[str, Any]):
    import jax.numpy as jnp
    h = rms_norm(h, weights["norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return linear(h, weights["lm_head"])


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            with_routing: bool = False,
            query_block: Optional[int] = None):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32 (and, asked
    for, the list of every layer's chosen ids [B*T, k])."""
    import jax

    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        logits = head(weights, hidden(weights, tokens, cfg, chosen,
                                      query_block), cfg)
    return (logits, chosen) if with_routing else logits


def next_token_nll(logits, targets):
    """[B, T]: -log p(target) of logits [B, T, V]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def next_token_loss(logits, targets):
    """Mean cross-entropy of logits [B, T, V] against targets [B, T]."""
    import jax.numpy as jnp
    return jnp.mean(next_token_nll(logits, targets))


def tokens_per_expert(chosen: List[Any], n_experts: int):
    """[layers, E] int32: how many of a layer's N*k token-slots chose each
    of the E routed experts."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sum(jax.nn.one_hot(
        top_e.reshape(-1), n_experts, dtype=jnp.int32), axis=0)
        for top_e in chosen])


def loss(weights: Dict[str, Any], tokens, targets, cfg: Dict[str, Any],
         query_block: Optional[int] = None):
    """The next-token loss of the batch (no aux term)."""
    return next_token_loss(
        forward(weights, tokens, cfg, query_block=query_block), targets)


def loss_and_grads(weights: Dict[str, Any], tokens, targets,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(
        lambda w: loss(w, tokens, targets, cfg))(weights)
