"""Plain reference for SDAR's sparse-expert decoder (`model_type:
sdar_moe`) trained as a block-diffusion model, float32, jax.numpy.

Written from the published equations (SDAR, arXiv:2510.06303; the
training form with a doubled sequence is block diffusion's, BD3-LMs,
arXiv:2503.09573) and the family's modelling code (Qwen3-MoE's layer),
importing nothing from `ray_tpu`:

    n     = RMSNorm(h_l; g_in)
    q     = RMSNorm_head(W_q n; g_q),  k = RMSNorm_head(W_k n; g_k),
    v     = W_v n        (QK-norm per head: each head of 128 normed over
                          its own columns, one gain a side shared by the
                          heads, before RoPE)
    a_l   = h_l + W_o . Attn(RoPE_p(q), RoPE_p(k), v; mask)
    m     = RMSNorm(a_l; g_post)
    s     = softmax(W_r m) over the E experts, float32
    S     = the k experts of largest s; weights s_e / sum_S s
              (`norm_topk_prob`)
    h_l+1 = a_l + sum_{e in S, e resident} w_e . W_down^e (silu(W_gate^e m) * (W_up^e m))
    logits = W_head . RMSNorm(h_L; g_final)

**The objective.** A sequence x of L tokens in blocks of `block_length`;
x^ is x with some tokens replaced by the mask id (the noise is the
caller's: this file is handed x^, x and the weights w, 1/t of the block
on a masked position and 0 elsewhere). The stream is `[x^ ; x]`, 2L
positions with the position ids `0..L-1` twice (`position_ids`), and
query i sees key j iff (`block_diffusion_mask`, blk(i) = (i mod L) div B)

    i <  L, j <  L:  blk(i) = blk(j)     a noised block, both directions
    i <  L, j >= L:  blk(j) <  blk(i)    the clean blocks strictly before
    i >= L, j >= L:  blk(j) <= blk(i)    clean, causal by block
    i >= L, j <  L:  never

The logits are read at the positions `0..L-1`, the targets are x (no shift
by one: a masked position's own logits predict its token), and the loss is
`sum_i w_i . -log p(x_i) / (B . L)` (`masked_diffusion_loss`): the mean
over the sequences of each one's bound. `forward_plain` is the objective's
own definition, one block at a time: a plain stream under
`block_causal_mask`.

**A share.** `lw["experts"]` is a dict `expert id -> weights` of the
experts resident here; the router scores all E and picks its k a token,
and what the absent experts would add is left out (as the program leaves
it out).

RMSNorm(x) = g * x / sqrt(mean(x^2) + eps); RoPE is the rotate-half form;
attention is grouped-query, scaled by 1/sqrt(d_head), softmax in float32,
the mask a dense boolean array. `query_block` runs attention's rows in
blocks of that many queries so that the [H, rows, 2L] scores fit a
device: the same arithmetic row by row. Every product is float32 under
`jax.default_matmul_precision("highest")`.

Weights arrive in the published layout (`y = x W^T`), one dict per layer:
`input_layernorm`, `q_proj`, `k_proj`, `v_proj`, `o_proj`, `q_norm`,
`k_norm` ([head_dim]), `post_attention_layernorm`, `mlp.gate` ([E,
hidden]) and `experts`. Departures from the HF modelling code, each
marked `# HF:`: every resident expert runs on every token and a 0/1 mask
picks the chosen ones; the routing weights stay float32; no padding, no
cache. `use_sliding_window`, a non-empty `mlp_only_layers` and a
`decoder_sparse_step` other than 1 are refused.

No kernels, no sort, no batching tricks, no sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional


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


def rope_tables(positions, head_dim: int, theta: float):
    """angle_i(p) = p * theta^(-2i/d_head), i < d_head/2, for the
    position ids p [T]; the tables repeated over both halves."""
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)   # [T, head_dim]
    return jnp.cos(emb), jnp.sin(emb)


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def position_ids(length: int):
    """The doubled stream's: 0..L-1 for the noised copy, and again for
    the clean one."""
    import jax.numpy as jnp
    return jnp.concatenate([jnp.arange(length), jnp.arange(length)])


def block_diffusion_mask(length: int, block: int):
    """[2L, 2L] bool: whether query i sees key j (module docstring)."""
    import jax.numpy as jnp
    i = jnp.arange(2 * length)[:, None]
    j = jnp.arange(2 * length)[None, :]
    blk_i, blk_j = (i % length) // block, (j % length) // block
    return ((i < length) & (j < length) & (blk_i == blk_j)) \
        | ((i < length) & (j >= length) & (blk_j < blk_i)) \
        | ((i >= length) & (j >= length) & (blk_j <= blk_i))


def block_causal_mask(t: int, block: int):
    """[t, t] bool: a plain stream, causal by block."""
    import jax.numpy as jnp
    blk = jnp.arange(t) // block
    return blk[None, :] <= blk[:, None]


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
    """m [N, hidden] -> (the resident experts' part of the routed sum
    [N, hidden], expert ids [N, k])."""
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


def refuse_what_is_not_here(cfg: Dict[str, Any]) -> None:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published expert is SiLU-gated")
    if cfg.get("use_sliding_window") or cfg.get("mlp_only_layers") \
            or cfg.get("decoder_sparse_step", 1) != 1 \
            or cfg.get("attention_bias"):
        raise ValueError("a window, dense MLP layers, a sparse step other "
                         "than 1 and projection biases are not in this "
                         "reference")


def decoder(weights: Dict[str, Any], tokens, positions, mask,
            cfg: Dict[str, Any], chosen: Optional[List[Any]] = None,
            query_block: Optional[int] = None):
    """tokens [B, T] at the position ids `positions` [T] under `mask`
    [T, T] -> the stream after the last layer [B, T, hidden], before the
    final norm; `chosen` collects every layer's expert ids [B*T, k]."""
    import jax.numpy as jnp

    refuse_what_is_not_here(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32
    b, t = tokens.shape
    cos, sin = rope_tables(positions, hd, float(cfg["rope_theta"]))
    h = weights["embed_tokens"].astype(f32)[tokens]
    for lw in weights["layers"]:
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
        o = masked_attention(q, k, v, mask, query_block)
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


def forward(weights: Dict[str, Any], noised, clean, cfg: Dict[str, Any],
            with_routing: bool = False,
            query_block: Optional[int] = None):
    """noised, clean [B, L] int32 -> logits [B, L, vocab] float32 at the
    noised positions of the doubled stream (and, asked for, the list of
    every layer's chosen ids [B*2L, k])."""
    import jax
    import jax.numpy as jnp

    length, block = clean.shape[1], cfg["block_length"]
    if length % block:
        raise ValueError(f"blocks of {block} do not divide {length} tokens")
    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        h = decoder(weights, jnp.concatenate([noised, clean], axis=1),
                    position_ids(length),
                    block_diffusion_mask(length, block), cfg, chosen,
                    query_block)
        logits = head(weights, h[:, :length], cfg)
    return (logits, chosen) if with_routing else logits


def forward_plain(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [B, T] -> logits [B, T, vocab] of a plain stream, causal by
    block: what a block's positions read given the clean blocks before
    it (the objective's own definition, and the sampler's pass)."""
    import jax
    import jax.numpy as jnp

    t = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        h = decoder(weights, tokens, jnp.arange(t),
                    block_causal_mask(t, cfg["block_length"]), cfg)
        return head(weights, h, cfg)


def masked_diffusion_loss(logits, targets, weights):
    """`sum_i w_i . -log p(x_i) / (B . L)` of logits [B, L, V] against
    targets [B, L] under the weights [B, L]."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * (logz - gold)) / targets.size


def tokens_per_expert(chosen: List[Any], n_experts: int):
    """[layers, E] int32: how many of a layer's N*k token-slots chose each
    of the E routed experts (the system's counter holds the resident
    experts' columns of it)."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sum(jax.nn.one_hot(
        top_e.reshape(-1), n_experts, dtype=jnp.int32), axis=0)
        for top_e in chosen])


def loss(weights: Dict[str, Any], noised, clean, loss_w,
         cfg: Dict[str, Any], query_block: Optional[int] = None):
    """The masked-token loss of the batch (no aux term)."""
    logits = forward(weights, noised, clean, cfg, query_block=query_block)
    return masked_diffusion_loss(logits, clean, loss_w)


def loss_and_grads(weights: Dict[str, Any], noised, clean, loss_w,
                   cfg: Dict[str, Any]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(
        lambda w: loss(w, noised, clean, loss_w, cfg))(weights)
