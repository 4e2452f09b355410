"""Plain reference for Granite 4.0-H (`model_type: granitemoehybrid` with
`num_local_experts` 0), float32, jax.numpy, over PACKED documents.

Written from the published config.json keys and the equations of the
`GraniteMoeHybrid` modelling code they name (whose mixer is Bamba's
Mamba-2), importing nothing from `ray_tpu`. Every layer is a mixer
followed by an MLP, each under its own pre-norm and each scaled before it
joins the stream (muP's four scalars):

    x_0 = embedding_multiplier * E[ids]
    u   = x + residual_multiplier * mixer(RMSNorm_1(x))
    x'  = u + residual_multiplier * mlp(RMSNorm_2(u))
    mlp(h) = W_out (silu(g) * v),  [g | v] = W_in h      `shared_mlp`

`layer_types[i]` says which mixer. `attention`: `num_attention_heads`
query heads over `num_key_value_heads` key/value heads of
hidden_size / num_attention_heads, causal, NO rotary embedding
(`position_embedding_type: nope`), no bias, and the softmax scale is
`attention_multiplier`, not head_dim^-1/2. `mamba`: H = `mamba_n_heads`
heads of P = `mamba_d_head`, G = `mamba_n_groups` groups of state N =
`mamba_d_state`, n the normed input:

    [z | xBC | dt] = W_in n                  widths H·P, H·P + 2·G·N, H
    xBC = silu(conv1d(xBC))                  depthwise, causal, kernel
                                             `mamba_d_conv`, with bias
    dt = softplus(dt + dt_bias),  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   S is P x N, B and C of
                                                 the head's group
    y_t = S_t C_t + D x_t
    out = W_out (RMSNorm(y * silu(z)) * gain)    ONE norm over all H·P
                                                 channels (`BambaRMSNormGated`)

    logits = E RMSNorm(x_L)^T / logits_scaling   (`tie_word_embeddings`)

The recurrence runs step by step (`jax.lax.scan` over the steps), scores
are dense, nothing is chunked but to fit memory (below).

**Packed documents, with no segment logic.** A sequence is documents laid
end to end; `lengths` (plain ints, one list a sequence) says how long
each is. `forward` CUTS the sequence there, runs every document ALONE
from a zero state through the whole model, and joins the logits: there
is no mask, no id and no reset anywhere in the model's functions, so
nothing here can agree with the program's masking by sharing its
mistake. The loss leaves out each document's last position (its label
would be the next document's first token).

A document is padded on the right to one of a few lengths (`LENGTHS`: 128,
512, 2,048, 8,192, each four times the one before) and a layer of each
kind runs under one `jax.jit` (`compiled_layers`: the same arithmetic, as
`_compiled_scan`), so that a sequence compiles two programs a length it
uses, eight at most, and not every op of every layer at every length a
seed draws: a cold first run of the cell stands under the trainer's wait
for a first report (ROADMAP D17). Every function is causal, so what
follows a document's last token reaches none of its positions, and the
padded positions' logits are dropped.

Every product is float32: on a TPU a float32 matmul runs in lower
precision unless `jax.default_matmul_precision("highest")` is set, which
the functions here do themselves. A sliced vocabulary is a smaller
vocabulary: `embed_tokens` simply has fewer rows.

Weights arrive in the published layout (`y = x W^T`, W of shape
[out, in]): `embed_tokens`, `norm`, and one dict a layer with
`input_layernorm`, `post_attention_layernorm`, `input_linear`
([2·width, hidden]: gate rows then up rows), `output_linear`, and a mamba
layer's `in_proj`, `conv1d` [channels, kernel], `conv1d_bias`, `dt_bias`,
`A_log`, `D`, `mixer_norm`, `out_proj` or an attention layer's `q_proj`,
`k_proj`, `v_proj`, `o_proj`. Whoever calls this converts the system's
parameters.

Departures from the HF modelling code, each marked `# HF:` where it is:
attention is computed for a block of queries at a time against the keys up
to the block's end and the recurrence for a block of heads at a time, so
that one 8,192-token document fits beside the weights on a chip (same
arithmetic, row by row); HF's `torch_forward` computes the recurrence in
chunks of `mamba_chunk_size`, here the plain recurrence it is equal to;
`time_step_limit` is absent from the published config and `dt` is not
clamped; HF packs with `seq_idx` / position ids, here the documents are
run one by one; no cache; experts (`num_local_experts` above 0) are
refused.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

QUERY_BLOCK = 1024
HEAD_BLOCK = 32
LENGTHS = (128, 512, 2048, 8192)    # what a document is padded to


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def linear(x, w):
    """`y = x W^T` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


# ---- mamba: the Mamba-2 mixer -------------------------------------------


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


def selective_scan(x, dt, a, b, c):
    """The recurrence, step by step, from a zero state: x [B, T, H, P],
    dt [B, T, H], a [H], b and c [B, T, H, N] (each head's group's) ->
    S_t C_t, [B, T, H, P]."""
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
    return jnp.moveaxis(y, 0, 1)


@functools.lru_cache(maxsize=None)
def _compiled_scan():
    import jax
    return jax.jit(selective_scan)


def skip(y, x, d_skip):
    """`y_t + D x_t`: y, x [B, T, H, P], d_skip [H]."""
    return y + d_skip[:, None] * x


def gated_rms_norm(y, z, gain, eps):
    """`RMSNorm(y * silu(z)) * gain` over ALL of the last axis (the H·P
    inner channels of all heads: `mamba_n_groups` is 1)."""
    import jax
    return rms_norm(y * jax.nn.silu(z), gain, eps)


def mamba2_mixer(n, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """n [B, T, hidden] (normed) -> the mixer's output before the
    residual."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    p, ns = cfg["mamba_d_head"], cfg["mamba_d_state"]
    heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    inner = heads * p
    conv_dim = inner + 2 * groups * ns
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
    # HF: no `time_step_limit` in the published config: no clamp
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(f32))
    a = -jnp.exp(lw["A_log"].astype(f32))
    # HF: torch_forward's chunked form; here the recurrence itself, a
    # block of heads at a time
    y = jnp.concatenate([
        _compiled_scan()(x[:, :, lo:lo + HEAD_BLOCK],
                         dt[:, :, lo:lo + HEAD_BLOCK],
                         a[lo:lo + HEAD_BLOCK],
                         b[:, :, lo:lo + HEAD_BLOCK],
                         c[:, :, lo:lo + HEAD_BLOCK])
        for lo in range(0, heads, HEAD_BLOCK)], axis=2)
    y = skip(y, x, lw["D"].astype(f32)).reshape(bsz, t, inner)
    y = gated_rms_norm(y, z, lw["mixer_norm"].astype(f32),
                       cfg["rms_norm_eps"])
    return linear(y, lw["out_proj"])


# ---- attention --------------------------------------------------------------


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


def softmax_scale(cfg: Dict[str, Any]) -> float:
    """`attention_multiplier`, as published: not head_dim^-1/2."""
    return float(cfg["attention_multiplier"])


def attention(n, lw: Dict[str, Any], cfg: Dict[str, Any]):
    """n [B, T, hidden] (normed) -> attention's output before the
    residual; no rotary embedding."""
    import jax.numpy as jnp
    bsz, t, _ = n.shape
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]

    def heads(w):
        return linear(n, w).reshape(bsz, t, -1, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(lw["q_proj"]), heads(lw["k_proj"]), heads(lw["v_proj"])
    rep = q.shape[1] // k.shape[1]
    # HF: position_embedding_type "nope": no rotary embedding
    o = causal_attention(q, jnp.repeat(k, rep, axis=1),
                         jnp.repeat(v, rep, axis=1), softmax_scale(cfg))
    return linear(o.transpose(0, 2, 1, 3).reshape(bsz, t, -1), lw["o_proj"])


# ---- the MLP ------------------------------------------------------------------


def gated_mlp(n, input_linear, output_linear):
    """`W_out (silu(g) * v)`, `[g | v] = W_in n` (`shared_mlp`)."""
    import jax
    gv = linear(n, input_linear)
    width = gv.shape[-1] // 2
    return linear(jax.nn.silu(gv[..., :width]) * gv[..., width:],
                  output_linear)


# ---- the model ------------------------------------------------------------------


def check(cfg: Dict[str, Any], weights: Dict[str, Any]) -> None:
    if cfg.get("num_local_experts", 0) or cfg.get("num_experts_per_tok", 0):
        raise ValueError("routed experts are not in this reference")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the published activation is silu")
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias"):
        raise ValueError("the published projections have no bias")
    if not cfg.get("mamba_conv_bias", True):
        raise ValueError("the published convolution has a bias")
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the published attention has no position "
                         "embedding (`nope`)")
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the published head is the embedding")
    kinds = cfg["layer_types"]
    if len(kinds) != len(weights["layers"]) \
            or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds!r} against "
                         f"{len(weights['layers'])} layers")


def embed(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    import jax.numpy as jnp
    return cfg["embedding_multiplier"] \
        * weights["embed_tokens"].astype(jnp.float32)[tokens]


def residual(x, out, cfg: Dict[str, Any]):
    return x + cfg["residual_multiplier"] * out


def head(h, weights: Dict[str, Any], cfg: Dict[str, Any]):
    return linear(h, weights["embed_tokens"]) / cfg["logits_scaling"]


def layer(h, lw: Dict[str, Any], cfg: Dict[str, Any], kind: str):
    """One layer on the stream h [B, n, hidden]: the mixer `kind` names,
    then the MLP, each under its pre-norm and the residual's scalar."""
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    n = rms_norm(h, lw["input_layernorm"].astype(f32), eps)
    mixed = mamba2_mixer(n, lw, cfg) if kind == "mamba" \
        else attention(n, lw, cfg)
    h = residual(h, mixed, cfg)
    n = rms_norm(h, lw["post_attention_layernorm"].astype(f32), eps)
    return residual(h, gated_mlp(n, lw["input_linear"],
                                 lw["output_linear"]), cfg)


def compiled_layers(cfg: Dict[str, Any]):
    """`layer` of each kind under `jax.jit`, for the documents of one
    `forward`: a kind compiles once a padded length, not op by op. Same
    arithmetic."""
    import jax
    return {kind: jax.jit(functools.partial(layer, cfg=cfg, kind=kind))
            for kind in ("mamba", "attention")}


def document_logits(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
                    layers=None):
    """ONE document (or a batch of documents of one length), alone:
    tokens [B, n] int32 -> logits [B, n, vocab] float32. `layers`: what
    `compiled_layers` gave, else `layer` as it is."""
    import jax.numpy as jnp
    h = embed(weights, tokens, cfg)
    for kind, lw in zip(cfg["layer_types"], weights["layers"]):
        h = layers[kind](h, lw) if layers else layer(h, lw, cfg, kind)
    h = rms_norm(h, weights["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return head(h, weights, cfg)


def documents(lengths: Sequence[int], total: int) -> List[Tuple[int, int]]:
    """(start, length) of the documents laid end to end over `total`
    positions; the last is cut where the sequence ends, and what would
    start behind it is dropped."""
    out, at = [], 0
    for n in lengths:
        n = min(int(n), total - at)
        if n <= 0:
            break
        out.append((at, n))
        at += n
    if at != total:
        raise ValueError(f"documents of {list(lengths)} cover {at} of "
                         f"{total} positions")
    return out


def padded_length(n: int) -> int:
    """The least of `LENGTHS` that holds n positions (n itself beyond
    them): what a document is padded to on the right (module
    docstring)."""
    return next((size for size in LENGTHS if size >= n), n)


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any],
            lengths: Sequence[Sequence[int]]):
    """tokens [B, T] int32, `lengths` each sequence's document lengths
    -> logits [B, T, vocab] float32: every document run alone, the
    logits joined."""
    import jax
    import jax.numpy as jnp

    check(cfg, weights)
    bsz, t = tokens.shape
    if len(lengths) != bsz:
        raise ValueError(f"{len(lengths)} lists of lengths for {bsz} "
                         f"sequences")
    rows, layers = [], compiled_layers(cfg)
    with jax.default_matmul_precision("highest"):
        for row, row_lengths in zip(tokens, lengths):
            parts = []
            for start, n in documents(row_lengths, t):
                doc = jnp.pad(row[start:start + n],
                              (0, padded_length(n) - n))
                parts.append(document_logits(weights, doc[None], cfg,
                                             layers)[0, :n])
            rows.append(jnp.concatenate(parts))
    return jnp.stack(rows)


def trained_positions(lengths: Sequence[Sequence[int]], total: int):
    """bool [B, total - 1] (numpy): position t of the inputs is trained
    on iff token t + 1 lies in its document, that is, t is not its
    document's last position. `lengths` are over the `total` tokens of
    the batch (inputs and the last label)."""
    import numpy as np
    mask = np.ones((len(lengths), total - 1), bool)
    for row, row_lengths in zip(mask, lengths):
        for start, n in documents(row_lengths, total):
            if start + n - 1 < total - 1:
                row[start + n - 1] = False
    return mask


def input_lengths(lengths: Sequence[Sequence[int]], total: int):
    """The documents of the first `total - 1` tokens: the last one a
    token shorter."""
    return [[n for _, n in documents(row, total)][:-1]
            + [documents(row, total)[-1][1] - 1] for row in lengths]


def next_token_loss(logits, batch_tokens, lengths: Sequence[Sequence[int]]):
    """Mean cross-entropy of logits [B, T, V] against batch_tokens
    [B, T+1] shifted by one, every document's last position left out;
    `lengths` over the T + 1 tokens."""
    import jax
    import jax.numpy as jnp
    targets = batch_tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    keep = jnp.asarray(trained_positions(lengths, batch_tokens.shape[1]),
                       jnp.float32)
    return jnp.sum((logz - gold) * keep) / jnp.sum(keep)


def loss(weights: Dict[str, Any], batch_tokens, cfg: Dict[str, Any],
         lengths: Sequence[Sequence[int]]):
    """batch_tokens [B, T+1], `lengths` over them -> the packed
    next-token cross-entropy."""
    logits = forward(weights, batch_tokens[:, :-1], cfg,
                     input_lengths(lengths, batch_tokens.shape[1]))
    return next_token_loss(logits, batch_tokens, lengths)


def loss_and_grads(weights: Dict[str, Any], batch_tokens,
                   cfg: Dict[str, Any], lengths: Sequence[Sequence[int]]):
    """(loss, d loss / d weights) by `jax.grad` of `loss`."""
    import jax
    return jax.value_and_grad(
        lambda w: loss(w, batch_tokens, cfg, lengths))(weights)
