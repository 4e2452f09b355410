"""Plain reference for Phi-4-mini-flash-reasoning (`model_type:
phi4flash`; the SambaY decoder-hybrid-decoder of arXiv:2507.06607),
float32, jax.numpy.

Written from the published config.json keys and the equations of the
`phi4flash` modelling code they name, importing nothing from `ray_tpu`.
d = `hidden_size`; LN is LayerNorm with gain and bias, eps
`layer_norm_eps`; there is no positional encoding anywhere. Every layer is

    h <- h + f(LN1 h),   h <- h + MLP(LN2 h)
    MLP(n) = W_down (u * silu(g)),  [g | u] = W_gu n          no bias

and f is one of five things, by the layer's character in `layer_kinds`
(the published model: layers 0, 2, .., 16 `m`, the last of them `s`; 1, 3,
.., 15 `w`; 17 `f`; 18, 20, .. `g`; 19, 21, .. `c`):

`m`, `s`, a Mamba-1 mixer (inner width C, state N, step rank R, K taps):

    [x | z] = W_in n
    x = silu(conv1d(x) + b_conv)                  depthwise, causal
    [delta | B | C] = W_x x                       widths R, N, N: off x,
                                                  not off the stream
    dt = softplus(W_dt delta + b_dt),  A = -exp(A_log)         A is C x N
    s_t = exp(dt_t (x) A) . s_{t-1} + (dt_t . x_t) (x) B_t     s is C x N
    y_t = s_t C_t + D . x_t
    f = W_out (y * silu(z))

  The recurrence runs step by step (`jax.lax.scan` over the T steps). The
  y of the layer `s` (with the D skip, BEFORE the gate) is the memory m.

`w`, `f`, differential attention: `num_attention_heads` query heads of
hd = d / heads in pairs p, `num_key_value_heads` key heads in pairs g,
half as many value heads of 2·hd; query pair p reads key pair and value
head g = p // (query pairs / key pairs):

    [Q | K | V] = W_qkv n + b
    A_j = softmax(mask + Q_{p,j} K_{g,j}^T / sqrt(hd)),  j = 1, 2
    O_p = (A_1 - lambda A_2) V_g
    O_p <- RMSNorm(O_p; gamma) (1 - lambda_init)        over the 2·hd
    f = W_o concat_p(O_p) + b_o
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)      l the layer's PUBLISHED index

  Q's heads are laid out (p, j), K's (g, j). The mask is causal; in a `w`
  layer also i - j < `sliding_window`. The K and V of the layer `f` are
  THE K and V of every `c` layer.

`g`, a gated memory unit: `f = W_2 (m * silu(W_1 n))`.

`c`, cross-attention: `Q = W_q n + b` only; K, V the layer `f`'s as they
are; causal; the same differential combination with its own lambda and
gamma; `W_o`, `b_o`.

logits = E . LN(h_L) over the rows of the (tied) embedding E that are
held; loss = next-token cross-entropy. Every product is float32: on a TPU
a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set, which the functions here
do themselves.

**A cut.** `first_layer_index` is the published index of the first layer
given (a configuration that holds layers 14-19 says 14): lambda_init reads
it. A sliced vocabulary is a smaller vocabulary: `embed_tokens` simply has
fewer rows.

Weights arrive in the published layout (`y = x W^T`, W of shape [out, in]),
one dict per layer with `input_layernorm`, `post_attention_layernorm`
(each `{weight, bias}`), `gate_up_proj`, `down_proj` and: a mixer
`in_proj`, `conv1d` [C, K], `conv1d_bias`, `x_proj`, `dt_proj`,
`dt_proj_bias`, `A_log`, `D`, `out_proj`; attention `Wqkv`, `Wqkv_bias`
(a `c` layer `Wq`, `Wq_bias`), `out_proj`, `out_proj_bias`, `lambda_q1`,
`lambda_k1`, `lambda_q2`, `lambda_k2`, `subln`; a gated memory unit
`in_proj`, `out_proj`. Whoever calls this converts the system's parameters.

Departures from the HF modelling code, each marked `# HF:` where it is:
attention is computed for a block of queries and one key pair's four maps
at a time against all keys under the mask, so that one 16,384-token
sequence fits beside the weights on a chip (same arithmetic, row by row);
HF runs four flash-attention calls on halves of V and concatenates, here
the two maps times the whole V_g (equal); HF's fused CUDA scan is the plain
recurrence here; dropout is 0 (`resid_pdrop`, `embd_pdrop`); no padding,
attention mask or cache.

No kernels, no chunked scan, no fused weights, no sharding.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

QUERY_BLOCK = 1024


def linear(x, w, b=None):
    """`y = x W^T (+ b)` in float32: every weight matmul of the model."""
    import jax.numpy as jnp
    y = x.astype(jnp.float32) @ w.astype(jnp.float32).T
    return y if b is None else y + b.astype(jnp.float32)


def layer_norm(x, leaves, eps):
    import jax.numpy as jnp
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return leaves["weight"] * (centred / jnp.sqrt(var + eps)) \
        + leaves["bias"]


def rms_norm(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return gain * (x / jnp.sqrt(var + eps))


def lambda_init(cfg: Dict[str, Any], place: int) -> float:
    """Of the layer at `place` among those given, by its published index."""
    return 0.8 - 0.6 * math.exp(
        -0.3 * (int(cfg.get("first_layer_index", 0)) + place))


def window_of(cfg: Dict[str, Any], kind: str) -> int:
    """Keys a query sees besides causality: 0 for all of them."""
    return int(cfg["sliding_window"]) if kind == "w" else 0


# ---- m, s: the Mamba-1 mixer ---------------------------------------------

STATE_DTYPE = "float32"


@functools.cache
def _jit(fn, static=()):
    """One compile a function and shape, where the loops below call it
    many times; JAX is imported when first used, as everywhere here."""
    import jax
    return jax.jit(fn, static_argnames=static)


def recurrence(x, dt, a, b, c, dtype="float32"):
    """One sequence: x, dt `[T, C]`, a `[C, N]`, b, c `[T, N]` -> y
    `[T, C]`, step by step; the state is kept in `dtype`."""
    import jax
    import jax.numpy as jnp

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t[:, None] * a) * s.astype(jnp.float32) \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        s = s.astype(dtype)
        return s, s.astype(jnp.float32) @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, dtype), (x, dt, b, c))
    return y


def causal_conv(x, w, bias):
    """Depthwise: x `[B, T, C]`, w `[C, K]` -> `y_t = bias + sum_j
    w[:, j] x_{t-K+1+j}`."""
    import jax.numpy as jnp
    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + t] * w[:, j] for j in range(k))


def silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def mamba(n, lw, cfg):
    """-> (f, y, gated): the mixer's output, its scan output before the
    gate and after it."""
    import jax
    import jax.numpy as jnp

    inner, state = lw["A_log"].shape
    rank = lw["dt_proj"].shape[1]
    xz = linear(n, lw["in_proj"])
    x, z = xz[..., :inner], xz[..., inner:]
    x = silu(causal_conv(x, lw["conv1d"], lw["conv1d_bias"]))
    dbc = linear(x, lw["x_proj"])
    delta, b, c = (dbc[..., :rank], dbc[..., rank:rank + state],
                   dbc[..., rank + state:])
    dt = jax.nn.softplus(linear(delta, lw["dt_proj"], lw["dt_proj_bias"]))
    a = -jnp.exp(lw["A_log"].astype(jnp.float32))
    # HF: selective_scan_fn, the fused scan; here the recurrence itself
    run = _jit(recurrence, ("dtype",))
    y = jnp.stack([run(x[i], dt[i], a, b[i], c[i], dtype=STATE_DTYPE)
                   for i in range(x.shape[0])])
    y = y + lw["D"] * x
    gated = y * silu(z)
    return linear(gated, lw["out_proj"]), y, gated


def memory_of(y, gated):
    """What the layer `s` hands on: its scan output before the gate."""
    return y


# ---- w, f, c: differential attention ---------------------------------------


def _maps_times_v(q, k, v, start, scale, window):
    """q `[maps, Bq, hd]` (rows start..), k `[maps, T, hd]`, v
    `[T, 2hd]` -> softmax(mask + q k^T scale) v, `[maps, Bq, 2hd]`."""
    import jax
    import jax.numpy as jnp

    scores = jnp.einsum("mqd,mkd->mqk", q, k) * scale
    i = start + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window:
        seen &= i - j < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("mqk,kd->mqd", probs, v)


def sub_norm(o, gain, eps):
    """The norm over a pair's output."""
    return rms_norm(o, gain, eps)


def combine(a1, a2, lam):
    return a1 - lam * a2


def differential(q, k, v, lw, cfg, place, window):
    """q `[B, T, P, 2, hd]`, k `[B, T, G, 2, hd]`, v `[B, T, G, 2hd]` ->
    `[B, T, P * 2hd]`: the pairs' outputs after the norm and the scale."""
    import jax.numpy as jnp

    bsz, t, pairs, _, hd = q.shape
    per = pairs // k.shape[2]
    lam_init = lambda_init(cfg, place)
    lam = jnp.exp(jnp.sum(lw["lambda_q1"] * lw["lambda_k1"])) \
        - jnp.exp(jnp.sum(lw["lambda_q2"] * lw["lambda_k2"])) + lam_init
    scale = hd ** -0.5
    out = []
    for i in range(bsz):
        rows = []
        # HF: four flash-attention calls over the whole sequence; here a
        # block of queries and a key pair's maps at a time
        for start in range(0, t, QUERY_BLOCK):
            stop = min(start + QUERY_BLOCK, t)
            groups = []
            for g in range(k.shape[2]):
                # maps (r, j): the key pair's `per` query pairs, two maps
                qs = q[i, start:stop, g * per:(g + 1) * per]
                qs = jnp.moveaxis(qs, 0, 2).reshape(per * 2, stop - start, hd)
                ks = jnp.tile(jnp.moveaxis(k[i, :, g], 0, 1), (per, 1, 1))
                o = _jit(_maps_times_v, ("window",))(
                    qs, ks, v[i, :, g], start, scale, window=window)
                o = o.reshape(per, 2, stop - start, 2 * hd)
                groups.append(combine(o[:, 0], o[:, 1], lam))
            rows.append(jnp.moveaxis(jnp.concatenate(groups), 0, 1))
        out.append(jnp.concatenate(rows))               # [T, P, 2hd]
    o = sub_norm(jnp.stack(out), lw["subln"], float(cfg["layer_norm_eps"]))
    return (o * (1.0 - lam_init)).reshape(bsz, t, pairs * 2 * hd)


def attention(n, lw, cfg, place, kind):
    """A `w` or `f` layer -> (f, (K, V))."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    bsz, t, _ = n.shape
    qkv = linear(n, lw["Wqkv"], lw["Wqkv_bias"])
    q = qkv[..., :heads * hd].reshape(bsz, t, heads // 2, 2, hd)
    k = qkv[..., heads * hd:(heads + kv) * hd].reshape(
        bsz, t, kv // 2, 2, hd)
    v = qkv[..., (heads + kv) * hd:].reshape(bsz, t, kv // 2, 2 * hd)
    o = differential(q, k, v, lw, cfg, place, window_of(cfg, kind))
    return linear(o, lw["out_proj"], lw["out_proj_bias"]), (k, v)


def cross_kv(n, lw, kv, cfg):
    """The keys and values a `c` layer reads: the layer `f`'s."""
    return kv


def cross_attention(n, lw, cfg, place, kv):
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    bsz, t, _ = n.shape
    q = linear(n, lw["Wq"], lw["Wq_bias"]).reshape(
        bsz, t, heads // 2, 2, hd)
    k, v = cross_kv(n, lw, kv, cfg)
    o = differential(q, k, v, lw, cfg, place, 0)
    return linear(o, lw["out_proj"], lw["out_proj_bias"])


# ---- g: the gated memory unit -----------------------------------------------


def gmu(n, lw, memory):
    return linear(memory * silu(linear(n, lw["in_proj"])), lw["out_proj"])


# ---- the model ---------------------------------------------------------------


def mlp(n, lw):
    gu = linear(n, lw["gate_up_proj"])
    half = gu.shape[-1] // 2
    return linear(gu[..., half:] * silu(gu[..., :half]), lw["down_proj"])


def forward(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens `[B, T]` -> logits `[B, T, rows held]`, float32."""
    import jax
    import jax.numpy as jnp

    eps = float(cfg["layer_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = weights["embed_tokens"].astype(jnp.float32)[tokens]
        memory = kv = None
        for place, (kind, lw) in enumerate(zip(cfg["layer_kinds"],
                                               weights["layers"])):
            n = layer_norm(h, lw["input_layernorm"], eps)
            if kind in "ms":
                f, y, gated = mamba(n, lw, cfg)
                if kind == "s":
                    memory = memory_of(y, gated)
            elif kind in "wf":
                f, made = attention(n, lw, cfg, place, kind)
                if kind == "f":
                    kv = made
            elif kind == "g":
                f = gmu(n, lw, memory)
            elif kind == "c":
                f = cross_attention(n, lw, cfg, place, kv)
            else:
                raise ValueError(f"layer kind {kind!r}")
            h = h + f
            h = h + mlp(layer_norm(h, lw["post_attention_layernorm"], eps),
                        lw)
        h = layer_norm(h, weights["final_layernorm"], eps)
        return linear(h, weights["embed_tokens"])


def next_token_loss(logits, targets):
    """Mean cross-entropy of `logits [B, T, V]` against `targets [B, T]`."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))
