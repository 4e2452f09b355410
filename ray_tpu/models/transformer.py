"""Decoder-only transformer, TPU-first.

Pure pytree params + jax functions (no framework objects cross the jit
boundary). One definition covers every parallelism mode: params carry
logical axis names (ray_tpu.parallel.sharding) so the same apply() runs
replicated, FSDP ("embed"->fsdp), tensor-parallel ("heads"/"mlp"->tensor),
and sequence-parallel (ring/Ulysses attention over the "seq" axis) — XLA
inserts the collectives. Layers are stacked and iterated with `lax.scan`
(one compiled layer body regardless of depth — fast compiles, and the
stacked leading dim is the natural pipeline-parallel axis). A model whose
first layers keep a dense FFN before its expert layers
(`moe_dense_layers`) is two such runs, `params["dense_layers"]` then
`params["layers"]`; a layer is what its leaves say it is. A hybrid
(`layer_pattern`: each layer a Mamba-2 mixer, an attention block or an
expert layer ALONE, `x + f(norm(x))` with one norm) is the runs of
`cfg.pattern_runs`, `params["runs"]`: each run a block, a list of unlike
sublayers, its leaves stacked over the block's repeats and scanned as one
body. A sublayer too is what its leaves say: `attn_norm` brings
attention, `ssm_norm` a mixer, `kda_norm` Kimi Delta Attention, `gmu_norm`
a gated memory unit, `mlp_norm` an FFN or experts (`w_router` makes it
experts), and the homogeneous layer is the one with both the first and
the last; `w_x` makes the mixer Mamba-1, `lambda_q1` makes
attention differential, no `wkv` makes it cross-attention, a
`<norm>_bias` makes the norm a LayerNorm, `gdn_norm` brings a Gated
DeltaNet mixer. A sublayer's residual is formed by ONE rule
(`_make_layer_fn`'s `entering` and `residual`): `x + f(norm(x))` where the
leaves have the norm `<name>_norm` that opens the sublayer, and under
OLMo-2/3's reordered norm (the kinds `d` and `a`) `x + norm(f(x))`, the
leaves holding `<name>_post_norm` in its place, and under a sandwich norm
`x + norm(f(norm(x)))`, the leaves holding both
(`TransformerConfig.norm_placement` says which the homogeneous stack's
have), and on a residual path of several streams
(`cfg.residual_streams` = n > 1, the leaves holding `<name>_hc_phi`,
`_hc_b`, `_hc_alpha`: ops/mhc.py) the stream is the n streams side by
side, `[B, T, n*d]`, from `embed` to the final norm and the sublayer leaves
`H_res X + H_post (x) f(norm(sum_i H_pre[i] X[i]))`, the three maps made of
the stream itself. A looped stack (`cfg.loops` > 1) passes the stream that
many times through the same stacked leaves, the final norm closing every pass
(`_looped_hidden`), and where `params` hold `exit_gate` each pass's
hidden state gives one f32 scalar a token, the exit gate's. What the
leaves cannot say the layer's kind in `layer_pattern` does: a window, and
which layer's tensors cross layers (`_stack`'s `shared`: the scan output
`memory` of the mixer `s`, the `k` and `v` of the attention layer `f`).
The kind `n` is a Mamba-2 mixer's leaves with an MLP's (a mixer FOLLOWED by
a dense MLP, each under its own pre-norm: Granite 4.0-H's layer, beside `l`
with `rope` off), and a model's four muP scalars (`cfg.embed_scale`,
`residual_scale`, `attn_scale`, `logit_divisor`) multiply the embedding's
output, every sublayer's output in `residual`, the scores, and divide the
logits (models/head.py); at their neutral values nothing is traced.

Packed documents: `segment_ids` [B, T] int32 (a batch's "segment_ids"
beside its "tokens") are an input of `apply` / `hidden` / `loss`, data and
not shape, a constant of the layer scan as cos and sin are. Attention
(dense and flash, causal or windowed) sees a query's own document; a
Mamba-2 mixer's convolution and scan start anew at a document's first
position (ops/ssm.py); `loss` leaves out the labels that cross a boundary
and its metrics gain `packed_docs`, `packed_labels` and
`packed_attn_pairs_needed` (int32: `_packed_labels`). Whatever else mixes
positions refuses them by name, in one place (`untaught_by_packing`); None
traces none of this.

Every block names itself with `jax.named_scope`, and the names are an
interface (PERF.md section 3; the benchmark's per-layer metrics and an
operator's `ray_tpu profile --device` read them off each op's op_name):
`embed`, `layers` (the scan's own stacking, slicing and carries),
`attn_norm`, `qkv` (projections, QK-norm and RoPE; with latent attention
`qkv/q_down`, `qkv/kv_down`, `qkv/q_up`, `qkv/kv_up`, `qkv/assemble`
inside it, `qkv/q_proj` in place of the first and third where the queries
have no latent; `qkv/qk_norm` around GQA's QK-norm), `attention` (kernels,
GQA repeat, layout transposes; `attention/block_diffusion` around the
call under the block-diffusion mask),
`attn_out`, `mlp_norm`, `mlp/gate_up`, `mlp/down` (a model that
mixes window and full layers: `attention/window` or `attention/full`
around the kernel call, and its two RoPE tables under `rope/plain` and
`rope/yarn`; differential
attention: `attention/window`, `attention/full` or `attention/cross`
around the kernel calls, `attention/diff` around lambda, the subtraction,
the pair norm and the scale; in an expert layer
`moe/router`, `moe/dispatch`, `moe/experts`, `moe/combine`, `moe/shared`,
`moe/latent`, on an expert mesh `moe/exchange`: ops/moe.py), in a mixer `ssm_norm` and `ssm/in_proj`,
`ssm/conv`, `ssm/scan`, `ssm/gate_norm`, `ssm/out_proj` (ops/ssm.py; a
Mamba-1 mixer `ssm/x_proj` and `ssm/gate` and no `ssm/gate_norm`), in a
gated memory unit `gmu_norm` and `gmu/in_proj`, `gmu/gate`,
`gmu/out_proj`, in Kimi Delta Attention `kda_norm` and `kda/qkv_proj`,
`kda/conv`, `kda/gates`, `kda/delta`, `kda/out_norm`, `kda/out_proj`
(ops/kda.py), in a Gated DeltaNet mixer `gdn/qkv_proj`, `gdn/conv`,
`gdn/gates`, `gdn/delta`, `gdn/out_norm`, `gdn/out_proj` (ops/kda.py), under
the reordered norm `attn_post_norm`, `gdn_post_norm`, `mlp_post_norm` (the
norm on a sublayer's output, inside the scope that closes the sublayer:
`attn_out/attn_post_norm`, `gdn/out_proj/gdn_post_norm`,
`mlp/down/mlp_post_norm`), `final_norm`, `head`, `loss` (the vocab head
and the cross-entropy: models/head.py); a looped stack adds `loops` (the
loop over the passes: stacking and slicing what the passes save, the sum
of the passes' weight gradients; `layers`, `final_norm` and the rest lie
inside it), `loop/exit_gate` (the product with the gate's gain) and
`loop/exit_loss` (the log-sigmoids, the exit distribution, its entropy,
laying the passes' hidden states out as rows for the head, the
weighting); a block-diffusion model's loss adds
`diffusion/noise` (the draws, the replacement, the weights and their
counts: models/diffusion.py) and `diffusion/stream` (the doubled
stream's concatenation and positions, the split before the final norm);
a residual path of several streams adds `mhc/maps` (a
sublayer's RMS statistic over all n*d values, the product with phi, the
sigmoids, the Sinkhorn rounds), `mhc/pre` (what the sublayer reads),
`mhc/post` (what it leaves, inside the scope that closes the sublayer:
`attn_out/mhc/post`, `mlp/down/mhc/post`, `moe/combine/mhc/post`),
`mhc/expand` and `mhc/collapse` (the entry after `embed`, the exit before
`final_norm`: ops/mhc.py);
the train step adds `optimizer`
(parallel/train_step.py); packed documents add `segments`, nested in the
scope whose work it is: `loss/segments` (the labels' mask and the step's
counters), `ssm/conv/segments` and `ssm/scan/segments` (the masks and the
chunks' marks, outside the kernels). Scopes are metadata only. Forward, backward
and recomputation need none: JAX wraps the path in `jvp(...)`,
`transpose(jvp(...))` and remat's `rematted_computation`.

Reference parity note: the reference has no in-tree LM (SURVEY.md §2.3,
§5.7); its model math arrives via user torch code over NCCL groups. This
module is the TPU-native replacement for that entire delegated stack.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from ray_tpu.models import head
from ray_tpu.models.configs import (EXPERT_KINDS, FFN_KINDS,
                                       REORDERED_KINDS, TransformerConfig)
from ray_tpu.parallel.mesh import AXIS_SEQ
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint


def _rope_tables(positions, head_dim, theta, yarn=None):
    """cos/sin tables [..., T, half] (f32) for explicit positions — global
    positions keep RoPE exact when the sequence axis is sharded. Computed
    once per forward and closed over by the layer scan (not recomputed
    per layer). `yarn` = (factor, original_len, beta_fast, beta_slow,
    attention_factor): YaRN's table (`TransformerConfig.rope_yarn_factor`),
    the frequencies of the pairs that turn fewer than beta_slow times over
    original_len positions divided by factor, those that turn more than
    beta_fast times kept, a linear blend between, and cos and sin times
    attention_factor."""
    import math

    import jax.numpy as jnp

    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    scale = None
    if yarn is not None:
        factor, original_len, beta_fast, beta_slow, scale = yarn

        def pair_that_turns(times):   # the (fractional) pair that does
            return head_dim * math.log(
                original_len / (times * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(pair_that_turns(beta_fast)), 0)
        high = min(math.ceil(pair_that_turns(beta_slow)), head_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs * (1.0 - ramp) + freqs / factor * ramp
    angles = positions[..., None].astype(jnp.float32) * freqs
    if scale is None:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rope(x, cos, sin):
    """Apply rotary embedding to [..., T, H, D] given [..., T, half]
    tables."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    c = cos[..., None, :]  # broadcast over heads: [..., T, 1, half]
    s = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    scale = jnp.reciprocal(
        jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps))
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype)


def _qk_norm(x, gain, eps):
    """QK-norm of q or k `[B, T, H, D]`, as the gain's width says: one
    head's (`[D]`: each head normed over its own columns, the heads
    sharing the gain) or the whole projection's (`[H * D]`: all heads
    normed together)."""
    if gain.shape[-1] == x.shape[-1]:
        return _rmsnorm(x, gain, eps)
    return _rmsnorm(x.reshape(x.shape[:2] + (-1,)), gain,
                    eps).reshape(x.shape)


def _layernorm(x, w, b, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jnp.reciprocal(
        jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps))
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype) \
        + b.astype(x.dtype)


def _norm(x, leaves, name, eps):
    """The norm whose leaves are `name` (and `name_bias`): a LayerNorm
    where there is a bias, else RMSNorm."""
    if name + "_bias" in leaves:
        return _layernorm(x, leaves[name], leaves[name + "_bias"], eps)
    return _rmsnorm(x, leaves[name], eps)


# the norms that open a sublayer, by their gain's leaf
NORMS = ("attn_norm", "ssm_norm", "kda_norm", "gmu_norm", "gdn_norm",
         "mlp_norm")


def _reordered(sub):
    """A sublayer's leaves (or specs) under the reordered norm: the norm
    that would open it, `<name>_norm`, is the one on its output,
    `<name>_post_norm`."""
    return {name[:-len("norm")] + "post_norm" if name in NORMS else name:
            leaf for name, leaf in sub.items()}


POST_NORMS = tuple(_reordered(dict.fromkeys(NORMS)))


def _placed(sub, placement):
    """The homogeneous layer's leaves (or specs), made with the norms
    that open its sublayers, with its norms where `placement`
    (`TransformerConfig.norm_placement`) says: "pre" as they are, "post"
    `_reordered`, "both" each `<name>_norm` and a `<name>_post_norm` like
    it (the sandwich norm)."""
    if placement == "pre":
        return sub
    after = _reordered(sub)
    return after if placement == "post" else {**sub, **after}


# the tensors of `_stack`'s `shared` each kind of layer makes
MAKES = {"s": ("memory",), "f": ("k", "v")}

# the logical axes of several streams as [B, T, n, d] (a layer function
# takes that form too; the model carries them flat, [B, T, n*d])
STREAMS = ("batch", "seq", None, "act_embed")


class Transformer:
    """Namespace for init / param_specs / apply / loss."""

    # ---- parameter construction ------------------------------------
    @staticmethod
    def init(key, cfg: TransformerConfig) -> Dict[str, Any]:
        import math

        import jax
        import jax.numpy as jnp

        pdt = jnp.dtype(cfg.param_dtype)
        d, hd = cfg.d_model, cfg.head_dim
        nh, nkv, f = cfg.n_heads, cfg.kv_heads, cfg.ff_dim

        def norm_init(stddev, k, shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    * stddev).astype(pdt)

        def attention(l, keys):
            """One run of l layers' attention leaves and norm gains."""
            layers = {
                "attn_norm": jnp.ones((l, d), pdt),
                "wo": norm_init((nh * cfg.v_dim) ** -0.5, keys[4],
                                (l, nh, cfg.v_dim, d)),
                "mlp_norm": jnp.ones((l, d), pdt),
            }
            if cfg.kv_lora_rank:
                # latent attention: down-projections to the latents (the
                # shared rotary key head rides on kv's), a norm gain per
                # latent, up-projections to the heads ([q_nope ; q_rope]
                # and [k_nope ; v] per head)
                qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
                if qr:
                    layers["wq_a"] = norm_init(d ** -0.5, keys[1],
                                               (l, d, qr))
                    layers["q_a_norm"] = jnp.ones((l, qr), pdt)
                    layers["wq_b"] = norm_init(qr ** -0.5, keys[2],
                                               (l, qr, nh, hd))
                else:   # no query latent: one projection of the stream
                    layers["wq"] = norm_init(d ** -0.5, keys[1],
                                             (l, d, nh, hd))
                layers["wkv_a"] = norm_init(d ** -0.5, keys[3],
                                            (l, d, kvr + cfg.rope_dim))
                layers["kv_a_norm"] = jnp.ones((l, kvr), pdt)
                layers["wkv_b"] = norm_init(
                    kvr ** -0.5, jax.random.fold_in(keys[3], 1),
                    (l, kvr, nh, cfg.qk_nope_head_dim + cfg.v_dim))
            elif nkv == nh:
                # QKV and gate/up projections are FUSED along an unsharded
                # group axis (one wide MXU matmul instead of 3/2 narrow
                # ones; slicing the group axis never crosses a shard
                # boundary). MHA fuses q,k,v into wqkv[..., 3, nh, hd]; GQA
                # keeps wq separate and fuses k,v.
                layers["wqkv"] = jnp.stack(
                    [norm_init(d ** -0.5, keys[1], (l, d, nh, hd)),
                     norm_init(d ** -0.5, keys[2], (l, d, nh, hd)),
                     norm_init(d ** -0.5, keys[3], (l, d, nh, hd))],
                    axis=2)  # (l, d, 3, nh, hd)
            else:
                layers["wq"] = norm_init(d ** -0.5, keys[1], (l, d, nh, hd))
                layers["wkv"] = jnp.stack(
                    [norm_init(d ** -0.5, keys[2], (l, d, nkv, hd)),
                     norm_init(d ** -0.5, keys[3], (l, d, nkv, hd))],
                    axis=2)  # (l, d, 2, nkv, hd)
            if cfg.qk_norm and (cfg.kv_lora_rank or cfg.qk_norm_per_head):
                # one gain a side over a head (latent attention's always)
                layers["q_norm"] = jnp.ones((l, hd), pdt)
                layers["k_norm"] = jnp.ones((l, hd), pdt)
            elif cfg.qk_norm:
                # RMSNorm gains over the whole q / k projection (all heads
                # together), applied before the split into heads and RoPE
                layers["q_norm"] = jnp.ones((l, nh * hd), pdt)
                layers["k_norm"] = jnp.ones((l, nkv * hd), pdt)
            if cfg.attn_bias:
                layers["bq"] = jnp.zeros((l, nh, hd), pdt)
                layers["bkv"] = jnp.zeros((l, 2, nkv, hd), pdt)
                layers["bo"] = jnp.zeros((l, d), pdt)
            if cfg.diff_attention:
                # lambda's four vectors at the published 0.1, the pair
                # norm's gain; lambda_init is set by `sublayer`, which
                # knows the layers' places
                for i, name in enumerate(("lambda_q1", "lambda_k1",
                                          "lambda_q2", "lambda_k2")):
                    layers[name] = norm_init(
                        0.1, jax.random.fold_in(keys[4], 1 + i), (l, hd))
                layers["subln"] = jnp.ones((l, 2 * hd), pdt)
            return layers

        def gated(keys, lead, width, d_in=d):
            """A gated FFN's two leaves: gate and up fused, and down."""
            return (jnp.stack(
                [norm_init(d_in ** -0.5, keys[5], lead + (d_in, width)),
                 norm_init(d_in ** -0.5, keys[6], lead + (d_in, width))],
                axis=len(lead) + 1),  # lead + (d_in, 2, width)
                norm_init(width ** -0.5, keys[7], lead + (width, d_in)))

        def ffn(keys, lead, width, d_in=d):
            """A gated FFN's leaves, or a plain one's `(up, down)`."""
            if cfg.moe_gated:
                return gated(keys, lead, width, d_in)
            return (norm_init(d_in ** -0.5, keys[5], lead + (d_in, width)),
                    norm_init(width ** -0.5, keys[7], lead + (width, d_in)))

        def experts(l, key, keys):
            """One run of l expert layers' leaves (ops/moe.py): per-layer
            router + stacked expert weights (those held here), each expert
            an MLP like the dense one below, in a latent where the model
            has one; expert dim sharded over the "expert" axis."""
            e, held = cfg.moe_experts, cfg.held_experts
            first = "gateup" if cfg.moe_gated else "up"
            layers = {"w_router": norm_init(
                0.02, jax.random.fold_in(key, 98),
                (l, d, e)).astype(jnp.float32)}
            layers["w_moe_" + first], layers["w_moe_down"] = ffn(
                keys, (l, held), f, cfg.moe_latent or d)
            if cfg.moe_scoring == "sigmoid":
                # the choice bias: a buffer (Transformer.frozen), zero
                # until whoever balances the load moves it
                layers["router_bias"] = jnp.zeros((l, e), jnp.float32)
            if cfg.moe_shared_experts:
                shared = jax.random.split(jax.random.fold_in(key, 97), 8)
                layers["w_shared_" + first], layers["w_shared_down"] = ffn(
                    shared, (l,), cfg.shared_ff)
            if cfg.moe_latent:
                latent = jax.random.split(jax.random.fold_in(key, 95))
                layers["w_latent_down"] = norm_init(
                    d ** -0.5, latent[0], (l, d, cfg.moe_latent))
                layers["w_latent_up"] = norm_init(
                    cfg.moe_latent ** -0.5, latent[1],
                    (l, cfg.moe_latent, d))
            return layers

        def mixer(l, key):
            """One run of l Mamba-2 mixers' leaves (ops/ssm.py), A and dt
            from the published initialiser: A uniform in [1, 16], dt
            log-uniform in [0.001, 0.1] through softplus's inverse, D 1, the
            convolution uniform within 1/sqrt(taps)."""
            ks = jax.random.split(key, 5)
            inner, conv, nh_ = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
            dt = jnp.exp(jax.random.uniform(
                ks[3], (l, nh_), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            bound = cfg.ssm_conv_kernel ** -0.5   # a depthwise tap's fan-in
            return {
                "ssm_norm": jnp.ones((l, d), pdt),
                "w_in": norm_init(d ** -0.5, ks[0],
                                  (l, d, inner + conv + nh_)),
                "conv_w": jax.random.uniform(
                    ks[1], (l, conv, cfg.ssm_conv_kernel), jnp.float32,
                    -bound, bound).astype(pdt),
                "conv_b": jax.random.uniform(
                    jax.random.fold_in(ks[1], 1), (l, conv), jnp.float32,
                    -bound, bound).astype(pdt),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (l, nh_), jnp.float32, 1.0, 16.0)).astype(pdt),
                "D": jnp.ones((l, nh_), pdt),
                "gate_norm": jnp.ones((l, inner), pdt),
                "w_out": norm_init(inner ** -0.5, ks[2], (l, inner, d)),
            }

        def mixer1(l, key):
            """One run of l Mamba-1 mixers' leaves (ops/ssm.py), from the
            published initialiser: A_{c,n} = n + 1, dt log-uniform in
            [0.001, 0.1] through softplus's inverse, D 1."""
            ks = jax.random.split(key, 6)
            inner, n, rank = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_dt_rank
            dt = jnp.exp(jax.random.uniform(
                ks[3], (l, inner), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            bound = cfg.ssm_conv_kernel ** -0.5
            return {
                "ssm_norm": jnp.ones((l, d), pdt),
                "w_in": norm_init(d ** -0.5, ks[0], (l, d, 2 * inner)),
                "conv_w": jax.random.uniform(
                    ks[1], (l, inner, cfg.ssm_conv_kernel), jnp.float32,
                    -bound, bound).astype(pdt),
                "conv_b": jax.random.uniform(
                    jax.random.fold_in(ks[1], 1), (l, inner), jnp.float32,
                    -bound, bound).astype(pdt),
                "w_x": norm_init(inner ** -0.5, ks[4],
                                 (l, inner, rank + 2 * n)),
                "w_dt": norm_init(rank ** -0.5, ks[5], (l, rank, inner)),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32)), (l, inner, n)).astype(pdt),
                "D": jnp.ones((l, inner), pdt),
                "w_out": norm_init(inner ** -0.5, ks[2], (l, inner, d)),
            }

        def kda(l, key):
            """One run of l Kimi Delta Attention sublayers' leaves
            (ops/kda.py): A_log uniform in log [1, 16] as a Mamba-2
            mixer's, the decay's bias zero, the convolutions uniform within
            1/sqrt(taps)."""
            ks = jax.random.split(key, 7)
            h_, hd_, taps = cfg.kda_heads, cfg.kda_head_dim, \
                cfg.kda_conv_kernel
            bound = taps ** -0.5
            return {
                "kda_norm": jnp.ones((l, d), pdt),
                "w_kda_qkv": norm_init(d ** -0.5, ks[0], (l, d, 3, h_, hd_)),
                "kda_conv": jax.random.uniform(
                    ks[1], (l, 3, h_ * hd_, taps), jnp.float32, -bound,
                    bound).astype(pdt),
                "w_kda_a": norm_init(d ** -0.5, ks[2], (l, d, h_, hd_)),
                "kda_A_log": jnp.log(jax.random.uniform(
                    ks[3], (l, h_), jnp.float32, 1.0, 16.0)).astype(pdt),
                "kda_a_bias": jnp.zeros((l, h_, hd_), pdt),
                "w_kda_bg": norm_init(d ** -0.5, ks[4], (l, d, 2, h_)),
                "kda_out_norm": jnp.ones((l, hd_), pdt),
                "w_kda_out": norm_init((h_ * hd_) ** -0.5, ks[5],
                                       (l, h_, hd_, d)),
            }

        def gdn(l, key):
            """One run of l Gated DeltaNet mixers' leaves (ops/kda.py), a
            head's q, k and v columns side by side: A uniform in (0, 16]
            and dt log-uniform in [0.001, 0.1] through softplus's inverse,
            as a Mamba-2 mixer's; the convolution uniform within
            1/sqrt(taps)."""
            ks = jax.random.split(key, 7)
            h_, taps = cfg.gdn_heads, cfg.gdn_conv_kernel
            dv = cfg.gdn_value_dim
            wide = 2 * cfg.gdn_key_dim + dv
            bound = taps ** -0.5
            dt = jnp.exp(jax.random.uniform(
                ks[3], (l, h_), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return {
                "gdn_norm": jnp.ones((l, d), pdt),
                "w_gdn_qkv": norm_init(d ** -0.5, ks[0], (l, d, h_, wide)),
                "gdn_conv": jax.random.uniform(
                    ks[1], (l, h_, wide, taps), jnp.float32, -bound,
                    bound).astype(pdt),
                "w_gdn_ab": norm_init(d ** -0.5, ks[2], (l, d, 2, h_)),
                "gdn_A_log": jnp.log(jax.random.uniform(
                    ks[4], (l, h_), jnp.float32, 1e-4, 16.0)).astype(pdt),
                "gdn_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
                "w_gdn_g": norm_init(d ** -0.5, ks[5], (l, d, h_, dv)),
                "gdn_out_norm": jnp.ones((l, dv), pdt),
                "w_gdn_out": norm_init((h_ * dv) ** -0.5, ks[6],
                                       (l, h_, dv, d)),
            }

        def streams(l, key):
            """One run of l layers' hyper-connection leaves (ops/mhc.py),
            both sublayers': the published kind of start, every map all
            but static (alpha 0.01) and the layer the one-stream layer on
            n equal streams: H_pre 1/n, H_post 1, H_res near the
            identity (off the diagonal e^-8 before the rounds)."""
            n, maps = cfg.residual_streams, cfg.hc_maps
            eye = jnp.where(jnp.eye(n, dtype=bool), 0.0, -8.0).reshape(-1)
            b = jnp.concatenate([jnp.full((n,), -math.log(n - 1)),
                                 jnp.zeros((n,)), eye]).astype(pdt)
            leaves = {}
            for i, name in enumerate(("attn", "mlp")):
                leaves[name + "_hc_phi"] = norm_init(
                    (n * d) ** -0.5, jax.random.fold_in(key, 90 + i),
                    (l, n * d, maps))
                leaves[name + "_hc_b"] = jnp.broadcast_to(b, (l, maps))
                leaves[name + "_hc_alpha"] = jnp.full((l, 3), 0.01, pdt)
            return leaves

        def with_mlp(sub, l, keys):
            """A lower-case kind: the sublayer, then a dense MLP."""
            sub["mlp_norm"] = jnp.ones((l, d), pdt)
            sub["w_gateup"], sub["w_down"] = gated(
                keys, (l,), cfg.moe_dense_ff or f)
            return sub

        def sublayer(kind, l, key, places):
            """`places`: the l layers' indices in the pattern."""
            keys = jax.random.split(key, 8)
            if kind == "M":
                sub = mixer(l, key)
            elif kind == "*":
                sub = attention(l, keys)
                del sub["mlp_norm"]
            elif kind in "ms":
                sub = with_mlp(mixer1(l, key), l, keys)
            elif kind == "n":
                sub = with_mlp(mixer(l, key), l, keys)
            elif kind in "wfc":
                sub = with_mlp(attention(l, keys), l, keys)
                if kind == "c":   # the keys and values are the layer f's
                    sub = {name: leaf for name, leaf in sub.items()
                           if name not in ("wkv", "bkv")}
            elif kind == "k":
                sub = with_mlp(kda(l, key), l, keys)
            elif kind in "la":
                sub = with_mlp(attention(l, keys), l, keys)
            elif kind == "d":
                sub = with_mlp(gdn(l, key), l, keys)
            elif kind == "K":
                sub = dict(kda(l, key), **experts(l, key, keys),
                           mlp_norm=jnp.ones((l, d), pdt))
            elif kind in "LW":
                sub = dict(attention(l, keys), **experts(l, key, keys))
            elif kind == "g":
                inner = cfg.ssm_d_inner
                sub = with_mlp({
                    "gmu_norm": jnp.ones((l, d), pdt),
                    "w_gmu_in": norm_init(d ** -0.5, keys[1], (l, d, inner)),
                    "w_gmu_out": norm_init(inner ** -0.5, keys[2],
                                           (l, inner, d))}, l, keys)
            else:
                sub = dict(experts(l, key, keys),
                           mlp_norm=jnp.ones((l, d), pdt))
            if "lambda_q1" in sub:   # a buffer (Transformer.frozen)
                sub["lambda_init"] = jnp.asarray(
                    [cfg.lambda_init(i) for i in places], jnp.float32)
            if kind in REORDERED_KINDS:
                sub = _reordered(sub)
            if cfg.norm == "layernorm":
                sub.update({name + "_bias": jnp.zeros((l, d), pdt)
                            for name in NORMS + POST_NORMS if name in sub})
            return sub

        keys = jax.random.split(key, 8)
        params = {
            "embed": norm_init(0.02, keys[0], (cfg.vocab_size, d)),
            "final_norm": jnp.ones((d,), pdt),
        }
        if cfg.norm == "layernorm":
            params["final_norm_bias"] = jnp.zeros((d,), pdt)
        if cfg.layer_pattern:
            params["runs"], at = [], 0
            for n, (block, repeats) in enumerate(cfg.pattern_runs):
                params["runs"].append([
                    sublayer(kind, repeats,
                             jax.random.fold_in(key, 1000 + 100 * n + i),
                             range(at + i, at + len(block) * repeats,
                                   len(block)))
                    for i, kind in enumerate(block)])
                at += len(block) * repeats
        else:
            l = cfg.n_layers - cfg.moe_dense_layers
            layers = attention(l, keys)
            if cfg.moe_experts:
                layers.update(experts(l, key, keys))
            else:
                layers["w_gateup"], layers["w_down"] = gated(keys, (l,), f)
            if cfg.residual_streams > 1:
                layers.update(streams(l, key))
            params["layers"] = _placed(layers, cfg.norm_placement)
        if cfg.exit_gate:
            # the exit gate: a gain over the normed hidden state, a bias
            params["exit_gate"] = norm_init(
                d ** -0.5, jax.random.fold_in(key, 94), (d,))
            params["exit_gate_bias"] = jnp.zeros((1,), pdt)
        if cfg.moe_dense_layers:
            lead = jax.random.split(jax.random.fold_in(key, 96), 8)
            dense = attention(cfg.moe_dense_layers, lead)
            dense["w_gateup"], dense["w_down"] = gated(
                lead, (cfg.moe_dense_layers,), cfg.moe_dense_ff or f)
            if cfg.residual_streams > 1:
                dense.update(streams(cfg.moe_dense_layers,
                                     jax.random.fold_in(key, 96)))
            params["dense_layers"] = _placed(dense, cfg.norm_placement)
        if not cfg.tie_embeddings:
            params["lm_head"] = norm_init(
                d ** -0.5, jax.random.fold_in(key, 99), (d, cfg.vocab_size))
        return params

    @staticmethod
    def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
        """Logical sharding spec tree, same structure as init()'s output."""
        def attention():
            layers = {
                "attn_norm": ("layers", "norm"),
                "wo": ("layers", "heads", "head_dim", "embed"),
                "mlp_norm": ("layers", "norm"),
            }
            if cfg.kv_lora_rank:
                # the latents are narrow and every head reads all of them:
                # down-projections shard with the model width, the
                # up-projections by head
                if cfg.q_lora_rank:
                    layers["wq_a"] = ("layers", "embed", None)
                    layers["q_a_norm"] = ("layers", "norm")
                    layers["wq_b"] = ("layers", None, "heads", "head_dim")
                else:
                    layers["wq"] = ("layers", "embed", "heads", "head_dim")
                layers["wkv_a"] = ("layers", "embed", None)
                layers["kv_a_norm"] = ("layers", "norm")
                layers["wkv_b"] = ("layers", None, "heads", "head_dim")
            elif cfg.kv_heads == cfg.n_heads:
                layers["wqkv"] = ("layers", "embed", None, "heads",
                                  "head_dim")
            else:
                layers["wq"] = ("layers", "embed", "heads", "head_dim")
                layers["wkv"] = ("layers", "embed", None, "kv_heads",
                                 "head_dim")
            if cfg.qk_norm:
                layers["q_norm"] = ("layers", "norm")
                layers["k_norm"] = ("layers", "norm")
            if cfg.attn_bias:
                layers["bq"] = ("layers", "heads", "head_dim")
                layers["bkv"] = ("layers", None, "kv_heads", "head_dim")
                layers["bo"] = ("layers", "norm")
            if cfg.diff_attention:
                for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2", "subln"):
                    layers[name] = ("layers", None)
                layers["lambda_init"] = ("layers",)
            return layers

        dense_ffn = {"w_gateup": ("layers", "embed", None, "mlp"),
                     "w_down": ("layers", "mlp", "embed")}
        # the hyper-connections' leaves of both sublayers (ops/mhc.py)
        streams = {} if cfg.residual_streams == 1 else {
            name + leaf: spec for name in ("attn", "mlp")
            for leaf, spec in (("_hc_phi", ("layers", "embed", None)),
                               ("_hc_b", ("layers", None)),
                               ("_hc_alpha", ("layers", None)))}
        # Kimi Delta Attention's heads are not sharded here
        kda = {"kda_norm": ("layers", "norm"),
               "w_kda_qkv": ("layers", "embed", None, None, None),
               "kda_conv": ("layers", None, None, None),
               "w_kda_a": ("layers", "embed", None, None),
               "kda_A_log": ("layers", None),
               "kda_a_bias": ("layers", None, None),
               "w_kda_bg": ("layers", "embed", None, None),
               "kda_out_norm": ("layers", None),
               "w_kda_out": ("layers", None, None, "embed")}

        # nor are a Gated DeltaNet mixer's
        gdn = {"gdn_norm": ("layers", "norm"),
               "w_gdn_qkv": ("layers", "embed", None, None),
               "gdn_conv": ("layers", None, None, None),
               "w_gdn_ab": ("layers", "embed", None, None),
               "gdn_A_log": ("layers", None),
               "gdn_dt_bias": ("layers", None),
               "w_gdn_g": ("layers", "embed", None, None),
               "gdn_out_norm": ("layers", None),
               "w_gdn_out": ("layers", None, None, "embed")}

        def experts():
            # an expert's own d_model dimension has a name of its own
            # (parallel/sharding.py: `expert_embed`)
            layers = {"w_router": ("layers", "embed", None),
                      "w_moe_down": ("layers", "expert", "mlp",
                                     "expert_embed")}
            if cfg.moe_gated:
                layers["w_moe_gateup"] = ("layers", "expert", "expert_embed",
                                          None, "mlp")
            else:
                layers["w_moe_up"] = ("layers", "expert", "expert_embed",
                                      "mlp")
            if cfg.moe_scoring == "sigmoid":
                layers["router_bias"] = ("layers", None)
            if cfg.moe_shared_experts and cfg.moe_gated:
                layers["w_shared_gateup"] = dense_ffn["w_gateup"]
            elif cfg.moe_shared_experts:
                layers["w_shared_up"] = ("layers", "embed", "mlp")
            if cfg.moe_shared_experts:
                layers["w_shared_down"] = dense_ffn["w_down"]
            if cfg.moe_latent:
                layers["w_latent_down"] = ("layers", "embed", None)
                layers["w_latent_up"] = ("layers", None, "embed")
            return layers

        def sublayer(kind):
            if kind in "Mnms":   # a mixer's channels are not sharded here
                sub = {"ssm_norm": ("layers", "norm"),
                       "w_in": ("layers", "embed", None),
                       "conv_w": ("layers", None, None),
                       "conv_b": ("layers", None),
                       "dt_bias": ("layers", None),
                       "D": ("layers", None),
                       "w_out": ("layers", None, "embed")}
                if kind in "Mn":
                    sub.update(A_log=("layers", None),
                               gate_norm=("layers", None))
                else:
                    sub.update(A_log=("layers", None, None),
                               w_x=("layers", None, None),
                               w_dt=("layers", None, None))
            elif kind in "kK":
                sub = dict(kda)
            elif kind == "d":
                sub = dict(gdn)
            elif kind in "*wfclLWa":
                sub = attention()
                if kind == "*":
                    del sub["mlp_norm"]
                if kind == "c":
                    sub = {name: spec for name, spec in sub.items()
                           if name not in ("wkv", "bkv")}
            elif kind == "g":
                sub = {"gmu_norm": ("layers", "norm"),
                       "w_gmu_in": ("layers", "embed", None),
                       "w_gmu_out": ("layers", None, "embed")}
            else:
                sub = {}
            if kind in EXPERT_KINDS:
                sub.update(experts(), mlp_norm=("layers", "norm"))
            if kind in FFN_KINDS:
                sub.update(dense_ffn, mlp_norm=("layers", "norm"))
            if kind in REORDERED_KINDS:
                sub = _reordered(sub)
            if cfg.norm == "layernorm":
                sub.update({name + "_bias": ("layers", "norm")
                            for name in NORMS + POST_NORMS if name in sub})
            return sub

        specs = {
            "embed": ("vocab", "embed"),
            "final_norm": ("norm",),
        }
        if cfg.norm == "layernorm":
            specs["final_norm_bias"] = ("norm",)
        if cfg.layer_pattern:
            specs["runs"] = [[sublayer(kind) for kind in block]
                             for block, _ in cfg.pattern_runs]
        else:
            layers = attention()
            layers.update(experts() if cfg.moe_experts else dense_ffn)
            specs["layers"] = _placed(dict(layers, **streams),
                                      cfg.norm_placement)
        if cfg.exit_gate:
            specs["exit_gate"] = ("norm",)
            specs["exit_gate_bias"] = (None,)
        if cfg.moe_dense_layers:
            specs["dense_layers"] = _placed(
                dict(attention(), **dense_ffn, **streams),
                cfg.norm_placement)
        if not cfg.tie_embeddings:
            specs["lm_head"] = ("embed", "vocab")
        return specs

    @staticmethod
    def frozen(cfg: TransformerConfig) -> Dict[str, Any]:
        """Which leaves of init()'s tree are buffers and not parameters
        (True): what `make_train_step(frozen=...)` keeps as it is, whatever
        the gradient and the optimizer's weight decay. Today the sigmoid
        router's choice bias (`e_score_correction_bias`) and differential
        attention's `lambda_init`, a constant of the layer's place."""
        import jax

        specs = Transformer.param_specs(cfg)
        mask = jax.tree.map(lambda _: False, specs,
                            is_leaf=lambda x: isinstance(x, tuple))
        blocks = mask["runs"] if "runs" in mask else [[mask["layers"]]]
        for block in blocks:
            for sub in block:
                for name in ("router_bias", "lambda_init"):
                    if name in sub:
                        sub[name] = True
        return mask

    # ---- forward ----------------------------------------------------
    @staticmethod
    def embed(params, tokens, cfg: TransformerConfig, *,
              mesh=None, rules: Optional[ShardingRules] = None):
        """tokens [B, T] int32 -> embeddings [B, T, d] (compute dtype); on
        a residual path of several streams (`cfg.residual_streams` = n >
        1) the embedding repeated into every stream, flat: [B, T, n*d],
        stream i the columns i*d:(i+1)*d (`ops/mhc.expand`, under
        `mhc/expand`; what the mixing's kernels tile, and no [.., n, d]
        array, whose minor axes the TPU pads or lays out tokens-minor)."""
        import jax
        import jax.numpy as jnp

        constrain = functools.partial(
            with_logical_constraint, mesh=mesh, rules=rules)

        # Constrain the lookup operand's embed dim to the ACTIVATION
        # sharding (replicated / tensor) rather than the param's fsdp
        # sharding: with the table's feature dim matching the output
        # layout, the gather partitions on the (batch/seq-sharded) index
        # dims directly. Leaving it fsdp-sharded makes SPMD emit a
        # d-sharded gather then an "involuntary full rematerialization"
        # to reshard d->batch/seq. This is the FSDP gather-at-use
        # pattern: fwd all-gathers the table's d shards, bwd
        # reduce-scatters the grad.
        with jax.named_scope("embed"):
            emb = constrain(params["embed"], ("vocab", "act_embed"))
            x = jnp.take(emb, tokens, axis=0)
            if cfg.embed_scale != 1.0:   # muP's `embedding_multiplier`
                x = x.astype(jnp.float32) * cfg.embed_scale
            x = constrain(x.astype(jnp.dtype(cfg.dtype)),
                          ("batch", "seq", "act_embed"))
        if cfg.residual_streams > 1:
            from ray_tpu.ops import mhc
            x = constrain(mhc.expand(x, cfg.residual_streams, flat=True),
                          ("batch", "seq", "act_embed"))
        return x

    @staticmethod
    def _remat(layer, cfg: TransformerConfig):
        """`layer` under cfg's rematerialization: the one place a layer is
        wrapped in `jax.checkpoint`.

        The default, "attention", keeps per layer, beside the scan's
        carry, what the layer names: what the flash forward kernel hands
        its backward (`ops.attention.FLASH_RESIDUALS`: the output
        [B, H, T, Dv] in the compute dtype and the logsumexp [B, H, T] in
        f32), an expert layer's routing (`ops.moe.ROUTING_RESIDUALS`:
        the router's logits [N, E] f32, the chosen experts [N, k] int32
        and their scores f32, `keep` [N, held] where held < k, the sort's
        two permutations [N·min(k, held)] and counts [E], int32) and what
        the delta rule's forward kernel hands its backward
        (`ops.kda.DELTA_RESIDUALS`, all f32: the output [B, T, H·D], the
        states entering the chunks [B, T/64, H·Dv, D] and the chunks'
        inverses [B, T/64, H/2·64, 128]). It recomputes the rest (norms,
        projections, RoPE, the convolutions and gates, the MLP or the
        experts past the sort). The backward pass then runs the forward
        kernels, the doubling of a chunk's inverse, the f32 router
        product, the top-k and the sorts once a step, not twice. The rule
        adapts by what the traced layer holds: only the flash path, a
        router and the delta rule's pallas kernels name those values, so
        under `dense`, `ring` or `ulysses` attention with a dense FFN, and
        under the delta rule in plain XLA (`ops.kda.gated_delta_rule`: the
        CPU, a mesh above one device, a decay a head), nothing is named,
        nothing is saved and the program is "full"'s. At many layers it
        costs L x B*T*H*Dv x 2 bytes (+ L x B*H*T x 4), an expert layer
        N*E x 4 bytes (+ the `[N, k]`s) and a KDA layer B*T*H x (4 D +
        8 Dv + 256) bytes (201 + 33.5 MB at 16,384 tokens of 8 heads of
        128) more than "full", which saves nothing but the carry and is
        there for whoever needs those bytes. "dots" saves every matmul's
        output besides. The carry is the stream between layers: B*T*d x 2
        bytes a layer in bf16, and on a residual path of n streams
        (`cfg.residual_streams`) n times that, `[B, T, n*d]` (235 MB a
        layer at 8,192 tokens of 4 x 3,584), kept a LAYER and not a
        sublayer; of a sublayer's mixing what `ops/mhc.enter`'s forward
        hands its backward is kept (`ops.mhc.MAPS_RESIDUALS`: the product
        with phi under the statistic `[n*n + 2n, B, T]` and the statistic
        `[B, T]`, f32, 0.8 MB there): remat's forward makes neither
        again, only the 20 rounds on the kept product and, from the
        recomputed stream, what the sublayer reads."""
        import jax

        from ray_tpu.ops.attention import FLASH_RESIDUALS
        from ray_tpu.ops.kda import DELTA_RESIDUALS
        from ray_tpu.ops.mhc import MAPS_RESIDUALS
        from ray_tpu.ops.moe import ROUTING_RESIDUALS

        policies = jax.checkpoint_policies
        residuals = policies.save_only_these_names(
            FLASH_RESIDUALS, ROUTING_RESIDUALS, DELTA_RESIDUALS,
            MAPS_RESIDUALS)
        known = {"attention": residuals, "full": None,
                 "dots": policies.save_from_both_policies(
                     policies.checkpoint_dots, residuals)}
        if cfg.remat_policy not in known:
            raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")
        if not cfg.remat:
            return layer
        return jax.checkpoint(layer, policy=known[cfg.remat_policy])

    @staticmethod
    def _stack(layers, x, cfg: TransformerConfig, *, mesh,
               rules: ShardingRules, positions=None, kinds=None,
               shared=None, noised: int = 0, segment_ids=None):
        """x [B, T, d] through a run of stacked layers (leaves
        [n, ...]: all of them in hidden(), one stage's in pipeline_loss())
        -> (x, routing, shared), `routing` the layers' stacked MoE records
        (None for dense FFN configs). A run that is a list is a block of
        unlike sublayers (module docstring) of the kinds `kinds`: the
        scan's body runs them in turn, each under `_remat` by itself, and
        `routing` is the list of the expert sublayers' stacked records.

        `shared` holds the tensors that cross layers, by name (`MAKES`):
        the run's layers read them as constants of the scan, so that the
        backward pass keeps one copy and their gradient is the sum over
        the layers that read them, and as inputs of a layer under
        `_remat`, which does not compute them again. A run with a layer
        that makes one runs once (`TransformerConfig` sees to it) and
        without a scan; the returned `shared` has what it made. `noised`:
        the leading positions of x that are the noised copy of the ones
        behind them (a block-diffusion model's doubled stream; 0: a plain
        stream). `segment_ids` [B, T] int32 or None: packed documents, a
        constant of the scan as cos and sin are, handed to every sublayer
        that mixes positions."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        cos = sin = window_rope = None
        if cfg.rope:
            if positions is None:
                positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
            if cfg.rope_yarn_factor:
                # two tables in one model: YaRN's for the layers that see
                # the whole context, the plain one for the window kinds
                with jax.named_scope("rope/plain"):
                    window_rope = _rope_tables(positions, cfg.rope_dim,
                                               cfg.rope_theta)
                with jax.named_scope("rope/yarn"):
                    cos, sin = _rope_tables(
                        positions, cfg.rope_dim, cfg.rope_theta,
                        (cfg.rope_yarn_factor, cfg.rope_yarn_original_len,
                         cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow,
                         cfg.yarn_attention_factor))
            else:
                with jax.named_scope("qkv"):
                    cos, sin = _rope_tables(positions, cfg.rope_dim,
                                            cfg.rope_theta)
        layer_fn = Transformer._make_layer_fn(cfg, mesh, rules, cos, sin,
                                              seq_len=x.shape[1],
                                              noised=noised,
                                              window_rope=window_rope,
                                              segment_ids=segment_ids)
        shared = dict(shared or {})

        @functools.cache
        def layer(kind):
            return Transformer._remat(
                functools.partial(layer_fn, kind=kind), cfg)

        def block(x, subs, shared):
            records = []
            for kind, sub in zip(kinds or [None] * len(subs), subs):
                x, routing, made = layer(kind)(x, sub, shared)
                shared = {**shared, **made}
                if routing is not None:
                    records.append(routing)
            return x, records, shared

        # the scan's own work (stacking and slicing saved activations,
        # carries) is "layers"; each block inside names itself
        with jax.named_scope("layers"):
            if not isinstance(layers, list):
                x, routing = lax.scan(
                    lambda x, lp: layer(None)(x, lp, shared)[:2],
                    x, layers, unroll=cfg.scan_unroll)
            elif set(kinds or ()) & set(MAKES):
                x, routing, shared = block(
                    x, jax.tree.map(lambda leaf: leaf[0], layers), shared)
            else:
                x, routing = lax.scan(
                    lambda x, subs: block(x, subs, shared)[:2],
                    x, layers, unroll=cfg.scan_unroll)
        return x, routing, shared

    @staticmethod
    def hidden(params, tokens, cfg: TransformerConfig, *,
               mesh=None, rules: Optional[ShardingRules] = None,
               positions=None, with_aux: bool = False, noised: int = 0,
               segment_ids=None):
        """tokens [B, T] int32 -> final-norm hidden states [B, T, d]
        (compute dtype) — apply() stopping before the lm head, so the
        loss can chunk head+softmax over T (the f32 [B,T,vocab] logits
        and their grad are the biggest HBM tenant at GPT-2 scale).
        with_aux=True returns (hidden, aux_loss, routing): the MoE
        load-balancing loss over all layers (0 for dense FFN configs) and
        the expert layers' stacked routing records (ops/moe.py `moe_ffn`:
        `tokens_per_expert [layers, held]`, `slots_elsewhere [layers]`,
        `router_prob [layers, E]`, `dropped [layers]`, `rows_bounded
        [layers]`; None for dense FFN configs). The sigmoid router has no
        aux loss: 0, as a softmax router over a held share has (the loss
        needs the other chips' counts).

        A block-diffusion model (`cfg.block_length`) attends causally by
        block. `noised` = L > 0: tokens is its doubled stream, L noised
        positions then their L clean copies (`positions` 0..L-1 twice),
        under the block-diffusion mask; only the noised half is read, so
        the final norm runs over it alone: -> [B, L, d].

        A looped stack (`cfg.loops` = R > 1) gives every pass's hidden
        state, [R, B, T, d], and with_aux=True (hidden, 0, None, z): the
        exit gate's f32 `z` [R, B, T], None where `params` hold no
        `exit_gate` (`_looped_hidden`).

        A residual path of several streams (`cfg.residual_streams` = n >
        1) runs the layers on [B, T, n*d] and sums the streams (in
        float32, `ops/mhc.collapse` under `mhc/collapse`) before the final
        norm, and with_aux=True gives a fourth, the dict `maps` (f32
        [layers, 2, B, T, n*n + 2n]: every sublayer's H_pre, H_post and
        H_res row by row, `ops/mhc.maps_by_token`) and `gain` (f32: the
        RMS of the summed streams / n over the RMS of the embedding).

        When `mesh` is provided and cfg.attention_impl is ring/ulysses, the
        attention op runs inside shard_map over the "seq" axis; everything
        else is GSPMD via logical sharding constraints.

        `segment_ids` [B, T] int32: packed documents, one id a position
        and each document one run of equal ids (data, not shape: other
        boundaries compile nothing). Every sublayer that mixes positions
        is handed them (`TransformerConfig`'s header says what a boundary
        does in each), and what has not been taught them refuses by name
        (`untaught_by_packing`). None: one document a sequence, and
        nothing of this is traced.
        """
        import jax
        import jax.numpy as jnp

        rules = rules or ShardingRules()
        if segment_ids is not None:
            Transformer.refuse_untaught_packing(
                cfg, Transformer.resolve_attention_impl(
                    cfg, mesh, tokens.shape[1]))
        x = Transformer.embed(params, tokens, cfg, mesh=mesh, rules=rules)
        if cfg.loops > 1:
            return Transformer._looped_hidden(
                params, x, cfg, mesh, rules, positions, with_aux)
        wide = cfg.residual_streams > 1
        maps = []   # on several streams, run by run: the sublayers' maps

        def without_maps(record):
            """A run's record as one stream's run gives it."""
            if not wide:
                return record
            record = dict(record)
            maps.append(record.pop("mhc_maps"))
            return record or None

        if wide:
            from ray_tpu.ops import mhc
            with jax.named_scope("mhc/expand"):
                entered = jnp.mean(jnp.square(
                    x[:, :, :cfg.d_model].astype(jnp.float32)))
        if "dense_layers" in params:   # the leading run with a dense FFN
            x, found, _ = Transformer._stack(
                params["dense_layers"], x, cfg, mesh=mesh, rules=rules,
                positions=positions, noised=noised,
                segment_ids=segment_ids)
            without_maps(found)
        if "runs" in params:
            records = []   # per run and expert sublayer: [repeats, ...]
            shared = {}    # the tensors that cross layers (`_stack`)
            for (kinds, _), run in zip(cfg.pattern_runs, params["runs"]):
                x, found, shared = Transformer._stack(
                    run, x, cfg, mesh=mesh, rules=rules, positions=positions,
                    kinds=kinds, shared=shared, segment_ids=segment_ids)
                if found:   # into the layers' order: [repeats * found, ...]
                    records.append(jax.tree.map(
                        lambda *r: jnp.stack(r, 1).reshape(
                            (-1,) + r[0].shape[1:]), *found))
            routing = jax.tree.map(lambda *r: jnp.concatenate(r),
                                   *records) if records else None
        else:
            x, routing, _ = Transformer._stack(
                params["layers"], x, cfg, mesh=mesh, rules=rules,
                positions=positions, noised=noised,
                segment_ids=segment_ids)
            routing = without_maps(routing)
        if wide:
            x = mhc.collapse(x, cfg.residual_streams)
            with jax.named_scope("mhc/collapse"):
                left = jnp.mean(jnp.square(
                    x.astype(jnp.float32) / cfg.residual_streams))
                streams = {"maps": jnp.concatenate(maps),
                           "gain": jnp.sqrt(left / entered)}
        aux_total = jnp.zeros((), jnp.float32)
        if cfg.moe_experts and cfg.moe_scoring == "softmax" \
                and cfg.held_experts == cfg.moe_experts:
            # not a sum of per-layer terms: the published loss takes its
            # two means over the tokens of all layers together
            from ray_tpu.ops.moe import load_balancing_loss
            with jax.named_scope("moe/router"):
                aux_total = load_balancing_loss(
                    routing["tokens_per_expert"], routing["router_prob"],
                    min(cfg.moe_top_k, cfg.moe_experts))

        if noised:   # nothing reads the clean copy's last layer
            with jax.named_scope("diffusion/stream"):
                x = x[:, :noised]
        with jax.named_scope("final_norm"):
            out = _norm(x, params, "final_norm", cfg.norm_eps)
        if with_aux and wide:
            return out, aux_total, routing, streams
        if with_aux:
            return out, aux_total, routing
        return out

    @staticmethod
    def _looped_hidden(params, x, cfg: TransformerConfig, mesh,
                       rules: ShardingRules, positions, with_aux: bool):
        """The embedded stream x [B, T, d] `cfg.loops` = R times through
        the same stacked layers, one `lax.scan` over the passes around
        `_stack` with the layers' leaves closed over: the final norm closes
        every pass, what it gives is that pass's hidden state and what the
        next pass reads, and the position ids are the same in every pass
        -> [R, B, T, d]. The backward pass sums the passes' weight
        gradients in the scan's f32 carry, and under `_remat` keeps
        R x n_layers carries and flash residuals. (A scan and not R
        unrolled calls: at Ouro-2.6B's widths, six layers and 8,192
        tokens the v5e ran the scan 0.7% faster in 1.6 GB less memory and
        compiled it in half the time; unrolled, XLA keeps bf16 copies of
        whole stacked leaves and eight layers do not fit: PERF.md
        section 6, PR 63.) with_aux=True:
        (hidden, 0, None, z), `z` [R, B, T] f32 the exit gate's scalar a
        token and pass, the normed hidden state times the gain `exit_gate`
        plus `exit_gate_bias` in float32 (an elementwise product and a sum,
        no matmul: a float32 matmul runs in bf16 passes on a TPU), None
        where `params` hold no gate."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def one_pass(x, _):
            x = Transformer._stack(params["layers"], x, cfg, mesh=mesh,
                                   rules=rules, positions=positions)[0]
            with jax.named_scope("final_norm"):
                x = _norm(x, params, "final_norm", cfg.norm_eps)
            return x, x

        # the loop's own work (stacking and slicing what the passes save,
        # the sum of the passes' weight gradients) is "loops"
        with jax.named_scope("loops"):
            out = lax.scan(one_pass, x, None, length=cfg.loops)[1]
        out = with_logical_constraint(
            out, (None, "batch", "seq", "act_embed"), mesh=mesh, rules=rules)
        if not with_aux:
            return out
        z = None
        if "exit_gate" in params:
            with jax.named_scope("loop/exit_gate"):
                f32 = jnp.float32
                z = jnp.sum(out.astype(f32) * params["exit_gate"].astype(f32),
                            axis=-1) + params["exit_gate_bias"].astype(f32)
        return out, jnp.zeros((), jnp.float32), None, z

    @staticmethod
    def _make_layer_fn(cfg: TransformerConfig, mesh,
                       rules: ShardingRules, cos, sin, seq_len: int,
                       noised: int = 0, window_rope=None,
                       segment_ids=None):
        """Build layer(x, lp, shared, kind) -> (x, routing, made), the body
        `_stack` scans (or, in a block, one of its sublayers): what lp's
        leaves say, attention under `attn_norm`, a mixer under `ssm_norm`,
        a gated memory unit under `gmu_norm`, a Gated DeltaNet mixer under
        `gdn_norm`, an FFN or experts under `mlp_norm`, each
        `x + f(norm(x))`, or `x + norm(f(x))` where the leaves hold
        `<name>_post_norm` instead, or the sandwich with both, or, the
        fourth form of the one rule, on n streams (the leaves hold
        `<name>_hc_phi`: x is `[B, T, n*d]`) `H_res X + H_post (x)
        f(norm(sum_i H_pre[i] X[i]))` with the sublayer's three maps made
        once, in `entering`, and handed to `residual` (`entering`,
        `residual`; no sublayer function knows of the streams). `routing`
        is the MoE layer's record (ops/moe.py `moe_ffn`), None without
        one, on n streams with `mhc_maps` f32 [sublayers, B, T, n*n + 2n]
        beside it (`ops/mhc.maps_by_token`);
        `shared` the tensors earlier layers made for this one, `made` what this layer
        makes for later ones (`MAKES[kind]`), `kind` the layer's character
        in `layer_pattern` (None outside one). cos and sin are None where
        the model has no rotary embedding; `window_rope`: the (cos, sin)
        the window kinds (`w`, `W`) take where the model has two tables
        (YaRN on the other layers). `segment_ids`: packed documents
        (`hidden`), for attention's mask and a Mamba-2 mixer's resets."""
        import jax
        import jax.numpy as jnp

        cdt = jnp.dtype(cfg.dtype)
        constrain = functools.partial(
            with_logical_constraint, mesh=mesh, rules=rules)
        attn_fn = Transformer._make_attention(
            cfg, mesh, rules, seq_len=seq_len, noised=noised,
            segment_ids=segment_ids)
        window_fn = Transformer._make_attention(
            cfg, mesh, rules, seq_len=seq_len, window=cfg.attn_window,
            segment_ids=segment_ids) if cfg.attn_window else None
        scale = cfg.softmax_scale
        # on several streams: the maps `entering` made of the stream, by
        # sublayer, until `residual` takes them; and every sublayer's maps
        # by token, for the layer's record
        open_maps, layer_maps = {}, []

        def entering(x, lp, name):
            """What the sublayer `name` reads: `norm(x)` under the norm
            that opens it, the stream itself where its leaves have none
            (the reordered norm). Where its leaves hold `<name>_hc_phi`, x
            is n streams ([B, T, n*d], or [B, T, n, d]): the sublayer's
            three maps are made of it here, once (`ops/mhc.enter`), what
            is normed is the streams' mix under H_pre, and `residual` is
            handed the other two with the stream as `enter` gave it
            back."""
            if name + "_hc_phi" in lp:
                from ray_tpu.ops import mhc
                h, (pre, post, res), x = mhc.enter(
                    x, lp[name + "_hc_phi"], lp[name + "_hc_b"],
                    lp[name + "_hc_alpha"], rounds=cfg.hc_sinkhorn_iters,
                    norm_eps=cfg.norm_eps, hc_eps=cfg.hc_eps,
                    clamp=cfg.hc_res_clamp, mesh=mesh)
                open_maps[name] = (x, post, res)
                with jax.named_scope("mhc/maps"):
                    layer_maps.append(mhc.maps_by_token(pre, post, res))
                x = constrain(h, ("batch", "seq", "act_embed"))
            if name + "_norm" not in lp:
                return x
            with jax.named_scope(name + "_norm"):
                return _norm(x, lp, name + "_norm", cfg.norm_eps)

        def residual(x, out, lp, name):
            """The stream after the sublayer `name`, `out` what it made of
            `entering(x, lp, name)`: `x + out`, `out` under the norm on
            the sublayer's output where its leaves have one; on n streams
            (the maps `entering` left for it) `H_res X + H_post out`
            (`ops/mhc.leave`). With `entering`, the one rule of how a
            residual is formed."""
            out = constrain(out, ("batch", "seq", "act_embed"))
            post = name + "_post_norm"
            if post in lp:
                with jax.named_scope(post):
                    out = _norm(out, lp, post, cfg.norm_eps)
            if name in open_maps:
                from ray_tpu.ops import mhc
                # the stream as `enter` handed it back, not x: its
                # cotangent then arrives in `enter`'s backward
                x, *maps = open_maps.pop(name)
                return constrain(
                    mhc.leave(x, out, *maps, mesh=mesh),
                    STREAMS if x.ndim == 4 else ("batch", "seq", "act_embed"))
            if cfg.residual_scale != 1.0:   # muP's `residual_multiplier`
                out = out * jnp.asarray(cfg.residual_scale, out.dtype)
            return x + out

        def heads_constrained(q, k, v):
            # GQA: k/v keep their true kv_heads width end-to-end — the
            # attention ops broadcast per group internally (ring then
            # rotates Hkv-wide tensors over ICI, not Hq-wide repeats)
            return (constrain(q, ("batch", "seq", "heads", "head_dim")),
                    constrain(k, ("batch", "seq", "kv_heads", "head_dim")),
                    constrain(v, ("batch", "seq", "kv_heads", "head_dim")))

        def latent_qkv(h, lp):
            """Latent attention's q, k, v `[B, T, H, .]` from the normed
            stream: each latent is down-projected and RMS-normed, the
            heads are up-projected from it; the rotary key head comes
            straight off the kv down-projection and is shared by all
            heads; RoPE touches only the rotary columns."""
            nope = cfg.qk_nope_head_dim
            if "wq" in lp:   # no query latent
                with jax.named_scope("q_proj"):
                    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(cdt))
            else:
                with jax.named_scope("q_down"):
                    c_q = _rmsnorm(jnp.einsum("btd,dr->btr", h,
                                              lp["wq_a"].astype(cdt)),
                                   lp["q_a_norm"], cfg.norm_eps)
            with jax.named_scope("kv_down"):
                ckv = jnp.einsum("btd,dr->btr", h, lp["wkv_a"].astype(cdt))
                c_kv = _rmsnorm(ckv[..., :cfg.kv_lora_rank],
                                lp["kv_a_norm"], cfg.norm_eps)
                k_rope = ckv[..., None, cfg.kv_lora_rank:]    # one head
            if "wq" not in lp:
                with jax.named_scope("q_up"):
                    q = jnp.einsum("btr,rhk->bthk", c_q,
                                   lp["wq_b"].astype(cdt))
            with jax.named_scope("kv_up"):
                kv = jnp.einsum("btr,rhk->bthk", c_kv,
                                lp["wkv_b"].astype(cdt))
            def roped(x):   # RoPE on a head's rotary columns
                return jnp.concatenate(
                    [x[..., :nope], _rope(x[..., nope:], cos, sin)], axis=-1)

            with jax.named_scope("assemble"):
                if "q_norm" in lp:
                    # each head normed over its own columns, the key head
                    # with the shared rotary columns it is given; then RoPE
                    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                        k_rope, q.shape[:3] + (cfg.rope_dim,))], axis=-1)
                    q = roped(_qk_norm(q, lp["q_norm"], cfg.norm_eps))
                    k = roped(_qk_norm(k, lp["k_norm"], cfg.norm_eps))
                else:
                    q = roped(q)
                    k_rope = jnp.broadcast_to(_rope(k_rope, cos, sin),
                                              q.shape[:3] + (cfg.rope_dim,))
                    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
                return heads_constrained(q, k, kv[..., nope:])

        def differential(q, k, v, lp, fn, kind):
            """Differential attention's `(A1 - lambda A2) V`: q
            `[B, T, H, D]` with the heads in the order (key pair g, map j,
            query pair r of the two that share g), k `[B, T, H/2, D]` as
            (g, j), v `[B, T, H/4, 2D]` -> `[B, T, H, D]`, the pairs
            (g, r) laid over two heads each. One kernel call: head
            (g, j, r) reads key head (g, j), GQA's own grouping, and value
            head g repeated for its two maps."""
            b, t, h, hd = q.shape
            with jax.named_scope("attention/" + kind):
                maps = fn(q, k, jnp.repeat(v, 2, axis=2), scale)
            with jax.named_scope("attention/diff"):
                f32 = jnp.float32
                lam = jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                                      * lp["lambda_k1"].astype(f32))) \
                    - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                                      * lp["lambda_k2"].astype(f32))) \
                    + lp["lambda_init"]
                maps = maps.reshape(b, t, h // 4, 2, 2, 2 * hd).astype(f32)
                o = maps[:, :, :, 0] - lam * maps[:, :, :, 1]
                o = _rmsnorm(o, lp["subln"].astype(f32), cfg.norm_eps) \
                    * (1.0 - lp["lambda_init"])
                return o.astype(cdt).reshape(b, t, h, hd)

        def attention(x, lp, shared, kind):
            # one jax.named_scope per block (module docstring): the names
            # reach every op's op_name, and so the device trace
            h = entering(x, lp, "attn")
            made = {}
            windowed = kind in ("w", "W")
            with jax.named_scope("qkv"):
                if cfg.kv_lora_rank:
                    q, k, v = latent_qkv(h, lp)
                elif cfg.kv_heads == cfg.n_heads:
                    qkv = jnp.einsum("btd,dghk->btghk", h,
                                     lp["wqkv"].astype(cdt))
                    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                else:
                    q = jnp.einsum("btd,dhk->bthk", h,
                                   lp["wq"].astype(cdt))
                    if "bq" in lp:
                        q = q + lp["bq"].astype(cdt)
                    if "wkv" in lp:
                        kv = jnp.einsum("btd,dghk->btghk", h,
                                        lp["wkv"].astype(cdt))
                        if "bkv" in lp:
                            kv = kv + lp["bkv"].astype(cdt)
                        k, v = kv[:, :, 0], kv[:, :, 1]
                    else:   # cross-attention: the layer f's, as they are
                        k, v = shared["k"], shared["v"]
                if cfg.qk_norm and not cfg.kv_lora_rank:
                    with jax.named_scope("qk_norm"):
                        q = _qk_norm(q, lp["q_norm"], cfg.norm_eps)
                        k = _qk_norm(k, lp["k_norm"], cfg.norm_eps)
                if not cfg.kv_lora_rank:
                    if cfg.rope:
                        c, s = window_rope if windowed \
                            and window_rope is not None else (cos, sin)
                        q, k = _rope(q, c, s), _rope(k, c, s)
                    q, k, v = heads_constrained(q, k, v)
                if "lambda_q1" in lp and "wkv" in lp:
                    # a pair's value head: two key heads' columns
                    v = v.reshape(v.shape[:2] + (-1, 2 * cfg.head_dim))
                if kind == "f":
                    made = {"k": k, "v": v}
            if "lambda_q1" in lp:
                fn = window_fn if kind == "w" else attn_fn
                o = differential(q, k, v, lp, fn, {
                    "w": "window", "f": "full", "c": "cross"}[kind])
            elif windowed:
                with jax.named_scope("attention/window"):
                    o = window_fn(q, k, v, scale)
            else:
                # a model that mixes windows and full layers names both
                with jax.named_scope(
                        "attention/block_diffusion" if cfg.block_length
                        else "attention/full" if cfg.attn_window and kind
                        else "attention"):
                    o = attn_fn(q, k, v, scale)
            with jax.named_scope("attn_out"):
                o = constrain(o, ("batch", "seq", "heads", "head_dim"))
                o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(cdt))
                if "bo" in lp:
                    o = o + lp["bo"].astype(cdt)
                return residual(x, o, lp, "attn"), made

        def mixer1(x, lp):
            """A Mamba-1 mixer -> (x, its scan output)."""
            from ray_tpu.ops.ssm import mamba1_mixer

            h = entering(x, lp, "ssm")
            cast = dict(lp)
            for scope, name in (("in_proj", "w_in"), ("x_proj", "w_x"),
                                ("x_proj", "w_dt"), ("out_proj", "w_out")):
                with jax.named_scope("ssm/" + scope):
                    cast[name] = lp[name].astype(cdt)
            # mamba1_mixer names its own scopes under `ssm/`
            out, y = mamba1_mixer(h, cast, chunk=min(cfg.ssm_chunk,
                                                     x.shape[1]), mesh=mesh)
            with jax.named_scope("ssm/out_proj"):
                return residual(x, out, lp, "ssm"), y

        def gmu(x, lp, memory):
            """A gated memory unit: the stream gates the memory."""
            h = entering(x, lp, "gmu")
            with jax.named_scope("gmu/in_proj"):
                gate = jnp.einsum("btd,de->bte", h,
                                  lp["w_gmu_in"].astype(cdt))
            with jax.named_scope("gmu/gate"):
                gated = memory * jax.nn.silu(gate)
            with jax.named_scope("gmu/out_proj"):
                out = jnp.einsum("bte,ed->btd", gated,
                                 lp["w_gmu_out"].astype(cdt))
                return residual(x, out, lp, "gmu")

        def kda(x, lp):
            """Kimi Delta Attention (ops/kda.py)."""
            from ray_tpu.ops.kda import kda_mixer

            h = entering(x, lp, "kda")
            cast = dict(lp)
            for scope, name in (("qkv_proj", "w_kda_qkv"),
                                ("gates", "w_kda_a"), ("gates", "w_kda_bg"),
                                ("out_proj", "w_kda_out")):
                with jax.named_scope("kda/" + scope):
                    cast[name] = lp[name].astype(cdt)
            # kda_mixer names its own scopes under `kda/`
            out = kda_mixer(h, cast, chunk=cfg.kda_chunk, mesh=mesh,
                            lower=cfg.kda_gate_lower, eps=cfg.norm_eps)
            with jax.named_scope("kda/out_proj"):
                return residual(x, out, lp, "kda")

        def gdn(x, lp):
            """A Gated DeltaNet mixer (ops/kda.py)."""
            from ray_tpu.ops.kda import gdn_mixer

            h = entering(x, lp, "gdn")
            cast = dict(lp)
            for scope, name in (("qkv_proj", "w_gdn_qkv"),
                                ("gates", "w_gdn_ab"), ("gates", "w_gdn_g"),
                                ("out_proj", "w_gdn_out")):
                with jax.named_scope("gdn/" + scope):
                    cast[name] = lp[name].astype(cdt)
            # gdn_mixer names its own scopes under `gdn/`
            out = gdn_mixer(h, cast, chunk=cfg.gdn_chunk, eps=cfg.norm_eps,
                            beta_scale=2.0 if cfg.gdn_neg_eigval else 1.0)
            with jax.named_scope("gdn/out_proj"):
                return residual(x, out, lp, "gdn")

        def mixer(x, lp):
            from ray_tpu.ops.ssm import mamba2_mixer

            h = entering(x, lp, "ssm")
            with jax.named_scope("ssm/in_proj"):
                w_in = lp["w_in"].astype(cdt)
            with jax.named_scope("ssm/out_proj"):
                w_out = lp["w_out"].astype(cdt)
            # mamba2_mixer names its own scopes under `ssm/`
            out = mamba2_mixer(
                h, dict(lp, w_in=w_in, w_out=w_out),
                head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                chunk=cfg.ssm_chunk, eps=cfg.norm_eps, mesh=mesh,
                segment_ids=segment_ids)
            with jax.named_scope("ssm/out_proj"):
                return residual(x, out, lp, "ssm")

        # an expert layer's leaves as `moe_ffn` names them, by the scope
        # their casts belong to
        expert_leaves = (
            ("moe/experts", ("w_moe_gateup", "w_moe_up", "w_moe_down")),
            ("moe/shared", ("w_shared_gateup", "w_shared_up",
                            "w_shared_down")),
            ("moe/latent", ("w_latent_down", "w_latent_up")))

        def ffn(x, lp):
            h = entering(x, lp, "mlp")
            if "w_router" in lp:   # an expert layer, by its leaves
                from ray_tpu.ops.moe import moe_ffn

                # moe_ffn names its own scopes under `moe/`
                experts = {"w_router": lp["w_router"]}
                if "router_bias" in lp:
                    experts["router_bias"] = lp["router_bias"]
                for scope, names in expert_leaves:
                    with jax.named_scope(scope):
                        experts.update(
                            (name.replace("w_moe_", "w_"),
                             lp[name].astype(cdt))
                            for name in names if name in lp)
                y, routing = moe_ffn(
                    experts, h.reshape(-1, h.shape[-1]),
                    num_selected=cfg.moe_top_k,
                    norm_topk=cfg.moe_norm_topk, scoring=cfg.moe_scoring,
                    routed_scale=cfg.moe_routed_scale,
                    expert_offset=cfg.moe_expert_offset, act=cfg.moe_act,
                    n_group=cfg.moe_groups, topk_group=cfg.moe_topk_groups,
                    mesh=mesh, rules=rules)
                with jax.named_scope("moe/combine"):
                    down = y.reshape(h.shape).astype(cdt)
                    x = residual(x, down, lp, "mlp")
                return x, routing
            with jax.named_scope("mlp/gate_up"):
                gu = jnp.einsum("btd,dgf->btgf", h,
                                lp["w_gateup"].astype(cdt))
                ff = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
                ff = constrain(ff, ("batch", "seq", "act_mlp"))
            with jax.named_scope("mlp/down"):
                down = jnp.einsum("btf,fd->btd", ff,
                                  lp["w_down"].astype(cdt))
                x = residual(x, down, lp, "mlp")
            return x, None

        def layer(x, lp, shared=None, kind=None):
            routing, made = None, {}
            open_maps.clear()
            layer_maps.clear()

            def has(name):   # the sublayer's norm, before it or after
                return name + "_norm" in lp or name + "_post_norm" in lp

            if has("attn"):
                x, made = attention(x, lp, shared, kind)
            if "w_x" in lp:       # a Mamba-1 mixer, by its leaves
                x, y = mixer1(x, lp)
                if kind == "s":
                    made = {"memory": y}
            elif has("ssm"):
                x = mixer(x, lp)
            if has("kda"):
                x = kda(x, lp)
            if has("gdn"):
                x = gdn(x, lp)
            if has("gmu"):
                x = gmu(x, lp, shared["memory"])
            if has("mlp"):
                x, routing = ffn(x, lp)
            if layer_maps:   # [sublayers, B, T, n*n + 2n] f32
                routing = dict(routing or {}, mhc_maps=jnp.stack(layer_maps))
            return x, routing, made

        return layer

    @staticmethod
    def apply(params, tokens, cfg: TransformerConfig, *,
              mesh=None, rules: Optional[ShardingRules] = None,
              positions=None, segment_ids=None):
        """tokens [B, T] int32 -> logits [B, T, vocab] (f32 accum); a
        looped stack's are its last pass's (no token leaves early).
        `segment_ids` [B, T]: packed documents (`hidden`)."""
        rules = rules or ShardingRules()
        x = Transformer.hidden(params, tokens, cfg, mesh=mesh, rules=rules,
                               positions=positions, segment_ids=segment_ids)
        if cfg.loops > 1:
            x = x[-1]
        return head.logits(params, x, cfg, mesh=mesh, rules=rules)

    @staticmethod
    def pipeline_loss(params, batch, cfg: TransformerConfig, *,
                      mesh, n_stages: int, n_micro: int,
                      rules: Optional[ShardingRules] = None):
        """Next-token loss with the layer stack executed as a microbatched
        ppermute pipeline over the "pipe" mesh axis
        (parallel/pipeline.py make_pipeline_fn) — the alternative
        execution of the same stacked layer params hidden() scans.

        Embedding runs outside the pipeline (vocab/fsdp-sharded GSPMD);
        each stage applies n_layers/n_stages layers (`_stack`, the scan
        hidden() runs); the last stage's loss_fn does final-norm + the
        head's `nll_sum` per microbatch. Requires
        batch divisible by n_micro, n_layers divisible by n_stages, and a
        stage-local attention impl (dense/flash — seq stays unsharded
        inside a stage)."""
        import jax

        from ray_tpu.parallel.pipeline import make_pipeline_fn

        rules = rules or ShardingRules()
        if "segment_ids" in batch:
            Transformer.refuse_untaught_packing(cfg, "dense",
                                                pipeline=True)
        tokens, targets = Transformer._tokens_and_targets(batch)
        b, t = tokens.shape
        if b % n_micro or cfg.n_layers % n_stages:
            raise ValueError(
                f"batch {b} % n_micro {n_micro} or n_layers "
                f"{cfg.n_layers} % n_stages {n_stages} != 0")
        if cfg.attention_impl in ("ring", "ulysses"):
            raise ValueError("pipeline stages need stage-local attention "
                             "(dense/flash), not ring/ulysses")
        if cfg.block_length:
            raise ValueError("pipeline_loss is next-token training: a "
                             "block-diffusion model (block_length) trains "
                             "through Transformer.loss")
        if cfg.loops > 1:
            raise ValueError("pipeline_loss runs its stages once: a looped "
                             "stack (loops above 1) trains through "
                             "Transformer.loss")
        if cfg.residual_streams > 1:
            raise ValueError("pipeline_loss passes one stream between its "
                             "stages: a residual path of several streams "
                             "(residual_streams above 1) trains through "
                             "Transformer.loss")
        if cfg.moe_experts or cfg.layer_pattern:
            raise ValueError(
                "pipeline_loss takes one homogeneous run of layers and "
                "does not thread the MoE aux "
                "(load-balancing) loss out of the pipeline yet; train "
                "MoE configs via Transformer.loss (expert axis), or set "
                "moe_experts=0 for the pipe axis")
        mb = b // n_micro

        # Embed outside the pipeline, then split into microbatches.
        x = Transformer.embed(params, tokens, cfg, mesh=mesh, rules=rules)
        x_micro = x.reshape(n_micro, mb, t, x.shape[-1])
        y_micro = targets.reshape(n_micro, mb, t)

        def stage_fn(stage_params, x):
            # no positions passed: the rope tables are rebuilt from static
            # positions inside the stage, shard-local constants and not
            # closure-captured traced arrays (shard_map rejects
            # auto-sharded implicit captures).
            # mesh=None inside the stage: the pipeline shard_map already
            # owns axis mapping; constraints no-op under manual meshes.
            return Transformer._stack(stage_params, x, cfg, mesh=None,
                                      rules=rules)[0]

        def mb_loss(out, y, extras):
            with jax.named_scope("final_norm"):
                h = _rmsnorm(out, extras["final_norm"], cfg.norm_eps)
            return head.nll_sum(extras["head"], h, y, cfg) / y.size

        run = make_pipeline_fn(stage_fn, n_stages, n_micro, mesh,
                               loss_fn=mb_loss)
        # [l, ...] stacked layers -> [n_stages, l/n_stages, ...]: the
        # leading stage dim aligns with the "pipe" shards of the "layers"
        # axis, so this reshape is shard-local.
        staged = jax.tree.map(
            lambda a: a.reshape((n_stages, -1) + a.shape[1:]),
            params["layers"])
        extras = {"final_norm": params["final_norm"],
                  "head": head.weight(params, cfg)}
        return run(staged, x_micro, y_micro, extras)

    @staticmethod
    def resolve_attention_impl(cfg: TransformerConfig, mesh=None,
                               seq_len: Optional[int] = None) -> str:
        """The implementation cfg.attention_impl runs as: explicit names
        pass through, and this is the one place "auto" is decided — ring
        when the seq axis is sharded, else the pallas flash kernel where
        the devices are TPUs and the kernel tiles the shape, else dense.
        Callers print it to say what a step compiled with."""
        import jax

        from ray_tpu.ops.attention import flash_shape_ok

        impl = cfg.attention_impl
        if impl not in ("auto", "dense", "flash", "ring", "ulysses"):
            raise ValueError(f"unknown attention_impl {impl!r}")
        if impl != "auto":
            return impl
        if mesh is not None and mesh.shape.get(AXIS_SEQ, 1) > 1:
            return "ring"
        device = mesh.devices.flat[0] if mesh is not None \
            else jax.devices()[0]
        t = cfg.max_seq_len if seq_len is None else seq_len
        # the kernel takes one width for q and k and another for v
        return "flash" if device.platform == "tpu" and \
            flash_shape_ok(t, cfg.head_dim) and \
            flash_shape_ok(t, cfg.v_dim) else "dense"

    @staticmethod
    def _make_attention(cfg: TransformerConfig, mesh, rules: ShardingRules,
                        seq_len: Optional[int] = None, window: int = 0,
                        noised: int = 0, segment_ids=None):
        """attention(q, k, v, scale) under a causal mask, with `window` > 0
        one that also ends `window` keys back, with `cfg.block_length` the
        block-diffusion mask (over a doubled stream of `noised` noised
        positions and their clean copies, or causal by block over a plain
        one); dense and flash take all three, ring and ulysses the first
        alone. `segment_ids` [B, T] (packed documents): dense and flash
        see a query's own document only, the ids sharded as the batch is
        where the kernel runs per shard; None adds no operand."""
        import jax
        from jax.sharding import PartitionSpec as P

        from ray_tpu.ops.attention import dense_attention, flash_attention

        impl = Transformer.resolve_attention_impl(cfg, mesh, seq_len)
        seq_unsharded = mesh is None or mesh.shape.get(AXIS_SEQ, 1) == 1
        if impl == "flash" and not seq_unsharded:
            raise ValueError("attention_impl='flash' requires an unsharded "
                             "seq axis; use ring/ulysses for SP")
        # [B, T, H, D] specs shared by every shard_map path; only the seq
        # entry differs (sharded for ring/ulysses SP, local for flash).
        # k/v get their own spec so GQA kv heads shard by the kv_heads
        # rule without being repeated to query-head width — UNLESS the
        # backing mesh axis doesn't divide kv_heads (TP degree > kv
        # heads), in which case k/v are widened to query heads first
        # (the pre-round-4 behavior) so shard_map can still split them.
        from ray_tpu.parallel.sharding import spec_entry_size

        def axis_size(logical):
            return spec_entry_size(rules.mesh_axes(logical), mesh) \
                if mesh is not None else 1

        kv_narrow = (mesh is not None and cfg.kv_heads != cfg.n_heads
                     and cfg.kv_heads % axis_size("kv_heads") == 0)
        kv_axis = "kv_heads" if (kv_narrow or cfg.kv_heads == cfg.n_heads) \
            else "heads"

        def maybe_widen(fn):
            if kv_axis == "kv_heads":
                return fn
            import jax.numpy as jnp
            rep = cfg.n_heads // cfg.kv_heads

            def widened(q, k, v, scale):
                return fn(q, jnp.repeat(k, rep, axis=2),
                          jnp.repeat(v, rep, axis=2), scale)
            return widened

        def qkv_spec(seq_entry, head_axis="heads"):
            return P(rules.mesh_axes("batch"), seq_entry,
                     rules.mesh_axes(head_axis), None)

        def shard_mapped(body, spec, kv_spec, **shard_map_kw):
            # packed documents: the ids are one operand more, sharded as
            # the batch is; without them the call is what it always was
            ids = () if segment_ids is None else (segment_ids,)
            ids_spec = (P(rules.mesh_axes("batch"), None),) * len(ids)

            def wrapped(q, k, v, scale):
                def local(q, k, v, *ids):
                    return body(q, k, v, scale=scale,
                                **({"segment_ids": ids[0]} if ids else {}))
                fn = jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(spec, kv_spec, kv_spec) + ids_spec,
                    out_specs=spec, **shard_map_kw)
                return fn(q, k, v, *ids)
            return maybe_widen(wrapped)

        if impl in ("dense", "flash") or seq_unsharded:
            local = flash_attention if impl == "flash" else dense_attention
            body = functools.partial(local, causal=True, window=window)
            if cfg.block_length:
                body = functools.partial(body, noised=noised,
                                         block_length=cfg.block_length)
            if impl == "flash" and mesh is not None:
                # pallas kernels don't GSPMD-partition; run per-shard under
                # shard_map with batch/heads sharded as the constraints say.
                return shard_mapped(body, qkv_spec(None),
                                    qkv_spec(None, kv_axis),
                                    check_vma=False)
            if segment_ids is not None:
                body = functools.partial(body, segment_ids=segment_ids)
            return lambda q, k, v, scale: body(q, k, v, scale=scale)

        from ray_tpu.parallel.ring import ring_attention
        from ray_tpu.parallel.ulysses import ulysses_attention

        if window:
            raise ValueError("ring and ulysses attention take no window")
        if cfg.loops > 1:
            raise ValueError("ring and ulysses attention run no looped "
                             "stack (loops above 1) yet")
        if cfg.block_length:
            raise ValueError("ring and ulysses attention take no "
                             "block-diffusion mask (block_length)")

        # Heads stay sharded over the tensor axis inside the shard_map —
        # SP composes with TP instead of all-gathering Q/K/V heads.
        sp = ring_attention if impl == "ring" else ulysses_attention
        return shard_mapped(functools.partial(sp, causal=True),
                            qkv_spec(AXIS_SEQ),
                            qkv_spec(AXIS_SEQ, kv_axis))

    # ---- packed documents -------------------------------------------
    @staticmethod
    def untaught_by_packing(cfg: TransformerConfig, impl: str,
                            pipeline: bool = False):
        """What of this configuration mixes positions and has not been
        taught document boundaries, by name: the one list behind every
        refusal of `segment_ids`. Taught: dense and flash attention under
        the causal mask or a window (the homogeneous stack, the kinds `*`,
        `l`, `L`, `a`, `w`, `W`), a Mamba-2 mixer (`M`, `n`), the loss.
        `impl`: what attention resolved to."""
        kinds = set(cfg.layer_pattern)
        return [what for what, has in (
            ("a Mamba-1 mixer (layer_pattern m, s)", kinds & set("ms")),
            ("Kimi Delta Attention (layer_pattern k, K)", kinds & set("kK")),
            ("a Gated DeltaNet mixer (layer_pattern d)", "d" in kinds),
            ("differential attention (diff_attention)", cfg.diff_attention),
            ("cross-attention (layer_pattern c)", "c" in kinds),
            ("the block-diffusion mask (block_length)", cfg.block_length),
            (impl + " attention", impl in ("ring", "ulysses")),
            ("a looped stack (loops)", cfg.loops > 1),
            ("a residual path of several streams (residual_streams)",
             cfg.residual_streams > 1),
            ("pipeline_loss", pipeline)) if has]

    @staticmethod
    def refuse_untaught_packing(cfg: TransformerConfig, impl: str,
                                pipeline: bool = False) -> None:
        untaught = Transformer.untaught_by_packing(cfg, impl, pipeline)
        if untaught:
            raise ValueError(
                "segment_ids (packed documents): " + "; ".join(untaught)
                + " mix positions and have not been taught document "
                "boundaries: they would carry one document into the next")

    @staticmethod
    def _packed_labels(segment_ids, tokens, mask):
        """A batch's `segment_ids` -> (the ids of the positions `hidden`
        runs [B, T], the labels' mask with the labels that cross a
        boundary left out, the step's counters). The ids come as the
        tokens do: [B, T + 1] beside tokens to be shifted by one (label t
        is position t + 1: its document is known), or [B, T] beside
        explicit targets (the last label's document is not: the caller's
        `mask` says). Counters, int32: `packed_docs` the documents of the
        batch, `packed_labels` the labels trained on, and
        `packed_attn_pairs_needed` the (query, key) pairs a head's
        attention needs under the document mask, the sum over documents
        of n (n + 1) / 2 (`ops/attention.causal_block_pairs` has what the
        kernels compute)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        with jax.named_scope("loss"), jax.named_scope("segments"):
            t = tokens.shape[1]
            if segment_ids.shape[1] not in (t, t + 1):
                raise ValueError(
                    f"segment_ids {segment_ids.shape} beside {t} positions: "
                    f"one id a token of the batch")
            ids = segment_ids[:, :t]
            follows = segment_ids[:, 1:] == segment_ids[:, :-1]
            if segment_ids.shape[1] == t:   # the last label's is unknown
                follows = jnp.pad(follows, ((0, 0), (0, 1)),
                                  constant_values=True)
            same = follows.astype(jnp.float32)
            mask = same if mask is None else mask.astype(jnp.float32) * same
            place = jnp.arange(t, dtype=jnp.int32)[None, :]
            starts = jnp.pad(ids[:, 1:] != ids[:, :-1], ((0, 0), (1, 0)),
                             constant_values=True)
            first = lax.cummax(jnp.where(starts, place, 0), axis=1)
            return ids, mask, {
                "packed_docs": jnp.sum(starts.astype(jnp.int32)),
                "packed_labels": jnp.sum(mask).astype(jnp.int32),
                "packed_attn_pairs_needed": jnp.sum(place - first + 1)}

    # ---- loss -------------------------------------------------------
    @staticmethod
    def _tokens_and_targets(batch):
        """batch = {"tokens": [B,T], "targets": [B,T]}, or {"tokens":
        [B,T+1]} to be shifted by one -> (tokens, targets)."""
        import jax

        if "targets" in batch:
            return batch["tokens"], batch["targets"]
        with jax.named_scope("loss"):
            return batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    @staticmethod
    def loss(params, batch, cfg: TransformerConfig, *,
             mesh=None, rules: Optional[ShardingRules] = None,
             with_metrics: bool = False):
        """Next-token cross-entropy. batch = {"tokens": [B,T+1] or
        ("tokens","targets") pair}, optionally a "mask" [B,T] and
        "segment_ids" (packed documents, shaped as "tokens": every
        sublayer that mixes positions is handed them, the labels that
        cross a boundary are left out, and the metrics gain
        `_packed_labels`' three counters); returns
        scalar mean loss (f32), for a MoE config plus `moe_aux_coeff` x the
        load-balancing loss. A block-diffusion model (`cfg.block_length`)
        has another objective, the masked-token loss of
        `_block_diffusion_loss`, over the batch `diffusion.noised` makes,
        and a looped stack with an exit gate (`cfg.exit_gate`) a third,
        the expected next-token loss under the distribution of the pass a
        token leaves at less `exit_entropy_coeff` x that distribution's
        entropy (`_exit_loss`; its metrics `loop_exit_mass` f32 [loops],
        `loop_exit_entropy` f32 and `loop_pass_nll` f32 [loops]). A looped
        stack without the gate trains its last pass's hidden state on
        the next-token loss.

        with_metrics=True returns (loss, metrics), the pair
        `make_train_step` takes: its step's metrics then carry, from the
        same forward pass, `moe_tokens_per_expert` (int32 [expert layers,
        held experts]), `moe_dropped` (int32 scalar; 0 on the sorted path
        by construction), `moe_aux_loss` and, where only a share of the
        experts is held, `moe_slots_elsewhere` (int32 [expert layers]:
        slots routed to experts held elsewhere) and `moe_rows_bounded`
        (int32 [expert layers]: 1 where this step's held rows fitted
        `ops/moe.row_bound`'s run and the path past the sort ran over it,
        0 where it ran over every row) and, under group-limited routing,
        `moe_groups_chosen` (int32 [expert layers, groups]: the tokens
        that kept each group; `moe_topk_groups` x tokens a layer) and, on
        a mesh whose experts' axis is above 1, a layer and shard each
        (int32 [expert layers, shards]; `ops/moe._exchange_ffn`),
        `moe_rows_received`, `moe_exchange_rows_sent`,
        `moe_exchange_rows_needed`, `moe_exchange_pairs` and
        `moe_exchange_bounded`; an empty dict for a dense config. On a
        residual path of several streams also `mhc_res_marginal_err` (f32:
        the largest |rowsum - 1| and |colsum - 1| of H_res over tokens and
        sublayers; under 1e-3 after 20 rounds or the step is unsound) and
        `mhc_stream_gain` (f32: the RMS of the summed streams / n over the
        RMS of the embedding, what the constraint is there to bound)."""
        import jax
        import jax.numpy as jnp

        rules = rules or ShardingRules()
        segment_ids, packed = batch.get("segment_ids"), {}
        if segment_ids is not None:   # before the losses that take none
            Transformer.refuse_untaught_packing(
                cfg, Transformer.resolve_attention_impl(
                    cfg, mesh, batch["tokens"].shape[1]))
        if cfg.block_length:
            return Transformer._block_diffusion_loss(
                params, batch, cfg, mesh, rules, with_metrics)
        if cfg.exit_gate:
            return Transformer._exit_loss(params, batch, cfg, mesh, rules,
                                          with_metrics)
        tokens, targets = Transformer._tokens_and_targets(batch)
        mask = batch.get("mask")
        if segment_ids is not None:
            segment_ids, mask, packed = Transformer._packed_labels(
                segment_ids, tokens, mask)
        # a looped stack hands its gate's z (None here) as a fourth,
        # several streams their maps and gain
        x, aux, routing, *streams = Transformer.hidden(
            params, tokens, cfg, mesh=mesh, rules=rules, with_aux=True,
            segment_ids=segment_ids)
        if cfg.loops > 1:   # no gate: the last pass alone is trained
            x = x[-1]
        total = head.nll_sum(head.weight(params, cfg), x, targets, cfg,
                             mask=mask, mesh=mesh, rules=rules)
        with jax.named_scope("loss"):
            loss_val = total / (targets.size if mask is None
                                else jnp.maximum(jnp.sum(mask), 1.0))
        if cfg.moe_experts and cfg.moe_scoring == "softmax":
            loss_val = loss_val + cfg.moe_aux_coeff * aux
        out = Transformer._loss_out(loss_val, aux, routing, cfg,
                                    with_metrics)
        if with_metrics:
            out[1].update(packed)
        if with_metrics and cfg.residual_streams > 1:
            from ray_tpu.ops import mhc
            with jax.named_scope("mhc/maps"):
                out[1].update(
                    mhc_res_marginal_err=mhc.marginal_error(
                        streams[0]["maps"], cfg.residual_streams),
                    mhc_stream_gain=streams[0]["gain"])
        return out

    @staticmethod
    def block_diffusion_hidden(params, batch, cfg: TransformerConfig, *,
                               mesh=None,
                               rules: Optional[ShardingRules] = None):
        """A block-diffusion model's final-norm hidden states at the L
        noised positions, [B, L, d], with `hidden`'s aux loss and routing
        and the noised batch. batch = what `diffusion.noised` makes
        ({"tokens": the noised copy [B, L], "targets": the clean tokens,
        "mask": the weights), or what it takes ({"tokens", "noise_key"}:
        noised here). The stream is the noised copy and the clean one
        behind it, 2L positions with the position ids 0..L-1 twice, under
        the block-diffusion mask (`ops/attention.block_visible`)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import diffusion

        if "noise_key" in batch:
            batch = diffusion.noised(batch, cfg)
        if not cfg.block_length or not {"tokens", "targets", "mask"} <= set(
                batch):
            raise ValueError(
                "a block-diffusion model (block_length) reads the batch "
                "diffusion.noised makes (tokens, targets, mask) or takes "
                f"(tokens, noise_key), got {sorted(batch)}")
        clean = batch["targets"]
        length = clean.shape[1]
        if length % cfg.block_length:
            raise ValueError(
                f"block_length {cfg.block_length} does not divide "
                f"sequences of {length} tokens")
        with jax.named_scope("diffusion/stream"):
            stream = jnp.concatenate([batch["tokens"], clean], axis=1)
            positions = jnp.tile(jnp.arange(length, dtype=jnp.int32),
                                 2)[None, :]
        return Transformer.hidden(
            params, stream, cfg, mesh=mesh, rules=rules or ShardingRules(),
            positions=positions, with_aux=True, noised=length) + (batch,)

    @staticmethod
    def _block_diffusion_loss(params, batch, cfg: TransformerConfig, mesh,
                              rules: ShardingRules, with_metrics: bool):
        """The masked-token loss of a block-diffusion model
        (models/diffusion.py) over `block_diffusion_hidden`'s read
        positions: a masked position's own logits predict its token (no
        shift by one), and the loss is the cross-entropy over the masked
        positions, each weighted by 1/t of its block, divided by B * L:
        the mean over the sequences of each one's bound. Metrics besides
        the MoE's: `diffusion_masked_tokens` (int32: masked positions of
        the batch) and `diffusion_weight_sum` (f32: the sum of their
        weights)."""
        import jax
        import jax.numpy as jnp

        x, aux, routing, batch = Transformer.block_diffusion_hidden(
            params, batch, cfg, mesh=mesh, rules=rules)
        clean, weights = batch["targets"], batch["mask"]
        total = head.nll_sum(head.weight(params, cfg), x, clean, cfg,
                             mask=weights, mesh=mesh, rules=rules)
        with jax.named_scope("loss"):
            loss_val = total / clean.size
        if cfg.moe_aux_coeff and cfg.moe_experts \
                and cfg.moe_scoring == "softmax":
            loss_val = loss_val + cfg.moe_aux_coeff * aux
        if not with_metrics:
            return loss_val
        with jax.named_scope("diffusion/noise"):
            counts = {
                "diffusion_masked_tokens": jnp.sum(weights > 0,
                                                   dtype=jnp.int32),
                "diffusion_weight_sum": jnp.sum(weights,
                                                dtype=jnp.float32)}
        loss_val, metrics = Transformer._loss_out(loss_val, aux, routing,
                                                  cfg, True)
        return loss_val, dict(metrics, **counts)

    @staticmethod
    def exit_log_probs(z):
        """The exit gate's z [R, ...] f32 -> log p [R, ...], the
        distribution over the pass a token leaves at, from log-sigmoids
        (never from products of probabilities): a token leaves after pass
        t < R with probability sigmoid(z_t) if it stayed through the
        passes before, `log p_t = log sigmoid(z_t) + sum_{j<t} log
        sigmoid(-z_j)`, and the last pass takes what is left, `log p_R =
        sum_{j<R} log sigmoid(-z_j)`: z_R is read by nothing."""
        import jax
        import jax.numpy as jnp

        stayed = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)
        before = jnp.concatenate([jnp.zeros_like(z[:1]), stayed[:-1]])
        return jnp.concatenate(
            [jax.nn.log_sigmoid(z[:-1]) + before, stayed[-1:]])

    @staticmethod
    def _exit_loss(params, batch, cfg: TransformerConfig, mesh,
                   rules: ShardingRules, with_metrics: bool):
        """The loss of a looped stack with an exit gate (arXiv 2510.25741,
        the first-stage objective): with p_t(i) the exit distribution of
        token i (`exit_log_probs`) and nll_t(i) the next-token
        cross-entropy of pass t's logits, the mean over the (masked)
        tokens of `sum_t p_t nll_t - exit_entropy_coeff * H(p)`, `H(p) =
        -sum_t p_t log p_t` (a uniform prior over the exit step), all in
        float32. The R passes' hidden states go through ONE call of the
        head as B*R rows (a sequence's passes side by side), the targets
        repeated, p in the place of the head's `weights`, which hands
        them their cotangent (`head.nll_sum`). Metrics, from the same
        forward pass: `loop_exit_mass` f32 [R] (the mean of p_t over the
        tokens: sums to 1), `loop_exit_entropy` (the mean of H) and
        `loop_pass_nll` f32 [R] (the mean of nll_t)."""
        import jax
        import jax.numpy as jnp

        tokens, targets = Transformer._tokens_and_targets(batch)
        mask = batch.get("mask")
        hs, _, _, z = Transformer.hidden(
            params, tokens, cfg, mesh=mesh, rules=rules, with_aux=True)
        r, b, t, d = hs.shape

        def rows(a):   # [R, B, T, ...] -> [B * R, T, ...]
            return jnp.swapaxes(a, 0, 1).reshape((b * r,) + a.shape[2:])

        with jax.named_scope("loop/exit_loss"):
            log_p = Transformer.exit_log_probs(z)
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=0)                # [B, T]
            if mask is not None:
                mask = mask.astype(jnp.float32)
                entropy = entropy * mask
            count = targets.size if mask is None \
                else jnp.maximum(jnp.sum(mask), 1.0)
            x, weights = rows(hs), rows(p)
            repeated = jnp.repeat(targets, r, axis=0)
            row_mask = None if mask is None else jnp.repeat(mask, r, axis=0)
        total, nll = head.nll_sum(
            head.weight(params, cfg), x, repeated, cfg, mask=row_mask,
            weights=weights, mesh=mesh, rules=rules)
        with jax.named_scope("loop/exit_loss"):
            loss_val = (total - cfg.exit_entropy_coeff
                        * jnp.sum(entropy)) / count
            if not with_metrics:
                return loss_val
            seen = p if mask is None else p * mask
            return loss_val, {
                "loop_exit_mass": jnp.sum(seen, axis=(1, 2)) / count,
                "loop_exit_entropy": jnp.sum(entropy) / count,
                "loop_pass_nll": jnp.sum(nll.reshape(b, r, t),
                                         axis=(0, 2)) / count}

    @staticmethod
    def _loss_out(loss_val, aux, routing, cfg: TransformerConfig,
                  with_metrics: bool):
        if not with_metrics:
            return loss_val
        if not cfg.moe_experts:
            return loss_val, {}
        metrics = {
            "moe_tokens_per_expert": routing["tokens_per_expert"],
            "moe_dropped": routing["dropped"].sum(),
            "moe_aux_loss": aux}
        if cfg.held_experts < cfg.moe_experts:
            metrics["moe_slots_elsewhere"] = routing["slots_elsewhere"]
            metrics["moe_rows_bounded"] = routing["rows_bounded"]
        if cfg.moe_groups > 1:
            metrics["moe_groups_chosen"] = routing["groups_chosen"]
        if "exchange_rows_sent" in routing:   # an expert mesh's exchange
            metrics.update(("moe_" + name, routing[name]) for name in (
                "rows_received", "exchange_rows_sent",
                "exchange_rows_needed", "exchange_pairs",
                "exchange_bounded"))
        return loss_val, metrics
