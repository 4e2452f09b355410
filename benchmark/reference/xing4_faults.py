"""What `xing4_f32` reads with one term of the model misread or left out,
or computed in a narrower precision: the second of the two readings a
configuration's `tolerance` is set from (the first is the system's own
error, in every run's `reference_logits`, `reference_loss` and
`reference_maps` checks). Each fault, and the precision below the one the
configuration states, has to come out as not correct, by the limit it is
listed under (`LISTED_UNDER`); bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample, the share the configuration holds.

The streams (by `maps_rel_l2`, the relative L2 difference of a sublayer's
n*n + 2n maps over the sample's tokens, the worst of the sublayers the job
judges, `judged_sublayers`: those up to the first expert sublayer, which no
rounded routing has touched; unless said):

    no_sinkhorn          H_res = exp(A), no round
    one_sinkhorn_round   one round where hc_sinkhorn_iters are published
    row_softmax          a softmax over rows in the rounds' place
    h_res_transposed     X'[i] = sum_j H_res[j, i] X[j]
    h_res_identity       H_res = I: four plain residual streams
    h_post_no_factor_2   H_post = sigmoid(.), the factor 2 left off
    h_pre_softmax        H_pre a softmax over the streams
    static_maps          the dynamic part left out (alpha = 0)
    per_stream_statistic the RMS over each stream's C and not over n*C
    no_sublayer_norm     the sublayer's own pre-norm left out (by the
                         logits: the first sublayer's maps read the
                         embedding alone and cannot show it)
    embedding_in_stream_0  the embedding in stream 0 alone, the others 0

Attention and the experts (by `logits_rel_l2`):

    no_yarn_softmax_factor  mscale_all_dim's factor left off the scale
    no_yarn_ramp         the frequencies left as theta^(-2i/d)
    no_kv_a_norm         the key/value latent's RMS norm left out
    no_rope_on_key       RoPE left off the shared rotary key head
    no_routed_scale      routed_scaling_factor left out (1.0)
    bias_ignored         e_score_correction_bias left out of the choice
    bfloat16, float8_e4m3fn, float8_e5m2
                         both operands of every weight matmul rounded
                         (projections, phi, router, experts, head;
                         attention's two products, the mixes and
                         everything else stay float32: a floor of what
                         the precision costs)

NOT a fault: the exit a mean and not a sum. The final RMSNorm divides by
the RMS of what it is given, so `mean` and `sum` are the same function of
the streams up to rms_norm_eps (`exit_mean` below reads 1e-6 on the logits
and 0 on the maps; `tests/test_xing4_reference.py` holds that). The clamp
cannot show at the stand-in weights either (|A| stays far under 30): that
test holds it at |A| above 30.

    python3 benchmark/reference/xing4_faults.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and variant: `rel_l2` of the logits against
the unchanged reference, `loss_diff`, `maps_rel_l2`, and `correct`, the
configuration's limits applied to them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
STREAM_FAULTS = ("no_sinkhorn", "one_sinkhorn_round", "row_softmax",
                 "h_res_transposed", "h_res_identity", "h_post_no_factor_2",
                 "h_pre_softmax", "static_maps", "per_stream_statistic",
                 "no_sublayer_norm", "embedding_in_stream_0")
LAYER_FAULTS = ("no_yarn_softmax_factor", "no_yarn_ramp", "no_kv_a_norm",
                "no_rope_on_key", "no_routed_scale", "bias_ignored")
FAULTS = STREAM_FAULTS + LAYER_FAULTS
NOT_A_FAULT = ("exit_mean",)
# the limit of the configuration's `tolerance` each fault has to fail
LISTED_UNDER = dict(
    {name: "maps_rel_l2" for name in STREAM_FAULTS},
    **{name: "logits_rel_l2" for name in LAYER_FAULTS + (
        "no_sublayer_norm", "float8_e4m3fn", "float8_e5m2")})


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_xing4_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                           "xing4_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    plain_maps, plain_rounds = ref.stream_maps, ref.sinkhorn

    def maps_with(change):
        """`stream_maps` with (pre, post, res) changed."""
        ref.stream_maps = lambda x, hw, cfg: change(*plain_maps(x, hw, cfg))

    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "no_sinkhorn":
        ref.sinkhorn = lambda m, rounds, eps: m
    elif name == "one_sinkhorn_round":
        ref.sinkhorn = lambda m, rounds, eps: plain_rounds(m, 1, eps)
    elif name == "row_softmax":
        ref.sinkhorn = lambda m, rounds, eps: m / m.sum(-1, keepdims=True)
    elif name == "h_res_transposed":
        maps_with(lambda pre, post, res: (pre, post,
                                          jnp.swapaxes(res, -1, -2)))
    elif name == "h_res_identity":
        maps_with(lambda pre, post, res: (
            pre, post, jnp.broadcast_to(jnp.eye(res.shape[-1]), res.shape)))
    elif name == "h_post_no_factor_2":
        maps_with(lambda pre, post, res: (pre, post / 2.0, res))
    elif name == "h_pre_softmax":   # of the logits the sigmoid was given
        maps_with(lambda pre, post, res: (
            jax.nn.softmax(jnp.log(pre) - jnp.log1p(-pre), axis=-1), post,
            res))
    elif name == "static_maps":
        weights = dict(weights, layers=[
            dict(lw, **{hc: dict(lw[hc], alpha=jnp.zeros_like(
                lw[hc]["alpha"])) for hc in ("attn_hc", "mlp_hc")})
            for lw in weights["layers"]])
    elif name == "per_stream_statistic":
        def per_stream(x, eps):
            b, t, n, c = x.shape
            return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                 + eps)).reshape(b, t, n * c)
        ref.normed_stream = per_stream
    elif name == "no_sublayer_norm":
        def unnormed(x, hw, gain, cfg, sublayer):   # `hyper_connected`
            b, t, n, _ = x.shape
            pre, post, res = ref.stream_maps(x, hw, cfg)
            y = sublayer(jnp.einsum("bti,btic->btc", pre, x))
            out = jnp.einsum("btij,btjc->btic", res, x) \
                + post[..., None] * y[:, :, None, :]
            return out, jnp.concatenate(
                [pre, post, res.reshape(b, t, n * n)], axis=-1)
        ref.hyper_connected = unnormed
    elif name == "embedding_in_stream_0":
        ref.enter_streams = lambda e, n: jnp.concatenate(
            [e[:, :, None, :], jnp.zeros(
                e.shape[:2] + (n - 1, e.shape[-1]), e.dtype)], axis=2)
    elif name == "exit_mean":
        ref.leave_streams = lambda x: jnp.mean(x, axis=2)
    elif name == "no_yarn_softmax_factor":
        ref.softmax_scale = lambda cfg: float(
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    elif name == "no_yarn_ramp":
        ref.yarn_inv_freq = lambda dim, theta, scaling: 1.0 / (theta ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    elif name == "no_kv_a_norm":    # the one norm of that width
        plain_norm, width = ref.rms_norm, model["kv_lora_rank"]
        ref.rms_norm = lambda x, g, eps: x if x.shape[-1] == width \
            else plain_norm(x, g, eps)
    elif name == "no_rope_on_key":  # the one rotary head of its own
        plain_rope = ref.apply_rope
        ref.apply_rope = lambda x, cos, sin: x if x.shape[1] == 1 \
            else plain_rope(x, cos, sin)
    elif name == "no_routed_scale":
        model = dict(model, routed_scaling_factor=1.0)
    elif name == "bias_ignored":
        weights = dict(weights, layers=[
            dict(lw, e_score_correction_bias=jnp.zeros_like(
                lw["e_score_correction_bias"]))
            if "e_score_correction_bias" in lw else lw
            for lw in weights["layers"]])
    elif name is not None:
        raise KeyError(name)
    return ref, model, weights


def judged_row(row: Dict[str, Any], tol: Dict[str, Any]) -> Dict[str, Any]:
    """`row` with `correct`: every limit of the configuration holds."""
    return dict(row, correct=row["rel_l2"] <= tol["logits_rel_l2"]
                and row["loss_diff"] <= tol["loss_abs"]
                and row["maps_rel_l2"] <= tol["maps_rel_l2"])


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + NOT_A_FAULT + PRECISIONS
         ) -> Iterator[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from benchlib.spec import load_module
    from benchlib.traffic import TokenBatches

    job = load_module("jobs", model["job"])
    batches = TokenBatches(mix, model["vocab_size"], seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = jax.jit(lambda k: job.init_params(k, cfg, model["init"]))(
        jax.random.key(seed))
    params, _ = job.balance_held_share(params, cfg, None, batches,
                                       model["init"])
    weights = jax.jit(lambda p: job.to_reference_layout(p, cfg))(params)
    del params
    sample = jnp.asarray(batches.reference_sample(
        **mix["reference_sample"]))

    def side(name):
        ref, cfg_, weights_ = variant(name, model, weights)
        logits, maps = ref.forward(weights_, sample[:, :-1], cfg_,
                                   with_maps=True)
        return logits, maps, float(ref.next_token_loss(logits,
                                                       sample[:, 1:]))

    base, base_maps, base_loss = side(None)
    judged = job.judged_sublayers(cfg)
    for name in names:
        logits, maps, loss = side(name)
        diff = logits - base
        by_sublayer = [float(x) for x in jnp.sqrt(
            jnp.sum((maps - base_maps) ** 2, axis=(1, 2, 3))
            / jnp.sum(base_maps ** 2, axis=(1, 2, 3)))]
        yield judged_row({
            "seed": seed, "variant": name,
            "rel_l2": float(jnp.sqrt(jnp.sum(diff * diff)
                                     / jnp.sum(base * base))),
            "loss_diff": abs(loss - base_loss),
            "maps_rel_l2": max(by_sublayer[:judged]),
            "maps_rel_l2_by_sublayer": by_sublayer,
            "listed_under": LISTED_UNDER.get(name)}, model["tolerance"])


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[3:]:
        for row in read(model, mix, int(seed)):
            print(json.dumps(row), flush=True)
