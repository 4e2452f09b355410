"""What `nemotron_h_f32` reads with one published term left out, or
computed in a narrower precision: the second of the two readings a
configuration's `tolerance` is set from (the first is the system's own
error, in every run's `reference_logits` and `reference_loss` checks).
Each fault, and the precision below the one the configuration states, has
to come out as not correct; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample, the share the configuration holds:

    no_gated_norm       the mixer's grouped RMS norm left out (the gate
                        `* silu(z)` stays)
    no_D_skip           `D x` left out of the scan's output
    no_dt_bias          `dt_bias` left out of softplus
    no_conv_bias        the convolution's bias left out
    relu_not_relu2      the experts' `relu(x)^2` read as `relu(x)`
    no_routed_scale     routed_scaling_factor left out (1.0)
    bias_ignored        the correction bias left out of the choice
    unnormalised        the chosen scores not normalised to sum 1
    rope_applied        rotary embedding (rope_theta, rotate-half) applied
                        to attention's queries and keys
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, router, experts, head; the scan,
                        attention's two products and everything else stay
                        float32: a floor of what the precision costs)

    python3 benchmark/reference/nemotron_h_faults.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and variant: `rel_l2` of the logits against
the unchanged reference, `loss_diff`, and `correct`, the configuration's
two limits applied to them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
ZEROED = {"no_D_skip": "D", "no_dt_bias": "dt_bias",
          "no_conv_bias": "conv1d_bias",
          "bias_ignored": "e_score_correction_bias"}
FAULTS = ("no_gated_norm", "no_D_skip", "no_dt_bias", "no_conv_bias",
          "relu_not_relu2", "no_routed_scale", "bias_ignored",
          "unnormalised", "rope_applied")


def rotary(x, theta: float):
    """Rotate-half rotary embedding of x [B, H, T, D]."""
    import jax.numpy as jnp
    t, d = x.shape[2], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_nemotron_h_f32_{name}", os.path.join(
            BENCH_DIR, "reference", "nemotron_h_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "no_gated_norm":   # the one norm over groups: 4 axes
        plain_norm = ref.rms_norm
        ref.rms_norm = lambda x, g, eps: x if x.ndim == 4 \
            else plain_norm(x, g, eps)
    elif name == "relu_not_relu2":
        ref.relu2 = lambda x: jnp.maximum(x, 0.0)
    elif name == "rope_applied":
        plain_attn, theta = ref.causal_attention, float(model["rope_theta"])
        ref.causal_attention = lambda q, k, v, scale: plain_attn(
            rotary(q, theta), rotary(k, theta), v, scale)
    elif name == "no_routed_scale":
        model = dict(model, routed_scaling_factor=1.0)
    elif name == "unnormalised":
        model = dict(model, norm_topk_prob=False)
    elif name in ZEROED:
        leaf = ZEROED[name]
        weights = dict(weights, layers=[
            dict(lw, **{leaf: jnp.zeros_like(lw[leaf])}) if leaf in lw
            else lw for lw in weights["layers"]])
    elif name is not None:
        raise KeyError(name)
    return ref, model, weights


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + PRECISIONS) -> Iterator[Dict[str, Any]]:
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
        logits = ref.forward(weights_, sample[:, :-1], cfg_)
        return logits, float(ref.next_token_loss(logits, sample[:, 1:]))

    base, base_loss = side(None)
    tol = model["tolerance"]
    for name in names:
        logits, loss = side(name)
        diff = logits - base
        rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                                / jnp.sum(base * base)))
        loss_diff = abs(loss - base_loss)
        yield {"seed": seed, "variant": name, "rel_l2": rel_l2,
               "loss_diff": loss_diff,
               "correct": rel_l2 <= tol["logits_rel_l2"]
               and loss_diff <= tol["loss_abs"]}


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[3:]:
        for row in read(model, mix, int(seed)):
            print(json.dumps(row), flush=True)
