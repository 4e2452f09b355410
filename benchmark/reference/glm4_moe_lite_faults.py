"""What `glm4_moe_lite_f32` reads with one published term left out, or
computed in a narrower precision: the second of the two readings a
configuration's `tolerance` is set from (the first is the system's own
error, in every run's `reference_logits` and `reference_loss` checks).
Each fault, and the precision below the one the configuration states, has
to come out as not correct; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample, the share the configuration holds:

    no_kv_a_norm        the key/value latent's RMS norm left out
    no_rope_on_key      RoPE left off the shared rotary key head
    no_routed_scale     routed_scaling_factor left out (1.0)
    bias_ignored        e_score_correction_bias left out of the choice
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, router, experts, head; attention's
                        two products and everything else stay float32:
                        a floor of what the precision costs)

    python3 benchmark/reference/glm4_moe_lite_faults.py <config.json> \\
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
FAULTS = ("no_kv_a_norm", "no_rope_on_key", "no_routed_scale",
          "bias_ignored")


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_glm4_moe_lite_f32_{name}", os.path.join(
            BENCH_DIR, "reference", "glm4_moe_lite_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
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
