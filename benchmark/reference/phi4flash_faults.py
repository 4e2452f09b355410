"""What `phi4flash_f32` reads with one published term left out or misread,
or computed in a narrower precision: the second of the two readings a
configuration's `tolerance` is set from (the first is the system's own
error, in every run's `reference_logits` and `reference_loss` checks).
Each fault, and the precision below the one the configuration states, has
to come out as not correct; bf16 operands pass. One variant is reported
and not refused: a bfloat16 state in the scan reads on both sides of the
system's own error on the chip (the configuration's `tolerance.why`).

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample:

    window_ignored      the `w` layers attend under the causal mask alone
    no_lambda_term      `O = A1 V`: the second map's term dropped
    no_sub_norm         the RMS norm over a pair's output left out
    lambda_init_at_cut  lambda_init at the layer's place in the cut (0..5)
                        instead of its published index (14..19)
    memory_after_gate   the memory m taken after `* silu(z)`
    kv_recomputed       a `c` layer computes K and V from its OWN normed
                        stream with the layer f's weights, instead of
                        reading the layer f's K and V
    no_dt_bias          `b_dt` left out of softplus
    no_layernorm_bias   every LayerNorm's bias left out
    bf16_scan_state     the recurrence's state kept in bfloat16
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, MLP, head; the recurrence,
                        attention's two products and everything else stay
                        float32: a floor of what the precision costs)

    python3 benchmark/reference/phi4flash_faults.py <config.json> \\
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
FAULTS = ("window_ignored", "no_lambda_term", "no_sub_norm",
          "lambda_init_at_cut", "memory_after_gate", "kv_recomputed",
          "no_dt_bias", "no_layernorm_bias", "bf16_scan_state")


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_phi4flash_f32_{name}", os.path.join(
            BENCH_DIR, "reference", "phi4flash_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    layers = weights["layers"]
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w, b=None: plain(
            x.astype(dtype), w.astype(dtype), b)
    elif name == "window_ignored":
        ref.window_of = lambda cfg, kind: 0
    elif name == "no_lambda_term":
        ref.combine = lambda a1, a2, lam: a1
    elif name == "no_sub_norm":
        ref.sub_norm = lambda o, gain, eps: o
    elif name == "lambda_init_at_cut":
        model = dict(model, first_layer_index=0)
    elif name == "memory_after_gate":
        ref.memory_of = lambda y, gated: gated
    elif name == "bf16_scan_state":
        ref.STATE_DTYPE = "bfloat16"
    elif name == "kv_recomputed":
        full = layers[model["layer_kinds"].index("f")]
        heads, kv = (model["num_attention_heads"],
                     model["num_key_value_heads"])
        hd = model["hidden_size"] // heads

        def own_kv(n, lw, made, cfg):
            bsz, t, _ = n.shape
            kv_ = ref.linear(n, full["Wqkv"][heads * hd:],
                             full["Wqkv_bias"][heads * hd:])
            return (kv_[..., :kv * hd].reshape(bsz, t, kv // 2, 2, hd),
                    kv_[..., kv * hd:].reshape(bsz, t, kv // 2, 2 * hd))
        ref.cross_kv = own_kv
    elif name == "no_dt_bias":
        layers = [dict(lw, dt_proj_bias=jnp.zeros_like(lw["dt_proj_bias"]))
                  if "dt_proj_bias" in lw else lw for lw in layers]
    elif name == "no_layernorm_bias":
        def bare(norm):
            return dict(norm, bias=jnp.zeros_like(norm["bias"]))
        layers = [dict(lw, input_layernorm=bare(lw["input_layernorm"]),
                       post_attention_layernorm=bare(
                           lw["post_attention_layernorm"]))
                  for lw in layers]
        weights = dict(weights,
                       final_layernorm=bare(weights["final_layernorm"]))
    elif name is not None:
        raise KeyError(name)
    return ref, model, dict(weights, layers=layers)


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
