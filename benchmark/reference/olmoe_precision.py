"""What `olmoe_f32` reads when it is computed in a narrower precision:
the second of the two readings a configuration's `tolerance` is set from
(the first is the system's own error, in every run's `reference_logits`
and `reference_loss` checks). The precision below the one the
configuration states has to come out as not correct.

The reference stays plain: the rounding happens here, outside it. A fresh
copy of the module gets a `linear` that rounds both operands of every
weight matmul (projections, router, experts, head) to the narrower type
and accumulates in float32 as before; attention's two products and
everything else stay float32, so the reading is a floor of what the
precision costs. Each copy is compared with the unrounded reference on the
job's own weights (`init_params`) and reference sample.

    python3 benchmark/reference/olmoe_precision.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and precision: `rel_l2` of the logits,
`loss_diff` of cross-entropy + aux, and `correct`, the configuration's
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


def rounded_reference(dtype):
    """A copy of `olmoe_f32` whose weight matmuls take operands rounded
    to `dtype` (None: the reference as it is)."""
    spec = importlib.util.spec_from_file_location(
        f"_olmoe_f32_{dtype}", os.path.join(BENCH_DIR, "reference",
                                            "olmoe_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    if dtype is not None:
        plain = ref.linear
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    return ref


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int
         ) -> Iterator[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from benchlib.spec import load_module
    from benchlib.traffic import TokenBatches

    job = load_module("jobs", model["job"])
    batches = TokenBatches(mix, model["vocab_size"], seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    weights = jax.jit(lambda k: job.to_reference_layout(
        job.init_params(k, cfg, model["init"]), cfg))(jax.random.key(seed))
    sample = jnp.asarray(batches.reference_sample(
        **mix["reference_sample"]))

    def side(dtype):
        ref = rounded_reference(dtype)
        logits, router_logits = ref.forward(
            weights, sample[:, :-1], model, with_router_logits=True)
        loss = ref.next_token_loss(logits, sample[:, 1:]) \
            + model["router_aux_loss_coef"] * ref.load_balancing_loss(
                router_logits, model)
        return logits, float(loss)

    base, base_loss = side(None)
    tol = model["tolerance"]
    for name in PRECISIONS:
        logits, loss = side(jnp.dtype(name))
        diff = logits - base
        rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                                / jnp.sum(base * base)))
        loss_diff = abs(loss - base_loss)
        yield {"seed": seed, "operands": name, "rel_l2": rel_l2,
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
