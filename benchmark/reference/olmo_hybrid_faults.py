"""What `olmo_hybrid_f32` reads with one published term left out or
misplaced, or computed in a narrower precision: the second of the two
readings a configuration's `tolerance` is set from (the first is the
system's own error, in every run's `reference_logits` and `reference_loss`
checks). Each fault, and the precision below the one the configuration
states, has to come out as not correct; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample, the share the configuration holds:

    no_decay            the decay left out (a = 1: a plain delta rule)
    beta_not_doubled    beta's factor 2 left out (beta in (0, 1))
    no_beta             beta left out (1)
    no_delta            the rank-one term dropped: S_t = a_t S_{t-1}
                        + beta_t v_t k_t^T, gated linear attention
    no_l2_norm          q and k not L2-normed
    no_conv             the three convolutions left out (silu stays)
    no_out_gate         the output gate left out (the head norm stays)
    no_head_norm        the head norm left out (the gate stays)
    norm_before         the norm moved before the sublayer: x + f(norm(x))
                        with the same gains, the usual block
    no_qk_norm          the QK-norm left out, queries and keys
    rope_applied        a rotary embedding applied (theta 10,000,
                        rotate-half) where rope_theta is null
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, MLPs, head; the delta rule,
                        attention's two products and everything else stay
                        float32: a floor of what the precision costs)

    python3 benchmark/reference/olmo_hybrid_faults.py <config.json> \\
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
FAULTS = ("no_decay", "beta_not_doubled", "no_beta", "no_delta",
          "no_l2_norm", "no_conv", "no_out_gate", "no_head_norm",
          "norm_before", "no_qk_norm", "rope_applied")
ROPE_THETA = 10000.0


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_olmo_hybrid_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                                 "olmo_hybrid_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "no_decay":
        ref.decay_gate = lambda a, a_log, dt_bias: jnp.zeros_like(a)
    elif name == "beta_not_doubled":
        model = dict(model, linear_allow_neg_eigval=False)
    elif name == "no_beta":
        ref.beta_gate = lambda b, allow: jnp.ones_like(b)
    elif name == "no_delta":
        def linear_attention(q, k, v, log_a, beta):
            def step(state, inp):
                q_t, k_t, v_t, a_t, b_t = inp
                state = state * jnp.exp(a_t)[..., None, None] + jnp.einsum(
                    "bhk,bhv->bhkv", k_t, v_t * b_t[..., None])
                return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)
            b, _, h, d = q.shape
            steps = tuple(jnp.moveaxis(a, 1, 0)
                          for a in (q, k, v, log_a, beta))
            _, o = jax.lax.scan(step, jnp.zeros(
                (b, h, d, v.shape[-1]), jnp.float32), steps)
            return jnp.moveaxis(o, 0, 1) * d ** -0.5
        ref.delta_rule = linear_attention
    elif name == "no_l2_norm":
        ref.l2_norm = lambda x: x
    elif name == "no_conv":
        ref.short_conv = lambda x, w: jax.nn.silu(x)
    elif name == "no_out_gate":
        ref.out_gate = jnp.ones_like
    elif name == "no_head_norm":   # the one norm over a head's values
        plain_norm, width = ref.rms_norm, model["linear_value_head_dim"]
        ref.rms_norm = lambda x, g, eps: x if x.ndim == 4 \
            and x.shape[-1] == width else plain_norm(x, g, eps)
    elif name == "norm_before":
        ref.sublayer = lambda x, f, gain, eps: x + f(
            ref.rms_norm(x, gain, eps))
    elif name == "no_qk_norm":
        ref.qk_norm = lambda x, gain, eps: x
    elif name == "rope_applied":
        def rotary(q, k):
            t, d = q.shape[2], q.shape[3]
            inv_freq = 1.0 / (ROPE_THETA ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
            emb = jnp.concatenate([angles, angles], axis=-1)
            cos, sin = jnp.cos(emb), jnp.sin(emb)

            def turn(x):
                half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]],
                                       axis=-1)
                return x * cos + half * sin
            return turn(q), turn(k)
        ref.rotary = rotary
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
