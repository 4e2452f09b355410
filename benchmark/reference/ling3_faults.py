"""What `ling3_f32` reads with one published term left out, or computed in
a narrower precision: the second of the two readings a configuration's
`tolerance` is set from (the first is the system's own error, in every
run's `reference_logits` and `reference_loss` checks). Each fault, and the
precision below the one the configuration states, has to come out as not
correct; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample, the share the configuration holds:

    no_decay            the decay left out (a = 1: a plain delta rule)
    gate_unbounded      the gate's bound ignored: log a = -exp(A) *
                        softplus(W_f x + b_f), KDA's unbounded form
    no_beta             beta left out (1)
    no_delta            the rank-one term dropped: S_t = diag(a_t) S_{t-1}
                        + beta_t k_t v_t^T, gated linear attention
    no_l2_norm          q and k not L2-normed
    no_head_norm        the head norm left out (the gate stays)
    no_out_gate         the output gate left out (the norm stays)
    no_conv             the three convolutions left out (silu stays)
    no_group_limit      the top 8 taken over all 512 experts
    no_routed_scale     routed_scaling_factor left out (1.0)
    bias_ignored        e_score_correction_bias left out of the choice
    no_q_norm           the query side of the QK-norm left out
    no_kv_a_norm        the key/value latent's RMS norm left out
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, router, experts, head; the delta
                        rule, attention's two products and everything
                        else stay float32: a floor of what the precision
                        costs)

    python3 benchmark/reference/ling3_faults.py <config.json> \\
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
FAULTS = ("no_decay", "gate_unbounded", "no_beta", "no_delta", "no_l2_norm",
          "no_head_norm", "no_out_gate", "no_conv", "no_group_limit",
          "no_routed_scale", "bias_ignored", "no_q_norm", "no_kv_a_norm")


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_ling3_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                           "ling3_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    def zeroed(leaf):
        return dict(weights, layers=[
            dict(lw, **{leaf: jnp.zeros_like(lw[leaf])}) if leaf in lw
            else lw for lw in weights["layers"]])

    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "no_decay":
        ref.decay_gate = lambda f, a_log, dt_bias, lower: jnp.zeros_like(f)
    elif name == "gate_unbounded":
        ref.decay_gate = lambda f, a_log, dt_bias, lower: \
            -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f + dt_bias.reshape(f.shape[2:]))
    elif name == "no_beta":
        plain_rule = ref.delta_rule
        ref.delta_rule = lambda q, k, v, log_a, beta: plain_rule(
            q, k, v, log_a, jnp.ones_like(beta))
    elif name == "no_delta":
        def linear_attention(q, k, v, log_a, beta):
            def step(state, inp):
                q_t, k_t, v_t, a_t, b_t = inp
                state = state * jnp.exp(a_t)[..., None] + jnp.einsum(
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
    elif name == "no_head_norm":   # the one norm of a head's width
        plain_norm, width = ref.rms_norm, model["head_dim"]
        ref.rms_norm = lambda x, g, eps: x if x.ndim == 4 \
            and x.shape[-1] == width else plain_norm(x, g, eps)
    elif name == "no_out_gate":
        weights = zeroed("g_proj")     # sigmoid(0): one half for all
    elif name == "no_conv":
        ref.short_conv = lambda x, w: jax.nn.silu(x)
    elif name == "no_group_limit":
        model = dict(model, n_group=1, topk_group=1)
    elif name == "no_routed_scale":
        model = dict(model, routed_scaling_factor=1.0)
    elif name == "bias_ignored":
        weights = zeroed("e_score_correction_bias")
    elif name == "no_q_norm":
        # `latent_attention` norms the query first, the key second
        plain_qk, calls = ref.qk_norm, []

        def qk_norm(x, gain, eps):
            calls.append(None)
            return x if len(calls) % 2 else plain_qk(x, gain, eps)
        ref.qk_norm = qk_norm
    elif name == "no_kv_a_norm":    # the one norm of that width
        plain_norm, width = ref.rms_norm, model["kv_lora_rank"]
        ref.rms_norm = lambda x, g, eps: x if x.shape[-1] == width \
            else plain_norm(x, g, eps)
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
