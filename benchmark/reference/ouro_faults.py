"""What `ouro_f32` reads with one published (or assumed) term left out,
moved or misread, or computed in a narrower precision: the second of the
two readings a configuration's `tolerance` is set from (the first is the
system's own error, in every run's `reference_*` checks). Each fault, and
the precision below the one the configuration states, has to come out as
not correct by the limit it is listed under; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
reference sample.

  by the logits (`logits_rel_l2`, the largest over the four passes):
    three_passes          three passes where four are published (its last
                          pass stands where the fourth should)
    next_pass_reads_before_norm
                          the final norm gives the pass's hidden state, but
                          the next pass reads the stream before it
    no_post_norms         the norms on the sublayers' outputs left out
    no_pre_norms          the norms before the sublayers left out
    rope_theta_1e4        RoPE at theta 10,000
    bfloat16, float8_e4m3fn, float8_e5m2
                          both operands of every weight matmul rounded
                          (projections, MLPs, head; attention's two
                          products, the gate and everything else stay
                          float32: a floor of what the precision costs)
  by z or the exit distribution (`gate_z_rel_l2`, `exit_prob_abs`):
    gate_before_norm      the gate reads the stream before the final norm
    last_pass_lambda      the last pass given lambda_R S_{R-1} and not what
                          is left
  by the objective (`objective_abs`: the variant's `exit_loss` and
  `exit_distribution` on the UNCHANGED forward pass's z and
  cross-entropies, as the job applies the reference's to the system's;
  `last_pass_lambda` fails here too):
    no_entropy            the entropy term left out
    entropy_sign          the entropy term's sign turned
    uniform_weights       every pass weighted 1/4 whatever the gate says
    last_pass_only        the last pass's loss alone
  by the gate's gradient (`gate_grad_rel_l2`: the variant's
  `gate_gradient` on the unchanged forward pass; the four above fail
  here too):
    stopped_weights       the weights p_t under a stopped gradient in the
                          expected loss (what a head that drops its
                          weights' cotangent trains): the loss itself does
                          not move
  by nothing, and listed to say so (`EQUIVALENT`):
    positions_run_on      the position ids run on from pass to pass, pass
                          t reading t T .. (t + 1) T - 1: RoPE turns q and
                          k by the same angle, a pass attends within
                          itself, and q . k reads the DIFFERENCE of two
                          positions, so this is the same model to rounding
                          (ISSUE 63 lists it among the faults; float32's
                          angles at positions up to 4 T are all it moves)

    python3 benchmark/reference/ouro_faults.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and variant: of the variant's own forward
pass against the unchanged reference's `rel_l2` of the logits (the largest
over the passes, and by pass), `z_rel_l2`, `exit_prob_abs`, `loss_diff`;
of the variant's objective on the unchanged forward pass `objective_diff`
and `gate_grad_rel_l2`; `fails` (the limits of the configuration's
`tolerance` it fails) and `correct`.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
BY_LOGITS = ("three_passes", "next_pass_reads_before_norm", "no_post_norms",
             "no_pre_norms", "rope_theta_1e4")
BY_EXIT = ("gate_before_norm", "last_pass_lambda")
BY_OBJECTIVE = ("no_entropy", "entropy_sign", "uniform_weights",
                "last_pass_only")
BY_GATE_GRADIENT = ("stopped_weights",)
FAULTS = BY_LOGITS + BY_EXIT + BY_OBJECTIVE + BY_GATE_GRADIENT
EQUIVALENT = ("positions_run_on",)
# the limit of `tolerance` each reading is held to
LIMITS = {"rel_l2": "logits_rel_l2", "z_rel_l2": "gate_z_rel_l2",
          "exit_prob_abs": "exit_prob_abs", "loss_diff": "loss_abs",
          "objective_diff": "objective_abs",
          "gate_grad_rel_l2": "gate_grad_rel_l2"}


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights) of the reference with `name` applied
    (None: the reference as it is)."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_ouro_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                          "ouro_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    plain_loss, plain_dist = ref.exit_loss, ref.exit_distribution

    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "three_passes":
        model = dict(model, total_ut_steps=model["total_ut_steps"] - 1)
    elif name == "next_pass_reads_before_norm":
        ref.close_pass = lambda x, w, cfg: (ref.rms_norm(
            x, w["norm"].astype(jnp.float32), cfg["rms_norm_eps"]), x)
    elif name == "no_post_norms":
        ref.sublayer = lambda x, f, before, after, eps: x + f(
            ref.rms_norm(x, before, eps))
    elif name == "no_pre_norms":
        ref.sublayer = lambda x, f, before, after, eps: x + ref.rms_norm(
            f(x), after, eps)
    elif name == "rope_theta_1e4":
        model = dict(model, rope_theta=1e4)
    elif name == "positions_run_on":
        ref.pass_positions = lambda t, seq: t * seq + jnp.arange(seq)
    elif name == "gate_before_norm":
        plain_gate = ref.exit_gate
        ref.exit_gate = lambda h, x, w: plain_gate(x, x, w)
    elif name == "last_pass_lambda":
        def exit_distribution(zs):
            log_p = plain_dist(zs)
            return log_p[:-1] + [log_p[-1] + jax.nn.log_sigmoid(zs[-1])]
        ref.exit_distribution = exit_distribution
    elif name == "no_entropy":
        ref.exit_loss = lambda log_p, nll, beta: plain_loss(log_p, nll, 0.0)
    elif name == "entropy_sign":
        ref.exit_loss = lambda log_p, nll, beta: plain_loss(log_p, nll,
                                                            -beta)
    elif name == "uniform_weights":
        ref.exit_loss = lambda log_p, nll, beta: plain_loss(
            [jnp.full_like(lp, -math.log(len(log_p))) for lp in log_p],
            nll, beta)
    elif name == "last_pass_only":
        ref.exit_loss = lambda log_p, nll, beta: jnp.mean(nll[-1])
    elif name == "stopped_weights":
        def exit_loss(log_p, nll, beta):
            expected = sum(jax.lax.stop_gradient(jnp.exp(lp)) * n
                           for lp, n in zip(log_p, nll))
            entropy = -sum(jnp.exp(lp) * lp for lp in log_p)
            return jnp.mean(expected - beta * entropy)
        ref.exit_loss = exit_loss
    elif name is not None:
        raise KeyError(name)
    return ref, model, weights


def readings(ref, model: Dict[str, Any], weights: Dict[str, Any], sample,
             passes: int) -> Dict[str, Any]:
    """One side's forward pass as the job compares it: the passes' hidden
    states (a pass's logits are made from them where they are compared:
    1.6 GB a pass at the cell's size) and streams, z, the passes'
    cross-entropies, p and the loss. A side with fewer passes than
    `passes` stands with its last pass where the later ones should be,
    and with p = 0 there."""
    import jax.numpy as jnp

    tokens, targets = sample[:, :-1], sample[:, 1:]
    hs, zs, streams = ref.passes(weights, tokens, model)
    nll = [ref.token_nll(ref.logits_of(weights, h), targets) for h in hs]
    log_p = ref.exit_distribution(zs)
    short = passes - len(hs)
    return {"hs": hs + hs[-1:] * short, "streams": streams, "zs": zs,
            "nll": nll, "z": jnp.stack(zs + zs[-1:] * short),
            "p": jnp.stack([jnp.exp(lp) for lp in log_p]
                           + [jnp.zeros_like(zs[0])] * short),
            "loss": float(ref.exit_loss(log_p, nll,
                                        model["exit_entropy_coeff"]))}


def objective(ref, model: Dict[str, Any], weights: Dict[str, Any],
              forward: Dict[str, Any]):
    """(`ref`'s objective, its gradient by the gate) on a forward pass
    `readings` made: what the job does with the reference's `exit_loss`
    and `gate_gradient` on the system's."""
    import jax.numpy as jnp

    n = len(forward["nll"])
    loss = float(ref.exit_loss(ref.exit_distribution(forward["zs"]),
                               forward["nll"], model["exit_entropy_coeff"]))
    grad = ref.gate_gradient(weights, forward["hs"][:n], forward["streams"],
                             forward["nll"], model)
    return loss, jnp.concatenate([grad["weight"].reshape(-1),
                                  grad["bias"].reshape(-1)])


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + EQUIVALENT + PRECISIONS) -> Iterator[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from benchlib.spec import load_module
    from benchlib.traffic import TokenBatches

    job = load_module("jobs", model["job"])
    rel_l2 = job.rel_l2   # the job's own measure
    batches = TokenBatches(mix, model["vocab_size"], seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = jax.jit(lambda k: job.init_params(k, cfg, model["init"]))(
        jax.random.key(seed))
    weights = jax.jit(lambda p: job.to_reference_layout(p, cfg))(params)
    del params
    sample = jnp.asarray(batches.reference_sample(
        **mix["reference_sample"]))

    plain = variant(None, model, weights)[0]
    passes = model["total_ut_steps"]
    base = readings(plain, model, weights, sample, passes)
    base_loss, base_grad = objective(plain, model, weights, base)
    tol = model["tolerance"]
    for name in names:
        ref, model_, weights_ = variant(name, model, weights)
        got = readings(ref, model_, weights_, sample, passes)
        # the head's product as the variant makes it (rounded operands
        # where the variant rounds them)
        by_pass = [rel_l2(ref.logits_of(weights_, h),
                          plain.logits_of(weights, want))
                   for h, want in zip(got["hs"], base["hs"])]
        loss, grad = objective(ref, model_, weights_, base)
        row = {"seed": seed, "variant": name, "rel_l2": max(by_pass),
               "rel_l2_by_pass": by_pass,
               "z_rel_l2": rel_l2(got["z"], base["z"]),
               "exit_prob_abs": float(jnp.max(jnp.abs(got["p"]
                                                      - base["p"]))),
               "loss_diff": abs(got["loss"] - base["loss"]),
               "objective_diff": abs(loss - base_loss),
               "gate_grad_rel_l2": rel_l2(grad, base_grad)}
        row["fails"] = [limit for key, limit in LIMITS.items()
                        if not row[key] <= tol[limit]]
        row["correct"] = not row["fails"]
        yield row


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[3:]:
        for row in read(model, mix, int(seed)):
            print(json.dumps(row), flush=True)
