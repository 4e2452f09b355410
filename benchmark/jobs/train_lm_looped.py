"""Job kind `train_lm_looped`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a looped decoder (the catalog row `Ouro-2.6B`): one
stack of dense layers under a sandwich norm run `total_ut_steps` times
through the same weights, the final norm closing every pass, one head and
one exit gate after every pass, trained on the expected next-token loss
under the exit distribution less `exit_entropy_coeff` times its entropy.

The driver side, the window, the clock and the record's keys are
`train_lm`'s (`run`, `HOST_ANNOTATIONS` and the layer mapping of
`to_reference_layout` are imported from it). The loop reads a step's
metrics while the next step runs, as `train_lm_gdn.one_step` / `drain`
do; those are closures of its `worker_loop` and cannot be imported, so
the loop is here as a class at module level (`StepLoop`), which a later
job can import.

- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): a unit embedding, every norm gain (the four a
  layer and the final one) drawn off 1 so that a norm left out or moved
  shows, the gate's gain drawn so that z spreads over about +-2 and its
  bias so that no pass takes under a tenth of the mass (a gate near 0 or
  1 hides a wrong exit distribution);
- `correct`: the parameter count four ways (the leaves, `jax.eval_shape`
  of the init, `TransformerConfig.num_params`,
  `flops_looped.total_params`, and the published depth's against the
  row's 2,667,974,657); of the timed path's own program against
  `reference/ouro_f32.py` on one sequence of the step's length: the
  logits of EVERY pass, the gate's z and the exit distribution, the
  step-0 loss, and, as float32 functions of the system's own forward
  pass, the objective's value and the gradient of the gate's 2,049
  parameters (through `Transformer.loss` and the head's weights'
  cotangent; the reference needs no backward pass for it:
  `against_the_reference`); the attention kernels in the compiled
  step; every step's loss and `loop_*` metrics finite and `loop_exit_mass`
  summing to 1 within 1e-4; the loss lower at the end; no compile inside
  the window;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks, are
  refused before the cluster starts.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Dict, List

from benchlib.spec import load_module

_train_lm = load_module("jobs", "train_lm")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
NEEDS = ("loops", "exit_gate", "exit_entropy_coeff", "norm_placement")
LOOP_FORM = "scan"   # `Transformer._looped_hidden`: one lax.scan over passes
MASS_SUMS_TO_ONE = 1e-4


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms the program does not run, and readings of the row that
    this job does not take, are refused, not silently ignored."""
    lacking = {
        "hidden_act": ("silu", "another MLP than the SiLU-gated one"),
        "tie_word_embeddings": (False, "tied embeddings in this job"),
        "rope_scaling": (None, "scaled RoPE in this job"),
        "use_sliding_window": (False, "window under a looped stack"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("this family's attention has one key/value head "
                         "a query head")
    if set(model["layer_types"]) != {"full_attention"}:
        raise ValueError(f"layer_types {sorted(set(model['layer_types']))}")
    if model["total_ut_steps"] < 2:
        raise ValueError("a looped decoder runs its stack twice or more")
    if model["head_dim"] * model["num_attention_heads"] \
            != model["hidden_size"]:
        raise ValueError("the program derives head_dim from d_model")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run a stack of layers several times through the same weights "
            f"under a sandwich norm with an exit gate "
            f"({ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    for key in ("packed_documents", "segment_masks"):
        if ctx["traffic"].get(key):   # one document a sequence, one mask
            raise ValueError(f"traffic.{key}: the causal mask and the exit "
                             f"distribution run over a whole sequence")
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig:
    `total_ut_steps` is `loops`, the sandwich norm `norm_placement`
    "both", the gate and the entropy's coefficient the file's `assumed`
    ones."""
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], max_seq_len=seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], tie_embeddings=False,
        loops=model["total_ut_steps"], exit_gate=True,
        exit_entropy_coeff=model["exit_entropy_coeff"],
        norm_placement="both",
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the family's layout
    (`y = x W^T`, one dict per layer) the reference takes: `train_lm`'s
    mapping of the dense layer, then the two norms on the sublayers'
    outputs and the gate."""
    out = _train_lm.to_reference_layout(params, cfg)
    lay = params["layers"]
    for i, lw in enumerate(out["layers"]):
        lw["input_layernorm_2"] = lay["attn_post_norm"][i]
        lw["post_attention_layernorm_2"] = lay["mlp_post_norm"][i]
    out["early_exit_gate"] = {"weight": params["exit_gate"][None, :],
                              "bias": params["exit_gate_bias"]}
    return out


GAINS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms (the
    configuration's `assumed.initializer` has the readings):

    - the embedding redrawn at `embed_std` (a unit embedding: at the
      program's 0.02 the first pass's first norm would divide by eps);
    - every norm gain, the four of a layer and the final one, drawn
      around 1 with `norm_gain_std` (a gain of exactly 1 hides a norm
      left out or moved);
    - the gate: its gain drawn N(0, gate_z_std^2 / d_model), so that z
      spreads with about `gate_z_std` over a normed hidden state, and its
      bias `gate_bias` (a gate near 0 or 1 hides a wrong exit
      distribution).
    """
    import jax

    from ray_tpu.models import Transformer

    def around_one(k, gain):
        return (gain + init["norm_gain_std"] * jax.random.normal(
            k, gain.shape)).astype(gain.dtype)

    params = Transformer.init(key, cfg)
    embed, gate = params["embed"], params["exit_gate"]
    params["embed"] = (init["embed_std"] * jax.random.normal(
        jax.random.fold_in(key, 27), embed.shape)).astype(embed.dtype)
    for n, name in enumerate(GAINS):
        params["layers"][name] = around_one(
            jax.random.fold_in(key, 6300 + n), params["layers"][name])
    params["final_norm"] = around_one(jax.random.fold_in(key, 6310),
                                      params["final_norm"])
    params["exit_gate"] = (
        init["gate_z_std"] * cfg.d_model ** -0.5 * jax.random.normal(
            jax.random.fold_in(key, 6311), gate.shape)).astype(gate.dtype)
    params["exit_gate_bias"] = params["exit_gate_bias"] + init["gate_bias"]
    return params


def rel_l2(got, want) -> float:
    """|got - want| / |want| over whole arrays, in float32."""
    import jax.numpy as jnp
    diff = got.astype(jnp.float32) - want
    return float(jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(want * want)))


def against_the_reference(reference, params, cfg, model, mesh, sample,
                          checks) -> Dict[str, Any]:
    """The timed path's own program against `reference/ouro_f32.py` on the
    reference sample [1, T + 1]. Two kinds of comparison:

    - the forward pass in bf16 against the reference's in float32: every
      pass's logits, the gate's z and the exit distribution, the loss (a
      pass's logits live one pass at a time: 1.6 GB each side at 8,192 x
      49,152);
    - the objective's own arithmetic, which bf16's rounding in the forward
      pass would hide (the passes' cross-entropies lie within 0.2 of each
      other on stand-in weights, so a wrong weighting moves the loss by
      less than the forward's rounding does): the reference's `exit_loss`
      and `gate_gradient` on the SYSTEM's z, hidden states and per-token
      cross-entropies against `Transformer.loss` and its gradient by the
      gate's 2,049 parameters, float32 functions of the same inputs. The
      gradient goes through the head's weights' cotangent; the reference
      needs no backward pass for it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer, head

    tol, beta = model["tolerance"], model["exit_entropy_coeff"]
    f32 = jnp.float32
    sample_dev = jnp.asarray(sample)
    tokens, targets = sample_dev[:, :-1], sample_dev[:, 1:]
    hs, _, _, z = jax.jit(lambda p, x: Transformer.hidden(
        p, x, cfg, mesh=mesh, with_aux=True))(params, tokens)
    p = jnp.exp(Transformer.exit_log_probs(z))

    def loss_of_gate(gate, rest, batch):
        return Transformer.loss({**rest, **gate}, batch, cfg, mesh=mesh,
                                with_metrics=True)

    def flat(grad, names):
        return jnp.concatenate([grad[n].reshape(-1) for n in names])

    gate = {k: params[k] for k in ("exit_gate", "exit_gate_bias")}
    rest = {k: v for k, v in params.items() if k not in gate}
    (sys_loss, metrics), gate_grad = jax.jit(jax.value_and_grad(
        loss_of_gate, has_aux=True))(gate, rest, {"tokens": sample_dev})
    sys_loss = float(sys_loss)
    gate_grad = flat(gate_grad, ("exit_gate", "exit_gate_bias"))

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loops compile one layer's ops and one block of queries once
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_hs, ref_zs, ref_xs = reference.passes(weights, tokens, model)
    project = jax.jit(lambda p, x: head.logits(p, x, cfg, mesh=mesh))
    logits_rel_l2, ref_nll, sys_nll = [], [], []
    for t, ref_h in enumerate(ref_hs):
        ref_logits = reference.logits_of(weights, ref_h)
        sys_logits = project(params, hs[t])
        logits_rel_l2.append(rel_l2(sys_logits, ref_logits))
        ref_nll.append(reference.token_nll(ref_logits, targets))
        sys_nll.append(reference.token_nll(sys_logits.astype(f32), targets))
        del ref_logits, sys_logits
    ref_log_p = reference.exit_distribution(ref_zs)
    ref_loss = float(reference.exit_loss(ref_log_p, ref_nll, beta))
    # the reference's objective and its gate gradient on the system's own
    # forward pass, and (reported, not judged) on the reference's
    sys_hs = [h.astype(f32) for h in hs]
    objective = float(reference.exit_loss(
        reference.exit_distribution(list(z)), sys_nll, beta))
    names = ("weight", "bias")
    want_grad = flat(reference.gate_gradient(
        weights, sys_hs, sys_hs, sys_nll, model), names)
    ref_grad = flat(reference.gate_gradient(
        weights, ref_hs, ref_xs, ref_nll, model), names)
    del weights, ref_xs, sys_hs

    ref_z, ref_p = jnp.stack(ref_zs), jnp.exp(jnp.stack(ref_log_p))
    z_rel = rel_l2(z, ref_z)
    p_abs = float(jnp.max(jnp.abs(p - ref_p)))
    mass = [float(m) for m in jnp.mean(ref_p, axis=(1, 2))]
    loss_diff = abs(sys_loss - ref_loss)
    objective_diff = abs(sys_loss - objective)
    grad_rel = rel_l2(gate_grad, want_grad)
    checks.add("reference_logits",
               max(logits_rel_l2) <= tol["logits_rel_l2"],
               {"rel_l2_by_pass": logits_rel_l2,
                "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_gate", z_rel <= tol["gate_z_rel_l2"]
               and p_abs <= tol["exit_prob_abs"],
               {"z_rel_l2": z_rel, "allowed": tol["gate_z_rel_l2"],
                "exit_prob_abs": p_abs,
                "allowed_prob": tol["exit_prob_abs"],
                "reference_exit_mass": mass})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": sys_loss, "reference": ref_loss,
                "allowed": tol["loss_abs"]})
    checks.add("reference_objective", objective_diff <= tol["objective_abs"],
               {"system": sys_loss, "reference_on_the_systems_forward":
                objective, "allowed": tol["objective_abs"]})
    checks.add("reference_gate_gradient",
               grad_rel <= tol["gate_grad_rel_l2"],
               {"rel_l2": grad_rel, "allowed": tol["gate_grad_rel_l2"],
                "system_norm": float(jnp.linalg.norm(gate_grad))})
    return {"reference_rel_l2": max(logits_rel_l2),
            "reference_rel_l2_by_pass": logits_rel_l2,
            "reference_z_rel_l2": z_rel, "reference_exit_prob_abs": p_abs,
            "reference_loss_diff": loss_diff,
            "reference_objective_diff": objective_diff,
            "reference_gate_grad_rel_l2": grad_rel,
            "end_to_end_gate_grad_rel_l2": rel_l2(gate_grad, ref_grad),
            "reference_exit_mass": mass,
            "sample_loop_pass_nll": [
                float(x) for x in metrics["loop_pass_nll"]]}


class StepLoop:
    """The loop's body, the same in warm-up, in the window and under the
    trace. A step's metrics are read, checked and reported while the NEXT
    step runs (every step still is, one step later), so the device does
    not wait for the host between steps; `drain` reads the last one
    (PERF.md section 6, PR 50: a read before the dispatch measures the
    host)."""

    def __init__(self, train_step, state, put, report_every: int):
        self.train_step, self.state, self.put = train_step, state, put
        self.report_every = report_every
        self.dispatched = 0
        self.in_flight: List[Any] = []   # the steps' metrics not read yet
        self.read_steps: List[Dict[str, Any]] = []

    def one_step(self, report: bool = True) -> None:
        import jax

        with jax.profiler.TraceAnnotation("make_batch"):
            batch = self.put(self.dispatched)
        with jax.profiler.TraceAnnotation("dispatch"):
            self.state, metrics = self.train_step(self.state, batch)
        self.dispatched += 1
        self.in_flight.append(metrics)
        if len(self.in_flight) > 1:
            self.read(self.in_flight.pop(0), report)

    def drain(self, report: bool = True) -> None:
        """Read what is still in flight: the device is then idle."""
        while self.in_flight:
            self.read(self.in_flight.pop(0), report)

    def read(self, metrics, report: bool) -> None:
        import jax

        import ray_tpu.train as train

        with jax.profiler.TraceAnnotation("report"):
            got = jax.device_get({k: metrics[k] for k in (
                "loss", "loop_exit_mass", "loop_exit_entropy",
                "loop_pass_nll")})     # the host read
            step = {"loss": float(got["loss"]),
                    "exit_mass": [float(x) for x in got["loop_exit_mass"]],
                    "exit_entropy": float(got["loop_exit_entropy"]),
                    "pass_nll": [float(x) for x in got["loop_pass_nll"]]}
            self.read_steps.append(step)
            n = len(self.read_steps)
            if report and n % self.report_every == 0:
                train.report({"step": n, "loss": step["loss"],
                              "loop_exit_mass": step["exit_mass"],
                              "loop_exit_entropy": step["exit_entropy"],
                              "loop_pass_nll": step["pass_nll"]})


def step_is_sound(step: Dict[str, Any]) -> bool:
    """Everything finite, and the exit distribution's mean mass a
    distribution."""
    numbers = [step["loss"], step["exit_entropy"]] + step["exit_mass"] \
        + step["pass_nll"]
    return all(math.isfinite(x) for x in numbers) \
        and abs(sum(step["exit_mass"]) - 1.0) <= MASS_SUMS_TO_ONE


def worker_loop(config: Dict[str, Any]) -> None:
    entered_at = time.time()
    phases: Dict[str, float] = {}
    clock = time.perf_counter

    def phase(name: str, since: float) -> float:
        now = clock()
        phases[name] = now - since
        return now

    import jax
    import optax

    import ray_tpu.train as train
    from benchlib import device as bdev
    from benchlib import flops_looped
    from benchlib.checks import Checks, attention_as_expected, kernel_calls
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import logical_sharding
    from ray_tpu.parallel.train_step import make_train_step

    compiles = bdev.count_compiles()

    t = clock()
    cell, model = config["cell"], config["config"]
    mix, tr_cfg = config["traffic"], config["config"]["train"]
    rehearsal = bool(model.get("rehearsal"))
    device = bdev.require_device(cell["chips"], rehearsal)
    devices = jax.devices()
    peaks = peaks_for(device["kind"]) if not rehearsal else None
    checks = Checks()

    batches = TokenBatches(mix, model["vocab_size"], config["seed"])
    seq = batches.tokens
    cfg = transformer_config(model, tr_cfg, seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]))
    batch_devices = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)

    # ---- weights from the seed, on the device, into their shardings --
    specs = Transformer.param_specs(cfg)
    key = jax.random.key(config["seed"])
    shapes = jax.eval_shape(lambda k: init_params(k, cfg, model["init"]),
                            key)
    shardings = jax.tree.map(
        lambda spec, leaf: logical_sharding(spec, mesh, shape=leaf.shape),
        specs, shapes, is_leaf=lambda x: isinstance(x, tuple))
    params = jax.jit(lambda k: init_params(k, cfg, model["init"]),
                     out_shardings=shardings)(key)
    jax.block_until_ready(params)
    shaped_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    published = flops_looped.published_params(model)
    checks.add("param_count",
               n_params == flops_looped.total_params(model)
               == cfg.num_params == shaped_params
               and published == model["published_params"],
               [n_params, flops_looped.total_params(model), cfg.num_params,
                shaped_params, published, model["published_params"]])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory ----------------------------------------
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample = batches.reference_sample(sample_cfg["sequences"],
                                      sample_cfg["tokens"])
    compared = against_the_reference(reference, params, cfg, model, mesh,
                                     sample, checks)
    t = phase("reference_check_s", t)

    # ---- the step -------------------------------------------------
    opt = tr_cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True), specs, mesh,
        optimizer=optax.adamw(opt["learning_rate"],
                              weight_decay=opt["weight_decay"]))
    state = init_state(params)
    del params
    batch_sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), None))

    def put(step: int):
        return {"tokens": jax.device_put(batches.batch(step),
                                         batch_sharding)}

    compiled = train_step.lower(state, put(0)).compile()
    ma = compiled.memory_analysis()
    memory_analysis = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")} if ma is not None else {}
    hlo = compiled.as_text()
    del compiled
    attn_calls = kernel_calls(hlo, model.get("kernels", {}).get("attn", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls})
    t = phase("compile_step_s", t)

    loop = StepLoop(train_step, state, put, int(mix["report_every"]))
    del state

    # ---- warm-up: every shape the window uses ---------------------
    for _ in range(int(mix["warmup_steps"])):
        loop.one_step(report=False)
    loop.drain(report=False)
    train.report({"step": len(loop.read_steps),
                  "loss": loop.read_steps[-1]["loss"], "warmup": True})
    t = phase("warmup_s", t)
    compiles_before = len(compiles)

    # ---- the measured window --------------------------------------
    step_s: List[float] = []
    window_started_at = time.time()
    t0 = clock()
    last = t0
    while last - t0 < config["seconds"]:
        loop.one_step()
        now = clock()
        step_s.append(now - last)
        last = now
    loop.drain()   # every step dispatched in the window ends inside it
    window_s = clock() - t0
    window_compiles = len(compiles) - compiles_before
    tokens_per_s = len(step_s) * batches.tokens_per_step / window_s

    # ---- a few traced steps, after the window ---------------------
    reduced = None
    if config["trace"]:
        def traced_steps():
            for _ in range(int(mix["trace_steps"])):
                loop.one_step()
            loop.drain()
        reduced = bdev.trace_window(
            os.path.join(config["scratch_dir"], "trace"), traced_steps,
            HOST_ANNOTATIONS, model.get("kernels"))

    # ---- checks on the run ----------------------------------------
    steps = loop.read_steps
    losses = [s["loss"] for s in steps]
    sound = [step_is_sound(s) for s in steps]
    checks.add("steps_sound", all(sound),
               {"steps": len(steps), "unsound": sound.count(False),
                "mass_sums_to_one_within": MASS_SUMS_TO_ONE,
                "first_unsound": next(
                    (s for s, ok in zip(steps, sound) if not ok), None)})
    checks.add("loss_fell", losses[-1] < losses[0],
               {"first": losses[0], "last": losses[-1],
                "unigram_entropy_nats": batches.unigram_entropy_nats})
    checks.add("no_compile_in_window", window_compiles == 0,
               {"compiles_in_window": window_compiles,
                "compiles_in_setup": compiles_before})
    checks.add("steps_in_window", len(step_s) >= 3, len(step_s))

    bdev.finish_device(device, reduced)
    tokens_here = batches.tokens_per_step // batch_devices
    record = {
        "device": device,
        "correct": checks.all_ok,
        "checks": dict(checks),
        "attempted": len(steps),
        "failed": sound.count(False),
        "window_started_at": window_started_at,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "clock": {
            "gang_start_s": entered_at - config["fit_called_at"],
            "setup_phases_s": phases,
            "step_s": step_s,
            "window_s": window_s,
            "tokens_per_step": batches.tokens_per_step,
        },
        "static": {
            "model": {k: v for k, v in model.items()
                      if isinstance(v, (int, float, bool))},
            "chips": len(devices),
            "peaks": peaks,
            "flops_per_token": flops_looped.train_flops_per_token(
                model, seq),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "attention_call": flops_looped.attention_call(
                model, batches.sequences // batch_devices, seq),
            "loop_call": flops_looped.loop_call(model, tokens_here,
                                                LOOP_FORM),
        },
        "counters": dict(
            compared, losses_first_last=[losses[0], losses[-1]],
            loop_exit_mass_first_last=[steps[0]["exit_mass"],
                                       steps[-1]["exit_mass"]],
            loop_exit_entropy_first_last=[steps[0]["exit_entropy"],
                                          steps[-1]["exit_entropy"]],
            loop_pass_nll_first_last=[steps[0]["pass_nll"],
                                      steps[-1]["pass_nll"]]),
        "trace": reduced,
    }
    train.report(record)
