"""Job kind `train_lm_mhc_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a latent-attention decoder with shared and routed
experts behind leading dense layers whose residual path is several streams
under manifold-constrained hyper-connections (the catalog row
`Xing4.0-29B-A4B`, `model_type: xing4_0`), of which this chip holds a
share: some of each layer's experts, some of its attention heads and a
slice of the vocabulary (the configuration file's `share`).

The driver side, the window, the clock and the record's keys are
`train_lm`'s; the stand-in weights, the balanced choice bias, the
reference layout of everything but the streams and the held load are
`train_lm_mla_moe`'s module-level functions and the loop is
`train_lm_looped.StepLoop` (a step's metrics read while the next step
runs), each imported and extended, none copied. What this job adds:

- the config mapping: `hc_mult` -> `residual_streams`, the Sinkhorn
  rounds, its epsilon and the clamp; `rope_scaling` (YaRN) with the
  factor on the whole softmax scale (`rope_yarn_mscale_all_dim`) and the
  rotary tables unscaled; the held heads;
- the stand-in hyper-connections (`init_params`): alpha near 1, phi at
  N(0, 1/sqrt(n*C)) times a gain, b drawn, and every sublayer norm's gain
  and the final one drawn off 1, so that a map's dynamic part, a Sinkhorn
  or a norm left out shows;
- `correct` adds, of the timed path's own program against
  `reference/xing4_f32.py` given the same share on one sequence of the
  step's length: the THREE MAPS of every sublayer (`[sublayers, T, n*n +
  2n]` f32 from the same forward pass as the logits) and
  `mhc_res_marginal_err`; every step's `mhc_*` metrics finite and the
  marginal error under its limit;
- `static.mhc_call` (streams, sublayers, tokens, rounds, the bytes a step
  by `benchlib.flops_mhc_moe.mhc_bytes`) for the per-layer metrics;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks (a
  multi-token prediction module, group-limited routing, another scaling
  than YaRN with mscale = mscale_all_dim), are refused before the cluster
  starts, as are packed documents and segment masks.
"""

from __future__ import annotations

import dataclasses
import math
import os
import statistics
import time
from typing import Any, Dict, List

from benchlib.spec import load_module

_train_lm = load_module("jobs", "train_lm")
_mla_moe = load_module("jobs", "train_lm_mla_moe")
_looped = load_module("jobs", "train_lm_looped")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
NEEDS = _mla_moe.NEEDS + (
    "residual_streams", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp",
    "rope_yarn_factor", "rope_yarn_mscale_all_dim")
balance_held_share = _mla_moe.balance_held_share
held_load = _mla_moe.held_load
rel_l2 = _looped.rel_l2
SUBLAYERS = ("attn", "mlp")
GAINS = ("attn_norm", "mlp_norm")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run, and
    readings of the row that this job does not take, are refused, not
    silently ignored."""
    lacking = {
        "num_nextn_predict_layers": (0, "a multi-token prediction module"),
        "n_group": (1, "group-limited routing in this job"),
        "topk_group": (1, "group-limited routing in this job"),
        "topk_method": ("noaux_tc", "another choice than noaux_tc"),
        "scoring_func": ("sigmoid", "another router than the sigmoid one "
                                    "in this job"),
        "hidden_act": ("silu", "another activation than silu"),
        "attention_bias": (False, "biases in the projections"),
        "moe_layer_freq": (1, "dense layers between the expert layers"),
        "tie_word_embeddings": (False, "tied embeddings in this job"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention has one key/value head per "
                         "query head")
    if model["hc_mult"] < 2:
        raise ValueError("hc_mult below 2 is train_lm_mla_moe's model")
    if -model["mhc_h_res_clamp_min"] != model["mhc_h_res_clamp_max"]:
        raise ValueError("the program clamps H_res's logits symmetrically")
    scaling = model.get("rope_scaling")
    if not scaling or scaling.get("type") != "yarn" \
            or scaling["mscale"] != scaling["mscale_all_dim"]:
        raise ValueError(
            f"rope_scaling = {scaling!r}: this job runs YaRN with mscale = "
            f"mscale_all_dim (the rotary tables unscaled, the factor on "
            f"the softmax scale)")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run a residual path of several streams "
            f"({ctx['config'].get('model_type')!r}, {ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    for key in ("packed_documents", "segment_masks"):
        if ctx["traffic"].get(key):   # one document a sequence, one mask
            raise ValueError(f"traffic.{key}: the causal mask runs over a "
                             f"whole sequence")
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig:
    `train_lm_mla_moe`'s mapping of everything GLM-4.7-Flash shares in
    kind, then the streams and YaRN. The top-level `num_attention_heads`,
    `n_routed_experts` and `vocab_size` are what this chip holds."""
    refuse_what_the_program_lacks(model)
    scaling = model["rope_scaling"]
    glm_like = dict(model, rope_scaling=None)   # its mapping knows no YaRN
    return _mla_moe.transformer_config(glm_like, train, seq).replace(
        residual_streams=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"],
        hc_eps=model["hc_eps"],
        hc_res_clamp=float(model["mhc_h_res_clamp_max"]),
        rope_yarn_factor=float(scaling["factor"]),
        rope_yarn_original_len=scaling["original_max_position_embeddings"],
        rope_yarn_beta_fast=float(scaling["beta_fast"]),
        rope_yarn_beta_slow=float(scaling["beta_slow"]),
        # mscale / mscale_all_dim on the tables, mscale_all_dim on the scale
        rope_yarn_attention_factor=1.0,
        rope_yarn_mscale_all_dim=float(scaling["mscale_all_dim"]))


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout the
    reference takes: `train_lm_mla_moe`'s mapping, then each sublayer's
    hyper-connections (`phi` as `y = x W^T`)."""
    out = _mla_moe.to_reference_layout(params, cfg)
    runs = [(params.get("dense_layers"), cfg.moe_dense_layers),
            (params["layers"], cfg.n_layers - cfg.moe_dense_layers)]
    at = 0
    for lay, count in runs:
        for i in range(count):
            for name in SUBLAYERS:
                out["layers"][at][name + "_hc"] = {
                    "phi": lay[name + "_hc_phi"][i].T,
                    "b": lay[name + "_hc_b"][i],
                    "alpha": lay[name + "_hc_alpha"][i]}
            at += 1
    return out


def init_params(key, cfg, init: Dict[str, Any]):
    """`train_lm_mla_moe.init_params` (a unit embedding, the latents' gains,
    the choice bias) as the stand-in for trained weights, with what the
    comparison needs to see the streams (the configuration's
    `assumed.initializer`):

    - every sublayer norm's gain and the final one drawn around 1 with
      `norm_gain_std` (a gain of exactly 1 hides a norm left out);
    - the hyper-connections: every alpha `hc_alpha`, phi redrawn at
      `hc_phi_gain` / sqrt(n*C) (u has RMS 1, so m spreads with about
      `hc_phi_gain`), b drawn N(0, `hc_bias_std`), H_res's part of both
      times `hc_res_spread`: maps that move with the token and an H_res
      far from the identity that 20 rounds still make doubly stochastic.
      `Transformer.init`'s own start (alpha 0.01, H_res near the
      identity) would hide a dynamic part left out, a Sinkhorn left out
      and a transposed H_res alike.
    """
    import jax
    import jax.numpy as jnp

    def normal(k, like, std, mean=0.0):
        return (mean + std * jax.random.normal(k, like.shape)).astype(
            like.dtype)

    params = _mla_moe.init_params(key, cfg, init)
    n = cfg.residual_streams
    n_d = n * cfg.d_model
    # H_res's logits at `hc_res_spread` of the other two maps': 20 rounds
    # bring logits of spread 0.7 within 2e-5 of doubly stochastic and
    # logits of spread 1.4 within 3e-2 only
    spread = jnp.where(jnp.arange(cfg.hc_maps) < 2 * n, 1.0,
                       init["hc_res_spread"])
    for r, run in enumerate(("dense_layers", "layers")):
        lay = params.get(run)
        if lay is None:
            continue
        for s, name in enumerate(SUBLAYERS):
            k = jax.random.fold_in(key, 6600 + 10 * r + s)
            lay[GAINS[s]] = normal(jax.random.fold_in(k, 0), lay[GAINS[s]],
                                   init["norm_gain_std"], 1.0)
            lay[name + "_hc_phi"] = normal(
                jax.random.fold_in(k, 1), lay[name + "_hc_phi"],
                init["hc_phi_gain"] * n_d ** -0.5) * spread
            lay[name + "_hc_b"] = normal(
                jax.random.fold_in(k, 2), lay[name + "_hc_b"],
                init["hc_bias_std"]) * spread
            lay[name + "_hc_alpha"] = jnp.full_like(
                lay[name + "_hc_alpha"], init["hc_alpha"])
    params["final_norm"] = normal(jax.random.fold_in(key, 6690),
                                  params["final_norm"],
                                  init["norm_gain_std"], 1.0)
    return params


def judged_sublayers(cfg) -> int:
    """The sublayers whose maps `correct` judges: those of the leading
    dense layers and the first expert layer, 2 x first_k_dense_replace +
    2; what the first experts add reaches the maps after them."""
    return 2 * cfg.moe_dense_layers + 2


def system_forward(params, tokens, cfg, mesh):
    """The program's logits [B, T, V] f32 and every sublayer's maps
    [sublayers, B, T, n*n + 2n] f32 (attention's before the MLP's or the
    experts', layer by layer: the reference's order), from ONE forward
    pass."""
    from ray_tpu.models import Transformer, head

    hidden, _, _, streams = Transformer.hidden(
        params, tokens, cfg, mesh=mesh, with_aux=True)
    maps = streams["maps"]          # [layers, 2, B, T, n*n + 2n]
    return (head.logits(params, hidden, cfg, mesh=mesh),
            maps.reshape((-1,) + maps.shape[2:]))


def against_the_reference(reference, params, cfg, model, mesh, sample,
                          checks) -> Dict[str, Any]:
    """The timed path's own program against `reference/xing4_f32.py` on
    the reference sample [1, T + 1], the same share given to both: the
    logits, the step-0 loss, the three maps of every sublayer and the
    marginal error of H_res, and that the sample reached every held
    expert."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import Transformer

    tol = model["tolerance"]
    sample_dev = jnp.asarray(sample)
    tokens, targets = sample_dev[:, :-1], sample_dev[:, 1:]
    sys_logits, sys_maps = jax.jit(lambda p, x: system_forward(
        p, x, cfg, mesh))(params, tokens)
    sys_loss, sys_metrics = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, with_metrics=True))(
            params, {"tokens": sample_dev})

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loop over the experts compiles one expert once
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits, chosen, ref_maps = reference.forward(
        weights, tokens, model, with_routing=True, with_maps=True)
    ref_loss = reference.next_token_loss(ref_logits, targets)
    n_experts, held = cfg.moe_experts, cfg.held_experts
    first = cfg.moe_expert_offset
    ref_counts = np.asarray(reference.tokens_per_expert(chosen, n_experts))
    del weights, chosen

    logits_rel = rel_l2(sys_logits, ref_logits)
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    # by sublayer, relative L2. JUDGED: the sublayers up to the first
    # expert sublayer, whose maps the rounded routing has not touched (a
    # near-tie that bf16 moves to another expert adds a held expert's
    # whole output to a token's stream or takes it away, and every later
    # map of that token moves with it: the later sublayers are reported)
    n = cfg.residual_streams
    judged = judged_sublayers(cfg)
    diff = sys_maps - ref_maps

    def rel(lo, hi, axes, upto=None):
        return jnp.sqrt(jnp.sum(diff[:upto, ..., lo:hi] ** 2, axis=axes)
                        / jnp.sum(ref_maps[:upto, ..., lo:hi] ** 2,
                                  axis=axes))

    by_map = {"h_pre": float(rel(0, n, None, judged)),
              "h_post": float(rel(n, 2 * n, None, judged)),
              "h_res": float(rel(2 * n, None, None, judged))}
    by_sublayer = [float(x) for x in rel(0, None, (1, 2, 3))]
    worst = max(by_sublayer[:judged])
    maps_abs = float(jnp.max(jnp.abs(diff)))
    marginal = float(sys_metrics["mhc_res_marginal_err"])
    sample_counts = np.asarray(sys_metrics["moe_tokens_per_expert"])
    checks.add("reference_logits", logits_rel <= tol["logits_rel_l2"],
               {"rel_l2": logits_rel, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": float(ref_loss),
                "allowed": tol["loss_abs"]})
    checks.add("reference_maps", worst <= tol["maps_rel_l2"],
               {"rel_l2_by_sublayer": by_sublayer,
                "allowed": tol["maps_rel_l2"], "judged_sublayers": judged,
                "rel_l2_by_map": by_map,
                "max_abs": maps_abs, "maps": list(sys_maps.shape)})
    checks.add("mhc_marginals", marginal <= tol["marginal_err"],
               {"mhc_res_marginal_err": marginal,
                "allowed": tol["marginal_err"],
                "rounds": cfg.hc_sinkhorn_iters})
    moved = int(np.abs(sample_counts
                       - ref_counts[:, first:first + held]).sum())
    checks.add("reference_sample_reaches_every_held_expert",
               int((sample_counts == 0).sum()) == 0,
               {"held_counts": sample_counts.tolist(),
                "count_differences_against_reference": moved})
    return {"reference_rel_l2": logits_rel,
            "reference_loss_diff": loss_diff,
            "reference_maps_rel_l2": worst,
            "reference_maps_rel_l2_by_sublayer": by_sublayer,
            "reference_maps_rel_l2_by_map": by_map,
            "reference_maps_max_abs": maps_abs,
            "sample_mhc_res_marginal_err": marginal,
            "sample_mhc_stream_gain": float(
                sys_metrics["mhc_stream_gain"])}


class StepLoop(_looped.StepLoop):
    """`train_lm_looped.StepLoop` (a step's metrics read, checked and
    reported while the next step runs) reading this job's metrics: the
    loss, the routing counters and the two `mhc_*` readings, in one host
    read."""

    def __init__(self, train_step, state, put, report_every: int,
                 slots_a_layer: int, expert_layers: int):
        super().__init__(train_step, state, put, report_every)
        self.slots_a_layer = slots_a_layer
        self.expert_layers = expert_layers

    def read(self, metrics, report: bool) -> None:
        import jax

        import ray_tpu.train as train

        with jax.profiler.TraceAnnotation("report"):
            got = jax.device_get({k: metrics[k] for k in (
                "loss", "moe_tokens_per_expert", "moe_slots_elsewhere",
                "moe_dropped", "mhc_res_marginal_err",
                "mhc_stream_gain")})     # the host read
            per_layer = got["moe_tokens_per_expert"].sum(axis=-1)
            step = {
                "loss": float(got["loss"]),
                "load": held_load(got["moe_tokens_per_expert"]),
                "held_slots": [int(x) for x in per_layer],
                "share": 100.0 * float(per_layer.sum()) / (
                    self.expert_layers * self.slots_a_layer),
                "dropped": int(got["moe_dropped"]),
                "elsewhere": int(got["moe_slots_elsewhere"].sum()),
                "miscounted": bool(
                    (per_layer + got["moe_slots_elsewhere"]
                     != self.slots_a_layer).any()),
                "marginal_err": float(got["mhc_res_marginal_err"]),
                "stream_gain": float(got["mhc_stream_gain"])}
            self.read_steps.append(step)
            n = len(self.read_steps)
            if report and n % self.report_every == 0:
                train.report({"step": n, "loss": step["loss"],
                              "held_expert_load_max_over_mean": step["load"],
                              "held_slots_share": step["share"],
                              "mhc_res_marginal_err": step["marginal_err"],
                              "mhc_stream_gain": step["stream_gain"]})


def step_is_sound(step: Dict[str, Any], marginal_limit: float) -> bool:
    """Everything finite, and H_res doubly stochastic within its limit."""
    return all(math.isfinite(step[k]) for k in (
        "loss", "marginal_err", "stream_gain")) \
        and step["marginal_err"] <= marginal_limit


def worker_loop(config: Dict[str, Any]) -> None:
    entered_at = time.time()
    phases: Dict[str, float] = {}
    clock = time.perf_counter

    def phase(name: str, since: float) -> float:
        now = clock()
        phases[name] = now - since
        return now

    import jax
    import numpy as np
    import optax

    import ray_tpu.train as train
    from benchlib import device as bdev
    from benchlib import flops_mhc_moe
    from benchlib.checks import (Checks, attention_as_expected,
                                 grouped_matmul_as_expected, kernel_calls)
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer
    from ray_tpu.ops.moe import grouped_matmul_impl
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

    # a sliced vocabulary is a smaller vocabulary: ids from the slice
    batches = TokenBatches(mix, model["vocab_size"], config["seed"])
    seq = batches.tokens
    cfg = transformer_config(model, tr_cfg, seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]))
    batch_devices = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)
    n_experts, held, top_k = cfg.moe_experts, cfg.held_experts, cfg.moe_top_k
    expert_layers = cfg.n_layers - cfg.moe_dense_layers
    slots_per_step = batches.tokens_per_step * top_k
    tol = model["tolerance"]

    # ---- weights from the seed, on the device, into their shardings --
    specs = Transformer.param_specs(cfg)
    frozen = Transformer.frozen(cfg)        # the choice bias: a buffer
    key = jax.random.key(config["seed"])
    shapes = jax.eval_shape(lambda k: init_params(k, cfg, model["init"]),
                            key)
    shardings = jax.tree.map(
        lambda spec, leaf: logical_sharding(spec, mesh, shape=leaf.shape),
        specs, shapes, is_leaf=lambda x: isinstance(x, tuple))
    params = jax.jit(lambda k: init_params(k, cfg, model["init"]),
                     out_shardings=shardings)(key)
    params, balanced = balance_held_share(params, cfg, mesh, batches,
                                          model["init"])
    jax.block_until_ready(params)
    # parameters: what is trained; the choice bias is a buffer
    n_params = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    published = flops_mhc_moe.published_params(model)
    checks.add("param_count",
               n_params == flops_mhc_moe.total_params(model)
               == cfg.num_params
               and published == model["published_params"],
               [n_params, flops_mhc_moe.total_params(model), cfg.num_params,
                published, model["published_params"]])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    bias_before = np.asarray(params["layers"]["router_bias"])
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
                              weight_decay=opt["weight_decay"]),
        frozen=frozen)
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
    kernels = model.get("kernels", {})
    attn_calls = kernel_calls(hlo, kernels.get("attn", {}))
    moe_calls = kernel_calls(hlo, kernels.get("moe", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls})
    gmm_impl = grouped_matmul_impl(
        mesh, slots_per_step // batch_devices, cfg.d_model, cfg.ff_dim)
    want_gmm = tr_cfg["expect_grouped_matmul"]
    checks.add("grouped_matmul_impl", grouped_matmul_as_expected(
        gmm_impl, want_gmm, moe_calls),
        {"resolved": gmm_impl, "expected": want_gmm, "calls": moe_calls})
    t = phase("compile_step_s", t)

    loop = StepLoop(train_step, state, put, int(mix["report_every"]),
                    slots_per_step, expert_layers)
    del state

    # ---- warm-up: every shape the window uses ---------------------
    for _ in range(int(mix["warmup_steps"])):
        loop.one_step(report=False)
    loop.drain(report=False)
    train.report({"step": len(loop.read_steps),
                  "loss": loop.read_steps[-1]["loss"], "warmup": True})
    t = phase("warmup_s", t)
    compiles_before = len(compiles)
    warmup_steps = len(loop.read_steps)

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
    window_steps = len(loop.read_steps)
    window = loop.read_steps[warmup_steps:window_steps]
    # the routed experts' FLOPs at the slots this chip computed
    routed_slots_per_token = sum(sum(s["held_slots"]) for s in window) / (
        len(step_s) * batches.tokens_per_step)

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
    sound = [step_is_sound(s, tol["marginal_err"]) for s in steps]
    marginal_max = max(s["marginal_err"] for s in steps)
    checks.add("steps_sound", all(sound),
               {"steps": len(steps), "unsound": sound.count(False),
                "marginal_err_allowed": tol["marginal_err"],
                "marginal_err_max": marginal_max,
                "first_unsound": next(
                    (s for s, ok in zip(steps, sound) if not ok), None)})
    checks.add("loss_fell", losses[-1] < losses[0],
               {"first": losses[0], "last": losses[-1],
                "unigram_entropy_nats": batches.unigram_entropy_nats})
    checks.add("no_compile_in_window", window_compiles == 0,
               {"compiles_in_window": window_compiles,
                "compiles_in_setup": compiles_before})
    checks.add("steps_in_window", len(step_s) >= 3, len(step_s))
    dropped_total = sum(s["dropped"] for s in steps)
    elsewhere_total = sum(s["elsewhere"] for s in steps)
    miscounted = sum(s["miscounted"] for s in steps)
    checks.add("no_token_dropped", dropped_total == 0,
               {"dropped_slots": dropped_total, "steps": len(steps)})
    checks.add("held_and_elsewhere_sum_to_slots", miscounted == 0,
               {"steps_off": miscounted, "slots_per_step": slots_per_step,
                "slots_elsewhere": elsewhere_total})
    bias_after = np.asarray(loop.state["params"]["layers"]["router_bias"])
    checks.add("router_bias_untrained",
               bias_before.tobytes() == bias_after.tobytes()
               and bool(bias_before.any()),
               {"max_abs_change": float(np.abs(
                   bias_after - bias_before).max())})
    window_shares = [s["share"] for s in window]
    if not rehearsal:   # a statement about the cell's traffic and widths
        even = 100.0 * held / n_experts
        band = model["share"]["held_slots_share_band"]
        median_share = statistics.median(window_shares)
        checks.add("held_share_in_band",
                   band[0] * even <= median_share <= band[1] * even,
                   {"held_slots_share_median": median_share,
                    "even_share": even, "band": band,
                    "first_last": [window_shares[0], window_shares[-1]]})

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
            "flops_per_token": flops_mhc_moe.train_flops_per_token(
                model, seq, routed_slots_per_token),
            "routed_slots_per_token": routed_slots_per_token,
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "grouped_matmul_impl": gmm_impl,
            "attention_call": flops_mhc_moe.attention_call(
                model, batches.sequences // batch_devices, seq),
            "mhc_call": flops_mhc_moe.mhc_call(
                model, tokens_here, tr_cfg["compute_dtype"],
                bool(tr_cfg["remat"])),
        },
        "counters": dict(
            compared, losses_first_last=[losses[0], losses[-1]],
            bias_balance=balanced,
            held_expert_load_max_over_mean=[s["load"] for s in window],
            held_slots_share=window_shares,
            traced_held_slots=[s["held_slots"]
                               for s in steps[window_steps:]],
            moe_slots_elsewhere=elsewhere_total,
            moe_dropped=dropped_total,
            mhc_res_marginal_err_max=marginal_max,
            mhc_stream_gain_first_last=[steps[0]["stream_gain"],
                                        steps[-1]["stream_gain"]]),
        "trace": reduced,
    }
    train.report(record)
