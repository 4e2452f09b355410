"""Job kind `train_lm_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a sparse-expert decoder (`model_type: olmoe`).

The driver side, the loop, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it; a
`benchmark` issue should fold the two files, PERF.md section 7).
`worker_loop` is a copy as far as three dense-only calls force one: the
mapping of the published config onto `TransformerConfig`, the parameters
in the reference's layout, and `benchlib.flops`. What it adds:

- the weights stand in for a trained model's, whose router spreads the
  tokens: `init_params` draws the embedding at the scale the
  configuration's `init` gives, so that the router reads the token and
  not the context's mean (PERF.md section 6, PR 27);
- the step's metrics carry the program's routing counters; the loop reads
  them with the loss in one host read and forwards the load's
  max-over-mean through `train.report`;
- `correct` adds: logits and step-0 loss (cross-entropy + aux) against
  `reference/olmoe_f32.py`, on a sample that sends tokens to every expert
  and all of them to none, so that the comparison covers the grouped
  matmul on all its groups; the window's median load under experts / 2k
  (in the median step no expert takes half the tokens: the routing the
  cell is for, where a transient may come nearer); no token-slot
  dropped in any step; the per-expert counts sum to tokens x k in every
  step; the grouped matmul the configuration expects, and its kernels in
  the compiled step; the compiled step holds no temporary the size of one
  `[tokens, experts, capacity]` float32 tensor;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs is refused before the cluster starts.
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
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
NEEDS = ("qk_norm", "moe_experts", "moe_top_k", "moe_norm_topk",
         "moe_aux_coeff")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run {ctx['config'].get('model_type')!r} "
            f"({ctx['cell']['name']})")
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig."""
    from ray_tpu.models.configs import TransformerConfig

    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu) only")
    for key in ("clip_qkv", "rope_scaling"):
        if model.get(key) is not None:
            raise ValueError(f"the program has no {key}")
    if model.get("attention_bias"):
        raise ValueError("the program's projections have no bias")
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], max_seq_len=seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings")),
        qk_norm=True, moe_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_aux_coeff=model["router_aux_loss_coef"],
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer, one dict per expert) the reference
    takes."""
    lay = params["layers"]
    d = cfg.d_model
    layers = []
    for i in range(cfg.n_layers):
        if "wqkv" in lay:
            q, k, v = (lay["wqkv"][i][:, j] for j in range(3))
        else:
            q = lay["wq"][i]
            k, v = lay["wkv"][i][:, 0], lay["wkv"][i][:, 1]
        gate_up, down = lay["w_moe_gateup"][i], lay["w_moe_down"][i]
        layers.append({
            "input_layernorm": lay["attn_norm"][i],
            "q_proj": q.reshape(d, -1).T,
            "k_proj": k.reshape(d, -1).T,
            "v_proj": v.reshape(d, -1).T,
            "o_proj": lay["wo"][i].reshape(-1, d).T,
            "q_norm": lay["q_norm"][i],
            "k_norm": lay["k_norm"][i],
            "post_attention_layernorm": lay["mlp_norm"][i],
            "mlp.gate": lay["w_router"][i].T,
            "experts": [{"gate_proj": gate_up[e][:, 0].T,
                         "up_proj": gate_up[e][:, 1].T,
                         "down_proj": down[e].T}
                        for e in range(cfg.moe_experts)],
        })
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": head}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with the embedding redrawn at `init["embed_std"]`. At the
    program's 0.02 the residual stream the router reads is the attention
    block's output, a mean over the context that is nearly the same at
    every position, and every token takes the same k experts."""
    import jax

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)
    embed = params["embed"]
    params["embed"] = (init["embed_std"] * jax.random.normal(
        jax.random.fold_in(key, 27), embed.shape)).astype(embed.dtype)
    return params


def routing_load(counts, top_k: int) -> Dict[str, Any]:
    """Of per-expert slot counts `[layers, E]`: the largest load over the
    mean load (E / k where every token takes the same expert) and how
    many experts got nothing, each of the layer where it is worst;
    `spread` unless a layer has an expert that every token takes or leaves
    most experts empty."""
    n_experts = counts.shape[-1]
    load = float((counts.max(axis=-1) * n_experts
                  / counts.sum(axis=-1)).max())
    empty = int((counts == 0).sum(axis=-1).max())
    return {"max_over_mean": load, "empty_experts": empty,
            "spread": load < n_experts / top_k and empty <= n_experts // 2}


def worker_loop(config: Dict[str, Any]) -> None:
    entered_at = time.time()
    phases: Dict[str, float] = {}
    clock = time.perf_counter

    def phase(name: str, since: float) -> float:
        now = clock()
        phases[name] = now - since
        return now

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import ray_tpu.train as train
    from benchlib import device as bdev
    from benchlib import flops, flops_moe
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

    batches = TokenBatches(mix, model["vocab_size"], config["seed"])
    seq = batches.tokens
    cfg = transformer_config(model, tr_cfg, seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]))
    batch_devices = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)
    n_experts, top_k = cfg.moe_experts, cfg.moe_top_k
    slots_per_step = batches.tokens_per_step * top_k

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
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    checks.add("param_count",
               n_params == flops_moe.total_params(model) == cfg.num_params,
               [n_params, flops_moe.total_params(model), cfg.num_params])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory ----------------------------------------
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample = batches.reference_sample(sample_cfg["sequences"],
                                      sample_cfg["tokens"])
    sample_dev = jnp.asarray(sample)
    sys_logits = jax.jit(lambda p, x: Transformer.apply(
        p, x, cfg, mesh=mesh))(params, sample_dev[:, :-1])
    sys_loss, sys_metrics = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, with_metrics=True))(
            params, {"tokens": sample_dev})

    # op by op, not under one jit: the reference's plain loop over the
    # experts, unrolled into one program, takes the chip's compiler a
    # minute and a half; called like this it compiles one expert once
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits, router_logits = reference.forward(
        weights, sample_dev[:, :-1], model, with_router_logits=True)
    ref_aux = reference.load_balancing_loss(router_logits, model)
    ref_loss = reference.next_token_loss(ref_logits, sample_dev[:, 1:]) \
        + model["router_aux_loss_coef"] * ref_aux
    del weights, router_logits
    diff = sys_logits.astype(jnp.float32) - ref_logits
    rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                            / jnp.sum(ref_logits * ref_logits)))
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    sample_load = routing_load(
        np.asarray(sys_metrics["moe_tokens_per_expert"]), top_k)
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": float(ref_loss),
                "allowed": tol["loss_abs"], "aux_system": float(
                    sys_metrics["moe_aux_loss"]),
                "aux_reference": float(ref_aux)})
    # what the two comparisons above covered: the grouped matmul on
    # every expert's group, none of them empty
    checks.add("reference_sample_routing_spread", sample_load["spread"]
               and sample_load["empty_experts"] == 0, sample_load)
    del sys_logits, ref_logits, diff, sample_dev, sys_metrics
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
    kernels = model.get("kernels", {})
    attn_calls = kernel_calls(hlo, kernels.get("attn", {}))
    moe_calls = kernel_calls(hlo, kernels.get("moe", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    collectives = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                   for k in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute")}
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
    # one [tokens, experts, capacity] float32 one-hot of the dispatch this
    # configuration cannot use (capacity 1.25 x tokens x k / experts)
    one_hot_bytes = 4 * (batches.tokens_per_step // batch_devices) \
        * n_experts * int(1.25 * slots_per_step / n_experts)
    if memory_analysis and not rehearsal:   # a statement about real sizes
        checks.add("no_tokens_by_experts_by_capacity_buffer",
                   memory_analysis["temp_size_in_bytes"] < one_hot_bytes,
                   {"temp_size_in_bytes":
                    memory_analysis["temp_size_in_bytes"],
                    "one_dispatch_one_hot_bytes": one_hot_bytes})
    t = phase("compile_step_s", t)

    # ---- the loop's body: the loss and the routing counters come back
    # in one host read ----------------------------------------------
    losses: List[float] = []
    loads: List[float] = []
    dropped_total = 0
    miscounted_steps = 0
    step_no = 0
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace (outside
        a trace an annotation costs about a microsecond)."""
        nonlocal state, step_no, dropped_total, miscounted_steps
        with jax.profiler.TraceAnnotation("make_batch"):
            batch = put(step_no)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = train_step(state, batch)
        with jax.profiler.TraceAnnotation("report"):
            loss, counts, dropped = jax.device_get(
                (metrics["loss"], metrics["moe_tokens_per_expert"],
                 metrics["moe_dropped"]))     # the loop's own host read
            loss = float(loss)
            load = routing_load(counts, top_k)["max_over_mean"]
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss,
                              "expert_load_max_over_mean": load})
        losses.append(loss)
        loads.append(load)
        dropped_total += int(dropped)
        miscounted_steps += int(
            (counts.sum(axis=-1) != slots_per_step).any())

    # ---- warm-up: every shape the window uses ---------------------
    for _ in range(int(mix["warmup_steps"])):
        one_step(report=False)
    train.report({"step": step_no, "loss": losses[-1], "warmup": True})
    t = phase("warmup_s", t)
    compiles_before = len(compiles)
    warmup_steps = len(losses)

    # ---- the measured window --------------------------------------
    step_s: List[float] = []
    window_started_at = time.time()
    t0 = clock()
    last = t0
    while last - t0 < config["seconds"]:
        one_step()
        now = clock()
        step_s.append(now - last)
        last = now
    window_s = last - t0
    window_compiles = len(compiles) - compiles_before
    tokens_per_s = len(step_s) * batches.tokens_per_step / window_s
    window_loads = loads[warmup_steps:]

    # ---- a few traced steps, after the window ---------------------
    reduced = None
    if config["trace"]:
        def traced_steps():
            for _ in range(int(mix["trace_steps"])):
                one_step()
        reduced = bdev.trace_window(
            os.path.join(config["scratch_dir"], "trace"), traced_steps,
            HOST_ANNOTATIONS, model.get("kernels"))

    # ---- checks on the run ----------------------------------------
    finite = [math.isfinite(x) for x in losses]
    checks.add("loss_finite", all(finite),
               {"steps": len(losses), "non_finite": finite.count(False)})
    checks.add("loss_fell", losses[-1] < losses[0],
               {"first": losses[0], "last": losses[-1],
                "unigram_entropy_nats": batches.unigram_entropy_nats})
    checks.add("no_compile_in_window", window_compiles == 0,
               {"compiles_in_window": window_compiles,
                "compiles_in_setup": compiles_before})
    checks.add("steps_in_window", len(step_s) >= 3, len(step_s))
    checks.add("no_token_dropped", dropped_total == 0,
               {"dropped_slots": dropped_total, "steps": len(losses)})
    checks.add("expert_counts_sum_to_slots", miscounted_steps == 0,
               {"steps_off": miscounted_steps,
                "slots_per_step": slots_per_step})
    if not rehearsal:   # the cell's traffic: at a rehearsal's tiny widths
        # the second layer's router does send every token to one expert
        # within some tens of steps
        checks.add("routing_spread_in_window",
                   statistics.median(window_loads) < n_experts / (2 * top_k),
                   {"max_over_mean_median": statistics.median(window_loads),
                    "first_last_max": [window_loads[0], window_loads[-1],
                                       max(window_loads)],
                    "allowed": n_experts / (2 * top_k)})

    bdev.finish_device(device, reduced)
    record = {
        "device": device,
        "correct": checks.all_ok,
        "checks": dict(checks),
        "attempted": len(losses),
        "failed": finite.count(False),
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
            "flops_per_token": flops_moe.train_flops_per_token(model, seq),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "grouped_matmul_impl": gmm_impl,
            "collectives_in_step": collectives,
            "attention_call": {
                "batch": batches.sequences // batch_devices,
                "heads": model["num_attention_heads"],
                "kv_heads": model["num_key_value_heads"], "seq": seq,
                "head_dim": flops.head_dim(model)},
            "experts_call": {
                "model": {k: model[k] for k in (
                    "hidden_size", "intermediate_size", "num_experts",
                    "num_experts_per_tok", "num_hidden_layers")},
                "tokens": batches.tokens_per_step // batch_devices,
                "remat": bool(tr_cfg["remat"])},
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff,
                     "expert_load_max_over_mean": window_loads,
                     "moe_dropped": dropped_total},
        "trace": reduced,
    }
    train.report(record)
