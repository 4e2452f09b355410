"""Job kind `train_lm`: a language-model fine-tune through `JaxTrainer.fit()`.

The process that runs the cell (run.py) never imports JAX: it starts the
cluster and calls `fit()`, and the train worker, which holds the chip(s),
does everything else in `worker_loop`: weights from the seed on the device,
the comparison with the plain reference, the ahead-of-time compile that is
read for memory and kernels, warm-up, the measured window, and with
`--trace 1` a few traced steps after it. The loop is the one
`examples/train_gpt2_jax.py` shows (make_mesh -> make_train_step ->
train.report), with a new seeded batch every step.

What belongs to the program: `ray_tpu.train`, `ray_tpu.models.Transformer`,
`ray_tpu.parallel`. What belongs to the benchmark: the batches, the clock,
the reference, the checks, the trace and its reduction.
"""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Any, Dict, List

HOST_ANNOTATIONS = ("make_batch", "dispatch", "report")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import tempfile

    import ray_tpu
    from benchlib.device import NoAccelerator
    from benchlib.entry import worker_entry
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    layout = ctx["config"]["layout"]
    rehearsal = bool(ctx["config"].get("rehearsal"))
    tpus = 0 if rehearsal else int(layout["tpus_per_worker"])
    ray_tpu.init()
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < tpus * layout["workers"]:
            raise NoAccelerator(
                f"the node advertises TPU: {have}, the cell needs "
                f"{tpus * layout['workers']}")
        resources: Dict[str, float] = {"CPU": 1}
        if tpus:
            resources["TPU"] = tpus
        config = {k: ctx[k] for k in (
            "bench_dir", "scratch_dir", "cell", "config", "traffic",
            "seed", "seconds", "trace")}
        with tempfile.TemporaryDirectory(prefix="bench_train_") as storage:
            config["fit_called_at"] = time.time()
            result = JaxTrainer(
                worker_entry, train_loop_config=config,
                scaling_config=ScalingConfig(
                    num_workers=int(layout["workers"]),
                    resources_per_worker=resources),
                run_config=RunConfig(name="bench_" + ctx["cell"]["name"],
                                     storage_path=storage)).fit()
        if result.error is not None:
            raise result.error
    finally:
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise AssertionError("the train driver imported JAX; the chip "
                             "belongs to the train worker")
    record = result.metrics
    if not isinstance(record, dict) or "end_to_end" not in record:
        raise RuntimeError(f"the worker's last report is no record: "
                           f"{str(record)[:300]}")
    return record


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig."""
    from ray_tpu.models.configs import TransformerConfig

    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLP is SwiGLU (silu) only")
    window = model.get("sliding_window")
    if window and seq > window:
        raise ValueError(
            f"sequences of {seq} tokens exceed sliding_window {window}: "
            f"the program has causal attention only")
    hd = model.get("head_dim")
    if hd and hd * model["num_attention_heads"] != model["hidden_size"]:
        raise ValueError("the program derives head_dim from d_model")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], max_seq_len=seq,
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings")),
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer) the reference takes."""
    lay = params["layers"]
    d = cfg.d_model
    layers = []
    for i in range(cfg.n_layers):
        if "wqkv" in lay:
            q, k, v = (lay["wqkv"][i][:, j] for j in range(3))
        else:
            q = lay["wq"][i]
            k, v = lay["wkv"][i][:, 0], lay["wkv"][i][:, 1]
        layers.append({
            "input_layernorm": lay["attn_norm"][i],
            "q_proj": q.reshape(d, -1).T,
            "k_proj": k.reshape(d, -1).T,
            "v_proj": v.reshape(d, -1).T,
            "o_proj": lay["wo"][i].reshape(-1, d).T,
            "post_attention_layernorm": lay["mlp_norm"][i],
            "gate_proj": lay["w_gateup"][i][:, 0].T,
            "up_proj": lay["w_gateup"][i][:, 1].T,
            "down_proj": lay["w_down"][i].T,
        })
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": head}


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
    import optax

    import ray_tpu.train as train
    from benchlib import device as bdev
    from benchlib import flops
    from benchlib.checks import (Checks, attention_as_expected,
                                 kernel_calls)
    from benchlib.peaks import peaks_for
    from benchlib.spec import load_module
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
    shapes = jax.eval_shape(lambda k: Transformer.init(k, cfg), key)
    shardings = jax.tree.map(
        lambda spec, leaf: logical_sharding(spec, mesh, shape=leaf.shape),
        specs, shapes, is_leaf=lambda x: isinstance(x, tuple))
    params = jax.jit(lambda k: Transformer.init(k, cfg),
                     out_shardings=shardings)(key)
    jax.block_until_ready(params)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    checks.add("param_count", n_params == flops.total_params(model),
               [n_params, flops.total_params(model)])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory ----------------------------------------
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample = batches.reference_sample(sample_cfg["sequences"],
                                      sample_cfg["tokens"])
    tokens = jnp.asarray(sample[:, :-1])
    targets = jnp.asarray(sample[:, 1:])
    sys_logits = jax.jit(
        lambda p, x: Transformer.apply(p, x, cfg, mesh=mesh))(params, tokens)
    sys_loss = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh))(params, {"tokens": jnp.asarray(sample)})
    ref_logits = jax.jit(lambda p, x: reference.forward(
        to_reference_layout(p, cfg), x, model))(params, tokens)
    ref_loss = reference.next_token_loss(ref_logits, targets)
    diff = sys_logits.astype(jnp.float32) - ref_logits
    rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                            / jnp.sum(ref_logits * ref_logits)))
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": float(ref_loss),
                "allowed": tol["loss_abs"]})
    del sys_logits, ref_logits, diff, tokens, targets
    t = phase("reference_check_s", t)

    # ---- the step -------------------------------------------------
    opt = tr_cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh), specs, mesh,
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
    n_kernel_calls = hlo.count("tpu_custom_call")
    attn_calls = kernel_calls(hlo, model.get("kernels", {}).get("attn", {}))
    collectives = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                   for k in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute")}
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls,
                "tpu_custom_call": n_kernel_calls})
    leaf_path = model["layout"].get("sharded_leaf")
    if leaf_path:
        leaf = state["params"]
        for k in leaf_path:
            leaf = leaf[k]
        shards = leaf.addressable_shards
        n = len(devices)
        checks.add("sharded_state",
                   len({s.device for s in shards}) == n and all(
                       s.data.size * n == leaf.size for s in shards),
                   {"leaf": list(leaf.shape),
                    "shards": [[s.device.id, list(s.data.shape)]
                               for s in shards]})
        del leaf, shards
    t = phase("compile_step_s", t)

    # ---- warm-up: every shape the window uses ---------------------
    losses: List[float] = []
    step_no = 0
    for _ in range(int(mix["warmup_steps"])):
        state, metrics = train_step(state, put(step_no))
        losses.append(float(metrics["loss"]))
        step_no += 1
    train.report({"step": step_no, "loss": losses[-1], "warmup": True})
    t = phase("warmup_s", t)
    compiles_before = len(compiles)

    # ---- the measured window --------------------------------------
    report_every = int(mix["report_every"])

    def one_step() -> float:
        """The loop's body, the same in the window and under the trace
        (outside a trace an annotation costs about a microsecond)."""
        nonlocal state, step_no
        with jax.profiler.TraceAnnotation("make_batch"):
            batch = put(step_no)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = train_step(state, batch)
        with jax.profiler.TraceAnnotation("report"):
            loss = float(metrics["loss"])    # the loop's own host read
            step_no += 1
            if step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss})
        return loss

    step_s: List[float] = []
    window_started_at = time.time()
    t0 = clock()
    last = t0
    while last - t0 < config["seconds"]:
        losses.append(one_step())
        now = clock()
        step_s.append(now - last)
        last = now
    window_s = last - t0
    window_compiles = len(compiles) - compiles_before
    tokens_per_s = len(step_s) * batches.tokens_per_step / window_s

    # ---- a few traced steps, after the window ---------------------
    reduced = None
    if config["trace"]:
        def traced_steps():
            for _ in range(int(mix["trace_steps"])):
                losses.append(one_step())
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
            "flops_per_token": flops.train_flops_per_token(model, seq),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "collectives_in_step": collectives,
            "attention_call": {
                "batch": batches.sequences // batch_devices,
                "heads": model["num_attention_heads"],
                "kv_heads": model["num_key_value_heads"], "seq": seq,
                "head_dim": flops.head_dim(model)},
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff},
        "trace": reduced,
    }
    train.report(record)
