"""Job kind `train_lm_mla_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a latent-attention decoder with shared and routed
experts behind leading dense layers (`model_type: glm4_moe_lite`), of
which this chip holds a share: some of each layer's experts and a slice
of the vocabulary (the configuration file's `share`).

The driver side, the loop, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it, the
routing load from `train_lm_moe`; a `benchmark` issue should fold the
three files, ROADMAP D10). `worker_loop` is a copy as far as the config
mapping, the parameters in the reference's layout, `benchlib.
flops_mla_moe` and the counters force one. What it adds:

- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): a unit embedding so that the router reads the
  token, a query latent gain that makes attention peaked so that the
  comparison sees the attention block, latent norm gains off 1 so that a
  norm left out shows, a choice bias that is not zero;
- the router's choice bias is a buffer: `Transformer.frozen` goes to
  `make_train_step`, and a check holds it bit-identical after the run;
- the step's metrics carry the held experts' counts and the slots routed
  elsewhere; the loop reads them with the loss in one host read and
  forwards the held load through `train.report`;
- `correct` adds: logits and step-0 loss against
  `reference/glm4_moe_lite_f32.py` given the same share, on a sample that
  reaches every held expert; in every step no slot dropped and held +
  elsewhere = tokens x k; the window's median held share of the slots in
  a band around held / E; the grouped matmul the configuration expects
  with its kernels in the compiled step;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks (a
  multi-token prediction module, group-limited routing, scaled RoPE), are
  refused before the cluster starts.
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
NEEDS = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "moe_scoring",
         "moe_routed_scale", "moe_shared_experts", "moe_dense_layers",
         "moe_dense_ff", "moe_experts_held", "moe_expert_offset")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run are refused,
    not silently ignored."""
    lacking = {
        "num_nextn_predict_layers": (0, "a multi-token prediction module"),
        "n_group": (1, "group-limited routing"),
        "topk_group": (1, "group-limited routing"),
        "rope_scaling": (None, "scaled (YaRN) RoPE"),
        "partial_rotary_factor": (1, "partial rotary columns"),
        "topk_method": ("noaux_tc", "another choice than noaux_tc"),
        "hidden_act": ("silu", "another activation than silu"),
        "attention_bias": (False, "biases in the projections"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention has one key/value head per "
                         "query head")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run {ctx['config'].get('model_type')!r} "
            f"({ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def held_load(counts) -> float:
    """Of the held experts' slot counts `[expert layers, held]`: the
    largest load over the held experts' mean load, of the layer where it
    is worst (`held` where one expert has every slot; 1 for a layer whose
    held experts got none)."""
    import numpy as np

    total = counts.sum(axis=-1)
    load = counts.max(axis=-1) * counts.shape[-1] / np.maximum(total, 1)
    return float(np.where(total > 0, load, 1.0).max())


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig.
    The router is sized from the published expert count; the top-level
    `n_routed_experts` and `vocab_size` are what this chip holds."""
    from benchlib import flops_mla_moe
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        d_ff=model["moe_intermediate_size"], max_seq_len=seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings")),
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        moe_experts=flops_mla_moe.router_experts(model),
        moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_scoring="sigmoid", moe_aux_coeff=0.0,
        moe_routed_scale=float(model["routed_scaling_factor"]),
        moe_shared_experts=model["n_shared_experts"],
        moe_dense_layers=model["first_k_dense_replace"],
        moe_dense_ff=model["intermediate_size"],
        moe_experts_held=model["n_routed_experts"],
        moe_expert_offset=model.get("share", {}).get("expert_offset", 0),
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer, the held experts by their ids) the
    reference takes."""
    d = cfg.d_model

    def gated(gate_up, down):
        return {"gate_proj": gate_up[:, 0].T, "up_proj": gate_up[:, 1].T,
                "down_proj": down.T}

    def attention(lay, i):
        return {
            "input_layernorm": lay["attn_norm"][i],
            "q_a_proj": lay["wq_a"][i].T,
            "q_a_layernorm": lay["q_a_norm"][i],
            "q_b_proj": lay["wq_b"][i].reshape(cfg.q_lora_rank, -1).T,
            "kv_a_proj_with_mqa": lay["wkv_a"][i].T,
            "kv_a_layernorm": lay["kv_a_norm"][i],
            "kv_b_proj": lay["wkv_b"][i].reshape(cfg.kv_lora_rank, -1).T,
            "o_proj": lay["wo"][i].reshape(-1, d).T,
            "post_attention_layernorm": lay["mlp_norm"][i],
        }

    layers = []
    lay = params.get("dense_layers")
    for i in range(cfg.moe_dense_layers):
        layers.append(dict(attention(lay, i), mlp=gated(
            lay["w_gateup"][i], lay["w_down"][i])))
    lay = params["layers"]
    for i in range(cfg.n_layers - cfg.moe_dense_layers):
        layers.append(dict(attention(lay, i), **{
            "mlp.gate": lay["w_router"][i].T,
            "e_score_correction_bias": lay["router_bias"][i],
            "experts": {
                cfg.moe_expert_offset + e: gated(
                    lay["w_moe_gateup"][i][e], lay["w_moe_down"][i][e])
                for e in range(cfg.held_experts)},
            "shared_experts": gated(lay["w_shared_gateup"][i],
                                    lay["w_shared_down"][i])}))
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": head}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms (the
    configuration's `assumed.initializer` has the readings):

    - the embedding redrawn at `embed_std` (the program's 0.02 leaves the
      router reading the context's mean, PERF.md section 6, PR 27);
    - the query latent's norm gain at `q_latent_gain`: the scores' spread
      grows with it, attention picks few keys instead of averaging the
      context, and its output is a large part of the stream;
    - the key/value latent's norm gain drawn around 1 with
      `kv_latent_gain_std` (a gain of exactly 1 on a latent of RMS 1 hides
      a norm left out);
    - the router's choice bias within `router_bias_max` of zero, evenly
      spaced values in a random order within each chip's block of held
      experts: small against the scores' spread (no expert is shut out),
      it changes the choice, and it favours no share over another;
      `balance_held_share` then shifts each layer's held block as a whole
      until the chip gets its eighth of the slots.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)
    embed = params["embed"]
    params["embed"] = (init["embed_std"] * jax.random.normal(
        jax.random.fold_in(key, 27), embed.shape)).astype(embed.dtype)
    for n, run in enumerate(("dense_layers", "layers")):
        lay = params.get(run)
        if lay is None:
            continue
        lay["q_a_norm"] = lay["q_a_norm"] * init["q_latent_gain"]
        gain = lay["kv_a_norm"]
        lay["kv_a_norm"] = (gain + init["kv_latent_gain_std"]
                            * jax.random.normal(jax.random.fold_in(
                                key, 33 + n), gain.shape)).astype(gain.dtype)
    # every chip's block of held experts gets the same values in an order
    # of its own, so that no share is favoured by the draw
    bias = params["layers"]["router_bias"]
    held = cfg.held_experts
    ramp = jnp.linspace(-init["router_bias_max"], init["router_bias_max"],
                        held, dtype=bias.dtype)
    blocks = jax.random.split(jax.random.fold_in(key, 35),
                              bias.size // held)
    params["layers"]["router_bias"] = jax.vmap(
        lambda k: jax.random.permutation(k, ramp))(blocks).reshape(
            bias.shape)
    return params


def balance_held_share(params, cfg, mesh, batches, init: Dict[str, Any]):
    """The choice bias as the family's balancing leaves it, for this
    chip's share: per expert layer ONE shift of the held experts' bias
    (their order among themselves stays), found by bisection on one seeded
    batch of the step's shape, so that the held experts together receive
    held / E of the token-slots: what balanced routing (`noaux_tc` moves the
    bias until the loads are even) gives every chip of a deployment. Without
    it the draw decides: the most frequent token types go where their
    embedding sends them, 2.7 points of the slots each time the top token
    picks a held expert, and the held share reads 11-16.5% by seed.
    Returns (params, what was done)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import Transformer

    rounds = int(init.get("balance_rounds", 0))
    if not rounds:
        return params, None
    tokens = jnp.asarray(batches.draw(3, 0, batches.sequences,
                                      batches.tokens + 1))
    first, held = cfg.moe_expert_offset, cfg.held_experts
    base = params["layers"]["router_bias"]
    target = tokens[:, :-1].size * cfg.moe_top_k * held / cfg.moe_experts

    def with_bias(p, shift):
        bias = base.at[:, first:first + held].add(
            jnp.asarray(shift, base.dtype)[:, None])
        return dict(p, layers=dict(p["layers"], router_bias=bias))

    # the batch is an argument: as a constant of the program it would make
    # every seed a compile of its own
    count = jax.jit(lambda p, shift, batch: Transformer.loss(
        with_bias(p, shift), {"tokens": batch}, cfg, mesh=mesh,
        with_metrics=True)[1]["moe_tokens_per_expert"].sum(-1))

    def held_slots(shift):
        return np.asarray(count(params, shift, tokens))

    lo = np.full(base.shape[0], -float(init["balance_span"]))
    hi = -lo
    before = held_slots(0 * lo)
    for _ in range(rounds):
        mid = (lo + hi) / 2
        over = held_slots(mid) > target
        hi, lo = np.where(over, mid, hi), np.where(over, lo, mid)
    shift = (lo + hi) / 2
    after = held_slots(shift)
    return with_bias(params, shift), {
        "shift": shift.tolist(), "target_slots": target,
        "held_slots_before": before.tolist(),
        "held_slots_after": after.tolist()}


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
    from benchlib import flops_mla_moe
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
    checks.add("param_count",
               n_params == flops_mla_moe.total_params(model)
               == cfg.num_params,
               [n_params, flops_mla_moe.total_params(model), cfg.num_params])
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
    sample_dev = jnp.asarray(sample)
    sys_logits = jax.jit(lambda p, x: Transformer.apply(
        p, x, cfg, mesh=mesh))(params, sample_dev[:, :-1])
    sys_loss, sys_metrics = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, with_metrics=True))(
            params, {"tokens": sample_dev})

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loop over the experts compiles one expert once
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits, chosen = reference.forward(
        weights, sample_dev[:, :-1], model, with_routing=True)
    ref_loss = reference.next_token_loss(ref_logits, sample_dev[:, 1:])
    ref_counts = np.asarray(reference.tokens_per_expert(chosen, n_experts))
    del weights, chosen
    diff = sys_logits.astype(jnp.float32) - ref_logits
    rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                            / jnp.sum(ref_logits * ref_logits)))
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    sample_counts = np.asarray(sys_metrics["moe_tokens_per_expert"])
    first = cfg.moe_expert_offset
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": float(ref_loss),
                "allowed": tol["loss_abs"]})
    # what the two comparisons above covered: the grouped matmul on every
    # held expert's group, none of them empty. How many slots the rounded
    # activations moved to another expert than the reference's is
    # reported, not judged: a near-tie may go either way.
    moved = int(np.abs(sample_counts
                       - ref_counts[:, first:first + held]).sum())
    checks.add("reference_sample_reaches_every_held_expert",
               int((sample_counts == 0).sum()) == 0,
               {"held_counts": sample_counts.tolist(),
                "count_differences_against_reference": moved})
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
    t = phase("compile_step_s", t)

    # ---- the loop's body: the loss and the routing counters come back
    # in one host read ----------------------------------------------
    losses: List[float] = []
    loads: List[float] = []
    held_shares: List[float] = []
    held_slots: List[List[int]] = []     # per step, per expert layer
    dropped_total = 0
    elsewhere_total = 0
    miscounted_steps = 0
    step_no = 0
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace (outside
        a trace an annotation costs about a microsecond)."""
        nonlocal state, step_no, dropped_total, elsewhere_total, \
            miscounted_steps
        with jax.profiler.TraceAnnotation("make_batch"):
            batch = put(step_no)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = train_step(state, batch)
        with jax.profiler.TraceAnnotation("report"):
            loss, counts, elsewhere, dropped = jax.device_get(
                (metrics["loss"], metrics["moe_tokens_per_expert"],
                 metrics["moe_slots_elsewhere"],
                 metrics["moe_dropped"]))     # the loop's own host read
            loss = float(loss)
            load = held_load(counts)
            per_layer = counts.sum(axis=-1)
            share = 100.0 * float(per_layer.sum()) / (
                expert_layers * slots_per_step)
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss,
                              "held_expert_load_max_over_mean": load,
                              "held_slots_share": share})
        losses.append(loss)
        loads.append(load)
        held_shares.append(share)
        held_slots.append([int(x) for x in per_layer])
        dropped_total += int(dropped)
        elsewhere_total += int(elsewhere.sum())
        miscounted_steps += int(
            (per_layer + elsewhere != slots_per_step).any())

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
    window_steps = len(losses)
    window_loads = loads[warmup_steps:window_steps]
    window_shares = held_shares[warmup_steps:window_steps]
    # the routed experts' FLOPs at the slots this chip computed
    routed_slots_per_token = sum(
        sum(step) for step in held_slots[warmup_steps:window_steps]) / (
            len(step_s) * batches.tokens_per_step)

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
    checks.add("held_and_elsewhere_sum_to_slots", miscounted_steps == 0,
               {"steps_off": miscounted_steps,
                "slots_per_step": slots_per_step,
                "slots_elsewhere": elsewhere_total})
    bias_after = np.asarray(state["params"]["layers"]["router_bias"])
    checks.add("router_bias_untrained",
               bias_before.tobytes() == bias_after.tobytes()
               and bool(bias_before.any()),
               {"max_abs_change": float(np.abs(
                   bias_after - bias_before).max())})
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
            "flops_per_token": flops_mla_moe.train_flops_per_token(
                model, seq, routed_slots_per_token),
            "routed_slots_per_token": routed_slots_per_token,
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "grouped_matmul_impl": gmm_impl,
            "collectives_in_step": collectives,
            "attention_call": {
                "batch": batches.sequences // batch_devices,
                "heads": model["num_attention_heads"],
                "kv_heads": model["num_key_value_heads"], "seq": seq,
                "head_dim": flops_mla_moe.qk_head_dim(model)},
            "held_experts_call": {
                "model": {k: model[k] for k in (
                    "hidden_size", "moe_intermediate_size",
                    "n_routed_experts", "num_experts_per_tok")},
                "router_experts": n_experts,
                "tokens": batches.tokens_per_step // batch_devices,
                "remat": bool(tr_cfg["remat"])},
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "bias_balance": balanced,
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff,
                     "held_expert_load_max_over_mean": window_loads,
                     "held_slots_share": window_shares,
                     "traced_held_slots": held_slots[window_steps:],
                     "moe_slots_elsewhere": elsewhere_total,
                     "moe_dropped": dropped_total},
        "trace": reduced,
    }
    train.report(record)
