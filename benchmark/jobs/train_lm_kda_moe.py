"""Job kind `train_lm_kda_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a hybrid whose layers are each Kimi Delta Attention
or latent attention without a query latent, followed by a dense MLP or by
experts under a group-limited sigmoid router (the catalog row
`Ling-3.0-flash-VL`'s language model), of which this chip holds a share:
some of each layer's heads, some of each layer's experts and a slice of
the vocabulary (the configuration file's `share`).

The driver side, the window, the clock and the record's keys are
`train_lm`'s, and the loop's shape but for one thing: a step's loss and
counters are read and reported while the next step runs (`one_step`
says why). (`run` and `HOST_ANNOTATIONS` are imported from it,
`held_load` from `train_lm_mla_moe`, `sublayers` and `router_bias` from
`train_lm_ssm_moe`, whose `worker_loop` this one copies as far as the
model forces one: the config mapping, the parameters in the reference's
layout, the stand-in weights, `benchlib.flops_kda_moe`, the group
counter). A `benchmark` issue should fold the five files' loops (ROADMAP
D10).

- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): a unit embedding so that the router reads the
  token, norm gains off 1 so that a norm left out shows, the decay's `A`
  and bias drawn so that `log a` spreads over (-5, 0) (a decay of 1 hides
  a decay left out), a choice bias that is not zero and is balanced to
  this chip's share under the group limit;
- the router's choice bias is a buffer: `Transformer.frozen` goes to
  `make_train_step`, and a check holds it bit-identical after the run;
- `correct`: the parameter count four ways (the trained leaves,
  `jax.eval_shape` of the init, `TransformerConfig.num_params`,
  `flops_kda_moe.total_params`); logits and step-0 loss against
  `reference/ling3_f32.py` given the same share, on a sample that reaches
  every held expert; in every step no slot dropped, held + elsewhere =
  tokens x k, and every token inside exactly `topk_group` groups
  (`moe_groups_chosen`); the window's median held share of the slots in a
  band around held / E; the attention and grouped-matmul kernels in the
  compiled step; the loss finite; no compile inside the window;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks, are
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
_ssm_moe = load_module("jobs", "train_lm_ssm_moe")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
held_load = load_module("jobs", "train_lm_mla_moe").held_load
sublayers, router_bias = _ssm_moe.sublayers, _ssm_moe.router_bias
NEEDS = ("kda_heads", "kda_head_dim", "kda_chunk", "kda_gate_lower",
         "moe_groups", "moe_topk_groups")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run, and
    readings of the row that this job does not take, are refused, not
    silently ignored."""
    lacking = {
        "q_lora_rank": (None, "a query latent in this family's attention"),
        "use_mla_nope": (False, "latent attention without rotary columns"),
        "use_nGPT": (False, "nGPT's normalised weights"),
        "scale_router_input": (False, "a scaled router input"),
        "value_norm": (False, "a norm on the values"),
        "up_proj_norm": (False, "a norm on the up projection"),
        "no_kda_lora": (True, "a low-rank decay projection"),
        "use_kda_lora": (False, "a low-rank decay projection"),
        "kda_safe_gate": (True, "the unbounded decay gate"),
        "linear_silu": (True, "KDA without silu after its convolutions"),
        "group_norm_size": (1, "a head norm over several heads"),
        "gated_attention_proj_granularity_type": (
            "head_wise", "another output gate than one scalar a head"),
        "num_kv_heads_for_linear_attn": (0, "grouped KDA key heads"),
        "score_function": ("sigmoid", "another router score than sigmoid"),
        "moe_router_enable_expert_bias": (True, "a router without its "
                                                "choice bias"),
        "rope_scaling": (None, "scaled RoPE"),
        "tie_word_embeddings": (False, "tied embeddings in this job"),
        "num_nextn_predict_layers": (0, "a multi-token prediction module"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention has one key/value head per "
                         "query head")
    if model["rotary_dim"] != model["qk_rope_head_dim"] or model[
            "partial_rotary_factor"] * model["head_dim"] != model[
                "rotary_dim"]:
        raise ValueError(
            "rotary_dim and partial_rotary_factor are read as latent "
            "attention's rotary columns: they must give qk_rope_head_dim")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if len(model[key]) != model["num_hidden_layers"] or any(model[key]):
            raise ValueError(
                f"{key} = {model[key]!r}: one entry a held layer, and the "
                f"program has no swiglu clamp (a non-zero entry)")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run Kimi Delta Attention under a group-limited router "
            f"({ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig.
    The router is sized from the published expert count; the top-level
    head and expert counts, the depth and `vocab_size` are what this chip
    holds, and the layers' kinds follow from their published indices
    (`flops_kda_moe.layer_pattern`)."""
    from benchlib import flops_kda_moe
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        layer_pattern=flops_kda_moe.layer_pattern(model),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        q_lora_rank=0, kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], qk_norm=bool(model["use_qk_norm"]),
        rope_theta=float(model["rope_theta"]),
        d_ff=model["moe_intermediate_size"],
        moe_dense_ff=model["intermediate_size"], max_seq_len=seq,
        norm_eps=model["rms_norm_eps"], tie_embeddings=False,
        kda_heads=model["num_attention_heads"],
        kda_head_dim=model["head_dim"],
        kda_conv_kernel=model["short_conv_kernel_size"],
        kda_chunk=train["kda_chunk"],
        kda_gate_lower=float(model["kda_lower_bound"]),
        moe_experts=flops_kda_moe.router_experts(model),
        moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_scoring="sigmoid", moe_aux_coeff=0.0,
        moe_routed_scale=float(model["routed_scaling_factor"]),
        moe_groups=model["n_group"], moe_topk_groups=model["topk_group"],
        moe_shared_experts=1,
        moe_shared_ff=model["moe_shared_expert_intermediate_size"],
        moe_experts_held=model["num_experts"],
        moe_expert_offset=model.get("share", {}).get("expert_offset", 0),
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published-style
    layout (`y = x W^T`, one dict per layer, the held experts by their
    ids) the reference takes."""
    d = cfg.d_model

    def gated(gate_up, down):
        return {"gate_proj": gate_up[:, 0].T, "up_proj": gate_up[:, 1].T,
                "down_proj": down.T}

    layers = []
    for kind, sub in sublayers(params["runs"], cfg):
        if kind in "kK":
            lw = {"input_layernorm": sub["kda_norm"],
                  "f_proj": sub["w_kda_a"].reshape(d, -1).T,
                  "A_log": sub["kda_A_log"],
                  "dt_bias": sub["kda_a_bias"].reshape(-1),
                  "b_proj": sub["w_kda_bg"][:, 0].T,
                  "g_proj": sub["w_kda_bg"][:, 1].T,
                  "o_norm": sub["kda_out_norm"],
                  "o_proj": sub["w_kda_out"].reshape(-1, d).T}
            for i, p in enumerate("qkv"):
                lw[p + "_proj"] = sub["w_kda_qkv"][:, i].reshape(d, -1).T
                lw[p + "_conv1d"] = sub["kda_conv"][i]
        else:
            lw = {"input_layernorm": sub["attn_norm"],
                  "q_proj": sub["wq"].reshape(d, -1).T,
                  "kv_a_proj_with_mqa": sub["wkv_a"].T,
                  "kv_a_layernorm": sub["kv_a_norm"],
                  "kv_b_proj": sub["wkv_b"].reshape(cfg.kv_lora_rank, -1).T,
                  "o_proj": sub["wo"].reshape(-1, d).T}
            if "q_norm" in sub:
                lw.update(q_layernorm=sub["q_norm"],
                          k_layernorm=sub["k_norm"])
        lw["post_attention_layernorm"] = sub["mlp_norm"]
        if "w_router" in sub:
            lw.update({
                "mlp.gate": sub["w_router"].T,
                "e_score_correction_bias": sub["router_bias"],
                "experts": {
                    cfg.moe_expert_offset + e: gated(
                        sub["w_moe_gateup"][e], sub["w_moe_down"][e])
                    for e in range(cfg.held_experts)},
                "shared_experts": gated(sub["w_shared_gateup"],
                                        sub["w_shared_down"])})
        else:
            lw["mlp"] = gated(sub["w_gateup"], sub["w_down"])
        layers.append(lw)
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": params["lm_head"].T}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms (the
    configuration's `assumed.initializer` has the readings):

    - the embedding redrawn at `embed_std` (the program's 0.02 leaves the
      router reading the context's mean, PERF.md section 6, PR 27);
    - every norm gain, the KDA head norm's, the key/value latent's and
      the QK-norm's among them, drawn around 1 with `norm_gain_std` (a
      gain of exactly 1 hides a norm left out);
    - the decay: `A` log-uniform in `kda_A_range` and its bias drawn with
      `kda_a_bias_std`, so that `log a` spreads over (-5, 0) and reaches
      the bound: a decay of 1 would hide a decay left out, one at the
      bound everywhere a state left out;
    - the query weights of latent attention times `q_gain`: the scores'
      spread grows with it and the one attention layer is a visible part
      of the stream;
    - the router's choice bias within `router_bias_max` of zero, evenly
      spaced values in a random order within each chip's block of held
      experts; `balance_held_share` then shifts each layer's held block
      as a whole until the chip gets its share of the slots.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)
    embed = params["embed"]
    params["embed"] = (init["embed_std"] * jax.random.normal(
        jax.random.fold_in(key, 27), embed.shape)).astype(embed.dtype)
    held = cfg.held_experts
    gains = ("kda_norm", "attn_norm", "mlp_norm", "kda_out_norm",
             "kv_a_norm", "q_norm", "k_norm")
    lo, hi = init["kda_A_range"]
    n = 0
    for subs in params["runs"]:
        for sub in subs:
            n += 1
            k = jax.random.fold_in(key, 5000 + n)
            for name in gains:
                if name in sub:
                    sub[name] = (sub[name] + init["norm_gain_std"]
                                 * jax.random.normal(jax.random.fold_in(
                                     k, len(name)), sub[name].shape)
                                 ).astype(sub[name].dtype)
            if "kda_A_log" in sub:
                a = sub["kda_A_log"]
                sub["kda_A_log"] = jax.random.uniform(
                    jax.random.fold_in(k, 1), a.shape, jnp.float32,
                    math.log(lo), math.log(hi)).astype(a.dtype)
                b = sub["kda_a_bias"]
                sub["kda_a_bias"] = (init["kda_a_bias_std"]
                                     * jax.random.normal(jax.random.fold_in(
                                         k, 3), b.shape)).astype(b.dtype)
            if "wq" in sub:
                sub["wq"] = sub["wq"] * init["q_gain"]
            if "router_bias" in sub:
                # every chip's block of held experts gets the same values
                # in an order of its own: no share is favoured by the draw
                bias = sub["router_bias"]
                ramp = jnp.linspace(-init["router_bias_max"],
                                    init["router_bias_max"], held,
                                    dtype=bias.dtype)
                blocks = jax.random.split(jax.random.fold_in(k, 2),
                                          bias.size // held)
                sub["router_bias"] = jax.vmap(
                    lambda b: jax.random.permutation(b, ramp))(
                        blocks).reshape(bias.shape)
    return params


def balance_held_share(params, cfg, mesh, batches, init: Dict[str, Any]):
    """The choice bias as the family's balancing leaves it, for this
    chip's share (`train_lm_mla_moe.balance_held_share`'s method over
    this model's runs, under the group limit): per expert layer ONE shift
    of the held experts' bias, found by bisection on one seeded batch of
    the step's shape, so that the held experts together receive held / E
    of the token-slots. The held experts lie in one group, so the shift
    moves that group's rank and its experts' places in it together; the
    count still grows with the shift. Returns (params, what was done)."""
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
    target = tokens[:, :-1].size * cfg.moe_top_k * held / cfg.moe_experts

    def with_bias(p, shift):
        """shift `[expert layers]`, in the counters' order."""
        runs, at = [], 0
        for subs in p["runs"]:
            found = sum("router_bias" in s for s in subs)
            if not found:   # the leading dense layer's run
                runs.append(subs)
                continue
            repeats = next(iter(subs[0].values())).shape[0]
            mine = jnp.asarray(shift)[at:at + repeats * found].reshape(
                repeats, found)
            at += repeats * found
            new, i = [], 0
            for sub in subs:
                if "router_bias" in sub:
                    bias = sub["router_bias"]
                    sub = dict(sub, router_bias=bias.at[
                        :, first:first + held].add(
                            mine[:, i, None].astype(bias.dtype)))
                    i += 1
                new.append(sub)
            runs.append(new)
        return dict(p, runs=runs)

    # the batch is an argument: as a constant of the program it would make
    # every seed a compile of its own
    count = jax.jit(lambda p, shift, batch: Transformer.loss(
        with_bias(p, shift), {"tokens": batch}, cfg, mesh=mesh,
        with_metrics=True)[1]["moe_tokens_per_expert"].sum(-1))

    def held_slots(shift):
        return np.asarray(count(params, shift, tokens))

    layers = router_bias(params).shape[0]
    lo = np.full(layers, -float(init["balance_span"]))
    hi = -lo
    before = held_slots(0 * lo)
    for _ in range(rounds):
        mid = (lo + hi) / 2
        over = held_slots(mid) > target
        hi, lo = np.where(over, mid, hi), np.where(over, lo, mid)
    shift = (lo + hi) / 2
    after = held_slots(shift)
    return jax.jit(with_bias)(params, shift), {
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
    from benchlib import flops_kda_moe
    from benchlib.checks import (Checks, attention_as_expected,
                                 grouped_matmul_as_expected, kernel_calls)
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer
    from ray_tpu.ops.moe import grouped_matmul_impl, row_bound
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
    expert_layers = flops_kda_moe.layers_of(model, "KL")
    n_groups, kept_groups = cfg.moe_groups, cfg.moe_topk_groups
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
    shaped_params = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(shapes), jax.tree.leaves(frozen)) if not keep)
    n_params = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    checks.add("param_count",
               n_params == flops_kda_moe.total_params(model)
               == cfg.num_params == shaped_params,
               [n_params, flops_kda_moe.total_params(model), cfg.num_params,
                shaped_params])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    bias_before = router_bias(params)
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
    # loops compile one expert and one block of heads once
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
    # the routed path past the sort moves tokens x min(k, held) rows
    gmm_rows = batches.tokens_per_step * min(top_k, held) // batch_devices
    gmm_impl = grouped_matmul_impl(
        mesh, row_bound(batches.tokens_per_step // batch_devices, top_k,
                        held, n_experts, gmm_rows) or gmm_rows,
        cfg.d_model, cfg.ff_dim)
    want_gmm = tr_cfg["expect_grouped_matmul"]
    checks.add("grouped_matmul_impl", grouped_matmul_as_expected(
        gmm_impl, want_gmm, moe_calls),
        {"resolved": gmm_impl, "expected": want_gmm, "calls": moe_calls,
         "rows": gmm_rows})
    t = phase("compile_step_s", t)

    # ---- the loop's body: the loss and the routing counters come back
    # in one host read ----------------------------------------------
    losses: List[float] = []
    loads: List[float] = []
    held_shares: List[float] = []
    held_slots: List[List[int]] = []     # per step, per expert layer
    groups_last: List[List[int]] = []    # the last step's [layers, groups]
    dropped_total = 0
    elsewhere_total = 0
    miscounted_steps = 0
    misgrouped_steps = 0
    step_no = 0
    dispatched = 0
    in_flight: List[Any] = []   # the metrics of the step not read yet
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace (outside
        a trace an annotation costs about a microsecond). The one place
        this loop departs from `train_lm`'s: a step's loss and counters
        are read, checked and reported while the NEXT step runs (every
        step still is, one step later), so the device does not wait for
        the host between steps; `drain` reads the last one. Why: with the
        read before the next dispatch the device's 697.4-697.5 ms a step
        (four runs, two seeds) read 703.3-708.9 in the window and six
        seeds spread 0.58%, over half the 1% bound, all of it the host's
        (PERF.md section 6, PR 50)."""
        nonlocal state, dispatched
        with jax.profiler.TraceAnnotation("make_batch"):
            batch = put(dispatched)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = train_step(state, batch)
        dispatched += 1
        in_flight.append(metrics)
        if len(in_flight) > 1:
            read(in_flight.pop(0), report)

    def drain(report: bool = True) -> None:
        """Read what is still in flight: the device is then idle."""
        while in_flight:
            read(in_flight.pop(0), report)

    def read(metrics, report: bool) -> None:
        nonlocal step_no, dropped_total, elsewhere_total, \
            miscounted_steps, misgrouped_steps
        with jax.profiler.TraceAnnotation("report"):
            loss, counts, elsewhere, dropped, groups = jax.device_get(
                (metrics["loss"], metrics["moe_tokens_per_expert"],
                 metrics["moe_slots_elsewhere"], metrics["moe_dropped"],
                 metrics["moe_groups_chosen"]))  # the loop's own host read
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
        groups_last[:] = groups.tolist()
        miscounted_steps += int(
            counts.shape != (expert_layers, held)
            or (per_layer + elsewhere != slots_per_step).any())
        # every token kept exactly `topk_group` groups: a group's count is
        # at most the tokens, and a layer's counts sum to tokens x kept
        misgrouped_steps += int(
            groups.shape != (expert_layers, n_groups)
            or (groups.sum(-1) != batches.tokens_per_step
                * kept_groups).any()
            or (groups > batches.tokens_per_step).any())

    # ---- warm-up: every shape the window uses ---------------------
    for _ in range(int(mix["warmup_steps"])):
        one_step(report=False)
    drain(report=False)
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
    drain()      # every step dispatched in the window ends inside it
    window_s = clock() - t0
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
            drain()
        reduced = bdev.trace_window(
            os.path.join(config["scratch_dir"], "trace"), traced_steps,
            HOST_ANNOTATIONS, model.get("kernels"))

    # ---- checks on the run ----------------------------------------
    finite = [math.isfinite(x) for x in losses]
    checks.add("loss_finite", all(finite),
               {"steps": len(losses), "non_finite": finite.count(False)})
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
    checks.add("every_token_inside_its_groups", misgrouped_steps == 0,
               {"steps_off": misgrouped_steps, "groups": n_groups,
                "kept_a_token": kept_groups})
    bias_after = router_bias(state["params"])
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
            "flops_per_token": flops_kda_moe.train_flops_per_token(
                model, seq, routed_slots_per_token, cfg.kda_chunk),
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
                # keys 192 wide, values 128: `attention_call_head_dim`
                "head_dim": flops_kda_moe.attention_call_head_dim(model),
                "qk_head_dim": cfg.head_dim, "v_head_dim": cfg.v_dim},
            "delta_call": {
                "tokens": batches.tokens_per_step // batch_devices,
                "layers": flops_kda_moe.layers_of(model, "kK"),
                "heads": cfg.kda_heads, "d_k": cfg.kda_head_dim,
                "d_v": cfg.kda_head_dim, "chunk": cfg.kda_chunk,
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
                     "groups_chosen_last_step": groups_last,
                     "moe_dropped": dropped_total},
        "trace": reduced,
    }
    train.report(record)
