"""Job kind `train_lm_gdn`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a dense hybrid whose layers are each a Gated
DeltaNet mixer or full attention, followed by a dense MLP, every sublayer
under OLMo-2/3's reordered norm (the catalog row `Olmo-Hybrid-7B`), of
which this chip holds a share: some of each layer's heads and a slice of
the vocabulary (the configuration file's `share`).

The driver side, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it,
`sublayers` from `train_lm_ssm_moe`), and the loop's shape but for one
thing: a step's loss is read, checked and reported while the next step
runs (`one_step` says why; `train_lm_kda_moe.worker_loop` found it, and
its `one_step` / `drain` are closures that cannot be imported, so the loop
is written here once more). A `benchmark` issue should fold the files'
loops (ROADMAP D10).

- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): a unit embedding (under the reordered norm
  nothing norms it before the first mixer reads it), norm gains off 1 so
  that a norm left out or moved shows, the decay's `A` and `dt` drawn so
  that `log a` spreads and some heads decay hard (a decay of 1 hides a
  decay left out); beta spreads over (0, 2) by itself on a unit stream;
- `correct`: the parameter count four ways (the leaves, `jax.eval_shape`
  of the init, `TransformerConfig.num_params`, `flops_gdn.total_params`);
  logits and step-0 loss of the timed path's own program against
  `reference/olmo_hybrid_f32.py` given the same share, on one sequence of
  the step's length; the attention kernels in the compiled step; the
  delta rule's implementation the one the configuration expects; the loss
  finite and lower at the end; no compile inside the window;
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
sublayers = load_module("jobs", "train_lm_ssm_moe").sublayers
NEEDS = ("gdn_heads", "gdn_key_dim", "gdn_value_dim", "gdn_conv_kernel",
         "gdn_neg_eigval", "gdn_chunk")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms the program does not run, and readings of the row that
    this job does not take, are refused, not silently ignored."""
    lacking = {
        "hidden_act": ("silu", "another MLP than the SiLU-gated one"),
        "attention_bias": (False, "a bias on this family's projections"),
        "tie_word_embeddings": (False, "tied embeddings in this job"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if (model.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rope_theta is read as null: no rotary embedding "
                         "in this family's attention")
    if model["linear_num_key_heads"] != model["linear_num_value_heads"]:
        raise ValueError("the mixer has one key head a value head")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("this family's attention has one key/value head "
                         "a query head")
    if set(model["layer_types"]) - {"linear_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(model['layer_types']))}")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.configs import TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if missing:
        raise RuntimeError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            f"run Gated DeltaNet mixers under the reordered norm "
            f"({ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    for key in ("packed_documents", "segment_masks"):
        if ctx["traffic"].get(key):   # one document a sequence, one mask
            raise ValueError(f"traffic.{key}: the delta rule's state and "
                             f"the causal mask run over a whole sequence")
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig.
    The top-level head counts, the depth and `vocab_size` are what this
    chip holds; a head's width is the published one (`head_dim`), and the
    layers' kinds are read off `layer_types` (`flops_gdn.layer_pattern`)."""
    from benchlib import flops_gdn
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        layer_pattern=flops_gdn.layer_pattern(model),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"], qk_norm=True, rope=False,
        d_ff=model["intermediate_size"], max_seq_len=seq,
        norm_eps=model["rms_norm_eps"], tie_embeddings=False,
        gdn_heads=model["linear_num_value_heads"],
        gdn_key_dim=model["linear_key_head_dim"],
        gdn_value_dim=model["linear_value_head_dim"],
        gdn_conv_kernel=model["linear_conv_kernel_dim"],
        gdn_neg_eigval=bool(model["linear_allow_neg_eigval"]),
        gdn_chunk=train["gdn_chunk"],
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published-style
    layout (`y = x W^T`, one dict per layer) the reference takes."""
    d, dk = cfg.d_model, cfg.gdn_key_dim

    layers = []
    for kind, sub in sublayers(params["runs"], cfg):
        if kind == "d":
            qkv, conv = sub["w_gdn_qkv"], sub["gdn_conv"]
            lw = {"a_proj": sub["w_gdn_ab"][:, 0].T,
                  "b_proj": sub["w_gdn_ab"][:, 1].T,
                  "A_log": sub["gdn_A_log"], "dt_bias": sub["gdn_dt_bias"],
                  "g_proj": sub["w_gdn_g"].reshape(d, -1).T,
                  "o_norm": sub["gdn_out_norm"],
                  "o_proj": sub["w_gdn_out"].reshape(-1, d).T,
                  "post_attention_layernorm": sub["gdn_post_norm"]}
            # a head's q, k and v columns lie side by side
            for p, lo, hi in (("q", 0, dk), ("k", dk, 2 * dk),
                              ("v", 2 * dk, qkv.shape[-1])):
                lw[p + "_proj"] = qkv[:, :, lo:hi].reshape(d, -1).T
                lw[p + "_conv1d"] = conv[:, lo:hi].reshape(
                    -1, conv.shape[-1])
        else:
            lw = {"q_norm": sub["q_norm"], "k_norm": sub["k_norm"],
                  "o_proj": sub["wo"].reshape(-1, d).T,
                  "post_attention_layernorm": sub["attn_post_norm"]}
            for i, p in enumerate("qkv"):
                lw[p + "_proj"] = sub["wqkv"][:, i].reshape(d, -1).T
        lw.update(gate_proj=sub["w_gateup"][:, 0].T,
                  up_proj=sub["w_gateup"][:, 1].T,
                  down_proj=sub["w_down"].T,
                  post_feedforward_layernorm=sub["mlp_post_norm"])
        layers.append(lw)
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": params["lm_head"].T}


GAINS = ("gdn_post_norm", "attn_post_norm", "mlp_post_norm", "gdn_out_norm",
         "q_norm", "k_norm")


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms (the
    configuration's `assumed.initializer` has the readings):

    - the embedding redrawn at `embed_std`: under the reordered norm
      nothing norms it before the first mixer's gates read it;
    - every norm gain, the head norm's and the QK-norm's among them,
      drawn around 1 with `norm_gain_std` (a gain of exactly 1 hides a
      norm left out or moved);
    - the decay: `A` log-uniform in `gdn_A_range` and `dt` uniform in
      `gdn_dt_bias_range`, so that `log a` spreads from near 0 to tens
      below it (a decay of 1 hides a decay left out; a hard one tries the
      chunk's masked differences).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)
    embed = params["embed"]
    params["embed"] = (init["embed_std"] * jax.random.normal(
        jax.random.fold_in(key, 27), embed.shape)).astype(embed.dtype)
    a_lo, a_hi = init["gdn_A_range"]
    dt_lo, dt_hi = init["gdn_dt_bias_range"]
    n = 0
    for subs in params["runs"]:
        for sub in subs:
            n += 1
            k = jax.random.fold_in(key, 5000 + n)
            for name in GAINS:
                if name in sub:
                    sub[name] = (sub[name] + init["norm_gain_std"]
                                 * jax.random.normal(jax.random.fold_in(
                                     k, len(name)), sub[name].shape)
                                 ).astype(sub[name].dtype)
            if "gdn_A_log" in sub:
                a = sub["gdn_A_log"]
                sub["gdn_A_log"] = jax.random.uniform(
                    jax.random.fold_in(k, 1), a.shape, jnp.float32,
                    math.log(a_lo), math.log(a_hi)).astype(a.dtype)
                sub["gdn_dt_bias"] = jax.random.uniform(
                    jax.random.fold_in(k, 3), a.shape, jnp.float32,
                    dt_lo, dt_hi).astype(a.dtype)
    return params


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
    from benchlib import flops_gdn
    from benchlib.checks import Checks, attention_as_expected, kernel_calls
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer
    from ray_tpu.ops.kda import kda_delta_impl
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
    checks.add("param_count",
               n_params == flops_gdn.total_params(model)
               == cfg.num_params == shaped_params,
               [n_params, flops_gdn.total_params(model), cfg.num_params,
                shaped_params])
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
    sys_loss = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh))(params, {"tokens": sample_dev})

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loops compile one block of queries once
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits = reference.forward(weights, sample_dev[:, :-1], model)
    ref_loss = reference.next_token_loss(ref_logits, sample_dev[:, 1:])
    del weights
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
    del sys_logits, ref_logits, diff, sample_dev
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
    attn_calls = kernel_calls(hlo, model.get("kernels", {}).get("attn", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls})
    tokens_here = batches.tokens_per_step // batch_devices
    delta_impl = kda_delta_impl(mesh, seq, cfg.gdn_heads, cfg.gdn_key_dim,
                                cfg.gdn_value_dim, cfg.gdn_chunk,
                                per_head=True)
    checks.add("delta_impl_as_expected",
               delta_impl == tr_cfg["expect_delta_rule"],
               {"resolved": delta_impl,
                "expected": tr_cfg["expect_delta_rule"]})
    t = phase("compile_step_s", t)

    # ---- the loop's body ------------------------------------------
    losses: List[float] = []
    step_no = 0
    dispatched = 0
    in_flight: List[Any] = []   # the metrics of the step not read yet
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace. A
        step's loss is read, checked and reported while the NEXT step
        runs (every step still is, one step later), so the device does
        not wait for the host between steps; `drain` reads the last one
        (PERF.md section 6, PR 50: a read before the dispatch measures
        the host)."""
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
        nonlocal step_no
        with jax.profiler.TraceAnnotation("report"):
            loss = float(jax.device_get(metrics["loss"]))  # the host read
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss})
        losses.append(loss)

    # ---- warm-up: every shape the window uses ---------------------
    for _ in range(int(mix["warmup_steps"])):
        one_step(report=False)
    drain(report=False)
    train.report({"step": step_no, "loss": losses[-1], "warmup": True})
    t = phase("warmup_s", t)
    compiles_before = len(compiles)

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
            "flops_per_token": flops_gdn.train_flops_per_token(
                model, seq, cfg.gdn_chunk),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "attention_call": {
                "batch": batches.sequences // batch_devices,
                "heads": model["num_attention_heads"],
                "kv_heads": model["num_key_value_heads"], "seq": seq,
                "head_dim": model["head_dim"]},
            "delta_call": flops_gdn.delta_call(
                model, tokens_here, cfg.gdn_chunk, bool(tr_cfg["remat"]),
                delta_impl),
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff},
        "trace": reduced,
    }
    train.report(record)
