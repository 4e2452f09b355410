"""Job kind `train_lm_sambay`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a SambaY decoder-hybrid-decoder (`model_type:
phi4flash`): Mamba-1 mixers, differential attention under a window, a full
mask or as cross-attention, gated memory units, each followed by a dense
MLP, with one mixer's scan output and one attention layer's keys and
values read by every later layer of the cross-decoder.

The driver side, the loop's shape, the window, the clock and the record's
keys are `train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it);
what is written here is what the model forces: the config mapping, the
parameters in the reference's layout, the stand-in weights,
`benchlib.flops_sambay`, what `static` says of each attention call and of
the scan. A `benchmark` issue should fold the five files' loops (ROADMAP
D10).

- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): query weights scaled so that attention is
  peaked and a window ignored shows, every norm's gain off 1 and every
  bias off 0 (LayerNorm's, the projections', the convolution's) so that
  one left out shows;
- differential attention's `lambda_init` is a constant of the layer's
  published place and a buffer in the program: `Transformer.frozen` goes
  to `make_train_step`;
- `correct`: the parameter count three ways; logits and step-0 loss
  against `reference/phi4flash_f32.py` on the seeded sample; the attention
  kernels in the compiled step; the loss finite and lower at the end; no
  compile inside the window;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks, are
  refused before the cluster starts.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Dict, Iterator, List, Tuple

from benchlib.spec import load_module

_train_lm = load_module("jobs", "train_lm")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
NEEDS = ("layer_pattern", "ssm_d_inner", "ssm_dt_rank", "attn_window",
         "diff_attention", "layer_index_offset", "attn_bias", "norm")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run are refused,
    not silently ignored."""
    lacking = {
        "hidden_act": ("silu", "another activation than silu"),
        "mlp_bias": (False, "a bias in the MLP"),
        "lm_head_bias": (False, "a bias on the head"),
        "tie_word_embeddings": (True, "an untied head in this job"),
        "embd_pdrop": (0, "dropout"),
        "resid_pdrop": (0, "dropout"),
        "mb_per_layer": (2, "another spacing of the mixers than every "
                            "second layer"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if set(model["layer_kinds"]) - set("mswfgc"):
        raise ValueError("the family's layers are m, s, w, f, g and c")
    if len(model["layer_kinds"]) != model["num_hidden_layers"]:
        raise ValueError("layer_kinds names every layer held")


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


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's
    TransformerConfig."""
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    mamba = model["mamba"]
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        layer_pattern=model["layer_kinds"],
        layer_index_offset=model["first_layer_index"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], rope=False,
        diff_attention=True, attn_bias=True,
        attn_window=model["sliding_window"],
        d_ff=model["intermediate_size"], max_seq_len=seq,
        norm="layernorm", norm_eps=model["layer_norm_eps"],
        tie_embeddings=True, ssm_d_inner=mamba["d_inner"],
        ssm_state=mamba["d_state"], ssm_dt_rank=mamba["dt_rank"],
        ssm_conv_kernel=mamba["d_conv"], ssm_chunk=train["scan_chunk"],
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def sublayers(runs, cfg) -> Iterator[Tuple[str, Dict[str, Any]]]:
    """(kind, leaves) of every layer in the model's order, from the
    program's runs of stacked blocks."""
    for (block, repeats), subs in zip(cfg.pattern_runs, runs):
        for j in range(repeats):
            for kind, sub in zip(block, subs):
                yield kind, {name: leaf[j] for name, leaf in sub.items()}


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's stacked parameters as the published layout
    (`y = x W^T`, one dict per layer) the reference takes. The program
    keeps the query heads in the order (key pair, map, query pair of the
    two that share it), the published order is (query pair, map)."""
    import jax.numpy as jnp

    d, hd = cfg.d_model, cfg.head_dim
    per = cfg.n_heads // cfg.kv_heads

    def published_q(leaf):   # [..., (g, j, r), hd] -> [..., (g, r, j) hd]
        lead = leaf.shape[:-2]
        turned = leaf.reshape(lead + (-1, 2, per, hd))
        return jnp.swapaxes(turned, -3, -2).reshape(lead + (-1,))

    def norm(sub, name):
        return {"weight": sub[name], "bias": sub[name + "_bias"]}

    layers = []
    for kind, sub in sublayers(params["runs"], cfg):
        first = next(n for n in ("ssm_norm", "attn_norm", "gmu_norm")
                     if n in sub)
        lw = {"input_layernorm": norm(sub, first),
              "post_attention_layernorm": norm(sub, "mlp_norm"),
              "gate_up_proj": jnp.concatenate(
                  [sub["w_gateup"][:, 0], sub["w_gateup"][:, 1]], axis=1).T,
              "down_proj": sub["w_down"].T}
        if kind in "ms":
            lw.update(in_proj=sub["w_in"].T, conv1d=sub["conv_w"],
                      conv1d_bias=sub["conv_b"], x_proj=sub["w_x"].T,
                      dt_proj=sub["w_dt"].T, dt_proj_bias=sub["dt_bias"],
                      A_log=sub["A_log"], D=sub["D"],
                      out_proj=sub["w_out"].T)
        elif kind == "g":
            lw.update(in_proj=sub["w_gmu_in"].T, out_proj=sub["w_gmu_out"].T)
        else:
            q, bq = published_q(sub["wq"]), published_q(sub["bq"])
            if kind == "c":
                lw.update(Wq=q.T, Wq_bias=bq)
            else:
                kv, bkv = sub["wkv"], sub["bkv"]
                lw.update(
                    Wqkv=jnp.concatenate(
                        [q, kv[:, 0].reshape(d, -1),
                         kv[:, 1].reshape(d, -1)], axis=1).T,
                    Wqkv_bias=jnp.concatenate(
                        [bq, bkv[0].reshape(-1), bkv[1].reshape(-1)]))
            lw.update(out_proj=sub["wo"].reshape(-1, d).T,
                      out_proj_bias=sub["bo"], subln=sub["subln"],
                      **{name: sub[name] for name in (
                          "lambda_q1", "lambda_k1", "lambda_q2",
                          "lambda_k2")})
        layers.append(lw)
    return {"embed_tokens": params["embed"], "layers": layers,
            "final_layernorm": {"weight": params["final_norm"],
                                "bias": params["final_norm_bias"]}}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights (A, dt and D from the published initialiser there), with what
    the comparison needs to see each mechanism (the configuration's
    `assumed.initializer` has the readings):

    - the query weights times `q_gain`: the scores spread, attention picks
      few keys instead of averaging the context, and what a window cuts
      off shows;
    - every norm gain, the pair norm's among them, drawn around 1 with
      `norm_gain_std` (a gain of exactly 1 hides a norm left out) and
      every bias (LayerNorm's, the projections', the convolution's) drawn
      around 0 with `bias_std` (zero would hide it left out).
    """
    import jax

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)

    def redrawn(leaf, k, mean, std):
        return (mean + std * jax.random.normal(k, leaf.shape)).astype(
            leaf.dtype)

    gains = ("ssm_norm", "attn_norm", "gmu_norm", "mlp_norm", "subln")
    biases = tuple(g + "_bias" for g in gains[:4]) + (
        "bq", "bkv", "bo", "conv_b")
    n = 0
    for subs in params["runs"]:
        for sub in subs:
            n += 1
            k = jax.random.fold_in(key, 4000 + n)
            for i, name in enumerate(gains + biases):
                if name in sub:
                    sub[name] = redrawn(
                        sub[name], jax.random.fold_in(k, i),
                        *((1.0, init["norm_gain_std"]) if name in gains
                          else (0.0, init["bias_std"])))
            if "wq" in sub:
                sub["wq"] = sub["wq"] * init["q_gain"]
    k = jax.random.fold_in(key, 3999)
    params["final_norm"] = redrawn(params["final_norm"], k, 1.0,
                                   init["norm_gain_std"])
    params["final_norm_bias"] = redrawn(
        params["final_norm_bias"], jax.random.fold_in(k, 1), 0.0,
        init["bias_std"])
    return params


def static_calls(model: Dict[str, Any], seq: int, batch: int,
                 remat: bool) -> Dict[str, Any]:
    """What `static` says of each attention call of a step (its mask, its
    widths, its head counts) and of the scan."""
    from benchlib import flops_sambay

    hd = flops_sambay.head_dim(model)
    return {
        "attention_calls": [
            {"layer": i, "kind": flops_sambay.ATTENTION_KINDS[kind],
             "mask": "causal" if kind != "w" else
             f"causal, i - j < {model['sliding_window']}",
             "batch": batch, "seq": seq,
             "query_heads": model["num_attention_heads"],
             "key_heads": model["num_key_value_heads"],
             "value_heads": model["num_key_value_heads"] // 2,
             "qk_dim": hd, "v_dim": 2 * hd}
            for i, kind in enumerate(model["layer_kinds"])
            if kind in flops_sambay.ATTENTION_KINDS],
        # the kernels' names, for the readers that tell their events
        # apart by the `attention/<kind>` of their paths
        "attention_kernels": model.get("kernels", {}).get("attn", {}),
        "scan_call": {
            "model": {k: model[k] for k in ("layer_kinds", "mamba")},
            "tokens": batch * seq, "remat": remat},
    }


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
    from benchlib import flops_sambay
    from benchlib.checks import (Checks, attention_as_expected,
                                 kernel_calls)
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

    # a sliced vocabulary is a smaller vocabulary: ids from the slice
    batches = TokenBatches(mix, model["vocab_size"], config["seed"])
    seq = batches.tokens
    cfg = transformer_config(model, tr_cfg, seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]))
    batch_devices = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)

    # ---- weights from the seed, on the device, into their shardings --
    specs = Transformer.param_specs(cfg)
    frozen = Transformer.frozen(cfg)        # lambda_init: a buffer
    key = jax.random.key(config["seed"])
    shapes = jax.eval_shape(lambda k: init_params(k, cfg, model["init"]),
                            key)
    shardings = jax.tree.map(
        lambda spec, leaf: logical_sharding(spec, mesh, shape=leaf.shape),
        specs, shapes, is_leaf=lambda x: isinstance(x, tuple))
    params = jax.jit(lambda k: init_params(k, cfg, model["init"]),
                     out_shardings=shardings)(key)
    jax.block_until_ready(params)
    # three counts: the leaves that are trained, the program's formula,
    # the benchmark's
    n_params = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    n_shapes = sum(math.prod(x.shape) for x, keep in zip(
        jax.tree.leaves(shapes), jax.tree.leaves(frozen)) if not keep)
    counts = [n_params, n_shapes, cfg.num_params,
              flops_sambay.total_params(model)]
    checks.add("param_count", len(set(counts)) == 1, counts)
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
    # op by op, not under one jit: the reference's plain loops compile one
    # block of queries and one recurrence once
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
    per_chip = batches.sequences // batch_devices
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
            "flops_per_token": flops_sambay.train_flops_per_token(
                model, seq),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "collectives_in_step": collectives,
            # for the readers that know one causal shape a cell
            # (attn_kernel_roofline): the shape under which their count
            # is not above what this model's calls computed
            "attention_call": flops_sambay.attention_call_not_above(
                model, seq),
            **static_calls(model, seq, per_chip, bool(tr_cfg["remat"])),
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff},
        "trace": reduced,
    }
    train.report(record)
