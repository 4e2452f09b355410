"""Job kind `train_lm_granite_packed`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a Granite 4.0-H hybrid (`model_type:
granitemoehybrid` without routed experts: every layer a Mamba-2 mixer or
GQA attention without positions FOLLOWED by a gated MLP, four muP scalars,
a tied embedding) on PACKED documents: a step's sequence is documents laid
end to end with no padding, and the batch carries `segment_ids` beside
`tokens`, which the program hands to every sublayer that mixes positions.

The driver side, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it,
`sublayers` from `train_lm_ssm_moe`); the loop's shape is `train_lm_gdn`'s
(a step's metrics are read while the next step runs; its `one_step` /
`drain` are closures that cannot be imported, so the loop is written here
once more: a `benchmark` issue should fold the files' loops, ROADMAP D10).
What is written here is what the model and the traffic force:

- the documents (`PackedBatches`): lengths drawn from the traffic's law
  (log-normal, clipped) in seeded order and laid end to end over the
  step's tokens + 1, the last cut where the sequence ends; the token ids
  are `benchlib.traffic.TokenBatches`'s; a pure function of (seed, step);
- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): the program's initialiser, with every norm
  gain (the gated norm's among them) drawn around 1 so that a norm left
  out or taken a head at a time shows, the convolution's bias drawn off
  zero, and the query weights times `q_gain` so that attention under the
  published softmax scale 1/64 picks keys instead of averaging them;
- `correct`: the parameter count four ways; logits and loss of the timed
  path's own program (`Transformer.apply` / `Transformer.loss` with
  `segment_ids`) on one packed sequence of the step's own law against
  `reference/granite_hybrid_f32.py`, which knows no segment and runs each
  document alone; the step's counters (documents, trained labels, the
  pairs the document mask needs) equal to the host's own count of the
  batch it drew, every step; the attention kernels in the compiled step
  and the scan's implementation the one the configuration expects, its
  kernels in the compiled step; the loss finite and lower at the end; no
  compile inside the window (other boundaries compile nothing);
- a program whose `TransformerConfig` or `Transformer.loss` lacks what
  this configuration needs, and a configuration with a mechanism the
  program lacks, are refused before the cluster starts.
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
NEEDS = ("embed_scale", "residual_scale", "attn_scale", "logit_divisor",
         "ssm_heads", "ssm_groups", "ssm_state", "ssm_chunk")
HONOURED = {   # every key of the published config this job reads
    "attention_bias", "attention_multiplier", "embedding_multiplier",
    "hidden_act", "hidden_size", "intermediate_size", "layer_types",
    "logits_scaling", "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
    "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_groups",
    "mamba_n_heads", "mamba_proj_bias", "max_position_embeddings",
    "model_type", "normalization_function", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_local_experts", "position_embedding_type", "residual_multiplier",
    "rms_norm_eps", "rope_scaling", "rope_theta",
    "shared_intermediate_size", "tie_word_embeddings", "vocab_size"}
OURS = {"source", "source_file", "job", "reference", "reduced", "stands_for",
        "assumed", "init", "train", "layout", "kernels", "tolerance",
        "rehearsal"}


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run, readings
    of the row that this job does not take and keys it does not know are
    refused, not silently ignored."""
    unknown = set(model) - HONOURED - OURS
    if unknown:
        raise ValueError(f"keys this job does not honour: {sorted(unknown)}")
    needed = {
        "model_type": ("granitemoehybrid", "another family"),
        "num_local_experts": (0, "routed experts in this job"),
        "num_experts_per_tok": (0, "routed experts in this job"),
        "hidden_act": ("silu", "another MLP than the SiLU-gated one"),
        "normalization_function": ("rmsnorm", "another norm"),
        "position_embedding_type": ("nope", "a position embedding in this "
                                            "family's attention"),
        "rope_scaling": (None, "a scaled rotary embedding"),
        "attention_bias": (False, "a bias on attention's projections"),
        "mamba_proj_bias": (False, "a bias on the mixer's projections"),
        "mamba_conv_bias": (True, "a convolution without its bias"),
        "tie_word_embeddings": (True, "an untied head in this job"),
    }
    for key, (have, what) in needed.items():
        if model[key] != have:
            raise ValueError(f"{key} = {model[key]!r}: the job runs no "
                             f"{what}")
    if model["shared_intermediate_size"] != model["intermediate_size"]:
        raise ValueError("the MLP's width is shared_intermediate_size, "
                         "published equal to intermediate_size")
    if model["mamba_expand"] * model["hidden_size"] \
            != model["mamba_n_heads"] * model["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is the mixer's inner "
                         "width, mamba_n_heads x mamba_d_head")
    if set(model["layer_types"]) - {"mamba", "attention"} \
            or len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError(f"layer_types {model['layer_types']!r}: "
                         f"num_hidden_layers of mamba and attention")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import inspect

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import FFN_KINDS, TransformerConfig

    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if "n" not in FFN_KINDS:
        missing.append("layer_pattern kind n (a Mamba-2 mixer then an MLP)")
    if "segment_ids" not in inspect.signature(Transformer.apply).parameters:
        missing.append("segment_ids (packed documents)")
    if missing:
        raise RuntimeError(
            f"this program has no {missing}: it cannot run a Granite "
            f"4.0-H hybrid on packed documents ({ctx['cell']['name']})")
    refuse_what_the_program_lacks(ctx["config"])
    if ctx["traffic"].get("documents") is None:
        raise ValueError("this job packs documents: the traffic mix names "
                         "no `documents` law")
    return _train_lm.run(ctx)


# ---------------------------------------------------------------------
# the traffic: documents laid end to end
# ---------------------------------------------------------------------


class PackedBatches:
    """`TokenBatches`' ids with documents over them. Every sequence of a
    step is documents laid end to end with no padding over tokens + 1
    positions (inputs and the last label): lengths from the mix's
    `documents` law (`lognormal`: exp(N(ln median, sigma)) rounded,
    clipped to [min, max]) in the order drawn, the last cut where the
    sequence ends. A pure function of (seed, stream, index), as the ids
    are."""

    DOCS = 0xD0C5   # the streams of the documents' own draws

    def __init__(self, mix: Dict[str, Any], vocab_size: int, seed: int):
        from benchlib.traffic import TokenBatches

        law = mix["documents"]
        if law["law"] != "lognormal":
            raise ValueError(f"unknown documents law {law['law']!r}")
        self.ids = TokenBatches(mix, vocab_size, seed)
        self.law = law
        self.seed = int(seed)
        self.sequences, self.tokens = self.ids.sequences, self.ids.tokens
        self.tokens_per_step = self.ids.tokens_per_step
        self.unigram_entropy_nats = self.ids.unigram_entropy_nats

    def lengths(self, stream: int, index: int, sequences: int,
                total: int) -> List[List[int]]:
        """Each sequence's document lengths over `total` positions."""
        import numpy as np

        rng = np.random.default_rng([self.seed, self.DOCS, stream, index])
        law, out = self.law, []
        for _ in range(sequences):
            row, left = [], total
            while left > 0:
                n = int(np.clip(np.rint(rng.lognormal(
                    math.log(law["median"]), law["sigma"])),
                    law["min"], law["max"]))
                row.append(min(n, left))
                left -= row[-1]
            out.append(row)
        return out

    @staticmethod
    def segment_ids(lengths: List[List[int]]):
        """int32 [sequences, total]: document j of a row is the id j."""
        import numpy as np

        return np.stack([np.repeat(np.arange(len(row), dtype=np.int32), row)
                         for row in lengths])

    def batch(self, step: int):
        """(tokens, segment_ids, lengths) of step `step`: [sequences,
        tokens + 1] each (inputs and next-token targets overlap by
        one)."""
        lengths = self.lengths(1, step, self.sequences, self.tokens + 1)
        return self.ids.batch(step), self.segment_ids(lengths), lengths

    def reference_sample(self, sequences: int, tokens: int):
        lengths = self.lengths(2, 0, sequences, tokens + 1)
        return (self.ids.reference_sample(sequences, tokens),
                self.segment_ids(lengths), lengths)


def counted(lengths: List[List[int]]) -> Dict[str, int]:
    """What the step's counters must read for a batch of these documents
    (over tokens + 1 positions): the documents among the inputs, the
    labels that cross no boundary, the pairs the document mask needs a
    head."""
    docs = labels = pairs = 0
    for row in lengths:
        inputs = row[:-1] + ([row[-1] - 1] if row[-1] > 1 else [])
        docs += len(inputs)
        labels += sum(n - 1 for n in row)
        pairs += sum(n * (n + 1) // 2 for n in inputs)
    return {"packed_docs": docs, "packed_labels": labels,
            "packed_attn_pairs_needed": pairs}


# ---------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------


def transformer_config(model: Dict[str, Any], train: Dict[str, Any],
                       seq: int):
    """The published config.json keys onto the program's TransformerConfig:
    `layer_types` as the kinds `n` and `l`, no rotary embedding, the four
    muP scalars."""
    from benchlib import flops_granite
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    if seq % model["mamba_chunk_size"]:
        raise ValueError(f"sequences of {seq} tokens are no whole chunks "
                         f"of {model['mamba_chunk_size']}")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        layer_pattern=flops_granite.layer_pattern(model),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], rope=False,
        d_ff=model["shared_intermediate_size"], max_seq_len=seq,
        norm_eps=model["rms_norm_eps"], tie_embeddings=True,
        ssm_heads=model["mamba_n_heads"],
        ssm_head_dim=model["mamba_d_head"],
        ssm_groups=model["mamba_n_groups"],
        ssm_state=model["mamba_d_state"],
        ssm_conv_kernel=model["mamba_d_conv"],
        ssm_chunk=model["mamba_chunk_size"],
        embed_scale=float(model["embedding_multiplier"]),
        residual_scale=float(model["residual_multiplier"]),
        attn_scale=float(model["attention_multiplier"]),
        logit_divisor=float(model["logits_scaling"]),
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer) the reference takes."""
    import jax.numpy as jnp

    d = cfg.d_model
    layers = []
    for kind, sub in sublayers(params["runs"], cfg):
        if kind == "n":
            lw = {"input_layernorm": sub["ssm_norm"],
                  "in_proj": sub["w_in"].T, "conv1d": sub["conv_w"],
                  "conv1d_bias": sub["conv_b"], "dt_bias": sub["dt_bias"],
                  "A_log": sub["A_log"], "D": sub["D"],
                  "mixer_norm": sub["gate_norm"],
                  "out_proj": sub["w_out"].T}
        else:
            lw = {"input_layernorm": sub["attn_norm"],
                  "q_proj": sub["wq"].reshape(d, -1).T,
                  "k_proj": sub["wkv"][:, 0].reshape(d, -1).T,
                  "v_proj": sub["wkv"][:, 1].reshape(d, -1).T,
                  "o_proj": sub["wo"].reshape(-1, d).T}
        lw.update(
            post_attention_layernorm=sub["mlp_norm"],
            # gate rows, then up rows
            input_linear=jnp.concatenate([sub["w_gateup"][:, 0].T,
                                          sub["w_gateup"][:, 1].T]),
            output_linear=sub["w_down"].T)
        layers.append(lw)
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"]}


GAINS = ("ssm_norm", "attn_norm", "mlp_norm", "gate_norm", "final_norm")


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights (the embedding's 0.02, A uniform in [1, 16], dt log-uniform in
    [0.001, 0.1] through softplus's inverse and D 1 from the published
    initialiser there), with what the comparison needs to see every term
    (the configuration's `assumed.initializer` has the readings):

    - every norm gain, the gated norm's and the final one among them,
      drawn around 1 with `norm_gain_std` (a gain of exactly 1 hides a
      norm left out; a gain that differs a channel shows a norm taken
      over the wrong channels);
    - the convolution's bias drawn with `conv_bias_std` (zero would hide
      it left out), D drawn around 1 with `d_skip_std`;
    - the query weights times `q_gain`: under the published softmax
      scale 1/64 the scores of unit queries and keys spread by 0.125 and
      attention averages its document; the gain makes it pick keys, so
      that the scale, a rotary embedding and a key of another document
      show in its output.
    """
    import jax

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)

    def around(leaf, std, k):
        return (leaf + std * jax.random.normal(k, leaf.shape)).astype(
            leaf.dtype)

    params["final_norm"] = around(params["final_norm"],
                                  init["norm_gain_std"],
                                  jax.random.fold_in(key, 28))
    n = 0
    for subs in params["runs"]:
        for sub in subs:
            n += 1
            k = jax.random.fold_in(key, 6000 + n)
            for name in GAINS:
                if name in sub:
                    sub[name] = around(sub[name], init["norm_gain_std"],
                                       jax.random.fold_in(k, len(name)))
            if "conv_b" in sub:
                sub["conv_b"] = around(0 * sub["conv_b"],
                                       init["conv_bias_std"],
                                       jax.random.fold_in(k, 1))
                sub["D"] = around(sub["D"], init["d_skip_std"],
                                  jax.random.fold_in(k, 2))
            if "wq" in sub:
                sub["wq"] = sub["wq"] * init["q_gain"]
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
    from benchlib import flops_granite
    from benchlib.checks import Checks, attention_as_expected, kernel_calls
    from benchlib.peaks import peaks_for
    from ray_tpu.models import Transformer
    from ray_tpu.ops.attention import causal_block_pairs
    from ray_tpu.ops.ssm import ssd_scan_impl
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
    batches = PackedBatches(mix, model["vocab_size"], config["seed"])
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
               n_params == flops_granite.total_params(model)
               == cfg.num_params == shaped_params,
               [n_params, flops_granite.total_params(model), cfg.num_params,
                shaped_params])
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory: the timed path's own program, segment_ids
    # and all, on one packed sequence of the step's law --------------
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample, sample_ids, sample_lengths = batches.reference_sample(
        sample_cfg["sequences"], sample_cfg["tokens"])
    sample_dev, ids_dev = jnp.asarray(sample), jnp.asarray(sample_ids)
    sys_logits = jax.jit(lambda p, x, ids: Transformer.apply(
        p, x, cfg, mesh=mesh, segment_ids=ids))(
            params, sample_dev[:, :-1], ids_dev[:, :-1])
    sys_loss, sys_counters = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, with_metrics=True))(
            params, {"tokens": sample_dev, "segment_ids": ids_dev})

    # op by op, not under one jit (train_lm_moe.py); the reference is
    # handed the documents' lengths and no id: it runs each alone
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits = reference.forward(
        weights, sample_dev[:, :-1], model,
        reference.input_lengths(sample_lengths, sample.shape[1]))
    ref_loss = reference.next_token_loss(ref_logits, sample_dev,
                                         sample_lengths)
    del weights
    diff = sys_logits.astype(jnp.float32) - ref_logits
    rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                            / jnp.sum(ref_logits * ref_logits)))
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape),
                "documents": [len(row) for row in sample_lengths]})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": float(ref_loss),
                "allowed": tol["loss_abs"]})
    sample_counted = {k: int(v) for k, v in sys_counters.items()}
    checks.add("reference_sample_counters",
               sample_counted == counted(sample_lengths),
               {"program": sample_counted,
                "host": counted(sample_lengths)})
    del sys_logits, ref_logits, diff, sample_dev, ids_dev
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
    host_counts: Dict[int, Dict[str, int]] = {}

    def put(step: int):
        """The step's batch on the device; what its counters must read
        is kept for the step's own report."""
        tokens, ids, lengths = batches.batch(step)
        host_counts[step] = counted(lengths)
        return {"tokens": jax.device_put(tokens, batch_sharding),
                "segment_ids": jax.device_put(ids, batch_sharding)}

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
    scan_calls = kernel_calls(hlo, kernels.get("scan", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls})
    scan_impl = ssd_scan_impl(mesh, seq, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk)
    want_scan = tr_cfg["expect_scan"]
    checks.add("scan_impl_as_expected",
               scan_impl == want_scan and (want_scan != "pallas" or (
                   scan_calls.get("fwd", 0) > 0
                   and scan_calls.get("bwd", 0) > 0)),
               {"resolved": scan_impl, "expected": want_scan,
                "calls": scan_calls})
    print(f"[bench] the scan runs as {scan_impl!r}, attention as {impl!r}",
          flush=True)
    t = phase("compile_step_s", t)

    # ---- the loop's body ------------------------------------------
    losses: List[float] = []
    counters: List[Dict[str, int]] = []   # per step, as the program read
    miscounted: List[int] = []
    step_no = 0
    dispatched = 0
    in_flight: List[Any] = []   # the metrics of the step not read yet
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace. A
        step's loss and counters are read, checked and reported while
        the NEXT step runs (every step still is, one step later), so the
        device does not wait for the host between steps; `drain` reads
        the last one (PERF.md section 6, PR 50)."""
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
            got = jax.device_get({k: metrics[k] for k in (
                "loss", "packed_docs", "packed_labels",
                "packed_attn_pairs_needed")})      # the one host read
            loss = float(got.pop("loss"))
            got = {k: int(v) for k, v in got.items()}
            if got != host_counts.pop(step_no):
                miscounted.append(step_no)
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss, **got})
        losses.append(loss)
        counters.append(got)

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
    window = counters[warmup_steps:window_steps]

    def mean_of(name: str, steps) -> float:
        return sum(c[name] for c in steps) / max(len(steps), 1)

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
    checks.add("counters_equal_the_batches", not miscounted,
               {"steps_off": miscounted[:8], "steps": len(losses)})

    bdev.finish_device(device, reduced)
    # what the kernels' blocks compute a head and step, whatever the
    # documents: the causal blocks of the whole sequence
    pairs_computed = batches.sequences * causal_block_pairs(
        seq, cfg.head_dim) if impl == "flash" else None
    tokens_here = batches.tokens_per_step // batch_devices
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
            # beside tokens/s, which counts every position of a step
            "trained_labels_per_s": mean_of("packed_labels", window)
            * len(step_s) / window_s,
        },
        "static": {
            "model": {k: v for k, v in model.items()
                      if isinstance(v, (int, float, bool))},
            "chips": len(devices),
            "peaks": peaks,
            "flops_per_token": flops_granite.train_flops_per_token(
                model, batches.tokens_per_step,
                mean_of("packed_attn_pairs_needed", window)),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "scan_impl": scan_impl,
            "attention_call": {
                "batch": batches.sequences // batch_devices,
                "heads": model["num_attention_heads"],
                "kv_heads": model["num_key_value_heads"], "seq": seq,
                "head_dim": cfg.head_dim},
            "packed_scan_call": {
                "model": {k: model[k] for k in (
                    "layer_types", "mamba_n_heads", "mamba_d_head",
                    "mamba_n_groups", "mamba_d_state", "mamba_chunk_size")},
                "tokens": tokens_here, "remat": bool(tr_cfg["remat"])},
            "packed_attn_pairs_computed": pairs_computed,
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff,
                     "packed_docs": [c["packed_docs"] for c in window],
                     "packed_labels": [c["packed_labels"] for c in window],
                     "packed_attn_pairs_needed": [
                         c["packed_attn_pairs_needed"] for c in window],
                     "traced": counters[window_steps:]},
        "trace": reduced,
    }
    train.report(record)
