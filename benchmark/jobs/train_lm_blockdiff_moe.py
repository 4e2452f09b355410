"""Job kind `train_lm_blockdiff_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a sparse-expert decoder with plain GQA and a
per-head QK-norm trained as a block-diffusion model (`model_type:
sdar_moe`), of which this chip holds a share: some of each layer's
experts and a slice of the vocabulary (the configuration file's `share`).

The driver side, the loop, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it; a
`benchmark` issue should fold the job files, ROADMAP D10). `worker_loop`
is `train_lm_mla_moe`'s as far as the config mapping, the parameters in
the reference's layout, `benchlib.flops_blockdiff_moe` and the counters
force another. What it adds:

- the objective is the program's: the host draws tokens and one key a
  sequence, `ray_tpu.models.diffusion.noised` makes the noised copy and
  the weights inside the jitted step, `Transformer.loss` the doubled
  stream under the block-diffusion mask. A step's tokens are its 8,192
  DATA tokens: `train_tokens_per_s` never counts the 16,384 positions;
- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): a unit embedding, norm gains off 1, a query
  gain that makes attention peaked, heads of unlike scale, a same-token
  head and a positional head a key group, and the held experts' router
  columns scaled until the chip gets its eighth of the slots
  (`balance_held_share`);
- the step's metrics carry the held experts' counts, the slots routed
  elsewhere, which branch `row_bound` took and the masked positions'
  count; the loop reads them with the loss in one host read;
- `correct` adds: the parameter count four ways; logits at the L read
  positions and the loss of the system against `reference/sdar_f32.py`
  given the same share, the same noised sample and the same weights, on a
  sample that reaches every held expert; in every step no slot dropped
  and held + elsewhere = positions x k a layer; every step's count of
  masked positions equal to what the host computes from the same keys; the
  window's median held share in a band around held / E over the layers
  after the first (the first layer's is reported: a quarter of its
  positions carry the mask token's embedding and choose alike); the
  kernels the configuration expects in the compiled step;
- a program whose `TransformerConfig` lacks the fields this configuration
  needs, and a configuration with a mechanism the program lacks, are
  refused before the cluster starts.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import statistics
import time
from typing import Any, Dict, List

from benchlib.spec import load_module

_train_lm = load_module("jobs", "train_lm")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
IN_FLIGHT = 6    # steps dispatched and not yet read (`one_step`)
NEEDS = ("block_length", "mask_token_id", "diffusion_t_min",
         "qk_norm_per_head", "moe_experts_held", "moe_expert_offset")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run are refused,
    not silently ignored."""
    lacking = {
        "use_sliding_window": (False, "window under the block-diffusion "
                                      "mask"),
        "mlp_only_layers": ([], "dense MLP layer among the expert layers"),
        "decoder_sparse_step": (1, "sparse step other than 1"),
        "rope_scaling": (None, "scaled RoPE"),
        "hidden_act": ("silu", "activation other than silu"),
        "attention_bias": (False, "bias in the projections"),
        "router_aux_loss_coef": (0, "aux loss over a held share"),
    }
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")
    if not model.get("block_length"):
        raise ValueError("a block-diffusion job needs block_length")


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
    """The published config.json keys onto the program's TransformerConfig.
    The router is sized from the published expert count; the top-level
    `num_experts` and `vocab_size` are what this chip holds. `seq`: the
    data tokens of a sequence; the stream is twice as long."""
    from benchlib import flops_blockdiff_moe
    from ray_tpu.models.configs import TransformerConfig

    refuse_what_the_program_lacks(model)
    if seq > model["max_position_embeddings"]:
        raise ValueError(f"sequences of {seq} tokens exceed the context")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        d_ff=model["moe_intermediate_size"], max_seq_len=2 * seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings")),
        qk_norm=True, qk_norm_per_head=True,
        moe_experts=flops_blockdiff_moe.router_experts(model),
        moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_scoring="softmax", moe_aux_coeff=0.0,
        moe_experts_held=model["num_experts"],
        moe_expert_offset=model.get("share", {}).get("expert_offset", 0),
        block_length=model["block_length"],
        mask_token_id=model["mask_token_id"],
        diffusion_t_min=model["diffusion_t_min"],
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"])


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer, the held experts by their ids) the
    reference takes."""
    d = cfg.d_model
    lay = params["layers"]
    layers = []
    for i in range(cfg.n_layers):
        layers.append({
            "input_layernorm": lay["attn_norm"][i],
            "q_proj": lay["wq"][i].reshape(d, -1).T,
            "k_proj": lay["wkv"][i][:, 0].reshape(d, -1).T,
            "v_proj": lay["wkv"][i][:, 1].reshape(d, -1).T,
            "q_norm": lay["q_norm"][i], "k_norm": lay["k_norm"][i],
            "o_proj": lay["wo"][i].reshape(-1, d).T,
            "post_attention_layernorm": lay["mlp_norm"][i],
            "mlp.gate": lay["w_router"][i].T,
            "experts": {
                cfg.moe_expert_offset + e: {
                    "gate_proj": lay["w_moe_gateup"][i][e][:, 0].T,
                    "up_proj": lay["w_moe_gateup"][i][e][:, 1].T,
                    "down_proj": lay["w_moe_down"][i][e].T}
                for e in range(cfg.held_experts)}})
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": head}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms (the
    configuration's `assumed.initializer` has the reasons):

    - the embedding redrawn at `embed_std`;
    - every norm gain (the layers' two, the final one, the two QK-norm
      gains) drawn N(1, `norm_gain_std`), the query gain then times
      `q_gain`;
    - each query head's and each key head's projection scaled by its own
      lognormal factor (`head_scale_std`): the per-head QK-norm cancels
      it, a norm over the whole projection does not;
    - in every key group the first query head's projection mixed into the
      key head's at `key_tie` (before the scales): a same-token head;
    - in every key group the last query head made positional (`anchor`,
      `anchor_pairs`, `look`; below): its score peaks at the keys just
      after or just before the query, whatever their tokens.

    The last two give the heads what trained heads have and random ones
    lack: attention that a few keys' visibility decides. Each of the
    mask's faults adds or removes `block_length` keys among thousands.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)
    lay = params["layers"]

    def normal(n, shape, std=1.0):
        return std * jax.random.normal(jax.random.fold_in(key, n), shape)

    embed = params["embed"]
    params["embed"] = normal(27, embed.shape, init["embed_std"]).astype(
        embed.dtype)
    for n, (tree, name) in enumerate((
            (lay, "attn_norm"), (lay, "mlp_norm"), (lay, "q_norm"),
            (lay, "k_norm"), (params, "final_norm"))):
        gain = tree[name]
        tree[name] = (gain + normal(40 + n, gain.shape,
                                    init["norm_gain_std"])).astype(gain.dtype)
    lay["q_norm"] = lay["q_norm"] * init["q_gain"]
    group = cfg.n_heads // cfg.kv_heads
    tie = init["key_tie"]
    wq, wkv = lay["wq"], lay["wkv"]            # [l, d, H, hd], [l, d, 2, ..]
    wk = tie * wq[:, :, ::group] + (1.0 - tie * tie) ** 0.5 * wkv[:, :, 0]
    q_scale = jnp.exp(normal(50, (wq.shape[0], 1, cfg.n_heads, 1),
                             init["head_scale_std"]))
    k_scale = jnp.exp(normal(51, (wq.shape[0], 1, cfg.kv_heads, 1),
                             init["head_scale_std"]))
    wq, wk = wq * q_scale, wk * k_scale
    if init.get("anchor"):
        # a positional head a key group (the group's last query head): one
        # embedding column holds the same value in every row, that head's
        # query reads it alone, the group's key head reads it beside the
        # token, both into the fastest-turning rotary pairs, the query
        # turned `look` positions on: the head's score peaks at the keys
        # just after the query (even groups) or just before it (odd
        # groups), whatever their tokens. Nothing else that writes to the
        # stream reads the column (the values, the held experts, the
        # router, the head): a value every position shares would reach
        # every later router as one offset an expert, and all positions
        # would choose alike (seed 3300000302: 7,305 of a layer's 16,384
        # held slots on one expert, none on another).
        pairs, hd = init["anchor_pairs"], cfg.head_dim
        half = hd // 2
        theta = cfg.rope_theta ** (-jnp.arange(pairs) / half)
        turn = theta * init["look"] * jnp.where(
            jnp.arange(cfg.kv_heads) % 2, -1.0, 1.0)[:, None]
        k_row = jnp.zeros((hd,)).at[:pairs].set(1.0)
        q_row = jnp.zeros((cfg.kv_heads, hd)).at[:, :pairs].set(
            jnp.cos(turn)).at[:, half:half + pairs].set(jnp.sin(turn))
        anchor = init["anchor"]
        params["embed"] = params["embed"].at[:, 0].set(anchor)
        last = jnp.arange(group - 1, cfg.n_heads, group)
        wq = wq.at[:, :, last].set(0.0).at[:, 0, last].set(q_row)
        # as much of a key's energy as its token part has
        wk = wk.at[:, 0].set(k_row * (hd / pairs) ** 0.5 / anchor
                             * k_scale[:, 0])
        lay["w_router"] = lay["w_router"].at[:, 0].set(0.0)
        lay["w_moe_gateup"] = lay["w_moe_gateup"].at[:, :, 0].set(0.0)
        wkv = wkv.at[:, 0, 1].set(0.0)
        params["lm_head"] = params["lm_head"].at[0].set(0.0)
    lay["wq"] = wq.astype(lay["wq"].dtype)
    lay["wkv"] = wkv.at[:, :, 0].set(wk.astype(wkv.dtype))
    return params


def noise_keys(seed: int, stream: int, index: int, sequences: int):
    """One threefry key a sequence, `[sequences, 2]` uint32, a pure
    function of (seed, stream, index): what the host hands over beside the
    tokens (stream 1: the step `index`'s batch; 2: the reference sample;
    3: the balancing batch)."""
    import numpy as np

    return np.stack([
        np.full(sequences, (seed ^ (stream << 28)) & 0xFFFFFFFF),
        index * sequences + np.arange(sequences)], axis=1).astype(np.uint32)


def balance_held_share(params, cfg, mesh, batches, init: Dict[str, Any]):
    """The router as a trained model's balancing leaves it, for this
    chip's share: per layer ONE factor on the held experts' router
    columns (a factor above 1 spreads their logits, so they win and lose
    more often than the others; the top-8 of 128 reads the winners),
    found by bisection on its logarithm on one seeded noised batch of the
    step's shape, so that the held experts together receive held / E of
    the token-slots. Without it the draw decides: the mask token's and
    the most frequent token types' experts are held or not. Returns
    (params, what was done)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import Transformer

    rounds = int(init.get("balance_rounds", 0))
    if not rounds:
        return params, None
    batch = {
        "tokens": jnp.asarray(batches.draw(3, 0, batches.sequences,
                                           batches.tokens)),
        "noise_key": jnp.asarray(noise_keys(batches.seed, 3, 0,
                                            batches.sequences))}
    first, held = cfg.moe_expert_offset, cfg.held_experts
    base = params["layers"]["w_router"]
    target = 2 * batch["tokens"].size * cfg.moe_top_k * held \
        / cfg.moe_experts

    def with_factor(p, log2_factor):
        factor = jnp.exp2(jnp.asarray(log2_factor, base.dtype))
        router = base.at[:, :, first:first + held].multiply(
            factor[:, None, None])
        return dict(p, layers=dict(p["layers"], w_router=router))

    # the batch is an argument: as a constant of the program it would make
    # every seed a compile of its own
    count = jax.jit(lambda p, log2_factor, b: Transformer.loss(
        with_factor(p, log2_factor), b, cfg, mesh=mesh,
        with_metrics=True)[1]["moe_tokens_per_expert"].sum(-1))

    def held_slots(log2_factor):
        return np.asarray(count(params, log2_factor, batch))

    lo = np.full(base.shape[0], -float(init["balance_span"]))
    hi = -lo
    before = held_slots(0 * lo)
    for _ in range(rounds):
        mid = (lo + hi) / 2
        over = held_slots(mid) > target
        hi, lo = np.where(over, mid, hi), np.where(over, lo, mid)
    log2_factor = (lo + hi) / 2
    after = held_slots(log2_factor)
    return with_factor(params, log2_factor), {
        "log2_factor": log2_factor.tolist(), "target_slots": target,
        "held_slots_before": before.tolist(),
        "held_slots_after": after.tolist()}


def loss_weight_norm(weights) -> float:
    """`||w||_2 / (B L)` of a noised sample's loss weights: what an error
    of one unit in every masked position's log-likelihood, independent
    from position to position, moves the loss by (one standard
    deviation). The weights are 1/t with t down to 1e-3, so a sample's
    largest few decide how far rounding moves its loss, and a limit in
    nats would be loose for most samples and tight for a few: the loss's
    limit is in units of this."""
    import numpy as np

    w = np.asarray(weights, np.float64)
    return float(np.sqrt((w * w).sum()) / w.size)


@functools.lru_cache(maxsize=None)
def _noise_fn(cfg):
    """`diffusion.noised` under one `jax.jit` a configuration: a loop
    over the steps' batches traces it once."""
    import jax

    from ray_tpu.models import diffusion

    return jax.jit(lambda batch: diffusion.noised(batch, cfg))


def noised_sample(cfg, tokens, keys):
    """The program's noise on the host's side of a comparison: `tokens`
    `[B, L]` under `keys` `[B, 2]` -> `diffusion.noised`'s batch."""
    import jax.numpy as jnp

    return _noise_fn(cfg)({"tokens": jnp.asarray(tokens),
                           "noise_key": jnp.asarray(keys)})


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
    from benchlib import flops_blockdiff_moe
    from benchlib.checks import (Checks, attention_as_expected,
                                 grouped_matmul_as_expected, kernel_calls)
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer, diffusion, head
    from ray_tpu.ops.attention import block_table, flash_shape_ok
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

    # a sliced vocabulary is a smaller vocabulary, and its last row stands
    # in for the mask id: the traffic's ids come from the rows before it
    if model["mask_token_id"] != model["vocab_size"] - 1:
        raise ValueError("the mask id is the slice's last row")
    batches = TokenBatches(mix, model["vocab_size"] - 1, config["seed"])
    seq = batches.tokens                       # data tokens a sequence
    cfg = transformer_config(model, tr_cfg, seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]))
    batch_devices = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)
    n_experts, held, top_k = cfg.moe_experts, cfg.held_experts, cfg.moe_top_k
    n_layers = cfg.n_layers
    slots_per_layer = 2 * batches.tokens_per_step * top_k

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
    params, balanced = balance_held_share(params, cfg, mesh, batches,
                                          model["init"])
    jax.block_until_ready(params)
    counts_four_ways = [
        sum(int(x.size) for x in jax.tree.leaves(params)),
        sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(
            lambda k: Transformer.init(k, cfg), key))),
        cfg.num_params, flops_blockdiff_moe.total_params(model)]
    n_params = counts_four_ways[0]
    checks.add("param_count", len(set(counts_four_ways)) == 1,
               counts_four_ways)
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory: the same noised sample and weights to both
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample = batches.reference_sample(sample_cfg["sequences"],
                                      sample_cfg["tokens"])[:, :-1]
    noisy = noised_sample(cfg, sample, noise_keys(
        config["seed"], 2, 0, sample.shape[0]))
    sys_logits = jax.jit(lambda p, b: head.logits(
        p, Transformer.block_diffusion_hidden(p, b, cfg, mesh=mesh)[0],
        cfg, mesh=mesh))(params, noisy)
    sys_loss, sys_metrics = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, with_metrics=True))(params, noisy)

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loops over the experts and the query blocks compile one of each
    weights = jax.jit(lambda p: to_reference_layout(p, cfg))(params)
    ref_logits, chosen = reference.forward(
        weights, noisy["tokens"], noisy["targets"], model,
        with_routing=True, query_block=model.get("reference_query_block"))
    ref_loss = reference.masked_diffusion_loss(
        ref_logits, noisy["targets"], noisy["mask"])
    ref_counts = np.asarray(reference.tokens_per_expert(chosen, n_experts))
    del weights, chosen
    diff = sys_logits.astype(jnp.float32) - ref_logits
    rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                            / jnp.sum(ref_logits * ref_logits)))
    loss_diff = abs(float(sys_loss) - float(ref_loss))
    weight_norm = loss_weight_norm(noisy["mask"])
    sample_counts = np.asarray(sys_metrics["moe_tokens_per_expert"])
    first = cfg.moe_expert_offset
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape),
                "positions": 2 * sample.shape[1]})
    checks.add("reference_loss",
               loss_diff <= tol["loss_per_weight_norm"] * weight_norm,
               {"system": float(sys_loss), "reference": float(ref_loss),
                "difference_over_weight_norm": loss_diff / weight_norm,
                "allowed": tol["loss_per_weight_norm"],
                "weight_norm": weight_norm,
                "masked_positions": int(
                    sys_metrics["diffusion_masked_tokens"])})
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
    del sys_logits, ref_logits, diff, noisy, sys_metrics
    t = phase("reference_check_s", t)

    # ---- the step -------------------------------------------------
    opt = tr_cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, diffusion.noised(b, cfg), cfg,
                                      mesh=mesh, with_metrics=True),
        specs, mesh,
        optimizer=optax.adamw(opt["learning_rate"],
                              weight_decay=opt["weight_decay"]))
    state = init_state(params)
    del params
    batch_sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), None))

    def host_batch(step: int):
        return {"tokens": batches.draw(1, step, batches.sequences, seq),
                "noise_key": noise_keys(config["seed"], 1, step,
                                        batches.sequences)}

    def put(step: int):
        return jax.device_put(host_batch(step), batch_sharding)

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
    impl = Transformer.resolve_attention_impl(cfg, mesh, 2 * seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls),
               {"resolved": impl, "expected": want, "calls": attn_calls})
    positions = 2 * batches.tokens_per_step // batch_devices
    bound = row_bound(positions, top_k, held, n_experts, positions * top_k)
    gmm_impl = grouped_matmul_impl(mesh, bound or positions * top_k,
                                   cfg.d_model, cfg.ff_dim)
    want_gmm = tr_cfg["expect_grouped_matmul"]
    checks.add("grouped_matmul_impl", grouped_matmul_as_expected(
        gmm_impl, want_gmm, moe_calls),
        {"resolved": gmm_impl, "expected": want_gmm, "calls": moe_calls,
         "row_bound": bound})
    # what the mask leaves of the kernel's grid, from its block table
    mask_blocks = block_table(2 * seq, cfg.head_dim, cfg.block_length,
                              seq) if flash_shape_ok(2 * seq, cfg.head_dim) \
        else None
    t = phase("compile_step_s", t)

    # ---- the loop's body: the loss and the counters come back in one
    # host read -----------------------------------------------------
    losses: List[float] = []
    held_shares: List[float] = []
    held_slots: List[List[int]] = []     # per step, per layer
    bounded: List[List[int]] = []        # per step, per layer
    masked: List[int] = []               # per step
    weight_sums: List[float] = []
    dropped_total = 0
    elsewhere_total = 0
    miscounted_steps = 0
    step_no = 0
    dispatched = 0
    in_flight: List[Any] = []   # the metrics of the step not read yet
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace (outside
        a trace an annotation costs about a microsecond). As
        `train_lm_kda_moe`'s loop, and for its reason: a step's loss and
        counters are read, checked and reported while later steps run
        (every step still is, `IN_FLIGHT` steps later), so the device does
        not wait for the host between steps; `drain` reads the last ones.
        With the read before the next dispatch five seeds spread 0.63%,
        over half the 1% bound, at 5.9 ms between steps of 553. With one
        step in flight, and with two, five of six seeds agreed to 0.1% and
        the sixth read 2.1% and 3.4% low: one stall of the one-chip
        machine's host of 1-2.4 s, which PERF.md section 7 knows from
        every cell (PR 37: no thread of the worker ran for 2.4 s). Six
        steps in flight are 3.3 s of work the device has when the host
        stops (PERF.md section 6, PR 53)."""
        nonlocal state, dispatched
        with jax.profiler.TraceAnnotation("make_batch"):
            batch = put(dispatched)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = train_step(state, batch)
        dispatched += 1
        in_flight.append(metrics)
        if len(in_flight) > IN_FLIGHT:
            read(in_flight.pop(0), report)

    def drain(report: bool = True) -> None:
        """Read what is still in flight: the device is then idle."""
        while in_flight:
            read(in_flight.pop(0), report)

    def read(metrics, report: bool) -> None:
        nonlocal step_no, dropped_total, elsewhere_total, miscounted_steps
        with jax.profiler.TraceAnnotation("report"):
            loss, counts, elsewhere, dropped, fits, n_masked, w_sum = \
                jax.device_get((
                    metrics["loss"], metrics["moe_tokens_per_expert"],
                    metrics["moe_slots_elsewhere"], metrics["moe_dropped"],
                    metrics["moe_rows_bounded"],
                    metrics["diffusion_masked_tokens"],
                    metrics["diffusion_weight_sum"]))   # one host read
            loss = float(loss)
            per_layer = counts.sum(axis=-1)
            share = 100.0 * float(per_layer.sum()) / (
                n_layers * slots_per_layer)
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({"step": step_no, "loss": loss,
                              "held_slots_share": share,
                              "masked_tokens": int(n_masked)})
        losses.append(loss)
        held_shares.append(share)
        held_slots.append([int(x) for x in per_layer])
        bounded.append([int(x) for x in fits])
        masked.append(int(n_masked))
        weight_sums.append(float(w_sum))
        dropped_total += int(dropped)
        elsewhere_total += int(elsewhere.sum())
        miscounted_steps += int(
            (per_layer + elsewhere != slots_per_layer).any())

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
    window = slice(warmup_steps, window_steps)
    window_shares = held_shares[window]
    # per layer: the slots this chip's experts computed, mean over the
    # window's steps; and the window's median share of each layer
    mean_held = np.mean(held_slots[window], axis=0)
    layer_shares = (100.0 * np.median(held_slots[window], axis=0)
                    / slots_per_layer).tolist()

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
                "slots_per_layer": slots_per_layer,
                "slots_elsewhere": elsewhere_total})
    # the masked positions of every step against the host's own count from
    # the same keys (the program's function, on the host's device)
    with jax.default_device(jax.devices("cpu")[0]):
        host_masked = [int((np.asarray(noised_sample(
            cfg, b["tokens"], b["noise_key"])["mask"]) > 0).sum())
            for b in map(host_batch, range(len(masked)))]
    off = [i for i, (a, b) in enumerate(zip(masked, host_masked)) if a != b]
    checks.add("masked_positions_as_the_host_counts", not off,
               {"steps": len(masked), "steps_off": off[:8],
                "first": [masked[0], host_masked[0]],
                "mean_masked": statistics.mean(masked)})
    if not rehearsal:   # a statement about the cell's traffic and widths
        even = 100.0 * held / n_experts
        share_cfg = model["share"]
        band = share_cfg["held_slots_share_band"]
        judged = [layer_shares[i]
                  for i in share_cfg["held_slots_share_band_layers"]]
        checks.add("held_share_in_band",
                   all(band[0] * even <= s <= band[1] * even for s in judged),
                   {"median_share_by_layer": layer_shares,
                    "judged_layers":
                        share_cfg["held_slots_share_band_layers"],
                    "even_share": even, "band": band})

    bdev.finish_device(device, reduced)
    call_model = {k: model[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "block_length")}
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
            "flops_per_token": flops_blockdiff_moe.train_flops_per_token(
                model, seq, mean_held.tolist()),
            "positions_per_step": 2 * batches.tokens_per_step,
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "grouped_matmul_impl": gmm_impl,
            "collectives_in_step": collectives,
            "attention_kernels": kernels.get("attn", {}),
            "mask_blocks": mask_blocks,
            # for the readers that know one causal shape
            "attention_call": flops_blockdiff_moe.attention_call_not_above(
                model, seq, batches.sequences // batch_devices),
            "blockdiff_call": {
                "model": call_model, "seq": seq,
                "batch": batches.sequences // batch_devices},
        },
        "counters": {"losses_first_last": [losses[0], losses[-1]],
                     "router_balance": balanced,
                     "reference_rel_l2": rel_l2,
                     "reference_loss_diff": loss_diff,
                     "reference_loss_diff_over_weight_norm":
                         loss_diff / weight_norm,
                     "reference_sample_held_counts_min_max": [
                         sample_counts.min(axis=-1).tolist(),
                         sample_counts.max(axis=-1).tolist()],
                     "held_slots_share": window_shares,
                     "held_slots_share_by_layer": layer_shares,
                     "moe_rows_bounded_steps_by_layer": np.sum(
                         bounded[window], axis=0).tolist(),
                     "window_steps": len(step_s),
                     "traced_held_slots": held_slots[window_steps:],
                     "diffusion_masked_tokens_mean": statistics.mean(
                         masked[window]),
                     "diffusion_weight_sum_mean": statistics.mean(
                         weight_sums[window]),
                     "moe_slots_elsewhere": elsewhere_total,
                     "moe_dropped": dropped_total},
        "trace": reduced,
    }
    train.report(record)
