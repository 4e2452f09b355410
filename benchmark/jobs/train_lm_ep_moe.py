"""Job kind `train_lm_ep_moe`: `train_lm`'s fine-tune through
`JaxTrainer.fit()` for a sparse-expert decoder with plain GQA, a per-head
QK-norm and window and full attention layers mixed (`model_type: mellum`)
whose experts are ALL present, spread by expert over the chips of one
host: the tokens travel (`ray_tpu/ops/moe._exchange_ffn`).

The driver side, the loop, the window, the clock and the record's keys are
`train_lm`'s (`run` and `HOST_ANNOTATIONS` are imported from it; a
`benchmark` issue should fold the job files, ROADMAP D10). `worker_loop`
is `train_lm_blockdiff_moe`'s as far as the config mapping, the layout,
`benchlib.flops_ep_moe` and the counters force another. What it adds:

- the layout is the configuration's: d8's mesh (`fsdp` over the host's
  four chips) with the experts' axis laid on it by `layout.rules`; two
  leaves of the state are held to it (`layout.sharded_leaves`: the
  experts four quarters BY EXPERT, a projection four quarters as d8's);
- the weights stand in for a trained model's (`init_params`, the
  configuration's `init`): SDAR's construction, the positional head
  turned back to the window's edge, and the router's columns permuted
  layer by layer until each chip's experts receive a near-equal share
  (`balance_chips`);
- the step's metrics carry every expert's count and, a layer and chip
  each, the rows received, sent and needed, the distinct (token, chip)
  pairs and which branch of the exchange ran; the loop reads them with
  the loss in one host read;
- `correct` adds: the parameter count four ways; logits and the step-0
  loss of the system against `reference/mellum2_f32.py`, which reads the
  same sharded weights and knows no exchange, on four sequences that
  together reach all experts and send rows between every ordered pair of
  chips, compared a run of positions at a time (the `[4, 8192, 98304]`
  f32 logits are never whole); in every step no slot dropped, every
  chip's received rows equal to the senders' counts for its experts, the
  counts of a layer summing to tokens x k; the kernels the configuration
  expects in the compiled step, with at least one all-to-all; the loss
  finite and lower at the end;
- a program whose `TransformerConfig`, layer kinds or sharding rules lack
  what this configuration needs, and a configuration with a mechanism the
  program lacks, are refused before the cluster starts.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
import time
from typing import Any, Dict, List

from benchlib.spec import load_module

_train_lm = load_module("jobs", "train_lm")
HOST_ANNOTATIONS = _train_lm.HOST_ANNOTATIONS
IN_FLIGHT = 2    # steps dispatched and not yet read (`one_step`)
NEEDS = ("rope_yarn_factor", "rope_yarn_original_len", "rope_yarn_beta_fast",
         "rope_yarn_beta_slow", "rope_yarn_attention_factor", "attn_window",
         "qk_norm_per_head", "layer_pattern")
KIND_OF = {"sliding_attention": "W", "full_attention": "L"}
COMPARE_CHUNK = 1024   # positions of logits compared at a time
# the step's metrics of the exchange, a layer and chip each
COUNTERS = ("moe_rows_received", "moe_exchange_rows_sent",
            "moe_exchange_rows_needed", "moe_exchange_pairs",
            "moe_exchange_bounded")


# ---------------------------------------------------------------------
# driver side (no JAX)
# ---------------------------------------------------------------------


def refuse_what_the_program_lacks(model: Dict[str, Any]) -> None:
    """Mechanisms of the family that the program does not run are refused
    by name, not silently ignored."""
    n = model["num_hidden_layers"]
    kinds = model["layer_types"][:n]
    if len(kinds) != n or set(kinds) - set(KIND_OF):
        raise ValueError(f"layer_types {sorted(set(kinds))}: the program "
                         f"has {sorted(KIND_OF)}")
    if "dense" in model.get("mlp_layer_types", [])[:n]:
        raise ValueError("mlp_layer_types has a dense layer among the "
                         "layers kept: the program's window and full kinds "
                         "W and L carry experts")
    for kind in set(kinds):
        rope = model["rope_parameters"][kind].get("rope_type", "default")
        if rope not in ("default", "yarn"):
            raise ValueError(f"rope_type {rope!r}: the program has default "
                             f"and yarn")
    if model["rope_parameters"]["sliding_attention"].get(
            "rope_type", "default") != "default":
        raise ValueError("the program's window kinds keep the plain table")
    if len({float(r["rope_theta"])
            for r in model["rope_parameters"].values()}) != 1:
        raise ValueError("the program has one rope_theta a model")
    lacking = {"hidden_act": ("silu", "activation other than silu"),
               "attention_bias": (False, "bias in the projections"),
               "router_aux_loss_coef": (0, "aux loss from exchanged counts")}
    for key, (have, what) in lacking.items():
        if model.get(key, have) != have:
            raise ValueError(f"{key} = {model[key]!r}: the program has no "
                             f"{what}")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models import configs
    from ray_tpu.parallel import sharding

    have = {f.name for f in dataclasses.fields(configs.TransformerConfig)}
    missing = [name for name in NEEDS if name not in have]
    if "W" not in getattr(configs, "EXPERT_KINDS", ""):
        missing.append("layer_pattern kind W (window attention, then "
                       "experts)")
    if "expert_embed" not in sharding.DEFAULT_RULES:
        missing.append("sharding rule expert_embed")
    if missing:
        raise RuntimeError(
            f"this program has no {missing}: it cannot run "
            f"{ctx['config'].get('model_type')!r} with its experts over "
            f"the chips ({ctx['cell']['name']})")
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
    n = model["num_hidden_layers"]
    kinds = model["layer_types"][:n]
    full = model["rope_parameters"]["full_attention"]
    yarn = {}
    if full.get("rope_type") == "yarn":
        if not full.get("truncate", True):
            raise ValueError("the program's YaRN ramp is truncated")
        yarn = dict(
            rope_yarn_factor=float(full["factor"]),
            rope_yarn_original_len=full["original_max_position_embeddings"],
            rope_yarn_beta_fast=float(full.get("beta_fast", 32)),
            rope_yarn_beta_slow=float(full.get("beta_slow", 1)),
            rope_yarn_attention_factor=float(
                full.get("attention_factor") or 0.0))
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        d_ff=model["moe_intermediate_size"], max_seq_len=seq,
        rope_theta=float(full["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings")),
        qk_norm=True, qk_norm_per_head=True,
        moe_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_scoring="softmax", moe_aux_coeff=0.0,
        layer_pattern="".join(KIND_OF[kind] for kind in kinds),
        attn_window=model["sliding_window"]
        if "sliding_attention" in kinds else 0,
        attention_impl=train["attention_impl"],
        dtype=train["compute_dtype"], param_dtype=train["param_dtype"],
        remat=train["remat"],   # what it saves is the program's to decide
        loss_chunk=train["loss_chunk"], scan_unroll=train["scan_unroll"],
        **yarn)


def layers_of(params: Dict[str, Any], cfg):
    """(run, sublayer, repeat) of each layer of `params["runs"]`, in the
    model's order."""
    out = []
    for r, (block, repeats) in enumerate(cfg.pattern_runs):
        for i in range(repeats):
            out.extend((r, s, i) for s in range(len(block)))
    return out


def to_reference_layout(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """The program's fused, stacked parameters as the published layout
    (`y = x W^T`, one dict per layer, every expert by its id) the
    reference takes."""
    d = cfg.d_model
    layers = []
    for r, s, i in layers_of(params, cfg):
        lay = params["runs"][r][s]
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
                e: {"gate_proj": lay["w_moe_gateup"][i][e][:, 0].T,
                    "up_proj": lay["w_moe_gateup"][i][e][:, 1].T,
                    "down_proj": lay["w_moe_down"][i][e].T}
                for e in range(cfg.moe_experts)}})
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": head}


def init_params(key, cfg, init: Dict[str, Any]):
    """The program's `Transformer.init` as the stand-in for trained
    weights, with what the comparison needs to see the new mechanisms
    (the configuration's `assumed.initializer` has the reasons; SDAR's
    construction, `train_lm_blockdiff_moe.init_params`, over the runs of a
    `layer_pattern`):

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
      `anchor_pairs`, `look`): its score peaks at the keys `look`
      positions BEFORE the query, whatever their tokens: at the window's
      edge with `look` = window - 0.5.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import Transformer

    params = Transformer.init(key, cfg)

    def normal(n, shape, std=1.0):
        return std * jax.random.normal(jax.random.fold_in(key, n), shape)

    embed = params["embed"]
    params["embed"] = normal(27, embed.shape, init["embed_std"]).astype(
        embed.dtype)
    gain = params["final_norm"]
    params["final_norm"] = (gain + normal(
        44, gain.shape, init["norm_gain_std"])).astype(gain.dtype)
    group = cfg.n_heads // cfg.kv_heads
    tie = init["key_tie"]
    pairs, hd = init.get("anchor_pairs", 0), cfg.head_dim
    half = hd // 2
    if init.get("anchor"):
        anchor = init["anchor"]
        params["embed"] = params["embed"].at[:, 0].set(anchor)
        params["lm_head"] = params["lm_head"].at[0].set(0.0)
        # the query of key group g's last head, turned BACK by `look`
        # positions in the fastest rotary pairs (all under YaRN's ramp)
        theta = cfg.rope_theta ** (-jnp.arange(pairs) / half)
        turn = -theta * init["look"]
        k_row = jnp.zeros((hd,)).at[:pairs].set(1.0)
        q_row = jnp.zeros((hd,)).at[:pairs].set(jnp.cos(turn)).at[
            half:half + pairs].set(jnp.sin(turn))
        last = jnp.arange(group - 1, cfg.n_heads, group)
    n = 0
    for block in params["runs"]:
        for lay in block:
            n += 100
            for j, name in enumerate(("attn_norm", "mlp_norm", "q_norm",
                                      "k_norm")):
                gain = lay[name]
                lay[name] = (gain + normal(
                    n + 40 + j, gain.shape,
                    init["norm_gain_std"])).astype(gain.dtype)
            lay["q_norm"] = lay["q_norm"] * init["q_gain"]
            wq, wkv = lay["wq"], lay["wkv"]     # [l, d, H, hd], [l, d, 2, ..]
            wk = tie * wq[:, :, ::group] \
                + (1.0 - tie * tie) ** 0.5 * wkv[:, :, 0]
            q_scale = jnp.exp(normal(n + 50, (wq.shape[0], 1, cfg.n_heads, 1),
                                     init["head_scale_std"]))
            k_scale = jnp.exp(normal(n + 51, (wq.shape[0], 1, cfg.kv_heads,
                                              1), init["head_scale_std"]))
            wq, wk = wq * q_scale, wk * k_scale
            if init.get("anchor"):
                wq = wq.at[:, :, last].set(0.0).at[:, 0, last].set(q_row)
                # as much of a key's energy as its token part has
                wk = wk.at[:, 0].set(k_row * (hd / pairs) ** 0.5 / anchor
                                     * k_scale[:, 0])
                lay["w_router"] = lay["w_router"].at[:, 0].set(0.0)
                lay["w_moe_gateup"] = lay["w_moe_gateup"].at[:, :, 0].set(
                    0.0)
                wkv = wkv.at[:, 0, 1].set(0.0)
            lay["wq"] = wq.astype(lay["wq"].dtype)
            lay["wkv"] = wkv.at[:, :, 0].set(wk.astype(wkv.dtype))
    return params


def even_chips(loads, chips: int):
    """The experts in `chips` runs of equal size and near-equal load:
    `perm[j]` the expert that takes place j, by the longest-processing-
    time rule (the heaviest expert left goes to the lightest chip that
    still has room). -> (perm, each chip's load)."""
    import numpy as np

    room = len(loads) // chips
    bins: List[List[int]] = [[] for _ in range(chips)]
    total = np.zeros(chips)
    for e in np.argsort(-np.asarray(loads), kind="stable"):
        open_bins = [c for c in range(chips) if len(bins[c]) < room]
        c = min(open_bins, key=lambda c: total[c])
        bins[c].append(int(e))
        total[c] += loads[e]
    return np.asarray([e for b in bins for e in sorted(b)]), total


def balance_chips(params, cfg, forward, tokens, chips: int):
    """The router as a trained model's balancing leaves it, between the
    chips: layer by layer, the router's COLUMNS permuted (which expert
    answers to which column: the columns are independent draws, so this
    is another draw of the same weights) so that each chip's run of
    experts receives a near-equal share of a seeded batch's token-slots
    (`even_chips`). Layer l is measured with the layers before it already
    permuted: one forward a layer. Without it the draw decides: a chip's
    16 experts read 1.04-1.21 of the mean load at step 0 by the seed, the
    step waits for the fullest chip, and `train_tokens_per_s` spread 0.67%
    over three seeds (PERF.md section 6, PR 57). Returns (params, what
    was done)."""
    import numpy as np

    before, after = [], []
    for layer, (r, s, i) in enumerate(layers_of(params, cfg)):
        loads = np.asarray(forward(params, tokens)[2]["tokens_per_expert"])[
            layer].astype(np.float64)
        perm, even = even_chips(loads, chips)
        leaf = params["runs"][r][s]["w_router"]
        params["runs"][r][s]["w_router"] = leaf.at[i].set(leaf[i][:, perm])
        held = loads.reshape(chips, -1).sum(1)
        before.append(float(held.max() / held.mean()))
        after.append(float(even.max() / even.mean()))
    return params, {"fullest_chip_over_mean_before": before,
                    "fullest_chip_over_mean_after": after}


def sharding_rules(layout: Dict[str, Any]):
    """The configuration's `layout.rules` over the program's defaults."""
    from ray_tpu.parallel.sharding import ShardingRules

    rules = {k: tuple(v) if isinstance(v, list) else v
             for k, v in layout.get("rules", {}).items()}
    return ShardingRules().replace(**rules)


def sharded_init(key, cfg, init: Dict[str, Any], mesh, rules):
    """`init_params` made on the devices, each leaf into the sharding its
    logical spec and `rules` give it."""
    import jax

    from ray_tpu.models import Transformer
    from ray_tpu.parallel.sharding import logical_sharding

    shapes = jax.eval_shape(lambda k: init_params(k, cfg, init), key)
    shardings = jax.tree.map(
        lambda spec, leaf: logical_sharding(spec, mesh, rules,
                                            shape=leaf.shape),
        Transformer.param_specs(cfg), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    return jax.jit(lambda k: init_params(k, cfg, init),
                   out_shardings=shardings)(key)


def reference_weights(params, cfg, mesh):
    """The system's own weights in the reference's layout
    (`to_reference_layout`) where a plain program, run op by op, keeps its
    activations a sequence a chip: every expert's matrices in equal parts
    over the mesh's `fsdp` axis along d_model, as `expert_embed` would lay
    them (left to itself the compiler hands every chip a whole copy of
    every expert: 6.3 GB a chip at the published widths), everything else
    whole on every chip (2.2 GB), so that the embedding's rows come out
    laid as the tokens are, by sequence, and every later product keeps
    that (a table in parts along d_model hands on a stream in parts along
    d_model, and attention's `[B, H, 1024, T]` scores then lie whole on
    every chip: 4 GB)."""
    import jax

    parts = mesh.shape.get("fsdp", 1)

    def placed(path, leaf):
        expert = any(getattr(k, "key", None) == "experts" for k in path)
        spec = (None, "fsdp") if expert and parts > 1 \
            and leaf.shape[1] % parts == 0 else ()
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*spec))

    return jax.jit(
        lambda p: to_reference_layout(p, cfg),
        out_shardings=jax.tree_util.tree_map_with_path(
            placed, jax.eval_shape(
                lambda p: to_reference_layout(p, cfg), params)))(params)


def quarters(leaf, axis: int, chips: int) -> Dict[str, Any]:
    """Whether `leaf` lies in `chips` equal parts along `axis`, one a
    device, and what was found."""
    shards = leaf.addressable_shards
    want = leaf.shape[axis] // chips
    ok = len({s.device for s in shards}) == chips and all(
        s.data.shape[axis] == want and s.data.size * chips == leaf.size
        for s in shards)
    return {"ok": ok, "leaf": list(leaf.shape), "axis": axis,
            "shards": [[s.device.id, list(s.data.shape)] for s in shards]}


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
    from benchlib import flops_ep_moe
    from benchlib.checks import (Checks, attention_as_expected,
                                 grouped_matmul_as_expected, kernel_calls)
    from benchlib.peaks import peaks_for
    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer, head
    from ray_tpu.ops.moe import exchange_bound, grouped_matmul_impl
    from ray_tpu.parallel import MeshConfig, make_mesh
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
    layout = model["layout"]
    mesh = make_mesh(MeshConfig(**layout["mesh"]))
    rules = sharding_rules(layout)
    chips = math.prod(
        mesh.shape[a] for a in ("data", "fsdp") if a in mesh.shape)
    n_experts, top_k, n_layers = cfg.moe_experts, cfg.moe_top_k, cfg.n_layers
    held = n_experts // chips
    slots_per_layer = batches.tokens_per_step * top_k

    # ---- weights from the seed, on the device, into their shardings --
    specs = Transformer.param_specs(cfg)
    key = jax.random.key(config["seed"])
    params = sharded_init(key, cfg, model["init"], mesh, rules)
    jax.block_until_ready(params)
    counts_four_ways = [
        sum(int(x.size) for x in jax.tree.leaves(params)),
        sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(
            lambda k: Transformer.init(k, cfg), key))),
        cfg.num_params, flops_ep_moe.total_params(model)]
    n_params = counts_four_ways[0]
    checks.add("param_count", len(set(counts_four_ways)) == 1,
               counts_four_ways)
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    checks.add("param_dtype", dtypes == [tr_cfg["param_dtype"]], dtypes)
    t = phase("init_weights_s", t)

    # ---- the system against the plain reference, before the optimizer
    # state takes its memory: the same sample and the same sharded
    # weights to both; the reference knows no mesh and no exchange
    reference = load_module("reference", model["reference"])
    sample_cfg = mix["reference_sample"]
    sample = batches.reference_sample(sample_cfg["sequences"],
                                      sample_cfg["tokens"])
    batch_sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), None))
    tokens = jax.device_put(sample[:, :-1], batch_sharding)
    targets = jax.device_put(sample[:, 1:], batch_sharding)
    forward = jax.jit(lambda p, x: Transformer.hidden(
        p, x, cfg, mesh=mesh, rules=rules, with_aux=True))
    balanced = None
    if model["init"].get("balance_chips") and chips > 1:
        # on a batch of its own (stream 3), the program the comparison
        # runs: no compile of its own
        params, balanced = balance_chips(
            params, cfg, forward, jax.device_put(batches.draw(
                3, 0, sample.shape[0], sample.shape[1] - 1),
                batch_sharding), chips)
    sys_hidden, _, sys_routing = forward(params, tokens)
    sys_loss = jax.jit(lambda p, b: Transformer.loss(
        p, b, cfg, mesh=mesh, rules=rules))(
            params, {"tokens": jax.device_put(sample, batch_sharding)})

    # op by op, not under one jit (train_lm_moe.py): the reference's plain
    # loops over the experts and the query blocks compile one of each
    weights = reference_weights(params, cfg, mesh)
    chosen: List[Any] = []
    with jax.default_matmul_precision("highest"):
        ref_hidden = reference.hidden(
            weights, tokens, model, chosen,
            query_block=model.get("reference_query_block"))
    ref_counts = np.asarray(reference.tokens_per_expert(chosen, n_experts))
    # which chip's tokens chose which chip's experts, by the reference's
    # own routing: [layers, from, to] (a sequence a chip, in order)
    per_chip = sample.shape[0] * (sample.shape[1] - 1) // chips
    pair_rows = np.stack([
        np.asarray(jax.nn.one_hot(np.asarray(top_e) // held, chips,
                                  dtype=jnp.int32)).reshape(
            chips, per_chip * top_k, chips).sum(1)
        for top_e in chosen])
    del chosen

    @jax.jit
    def compare(p, w, sys_h, ref_h, tgt):
        """One run of positions: both heads, and the sums the two
        comparisons need."""
        sys_logits = head.logits(p, sys_h, cfg, mesh=mesh,
                                 rules=rules).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref_logits = reference.head(w, ref_h, model)
        diff = sys_logits - ref_logits
        return (jnp.sum(diff * diff), jnp.sum(ref_logits * ref_logits),
                jnp.sum(reference.next_token_nll(ref_logits, tgt)))

    sums = np.zeros(3)
    for s in range(0, tokens.shape[1], COMPARE_CHUNK):
        e = s + COMPARE_CHUNK
        sums += np.asarray(jax.device_get(compare(
            params, weights, sys_hidden[:, s:e], ref_hidden[:, s:e],
            targets[:, s:e])), np.float64)
    del weights, sys_hidden, ref_hidden
    rel_l2 = float(np.sqrt(sums[0] / sums[1]))
    ref_loss = float(sums[2] / targets.size)
    loss_diff = abs(float(sys_loss) - ref_loss)
    sample_counts = np.asarray(sys_routing["tokens_per_expert"])
    tol = model["tolerance"]
    checks.add("reference_logits", rel_l2 <= tol["logits_rel_l2"],
               {"rel_l2": rel_l2, "allowed": tol["logits_rel_l2"],
                "sample": list(sample.shape)})
    checks.add("reference_loss", loss_diff <= tol["loss_abs"],
               {"system": float(sys_loss), "reference": ref_loss,
                "difference": loss_diff, "allowed": tol["loss_abs"]})
    # what the two comparisons above covered: the grouped matmul on every
    # expert's group, none of them empty, and rows between every ordered
    # pair of chips. How many slots the rounded activations moved to
    # another expert than the reference's is reported, not judged: a
    # near-tie may go either way.
    moved = int(np.abs(sample_counts - ref_counts).sum())
    checks.add("reference_sample_reaches_every_expert",
               int((sample_counts == 0).sum()) == 0
               and int((ref_counts == 0).sum()) == 0,
               {"counts_min_max": [int(sample_counts.min()),
                                   int(sample_counts.max())],
                "count_differences_against_reference": moved})
    checks.add("reference_sample_sends_between_every_pair_of_chips",
               bool((pair_rows > 0).all()),
               {"rows_from_to_min": int(pair_rows.min()),
                "layer_0": pair_rows[0].tolist()})
    sample_exchange = {
        name: np.asarray(sys_routing[name]).tolist()
        for name in ("rows_received", "exchange_rows_sent",
                     "exchange_rows_needed", "exchange_pairs",
                     "exchange_bounded") if name in sys_routing}
    del sys_routing, tokens, targets
    t = phase("reference_check_s", t)

    # ---- the step -------------------------------------------------
    opt = tr_cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh, rules=rules,
                                      with_metrics=True),
        specs, mesh, rules=rules,
        optimizer=optax.adamw(opt["learning_rate"],
                              weight_decay=opt["weight_decay"]))
    state = init_state(params)
    del params

    def put(step: int):
        return {"tokens": jax.device_put(batches.batch(step),
                                         batch_sharding)}

    compiled = train_step.lower(state, put(0)).compile()
    ma = compiled.memory_analysis()
    memory_analysis = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes", "peak_memory_in_bytes")
        if hasattr(ma, k)} if ma is not None else {}
    hlo = compiled.as_text()
    del compiled
    kernels = model.get("kernels", {})
    attn_calls = kernel_calls(hlo, kernels.get("attn", {}))
    moe_calls = kernel_calls(hlo, kernels.get("moe", {}))
    n_kernel_calls = hlo.count("tpu_custom_call")
    collectives = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                   for k in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute")}
    # the attention kernel's calls by the scope of their op_name (a splash
    # call's instruction spans three lines: its block sizes as JSON)
    joined = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                    hlo)
    scoped_calls = {kind: sum(
        f"attention/{kind}" in line and "tpu_custom_call" in line
        for line in joined.splitlines()) for kind in ("window", "full")}
    del joined
    del hlo
    impl = Transformer.resolve_attention_impl(cfg, mesh, seq)
    want = tr_cfg["expect_attention"]
    checks.add("attention_impl", attention_as_expected(impl, want,
                                                       attn_calls)
               and (want != "flash" or all(scoped_calls.values())),
               {"resolved": impl, "expected": want, "calls": attn_calls,
                "calls_by_scope": scoped_calls})
    slots_per_chip = batches.tokens_per_step // chips * top_k
    bucket = exchange_bound(slots_per_chip, chips) or slots_per_chip
    gmm_impl = grouped_matmul_impl(mesh, chips * bucket, cfg.d_model,
                                   cfg.ff_dim, per_shard=True)
    want_gmm = tr_cfg["expect_grouped_matmul"]
    checks.add("grouped_matmul_impl", grouped_matmul_as_expected(
        gmm_impl, want_gmm, moe_calls),
        {"resolved": gmm_impl, "expected": want_gmm, "calls": moe_calls,
         "exchange_bucket_rows": bucket})
    checks.add("exchange_in_the_step",
               rehearsal or collectives["all-to-all"] > 0, collectives)
    sharded = {}
    for name, axis in (("by_expert", 1), ("by_embed", 1)):
        path = layout.get("sharded_leaves", {}).get(name)
        if path and len(devices) > 1:
            leaf = state["params"]
            for k in path:
                leaf = leaf[k]
            sharded[name] = quarters(leaf, axis, len(devices))
            del leaf
    checks.add("sharded_state", all(v["ok"] for v in sharded.values()),
               sharded)
    t = phase("compile_step_s", t)

    # ---- the loop's body: the loss and the counters come back in one
    # host read -----------------------------------------------------
    losses: List[float] = []
    per_expert: List[Any] = []           # per step: [layers, E]
    exchange: Dict[str, List[Any]] = {name: [] for name in COUNTERS}
    dropped_total = 0
    miscounted_steps = 0
    unreceived_steps = 0
    step_no = 0
    dispatched = 0
    in_flight: List[Any] = []   # the metrics of the steps not read yet
    report_every = int(mix["report_every"])

    def one_step(report: bool = True) -> None:
        """The same in warm-up, in the window and under the trace (outside
        a trace an annotation costs about a microsecond). As
        `train_lm_blockdiff_moe`'s loop, and for its reason: a step's
        loss and counters are read, checked and reported while later steps
        run (every step still is, `IN_FLIGHT` steps later), so the device
        does not wait for the host between steps; `drain` reads the last
        ones."""
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
        nonlocal step_no, dropped_total, miscounted_steps, unreceived_steps
        with jax.profiler.TraceAnnotation("report"):
            loss, counts, dropped, *found = jax.device_get((
                metrics["loss"], metrics["moe_tokens_per_expert"],
                metrics["moe_dropped"],
                *(metrics[name] for name in COUNTERS)))   # one host read
            loss = float(loss)
            received = found[0]
            step_no += 1
            if report and step_no % report_every == 0:
                train.report({
                    "step": step_no, "loss": loss,
                    "fullest_chip_rows": int(received.max()),
                    "rows_sent": int(found[1].sum())})
        losses.append(loss)
        per_expert.append(counts)
        for name, value in zip(COUNTERS, found):
            exchange[name].append(value)
        dropped_total += int(dropped)
        miscounted_steps += int(
            (counts.sum(axis=-1) != slots_per_layer).any())
        # what a chip received (its own count of the rows that came) is
        # what the senders counted for its experts
        unreceived_steps += int((counts.reshape(
            n_layers, chips, held).sum(-1) != received).any())

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
    checks.add("no_token_dropped", dropped_total == 0,
               {"dropped_slots": dropped_total, "steps": len(losses)})
    checks.add("counts_sum_to_slots", miscounted_steps == 0,
               {"steps_off": miscounted_steps,
                "slots_per_layer": slots_per_layer})
    checks.add("rows_received_are_the_rows_sent", unreceived_steps == 0,
               {"steps_off": unreceived_steps, "steps": len(losses)})

    received = np.asarray(exchange["moe_rows_received"], np.float64)
    loads = np.asarray(per_expert, np.float64)       # [steps, layers, E]
    # per step and layer: the fullest chip over the mean, the fullest
    # expert over the mean
    chip_skew = (received.max(-1) / received.mean(-1))[window]
    expert_skew = (loads.max(-1) / loads.mean(-1))[window]
    sent = np.asarray(exchange["moe_exchange_rows_sent"], np.float64)
    needed = np.asarray(exchange["moe_exchange_rows_needed"], np.float64)
    pairs = np.asarray(exchange["moe_exchange_pairs"], np.float64)
    bounded = np.asarray(exchange["moe_exchange_bounded"])

    bdev.finish_device(device, reduced)
    per_chip_batch = batches.sequences // chips
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
            "flops_per_token": flops_ep_moe.train_flops_per_token(model,
                                                                  seq),
            "forward_flops_shares": flops_ep_moe.forward_flops_shares(
                model, seq),
            "params": n_params,
            "memory_analysis": memory_analysis,
            "kernel_calls_in_step": n_kernel_calls,
            "grouped_matmul_impl": gmm_impl,
            "collectives_in_step": collectives,
            "attention_kernels": kernels.get("attn", {}),
            # for the readers that know one causal shape
            "attention_call": flops_ep_moe.attention_call_not_above(
                model, seq, per_chip_batch),
            "ep_call": {
                "model": {k: model[k] for k in (
                    "hidden_size", "head_dim", "num_attention_heads",
                    "num_key_value_heads", "moe_intermediate_size",
                    "num_experts", "num_experts_per_tok", "sliding_window",
                    "num_hidden_layers", "layer_types")},
                "seq": seq, "batch": per_chip_batch, "held": held,
                "remat": bool(tr_cfg["remat"]),
                # the device behind each shard's place in the counters
                "shard_device_ids": [int(d.id)
                                     for d in mesh.devices.flat]},
        },
        "counters": {
            "losses_first_last": [losses[0], losses[-1]],
            "reference_rel_l2": rel_l2,
            "reference_loss_diff": loss_diff,
            "router_balance": balanced,
            "reference_sample_exchange": sample_exchange,
            "reference_sample_rows_from_to": pair_rows[0].tolist(),
            "window_steps": len(step_s),
            "expert_load_max_over_mean": np.max(expert_skew,
                                                axis=-1).tolist(),
            "chip_rows_max_over_mean": chip_skew.reshape(-1).tolist(),
            "rows_sent_over_needed": float(
                sent[window].sum() / max(needed[window].sum(), 1.0)),
            "rows_needed_over_pairs": float(
                needed[window].sum() / max(pairs[window].sum(), 1.0)),
            "exchange_bounded_steps_by_layer": bounded[window].min(
                -1).sum(0).tolist(),
            "exchange_unbounded_steps": int(
                (bounded.min(-1) == 0).any(-1).sum()),
            "rows_received_mean_by_chip": received[window].mean(
                (0, 1)).tolist(),
            "traced_rows_received": received[window_steps:].tolist(),
            "traced_exchange_pairs": pairs[window_steps:].tolist(),
            "traced_exchange_rows_sent": sent[window_steps:].tolist(),
            "moe_dropped": dropped_total},
        "trace": reduced,
    }
    train.report(record)
