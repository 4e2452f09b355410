"""What PR 33 adds to the benchmark for `train_glm47flash_ep8_d5`, checked
without a chip: `benchlib/flops_mla_moe.py` against hand-worked numbers
at the published widths, `benchlib/subscope_reduce.py` and the eight new
readers on a hand-made trace (and on a program or a run that gives them
nothing to read), the spec's new entries and the configuration file
against the catalog row, what the job refuses, the fault reader, and the
job kind `train_lm_mla_moe` rehearsed at a tiny size on the CPU (a
rehearsal's numbers carry the `rehearsal_` prefix and are never a device
metric)."""

import importlib.util
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import (flops, flops_mla_moe, flops_moe,  # noqa: E402
                      scope_reduce, subscope_reduce)
from benchlib.spec import load_json, load_module, metrics_of  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_glm", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_glm47flash_ep8_d5"
CONFIG = os.path.join(BENCH_DIR, "configs", "glm-4.7-flash-ep8-d5.json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs",
                    "tiny-glm4-moe-lite.json")
NEW_METRICS = ["mla_down_share", "mla_up_share", "mla_assemble_share",
               "moe_shared_share", "moe_held_share",
               "moe_held_experts_roofline", "held_slots_share",
               "held_expert_load_max_over_mean"]
TRACE_READERS = NEW_METRICS[:6]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_mla_moe_hand_worked():
    """GLM-4.7-Flash's published widths worked by hand (ISSUE 33)."""
    cfg = load_json(CONFIG)
    assert flops_mla_moe.router_experts(cfg) == 64
    attn = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
            + 20 * 256 * 2048)
    assert attn == 21_757_952 == flops_mla_moe.attention_params(cfg)
    assert attn + 768 + 512 == 21_759_232      # with the two latent norms
    dense = attn + 1280 + 3 * 2048 * 10240 + 2 * 2048
    assert dense == 84_677_888 == flops_mla_moe.dense_layer_params(cfg)
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184 == flops_mla_moe.expert_params(cfg) \
        == flops_mla_moe.shared_params(cfg)
    router = 2048 * 64
    layer = attn + 1280 + expert + router + 8 * expert + 2 * 2048
    assert layer == 106_829_056 == flops_mla_moe.expert_layer_params(cfg)
    embed_and_head = 2 * 19360 * 2048
    assert embed_and_head == 79_298_560
    assert flops_mla_moe.total_params(cfg) == \
        dense + 4 * layer + embed_and_head + 2048 == 591_294_720
    # the whole model at the published sizes: 29.94B
    whole = dict(cfg, num_hidden_layers=47, n_routed_experts=64,
                 vocab_size=154880)
    assert round(flops_mla_moe.total_params(whole) / 1e9, 2) == 29.94
    # what one token passes here at half a held slot a token and layer
    per_token = (5 * attn + 3 * 2048 * 10240
                 + 4 * (router + expert) + 2 * expert + 19360 * 2048)
    assert flops_mla_moe.matmul_params_per_token(cfg, 2.0) == per_token
    # causal attention, 6 passes at 20 heads of 256 (q/k) and 256 (v)
    attn_flops = 5 * 6 * 2 * 8192 * 256 * 20 // 2
    assert flops_mla_moe.attention_train_flops_per_token(cfg, 8192) == \
        attn_flops
    total = flops_mla_moe.train_flops_per_token(cfg, 8192, 2.0)
    assert total == 6 * per_token + attn_flops
    assert round(total / 1e9, 2) == 2.87          # ISSUE 33's 2.87 GFLOP
    # the kernel at head_dim 256 alone is 44% of it, latent attention two
    # thirds
    assert round(100 * attn_flops / total) == 44
    assert round(100 * (attn_flops + 6 * 5 * attn) / total) == 67
    # with every slot of a token held here, k = 4 a layer
    assert flops_mla_moe.matmul_params_per_token(cfg, 16.0) - per_token \
        == 14 * expert
    # the dense functions at the same call
    assert flops.attention_call_flops("fwd", 2, 20, 8192, 256) == \
        2 * 2 * 2 * 20 * 8192 * 8192 * 256 // 2


def test_held_experts_roofline_hand_worked():
    cfg = load_json(CONFIG)
    rows = [[8192, 8192, 4096, 16384]]           # one step, four layers
    least, bound = flops_mla_moe.held_experts_least_time_s(
        cfg, rows, True, PEAKS)
    assert bound == "compute"
    # forward, remat's forward, d lhs, d rhs: 4 passes of each matmul
    per_row = 4 * (2 * 2048 * 3072 + 2 * 1536 * 2048)
    assert abs(least - sum(rows[0]) * per_row / 197e12) < 1e-12
    no_remat, _ = flops_mla_moe.held_experts_least_time_s(
        cfg, rows, False, PEAKS)
    assert abs(no_remat - 0.75 * least) < 1e-12
    # few rows: the 8 held experts' weights bound the call, not all 64
    t, which = flops_mla_moe.held_experts_least_time_s(
        cfg, [[64]], True, PEAKS)
    assert which == "memory"
    held_bytes = flops_moe.grouped_matmul_bytes(64, 2048, 3072, 8)
    assert held_bytes == 2 * (64 * 2048 + 64 * 3072 + 8 * 2048 * 3072)
    assert flops_mla_moe.held_experts_least_time_s(
        cfg, [], True, PEAKS)[0] == 0.0


# ---- the sub-scope reduction and the readers ---------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 900, STEP + "jvp(layers)/while"],
        ["%fusion.1 = f", 0, 40, FWD + "qkv/q_down/btd,dr->btr/dot_general"],
        ["%fusion.2 = f", 40, 30, FWD + "qkv/kv_down/btd,dr->btr/dot"],
        ["%fusion.3 = f", 70, 50, FWD + "qkv/q_up/btr,rhk->bthk/dot_general"],
        ["%fusion.4 = f", 120, 60, BWD + "qkv/kv_up/transpose(jvp(x))/dot"],
        ["%fusion.5 = f", 180, 20, REMAT + "qkv/assemble/concatenate"],
        ["%fusion.6 = f", 200, 10, STEP + "jvp(qkv)/cos"],   # RoPE's tables
        ["%fusion.7 = f", 210, 90, FWD + "moe/shared/nd,dgf->ngf/dot"],
        ["%fusion.8 = f", 300, 25, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 325, 25, FWD + "moe/dispatch/sort"],
        ["%gmm.1 = custom-call()", 350, 200, FWD + "moe/experts/gmm"],
        ["%tgmm.1 = custom-call()", 550, 50,
         BWD + "moe/experts/transpose(jvp(gmm))"],
        ["%gather.2 = g", 600, 50, FWD + "moe/combine/gather"],
        ["%fusion.9 = f", 650, 50, FWD + "moe/add"],        # no sub-scope
        ["%fusion.10 = f", 700, 100, FWD + "attention/splash"],
        ["%fusion.11 = f", 800, 100, FWD + "mlp/gate_up/dot"],
        ["%fusion.12 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_subscope_reduce_on_a_hand_made_trace():
    trace = hand_made_trace()
    ns = 1e-9
    qkv = subscope_reduce.reduce_sub(trace, "qkv")
    want = {"q_down": 40, "kv_down": 30, "q_up": 50, "kv_up": 60,
            "assemble": 20, "cos": 10}
    assert set(qkv) == set(want)
    for sub, t in want.items():
        assert abs(qkv[sub] - t * ns) < 1e-15, sub
    moe = subscope_reduce.reduce_sub(trace, "moe")
    want = {"shared": 90, "router": 25, "dispatch": 25, "experts": 250,
            "combine": 50, "add": 50}
    for sub, t in want.items():
        assert abs(moe[sub] - t * ns) < 1e-15, sub
    # the sub-scopes tile what scope_reduce books under the scope
    scopes = scope_reduce.reduce_scopes(trace)
    assert abs(scopes["bucket_s"]["qkv"] - sum(qkv.values())) < 1e-15
    assert abs(scopes["bucket_s"]["moe"] - sum(moe.values())) < 1e-15


@pytest.mark.parametrize("path,scope,sub", [
    (FWD + "qkv/q_down/btd,dr->btr/dot_general:", "qkv", "q_down"),
    (STEP + "transpose(jvp(qkv/assemble))/mul", "qkv", "assemble"),
    (FWD + "qkv/btd,dghk->btghk/dot_general", "qkv", "other"),
    (FWD + "qkv", "qkv", "other"),
    (REMAT + "moe/shared/nf,fd->nd/dot_general", "moe", "shared"),
    (FWD + "remoe/shared/x", "moe", "other"),
])
def test_subscope_of_a_path(path, scope, sub):
    assert subscope_reduce.subscope_of(path, scope) == sub


def _record(**over):
    cfg = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "held_experts_call": {
            "model": {k: cfg[k] for k in (
                "hidden_size", "moe_intermediate_size", "n_routed_experts",
                "num_experts_per_tok")},
            "router_experts": 64, "tokens": 16384, "remat": True}},
        "counters": {
            "held_expert_load_max_over_mean": [1.2, 1.5, 1.3, 4.0],
            "held_slots_share": [12.0, 13.0, 12.6, 30.0],
            "traced_held_slots": [[1, 2, 1, 0]]},
    }
    record.update(over)
    return record


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    trace = hand_made_trace()
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_09_28"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["mla_down_share"] - 7.0) < 1e-9
    assert abs(values["mla_up_share"] - 11.0) < 1e-9
    assert abs(values["mla_assemble_share"] - 2.0) < 1e-9
    assert abs(values["moe_shared_share"] - 9.0) < 1e-9
    assert abs(values["moe_held_share"] - 35.0) < 1e-9
    assert values["held_slots_share"] == 12.8          # the medians
    assert values["held_expert_load_max_over_mean"] == 1.4
    # least time of the traced step's calls at its four rows over the
    # 250 ns under moe/experts
    least, _ = flops_mla_moe.held_experts_least_time_s(
        load_json(CONFIG), [[1, 2, 1, 0]], True, PEAKS)
    assert abs(values["moe_held_experts_roofline"]
               - 100 * least / 250e-9) < 1e-6 * values[
                   "moe_held_experts_roofline"]
    out = load_module("layer_metrics",
                      "moe_held_experts_roofline").roofline(_record())
    assert out["bound"] == "memory"     # four rows against 8 experts
    # attn_proj_share keeps reading the whole of `qkv`
    assert abs(load_module("layer_metrics", "attn_proj_share").read(
        _record()) - 21.0) < 1e-9


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program: scopes, but none of the new ones (its `qkv`
    # has no sub-scope and it has no `moe/shared`; an OLMoE-like step
    # keeps the four old `moe/` names, so `moe_held_share` reads there)
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                for new in ("q_down/", "kv_down/", "q_up/", "kv_up/",
                            "assemble/"):
                    e[3] = e[3].replace("qkv/" + new, "qkv/")
                e[3] = e[3].replace("moe/shared", "moe/experts")
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: bare)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    if name in ("moe_held_share", "moe_held_experts_roofline"):
        assert read(_record()) is not None
    else:
        assert read(_record()) is None
    # a dense model's program: nothing under `moe` at all
    dense = hand_made_trace()
    for line in dense["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("moe/", "mlp/")
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: dense)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    if name.startswith("moe_"):
        assert read(_record()) is None
    # a record without the job's counters or call
    if name == "moe_held_experts_roofline":
        monkeypatch.setattr(scope_reduce, "from_xplane",
                            lambda path: hand_made_trace())
        monkeypatch.setattr(scope_reduce, "_REDUCED", {})
        monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
        assert read(_record(counters={})) is None
        assert read(_record(static={"peaks": PEAKS})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    assert read(_record()) is None


@pytest.mark.parametrize("name", NEW_METRICS[6:])
def test_counter_reader_with_nothing_to_read(name):
    read = load_module("layer_metrics", name).read
    assert read({}) is None
    assert read({"counters": {}}) is None
    assert read({"counters": {name: []}}) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = spec["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "glm-4.7-flash-ep8-d5", "sft_8k", 1)
    entry = spec["configs"][-1]
    assert entry["name"] == cell["config"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"].endswith("GLM-4.7-Flash/blob/main/config.json")
    # appended behind the accepted entries, which keep their order
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-8:] == NEW_METRICS
    assert [w["name"] for w in spec["workloads"]][:3] == [
        "train_mistral7b_d2", "train_mistral7b_d8_fsdp4", "train_olmoe_d1"]
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
    assert mine["moe_held_experts_roofline"]["unit"] == "%"
    # OLMoE's metrics keep their lists; the readers without one apply here
    for name in ("moe_share", "moe_experts_roofline",
                 "expert_load_max_over_mean", "collective_exposed_share"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_8k.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (2, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    catalog = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    differ = sorted(k for k, v in catalog.items() if held.get(k, "") != v)
    assert differ == sorted(held["reduced"]) == [
        "n_routed_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    for key, cut in held["reduced"].items():
        assert cut["published"] == catalog[key] and cut["here"] == held[key]
        assert not selfcheck.WIDTH_KEY.search(key), key
    # the floors: four expert layers behind the dense one, 8 experts, an
    # eighth of the vocabulary
    assert held["num_hidden_layers"] - held["first_k_dense_replace"] == 4
    assert held["n_routed_experts"] == 8 and held["vocab_size"] * 8 == 154880
    assert held["share"]["chips_per_layer"] == 8
    for key in ("multi_token_prediction", "aux_loss",
                "e_score_correction_bias", "rope_pairing", "initializer"):
        assert key in held["assumed"], key
    job = load_module("jobs", "train_lm_mla_moe")
    cfg = job.transformer_config(held, held["train"], 8192)
    assert cfg.num_params == flops_mla_moe.total_params(held) == 591_294_720
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset) == \
        (64, 8, 0)
    assert (cfg.head_dim, cfg.v_dim, cfg.moe_dense_ff, cfg.ff_dim) == \
        (256, 256, 10240, 1536)


@pytest.mark.parametrize("key,value,why", [
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("n_group", 4, "group-limited"),
    ("topk_group", 2, "group-limited"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "YaRN"),
    ("topk_method", "greedy", "noaux_tc"),
    ("partial_rotary_factor", 0.5, "partial rotary"),
    ("num_key_value_heads", 4, "one key/value head"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_mla_moe")
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], 8192)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", "train_lm_mla_moe")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="kv_lora_rank"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


def test_init_params_is_the_programs_but_for_four_leaves():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_mla_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset) == \
        (16, 4, 4)
    key = jax.random.key(3300000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    assert abs(float(mine["embed"].std()) - 1.0) < 0.02
    bias = np.asarray(mine["layers"]["router_bias"])
    assert bias.any() and abs(float(np.abs(bias).max()) - 0.01) < 1e-6
    # every share's block of 4 holds the same values, in its own order
    blocks = np.sort(bias.reshape(-1, 4), axis=-1)
    assert (blocks == blocks[0]).all() and len(set(map(
        tuple, bias.reshape(-1, 4).tolist()))) > 1
    assert not np.asarray(theirs["layers"]["router_bias"]).any()
    for run in ("dense_layers", "layers"):
        assert (np.asarray(mine[run]["q_a_norm"]) == 2.0).all()
        gain = np.asarray(mine[run]["kv_a_norm"])
        assert abs(gain.mean() - 1.0) < 0.2 and gain.std() > 0.1
    changed = {"embed", "q_a_norm", "kv_a_norm", "router_bias"}
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: str(path[-1].key) in changed
        or bool(np.array_equal(a, b)), mine, theirs)
    assert all(jax.tree.leaves(same)), same


def test_the_held_blocks_bias_is_shifted_until_the_share_is_even():
    import jax
    import numpy as np

    from benchlib.traffic import TokenBatches
    job = load_module("jobs", "train_lm_mla_moe")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    batches = TokenBatches(mix, model["vocab_size"], 11)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = job.init_params(jax.random.key(11), cfg, model["init"])
    out, info = job.balance_held_share(params, cfg, None, batches,
                                       model["init"])
    target = batches.tokens_per_step * 4 * 4 / 16
    assert info["target_slots"] == target
    off = lambda slots: np.abs(np.asarray(slots) - target).max()  # noqa: E731
    assert off(info["held_slots_after"]) <= max(
        0.05 * target, 0.5 * off(info["held_slots_before"]))
    # one shift per layer, on the held block (experts 4..8) alone
    delta = np.asarray(out["layers"]["router_bias"]
                       - params["layers"]["router_bias"])
    np.testing.assert_allclose(delta[:, 4:8], np.asarray(
        info["shift"])[:, None] * np.ones((1, 4)), atol=1e-7)
    assert not delta[:, :4].any() and not delta[:, 8:].any()
    assert np.abs(info["shift"]).max() <= model["init"]["balance_span"]
    same = jax.tree.map(lambda a, b: a is b, out, params)
    assert sum(not x for x in jax.tree.leaves(same)) == 1   # the bias
    still, nothing = job.balance_held_share(
        params, cfg, None, batches, dict(model["init"], balance_rounds=0))
    assert still is params and nothing is None


def test_fault_reader_leaves_the_reference_plain():
    """`reference/glm4_moe_lite_faults.py` breaks copies of the reference,
    outside it: every fault and every narrower precision moves the logits
    (float32 here: each is far over rounding), and the module the job
    compares with is untouched."""
    import inspect

    faults = load_module("reference", "glm4_moe_lite_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.PRECISIONS)
    for name in faults.FAULTS:
        assert rows[name]["rel_l2"] > 1e-3, rows[name]
    assert 0 < rows["bfloat16"]["rel_l2"] < 0.05 \
        < rows["float8_e4m3fn"]["rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "glm4_moe_lite_f32")
    for name in ("linear", "gated_mlp", "routed_experts", "forward",
                 "latent_attention"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    assert plain.rms_norm.__module__ == plain.__name__


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-glm4-moe-lite", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-glm4-moe-lite.json",
        "reduced": ["n_routed_experts"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_glm", "config": "tiny-glm4-moe-lite",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_glm")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_glm"]))
    path = tmp_path_factory.mktemp("glm_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_mla_moe_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_glm", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        share = line["metrics"]["rehearsal_held_slots_share"]
        assert 5.0 <= share["value"] <= 80.0 and share["unit"] == "%"
        load = line["metrics"]["rehearsal_held_expert_load_max_over_mean"]
        assert 1.0 <= load["value"] <= 4.0 and load["unit"] == "ratio"
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
