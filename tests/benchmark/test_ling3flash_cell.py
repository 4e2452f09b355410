"""What PR 50 adds to the benchmark for `train_ling3flash_ep64_d7`,
checked without a chip: `benchlib/flops_kda_moe.py` against hand-worked
numbers at the published widths, `benchlib/kda_reduce.py` and the six new
readers on a hand-made trace (and on a program or a run that gives them
nothing to read), the spec's new entries BY NAME, never by position, and
the configuration file against the catalog row key by key, what the job
refuses, the stand-in weights, the fault reader, and the job kind
`train_lm_kda_moe` rehearsed at a tiny size on the CPU (a rehearsal's
numbers carry the `rehearsal_` prefix and are never a device metric)."""

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

from benchlib import (flops, flops_kda_moe, kda_reduce,  # noqa: E402
                      moe_reduce, scope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_ling3", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_ling3flash_ep64_d7"
NAME = "ling-3.0-flash-ep64-tp4-d7"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-ling3.json")
NEW_METRICS = ["kda_share", "kda_delta_share", "kda_proj_share",
               "kda_delta_roofline", "group_router_share",
               "group_moe_held_slots_share"]
TRACE_READERS = NEW_METRICS[:5]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CALL = {"tokens": 16384, "layers": 6, "heads": 8, "d_k": 128, "d_v": 128,
        "chunk": 64, "remat": True}


# ---- arithmetic --------------------------------------------------------


def test_flops_kda_moe_hand_worked():
    cfg = load_json(CONFIG)
    f = flops_kda_moe
    assert f.router_experts(cfg) == 512
    assert f.layer_pattern(cfg) == "kKKKLKK"
    assert (f.layers_of(cfg, "kK"), f.layers_of(cfg, "lL"),
            f.layers_of(cfg, "KL"), f.layers_of(cfg, "kl")) == (6, 1, 6, 1)
    # the published 42 layers: two dense, a latent layer every sixth
    whole = dict(cfg, num_hidden_layers=42, first_k_dense_replace=2,
                 share={})
    assert f.layer_pattern(whole) == "kk" + "KKKL" + "KKKKKL" * 6
    # ISSUE 50's table, part by part
    assert f.kda_params(cfg) == 13_161_608
    assert f.kda_matmul_params(cfg) == \
        4 * 2560 * 1024 + 2 * 2560 * 8 + 1024 * 2560
    assert f.mla_params(cfg) == 9_077_248 + 2 * 192
    assert f.mla_matmul_params(cfg) == 9_077_248 - 512
    assert f.expert_layer_params(cfg) == 54_394_880
    assert (f.router_params(cfg), f.shared_params(cfg),
            f.expert_params(cfg)) == (1_310_720, 5_898_240, 5_898_240)
    assert f.dense_mlp_params(cfg) == 47_185_920
    assert f.total_params(cfg) == 562_238_640
    assert 16 * f.total_params(cfg) == 8_995_818_240            # 9.0 GB
    # a token's matmul parameters at an even share of 8 x 8 / 512 slots
    even = 6 * 8 * 8 / 512
    per_token = f.matmul_params_per_token(cfg, even)
    assert per_token == (6 * 13_148_160 + 9_076_736 + 47_185_920
                         + 6 * (1_310_720 + 5_898_240)
                         + even * 5_898_240 + 2560 * 19648)
    assert 233e6 < per_token < 234e6
    # attention: QK^T over 192 columns, PV over 128, three passes each
    assert f.attention_train_flops_per_token(cfg, 16384) == \
        (flops.attention_matmul_flops(1, 8, 16384, 192, 3)
         + flops.attention_matmul_flops(1, 8, 16384, 128, 3)) / 16384
    assert f.attention_call_head_dim(cfg) == 160
    # the call's one width: exact forward, 3.8% under for the fused backward
    assert flops.attention_call_flops("fwd", 1, 8, 16384, 160) == \
        flops.attention_matmul_flops(1, 8, 16384, 192, 1) \
        + flops.attention_matmul_flops(1, 8, 16384, 128, 1)
    fused = flops.attention_call_flops("bwd_fused", 1, 8, 16384, 160)
    true = flops.attention_matmul_flops(1, 8, 16384, 192, 3) \
        + flops.attention_matmul_flops(1, 8, 16384, 128, 2)
    assert 0.96 < fused / true < 0.97
    # the delta rule, one layer, forward, a token and head: the causal
    # halves of K K^T and Q K^T (2 x 64 x 128), the triangular inverse
    # (2 x 64^2 / 3), the inverse times [V | K] (64 x 256), the state in,
    # out and onto the outputs (6 x 128^2), the query-key block times the
    # corrected values (64 x 128)
    per_head = 2 * 64 * 128 + 2 * 64 * 64 / 3 + 64 * 256 \
        + 6 * 128 * 128 + 64 * 128
    assert f.delta_flops_per_token(CALL) == 8 * per_head
    assert f.delta_bytes_per_token(CALL) == 8 * (2 * 384 + 4 * 257)
    assert f.delta_passes_per_step(True) == 4
    assert f.delta_passes_per_step(False) == 3
    least, bound = f.delta_least_time_s(CALL, 1, PEAKS)
    flops_s = 16384 * f.delta_flops_per_token(CALL) / 197e12
    bytes_s = 16384 * f.delta_bytes_per_token(CALL) / 819e9
    assert bound == "memory" and bytes_s > flops_s
    assert abs(least - 6 * 4 * bytes_s) < 1e-12
    assert f.delta_least_time_s(dict(CALL, remat=False), 2, PEAKS)[0] == \
        pytest.approx(least * 2 * 3 / 4)
    total = f.train_flops_per_token(cfg, 16384, even, 64)
    assert total == 6 * per_token \
        + f.attention_train_flops_per_token(cfg, 16384) \
        + 3 * 6 * f.delta_flops_per_token(CALL)
    assert 1.5e9 < total < 1.6e9
    # the delta rule is under 2% of a token's FLOPs
    assert 3 * 6 * f.delta_flops_per_token(CALL) / total < 0.02


# ---- the reducers and the readers --------------------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 900, STEP + "jvp(layers)/while"],
        ["%fusion.1 = f", 0, 60,
         FWD + "kda/qkv_proj/btd,dghk->btghk/dot_general"],
        ["%fusion.2 = f", 60, 20, FWD + "kda/conv/mul"],
        ["%fusion.3 = f", 80, 30, FWD + "kda/gates/logistic"],
        ["%fusion.4 = f", 110, 100, FWD + "kda/delta/...ij,...jk->...ik/dot"],
        ["%while.2 = while()", 210, 40, BWD + "kda/delta/while"],
        ["%fusion.5 = f", 250, 30, REMAT + "kda/out_norm/rsqrt"],
        ["%fusion.6 = f", 280, 50, BWD + "kda/out_proj/transpose(jvp(x))/dot"],
        ["%fusion.7 = f", 330, 10, FWD + "kda_norm/mul"],
        ["%fusion.8 = f", 340, 40, FWD + "qkv/q_proj/btd,dhk->bthk/dot"],
        ["%fusion.9 = f", 380, 90, FWD + "moe/shared/nd,df->nf/dot_general"],
        ["%fusion.10 = f", 470, 25, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 495, 25, BWD + "moe/router/top_k"],
        ["%gmm.1 = custom-call()", 520, 80, FWD + "moe/experts/gmm"],
        ["%gather.2 = g", 600, 50, FWD + "moe/combine/gather"],
        ["%fusion.11 = f", 650, 50, FWD + "attention/splash"],
        ["%fusion.12 = f", 700, 100, STEP + "jvp(head)/dot"],
        ["%fusion.13 = f", 800, 100, FWD + "akda/delta/x"],   # not a scope
        ["%fusion.14 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_kda_reduce_on_a_hand_made_trace():
    reduced = kda_reduce.reduce_kda(hand_made_trace())
    want = {"kda/qkv_proj": 60, "kda/conv": 20, "kda/gates": 30,
            "kda/delta": 140, "kda/out_norm": 30, "kda/out_proj": 50,
            "kda_norm": 10}
    assert set(reduced) == set(want)
    for scope, t in want.items():
        assert abs(reduced[scope] - t * 1e-9) < 1e-15, scope
    # `kda` is no bucket of scope_reduce's: its ops are under `layers`
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert "kda" not in scopes["bucket_s"]
    assert scopes["bucket_s"]["layers"] >= 340e-9
    # the direct query projection is under the vocabulary's `qkv`
    assert abs(scopes["bucket_s"]["qkv"] - 40e-9) < 1e-15
    assert kda_reduce.reduce_kda({"planes": []}) is None


@pytest.mark.parametrize("path,scope", [
    (FWD + "kda/qkv_proj/btd,dghk->btghk/dot_general:", "kda/qkv_proj"),
    (STEP + "transpose(jvp(kda/delta))/mul", "kda/delta"),
    (REMAT + "kda/delta/while/body/checkpoint/mul", "kda/delta"),
    (FWD + "kda_norm/mul", "kda_norm"),
    (FWD + "akda/delta/x", None),
    (FWD + "kda/other/x", None),
    (FWD + "ssm/scan/x", None),
])
def test_kda_scope_of_a_path(path, scope):
    assert kda_reduce.scope_of(path) == scope


def _record(**over):
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "delta_call": dict(CALL)},
        "counters": {"held_slots_share": [1.5, 1.7, 1.6, 3.0]},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(moe_reduce, "_REDUCED", {})
    monkeypatch.setattr(kda_reduce, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_10_01"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["kda_share"] - 34.0) < 1e-9
    assert abs(values["kda_delta_share"] - 14.0) < 1e-9
    assert abs(values["kda_proj_share"] - 19.0) < 1e-9
    assert abs(values["group_router_share"] - 5.0) < 1e-9
    assert values["group_moe_held_slots_share"] == 1.65       # the median
    least, bound = flops_kda_moe.delta_least_time_s(CALL, 1, PEAKS)
    assert values["kda_delta_roofline"] == pytest.approx(
        100 * least / 140e-9)
    out = load_module("layer_metrics", "kda_delta_roofline").roofline(
        _record())
    assert out["bound"] == bound == "memory"
    # the accepted readers read the same trace as they did
    assert abs(load_module("layer_metrics", "moe_shared_share").read(
        _record()) - 9.0) < 1e-9
    assert abs(load_module("layer_metrics", "attn_proj_share").read(
        _record()) - 4.0) < 1e-9
    assert load_module("layer_metrics", "mlp_share").read(_record()) == 0.0


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no KDA scope (GLM's
    # and Nemotron's steps keep `moe/router`, so `group_router_share`
    # would read there; it lists this cell alone)
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("kda/", "ssm/").replace(
                    "kda_norm", "ssm_norm")
    _fresh(monkeypatch, bare)
    if name == "group_router_share":
        assert read(_record()) is not None
    else:
        assert read(_record()) is None
    # a dense model's program: nothing under `moe` at all
    dense = hand_made_trace()
    for line in dense["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("moe/", "mlp/")
    _fresh(monkeypatch, dense)
    if name == "group_router_share":
        assert read(_record()) is None
    # a record without the job's call
    _fresh(monkeypatch, hand_made_trace())
    if name == "kda_delta_roofline":
        assert read(_record(static={"peaks": PEAKS})) is None
        assert read(_record(static={"delta_call": dict(CALL)})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


def test_counter_reader_with_nothing_to_read():
    read = load_module("layer_metrics", "group_moe_held_slots_share").read
    assert read({}) is None
    assert read({"counters": {}}) is None
    assert read({"counters": {"held_slots_share": []}}) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, never by position: a later PR appends behind them
    (the two cells whose tests pinned theirs as the last entry have been
    red since the next cell came)."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_16k_1seq", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/inclusionAI/"
                               "Ling-3.0-flash-VL/blob/main/config.json")
    assert "config.json, language model" in entry["why"]
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # the accepted entries keep their order in front of it
    names = [w["name"] for w in spec["workloads"]]
    accepted = ["train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                "train_olmoe_d1", "train_glm47flash_ep8_d5",
                "train_nemotron3super_ep64_d11", "train_phi4miniflash_d6"]
    assert names[:6] == accepted and names.index(CELL) >= 6
    per_layer = [m["name"] for m in spec["per_layer"]]
    at = per_layer.index(NEW_METRICS[0])
    assert per_layer[at:at + 6] == NEW_METRICS
    assert at > per_layer.index("masked_attn_kernel_roofline")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    assert (mine["kda_delta_roofline"]["unit"],
            mine["kda_delta_roofline"]["layer"],
            mine["kda_delta_roofline"]["better"]) == ("%", "kernels",
                                                      "higher")
    assert mine["group_moe_held_slots_share"]["source"] == "program_counter"
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "moe_experts_roofline", "moe_held_share",
                 "mla_down_share", "collective_exposed_share", "ssm_share",
                 "mamba1_share", "latent_moe_held_slots_share"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the traffic file is Phi-4-mini-flash's, unedited
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_16k_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 16384)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 16384}
    assert (mix["warmup_steps"], mix["trace_steps"]) == (2, 4)
    assert by_name(spec["workloads"], "train_phi4miniflash_d6",
                   "workload")["traffic"] == cell["traffic"]


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Ling-3.0-flash-VL"][0]


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    row = catalog_row()
    catalog = row["config"]
    assert held["source"] == row["source_url"]
    # key by key: every key of the row is in the file, at its published
    # value but for `reduced`
    assert set(catalog) <= set(held)
    differ = sorted(k for k, v in catalog.items() if held[k] != v)
    assert differ == sorted(held["reduced"]) == sorted(REDUCED)
    for key, cut in held["reduced"].items():
        assert cut["here"] == held[key]
        if not key.endswith("_list"):
            assert cut["published"] == catalog[key]
        assert not selfcheck.WIDTH_KEY.search(key), key
    # every width as published
    assert (held["hidden_size"], held["intermediate_size"],
            held["moe_intermediate_size"],
            held["moe_shared_expert_intermediate_size"], held["head_dim"],
            held["kv_lora_rank"], held["qk_nope_head_dim"],
            held["qk_rope_head_dim"], held["v_head_dim"],
            held["num_experts_per_tok"], held["n_group"],
            held["topk_group"], held["short_conv_kernel_size"],
            held["kda_lower_bound"], held["q_lora_rank"]) == \
        (2560, 6144, 768, 768, 128, 512, 128, 64, 128, 8, 8, 4, 4, -5, None)
    # the cut: published layers 1-7, their clamp entries all 0
    first = held["share"]["layer_offset"]
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert catalog[key][first:first + 7] == held[key] == [0] * 7
        assert len(catalog[key]) == 42
    # the floors and the shares: 8 experts, an eighth of the vocabulary,
    # heads 4 ways, one whole period behind one dense layer
    assert held["num_experts"] == 8
    assert held["vocab_size"] * 8 == catalog["vocab_size"]
    assert held["num_attention_heads"] * 4 == catalog["num_attention_heads"]
    assert held["num_key_value_heads"] == held["num_attention_heads"]
    assert flops_kda_moe.layer_pattern(held) == "kKKKLKK"
    share = held["share"]
    assert (share["chips_per_layer"], share["head_parallel"],
            share["vocab_parallel"], share["router_experts"],
            share["expert_parallel"]) == (64, 4, 8, 512, 64)
    assert "35 layers" in held["stands_for"]
    for key in ("layer_kinds", "kda_gate", "kda_convolutions", "kda_rotary",
                "mla_qk_norm", "mla_queries", "router",
                "tie_word_embeddings", "multi_token_prediction",
                "vision_tower", "e_score_correction_bias", "initializer",
                "learning_rate"):
        assert key in held["assumed"], key
    assert "TO BE SET" not in held["tolerance"]["why"]
    job = load_module("jobs", "train_lm_kda_moe")
    cfg = job.transformer_config(held, held["train"], 16384)
    assert cfg.num_params == flops_kda_moe.total_params(held) == 562_238_640
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset,
            cfg.moe_groups, cfg.moe_topk_groups, cfg.moe_top_k) == \
        (512, 8, 0, 8, 4, 8)
    assert (cfg.head_dim, cfg.v_dim, cfg.rope_dim, cfg.kv_heads,
            cfg.q_lora_rank, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_chunk,
            cfg.shared_ff, cfg.ff_dim, cfg.moe_dense_ff) == \
        (192, 128, 64, 8, 0, 8, 128, 64, 768, 768, 6144)
    assert cfg.qk_norm and cfg.rope and cfg.rope_theta == 6e6
    assert cfg.kda_gate_lower == -5.0
    assert cfg.pattern_runs == [("k", 1), ("K", 3), ("L", 1), ("K", 2)]


@pytest.mark.parametrize("key,value,why", [
    ("q_lora_rank", 768, "query latent"),
    ("kda_safe_gate", False, "unbounded decay gate"),
    ("no_kda_lora", False, "low-rank decay"),
    ("linear_silu", False, "silu"),
    ("group_norm_size", 4, "several heads"),
    ("gated_attention_proj_granularity_type", "element_wise", "output gate"),
    ("num_kv_heads_for_linear_attn", 8, "grouped KDA"),
    ("score_function", "softmax", "sigmoid"),
    ("use_nGPT", True, "nGPT"),
    ("rotary_dim", 128, "rotary columns"),
    ("partial_rotary_factor", 1.0, "rotary columns"),
    ("num_key_value_heads", 4, "one key/value head"),
    ("expert_swiglu_limit_list", [0, 0, 0, 0, 0, 0, 4], "swiglu clamp"),
    ("share_expert_swiglu_limit_list", [0] * 6, "swiglu clamp"),
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_kda_moe")
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], 16384)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", "train_lm_kda_moe")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="kda_heads"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_kda_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset,
            cfg.layer_pattern) == (16, 4, 4, "kKKKLKK")
    key = jax.random.key(5000000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    assert abs(float(mine["embed"].std()) - 1.0) < 0.02
    bias = job.router_bias(mine)
    assert bias.shape == (6, 16)
    assert bias.any() and abs(float(np.abs(bias).max()) - 0.01) < 1e-6
    # every share's block of 4 holds the same values, in its own order
    blocks = np.sort(bias.reshape(-1, 4), axis=-1)
    assert (blocks == blocks[0]).all() and len(set(map(
        tuple, bias.reshape(-1, 4).tolist()))) > 1
    assert not job.router_bias(theirs).any()
    changed = {"embed", "kda_norm", "attn_norm", "mlp_norm", "kda_out_norm",
               "kv_a_norm", "q_norm", "k_norm", "kda_A_log", "kda_a_bias",
               "wq", "router_bias"}
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: str(path[-1].key) in changed
        or bool(np.array_equal(a, b)), mine, theirs)
    assert all(jax.tree.leaves(same)), same
    lo, hi = model["init"]["kda_A_range"]
    for mine_run, their_run in zip(mine["runs"], theirs["runs"]):
        for a, b in zip(mine_run, their_run):
            if "wq" in a:
                np.testing.assert_allclose(a["wq"], 3.0 * b["wq"])
                assert np.asarray(a["q_norm"]).std() > 0.1
            if "kda_A_log" in a:
                scale = np.exp(np.asarray(a["kda_A_log"]))
                assert lo <= scale.min() and scale.max() <= hi
                assert np.asarray(a["kda_a_bias"]).std() > 0.5
                assert not np.asarray(b["kda_a_bias"]).any()
                gain = np.asarray(a["kda_out_norm"])
                assert abs(gain.mean() - 1) < 0.2 and gain.std() > 0.1


def test_the_stand_in_decay_spreads_and_reaches_the_bound():
    """`log a` of the stand-in weights at the published widths: spread
    over (-5, 0), within 5% of the bound somewhere and near 0 somewhere
    (a decay of 1 hides a decay left out, one at the bound a state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.kda import log_decay
    model = load_json(CONFIG)
    lo, hi = model["init"]["kda_A_range"]
    ks = jax.random.split(jax.random.key(3), 4)
    heads, d = 8, 128
    # a normed stream of unit RMS through W_a at 1/sqrt(fan_in): N(0, 1)
    a = jax.random.normal(ks[0], (1, 512, heads, d))
    a_log = jax.random.uniform(ks[1], (heads,), jnp.float32, np.log(lo),
                               np.log(hi))
    bias = model["init"]["kda_a_bias_std"] * jax.random.normal(
        ks[2], (heads, d))
    g = np.asarray(log_decay(a, a_log, bias, float(
        model["kda_lower_bound"])))
    assert -5.0 < g.min() < -4.75 and -0.25 < g.max() < 0.0
    hist, _ = np.histogram(g, bins=5, range=(-5, 0))
    assert (hist / g.size > 0.08).all(), hist / g.size


def test_the_held_blocks_bias_is_shifted_until_the_share_is_even():
    import jax
    import numpy as np

    from benchlib.traffic import TokenBatches
    job = load_module("jobs", "train_lm_kda_moe")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    batches = TokenBatches(mix, model["vocab_size"], 11)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = job.init_params(jax.random.key(11), cfg, model["init"])
    out, info = job.balance_held_share(params, cfg, None, batches,
                                       model["init"])
    target = batches.tokens_per_step * 3 * 4 / 16
    assert info["target_slots"] == target
    off = lambda slots: np.abs(np.asarray(slots) - target).max()  # noqa: E731
    assert off(info["held_slots_after"]) <= max(
        0.05 * target, 0.5 * off(info["held_slots_before"]))
    # one shift per expert layer, in the layers' order, on the held block
    # (experts 4..8, the whole of group 1) alone; the dense layer's run
    # has no bias and is handed on as it is
    delta = job.router_bias(out) - job.router_bias(params)
    assert delta.shape == (6, 16)
    np.testing.assert_allclose(delta[:, 4:8], np.asarray(
        info["shift"])[:, None] * np.ones((1, 4)), atol=1e-7)
    assert not delta[:, :4].any() and not delta[:, 8:].any()
    assert np.abs(info["shift"]).max() <= model["init"]["balance_span"]
    still, nothing = job.balance_held_share(
        params, cfg, None, batches, dict(model["init"], balance_rounds=0))
    assert still is params and nothing is None


def test_the_reference_layout_is_in_the_layers_order():
    import jax

    job = load_module("jobs", "train_lm_kda_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    params = job.init_params(jax.random.key(1), cfg, model["init"])
    w = job.to_reference_layout(params, cfg)
    kinds = "".join(
        ("k" if "q_conv1d" in lw else "l") if "mlp" in lw
        else ("K" if "q_conv1d" in lw else "L") for lw in w["layers"])
    assert kinds == cfg.layer_pattern == "kKKKLKK"
    bias = job.router_bias(params)
    experts = [lw for lw in w["layers"] if "experts" in lw]
    for i, lw in enumerate(experts):
        assert (bias[i] == lw["e_score_correction_bias"]).all()
        assert sorted(lw["experts"]) == [4, 5, 6, 7]
    latent = [lw for lw in w["layers"] if "kv_a_proj_with_mqa" in lw]
    assert len(latent) == 1 and "q_a_proj" not in latent[0]
    assert latent[0]["q_layernorm"].shape == (24,)


def test_fault_reader_leaves_the_reference_plain():
    """`reference/ling3_faults.py` breaks copies of the reference, outside
    it: every fault and every narrower precision moves the logits
    (float32 here: each is far over rounding), and the module the job
    compares with is untouched."""
    import inspect

    faults = load_module("reference", "ling3_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.PRECISIONS)
    for name in faults.FAULTS:
        assert rows[name]["rel_l2"] > 1e-2, rows[name]
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "ling3_f32")
    for name in ("linear", "gated_mlp", "routed_experts", "forward",
                 "kda_attention", "latent_attention", "delta_rule"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    for name in ("rms_norm", "l2_norm", "decay_gate", "short_conv",
                 "qk_norm", "delta_rule"):
        assert getattr(plain, name).__module__ == plain.__name__


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-ling3", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-ling3.json",
        "reduced": ["num_experts"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_ling3", "config": "tiny-ling3",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_ling3")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_ling3"]))
    path = tmp_path_factory.mktemp("ling3_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_kda_moe_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_ling3", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        share = line["metrics"]["rehearsal_group_moe_held_slots_share"]
        assert 5.0 <= share["value"] <= 80.0 and share["unit"] == "%"
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_kda_delta_roofline" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
