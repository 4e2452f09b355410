"""What PR 35 adds to the benchmark for `train_nemotron3super_ep64_d11`,
checked without a chip: `benchlib/flops_ssm_moe.py` against hand-worked
numbers at the published widths, `benchlib/ssm_reduce.py` and the eight new
readers on a hand-made trace (and on a program or a run that gives them
nothing to read), the spec's new entries BY NAME and the configuration
file against the catalog row, what the job refuses, the stand-in weights,
the fault reader, and the job kind `train_lm_ssm_moe` rehearsed at a tiny
size on the CPU (a rehearsal's numbers carry the `rehearsal_` prefix and
are never a device metric)."""

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

from benchlib import (flops, flops_moe, flops_ssm_moe,  # noqa: E402
                      scope_reduce, ssm_reduce, subscope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_nemotron", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_nemotron3super_ep64_d11"
NAME = "nemotron-3-super-ep64-tp4-d11"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs",
                    "tiny-nemotron-h.json")
NEW_METRICS = ["ssm_share", "ssm_scan_share", "ssm_proj_share",
               "ssm_scan_roofline", "moe_latent_share", "moe_routed_share",
               "latent_moe_experts_roofline", "latent_moe_held_slots_share"]
TRACE_READERS = NEW_METRICS[:7]
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_ssm_moe_hand_worked():
    cfg = load_json(CONFIG)
    f = flops_ssm_moe
    assert f.router_experts(cfg) == 512
    assert (f.layers_of(cfg, "M"), f.layers_of(cfg, "E"),
            f.layers_of(cfg, "*")) == (5, 5, 1)
    assert (f.mixer_inner(cfg), f.mixer_conv_dim(cfg)) == (2048, 2560)
    # ISSUE 35's table, part by part (each without its norm of 4,096)
    assert f.mixer_matmul_params(cfg) == 4096 * 4640 + 2048 * 4096
    assert f.mixer_params(cfg) + 4096 == 27_413_088
    assert f.attention_params(cfg) + 4096 == 9_441_280
    assert f.expert_params(cfg) == 5_505_024
    assert f.shared_params(cfg) == 44_040_192
    assert f.latent_params(cfg) == 8_388_608
    assert f.router_params(cfg) == 2_097_152
    assert f.expert_layer_params(cfg) + 4096 == 98_570_240
    assert f.total_params(cfg) == 773_579_744
    assert 16 * f.total_params(cfg) == 12_377_275_904          # 12.38 GB
    # a token's matmul parameters at an even share of 22 x 8 / 512 slots
    even = 5 * 22 * 8 / 512
    per_token = f.matmul_params_per_token(cfg, even)
    assert per_token == (5 * 27_394_048 + 9_437_184
                         + 5 * (2_097_152 + 8_388_608 + 44_040_192)
                         + even * 5_505_024 + 4096 * 16384)
    assert 494e6 < per_token < 496e6
    # the scan, one mixer, forward, a token: the causal half of the
    # chunk's block for C.B (G·N = 256) and for the weights times x
    # (H·P = 2,048), the state in and out (2 x 2·H·P·N)
    assert f.scan_flops_per_token(cfg) == \
        (2 * 256 + 2 * 2048) * 129 / 2 + 4 * 2048 * 128
    assert f.scan_bytes_per_token(cfg) == 2 * (2048 + 512) + 4 * (32 + 2048)
    assert f.scan_passes_per_step(True) == 4
    least, bound = f.scan_least_time_s(cfg, 8192, 1, True, PEAKS)
    flops_s = 8192 * f.scan_flops_per_token(cfg) / 197e12
    bytes_s = 8192 * f.scan_bytes_per_token(cfg) / 819e9
    assert bound == "memory" and bytes_s > flops_s
    assert abs(least - 5 * 4 * bytes_s) < 1e-12
    # attention: one layer of 8 heads of 128 over the causal triangle
    assert f.attention_train_flops_per_token(cfg, 8192) == \
        flops.attention_matmul_flops(1, 8, 8192, 128, 6) / 8192
    total = f.train_flops_per_token(cfg, 8192, even)
    assert total == 6 * per_token \
        + f.attention_train_flops_per_token(cfg, 8192) \
        + 3 * 5 * f.scan_flops_per_token(cfg)
    assert 3.0e9 < total < 3.1e9


def test_held_experts_roofline_hand_worked():
    cfg = load_json(CONFIG)
    rows = [[2816, 3000, 0, 2500, 2816]]
    least, bound = flops_ssm_moe.held_experts_least_time_s(
        cfg, rows, True, PEAKS)
    want = 0.0
    for r in rows[0]:
        for k, n in ((1024, 2688), (2688, 1024)):
            t, _ = flops.least_time_s(
                flops_moe.grouped_matmul_flops(r, k, n),
                flops_moe.grouped_matmul_bytes(r, k, n, 8), PEAKS)
            want += 4 * t
    assert abs(least - want) < 1e-12 and bound == "memory"
    assert flops_ssm_moe.held_experts_least_time_s(
        cfg, rows, False, PEAKS)[0] == pytest.approx(want * 3 / 4)


# ---- the reducers and the readers --------------------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 900, STEP + "jvp(layers)/while"],
        ["%fusion.1 = f", 0, 60, FWD + "ssm/in_proj/btd,de->bte/dot_general"],
        ["%fusion.2 = f", 60, 20, FWD + "ssm/conv/mul"],
        ["%fusion.3 = f", 80, 100, FWD + "ssm/scan/zcijgr,zcjgrp->zcigrp/dot"],
        ["%while.2 = while()", 180, 40, BWD + "ssm/scan/while"],
        ["%fusion.4 = f", 220, 30, REMAT + "ssm/gate_norm/rsqrt"],
        ["%fusion.5 = f", 250, 50, BWD + "ssm/out_proj/transpose(jvp(x))/dot"],
        ["%fusion.6 = f", 300, 10, FWD + "ssm_norm/mul"],
        ["%fusion.7 = f", 310, 40, FWD + "moe/latent/nd,dr->nr/dot_general"],
        ["%fusion.8 = f", 350, 30, BWD + "moe/latent/transpose(jvp(y))/dot"],
        ["%fusion.9 = f", 380, 90, FWD + "moe/shared/nd,df->nf/dot_general"],
        ["%fusion.10 = f", 470, 25, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 495, 25, FWD + "moe/dispatch/sort"],
        ["%gmm.1 = custom-call()", 520, 80, FWD + "moe/experts/gmm"],
        ["%tgmm.1 = custom-call()", 600, 20,
         BWD + "moe/experts/transpose(jvp(gmm))"],
        ["%gather.2 = g", 620, 30, FWD + "moe/combine/gather"],
        ["%fusion.11 = f", 650, 50, FWD + "attention/splash"],
        ["%fusion.12 = f", 700, 100, STEP + "jvp(head)/dot"],
        ["%fusion.13 = f", 800, 100, FWD + "assm/scan/x"],   # not a scope
        ["%fusion.14 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_ssm_reduce_on_a_hand_made_trace():
    reduced = ssm_reduce.reduce_ssm(hand_made_trace())
    want = {"in_proj": 60, "conv": 20, "scan": 140, "gate_norm": 30,
            "out_proj": 50}
    assert set(reduced) == set(want)
    for sub, t in want.items():
        assert abs(reduced[sub] - t * 1e-9) < 1e-15, sub
    # `ssm` is no bucket of scope_reduce's: its ops are under `layers`
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert "ssm" not in scopes["bucket_s"]
    assert scopes["bucket_s"]["layers"] >= 300e-9
    assert ssm_reduce.reduce_ssm({"planes": []}) is None


@pytest.mark.parametrize("path,sub", [
    (FWD + "ssm/in_proj/btd,de->bte/dot_general:", "in_proj"),
    (STEP + "transpose(jvp(ssm/scan))/mul", "scan"),
    (REMAT + "ssm/scan/while/body/mul", "scan"),
    (FWD + "ssm_norm/mul", None),
    (FWD + "assm/scan/x", None),
    (FWD + "ssm/other/x", None),
])
def test_ssm_subscope_of_a_path(path, sub):
    assert ssm_reduce.subscope_of(path) == sub


def _record(**over):
    cfg = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "scan_call": {
            "model": {k: cfg[k] for k in (
                "hybrid_override_pattern", "mamba_num_heads",
                "mamba_head_dim", "n_groups", "ssm_state_size",
                "chunk_size")}, "tokens": 8192, "remat": True},
            "held_experts_call": {
            "model": {k: cfg[k] for k in (
                "moe_latent_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok")},
            "router_experts": 512, "tokens": 8192, "remat": True}},
        "counters": {"held_slots_share": [1.5, 1.7, 1.6, 3.0],
                     "traced_held_slots": [[1, 2, 1, 0, 3]]},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    monkeypatch.setattr(ssm_reduce, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_09_28"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["ssm_share"] - 30.0) < 1e-9
    assert abs(values["ssm_scan_share"] - 14.0) < 1e-9
    assert abs(values["ssm_proj_share"] - 16.0) < 1e-9
    assert abs(values["moe_latent_share"] - 7.0) < 1e-9
    assert abs(values["moe_routed_share"] - 18.0) < 1e-9
    assert values["latent_moe_held_slots_share"] == 1.65     # the median
    least, _ = flops_ssm_moe.scan_least_time_s(
        load_json(CONFIG), 8192, 1, True, PEAKS)
    assert values["ssm_scan_roofline"] == pytest.approx(
        100 * least / 140e-9)
    least, _ = flops_ssm_moe.held_experts_least_time_s(
        load_json(CONFIG), [[1, 2, 1, 0, 3]], True, PEAKS)
    assert values["latent_moe_experts_roofline"] == pytest.approx(
        100 * least / 100e-9)
    out = load_module("layer_metrics",
                      "latent_moe_experts_roofline").roofline(_record())
    assert out["bound"] == "memory"     # seven rows against 8 experts
    # the accepted readers read the same trace as they did
    assert abs(load_module("layer_metrics", "moe_shared_share").read(
        _record()) - 9.0) < 1e-9
    assert load_module("layer_metrics", "mlp_share").read(_record()) == 0.0


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no mixer, no latent
    # (GLM's step keeps the four routed names, so `moe_routed_share`
    # reads there; its `held_experts_call` is not a latent's)
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("ssm/", "mlp/").replace(
                    "moe/latent", "moe/shared")
    _fresh(monkeypatch, bare)
    glm = _record()
    glm["static"]["held_experts_call"]["model"] = {
        "hidden_size": 2048, "moe_intermediate_size": 1536,
        "n_routed_experts": 8, "num_experts_per_tok": 4}
    if name == "moe_routed_share":
        assert read(glm) is not None
    else:
        assert read(glm) is None
    # a dense model's program: nothing under `moe` at all
    dense = hand_made_trace()
    for line in dense["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("moe/", "mlp/")
    _fresh(monkeypatch, dense)
    if "moe" in name:
        assert read(_record()) is None
    # a record without the job's counters or calls
    _fresh(monkeypatch, hand_made_trace())
    if name.endswith("roofline"):
        assert read(_record(static={"peaks": PEAKS})) is None
    if name == "latent_moe_experts_roofline":
        assert read(_record(counters={})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


def test_counter_reader_with_nothing_to_read():
    read = load_module("layer_metrics", "latent_moe_held_slots_share").read
    assert read({}) is None
    assert read({"counters": {}}) is None
    assert read({"counters": {"held_slots_share": []}}) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, not by position: a later PR appends behind them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_1seq", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["source"].endswith(
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # behind the accepted entries, which keep their order
    names = [w["name"] for w in spec["workloads"]]
    assert names[:4] == ["train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                         "train_olmoe_d1", "train_glm47flash_ep8_d5"]
    assert names.index(CELL) >= 4
    per_layer = [m["name"] for m in spec["per_layer"]]
    at = per_layer.index(NEW_METRICS[0])
    assert per_layer[at:at + 8] == NEW_METRICS
    assert at > per_layer.index("held_expert_load_max_over_mean")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    for name in ("ssm_scan_roofline", "latent_moe_experts_roofline"):
        assert (mine[name]["unit"], mine[name]["layer"]) == ("%", "kernels")
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "moe_experts_roofline", "moe_held_share",
                 "mla_down_share", "collective_exposed_share"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}
    assert (mix["warmup_steps"], mix["trace_steps"]) == (2, 4)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"][0]


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    row = catalog_row()
    catalog = row["config"]
    assert held["source"] == row["source_url"]
    differ = sorted(k for k, v in catalog.items() if held.get(k, "") != v)
    assert differ == sorted(held["reduced"]) == sorted(REDUCED)
    for key, cut in held["reduced"].items():
        assert cut["published"] == catalog[key] and cut["here"] == held[key]
        assert not selfcheck.WIDTH_KEY.search(key), key
    # every width as published
    assert (held["hidden_size"], held["mamba_head_dim"],
            held["ssm_state_size"], held["head_dim"],
            held["moe_intermediate_size"], held["moe_latent_size"],
            held["moe_shared_expert_intermediate_size"],
            held["num_experts_per_tok"], held["chunk_size"]) == \
        (4096, 64, 128, 128, 2688, 1024, 5376, 22, 128)
    # the cut: layers 27-37 of the published pattern, a whole period
    assert catalog["hybrid_override_pattern"][27:38] == \
        held["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert len(catalog["hybrid_override_pattern"]) == 88
    # the floors and the shares: 8 experts, an eighth of the vocabulary,
    # heads and their groups 4 ways with the published heads a group
    assert held["n_routed_experts"] == 8
    assert held["vocab_size"] * 8 == catalog["vocab_size"]
    assert held["mamba_num_heads"] * 4 == catalog["mamba_num_heads"]
    assert held["n_groups"] * 4 == catalog["n_groups"]
    assert held["num_attention_heads"] * 4 == catalog["num_attention_heads"]
    share = held["share"]
    assert (share["chips_per_layer"], share["head_parallel"],
            share["vocab_parallel"], share["router_experts"]) == \
        (64, 4, 8, 512)
    for key in ("rotary_embedding", "router", "dt", "initializer",
                "router_and_shared_expert_read_the_stream",
                "e_score_correction_bias", "learning_rate",
                "multi_token_prediction"):
        assert key in held["assumed"], key
    assert "PLACEHOLDER" not in held["tolerance"]["why"]
    job = load_module("jobs", "train_lm_ssm_moe")
    cfg = job.transformer_config(held, held["train"], 8192)
    assert cfg.num_params == flops_ssm_moe.total_params(held) == 773_579_744
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset) == \
        (512, 8, 0)
    assert (cfg.head_dim, cfg.kv_heads, cfg.ssm_inner, cfg.ssm_conv_dim,
            cfg.moe_latent, cfg.shared_ff, cfg.ff_dim) == \
        (128, 1, 2048, 2560, 1024, 5376, 2688)
    assert not cfg.rope and cfg.pattern_runs == [("ME", 4), ("M*E", 1)]


@pytest.mark.parametrize("key,value,why", [
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("n_group", 4, "group-limited"),
    ("topk_group", 2, "group-limited"),
    ("mlp_hidden_act", "silu", "relu2"),
    ("use_conv_bias", False, "bias"),
    ("sliding_window", 4096, "window"),
    ("mamba_proj_bias", True, "no bias"),
    ("hybrid_override_pattern", "MEMEMEMEM-E", "M, E and"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_ssm_moe")
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
    job = load_module("jobs", "train_lm_ssm_moe")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="layer_pattern"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_ssm_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset) == \
        (16, 4, 4)
    key = jax.random.key(3500000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    assert abs(float(mine["embed"].std()) - 1.0) < 0.02
    bias = job.router_bias(mine)
    assert bias.shape == (3, 16)
    assert bias.any() and abs(float(np.abs(bias).max()) - 0.01) < 1e-6
    # every share's block of 4 holds the same values, in its own order
    blocks = np.sort(bias.reshape(-1, 4), axis=-1)
    assert (blocks == blocks[0]).all() and len(set(map(
        tuple, bias.reshape(-1, 4).tolist()))) > 1
    assert not job.router_bias(theirs).any()
    changed = {"embed", "ssm_norm", "attn_norm", "mlp_norm", "gate_norm",
               "conv_b", "wq", "router_bias"}
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: str(path[-1].key) in changed
        or bool(np.array_equal(a, b)), mine, theirs)
    assert all(jax.tree.leaves(same)), same
    for mine_run, their_run in zip(mine["runs"], theirs["runs"]):
        for a, b in zip(mine_run, their_run):
            if "wq" in a:
                np.testing.assert_allclose(a["wq"], 3.0 * b["wq"])
            if "gate_norm" in a:
                gain = np.asarray(a["gate_norm"])
                assert abs(gain.mean() - 1) < 0.1 and gain.std() > 0.2
                assert np.asarray(a["conv_b"]).std() > 0.2
                # A in [1, 16], dt in [0.001, 0.1]: the published ranges
                a_ = np.exp(np.asarray(b["A_log"]))
                dt = np.log1p(np.exp(np.asarray(b["dt_bias"])))
                assert 1 <= a_.min() and a_.max() <= 16
                assert 1e-3 <= dt.min() * 1.001 and dt.max() <= 0.1001
                assert (np.asarray(b["D"]) == 1).all()


def test_the_held_blocks_bias_is_shifted_until_the_share_is_even():
    import jax
    import numpy as np

    from benchlib.traffic import TokenBatches
    job = load_module("jobs", "train_lm_ssm_moe")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    batches = TokenBatches(mix, model["vocab_size"], 11)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = job.init_params(jax.random.key(11), cfg, model["init"])
    out, info = job.balance_held_share(params, cfg, None, batches,
                                       model["init"])
    target = batches.tokens_per_step * 6 * 4 / 16
    assert info["target_slots"] == target
    off = lambda slots: np.abs(np.asarray(slots) - target).max()  # noqa: E731
    assert off(info["held_slots_after"]) <= max(
        0.05 * target, 0.5 * off(info["held_slots_before"]))
    # one shift per expert layer, in the layers' order, on the held block
    # (experts 4..8) alone
    delta = job.router_bias(out) - job.router_bias(params)
    np.testing.assert_allclose(delta[:, 4:8], np.asarray(
        info["shift"])[:, None] * np.ones((1, 4)), atol=1e-7)
    assert not delta[:, :4].any() and not delta[:, 8:].any()
    assert np.abs(info["shift"]).max() <= model["init"]["balance_span"]
    still, nothing = job.balance_held_share(
        params, cfg, None, batches, dict(model["init"], balance_rounds=0))
    assert still is params and nothing is None


def test_the_reference_layout_is_in_the_layers_order():
    import jax

    job = load_module("jobs", "train_lm_ssm_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    params = job.init_params(jax.random.key(1), cfg, model["init"])
    w = job.to_reference_layout(params, cfg)
    kinds = ["M" if "in_proj" in lw else "*" if "q_proj" in lw else "E"
             for lw in w["layers"]]
    assert "".join(kinds) == model["hybrid_override_pattern"] == "MEMEM*E"
    bias = job.router_bias(params)
    experts = [lw for lw in w["layers"] if "experts" in lw]
    for i, lw in enumerate(experts):
        assert (bias[i] == lw["e_score_correction_bias"]).all()
        assert sorted(lw["experts"]) == [4, 5, 6, 7]


def test_fault_reader_leaves_the_reference_plain():
    """`reference/nemotron_h_faults.py` breaks copies of the reference,
    outside it: every fault and every narrower precision moves the logits
    (float32 here: each is far over rounding), and the module the job
    compares with is untouched."""
    import inspect

    faults = load_module("reference", "nemotron_h_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.PRECISIONS)
    for name in faults.FAULTS:
        assert rows[name]["rel_l2"] > 1e-2, rows[name]
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "nemotron_h_f32")
    for name in ("linear", "plain_mlp", "latent_experts", "forward",
                 "mamba2_mixer", "attention", "selective_scan"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    assert plain.rms_norm.__module__ == plain.__name__
    assert plain.relu2.__module__ == plain.__name__


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-nemotron-h", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-nemotron-h.json",
        "reduced": ["n_routed_experts"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_nemotron", "config": "tiny-nemotron-h",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_nemotron")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_nemotron"]))
    path = tmp_path_factory.mktemp("nemotron_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_ssm_moe_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_nemotron", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        share = line["metrics"]["rehearsal_latent_moe_held_slots_share"]
        assert 5.0 <= share["value"] <= 80.0 and share["unit"] == "%"
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_ssm_scan_roofline" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
