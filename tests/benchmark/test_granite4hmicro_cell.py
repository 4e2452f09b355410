"""What PR 68 adds to the benchmark for `train_granite4hmicro_d10_packed`,
checked without a chip: `benchlib/flops_granite.py` against hand-worked
numbers at the published widths, the six new readers on a hand-made trace
and on a record's counters (and on a program or a run that gives them
nothing to read), the spec's new entries BY NAME, never by position, and
the configuration file against the catalog row, what the job refuses, the
stand-in weights, the fault reader, and the job kind
`train_lm_granite_packed` rehearsed at a tiny size on the CPU (a
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

from benchlib import (flops, flops_granite, scope_reduce,  # noqa: E402
                      ssm_reduce, subscope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_granite", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_granite4hmicro_d10_packed"
NAME = "granite-4.0-h-micro-d10-v8"
JOB = "train_lm_granite_packed"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs",
                    "tiny-granite-hybrid.json")
NEW_METRICS = ["packed_ssm_share", "packed_ssm_scan_share",
               "packed_ssm_proj_share", "packed_ssm_scan_roofline",
               "packed_attn_pairs_computed_over_needed",
               "packed_docs_per_step"]
TRACE_READERS = NEW_METRICS[:4]
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_granite_hand_worked():
    cfg = load_json(CONFIG)
    f = flops_granite
    assert f.layer_pattern(cfg) == "nnnnnlnnnn"
    assert (f.layers_of(cfg, "mamba"), f.layers_of(cfg, "attention")) == \
        (9, 1)
    assert (f.mixer_inner(cfg), f.mixer_conv_dim(cfg), f.head_dim(cfg)) == \
        (4096, 4352, 64)
    # ISSUE 68's table, part by part
    assert f.mixer_matmul_params(cfg) == 2048 * 8512 + 4096 * 2048
    assert f.mixer_params(cfg) == 25_847_232
    assert f.mlp_params(cfg) == 50_331_648
    assert f.mixer_params(cfg) + f.mlp_params(cfg) + 4096 == 76_182_976
    assert f.attention_params(cfg) == 10_485_760
    assert f.attention_params(cfg) + f.mlp_params(cfg) + 4096 == 60_821_504
    assert f.total_params(cfg) == 772_160_448
    assert 16 * f.total_params(cfg) == 12_354_567_168          # 12.35 GB
    whole = dict(cfg, num_hidden_layers=40, vocab_size=100352,
                 layer_types=cfg["reduced"]["layer_types"]["published"])
    assert f.total_params(whole) == 3_191_396_096
    per_token = f.matmul_params_per_token(cfg)
    assert per_token == (9 * (2048 * 8512 + 4096 * 2048) + 10_485_760
                         + 10 * 50_331_648 + 2048 * 12544)
    # the scan, one mixer, forward, a token: the causal half of the
    # chunk's block for C.B (G·N = 128) and for the weights times x
    # (H·P = 4,096), the state in and out (2 x 2·H·P·N)
    assert f.scan_flops_per_token(cfg) == \
        (2 * 128 + 2 * 4096) * 257 / 2 + 4 * 4096 * 128
    assert f.scan_bytes_per_token(cfg) == 2 * (4096 + 256) + 4 * (64 + 4096)
    assert f.scan_passes_per_step(True) == 4
    assert f.scan_passes_per_step(False) == 3
    least, bound = f.scan_least_time_s(cfg, 8192, 1, True, PEAKS)
    flops_s = 8192 * f.scan_flops_per_token(cfg) / 197e12
    bytes_s = 8192 * f.scan_bytes_per_token(cfg) / 819e9
    assert bound == "memory" and bytes_s > flops_s
    assert abs(least - 9 * 4 * bytes_s) < 1e-12
    # attention over the pairs the documents need: ten documents of 819
    # against the causal triangle of the sequence
    pairs = 10 * 819 * 820 // 2
    assert f.attention_train_flops(cfg, pairs) == \
        6 * 2 * 64 * 32 * pairs
    assert f.attention_train_flops(cfg, 8192 * 8193 / 2) == pytest.approx(
        flops.attention_matmul_flops(1, 32, 8192, 64, 6), rel=2e-4)
    total = f.train_flops_per_token(cfg, 8192, pairs)
    assert total == 6 * per_token + f.attention_train_flops(cfg, pairs) \
        / 8192 + 3 * 9 * f.scan_flops_per_token(cfg)
    assert 4.5e9 < total < 4.9e9


# ---- the readers ---------------------------------------------------------

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
        ["%fusion.3 = f", 80, 10, FWD + "ssm/conv/segments/eq"],
        ["%ssd_scan_fwd.1 = custom-call()", 90, 100,
         FWD + "ssm/scan/pallas_call"],
        ["%fusion.4 = f", 190, 10, FWD + "ssm/scan/segments/eq"],
        ["%ssd_scan_bwd.1 = custom-call()", 200, 40,
         BWD + "ssm/scan/transpose(jvp(ssd_scan_bwd))/pallas_call"],
        ["%fusion.5 = f", 240, 30, REMAT + "ssm/gate_norm/rsqrt"],
        ["%fusion.6 = f", 270, 50, BWD + "ssm/out_proj/transpose(jvp(x))/dot"],
        ["%fusion.7 = f", 320, 10, FWD + "ssm_norm/mul"],
        ["%fusion.8 = f", 330, 170, FWD + "mlp/gate_up/btd,dgf->btgf/dot"],
        ["%fusion.9 = f", 500, 100, FWD + "mlp/down/btf,fd->btd/dot"],
        ["%fusion.10 = f", 600, 50, FWD + "attention/splash"],
        ["%fusion.11 = f", 650, 50, STEP + "jvp(loss)/segments/cummax"],
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


def _record(**over):
    cfg = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1,
                  "kernel_s": {"scan": {"fwd": [100e-9, 1],
                                        "bwd": [40e-9, 1]}}},
        "static": {"peaks": PEAKS, "packed_scan_call": {
            "model": {k: cfg[k] for k in (
                "layer_types", "mamba_n_heads", "mamba_d_head",
                "mamba_n_groups", "mamba_d_state", "mamba_chunk_size")},
            "tokens": 8192, "remat": True},
            "packed_attn_pairs_computed": 36 * 1024 * 1024},
        "counters": {"packed_docs": [9, 11, 10, 30],
                     "packed_attn_pairs_needed": [
                         4_000_000, 6_291_456, 9_000_000]},
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
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_10_05"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    # the marks and masks under `segments` count with their sub-scope
    assert abs(values["packed_ssm_share"] - 32.0) < 1e-9
    assert abs(values["packed_ssm_scan_share"] - 15.0) < 1e-9
    assert abs(values["packed_ssm_proj_share"] - 17.0) < 1e-9
    least, bound = flops_granite.scan_least_time_s(
        load_json(CONFIG), 8192, 1, True, PEAKS)
    # the kernels' own events: 100 + 40 ns, not the scope's 150
    assert values["packed_ssm_scan_roofline"] == pytest.approx(
        100 * least / 140e-9)
    out = load_module("layer_metrics",
                      "packed_ssm_scan_roofline").roofline(_record())
    assert (out["bound"], out["time_of"]) == ("memory", "kernels")
    # the scan in plain XLA: no kernel event, the scope's time
    xla = load_module("layer_metrics", "packed_ssm_scan_roofline").roofline(
        _record(trace={"devices": 1, "modules_per_device": 1}))
    assert xla["time_of"] == "scope"
    assert xla["share"] == pytest.approx(100 * least / 150e-9)
    assert values["packed_attn_pairs_computed_over_needed"] == 6.0
    assert values["packed_docs_per_step"] == 10.5
    # the accepted readers read the same trace as they always did: the
    # boundary work in the loss is booked under `loss`
    assert load_module("layer_metrics", "mlp_share").read(
        _record()) == pytest.approx(27.0)
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert scopes["bucket_s"]["loss"] == pytest.approx(50e-9)
    assert abs(load_module("layer_metrics", "ssm_scan_share").read(
        _record()) - 15.0) < 1e-9


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no mixer
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("ssm/", "mlp/")
    _fresh(monkeypatch, bare)
    if name == "packed_ssm_scan_roofline":   # no kernel event either
        assert read(_record(trace={"devices": 1,
                                   "modules_per_device": 1})) is None
    else:
        assert read(_record()) is None
    # a record without the job's call (another job's record)
    _fresh(monkeypatch, hand_made_trace())
    if name.endswith("roofline"):
        assert read(_record(static={"peaks": PEAKS})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    if name != "packed_ssm_scan_roofline":
        assert read(_record()) is None


@pytest.mark.parametrize("name", NEW_METRICS[4:])
def test_counter_reader_with_nothing_to_read(name):
    read = load_module("layer_metrics", name).read
    assert read({}) is None
    assert read({"counters": {}, "static": {}}) is None
    assert read({"counters": {"packed_docs": [],
                              "packed_attn_pairs_needed": []},
                 "static": {"packed_attn_pairs_computed": None}}) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, never by position: a later PR appends behind them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_8k_packed", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    assert len(entry["why"]) <= 200
    # behind the accepted entries, which keep their order
    names = [w["name"] for w in spec["workloads"]]
    assert names[:4] == ["train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                         "train_olmoe_d1", "train_glm47flash_ep8_d5"]
    assert names.index(CELL) > names.index("train_xing4_ep8_d5")
    assert [w["config"] for w in spec["workloads"]].count(NAME) == 1
    per_layer = [m["name"] for m in spec["per_layer"]]
    at = per_layer.index(NEW_METRICS[0])
    assert per_layer[at:at + 6] == NEW_METRICS
    assert at > per_layer.index("mhc_moe_held_slots_share")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    assert (mine["packed_ssm_scan_roofline"]["unit"],
            mine["packed_ssm_scan_roofline"]["layer"]) == ("%", "kernels")
    # the other cells' metrics keep their lists (Nemotron's `ssm_*` too);
    # the readers without one apply here
    for name in ("ssm_share", "ssm_scan_roofline", "mamba1_share",
                 "moe_share", "gdn_share", "collective_exposed_share"):
        assert name not in mine
        listed = by_name(spec["per_layer"], name, "metric")["workloads"]
        assert CELL not in listed
    assert by_name(spec["per_layer"], "ssm_share", "metric")[
        "workloads"] == ["train_nemotron3super_ep64_d11"]
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_8k_packed.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}
    assert (mix["warmup_steps"], mix["trace_steps"]) == (2, 4)
    assert mix["unigram"] == {"law": "zipf", "exponent": 1.1}
    assert mix["documents"] == {"law": "lognormal", "median": 512,
                                "sigma": 1.0, "min": 16, "max": 8192}


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "granite-4.0-h-micro"][0]


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
    # every width, head count, the chunk, the one group and the four
    # scalars as published
    assert (held["hidden_size"], held["intermediate_size"],
            held["shared_intermediate_size"], held["mamba_d_head"],
            held["mamba_d_state"], held["mamba_n_heads"],
            held["mamba_n_groups"], held["mamba_chunk_size"],
            held["mamba_d_conv"], held["num_attention_heads"],
            held["num_key_value_heads"]) == \
        (2048, 8192, 8192, 64, 128, 64, 1, 256, 4, 32, 8)
    assert (held["embedding_multiplier"], held["residual_multiplier"],
            held["attention_multiplier"], held["logits_scaling"]) == \
        (12, 0.22, 0.015625, 8)
    assert held["tie_word_embeddings"] is True
    assert held["position_embedding_type"] == "nope"
    # the cut: layers 0-9 of the published list, a whole period 9 : 1
    assert catalog["layer_types"][:10] == held["layer_types"]
    assert held["layer_types"].count("attention") == 1
    assert catalog["layer_types"].count("attention") * 10 == 40
    assert held["vocab_size"] * 8 == catalog["vocab_size"]
    for key in ("position_embedding", "dt", "initializer", "learning_rate",
                "packing", "experts"):
        assert key in held["assumed"], key
    assert "four pipeline stages" in held["stands_for"]
    assert "TO BE WRITTEN" not in held["tolerance"]["why"]
    assert held["train"]["expect_scan"] == "pallas"
    assert held["train"]["expect_attention"] == "flash"
    job = load_module("jobs", JOB)
    cfg = job.transformer_config(held, held["train"], 8192)
    assert cfg.num_params == flops_granite.total_params(held) == 772_160_448
    assert (cfg.head_dim, cfg.kv_heads, cfg.ssm_inner, cfg.ssm_conv_dim,
            cfg.ff_dim, cfg.ssm_chunk) == (64, 8, 4096, 4352, 8192, 256)
    assert not cfg.rope and cfg.tie_embeddings
    assert cfg.pattern_runs == [("n", 5), ("l", 1), ("n", 4)]
    assert (cfg.embed_scale, cfg.residual_scale, cfg.softmax_scale,
            cfg.logit_divisor) == (12.0, 0.22, 0.015625, 8.0)
    from ray_tpu.ops.ssm import scan_head_block, scan_shape_ok
    assert scan_shape_ok(8192, 64, 64, 1, 128, 256)
    assert scan_head_block(64, 64, 256) == 8


@pytest.mark.parametrize("key,value,why", [
    ("num_local_experts", 8, "routed experts"),
    ("position_embedding_type", "rope", "position embedding"),
    ("tie_word_embeddings", False, "untied"),
    ("mamba_conv_bias", False, "bias"),
    ("attention_bias", True, "bias"),
    ("hidden_act", "gelu", "SiLU"),
    ("layer_types", ["mamba"] * 9 + ["moe"], "layer_types"),
    ("shared_intermediate_size", 4096, "shared_intermediate_size"),
    ("time_step_limit", [0.0, 1.0], "does not honour"),
])
def test_the_job_refuses_what_it_does_not_honour(key, value, why):
    job = load_module("jobs", JOB)
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], 8192)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds, with no hang)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", JOB)
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS[:4]])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    ctx = {"config": load_json(CONFIG), "cell": {"name": CELL},
           "traffic": load_json(os.path.join(BENCH_DIR, "traffic",
                                             "sft_8k_packed.json"))}
    with pytest.raises(RuntimeError, match="embed_scale"):
        job.run(ctx)
    assert not started
    monkeypatch.undo()
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(ValueError, match="documents"):
        job.run(dict(ctx, traffic=load_json(os.path.join(
            BENCH_DIR, "traffic", "sft_1seq.json"))))
    assert not started


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", JOB)
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert cfg.layer_pattern == "nnln"
    key = jax.random.key(3500000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert np.array_equal(mine["embed"], theirs["embed"])    # 0.02, tied
    assert "lm_head" not in mine
    changed = set()
    for subs, plain in zip(mine["runs"], theirs["runs"]):
        for sub, base in zip(subs, plain):
            for name in sub:
                if not np.array_equal(sub[name], base[name]):
                    changed.add(name)
    assert changed == {"ssm_norm", "attn_norm", "mlp_norm", "gate_norm",
                       "conv_b", "D", "wq"}
    mixer = mine["runs"][0][0]
    assert 0.2 < float(mixer["gate_norm"].std()) < 0.4
    assert 0.2 < float(mixer["conv_b"].std()) < 0.4
    attn = mine["runs"][1][0]
    assert np.allclose(attn["wq"], 8.0 * theirs["runs"][1][0]["wq"])
    assert not np.array_equal(mine["final_norm"], theirs["final_norm"])
    weights = job.to_reference_layout(mine, cfg)
    assert len(weights["layers"]) == 4 and "lm_head" not in weights
    assert weights["layers"][0]["input_linear"].shape == (2 * 96, 64)
    assert weights["layers"][2]["q_proj"].shape == (64, 64)


def test_faults_show_at_a_tiny_size():
    """Every fault of `granite_hybrid_faults.py` moves a reading over a
    limit (the crossing labels by their count), the precisions in their
    order, on the rehearsal's configuration in float32."""
    import inspect

    faults = load_module("reference", "granite_hybrid_faults")
    model = load_json(TINY)
    model["train"] = dict(model["train"], compute_dtype="float32")
    model["tolerance"] = {"logits_rel_l2": 0.02, "loss_abs": 0.01}
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny_packed.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.PRECISIONS)
    for name in faults.FAULTS:
        assert not rows[name]["correct"], rows[name]
    assert rows["crossing_labels"]["rel_l2"] == 0.0
    assert rows["crossing_labels"]["labels"] > rows["no_D_skip"]["labels"]
    for name in set(faults.FAULTS) - {"crossing_labels"}:
        assert rows[name]["rel_l2"] > 2e-2, rows[name]
    assert rows["bfloat16"]["correct"]
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "granite_hybrid_f32")
    for name in ("linear", "gated_mlp", "forward", "mamba2_mixer",
                 "attention", "selective_scan"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    assert plain.rms_norm.__module__ == plain.__name__


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-granite-hybrid", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-granite-hybrid.json",
        "reduced": [], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_granite", "config": "tiny-granite-hybrid",
        "traffic": "rehearsal_tiny_packed", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_granite")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_METRICS:
        spec["per_layer"].append(dict(
            by_name(real["per_layer"], name, "metric"),
            workloads=["rehearse_train_granite"]))
    path = tmp_path_factory.mktemp("granite_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_granite_packed_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_granite", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        docs = line["metrics"]["rehearsal_packed_docs_per_step"]
        assert 4 <= docs["value"] <= 40 and docs["unit"] == "docs"
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_packed_ssm_scan_roofline" not in line["metrics"]
        # dense attention on the CPU: no kernel blocks to count
        assert "rehearsal_packed_attn_pairs_computed_over_needed" \
            not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
