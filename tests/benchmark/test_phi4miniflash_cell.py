"""What PR 43 adds to the benchmark for `train_phi4miniflash_d6`, checked
without a chip: `benchlib/flops_sambay.py` against hand-worked numbers at
the published widths, `benchlib/sambay_reduce.py` and the six new readers
on a hand-made trace (and on a program or a run that gives them nothing
to read), the spec's new entries BY NAME and the configuration file
against the catalog row, what the job refuses, the stand-in weights, every
fault of `reference/phi4flash_faults.py` at a small size, and the job kind
`train_lm_sambay` rehearsed at a tiny size on the CPU (a rehearsal's
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

from benchlib import (flops, flops_sambay, sambay_reduce,  # noqa: E402
                      scope_reduce, ssm_reduce, subscope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_phi4flash", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_phi4miniflash_d6"
NAME = "phi-4-mini-flash-reasoning-d6-v8"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-phi4flash.json")
NEW_METRICS = ["mamba1_share", "mamba1_scan_share", "mamba1_scan_roofline",
               "gmu_share", "diff_attn_combine_share",
               "masked_attn_kernel_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
T = 16384


# ---- arithmetic --------------------------------------------------------


def test_flops_sambay_hand_worked():
    cfg = load_json(CONFIG)
    f = flops_sambay
    assert (f.kinds(cfg), f.head_dim(cfg), f.mixers(cfg)) == ("mwsfgc", 64, 2)
    # ISSUE 43's table, part by part
    assert f.mlp_params(cfg) == 78_643_200
    assert f.mixer_params(cfg) == 41_241_600
    assert f.mixer_matmul_params(cfg) == \
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert f.layer_params(cfg, "m") == f.layer_params(cfg, "s") \
        == 119_895_040
    assert f.layer_params(cfg, "w") == f.layer_params(cfg, "f") \
        == 98_322_304
    assert f.layer_params(cfg, "g") == 104_867_840
    assert f.layer_params(cfg, "c") == 91_766_144
    assert sum(f.layer_params(cfg, k) for k in "mwsfgc") == 633_068_672
    assert f.total_params(cfg) == 697_094_272
    assert 16 * f.total_params(cfg) == 11_153_508_352          # 11.15 GB
    # the whole model by the same count
    whole = dict(cfg, num_hidden_layers=32, vocab_size=200064,
                 layer_kinds="mw" * 8 + "sf" + "gc" * 7)
    assert f.total_params(whole) == 3_852_562_944
    # a token's matmul parameters: everything but norms, biases, lambda,
    # the convolution, A and D; the tied head is a matmul
    per_token = f.matmul_params_per_token(cfg)
    assert per_token == 696_770_560
    # attention: a map's QK^T is 64 wide and its PV 128: 3 x 64 forward,
    # 6 x 64 more backward, 40 maps; a window call over T W - W^2 / 2
    # pairs, a causal one over T^2 / 2
    assert f.attention_pairs("full", T, 512) == T * T / 2
    assert f.attention_pairs("cross", T, 512) == T * T / 2
    assert f.attention_pairs("window", T, 512) == T * 512 - 512 * 512 / 2
    assert f.attention_pairs("window", 256, 512) == 256 * 256 / 2
    pairs = 2 * T * T / 2 + T * 512 - 512 * 512 / 2
    assert f.attention_train_flops_per_token(cfg, T) == \
        2 * 9 * 64 * 40 * pairs / T
    # ISSUE 43's 7,680 T a token, layer and forward
    assert f.attention_call_flops("fwd", "full", cfg, T) / T == 7680 * T
    assert f.attention_call_flops("bwd_fused", "full", cfg, T) == \
        2 * (3 * 64 + 2 * 128) * 40 * T * T / 2
    assert f.attention_call_flops("bwd_dkv", "window", cfg, T) == \
        2 * (2 * 64 + 2 * 128) * 40 * (T * 512 - 512 * 512 / 2)
    assert f.attention_call_flops("bwd_dq", "cross", cfg, T) == \
        2 * (2 * 64 + 128) * 40 * T * T / 2
    # a window call does a sixteenth of a full one
    assert 15.7 < f.attention_call_flops("fwd", "full", cfg, T) \
        / f.attention_call_flops("fwd", "window", cfg, T) < 16.3
    q, k = 40 * T * 64 * 2, 20 * T * 64 * 2
    assert f.attention_call_bytes("fwd", cfg, T) == \
        q + 2 * k + 2 * q + 2 * 40 * T * 4
    assert f.attention_call_bytes("bwd_fused", cfg, T) == \
        2 * q + 2 * q + 4 * k + 3 * 40 * T * 4
    # the scan: 7 operations a state element, memory-bound
    assert f.scan_flops_per_token(cfg) == 7 * 5120 * 16
    assert f.scan_bytes_per_token(cfg) == 2 * 5120 + 4 * (2 * 5120 + 32)
    least, bound = f.scan_least_time_s(cfg, T, 1, True, PEAKS)
    assert bound == "memory"
    assert least == pytest.approx(
        2 * 4 * T * f.scan_bytes_per_token(cfg) / 819e9)
    total = f.train_flops_per_token(cfg, T)
    assert total == 6 * per_token \
        + f.attention_train_flops_per_token(cfg, T) \
        + 3 * 2 * f.scan_flops_per_token(cfg)
    assert 4.9e9 < total < 5.0e9
    assert 0.15 < f.attention_train_flops_per_token(cfg, T) / total < 0.16


def test_the_one_shape_reader_is_given_a_shape_that_does_not_overcount():
    """`attn_kernel_roofline` counts every attention event as one causal
    call of `static.attention_call` with both products head_dim wide: at
    the true shape it would count 3 x 40 x 4 x 64 forward where the calls
    computed 2.06 x 40 x 6 x 64. The job hands it the longest sequence at
    which its count is not above the truth, for every kind of call."""
    cfg = load_json(CONFIG)
    call = flops_sambay.attention_call_not_above(cfg, T)
    assert (call["heads"], call["kv_heads"], call["head_dim"],
            call["batch"]) == (40, 20, 64, 1)
    assert call["seq"] % 128 == 0 and call["seq"] < T
    ratios = {}
    for kind in ("fwd", "bwd_dkv", "bwd_dq", "bwd_fused"):
        truth = sum(flops_sambay.attention_call_flops(kind, k, cfg, T)
                    for k in ("window", "full", "cross"))
        counted = 3 * flops.attention_call_flops(
            kind, 1, 40, call["seq"], 64)
        assert counted <= truth, kind
        ratios[kind] = counted / truth
        # one block of 128 longer and some kind is over
    longer = call["seq"] + 128
    assert any(3 * flops.attention_call_flops(kind, 1, 40, longer, 64)
               > sum(flops_sambay.attention_call_flops(kind, k, cfg, T)
                     for k in ("window", "full", "cross"))
               for kind in ratios)
    assert 0.85 < ratios["fwd"] < 0.90 and 0.93 < ratios["bwd_fused"] <= 1.0
    # at the true shape the backward would be counted over what ran
    assert 3 * flops.attention_call_flops("bwd_fused", 1, 40, T, 64) > sum(
        flops_sambay.attention_call_flops("bwd_fused", k, cfg, T)
        for k in ("window", "full", "cross"))


# ---- the reducer and the readers ---------------------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/checkpoint/"
REMAT = BWD + "rematted_computation/"
KERNELS = load_json(CONFIG)["kernels"]["attn"]


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%fusion.1 = f", 0, 60, FWD + "ssm/in_proj/btd,de->bte/dot_general"],
        ["%fusion.2 = f", 60, 20, FWD + "ssm/conv/mul"],
        ["%fusion.3 = f", 80, 30, FWD + "ssm/x_proj/btc,ce->bte/dot_general"],
        ["%while.1 = while()", 110, 100, FWD + "ssm/scan/while"],
        ["%fusion.4 = f", 120, 40, FWD + "ssm/scan/while/body/exp"],
        ["%while.2 = while()", 210, 50, REMAT + "ssm/scan/while"],
        ["%fusion.5 = f", 260, 10, BWD + "ssm/gate/mul"],
        ["%fusion.6 = f", 270, 30, BWD + "ssm/out_proj/transpose(jvp(x))/dot"],
        ["%fusion.7 = f", 300, 10, FWD + "ssm_norm/mul"],
        ["%fusion.8 = f", 310, 25, FWD + "gmu/in_proj/btd,de->bte/dot_general"],
        ["%fusion.9 = f", 335, 5, FWD + "gmu/gate/mul"],
        ["%fusion.10 = f", 340, 20, BWD + "gmu/out_proj/dot"],
        ["%splash_mha_fwd_residuals.3 = custom-call()", 360, 4,
         FWD + "attention/window/pallas_call"],
        ["%splash_mha_dkv_no_residuals.3 = custom-call()", 364, 10,
         BWD + "attention/window/transpose(jvp(pallas_call))"],
        ["%splash_mha_fwd_residuals.4 = custom-call()", 374, 50,
         FWD + "attention/full/pallas_call"],
        ["%splash_mha_dkv_no_residuals.4 = custom-call()", 424, 110,
         BWD + "attention/full/transpose(jvp(pallas_call))"],
        ["%splash_mha_fwd_residuals.5 = custom-call()", 534, 50,
         FWD + "attention/cross/pallas_call"],
        ["%copy.1 = copy()", 584, 6, FWD + "attention/cross/pallas_call"],
        ["%fusion.11 = f", 590, 30, FWD + "attention/diff/sub"],
        ["%fusion.12 = f", 620, 10, BWD + "attention/diff/rsqrt"],
        ["%fusion.13 = f", 630, 70, FWD + "mlp/gate_up/dot_general"],
        ["%fusion.14 = f", 700, 100, STEP + "jvp(head)/dot"],
        ["%fusion.15 = f", 800, 100, FWD + "assm/scan/x"],   # not a scope
        ["%fusion.16 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_sambay_reduce_on_a_hand_made_trace():
    reduced = sambay_reduce.reduce_sambay(hand_made_trace(), KERNELS)
    want = {"ssm/in_proj": 60, "ssm/conv": 20, "ssm/x_proj": 30,
            "ssm/scan": 150, "ssm/gate": 10, "ssm/out_proj": 30,
            "gmu/in_proj": 25, "gmu/gate": 5, "gmu/out_proj": 20,
            "attention/window": 14, "attention/full": 160,
            "attention/cross": 56, "attention/diff": 40}
    assert set(reduced["sub_s"]) == set(want)
    for sub, t in want.items():
        assert abs(reduced["sub_s"][sub] - t * 1e-9) < 1e-15, sub
    kernels = reduced["kernel_s"]
    assert {k: {c: (round(s * 1e9), n) for c, (s, n) in v.items()}
            for k, v in kernels.items()} == {
        "window": {"fwd": (4, 1), "bwd_dkv": (10, 1)},
        "full": {"fwd": (50, 1), "bwd_dkv": (110, 1)},
        "cross": {"fwd": (50, 1)}}          # the copy is no kernel
    assert sambay_reduce.reduce_sambay({"planes": []}, KERNELS) is None
    # the accepted vocabulary books the same ops as it did: attention's
    # under `attention`, the mixer's and the GMU's under `layers`
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert abs(scopes["bucket_s"]["attention"] - 270e-9) < 1e-15
    assert "ssm" not in scopes["bucket_s"] and "gmu" not in scopes["bucket_s"]


@pytest.mark.parametrize("path,sub", [
    (FWD + "ssm/x_proj/btc,ce->bte/dot_general:", "ssm/x_proj"),
    (STEP + "transpose(jvp(ssm/scan))/mul", "ssm/scan"),
    (REMAT + "ssm/scan/while/body/checkpoint/mul", "ssm/scan"),
    (FWD + "attention/window/shard_map/pallas_call", "attention/window"),
    (FWD + "attention/diff/mul", "attention/diff"),
    (FWD + "gmu/gate/mul", "gmu/gate"),
    (FWD + "ssm_norm/mul", None), (FWD + "gmu_norm/mul", None),
    (FWD + "assm/scan/x", None), (FWD + "ssm/gate_norm/x", None),
    (FWD + "attention/splash", None),
])
def test_named_scope_of_a_path(path, sub):
    assert sambay_reduce.named(path) == sub


def _record(**over):
    cfg = load_json(CONFIG)
    job = load_module("jobs", "train_lm_sambay")
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": dict(
            job.static_calls(cfg, T, 1, True), peaks=PEAKS,
            model={k: v for k, v in cfg.items()
                   if isinstance(v, (int, float, bool))}),
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    for module in (scope_reduce, subscope_reduce, ssm_reduce, sambay_reduce):
        monkeypatch.setattr(module, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_09_30"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    cfg = load_json(CONFIG)
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["mamba1_share"] - 30.0) < 1e-9
    assert abs(values["mamba1_scan_share"] - 15.0) < 1e-9
    assert abs(values["gmu_share"] - 5.0) < 1e-9
    assert abs(values["diff_attn_combine_share"] - 4.0) < 1e-9
    least, _ = flops_sambay.scan_least_time_s(cfg, T, 1, True, PEAKS)
    assert values["mamba1_scan_roofline"] == pytest.approx(
        100 * least / 150e-9)
    # every call as what it computed: a backward with no dq event is fused
    want = 0.0
    for kind, calls in (("window", ("fwd", "bwd_fused")),
                        ("full", ("fwd", "bwd_fused")), ("cross", ("fwd",))):
        for call in calls:
            want += flops.least_time_s(
                flops_sambay.attention_call_flops(call, kind, cfg, T),
                flops_sambay.attention_call_bytes(call, cfg, T), PEAKS)[0]
    assert values["masked_attn_kernel_roofline"] == pytest.approx(
        100 * want / 224e-9)
    out = load_module("layer_metrics",
                      "masked_attn_kernel_roofline").roofline(_record())
    assert set(out["by_kind"]) == {"window.fwd", "window.bwd_fused",
                                   "full.fwd", "full.bwd_fused", "cross.fwd"}
    assert out["bound"]["full.fwd"] == "compute"
    assert out["by_kind"]["full.fwd"] == pytest.approx(
        100 * flops_sambay.attention_call_flops("fwd", "full", cfg, T)
        / 197e12 / 50e-9)
    # the accepted readers read the same trace as they did; Nemotron's
    # count the names they know (no `ssm/gate_norm` here)
    assert abs(load_module("layer_metrics", "mlp_share").read(
        _record()) - 7.0) < 1e-9
    assert abs(load_module("layer_metrics", "ssm_scan_share").read(
        _record()) - 15.0) < 1e-9


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no Mamba-1 mixer, no
    # GMU, no differential attention; its job gives no `attention_calls`
    # and Nemotron's `scan_call` is a Mamba-2's
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                for old, new in (("ssm/", "mlp/"), ("gmu/", "mlp/"),
                                 ("attention/window", "attention"),
                                 ("attention/full", "attention"),
                                 ("attention/cross", "attention"),
                                 ("attention/diff", "attention")):
                    e[3] = e[3].replace(old, new)
    _fresh(monkeypatch, bare)
    assert read(_record()) is None
    parents = _record()
    parents["static"] = {"peaks": PEAKS, "scan_call": {
        "model": {"mamba_num_heads": 32}, "tokens": 8192, "remat": True},
        "attention_call": {"batch": 1, "heads": 8, "seq": 8192,
                           "head_dim": 128}}
    _fresh(monkeypatch, hand_made_trace())
    if name.endswith("roofline"):
        assert read(parents) is None
        assert read(_record(static={"peaks": PEAKS})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, never by position: a later PR appends behind them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_16k_1seq", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    assert "18:14" in entry["why"] and "8:1" in entry["why"]
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert mine[name]["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    for name in ("mamba1_scan_roofline", "masked_attn_kernel_roofline"):
        assert (mine[name]["unit"], mine[name]["layer"],
                mine[name]["better"]) == ("%", "kernels", "higher")
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "ssm_share", "ssm_scan_roofline",
                 "mla_down_share", "collective_exposed_share"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_16k_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 16384)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 16384}
    assert (mix["warmup_steps"], mix["trace_steps"], mix["report_every"]) \
        == (2, 4, 1)
    assert mix["unigram"] == {"law": "zipf", "exponent": 1.1}


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"][0]


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    row = catalog_row()
    catalog = row["config"]
    assert held["source"] == row["source_url"]
    differ = sorted(k for k, v in catalog.items() if held.get(k, "") != v)
    assert differ == sorted(held["reduced"]) == ["num_hidden_layers",
                                                 "vocab_size"]
    for key, cut in held["reduced"].items():
        assert cut["published"] == catalog[key] and cut["here"] == held[key]
        assert not selfcheck.WIDTH_KEY.search(key), key


def test_the_configuration_files_cut_and_what_it_assumes():
    held = load_json(CONFIG)
    # every width as published: nothing cut, no head shared out
    assert (held["hidden_size"], held["intermediate_size"],
            held["num_attention_heads"], held["num_key_value_heads"],
            held["sliding_window"]) == (2560, 10240, 40, 20, 512)
    assert held["mamba"] == {"d_inner": 5120, "d_state": 16, "d_conv": 4,
                             "dt_rank": 160}
    # the cut: published layers 14-19, every kind across the boundary
    assert (held["layer_kinds"], held["first_layer_index"],
            held["num_hidden_layers"]) == ("mwsfgc", 14, 6)
    published = "mw" * 8 + "sf" + "gc" * 7
    assert published[14:20] == held["layer_kinds"] and len(published) == 32
    assert held["vocab_size"] * 8 == 200064
    assert held["share"] == {"vocab_parallel": 8, "vocab_rows": [0, 25008],
                             "layers": [14, 20]}
    why = held["reduced"]["num_hidden_layers"]["why"]
    for number in ("119,895,040", "98,322,304", "104,867,840", "91,766,144",
                   "633,068,672", "64,020,480", "697,094,272", "11.15 GB",
                   "18 : 14", "8 : 1"):
        assert number in why, number
    for key in ("differential_attention", "attention_bias",
                "self_decoder_ends", "mamba", "memory", "position", "norms",
                "initializer", "learning_rate"):
        assert key in held["assumed"], key
    assert "catalog" in held["assumed"]["differential_attention"]
    assert "pipeline stages" in held["stands_for"]
    assert "8 chips" in held["stands_for"]
    assert "PLACEHOLDER" not in held["tolerance"]["why"]
    assert held["train"]["optimizer"]["learning_rate"] == 3e-4
    job = load_module("jobs", "train_lm_sambay")
    cfg = job.transformer_config(held, held["train"], T)
    assert cfg.num_params == flops_sambay.total_params(held) == 697_094_272
    assert 16 * cfg.num_params == 11_153_508_352
    assert (cfg.head_dim, cfg.kv_heads, cfg.ssm_d_inner, cfg.ssm_state,
            cfg.ssm_dt_rank, cfg.attn_window, cfg.ff_dim,
            cfg.layer_index_offset) == (64, 20, 5120, 16, 160, 512, 10240,
                                        14)
    assert not cfg.rope and cfg.tie_embeddings and cfg.diff_attention
    assert cfg.pattern_runs == [("mwsfgc", 1)]


@pytest.mark.parametrize("key,value,why", [
    ("hidden_act", "gelu", "silu"),
    ("mlp_bias", True, "bias in the MLP"),
    ("lm_head_bias", True, "bias on the head"),
    ("tie_word_embeddings", False, "untied"),
    ("resid_pdrop", 0.1, "dropout"),
    ("mb_per_layer", 4, "spacing"),
    ("layer_kinds", "mwsf*c", "m, s, w, f, g and c"),
    ("layer_kinds", "mwsfgcgc", "every layer held"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_sambay")
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], T)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", "train_lm_sambay")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS
        or f.name == "layer_pattern"])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="ssm_d_inner"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_sambay")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    key = jax.random.key(4300000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    changed = {"ssm_norm", "attn_norm", "gmu_norm", "mlp_norm", "subln",
               "conv_b", "wq", "bq", "bkv", "bo", "final_norm"}
    changed |= {n + "_bias" for n in changed}
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: str(getattr(path[-1], "key", "")) in changed
        or bool(np.array_equal(a, b)), mine, theirs)
    assert all(jax.tree.leaves(same)), same
    assert abs(float(mine["final_norm_bias"].std()) - 0.3) < 0.1
    for a, b in zip(sum(mine["runs"], []), sum(theirs["runs"], [])):
        for name in a:
            if name.endswith("norm_bias") or name in ("bq", "bkv", "bo"):
                assert np.asarray(a[name]).std() > 0.2, name
                assert not np.asarray(b[name]).any(), name
        if "wq" in a:
            np.testing.assert_allclose(a["wq"], 3.0 * b["wq"])
            gain = np.asarray(a["subln"])
            assert abs(gain.mean() - 1) < 0.15 and gain.std() > 0.2
            assert np.asarray(a["lambda_init"]).tobytes() == np.asarray(
                b["lambda_init"]).tobytes()
        if "A_log" in a:
            # A_{c,n} = n + 1, dt in [0.001, 0.1]: the published ranges
            np.testing.assert_allclose(
                np.exp(np.asarray(b["A_log"]))[0, 0], np.arange(1, 9),
                rtol=1e-6)
            dt = np.log1p(np.exp(np.asarray(b["dt_bias"])))
            assert 1e-3 <= dt.min() * 1.001 and dt.max() <= 0.1001
            assert (np.asarray(b["D"]) == 1).all()


def test_the_reference_layout_is_in_the_layers_order():
    import jax

    job = load_module("jobs", "train_lm_sambay")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert cfg.pattern_runs == [("mwsf", 1), ("gc", 2)]
    params = job.init_params(jax.random.key(1), cfg, model["init"])
    w = job.to_reference_layout(params, cfg)
    kinds = ["m" if "x_proj" in lw else "g" if "in_proj" in lw else
             "c" if "Wq" in lw else "a" for lw in w["layers"]]
    assert "".join(kinds) == "mamagcgc"
    d = model["hidden_size"]
    assert w["layers"][1]["Wqkv"].shape == (2 * d, d)
    assert w["layers"][5]["Wq"].shape == (d, d)
    assert w["layers"][0]["gate_up_proj"].shape == (
        2 * model["intermediate_size"], d)


FAULT_MODEL = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": 16, "vocab_size": 128,
    "num_hidden_layers": 6, "layer_kinds": "mwsfgc", "first_layer_index": 14,
    "layer_norm_eps": 1e-5, "max_position_embeddings": 512,
    "tie_word_embeddings": True, "job": "train_lm_sambay",
    "mamba": {"d_inner": 48, "d_state": 4, "d_conv": 4, "dt_rank": 4},
    "init": {"q_gain": 3.0, "norm_gain_std": 0.3, "bias_std": 0.3},
    "train": {"compute_dtype": "float32", "param_dtype": "float32",
              "attention_impl": "dense", "remat": False, "loss_chunk": 0,
              "scan_unroll": 1, "scan_chunk": 32},
    "tolerance": {"logits_rel_l2": 0.02, "loss_abs": 0.004},
}
FAULT_MIX = {"kind": "token_batches", "sequences_per_step": 2,
             "tokens_per_sequence": 96,
             "unigram": {"law": "zipf", "exponent": 1.1},
             "reference_sample": {"sequences": 2, "tokens": 96}}


@pytest.fixture(scope="module")
def fault_rows():
    faults = load_module("reference", "phi4flash_faults")
    return faults, {r["variant"]: r
                    for r in faults.read(FAULT_MODEL, FAULT_MIX, 7)}


FAULT_NAMES = ("window_ignored", "no_lambda_term", "no_sub_norm",
               "lambda_init_at_cut", "memory_after_gate", "kv_recomputed",
               "no_dt_bias", "no_layernorm_bias", "float8_e4m3fn",
               "float8_e5m2")


@pytest.mark.parametrize("name", FAULT_NAMES)
def test_each_fault_fails_a_limit_at_a_small_size(fault_rows, name):
    """Every fault of `reference/phi4flash_faults.py`, and float8 operands,
    reads over the logits' limit at a small size (float32 here: each is
    far over rounding); `correct` says so."""
    faults, rows = fault_rows
    assert set(FAULT_NAMES) | {"bfloat16", "bf16_scan_state"} == set(
        faults.FAULTS + faults.PRECISIONS) == set(rows)
    assert rows[name]["rel_l2"] > FAULT_MODEL["tolerance"]["logits_rel_l2"]
    assert rows[name]["correct"] is False


def test_a_bfloat16_scan_state_is_seen_and_not_told_from_bf16_operands(
        fault_rows):
    """The one variant no limit of the cell refuses (the configuration's
    `tolerance.why` has the chip's readings, on both sides of the system's
    own): a state's rounding is bf16 operands' rounding summed over the
    steps it remembers. It moves the logits, by less than bf16 operands
    do at this size; `tests/test_phi4flash_reference.py` refuses it in
    float32."""
    _, rows = fault_rows
    assert 1e-4 < rows["bf16_scan_state"]["rel_l2"] < rows["bfloat16"][
        "rel_l2"]
    assert rows["bf16_scan_state"]["correct"] is True


def test_bf16_operands_pass_and_the_reference_stays_plain(fault_rows):
    import inspect

    faults, rows = fault_rows
    assert rows["bfloat16"]["correct"] is True
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "phi4flash_f32")
    assert plain.STATE_DTYPE == "float32"
    for name in ("linear", "layer_norm", "sub_norm", "combine", "window_of",
                 "memory_of", "cross_kv", "lambda_init"):
        assert getattr(plain, name).__module__ == plain.__name__, name
    assert not [p for p in inspect.signature(plain.linear).parameters
                if "dtype" in p]
    assert "ray_tpu" not in inspect.getsource(plain).replace(
        "importing nothing from `ray_tpu`", "")


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended; the rehearsal
    files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-phi4flash", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-phi4flash.json",
        "reduced": [], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_phi4flash", "config": "tiny-phi4flash",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_phi4flash")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_phi4flash"]))
    path = tmp_path_factory.mktemp("phi4flash_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_sambay_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_phi4flash", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True and line["failed"] == 0
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        assert "rehearsal_step_ms" in line["metrics"]
        for name in NEW_METRICS + ["model_flops_util"]:
            assert "rehearsal_" + name not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
