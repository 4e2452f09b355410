"""What PR 53 adds to the benchmark for `train_sdar30b_ep8_d4`, checked
without a chip: `benchlib/flops_blockdiff_moe.py` against hand-worked
numbers at the published widths, `benchlib/blockdiff_reduce.py` and the
five new readers on a hand-made trace (and on a program or a run that
gives them nothing to read), the spec's new entries BY NAME, never by
position, the configuration file against the catalog row key by key, what
the job refuses, the stand-in weights, the fault reader, and the job kind
`train_lm_blockdiff_moe` rehearsed at a tiny size on the CPU (a
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

from benchlib import (blockdiff_reduce, flops,  # noqa: E402
                      flops_blockdiff_moe, scope_reduce, subscope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_sdar", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_sdar30b_ep8_d4"
NAME = "sdar-30b-a3b-chat-ep8-d4"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-sdar.json")
NEW_METRICS = ["blockdiff_attn_kernel_roofline",
               "blockdiff_pairs_computed_over_needed",
               "diffusion_stream_share", "softmax_held_moe_share",
               "softmax_held_slots_share"]
TRACE_READERS = [NEW_METRICS[0], NEW_METRICS[2], NEW_METRICS[3]]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEQ = 8192
KERNELS = {"fwd": "^%?splash_m[hq]a_fwd(_segmented)?(_no)?_residuals",
           "bwd_dkv": "^%?splash_m[hq]a_dkv(_segmented)?_no_residuals",
           "bwd_dq": "^%?splash_m[hq]a_dq(_segmented)?_no_residuals"}


# ---- arithmetic --------------------------------------------------------


def test_flops_blockdiff_moe_hand_worked():
    """The issue's own arithmetic, from the configuration file."""
    model = load_json(CONFIG)
    f = flops_blockdiff_moe
    assert f.router_experts(model) == 128
    assert f.qkv_params(model) == (2 * 8_388_608, 2 * 1_048_576)
    assert f.expert_params(model) == 4_718_592
    assert f.router_params(model) == 262_144
    assert f.layer_params(model) == 94_638_336
    assert f.total_params(model) == 456_346_624
    # whole: 48 layers of 128 experts, the whole vocabulary
    whole = dict(model, num_hidden_layers=48, num_experts=128,
                 vocab_size=151936)
    assert f.layer_params(whole) == 623_120_640
    assert round(f.total_params(whole) / 1e9, 2) == 30.53
    # the mask: L^2 + L*B pairs of 4 L^2, half of them (and L*B/2 more)
    # under a noised query
    assert f.mask_pairs(SEQ, 4) == SEQ * SEQ + SEQ * 4 == 67_141_632
    assert f.read_pairs(SEQ, 4) == SEQ * 4 + (SEQ * SEQ - SEQ * 4) / 2
    assert f.mask_pairs(SEQ, 4) - f.read_pairs(SEQ, 4) \
        == (SEQ * SEQ + SEQ * 4) / 2
    # a layer's forward kernel work: 4 x pairs x 32 x 128 = 1.10 TFLOP
    assert f.attention_flops(f.mask_pairs(SEQ, 4), model, 2) \
        == 4 * 67_141_632 * 32 * 128
    assert round(f.attention_call_flops("fwd", model, SEQ) / 1e12, 2) == 1.1
    assert f.attention_call_flops("bwd_fused", model, SEQ) \
        == 2.5 * f.attention_call_flops("fwd", model, SEQ)
    # the step, at an even share of the slots (16,384 a layer): what
    # nothing reads is left out of the last layer
    slots = [16384.0] * 4
    full = 2.0 * (2 * SEQ * (sum(f.qkv_params(model)) + 262_144)
                  + 16384 * 4_718_592) + 4 * 67_141_632 * 32 * 128
    last = 2.0 * (SEQ * (2 * 8_388_608 + 262_144) + 2 * SEQ * 2 * 1_048_576
                  + 8192 * 4_718_592) \
        + 4 * f.read_pairs(SEQ, 4) * 32 * 128
    head = 2.0 * SEQ * 2048 * 18992
    assert f.forward_flops_per_sequence(model, SEQ, slots) \
        == 3 * full + last + head
    assert last < 0.55 * full
    per_token = f.train_flops_per_token(model, SEQ, slots)
    assert per_token == 3 * (3 * full + last + head) / SEQ
    assert 2.5e9 < per_token < 2.8e9
    with pytest.raises(ValueError, match="layers"):
        f.forward_flops_per_sequence(model, SEQ, slots[:3])
    # the one causal shape that is not above the mask's count
    call = f.attention_call_not_above(model, SEQ)
    assert call == {"batch": 1, "heads": 32, "kv_heads": 4, "seq": 11520,
                    "head_dim": 128}
    assert 11520 ** 2 / 2 <= f.mask_pairs(SEQ, 4) < (11520 + 128) ** 2 / 2
    for kind in ("fwd", "bwd_dkv", "bwd_dq", "bwd_fused"):
        assert flops.attention_call_flops(kind, 1, 32, 11520, 128) \
            <= f.attention_call_flops(kind, model, SEQ)
    # every kind is compute-bound at the cell's shape
    for kind in ("fwd", "bwd_fused"):
        t, bound = f.attention_least_time_s(kind, model, SEQ, PEAKS)
        assert bound == "compute"
        assert t == f.attention_call_flops(kind, model, SEQ) / 197e12


# ---- the trace ------------------------------------------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%fusion.1 = f", 0, 10, STEP + "diffusion/noise/threefry2x32"],
        ["%fusion.2 = f", 10, 5, STEP + "diffusion/noise/select_n"],
        ["%fusion.3 = f", 15, 5, STEP + "diffusion/stream/concatenate"],
        ["%fusion.4 = f", 20, 10, STEP + "jvp(embed)/gather"],
        ["%while.1 = while()", 30, 700, STEP + "jvp(layers)/while"],
        ["%fusion.5 = f", 30, 40, FWD + "qkv/btd,dhk->bthk/dot_general"],
        ["%fusion.6 = f", 70, 10, FWD + "qkv/qk_norm/rsqrt"],
        ["%splash_mha_fwd_residuals.1 = custom-call()", 80, 200,
         FWD + "attention/block_diffusion/vmap(splash)"],
        ["%fusion.7 = f", 280, 20, FWD + "attention/block_diffusion/mul"],
        ["%fusion.8 = f", 300, 30, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 330, 20, FWD + "moe/dispatch/sort"],
        ["%gmm.1 = custom-call()", 350, 60, FWD + "moe/experts/gmm"],
        ["%gather.1 = g", 410, 20, FWD + "moe/combine/gather"],
        ["%splash_mha_dkv_no_residuals.2 = custom-call()", 430, 300,
         BWD + "attention/block_diffusion/transpose(vmap(splash))"],
        ["%fusion.9 = f", 730, 10,
         STEP + "transpose(jvp(diffusion/stream))/pad"],
        ["%fusion.10 = f", 740, 100, STEP + "jvp(head)/dot"],
        ["%fusion.11 = f", 840, 60, STEP + "adiffusion/noise/x"],  # no scope
        ["%fusion.12 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_blockdiff_reduce_on_a_hand_made_trace():
    reduced = blockdiff_reduce.reduce_blockdiff(hand_made_trace(), KERNELS)
    want = {"diffusion/noise": 15, "diffusion/stream": 15,
            "attention/block_diffusion": 520}
    assert set(reduced["sub_s"]) == set(want)
    for scope, t in want.items():
        assert abs(reduced["sub_s"][scope] - t * 1e-9) < 1e-15, scope
    kernels = reduced["kernel_s"]
    assert set(kernels) == {"fwd", "bwd_dkv"}
    assert kernels["fwd"][1] == kernels["bwd_dkv"][1] == 1
    assert abs(kernels["fwd"][0] - 200e-9) < 1e-15
    assert abs(kernels["bwd_dkv"][0] - 300e-9) < 1e-15
    # the kernel's call is under the vocabulary's `attention`, the
    # QK-norm under `qkv`; `diffusion` is no bucket of scope_reduce's
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert abs(scopes["bucket_s"]["attention"] - 520e-9) < 1e-15
    assert abs(scopes["bucket_s"]["qkv"] - 50e-9) < 1e-15
    assert "diffusion" not in scopes["bucket_s"]
    assert blockdiff_reduce.reduce_blockdiff({"planes": []}, KERNELS) is None


@pytest.mark.parametrize("path,scope", [
    (STEP + "diffusion/noise/threefry2x32", "diffusion/noise"),
    (STEP + "transpose(jvp(diffusion/stream))/pad", "diffusion/stream"),
    (FWD + "attention/block_diffusion/vmap(splash)",
     "attention/block_diffusion"),
    (FWD + "attention/mul", None),
    (FWD + "attention/window/x", None),
    (STEP + "adiffusion/noise/x", None),
    (STEP + "diffusion/other/x", None),
])
def test_named_scope_of_a_path(path, scope):
    assert blockdiff_reduce.named(path) == scope


def _record(**over):
    model = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {
            "peaks": PEAKS, "attention_kernels": KERNELS,
            "mask_blocks": {"blocks": 256, "non_empty": 80, "partial": 24,
                            "block_pairs": 1048576,
                            "pairs_needed": 67141632},
            "blockdiff_call": {"model": {k: model[k] for k in (
                "hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "block_length")},
                "seq": SEQ, "batch": 1}},
        "counters": {"held_slots_share": [12.1, 12.9, 12.5, 14.0]},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    monkeypatch.setattr(blockdiff_reduce, "_REDUCED", {})


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
    assert abs(values["diffusion_stream_share"] - 3.0) < 1e-9
    assert abs(values["softmax_held_moe_share"] - 13.0) < 1e-9
    assert values["softmax_held_slots_share"] == 12.7        # the median
    assert values["blockdiff_pairs_computed_over_needed"] == pytest.approx(
        80 * 1048576 / 67141632)
    assert 1.24 < values["blockdiff_pairs_computed_over_needed"] < 1.25
    # the kernel: a forward and a backward that made dQ too (no dq event)
    model = _record()["static"]["blockdiff_call"]["model"]
    least = sum(flops_blockdiff_moe.attention_least_time_s(
        kind, model, SEQ, PEAKS)[0] for kind in ("fwd", "bwd_fused"))
    assert values["blockdiff_attn_kernel_roofline"] == pytest.approx(
        100 * least / 500e-9)
    out = load_module("layer_metrics",
                      "blockdiff_attn_kernel_roofline").roofline(_record())
    assert set(out["by_kind"]) == {"fwd", "bwd_fused"}
    assert set(out["bound"].values()) == {"compute"}
    # the accepted readers read the same trace as they did
    assert abs(load_module("layer_metrics", "attn_proj_share").read(
        _record()) - 5.0) < 1e-9
    assert load_module("layer_metrics", "mlp_share").read(_record()) == 0.0
    assert abs(load_module("layer_metrics", "head_share").read(
        _record()) - 10.0) < 1e-9


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no diffusion scope,
    # the kernels under plain `attention` (the expert cells' steps keep
    # `moe/*`, so `softmax_held_moe_share` would read there; it lists
    # this cell alone)
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("diffusion/", "loss/").replace(
                    "attention/block_diffusion", "attention")
    _fresh(monkeypatch, bare)
    if name == "softmax_held_moe_share":
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
    if name == "softmax_held_moe_share":
        assert read(_record()) is None
    # a record without the job's call
    _fresh(monkeypatch, hand_made_trace())
    if name == "blockdiff_attn_kernel_roofline":
        assert read(_record(static={"peaks": PEAKS})) is None
        assert read(_record(static={})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


def test_counter_readers_with_nothing_to_read():
    read = load_module("layer_metrics", "softmax_held_slots_share").read
    assert read({}) is None
    assert read({"counters": {}}) is None
    assert read({"counters": {"held_slots_share": []}}) is None
    read = load_module("layer_metrics",
                       "blockdiff_pairs_computed_over_needed").read
    assert read({}) is None
    assert read({"static": {}}) is None
    assert read({"static": {"mask_blocks": None}}) is None     # off a TPU


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, never by position: a later PR appends behind them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_1seq", 1)
    assert len(cell["why"]) <= 200
    assert "data tokens" in cell["why"] and "16384 positions" in cell["why"]
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # the accepted entries keep their order in front of it
    names = [w["name"] for w in spec["workloads"]]
    accepted = ["train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                "train_olmoe_d1", "train_glm47flash_ep8_d5",
                "train_nemotron3super_ep64_d11", "train_phi4miniflash_d6",
                "train_ling3flash_ep64_d7"]
    assert names[:7] == accepted and names.index(CELL) >= 7
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    per_layer = [m["name"] for m in spec["per_layer"]]
    at = per_layer.index(NEW_METRICS[0])
    assert per_layer[at:at + 5] == NEW_METRICS
    assert at > per_layer.index("group_moe_held_slots_share")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    roofline = mine["blockdiff_attn_kernel_roofline"]
    assert (roofline["unit"], roofline["layer"], roofline["better"],
            roofline["source"]) == ("%", "kernels", "higher", "device_trace")
    assert mine["blockdiff_pairs_computed_over_needed"]["better"] == "lower"
    assert mine["softmax_held_slots_share"]["source"] == "program_counter"
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "moe_experts_roofline", "moe_held_share",
                 "held_slots_share", "mla_down_share", "ssm_share",
                 "collective_exposed_share", "masked_attn_kernel_roofline",
                 "kda_share", "group_moe_held_slots_share"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the traffic file is Nemotron-3-Super's, unedited
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, SEQ)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": SEQ}
    assert (mix["warmup_steps"], mix["trace_steps"]) == (2, 4)
    assert by_name(spec["workloads"], "train_nemotron3super_ep64_d11",
                   "workload")["traffic"] == cell["traffic"]


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"][0]


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
        assert cut["published"] == catalog[key]
        assert not selfcheck.WIDTH_KEY.search(key), key
    # every width as published
    assert (held["hidden_size"], held["intermediate_size"],
            held["moe_intermediate_size"], held["head_dim"],
            held["num_attention_heads"], held["num_key_value_heads"],
            held["num_experts_per_tok"], held["rope_theta"],
            held["rms_norm_eps"]) == \
        (2048, 6144, 768, 128, 32, 4, 8, 1000000, 1e-06)
    # the floors and the shares: 16 experts of 128 on 8 chips, an eighth
    # of the vocabulary, four layers of one kind
    assert held["num_experts"] * 8 == catalog["num_experts"]
    assert held["vocab_size"] * 8 == catalog["vocab_size"]
    assert held["num_hidden_layers"] == 4
    share = held["share"]
    assert (share["chips_per_layer"], share["router_experts"],
            share["expert_offset"], share["vocab_rows"]) == \
        (8, 128, 0, [0, 18992])
    assert share["held_slots_share_band_layers"] == [1, 2, 3]
    assert "44 layers" in held["stands_for"]
    assert "never the 16,384 positions" in held["stands_for"]
    for key in ("block_length", "noise_schedule", "no_shift",
                "mask_token_id", "qk_norm", "aux_loss", "initializer",
                "learning_rate"):
        assert key in held["assumed"], key
    assert (held["block_length"], held["diffusion_t_min"],
            held["mask_token_id"]) == (4, 0.001, held["vocab_size"] - 1)
    assert "TO BE SET" not in held["tolerance"]["why"]
    job = load_module("jobs", "train_lm_blockdiff_moe")
    cfg = job.transformer_config(held, held["train"], SEQ)
    assert cfg.num_params == flops_blockdiff_moe.total_params(held) \
        == 456_346_624
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_expert_offset,
            cfg.moe_top_k, cfg.moe_scoring, cfg.moe_aux_coeff,
            cfg.moe_norm_topk) == (128, 16, 0, 8, "softmax", 0.0, True)
    assert (cfg.head_dim, cfg.n_heads, cfg.kv_heads, cfg.ff_dim,
            cfg.max_seq_len, cfg.block_length, cfg.mask_token) == \
        (128, 32, 4, 768, 2 * SEQ, 4, 18991)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.rope
    assert cfg.rope_theta == 1e6 and not cfg.layer_pattern


@pytest.mark.parametrize("key,value,why", [
    ("use_sliding_window", True, "window"),
    ("mlp_only_layers", [1], "dense MLP layer"),
    ("decoder_sparse_step", 2, "sparse step"),
    ("rope_scaling", {"type": "yarn"}, "scaled RoPE"),
    ("hidden_act", "gelu", "silu"),
    ("attention_bias", True, "bias"),
    ("router_aux_loss_coef", 0.001, "aux loss"),
    ("block_length", 0, "block_length"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_blockdiff_moe")
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], SEQ)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", "train_lm_blockdiff_moe")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig)
        if f.name not in ("block_length", "mask_token_id",
                          "diffusion_t_min", "qk_norm_per_head")])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="block_length"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


# ---- the stand-in weights ----------------------------------------------------


def tiny_setup(seed=3):
    import jax

    from benchlib.traffic import TokenBatches
    job = load_module("jobs", "train_lm_blockdiff_moe")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    batches = TokenBatches(mix, model["vocab_size"] - 1, seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = job.init_params(jax.random.key(seed), cfg, model["init"])
    return job, model, batches, cfg, params


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job, model, _, cfg, params = tiny_setup()
    plain = Transformer.init(jax.random.key(3), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    same = {jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree.leaves(plain))
        if a.shape == b.shape and np.array_equal(a, b)}
    assert same == {"['layers']['w_moe_down']", "['layers']['wo']"}
    lay = params["layers"]
    # the anchor column: the same value in every row, the mask's too; the
    # router and the head do not read it
    assert (np.asarray(params["embed"][:, 0]) == model["init"]["anchor"]).all()
    assert not np.asarray(lay["w_router"][:, 0]).any()
    assert not np.asarray(params["lm_head"][0]).any()
    assert abs(float(params["embed"][:, 1:].std()) - 1.0) < 0.05
    # gains off 1, the query's around q_gain
    for name in ("attn_norm", "mlp_norm", "k_norm"):
        assert 0.15 < float(lay[name].std()) < 0.45, name
    assert abs(float(lay["q_norm"].mean()) - model["init"]["q_gain"]) < 0.4
    # the positional head reads the anchor alone; the values and the
    # experts are the program's but for the anchor's row, which they do
    # not read
    group = cfg.n_heads // cfg.kv_heads
    last = np.arange(group - 1, cfg.n_heads, group)
    wq = np.asarray(lay["wq"])
    assert not wq[:, 1:, last].any() and wq[:, 0, last].any()
    np.testing.assert_array_equal(lay["wkv"][:, 1:, 1], plain["layers"][
        "wkv"][:, 1:, 1])
    np.testing.assert_array_equal(
        lay["w_moe_gateup"][:, :, 1:],
        plain["layers"]["w_moe_gateup"][:, :, 1:])
    assert not np.asarray(lay["wkv"][:, 0, 1]).any()
    assert not np.asarray(lay["w_moe_gateup"][:, :, 0]).any()
    # the mask id is nowhere in the traffic
    assert cfg.mask_token == model["vocab_size"] - 1


def test_the_held_columns_are_scaled_until_the_share_is_even():
    import numpy as np
    job, model, batches, cfg, params = tiny_setup()
    balanced, what = job.balance_held_share(params, cfg, None, batches,
                                            model["init"])
    target = what["target_slots"]
    assert target == 2 * batches.tokens_per_step * cfg.moe_top_k \
        * cfg.held_experts / cfg.moe_experts
    before = np.abs(np.asarray(what["held_slots_before"]) - target)
    after = np.abs(np.asarray(what["held_slots_after"]) - target)
    assert (after <= before).all() and (after <= 0.03 * target).all()
    first, held = cfg.moe_expert_offset, cfg.held_experts
    was, now = (np.asarray(p["layers"]["w_router"])
                for p in (params, balanced))
    factor = 2.0 ** np.asarray(what["log2_factor"])
    np.testing.assert_allclose(
        now[:, :, first:first + held],
        was[:, :, first:first + held] * factor[:, None, None], rtol=1e-6)
    others = np.ones(cfg.moe_experts, bool)
    others[first:first + held] = False
    np.testing.assert_array_equal(now[:, :, others], was[:, :, others])
    # nothing to do without rounds
    assert job.balance_held_share(params, cfg, None, batches, dict(
        model["init"], balance_rounds=0)) == (params, None)


def test_noise_keys_are_a_function_of_seed_stream_and_index():
    import numpy as np
    job = load_module("jobs", "train_lm_blockdiff_moe")
    a = job.noise_keys(3300000101, 1, 7, 2)
    assert a.dtype == np.uint32 and a.shape == (2, 2)
    np.testing.assert_array_equal(a, job.noise_keys(3300000101, 1, 7, 2))
    seen = {job.noise_keys(seed, stream, index, 1).tobytes()
            for seed in (0, 1, 3300000101) for stream in (1, 2, 3)
            for index in (0, 1, 2)}
    assert len(seen) == 27
    assert a[0, 1] != a[1, 1]       # a key a sequence


def test_the_reference_layout_holds_the_share_by_its_ids():
    import numpy as np
    job, model, _, cfg, params = tiny_setup()
    w = job.to_reference_layout(params, cfg)
    assert len(w["layers"]) == cfg.n_layers
    first = cfg.moe_expert_offset
    for i, lw in enumerate(w["layers"]):
        assert sorted(lw["experts"]) == list(
            range(first, first + cfg.held_experts))
        assert lw["mlp.gate"].shape == (cfg.moe_experts, cfg.d_model)
        assert lw["q_norm"].shape == lw["k_norm"].shape == (cfg.head_dim,)
        np.testing.assert_array_equal(
            lw["experts"][first]["down_proj"],
            np.asarray(params["layers"]["w_moe_down"][i][0]).T)
    assert w["lm_head"].shape == (cfg.vocab_size, cfg.d_model)


def test_fault_reader_leaves_the_reference_plain():
    faults = load_module("reference", "sdar_faults")
    plain = load_module("reference", "sdar_f32")
    model = load_json(TINY)
    assert set(faults.FAULTS) == set(
        faults.MASK_FAULTS + faults.LOSS_FAULTS + faults.LAYER_FAULTS)
    assert len(faults.FAULTS) == 10
    for name in faults.FAULTS + faults.PRECISIONS + (None,):
        ref, cfg = faults.variant(name, model)
        assert ref is not plain
        assert (cfg is model) == (name != "topk_not_normalised")
    for name in ("linear", "qk_norm", "position_ids",
                 "block_diffusion_mask", "rms_norm"):
        assert getattr(plain, name).__module__ == plain.__name__
    with pytest.raises(KeyError):
        faults.variant("no_such_fault", model)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = list(faults.read(model, mix, 3, names=(
        "own_clean_copy_visible", "no_inverse_t", "bfloat16")))
    assert [r["variant"] for r in rows] == [
        "own_clean_copy_visible", "no_inverse_t", "bfloat16"]
    assert rows[0]["rel_l2"] > rows[2]["rel_l2"] > 0
    assert rows[1]["rel_l2"] == 0.0 and rows[1]["loss_diff"] > 0.5
    assert not rows[1]["correct"]


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-sdar", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-sdar.json",
        "reduced": ["num_experts"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_sdar", "config": "tiny-sdar",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_sdar")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_sdar"]))
    path = tmp_path_factory.mktemp("sdar_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_blockdiff_moe_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_sdar", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        share = line["metrics"]["rehearsal_softmax_held_slots_share"]
        assert 10.0 <= share["value"] <= 50.0 and share["unit"] == "%"
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_blockdiff_attn_kernel_roofline" \
            not in line["metrics"]
        # off a TPU the flash kernel is not on the path: no block table
        assert "rehearsal_blockdiff_pairs_computed_over_needed" \
            not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
