"""What PR 66 adds to the benchmark for `train_xing4_ep8_d5`, checked
without a chip: `benchlib/flops_mhc_moe.py` and `mhc_bytes` against
hand-worked numbers at the published widths, `benchlib/mhc_reduce.py` and
the five new readers on a hand-made trace (and on a program or a run that
gives them nothing to read), the spec's new entries BY NAME, never by
position, the configuration file against the catalog row key by key and
its arithmetic, what the job refuses, the stand-in weights, the fault
reader, and the job kind `train_lm_mhc_moe` rehearsed at a tiny size on
the CPU (a rehearsal's numbers carry the `rehearsal_` prefix and are never
a device metric)."""

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

from benchlib import (flops_mhc_moe, flops_mla_moe, kda_reduce,  # noqa: E402
                      mhc_reduce, scope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_xing4", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_xing4_ep8_d5"
NAME = "xing4.0-29b-a4b-ep8-tp4-d5"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-xing4.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TRACE_METRICS = ["mhc_share", "mhc_maps_share", "mhc_mix_share",
                 "mhc_stream_roofline"]
NEW_METRICS = TRACE_METRICS + ["mhc_moe_held_slots_share"]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size",
           "num_nextn_predict_layers"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_mhc_moe_hand_worked():
    f, held = flops_mhc_moe, load_json(CONFIG)
    # latent attention with heads 0-7 of 32: q down 3,584 x 768 and up
    # 768 x 8 x 192, kv down 3,584 x 576 and up 512 x 8 x 256, o 8 x 128 x
    # 3,584; the two latent norms
    assert flops_mla_moe.attention_params(held) == 2_752_512 + 1_179_648 \
        + 2_064_384 + 1_048_576 + 3_670_016 == 10_715_136
    assert flops_mla_moe.latent_norm_params(held) == 768 + 512
    # a sublayer's maps: phi 14,336 x 24, b 24, three alphas
    assert (f.streams(held), f.maps_a_token(held)) == (4, 24)
    assert f.maps_matmul_params(held) == 14_336 * 24 == 344_064
    assert f.hc_params(held) == 344_064 + 24 + 3 == 344_091
    dense = flops_mla_moe.dense_layer_params(held) + 2 * f.hc_params(held)
    expert = flops_mla_moe.expert_layer_params(held) + 2 * f.hc_params(held)
    assert dense == 10_716_416 + 7_168 + 688_182 + 99_090_432 \
        == 110_502_198
    assert expert == 10_716_416 + 7_168 + 688_182 + 11_010_048 + 229_376 \
        + 88_080_384 == 110_731_574
    assert f.total_params(held) == dense + 4 * expert + 117_440_512 + 3_584 \
        == 670_872_590
    assert f.total_params(held) * 16 / 1e9 == pytest.approx(10.73, abs=5e-3)
    # at the published sizes, without the multi-token prediction module
    assert f.published_params(held) == 2 * 128_196_918 + 38 * 744_988_982 \
        + 939_524_096 + 3_584 == 29_505_502_832 == held["published_params"]
    # with all 32 heads: 12.15 GB, what the heads' cut is for
    assert f.total_params(dict(held, num_attention_heads=32,
                               num_key_value_heads=32)) == 759_346_190
    # a token's matmul parameters at 2 routed slots a token (4 layers x 4
    # slots x 8 / 64): attention 5 x, the dense MLP, the router and the
    # shared expert 4 x, two held slots, the head, ten maps' products
    assert f.matmul_params_per_token(held, 2.0) == 5 * 10_715_136 \
        + 99_090_432 + 4 * (229_376 + 11_010_048) + 2 * 11_010_048 \
        + 3_584 * 16_384 + 10 * 344_064 == 281_804_800
    # attention over the causal pairs, QK^T at 192 and PV at 128: forward
    # and twice that backward, 8 heads x 8,192 keys x 2 / 2 a token and
    # layer
    assert flops_mla_moe.attention_train_flops_per_token(held, 8192) == \
        5 * 3 * 8 * 8192 * (192 + 128) == 314_572_800
    assert f.train_flops_per_token(held, 8192, 2.0) == \
        6 * 281_804_800 + 314_572_800 == 2_005_401_600
    # the maps' product: 688,128 FLOPs a token and sublayer forward
    assert 2 * f.maps_matmul_params(held) == 688_128
    assert f.attention_call(held, 1, 8192) == {
        "batch": 1, "heads": 8, "kv_heads": 8, "seq": 8192, "head_dim": 192}
    assert f.router_experts(held) == 64


def test_mhc_bytes_hand_worked():
    f = flops_mhc_moe
    # a token and sublayer in bf16: forward reads 4 x 3,584 and writes it,
    # writes h and reads y: 10 x 3,584 values; again under remat; backward
    # the stream's gradient in and out and the stream: 12 x 3,584
    assert f.mhc_bytes(1, 4, 3584, 1, "bfloat16") == (10 + 10 + 12) \
        * 3584 * 2 == 229_376
    assert f.mhc_bytes(1, 4, 3584, 1, "bfloat16", remat=False) == \
        22 * 3584 * 2
    assert f.mhc_bytes(1, 4, 3584, 1, "float32") == 2 * 229_376
    assert f.mhc_bytes(1, 1, 3584, 1, "bfloat16") == (4 + 4 + 3) * 3584 * 2
    call = f.mhc_call(load_json(CONFIG), 8192, "bfloat16", True)
    assert call == {"streams": 4, "width": 3584, "sublayers": 10,
                    "tokens": 8192, "rounds": 20, "dtype": "bfloat16",
                    "remat": True, "bytes_a_step": 18_790_481_920}
    # 22.9 ms a step at 819 GB/s
    assert f.mhc_least_time_s(call, 1, PEAKS) == pytest.approx(
        0.022943, rel=1e-4)
    assert f.mhc_least_time_s(call, 4, PEAKS) == \
        4 * f.mhc_least_time_s(call, 1, PEAKS)


# ---- the reducer and the readers ---------------------------------------

# an op's path as the compiled step has it (seen in the compiled text, PR
# 66): the layers' scan, its body a closed call, under `_remat`
STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%fusion.1 = f", 0, 10, STEP + "jvp(mhc/expand)/broadcast_in_dim"],
        ["%while.1 = while()", 10, 790, STEP + "jvp(layers)/while"],
        ["%fusion.2 = f", 10, 40, FWD + "mhc/maps/btnd,ndm->mbt/"
                                        "dot_general"],
        ["%fusion.3 = f", 50, 30, FWD + "mhc/maps/div"],
        ["%fusion.4 = f", 80, 20, FWD + "mhc/pre/mul"],
        ["%fusion.5 = f", 100, 50, FWD + "attn_norm/mul"],
        ["%fusion.6 = f", 150, 100, FWD + "qkv/q_down/btd,dr->btr/dot"],
        ["%fusion.7 = f", 250, 60, FWD + "attn_out/bthk,hkd->btd/dot"],
        ["%fusion.8 = f", 310, 45, FWD + "attn_out/mhc/post/concatenate"],
        ["%fusion.9 = f", 355, 25, FWD + "moe/combine/mhc/post/"
                                         "concatenate"],
        ["%fusion.10 = f", 380, 35, REMAT + "mhc/maps/reduce_sum"],
        ["%fusion.11 = f", 415, 15, REMAT + "mlp/down/mhc/post/mul"],
        ["%fusion.12 = f", 430, 70, BWD + "mhc/maps/btnd,ndm->mbt/"
                                          "dot_general"],
        ["%fusion.13 = f", 500, 40, BWD + "mhc/pre/broadcast_in_dim"],
        ["%fusion.14 = f", 540, 60, BWD + "mlp/gate_up/dot"],
        ["%fusion.15 = f", 800, 20, STEP + "jvp(mhc/collapse)/reduce_sum"],
        ["%fusion.16 = f", 820, 50, STEP + "jvp(head)/dot"],
        ["%fusion.17 = f", 870, 30, STEP + "amhc/maps/x"],   # no scope
        ["%fusion.18 = f", 900, 50, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_mhc_reduce_on_a_hand_made_trace():
    reduced = mhc_reduce.reduce_mhc(hand_made_trace())
    want = {"mhc/maps": 40 + 30 + 35 + 70, "mhc/pre": 20 + 40,
            "mhc/post": 45 + 25 + 15, "mhc/expand": 10, "mhc/collapse": 20}
    assert set(reduced) == set(want)
    for scope, t in want.items():
        assert abs(reduced[scope] - t * 1e-9) < 1e-15, scope
    # the loop it borrowed is handed back as it was
    assert kda_reduce.scope_of(STEP + "kda/delta/x") == "kda/delta"
    assert kda_reduce.reduce_kda(hand_made_trace()) == {}
    # no new name is a bucket of scope_reduce's: the write is booked with
    # the scope that closes its sublayer, the rest with `layers`
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert not [b for b in scopes["bucket_s"] if "mhc" in b]
    assert abs(scopes["bucket_s"]["attn_out"] - (60 + 45) * 1e-9) < 1e-15
    assert abs(scopes["bucket_s"]["moe"] - 25e-9) < 1e-15
    assert mhc_reduce.reduce_mhc({"planes": []}) is None


@pytest.mark.parametrize("path,scope", [
    (FWD + "mhc/maps/btnd,ndm->mbt/dot_general:", "mhc/maps"),
    (REMAT + "mhc/pre/mul", "mhc/pre"),
    (BWD + "attn_out/mhc/post/concatenate", "mhc/post"),
    (STEP + "jvp(mhc/expand)/broadcast_in_dim", "mhc/expand"),
    (STEP + "transpose(jvp(mhc/collapse))/broadcast", "mhc/collapse"),
    (FWD + "attn_out/dot", None),
    (STEP + "amhc/maps/x", None),
    (STEP + "mhc/other/x", None),
    (STEP + "mhc/mapsx/y", None),
])
def test_mhc_scope_of_a_path(path, scope):
    assert mhc_reduce.scope_of(path) == scope


def _record(**over):
    held = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "mhc_call": flops_mhc_moe.mhc_call(
            held, 8192, "bfloat16", True)},
        "counters": {"held_slots_share": [12.0, 12.5, 13.5]},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(mhc_reduce, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_10_04"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    busy = 10 + 790 + 20 + 50 + 30 + 50   # 950 of the window's 1000 ns
    assert values["mhc_share"] == pytest.approx(100 * 350 / busy)
    assert values["mhc_maps_share"] == pytest.approx(100 * 175 / busy)
    assert values["mhc_mix_share"] == pytest.approx(100 * 145 / busy)
    # one step's 22.94 ms of bytes over 350 ns under `mhc/*`: a hand-made
    # trace, not a chip; the quotient is what is checked
    assert values["mhc_stream_roofline"] == pytest.approx(
        100 * (18_790_481_920 / 819e9) / 350e-9)
    roofline = load_module("layer_metrics", "mhc_stream_roofline").roofline(
        _record())
    assert roofline["bound"] == "memory"
    assert values["mhc_moe_held_slots_share"] == 12.5
    # the accepted readers read the same trace as they do elsewhere
    assert load_module("layer_metrics", "attn_proj_share").read(
        _record()) is not None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no `mhc/*` scope
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("mhc/", "layers_")
    _fresh(monkeypatch, bare)
    assert read(_record()) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


def test_counter_reader_with_nothing_to_read():
    read = load_module("layer_metrics", "mhc_moe_held_slots_share").read
    assert read({"counters": {}}) is None and read({}) is None
    # the roofline needs the job's call and the chip's peaks
    roofline = load_module("layer_metrics", "mhc_stream_roofline").read
    assert roofline(_record(static={"peaks": PEAKS})) is None
    assert roofline(_record(static={})) is None


# ---- the spec and the configuration ------------------------------------


def test_the_cells_entries_are_in_the_spec_by_name():
    """Found by name, never by position: a later PR appends behind
    them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_1seq", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/XingChen-AGI/"
                               "Xing4.0-29B-A4B/blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # the accepted cells are still there, each before this one
    names = [w["name"] for w in spec["workloads"]]
    for accepted in ("train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                     "train_olmoe_d1", "train_glm47flash_ep8_d5",
                     "train_nemotron3super_ep64_d11",
                     "train_phi4miniflash_d6", "train_ling3flash_ep64_d7",
                     "train_sdar30b_ep8_d4", "train_mellum2_ep4_d4",
                     "train_olmohybrid7b_tp2_d4", "train_ouro26b_d8"):
        assert names.index(accepted) < names.index(CELL)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(names) // 4)
    per_layer = [m["name"] for m in spec["per_layer"]]
    for name in NEW_METRICS:
        assert per_layer.index(name) > per_layer.index("loop_exit_share")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    want = {"mhc_share": ("lower", "device_trace", "model step"),
            "mhc_maps_share": ("lower", "device_trace", "model step"),
            "mhc_mix_share": ("lower", "device_trace", "model step"),
            "mhc_stream_roofline": ("higher", "device_trace", "kernels"),
            "mhc_moe_held_slots_share": ("higher", "program_counter",
                                         "model step")}
    for name, (better, source, layer) in want.items():
        assert mine[name] == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "train_tokens_per_s",
            "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "kda_share", "gdn_share", "ssm_share",
                 "mla_down_share", "moe_held_share", "held_slots_share",
                 "loop_carry_share", "collective_exposed_share"):
        assert name not in mine
        assert CELL not in by_name(spec["per_layer"], name,
                                   "metric")["workloads"]
    unlisted = {m["name"] for m in spec["per_layer"]
                if "workloads" not in m and m["moves"] in (
                    "train_tokens_per_s", "setup_s")}
    assert unlisted <= set(mine) and len(mine) == len(unlisted) + 5
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "unscoped_share",
            "peak_hbm_gb", "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the traffic file is four accepted cells', unedited
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    cut = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1),
           "n_routed_experts": (64, 8), "num_attention_heads": (32, 8),
           "num_key_value_heads": (32, 8), "vocab_size": (131072, 16384),
           "num_nextn_predict_layers": (1, 0)}
    assert list(held["reduced"]) == REDUCED == list(cut)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cut:
                assert (value, held[key]) == cut[key], key
            else:
                assert held[key] == value, key
    for key, (published, here) in cut.items():
        assert (held["reduced"][key]["published"],
                held["reduced"][key]["here"]) == (published, here)
    # no width is cut, no stream, no round, nothing of the router or YaRN
    assert (held["hidden_size"], held["intermediate_size"],
            held["moe_intermediate_size"], held["q_lora_rank"],
            held["kv_lora_rank"], held["qk_nope_head_dim"],
            held["qk_rope_head_dim"], held["v_head_dim"], held["hc_mult"],
            held["hc_sinkhorn_iters"], held["num_experts_per_tok"]) == \
        (3584, 9216, 1024, 768, 512, 128, 64, 128, 4, 20, 4)
    assert held["rope_scaling"]["factor"] == 64
    assert held["share"]["router_experts"] == 64
    assert (held["job"], held["reference"]) == ("train_lm_mhc_moe",
                                                "xing4_f32")
    for key in ("hyper_connection_form", "hc_eps", "mhc_h_res_clamp",
                "sinkhorn_order", "entry_and_exit", "sublayer_pre_norm",
                "yarn", "rope_pairing", "e_score_correction_bias",
                "aux_loss", "multi_token_prediction", "initializer",
                "learning_rate"):
        assert held["assumed"][key], key
    assert "8 chips" in held["stands_for"]
    tol = held["tolerance"]
    assert set(tol) == {"logits_rel_l2", "loss_abs", "maps_rel_l2",
                        "marginal_err", "why"}
    assert tol["marginal_err"] == 1e-3
    # each limit between its two readings (the `why` has them)
    assert 0.0618 < tol["logits_rel_l2"] < 0.170
    assert 0.0026 < tol["maps_rel_l2"] < 0.0362
    assert 0.00115 * 3 < tol["loss_abs"] <= 0.005
    # YaRN's factor on the scale is a gain of its own: GLM's 2.0 is 1.0
    assert held["init"]["q_latent_gain"] == 1.0
    assert held["init"]["hc_res_spread"] == 0.5
    glm = load_json(os.path.join(BENCH_DIR, "configs",
                                 "glm-4.7-flash-ep8-d5.json"))
    assert held["train"] == glm["train"]
    assert held["kernels"] == glm["kernels"]
    assert held["layout"] == glm["layout"]


def test_the_job_maps_the_row_onto_the_program():
    from ray_tpu.models.configs import TransformerConfig

    job = load_module("jobs", "train_lm_mhc_moe")
    held = load_json(CONFIG)
    cfg = job.transformer_config(held, held["train"], 8192)
    assert isinstance(cfg, TransformerConfig)
    assert (cfg.residual_streams, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, 30.0)
    assert (cfg.rope_yarn_factor, cfg.rope_yarn_original_len,
            cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow,
            cfg.yarn_attention_factor, cfg.rope_yarn_mscale_all_dim) == \
        (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 2.0047,
                                              rel=1e-4)
    assert (cfg.n_layers, cfg.moe_dense_layers, cfg.d_model, cfg.n_heads,
            cfg.head_dim, cfg.v_dim, cfg.moe_experts, cfg.held_experts,
            cfg.moe_top_k, cfg.moe_routed_scale, cfg.vocab_size,
            cfg.moe_dense_ff, cfg.ff_dim, cfg.norm_eps) == \
        (5, 1, 3584, 8, 192, 128, 64, 8, 4, 2.0, 16384, 9216, 1024, 1e-6)
    assert cfg.num_params == flops_mhc_moe.total_params(held) == 670_872_590
    with pytest.raises(ValueError, match="exceed the context"):
        job.transformer_config(held, held["train"], 262145)
    for key, value, said in (
            ("num_nextn_predict_layers", 1, "multi-token"),
            ("n_group", 2, "group-limited"),
            ("hidden_act", "gelu", "silu"),
            ("scoring_func", "softmax", "sigmoid"),
            ("num_key_value_heads", 4, "key/value head"),
            ("hc_mult", 1, "train_lm_mla_moe"),
            ("mhc_h_res_clamp_min", -20, "symmetrically"),
            ("rope_scaling", None, "YaRN"),
            ("rope_scaling", dict(held["rope_scaling"], mscale=0.7),
             "mscale")):
        with pytest.raises(ValueError, match=said):
            job.refuse_what_the_program_lacks(dict(held, **{key: value}))


def test_the_job_refuses_before_any_process_starts(monkeypatch):
    import dataclasses

    from ray_tpu.models import configs

    job = load_module("jobs", "train_lm_mhc_moe")
    held = load_json(CONFIG)
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    ctx = {"cell": {"name": CELL}, "config": held, "traffic": mix}
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    for key in ("packed_documents", "segment_masks"):
        with pytest.raises(ValueError, match=key):
            job.run(dict(ctx, traffic=dict(mix, **{key: True})))
    # a program without the new fields (the parent of PR 66)
    new = ("residual_streams", "hc_sinkhorn_iters", "hc_eps",
           "hc_res_clamp", "rope_yarn_mscale_all_dim")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in new])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    with pytest.raises(RuntimeError, match="TransformerConfig has no"):
        job.run(ctx)
    assert not started


def test_the_stand_in_weights_and_the_reference_layout():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer

    job = load_module("jobs", "train_lm_mhc_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 64)
    params = job.init_params(jax.random.key(3), cfg, model["init"])
    full = job.init_params(jax.random.key(3), cfg,
                           dict(model["init"], hc_res_spread=1.0))
    frozen = jax.tree.leaves(Transformer.frozen(cfg))
    counted = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), frozen) if not keep)
    assert counted == flops_mhc_moe.total_params(model) == cfg.num_params
    assert flops_mhc_moe.published_params(model) == model["published_params"]
    n, maps = cfg.residual_streams, cfg.hc_maps
    for run in ("dense_layers", "layers"):
        lay = params[run]
        for name in job.GAINS:   # off 1: a norm left out shows
            gain = np.asarray(lay[name])
            assert 0.2 < gain.std() < 0.4, name
        for name in job.SUBLAYERS:
            assert float(np.asarray(lay[name + "_hc_alpha"]).min()) == 1.0
            b = np.asarray(lay[name + "_hc_b"])
            phi = np.asarray(lay[name + "_hc_phi"])
            assert phi.shape[1:] == (n * cfg.d_model, maps)
            # H_res's part at half the spread of the other two maps'
            whole = np.asarray(full[run][name + "_hc_b"])
            np.testing.assert_array_equal(b[:, :2 * n], whole[:, :2 * n])
            np.testing.assert_allclose(b[:, 2 * n:], 0.5 * whole[:, 2 * n:])
            assert phi[..., :2 * n].std() == pytest.approx(
                2 * phi[..., 2 * n:].std(), rel=0.1)
            assert phi[..., :2 * n].std() == pytest.approx(
                (n * cfg.d_model) ** -0.5, rel=0.1)
    w = job.to_reference_layout(params, cfg)
    assert len(w["layers"]) == 3
    for at, (run, i) in enumerate((("dense_layers", 0), ("layers", 0),
                                   ("layers", 1))):
        for name in job.SUBLAYERS:
            hc = w["layers"][at][name + "_hc"]
            assert set(hc) == {"phi", "b", "alpha"}
            np.testing.assert_array_equal(
                hc["phi"], np.asarray(params[run][name + "_hc_phi"][i]).T)
            np.testing.assert_array_equal(
                hc["b"], np.asarray(params[run][name + "_hc_b"][i]))
    assert "mlp" in w["layers"][0] and "experts" in w["layers"][1]


def test_a_step_is_sound_only_with_doubly_stochastic_maps():
    job = load_module("jobs", "train_lm_mhc_moe")
    step = {"loss": 7.1, "marginal_err": 2e-5, "stream_gain": 2.4}
    assert job.step_is_sound(step, 1e-3)
    assert not job.step_is_sound(dict(step, loss=float("nan")), 1e-3)
    assert not job.step_is_sound(dict(step, stream_gain=float("inf")), 1e-3)
    assert not job.step_is_sound(dict(step, marginal_err=2e-3), 1e-3)


def test_fault_reader_on_the_jobs_own_weights():
    """`reference/xing4_faults.py` through `read`, as the chip runs it: on
    the job's stand-in weights and sample at the tiny size, every fault
    moves the limit it is listed under well over float32's rounding, and
    what is no fault shows nowhere."""
    faults = load_module("reference", "xing4_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(
        model, mix, 7, names=("no_sinkhorn", "h_res_transposed",
                              "no_yarn_softmax_factor", "no_routed_scale",
                              "exit_mean", "float8_e4m3fn"))}
    key = {"maps_rel_l2": "maps_rel_l2", "logits_rel_l2": "rel_l2"}
    for name in ("no_sinkhorn", "h_res_transposed",
                 "no_yarn_softmax_factor", "no_routed_scale",
                 "float8_e4m3fn"):
        assert rows[name]["listed_under"] == faults.LISTED_UNDER[name]
        assert rows[name][key[rows[name]["listed_under"]]] > 1e-2, rows[name]
    assert rows["exit_mean"]["rel_l2"] < 1e-5
    assert rows["exit_mean"]["maps_rel_l2"] == 0.0
    assert rows["exit_mean"]["correct"] is True


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-xing4", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-xing4.json",
        "reduced": ["n_routed_experts"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_xing4", "config": "tiny-xing4",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_xing4")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_xing4"]))
    path = tmp_path_factory.mktemp("xing4_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_streams_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_xing4", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True and line["failed"] == 0
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_mhc_moe_held_slots_share" in line["metrics"]
        assert "rehearsal_mhc_share" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
