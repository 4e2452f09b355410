"""What PR 57 adds to the benchmark for `train_mellum2_ep4_d4`, checked
without a chip: `benchlib/flops_ep_moe.py` against hand-worked numbers at
the published widths, `benchlib/ep_reduce.py` and the eight new readers on
a hand-made trace of two chips (and on a program or a run that gives them
nothing to read), the spec's new entries BY NAME, never by position and
with no count of cells of any kind, the configuration file against the
catalog row key by key, what the job refuses, the stand-in weights, the
fault reader, and the job kind `train_lm_ep_moe` rehearsed at a tiny size
on four virtual CPU devices (a rehearsal's numbers carry the `rehearsal_`
prefix and are never a device metric)."""

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

from benchlib import (ep_reduce, flops, flops_ep_moe,  # noqa: E402
                      sambay_reduce, scope_reduce, subscope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_mellum2", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_mellum2_ep4_d4"
NAME = "mellum2-12b-a2.5b-ep4-d4"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-mellum2.json")
NEW_METRICS = ["ep_moe_share", "ep_exchange_share",
               "ep_exchange_exposed_share", "ep_exchange_roofline",
               "ep_experts_roofline", "ep_rows_sent_over_needed",
               "ep_chip_rows_max_over_mean", "swa_attn_kernel_roofline"]
TRACE_READERS = NEW_METRICS[:5] + NEW_METRICS[7:]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "ici_bits_per_s": 1600e9}
SEQ = 8192
KERNELS = {"fwd": "^%?splash_m[hq]a_fwd(_segmented)?(_no)?_residuals",
           "bwd_dkv": "^%?splash_m[hq]a_dkv(_segmented)?_no_residuals",
           "bwd_dq": "^%?splash_m[hq]a_dq(_segmented)?_no_residuals"}


# ---- arithmetic --------------------------------------------------------


def test_flops_ep_moe_hand_worked():
    """The issue's own arithmetic, from the configuration file."""
    model = load_json(CONFIG)
    f = flops_ep_moe
    assert f.layer_kinds(model) == ["window", "window", "window", "full"]
    # q and o 2 x 2304 x 4096, k and v 2 x 2304 x 512
    assert f.attention_params(model) == 2 * 9_437_184 + 2 * 1_179_648
    assert f.expert_params(model) == 6_193_152
    assert f.router_params(model) == 147_456
    assert f.layer_params(model) == 417_747_712
    assert f.total_params(model) == 2_123_977_984
    whole = dict(model, num_hidden_layers=28)
    assert f.total_params(whole) == 12_149_923_072 \
        == 28 * 417_747_712 + 452_987_136
    # a token's forward FLOPs: experts 35%, head 40%, projections 15%,
    # kernels 10% (a window layer 1,024 keys a query, not T / 2)
    shares = f.forward_flops_shares(model, SEQ)
    assert sum(shares.values()) == pytest.approx(1.0)
    assert round(100 * shares["experts"]) == 35
    assert round(100 * shares["head"]) == 40
    assert round(100 * shares["attention_projections"]) == 15
    assert round(100 * shares["attention_kernels"]) == 10
    assert f.attention_pairs("full", SEQ, 1024) == SEQ * SEQ / 2
    assert f.attention_pairs("window", SEQ, 1024) == \
        SEQ * 1024 - 1024 * 1024 / 2 == 7_864_320
    assert f.attention_pairs("window", 512, 1024) == 512 * 512 / 2
    full = f.attention_call_flops("fwd", "full", model, SEQ)
    assert full == flops.attention_call_flops("fwd", 1, 32, SEQ, 128)
    assert f.attention_call_flops("fwd", "window", model, SEQ) \
        == full * 7_864_320 / (SEQ * SEQ / 2)
    assert f.train_flops_per_token(model, SEQ) == \
        3 * f.forward_flops_per_token(model, SEQ)
    assert round(f.train_flops_per_token(model, SEQ) / 1e9, 2) == 3.4
    # the one-shape reader's length: four causal calls of it are not
    # above three window calls and a full one
    call = f.attention_call_not_above(model, SEQ)
    assert (call["heads"], call["kv_heads"], call["head_dim"]) == \
        (32, 4, 128)
    t = call["seq"]
    assert t % 128 == 0 and 4 * t * t / 2 <= 3 * 7_864_320 + SEQ * SEQ / 2 \
        < 4 * (t + 128) ** 2 / 2


def test_exchange_and_experts_least_time_hand_worked():
    model = load_json(CONFIG)
    f = flops_ep_moe
    # 1,000 pairs: 1,000 rows of 2,304 bf16 out four times, at 200 GB/s
    assert f.exchange_bytes_out(model, 1000) == 4 * 1000 * 2304 * 2
    least = f.exchange_least_time_s(
        model, [[[1000, 0], [3000, 500]]], PEAKS)
    assert least == pytest.approx([4000 * 2304 * 8 / 200e9,
                                   500 * 2304 * 8 / 200e9])
    # one step, one layer, two chips of 65,536 and 0 rows: the mean
    rows = 65536
    got, bound = f.experts_least_time_s(model, [[[rows, 0]]], 16, True,
                                        PEAKS)
    per_call = [max(2.0 * rows * k * n / 197e12,
                    2.0 * (rows * k + rows * n + 16 * k * n) / 819e9)
                for k, n in ((2304, 1792), (896, 2304))]
    empty = [2.0 * 16 * k * n / 819e9 for k, n in ((2304, 1792),
                                                   (896, 2304))]
    assert got == pytest.approx(4 * (sum(per_call) + sum(empty)) / 2)
    assert bound == "compute"


# ---- the reduction on a hand-made trace ----------------------------------

STEP = "jit(_step)/jit(main)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
MAP = "shard_map/"


def chip_ops(slow: int):
    """One chip's ops in a window of 1000 ns; `slow`: how much longer its
    grouped matmul runs (the fuller chip)."""
    return [
        ["%fusion.1 = f", 0, 20, STEP + "jvp(rope/yarn)/cos"],
        ["%while.1 = while()", 20, 800, STEP + "jvp(layers)/while"],
        ["%splash_mha_fwd_residuals.1 = custom-call()", 20, 50,
         FWD + "attention/window/vmap(splash)"],
        ["%splash_mha_fwd_residuals.2 = custom-call()", 70, 100,
         FWD + "attention/full/vmap(splash)"],
        ["%fusion.2 = f", 170, 30, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 200, 20, FWD + MAP + "moe/dispatch/sort"],
        ["%all-to-all.1 = a", 220, 10, FWD + MAP + "moe/exchange/counts"],
        ["%all-to-all.2 = a", 230, 60, FWD + MAP + "moe/exchange/rows"],
        ["%gmm.1 = custom-call()", 290, 100 + slow,
         FWD + MAP + "moe/experts/gmm"],
        ["%all-to-all.3 = a", 400 + slow, 60,
         FWD + MAP + "moe/exchange/back"],
        ["%gather.1 = g", 470 + slow, 20, FWD + MAP + "moe/combine/gather"],
        ["%splash_mha_dkv_no_residuals.3 = custom-call()", 500 + slow, 150,
         BWD + "attention/full/transpose(vmap(splash))"],
        ["%tgmm.1 = custom-call()", 650 + slow, 50,
         BWD + MAP + "moe/experts/tgmm"],
        ["%all-gather.1 = a", 700 + slow, 40, STEP + "jvp(head)/all-gather"],
        ["%fusion.9 = f", 820, 80, STEP + "jvp(head)/dot"],
        ["%fusion.12 = f", 900, 100, STEP + "optimizer/adamw"],
    ]


def hand_made_trace():
    """Two chips, one window of 1000 ns; op, start, duration, path. Chip
    0's asynchronous all-to-all (start to done 300..380) overlaps its
    grouped matmul."""
    planes = []
    for chip, slow in ((0, 0), (1, 20)):
        lines = [{"name": "XLA Ops", "events": chip_ops(slow)},
                 {"name": "XLA Modules",
                  "events": [["jit__step(1)", 0, 1000]]}]
        if chip == 0:
            lines.append({"name": "Async XLA Ops", "events": [
                ["%all-to-all-start.7 = a", 300, 80,
                 FWD + MAP + "moe/exchange/async"]]})
        planes.append({"name": f"/device:TPU:{chip}", "lines": lines})
    planes.append({"name": "/host:CPU", "lines": [{"name": "py", "events": [
        ["bench_window", 0, 1000]]}]})
    return {"planes": planes}


def test_ep_reduce_on_a_hand_made_trace():
    reduced = ep_reduce.reduce_ep(hand_made_trace())
    assert reduced["chips"] == [0, 1]
    assert reduced["exchange_events"] == [4, 3]
    for chip, slow in ((0, 0), (1, 20)):
        sub = reduced["sub_s"][chip]
        assert set(sub) == set(ep_reduce.MOE)
        assert sub["exchange"] == pytest.approx(130e-9)
        assert sub["experts"] == pytest.approx((150 + slow) * 1e-9)
        assert sub["router"] == pytest.approx(30e-9)
        assert reduced["busy_s"][chip] == pytest.approx(1000e-9)
    # chip 0: 130 ns of blocking all-to-alls and an asynchronous one from
    # 300 to 380 that the grouped matmul (290..390) hides whole
    assert reduced["exchange_s"][0] == pytest.approx(210e-9)
    assert reduced["exposed_s"][0] == pytest.approx(130e-9)
    assert reduced["exchange_s"][1] == pytest.approx(130e-9)
    assert reduced["exposed_s"][1] == pytest.approx(130e-9)
    assert ep_reduce.reduce_ep({"planes": []}) is None


@pytest.mark.parametrize("path,scope", [
    (FWD + MAP + "moe/exchange/rows", "exchange"),
    (BWD + MAP + "moe/experts/tgmm", "experts"),
    (FWD + "moe/router/dot", "router"),
    (FWD + "moe/exchanged/x", None),
    (FWD + "amoe/exchange/x", None),
    (FWD + "attention/window/x", None),
])
def test_subscope_of_a_path(path, scope):
    assert ep_reduce.subscope_of(path) == scope


def _record(**over):
    model = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 2, "modules_per_device": 1,
                  "kernel_s": {"moe": {"gmm": [110e-9, 1.0],
                                       "tgmm": [50e-9, 1.0]}}},
        "static": {
            "peaks": PEAKS, "attention_kernels": KERNELS, "chips": 2,
            "ep_call": {
                "model": {k: model[k] for k in (
                    "hidden_size", "head_dim", "num_attention_heads",
                    "num_key_value_heads", "moe_intermediate_size",
                    "num_experts", "num_experts_per_tok", "sliding_window",
                    "num_hidden_layers", "layer_types")},
                "seq": SEQ, "batch": 1, "held": 16, "remat": True,
                "shard_device_ids": [1, 0]}},
        "counters": {"rows_sent_over_needed": 2.04,
                     "chip_rows_max_over_mean": [1.1, 1.3, 1.2],
                     "traced_rows_received": [[[2, 1]]],
                     "traced_exchange_pairs": [[[1, 2]]]},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(ep_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(subscope_reduce, "_REDUCED", {})
    monkeypatch.setattr(sambay_reduce, "_REDUCED", {})
    monkeypatch.setattr(ep_reduce, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_10_02"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    busy = 2 * 1000.0     # the scan's `while` covers what lies between
    # every op under a moe/<name>, the all-to-alls among them
    assert values["ep_moe_share"] == pytest.approx(
        100 * (2 * 350 + 20) / busy)
    assert values["ep_exchange_share"] == pytest.approx(
        100 * (210 + 130) / busy)
    assert values["ep_exchange_exposed_share"] == pytest.approx(
        100 * (130 + 130) / busy)
    assert values["ep_rows_sent_over_needed"] == 2.04
    assert values["ep_chip_rows_max_over_mean"] == 1.2      # the median
    # the exchange: shard 0 is device 1 (1 pair), shard 1 device 0 (2
    # pairs); the worst chip is the smaller share
    row = 4 * 2304 * 2 / 200e9
    out = load_module("layer_metrics", "ep_exchange_roofline").roofline(
        _record())
    assert out["by_chip"] == pytest.approx({0: 100 * 2 * row / 210e-9,
                                            1: 100 * row / 130e-9})
    assert values["ep_exchange_roofline"] == pytest.approx(
        min(out["by_chip"].values()))
    # the experts: the kernels' events of the reduced trace
    model = _record()["static"]["ep_call"]["model"]
    least, bound = flops_ep_moe.experts_least_time_s(
        model, [[[2, 1]]], 16, True, PEAKS)
    assert values["ep_experts_roofline"] == pytest.approx(
        100 * least / 160e-9)
    assert bound == "memory"      # three rows: the weights' bytes
    # the kernels: by the scope of their path, each at its own pair count
    out = load_module("layer_metrics",
                      "swa_attn_kernel_roofline").roofline(_record())
    assert set(out["by_kind"]) == {"window.fwd", "full.fwd",
                                   "full.bwd_fused"}
    t_window = flops.least_time_s(
        flops_ep_moe.attention_call_flops("fwd", "window", model, SEQ),
        flops_ep_moe.attention_call_bytes("fwd", model, SEQ), PEAKS)[0]
    # two chips' events, the mean of their seconds
    assert out["by_kind"]["window.fwd"] == pytest.approx(
        100 * t_window * 2 / 2 / 50e-9)
    # the accepted readers read the same trace as they did
    assert load_module("layer_metrics", "mlp_share").read(_record()) == 0.0
    assert load_module("layer_metrics", "head_share").read(
        _record()) == pytest.approx(100 * 80 / 1000)
    # `moe_share`'s bucket leaves the collectives out: the reason for
    # `ep_moe_share`
    assert scope_reduce.share(_record(), ("moe",)) == pytest.approx(
        100 * (2 * 220 + 20) / busy)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own expert cells: `moe/*` and no
    # exchange, the kernels under plain `attention`
    bare = hand_made_trace()
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [
                e for e in line["events"]
                if len(e) < 4 or "moe/exchange" not in e[3]]
            for e in line["events"]:
                if len(e) == 4:
                    e[3] = e[3].replace("attention/window", "attention") \
                        .replace("attention/full", "attention")
    _fresh(monkeypatch, bare)
    if name == "ep_experts_roofline":     # the job's call and counters
        assert read(_record()) is not None
        assert read(_record(counters={})) is None
    else:
        assert read(_record()) is None
    # a record without the job's call
    _fresh(monkeypatch, hand_made_trace())
    if "roofline" in name:
        assert read(_record(static={"peaks": PEAKS})) is None
        assert read(_record(static={})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    if name != "ep_experts_roofline":
        assert read(_record()) is None


def test_counter_readers_with_nothing_to_read():
    for name in ("ep_rows_sent_over_needed", "ep_chip_rows_max_over_mean"):
        read = load_module("layer_metrics", name).read
        assert read({}) is None
        assert read({"counters": {}}) is None
    assert load_module("layer_metrics", "ep_chip_rows_max_over_mean").read(
        {"counters": {"chip_rows_max_over_mean": []}}) is None


# ---- the spec and the configuration ------------------------------------


def test_spec_entries_of_the_cell():
    """Found by name, never by position, and no cell of any kind is
    counted: a later PR appends behind them."""
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "sft_8k_global4", 4)
    assert len(cell["why"]) <= 200
    for word in ("exchanged", "3 window : 1 full", "head 40%"):
        assert word in cell["why"], word
    entry = by_name(spec["configs"], NAME, "configuration")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    assert {(mine[n]["unit"], mine[n]["better"]) for n in NEW_METRICS
            if n.endswith("_roofline")} == {("%", "higher")}
    assert {mine[n]["layer"] for n in NEW_METRICS} == \
        {"model step", "collectives", "kernels"}
    assert mine["ep_exchange_roofline"]["layer"] == "collectives"
    assert mine["ep_experts_roofline"]["layer"] == "kernels"
    assert mine["ep_rows_sent_over_needed"]["source"] == "program_counter"
    assert mine["ep_chip_rows_max_over_mean"]["better"] == "lower"
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "moe_experts_roofline", "moe_held_share",
                 "held_slots_share", "collective_exposed_share",
                 "masked_attn_kernel_roofline", "softmax_held_moe_share",
                 "blockdiff_attn_kernel_roofline"):
        assert name not in mine
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms", "gang_backend_s"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "sft_8k_global4.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (4, SEQ)
    assert mix["reference_sample"] == {"sequences": 4, "tokens": SEQ}
    assert (mix["warmup_steps"], mix["trace_steps"],
            mix["report_every"]) == (2, 4, 1)
    assert mix["unigram"] == {"law": "zipf", "exponent": 1.1}
    same = load_json(os.path.join(BENCH_DIR, "traffic",
                                  "sft_4k_global8.json"))
    assert set(mix) == set(same)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"][0]


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    held = load_json(CONFIG)
    row = catalog_row()
    catalog = row["config"]
    assert held["source"] == row["source_url"]
    # key by key: every key of the row is in the file, at its published
    # value but for `reduced`; the nested groups whole
    assert set(catalog) <= set(held)
    differ = sorted(k for k, v in catalog.items() if held[k] != v)
    assert differ == sorted(held["reduced"]) == ["num_hidden_layers"]
    cut = held["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"]) == (28, 4) \
        == (catalog["num_hidden_layers"], held["num_hidden_layers"])
    # every width, the expert count and the vocabulary as published
    assert (held["hidden_size"], held["intermediate_size"],
            held["moe_intermediate_size"], held["head_dim"],
            held["num_attention_heads"], held["num_key_value_heads"],
            held["num_experts"], held["num_experts_per_tok"],
            held["vocab_size"], held["sliding_window"],
            held["rms_norm_eps"]) == \
        (2304, 7168, 896, 128, 32, 4, 64, 8, 98304, 1024, 1e-06)
    assert held["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert len(held["layer_types"]) == len(held["mlp_layer_types"]) == 28
    full = held["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"],
            full["original_max_position_embeddings"]) == ("yarn", 16, 8192)
    layout = held["layout"]
    assert (layout["workers"], layout["tpus_per_worker"],
            layout["mesh"]) == (1, 4, {"data": 1, "fsdp": 4})
    assert layout["rules"] == {"expert": "fsdp", "expert_embed": None}
    assert set(layout["sharded_leaves"]) == {"by_expert", "by_embed"}
    assert "24 layers lie on six further hosts" in held["stands_for"]
    for key in ("qk_norm", "layer_types", "aux_loss", "mtp_head",
                "intermediate_size", "rope_pairing", "yarn_truncate",
                "initializer", "learning_rate"):
        assert key in held["assumed"], key
    assert "READING" in held["assumed"]["qk_norm"]
    assert "TO BE SET" not in held["tolerance"]["why"]
    job = load_module("jobs", "train_lm_ep_moe")
    cfg = job.transformer_config(held, held["train"], SEQ)
    assert cfg.num_params == flops_ep_moe.total_params(held) \
        == 2_123_977_984
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_top_k,
            cfg.moe_scoring, cfg.moe_aux_coeff, cfg.moe_norm_topk) == \
        (64, 64, 8, "softmax", 0.0, True)
    assert (cfg.head_dim, cfg.n_heads, cfg.kv_heads, cfg.ff_dim,
            cfg.max_seq_len, cfg.layer_pattern, cfg.attn_window) == \
        (128, 32, 4, 896, SEQ, "WWWL", 1024)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.rope
    assert (cfg.rope_theta, cfg.rope_yarn_factor,
            cfg.rope_yarn_original_len, cfg.rope_yarn_beta_fast,
            cfg.rope_yarn_beta_slow) == (5e5, 16.0, 8192, 32.0, 1.0)
    assert cfg.yarn_attention_factor == 1.2772588722239782
    assert cfg.pattern_runs == [("W", 3), ("L", 1)]
    rules = job.sharding_rules(layout)
    assert rules.mesh_axes("expert") == "fsdp"
    assert rules.mesh_axes("expert_embed") is None
    assert rules.mesh_axes("embed") == "fsdp"


@pytest.mark.parametrize("change,why", [
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
     "dense layer"),
    ({"layer_types": ["sliding_attention", "chunked_attention",
                      "sliding_attention", "full_attention"]},
     "layer_types"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"attention_bias": True}, "bias"),
    ({"router_aux_loss_coef": 0.001}, "aux loss"),
    ("rope_type", "rope_type"),
    ("sliding_yarn", "plain table"),
    ("two_thetas", "one rope_theta"),
])
def test_the_job_refuses_what_the_program_lacks(change, why):
    job = load_module("jobs", "train_lm_ep_moe")
    model = load_json(CONFIG)
    rope = model["rope_parameters"]
    if change == "rope_type":
        change = {"rope_parameters": dict(rope, full_attention=dict(
            rope["full_attention"], rope_type="longrope"))}
    elif change == "sliding_yarn":
        change = {"rope_parameters": dict(
            rope, sliding_attention=rope["full_attention"])}
    elif change == "two_thetas":
        change = {"rope_parameters": dict(rope, sliding_attention=dict(
            rope["sliding_attention"], rope_theta=10000))}
    model = dict(model, **change)
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], SEQ)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


@pytest.mark.parametrize("missing", ["field", "kind", "rule"])
def test_the_job_refuses_a_program_without_the_mechanisms(monkeypatch,
                                                          missing):
    """The parent's program: refused before the cluster starts (run.py
    then exits 1 in seconds and holds no chip)."""
    import dataclasses

    from ray_tpu.models import configs
    from ray_tpu.parallel import sharding
    job = load_module("jobs", "train_lm_ep_moe")
    if missing == "field":
        old = dataclasses.make_dataclass("TransformerConfig", [
            (f.name, f.type, f) for f in dataclasses.fields(
                configs.TransformerConfig)
            if not f.name.startswith("rope_yarn")])
        monkeypatch.setattr(configs, "TransformerConfig", old)
        match = "rope_yarn_factor"
    elif missing == "kind":
        monkeypatch.setattr(configs, "EXPERT_KINDS", "EKL")
        match = "kind W"
    else:
        monkeypatch.setattr(sharding, "DEFAULT_RULES", {
            k: v for k, v in sharding.DEFAULT_RULES.items()
            if k != "expert_embed"})
        match = "expert_embed"
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match=match):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL}})
    assert not started


# ---- the stand-in weights ----------------------------------------------------


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import Transformer

    job = load_module("jobs", "train_lm_ep_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    key = jax.random.key(3)
    plain = Transformer.init(key, cfg)
    params = job.init_params(key, cfg, model["init"])
    assert jax.tree.structure(plain) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(plain)] == \
        [a.shape for a in jax.tree.leaves(params)]
    assert float(jnp.std(params["embed"][:, 1:])) == pytest.approx(1.0,
                                                                   rel=0.05)
    assert np.allclose(params["embed"][:, 0], model["init"]["anchor"])
    group = cfg.n_heads // cfg.kv_heads
    pairs, half = model["init"]["anchor_pairs"], cfg.head_dim // 2
    for block in params["runs"]:
        for lay in block:
            assert float(jnp.std(lay["attn_norm"])) > 0.1
            # untouched: the experts' second matmul, the output projection
            # the positional head: its query reads column 0 alone
            last = np.arange(group - 1, cfg.n_heads, group)
            wq = np.asarray(lay["wq"])
            assert np.all(wq[:, 1:, last] == 0)
            assert np.all(wq[:, 0, last, pairs:half] == 0)
            # turned back by `look` positions in the fastest pairs
            turn = np.arctan2(wq[0, 0, last[0], half:half + pairs],
                              wq[0, 0, last[0], :pairs])
            theta = cfg.rope_theta ** (-np.arange(pairs) / half)
            want = -theta * model["init"]["look"]
            assert np.allclose(np.cos(turn), np.cos(want), atol=1e-5)
            assert np.allclose(np.sin(turn), np.sin(want), atol=1e-5)
            # nothing but the two heads' projections reads the column
            assert np.all(np.asarray(lay["w_router"])[:, 0] == 0)
            assert np.all(np.asarray(lay["w_moe_gateup"])[:, :, 0] == 0)
            assert np.all(np.asarray(lay["wkv"])[:, 0, 1] == 0)
    assert np.all(np.asarray(params["lm_head"])[0] == 0)
    np.testing.assert_array_equal(
        plain["runs"][0][0]["w_moe_down"], params["runs"][0][0]["w_moe_down"])
    np.testing.assert_array_equal(plain["runs"][1][0]["wo"],
                                  params["runs"][1][0]["wo"])


def test_the_reference_layout_holds_every_expert_by_its_id():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer

    job = load_module("jobs", "train_lm_ep_moe")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    params = Transformer.init(jax.random.key(0), cfg)
    weights = job.to_reference_layout(params, cfg)
    assert len(weights["layers"]) == 4
    assert job.layers_of(params, cfg) == [(0, 0, 0), (0, 0, 1), (0, 0, 2),
                                          (1, 0, 0)]
    for i, (r, s, j) in enumerate(job.layers_of(params, cfg)):
        lay, lw = params["runs"][r][s], weights["layers"][i]
        assert sorted(lw["experts"]) == list(range(cfg.moe_experts))
        np.testing.assert_array_equal(
            lw["experts"][5]["up_proj"], lay["w_moe_gateup"][j][5][:, 1].T)
        np.testing.assert_array_equal(lw["mlp.gate"], lay["w_router"][j].T)
        assert lw["q_proj"].shape == (cfg.n_heads * cfg.head_dim,
                                      cfg.d_model)


def test_fault_reader_leaves_the_reference_plain():
    faults = load_module("reference", "mellum2_faults")
    plain = load_module("reference", "mellum2_f32")
    model = load_json(TINY)
    assert set(faults.FAULTS) == set(
        faults.WINDOW_FAULTS + faults.ROPE_FAULTS + faults.LAYER_FAULTS
        + faults.EXCHANGE_FAULTS)
    assert len(faults.FAULTS) == 16
    changed = ("no_yarn_on_full", "yarn_on_sliding", "topk_not_normalised")
    for name in faults.FAULTS + faults.PRECISIONS + (None,):
        ref, cfg = faults.variant(name, model)
        assert ref is not plain
        assert (cfg is model) == (name not in changed), name
    for name in ("linear", "qk_norm", "rope_tables", "sliding_mask",
                 "causal_mask", "sparse_moe", "rotate_half",
                 "yarn_inv_freq", "attention_factor"):
        assert getattr(plain, name).__module__ == plain.__name__
    with pytest.raises(KeyError):
        faults.variant("no_such_fault", model)


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    # the contract's quota of four-chip cells (a quarter of the cells, one
    # always) is the real benchmark's to meet: this copy hands the
    # rehearsal's four-chip place to the new cell
    spec["workloads"] = [w for w in spec["workloads"] if w["chips"] != 4]
    used = {w["config"] for w in spec["workloads"]}
    spec["configs"] = [c for c in spec["configs"] if c["name"] in used]
    names = {w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w in names]
    spec["configs"].append({
        "name": "tiny-mellum2", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-mellum2.json",
        "reduced": [], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_mellum2", "config": "tiny-mellum2",
        "traffic": "rehearsal_tiny", "chips": 4, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_mellum2")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_mellum2"]))
    path = tmp_path_factory.mktemp("mellum2_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [
    pytest.param(0, marks=pytest.mark.slow), 1])
def test_rehearsal_of_the_ep_moe_job(rehearsal_spec, trace):
    """Four virtual devices: the exchange runs, the weights lie as the
    cell's do, every check of the job passes."""
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_mellum2", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        sent = line["metrics"]["rehearsal_ep_rows_sent_over_needed"]
        assert sent["value"] >= 1.0 and sent["unit"] == "ratio"
        skew = line["metrics"]["rehearsal_ep_chip_rows_max_over_mean"]
        assert 1.0 <= skew["value"] <= 4.0
        assert "rehearsal_step_ms" in line["metrics"]
        # no device trace on the CPU: nothing under a device metric's name
        for name in TRACE_READERS + ["model_flops_util"]:
            assert "rehearsal_" + name not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
