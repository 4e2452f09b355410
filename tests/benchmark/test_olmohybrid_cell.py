"""What PR 59 adds to the benchmark for `train_olmohybrid7b_tp2_d4`,
checked without a chip: `benchlib/flops_gdn.py` against hand-worked
numbers at the published widths, `benchlib/gdn_reduce.py` and the four new
readers on a hand-made trace (and on a program or a run that gives them
nothing to read), the spec's new entries BY NAME, never by position, the
configuration file against the catalog row key by key and its arithmetic,
what the job refuses, the stand-in weights, the fault reader, and the job
kind `train_lm_gdn` rehearsed at a tiny size on the CPU (a rehearsal's
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

from benchlib import (flops, flops_gdn, flops_kda_moe,  # noqa: E402
                      gdn_reduce, kda_reduce, scope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_olmohybrid", os.path.join(BENCH_DIR,
                                                    "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_olmohybrid7b_tp2_d4"
NAME = "olmo-hybrid-7b-tp2-d4"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs",
                    "tiny-olmohybrid.json")
NEW_METRICS = ["gdn_share", "gdn_delta_share", "gdn_proj_share",
               "gdn_delta_roofline"]
REDUCED = ["num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CALL = {"tokens": 8192, "layers": 3, "heads": 15, "d_k": 96, "d_v": 192,
        "chunk": 64, "remat": True, "implementation": "xla"}


# ---- arithmetic --------------------------------------------------------


def test_flops_gdn_hand_worked():
    f, held = flops_gdn, load_json(CONFIG)
    assert f.layer_pattern(held) == "ddda"
    assert (f.layers_of(held, "d"), f.layers_of(held, "a")) == (3, 1)
    # the mixer: q, k 2 x 3,840 x 15 x 96; v, the gate, W_o 3 x 3,840 x
    # 15 x 192; the decay's and beta's columns 2 x 3,840 x 15
    assert f.mixer_matmul_params(held) == \
        2 * 5_529_600 + 3 * 11_059_200 + 115_200 == 44_352_000
    # + three convolutions 15 x 384 x 4, A and dt, the head norm's gain
    assert f.mixer_params(held) == 44_352_000 + 23_040 + 30 + 192 \
        == 44_375_262
    assert f.attention_matmul_params(held) == 4 * 7_372_800
    assert f.attention_params(held) == 4 * 7_372_800 + 3_840 == 29_495_040
    assert f.mlp_params(held) == 3 * 3_840 * 11_008 == 126_812_160
    # a mixer layer 171,195,102, the attention layer 156,314,880,
    # embedding, head and final norm 96,341,760
    assert f.total_params(held) == 3 * 171_195_102 + 156_314_880 \
        + 96_341_760 == 766_241_946
    assert f.total_params(held) * 16 / 1e9 == pytest.approx(12.26, abs=5e-3)
    # at the published sizes: 7.43B
    published = dict(held, **{k: c["published"]
                              for k, c in held["reduced"].items()})
    assert f.mixer_params(published) + f.mlp_params(published) + 7_680 \
        == 215_570_172
    assert f.attention_params(published) + f.mlp_params(published) \
        + 7_680 == 185_809_920
    assert f.total_params(published) == 24 * 215_570_172 \
        + 8 * 185_809_920 + 770_707_200 == 7_430_870_688
    # a token's matmul parameters here: three mixers, attention, four
    # MLPs, the head's 3,840 x 12,544
    assert f.matmul_params_per_token(held) == 3 * 44_352_000 \
        + 29_491_200 + 4 * 126_812_160 + 48_168_960
    # attention over the causal pairs, both products 128 wide: 6 products
    # of 15 heads x 8,192 x 128 x 2 / 2 a token
    assert f.attention_train_flops_per_token(held, 8192) == \
        6 * 15 * 8192 * 128
    # the delta rule is KDA's count at keys of 96 beside values of 192
    per_head = (2 * 64 * 96 + 2 * 64 * 64 / 3 + 64 * (96 + 192)
                + 6 * 96 * 192 + 64 * 192)
    call = f.delta_call(held, 8192, 64, True, "xla")
    assert call == CALL
    assert flops_kda_moe.delta_flops_per_token(call) == \
        pytest.approx(15 * per_head)
    total = f.train_flops_per_token(held, 8192, 64)
    assert total == pytest.approx(
        6 * f.matmul_params_per_token(held) + 6 * 15 * 8192 * 128
        + 3 * 3 * 15 * per_head)
    # the delta rule is under 2% of a token's FLOPs: sized from time
    assert 3 * 3 * 15 * per_head / total < 0.02
    # least bytes: q, k, v in bf16, a decay and a beta a head and the
    # output in f32
    assert f.delta_bytes_per_token(call) == 15 * (2 * 384 + 4 * 194)
    least, bound = f.delta_least_time_s(call, 4, PEAKS)
    one_pass = max(8192 * 15 * per_head / 197e12,
                   8192 * f.delta_bytes_per_token(call) / 819e9)
    assert least == pytest.approx(4 * 3 * 4 * one_pass)
    assert bound == "memory"
    assert flops.least_time_s(1.0, 1.0, PEAKS)[1] == "memory"


# ---- the reducer and the readers ---------------------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 900, STEP + "jvp(layers)/while"],
        ["%fusion.1 = f", 0, 60,
         FWD + "gdn/qkv_proj/btd,dhw->bthw/dot_general"],
        ["%fusion.2 = f", 60, 20, FWD + "gdn/conv/mul"],
        ["%fusion.3 = f", 80, 30, FWD + "gdn/gates/logistic"],
        ["%fusion.4 = f", 110, 100, FWD + "gdn/delta/...ij,...jk->...ik/dot"],
        ["%while.2 = while()", 210, 40, BWD + "gdn/delta/while"],
        ["%fusion.5 = f", 250, 30, REMAT + "gdn/out_norm/rsqrt"],
        ["%fusion.6 = f", 280, 40, BWD + "gdn/out_proj/transpose(jvp(x))/dot"],
        ["%fusion.7 = f", 320, 10, FWD + "gdn/out_proj/gdn_post_norm/mul"],
        ["%fusion.8 = f", 330, 50, FWD + "qkv/btd,dghk->btghk/dot_general"],
        ["%fusion.9 = f", 380, 90, FWD + "mlp/gate_up/btd,dgf->btgf/dot"],
        ["%fusion.10 = f", 470, 50, FWD + "mlp/down/btf,fd->btd/dot"],
        ["%fusion.11 = f", 520, 10, FWD + "mlp/down/mlp_post_norm/mul"],
        ["%fusion.12 = f", 530, 70, FWD + "attention/splash"],
        ["%fusion.13 = f", 600, 30, FWD + "attn_out/attn_post_norm/mul"],
        ["%fusion.14 = f", 630, 70, STEP + "jvp(head)/dot"],
        ["%fusion.15 = f", 700, 100, FWD + "agdn/delta/x"],   # not a scope
        ["%fusion.16 = f", 800, 100, FWD + "kda/delta/x"],    # another's
        ["%fusion.17 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_gdn_reduce_on_a_hand_made_trace():
    reduced = gdn_reduce.reduce_gdn(hand_made_trace())
    want = {"gdn/qkv_proj": 60, "gdn/conv": 20, "gdn/gates": 30,
            "gdn/delta": 140, "gdn/out_norm": 30, "gdn/out_proj": 40,
            "gdn_post_norm": 10}
    assert set(reduced) == set(want)
    for scope, t in want.items():
        assert abs(reduced[scope] - t * 1e-9) < 1e-15, scope
    # the loop it borrowed is handed back as it was
    assert kda_reduce.scope_of(FWD + "kda/delta/x") == "kda/delta"
    assert kda_reduce.reduce_kda(hand_made_trace()) == {
        "kda/delta": pytest.approx(100e-9)}
    # `gdn` is no bucket of scope_reduce's: its ops are under `layers`;
    # the norms on a sublayer's output are booked with the scope they
    # close
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert "gdn" not in scopes["bucket_s"]
    assert abs(scopes["bucket_s"]["mlp/down"] - 60e-9) < 1e-15
    assert abs(scopes["bucket_s"]["attn_out"] - 30e-9) < 1e-15
    assert gdn_reduce.reduce_gdn({"planes": []}) is None


@pytest.mark.parametrize("path,scope", [
    (FWD + "gdn/qkv_proj/btd,dhw->bthw/dot_general:", "gdn/qkv_proj"),
    (STEP + "transpose(jvp(gdn/delta))/mul", "gdn/delta"),
    (REMAT + "gdn/delta/while/body/checkpoint/mul", "gdn/delta"),
    (FWD + "gdn/out_proj/gdn_post_norm/mul", "gdn_post_norm"),
    (FWD + "gdn/out_proj/add", "gdn/out_proj"),
    (FWD + "agdn/delta/x", None),
    (FWD + "gdn/other/x", None),
    (FWD + "kda/delta/x", None),
    (FWD + "mlp/down/mlp_post_norm/mul", None),
])
def test_gdn_scope_of_a_path(path, scope):
    assert gdn_reduce.scope_of(path) == scope


def _record(**over):
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "delta_call": dict(CALL)},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(gdn_reduce, "_REDUCED", {})
    monkeypatch.setattr(kda_reduce, "_REDUCED", {})


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """A scratch directory with a trace file of `this run` whose content
    is the hand-made trace."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    _fresh(monkeypatch, hand_made_trace())
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_10_03"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["gdn_share"] - 33.0) < 1e-9
    assert abs(values["gdn_delta_share"] - 14.0) < 1e-9
    assert abs(values["gdn_proj_share"] - 19.0) < 1e-9
    least, bound = flops_gdn.delta_least_time_s(CALL, 1, PEAKS)
    assert values["gdn_delta_roofline"] == pytest.approx(
        100 * least / 140e-9)
    out = load_module("layer_metrics", "gdn_delta_roofline").roofline(
        _record())
    assert out["bound"] == bound == "memory"
    # the accepted readers read the same trace as they do elsewhere
    assert abs(load_module("layer_metrics", "mlp_share").read(
        _record()) - 15.0) < 1e-9
    assert abs(load_module("layer_metrics", "attn_proj_share").read(
        _record()) - 8.0) < 1e-9
    assert abs(load_module("layer_metrics", "kda_delta_share").read(
        _record()) - 10.0) < 1e-9


@pytest.mark.parametrize("name", NEW_METRICS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no `gdn` scope
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("gdn/", "kda/").replace(
                    "gdn_post_norm", "kda_norm")
    _fresh(monkeypatch, bare)
    assert read(_record()) is None
    # a record without the job's call
    _fresh(monkeypatch, hand_made_trace())
    if name == "gdn_delta_roofline":
        assert read(_record(static={"peaks": PEAKS})) is None
        assert read(_record(static={"delta_call": dict(CALL)})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    _fresh(monkeypatch, hand_made_trace())
    assert read(_record()) is None


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
    assert entry["source"] == ("https://huggingface.co/allenai/"
                               "Olmo-Hybrid-7B/blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # the accepted cells are in front of it, in their order
    names = [w["name"] for w in spec["workloads"]]
    accepted = ["train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                "train_olmoe_d1", "train_glm47flash_ep8_d5",
                "train_nemotron3super_ep64_d11", "train_phi4miniflash_d6",
                "train_ling3flash_ep64_d7", "train_sdar30b_ep8_d4",
                "train_mellum2_ep4_d4"]
    assert names[:9] == accepted and names.index(CELL) >= 9
    # two of its cells hold four chips: the quota of a quarter, one at
    # least
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(names) // 4)
    per_layer = [m["name"] for m in spec["per_layer"]]
    at = per_layer.index(NEW_METRICS[0])
    assert per_layer[at:at + 4] == NEW_METRICS
    assert at > per_layer.index("swa_attn_kernel_roofline")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
        assert (mine[name]["unit"], mine[name]["source"]) == \
            ("%", "device_trace")
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    assert (mine["gdn_delta_roofline"]["layer"],
            mine["gdn_delta_roofline"]["better"]) == ("kernels", "higher")
    assert {mine[n]["layer"] for n in NEW_METRICS[:3]} == {"model step"}
    assert {mine[n]["better"] for n in NEW_METRICS[:3]} == {"lower"}
    # the other cells' metrics keep their lists; the readers without one
    # apply here
    for name in ("moe_share", "kda_share", "kda_delta_roofline",
                 "ssm_share", "mamba1_share", "ep_moe_share",
                 "collective_exposed_share", "swa_attn_kernel_roofline"):
        assert name not in mine
        assert CELL not in by_name(spec["per_layer"], name,
                                   "metric")["workloads"]
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "peak_hbm_gb",
            "step_ms"} <= set(mine)
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the traffic file is Nemotron's and SDAR's, unedited
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}
    for other in ("train_nemotron3super_ep64_d11", "train_sdar30b_ep8_d4"):
        assert by_name(spec["workloads"], other,
                       "workload")["traffic"] == cell["traffic"]


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Olmo-Hybrid-7B"][0]


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
    # every width as published; layer_types kept whole
    assert (held["hidden_size"], held["intermediate_size"],
            held["linear_key_head_dim"], held["linear_value_head_dim"],
            held["linear_conv_kernel_dim"], held["head_dim"]) == \
        (3840, 11008, 96, 192, 4, 128)
    assert held["head_dim"] * catalog["num_attention_heads"] == \
        catalog["hidden_size"]
    assert held["layer_types"] == catalog["layer_types"] \
        and len(held["layer_types"]) == 32
    assert held["rope_parameters"] == {"rope_theta": None}
    # the cut: a whole period, heads 2 ways, an eighth of the vocabulary
    assert held["num_hidden_layers"] == 4
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert held[key] * 2 == catalog[key] == 30, key
    assert held["vocab_size"] * 8 == catalog["vocab_size"]
    share = held["share"]
    assert (share["chips_per_layer"], share["head_parallel"],
            share["vocab_parallel"], share["layer_offset"]) == (2, 2, 8, 0)
    assert set(share["departures"]) == {"qk_norm", "post_norm"}
    assert "28 layers" in held["stands_for"]
    assert "twice its share" in held["stands_for"]
    for key in ("norm_placement", "rope", "qk_norm", "head_dim", "mixer",
                "tie_word_embeddings", "initializer", "learning_rate",
                "gdn_chunk"):
        assert key in held["assumed"], key
    assert "TO BE SET" not in held["tolerance"]["why"]
    assert 0 < held["tolerance"]["logits_rel_l2"] < 0.1
    job = load_module("jobs", "train_lm_gdn")
    cfg = job.transformer_config(held, held["train"], 8192)
    assert cfg.num_params == flops_gdn.total_params(held) == 766_241_946
    assert (cfg.layer_pattern, cfg.pattern_runs) == \
        ("ddda", [("d", 3), ("a", 1)])
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.gdn_heads,
            cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv_kernel,
            cfg.gdn_chunk, cfg.ff_dim, cfg.vocab_size) == \
        (15, 15, 128, 15, 96, 192, 4, 64, 11008, 12544)
    assert cfg.qk_norm and not cfg.qk_norm_per_head and not cfg.rope
    assert cfg.gdn_neg_eigval and cfg.remat and cfg.norm_eps == 1e-6
    assert (held["train"]["expect_attention"],
            held["train"]["expect_delta_rule"]) == ("flash", "xla")
    ling = load_json(os.path.join(BENCH_DIR, "configs",
                                  "ling-3.0-flash-ep64-tp4-d7.json"))
    assert held["kernels"]["attn"] == ling["kernels"]["attn"]


@pytest.mark.parametrize("key,value,why", [
    ("hidden_act", "gelu", "SiLU-gated"),
    ("attention_bias", True, "bias"),
    ("tie_word_embeddings", True, "tied"),
    ("rope_parameters", {"rope_theta": 500000}, "rotary"),
    ("linear_num_key_heads", 5, "one key head a value head"),
    ("num_key_value_heads", 5, "one key/value head"),
    ("layer_types", ["sliding_attention"] * 4, "layer_types"),
])
def test_the_job_refuses_what_the_program_lacks(key, value, why):
    job = load_module("jobs", "train_lm_gdn")
    model = dict(load_json(CONFIG), **{key: value})
    with pytest.raises(ValueError, match=why):
        job.transformer_config(model, model["train"], 8192)
    with pytest.raises(ValueError, match=why):
        job.refuse_what_the_program_lacks(model)   # before the cluster


def test_the_job_refuses_a_longer_sequence_and_packed_documents(monkeypatch):
    job = load_module("jobs", "train_lm_gdn")
    model = load_json(CONFIG)
    with pytest.raises(ValueError, match="exceed the context"):
        job.transformer_config(model, model["train"], 65537)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    for key in ("packed_documents", "segment_masks"):
        with pytest.raises(ValueError, match=key):
            job.run({"config": model, "cell": {"name": CELL},
                     "traffic": {key: True}})
    assert not started


def test_the_job_refuses_a_program_without_the_fields(monkeypatch):
    """The parent's TransformerConfig: refused before the cluster starts
    (run.py then exits 1 in seconds)."""
    import dataclasses

    from ray_tpu.models import configs
    job = load_module("jobs", "train_lm_gdn")
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    with pytest.raises(RuntimeError, match="gdn_heads"):
        job.run({"config": load_json(CONFIG), "cell": {"name": CELL},
                 "traffic": {}})
    assert not started


def test_init_params_is_the_programs_but_for_the_stand_ins():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_gdn")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    assert cfg.layer_pattern == "ddda" and cfg.gdn_heads == 2
    key = jax.random.key(5900000011)
    mine = job.init_params(key, cfg, model["init"])
    theirs = Transformer.init(key, cfg)
    assert abs(float(mine["embed"].std()) - 1.0) < 0.02
    changed = {"embed", "gdn_A_log", "gdn_dt_bias"} | set(job.GAINS)
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: str(path[-1].key) in changed
        or bool(np.array_equal(a, b)), mine, theirs)
    assert all(jax.tree.leaves(same)), same
    a_lo, a_hi = model["init"]["gdn_A_range"]
    dt_lo, dt_hi = model["init"]["gdn_dt_bias_range"]
    seen = set()
    for run in mine["runs"]:
        for sub in run:
            seen |= set(sub)
            for name in job.GAINS:
                if name in sub:
                    gain = np.asarray(sub[name])
                    assert abs(gain.mean() - 1) < 0.2 and gain.std() > 0.1
            if "gdn_A_log" in sub:
                scale = np.exp(np.asarray(sub["gdn_A_log"]))
                assert a_lo <= scale.min() and scale.max() <= a_hi
                dt = np.asarray(sub["gdn_dt_bias"])
                assert dt_lo <= dt.min() and dt.max() <= dt_hi
    assert set(job.GAINS) <= seen


def test_the_stand_in_decay_spreads_and_beta_passes_one():
    """`log a` and beta of the stand-in weights at the published widths
    on a unit stream: the decay from a few hundredths to tens below zero
    a step (a decay of 1 hides a decay left out, a hard one tries the
    chunk's masked differences), half of beta above 1 (a factor 2 left
    out must show)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.kda import head_log_decay
    model = load_json(CONFIG)
    a_lo, a_hi = model["init"]["gdn_A_range"]
    dt_lo, dt_hi = model["init"]["gdn_dt_bias_range"]
    ks = jax.random.split(jax.random.key(3), 4)
    heads = 15
    # a stream of unit RMS through w_a and w_b at 1/sqrt(fan_in): N(0, 1)
    a = jax.random.normal(ks[0], (1, 2048, heads))
    a_log = jax.random.uniform(ks[1], (heads,), jnp.float32, np.log(a_lo),
                               np.log(a_hi))
    dt = jax.random.uniform(ks[2], (heads,), jnp.float32, dt_lo, dt_hi)
    g = np.asarray(head_log_decay(a, a_log, dt))
    assert g.max() < 0 and g.max() > -0.05 and g.min() < -10
    assert 0.2 < (g > -0.5).mean() < 0.8
    beta = 2 * np.asarray(jax.nn.sigmoid(
        jax.random.normal(ks[3], (1, 2048, heads))))
    assert 0.4 < (beta > 1).mean() < 0.6 and beta.max() > 1.8


def test_the_reference_layout_is_in_the_layers_order():
    import jax
    import numpy as np

    job = load_module("jobs", "train_lm_gdn")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 128)
    params = job.init_params(jax.random.key(1), cfg, model["init"])
    w = job.to_reference_layout(params, cfg)
    kinds = "".join("d" if "q_conv1d" in lw else "a" for lw in w["layers"])
    assert kinds == cfg.layer_pattern == "ddda"
    mixer = w["layers"][1]
    assert mixer["q_proj"].shape == (2 * 16, 64)
    assert mixer["v_proj"].shape == mixer["g_proj"].shape == (2 * 32, 64)
    assert mixer["k_conv1d"].shape == (2 * 16, 4)
    assert mixer["o_proj"].shape == (64, 2 * 32)
    # a head's q, k and v columns side by side in the program's leaf
    qkv = np.asarray(params["runs"][0][0]["w_gdn_qkv"][1])   # [d, H, 64]
    np.testing.assert_array_equal(
        mixer["k_proj"][16:32], qkv[:, 1, 16:32].T)
    np.testing.assert_array_equal(
        mixer["v_proj"][:32], qkv[:, 0, 32:].T)
    attention = w["layers"][3]
    assert attention["q_norm"].shape == (2 * 16,)
    assert set(attention) == {
        "q_proj", "k_proj", "v_proj", "q_norm", "k_norm", "o_proj",
        "post_attention_layernorm", "gate_proj", "up_proj", "down_proj",
        "post_feedforward_layernorm"}


def test_fault_reader_leaves_the_reference_plain():
    """`reference/olmo_hybrid_faults.py` breaks copies of the reference,
    outside it: every fault and every narrower precision moves the logits
    (float32 here: each is far over rounding), and the module the job
    compares with is untouched."""
    import inspect

    faults = load_module("reference", "olmo_hybrid_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.PRECISIONS)
    for name in faults.FAULTS:
        assert not rows[name]["rel_l2"] <= 1e-2, rows[name]
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "olmo_hybrid_f32")
    for name in ("linear", "gated_mlp", "forward", "gated_delta_net",
                 "full_attention", "delta_rule"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    for name in ("rms_norm", "l2_norm", "decay_gate", "beta_gate",
                 "short_conv", "qk_norm", "rotary", "out_gate", "sublayer",
                 "delta_rule"):
        assert getattr(plain, name).__module__ == plain.__name__


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-olmohybrid", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-olmohybrid.json",
        "reduced": ["num_attention_heads"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_olmohybrid", "config": "tiny-olmohybrid",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_olmohybrid")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_olmohybrid"]))
    path = tmp_path_factory.mktemp("olmohybrid_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_gdn_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_olmohybrid", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_gdn_delta_roofline" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
