"""What PR 63 adds to the benchmark for `train_ouro26b_d8`, checked
without a chip: `benchlib/flops_looped.py` against hand-worked numbers at
the published widths, `benchlib/loop_reduce.py` and the two new readers on
a hand-made trace (and on a program or a run that gives them nothing to
read), the spec's new entries BY NAME, never by position, the
configuration file against the catalog row key by key and its arithmetic,
what the job refuses, the stand-in weights, the fault reader, and the job
kind `train_lm_looped` rehearsed at a tiny size on the CPU (a rehearsal's
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

from benchlib import (flops, flops_looped, kda_reduce,  # noqa: E402
                      loop_reduce, scope_reduce)
from benchlib.spec import (by_name, load_json, load_module,  # noqa: E402
                           metrics_of)

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck_ouro", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)

CELL = "train_ouro26b_d8"
NAME = "ouro-2.6b-d8"
CONFIG = os.path.join(BENCH_DIR, "configs", NAME + ".json")
TINY = os.path.join(BENCH_DIR, "rehearsal", "configs", "tiny-ouro.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ["loop_carry_share", "loop_exit_share"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_looped_hand_worked():
    f, held = flops_looped, load_json(CONFIG)
    # q, k, v, o 4 x 2,048 x 2,048; gate, up, down 3 x 2,048 x 5,632
    assert flops.layer_matmul_params(held) == 4 * 4_194_304 \
        + 3 * 11_534_336 == 51_380_224
    # + the sandwich norm's four gains
    assert f.layer_params(held) == 51_380_224 + 4 * 2_048 == 51_388_416
    assert f.gate_params(held) == 2_049
    # eight layers, embedding and head, the final norm, the gate
    assert f.total_params(held) == 8 * 51_388_416 + 201_326_592 + 2_048 \
        + 2_049 == 411_107_328 + 201_330_689 == 612_438_017
    assert f.total_params(held) * 16 / 1e9 == pytest.approx(9.80, abs=5e-3)
    # at the published depth: 2.67B, counted ONCE for the four passes
    assert f.published_params(held) == 48 * 51_388_416 + 201_326_592 \
        + 4_097 == 2_667_974_657 == held["published_params"]
    # six layers, the size the issue falls back to: 8.15 GB
    assert f.total_params(dict(held, num_hidden_layers=6)) == 509_661_185
    # a token's matmul parameters in ONE pass: the layers', the head's
    # 2,048 x 49,152, the gate's gain
    assert f.matmul_params_per_pass(held) == 8 * 51_380_224 \
        + 100_663_296 + 2_048
    # attention over the causal pairs: 6 products of 16 heads x 8,192 x
    # 128 x 2 / 2 a token and layer
    assert flops.attention_train_flops_per_token(held, 8192) == \
        8 * 6 * 16 * 8192 * 128 == 8 * 100_663_296
    # everything runs once a PASS: four times what the parameters say
    total = f.train_flops_per_token(held, 8192)
    assert total == 4 * (6 * (8 * 51_380_224 + 100_663_296 + 2_048)
                         + 8 * 100_663_296) == 15_502_196_736
    assert total / flops.train_flops_per_token(held, 8192) == \
        pytest.approx(4.0, rel=1e-4)
    assert total * 8192 / 1e12 == pytest.approx(127.0, abs=0.1)
    assert f.attention_call(held, 1, 8192) == {
        "batch": 1, "heads": 16, "kv_heads": 16, "seq": 8192,
        "head_dim": 128}
    assert f.loop_call(held, 8192, "scan") == {
        "passes": 4, "layers": 8, "tokens": 8192, "form": "scan",
        "layer_applications": 32, "heads_a_step": 4}
    with pytest.raises(ValueError, match="untied"):
        f.total_params(dict(held, tie_word_embeddings=True))


# ---- the reducer and the readers ---------------------------------------

# an op's path as the compiled step has it (seen in the compiled text, PR
# 63): the passes' scan, its body a closed call, the layers' scan inside
STEP = "jit(_step)/"
PASS = "/while/body/closed_call/"
FWD = STEP + "jvp(loops)" + PASS + "layers/while/body/closed_call/" \
    "checkpoint/"
BWD = STEP + "transpose(jvp(loops))" + PASS + "layers/while/body/" \
    "closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 800, STEP + "jvp(loops)/while"],
        # the outer loop's own: what the passes save, the gradients' sum
        ["%fusion.1 = f", 0, 30, STEP + "jvp(loops)/while/body/"
                                        "dynamic_update_slice"],
        ["%fusion.2 = f", 30, 50, STEP + "transpose(jvp(loops))/while/body/"
                                         "add_any"],
        # the inner scan's own, and the layers: nested scopes
        ["%fusion.3 = f", 80, 40, STEP + "jvp(loops)" + PASS
                                  + "layers/while/body/dynamic_slice"],
        ["%fusion.4 = f", 120, 60, FWD + "qkv/btd,dghk->btghk/dot_general"],
        ["%fusion.5 = f", 180, 90, FWD + "mlp/gate_up/btd,dgf->btgf/dot"],
        ["%fusion.6 = f", 270, 50, REMAT + "mlp/down/btf,fd->btd/dot"],
        ["%fusion.7 = f", 320, 10, FWD + "mlp/down/mlp_post_norm/mul"],
        ["%fusion.8 = f", 330, 20, FWD + "attn_out/attn_post_norm/mul"],
        ["%fusion.9 = f", 350, 70, BWD + "attention/splash"],
        ["%fusion.10 = f", 420, 20, STEP + "jvp(loops)" + PASS
                                    + "final_norm/mul"],
        # the gate and the exit loss
        ["%fusion.11 = f", 800, 15, STEP + "jvp(loop/exit_gate)/reduce_sum"],
        ["%fusion.12 = f", 815, 25, STEP + "transpose(jvp(loop/exit_loss))/"
                                           "mul"],
        ["%fusion.13 = f", 840, 10, STEP + "jvp(loop/exit_loss)/"
                                           "log_sigmoid/log1p"],
        ["%fusion.14 = f", 850, 50, STEP + "jvp(head)/dot"],
        ["%fusion.15 = f", 900, 20, STEP + "jvp(loss)/reduce"],
        ["%fusion.16 = f", 920, 30, STEP + "aloop/exit_loss/x"],  # no scope
        ["%fusion.17 = f", 950, 50, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_loop_reduce_on_a_hand_made_trace():
    reduced = loop_reduce.reduce_loop(hand_made_trace())
    # `loops`: the while's own self time (800 less its 440 of children)
    # and the two ops under no nested scope
    want = {"loops": 360 + 30 + 50, "loop/exit_gate": 15,
            "loop/exit_loss": 35}
    assert set(reduced) == set(want)
    for scope, t in want.items():
        assert abs(reduced[scope] - t * 1e-9) < 1e-15, scope
    # the loop it borrowed is handed back as it was
    assert kda_reduce.scope_of(STEP + "kda/delta/x") == "kda/delta"
    assert kda_reduce.reduce_kda(hand_made_trace()) == {}
    # no new name is a bucket of scope_reduce's: the loop's own ops and
    # the exit's are `unscoped` there, the nested scopes keep theirs
    scopes = scope_reduce.reduce_scopes(hand_made_trace())
    assert not {"loops", "loop"} & set(scopes["bucket_s"])
    assert abs(scopes["bucket_s"]["layers"] - 40e-9) < 1e-15
    assert abs(scopes["bucket_s"]["final_norm"] - 20e-9) < 1e-15
    assert abs(scopes["bucket_s"]["mlp/down"] - 60e-9) < 1e-15
    assert abs(scopes["bucket_s"]["unscoped"] - (440 + 50 + 30) * 1e-9) \
        < 1e-15
    assert loop_reduce.reduce_loop({"planes": []}) is None


@pytest.mark.parametrize("path,scope", [
    (STEP + "jvp(loops)/while/body/dynamic_update_slice:", "loops"),
    (STEP + "transpose(jvp(loops))/while/body/add_any", "loops"),
    (STEP + "jvp(loops)/while", "loops"),
    (FWD + "mlp/gate_up/dot", None),
    (STEP + "jvp(loops)" + PASS + "layers/while/body/dynamic_slice", None),
    (STEP + "jvp(loops)" + PASS + "final_norm/mul", None),
    (STEP + "transpose(jvp(loops))" + PASS + "add_any", "loops"),
    (STEP + "jvp(loop/exit_gate)/reduce_sum", "loop/exit_gate"),
    (STEP + "transpose(jvp(loop/exit_loss))/mul", "loop/exit_loss"),
    (STEP + "aloop/exit_loss/x", None),
    (STEP + "loop/other/x", None),
    (STEP + "jvp(layers)/while/body/mlp/down/dot", None),
    (STEP + "myloops/x", None),
])
def test_loop_scope_of_a_path(path, scope):
    assert loop_reduce.scope_of(path) == scope


def _record(**over):
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS},
    }
    record.update(over)
    return record


def _fresh(monkeypatch, trace):
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(loop_reduce, "_REDUCED", {})


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
    assert abs(values["loop_carry_share"] - 44.0) < 1e-9
    assert abs(values["loop_exit_share"] - 5.0) < 1e-9
    # the accepted readers read the same trace as they do elsewhere
    assert abs(load_module("layer_metrics", "mlp_share").read(
        _record()) - 15.0) < 1e-9
    assert abs(load_module("layer_metrics", "head_share").read(
        _record()) - 7.0) < 1e-9      # `head` and `loss`
    assert abs(load_module("layer_metrics", "unscoped_share").read(
        _record()) - 52.0) < 1e-9
    assert abs(load_module("layer_metrics", "recompute_share").read(
        _record()) - 5.0) < 1e-9


@pytest.mark.parametrize("name", NEW_METRICS)
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # the parent's program on one of its own cells: no `loops` scope
    bare = hand_made_trace()
    for line in bare["planes"][0]["lines"]:
        for e in line["events"]:
            if len(e) == 4:
                e[3] = e[3].replace("jvp(loops)" + PASS, "jvp(").replace(
                    "layers/while", "layers)/while").replace(
                    "loops", "layers").replace("loop/exit_", "loss/")
    _fresh(monkeypatch, bare)
    assert read(_record()) is None
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
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/ByteDance/"
                               "Ouro-2.6B/blob/main/config.json")
    assert len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/" + NAME + ".json"
    # the accepted cells are still there, each with its configuration
    names = [w["name"] for w in spec["workloads"]]
    for accepted in ("train_mistral7b_d2", "train_mistral7b_d8_fsdp4",
                     "train_olmoe_d1", "train_glm47flash_ep8_d5",
                     "train_nemotron3super_ep64_d11",
                     "train_phi4miniflash_d6", "train_ling3flash_ep64_d7",
                     "train_sdar30b_ep8_d4", "train_mellum2_ep4_d4",
                     "train_olmohybrid7b_tp2_d4"):
        assert names.index(accepted) < names.index(CELL)
    # both four-chip places are taken: this cell holds one chip
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(names) // 4)
    per_layer = [m["name"] for m in spec["per_layer"]]
    for name in NEW_METRICS:
        assert per_layer.index(name) > per_layer.index("gdn_delta_roofline")
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name] == {
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "train_tokens_per_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    # the other cells' metrics keep their lists; the readers without one
    # apply here: 24 of them
    for name in ("moe_share", "kda_share", "gdn_share", "ssm_share",
                 "mamba1_share", "ep_moe_share", "diffusion_stream_share",
                 "collective_exposed_share", "swa_attn_kernel_roofline"):
        assert name not in mine
        assert CELL not in by_name(spec["per_layer"], name,
                                   "metric")["workloads"]
    unlisted = {m["name"] for m in spec["per_layer"]
                if "workloads" not in m and m["moves"] in (
                    "train_tokens_per_s", "setup_s")}
    assert unlisted <= set(mine) and len(mine) == len(unlisted) + 2
    assert {"model_flops_util", "attn_kernel_roofline", "attn_kernel_share",
            "attn_glue_share", "head_share", "mlp_share", "attn_proj_share",
            "optimizer_share", "recompute_share", "unscoped_share",
            "peak_hbm_gb", "step_ms"} <= set(mine)
    # no kernel is added: no roofline of this PR's
    assert not [n for n in NEW_METRICS if n.endswith("_roofline")]
    e2e = {m["name"] for m in metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the traffic file is Nemotron's, SDAR's and Olmo-Hybrid's, unedited
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    assert (mix["sequences_per_step"], mix["tokens_per_sequence"]) == \
        (1, 8192)
    assert mix["reference_sample"] == {"sequences": 1, "tokens": 8192}


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    held = load_json(CONFIG)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "num_hidden_layers":
                assert (value, held[key]) == (48, 8)
            else:
                assert held[key] == value, key
    assert list(held["reduced"]) == ["num_hidden_layers"]
    assert held["reduced"]["num_hidden_layers"]["published"] == 48
    # no width is cut, no pass, no row of the vocabulary
    assert (held["hidden_size"], held["intermediate_size"],
            held["head_dim"], held["num_attention_heads"],
            held["vocab_size"], held["total_ut_steps"]) == \
        (2048, 5632, 128, 16, 49152, 4)
    assert len(held["layer_types"]) == 48
    assert (held["job"], held["reference"]) == ("train_lm_looped",
                                                "ouro_f32")
    for key in ("sandwich_norm", "final_norm_inside_the_pass", "exit_gate",
                "objective", "early_exit_threshold", "initializer"):
        assert held["assumed"][key], key
    assert held["exit_entropy_coeff"] == 0.05
    tol = held["tolerance"]
    assert set(tol) == {"logits_rel_l2", "gate_z_rel_l2", "exit_prob_abs",
                        "loss_abs", "objective_abs", "gate_grad_rel_l2",
                        "why"}
    # each limit between its two readings (the `why` has them)
    assert 0.034 < tol["logits_rel_l2"] < 0.329
    assert 0.04 < tol["gate_z_rel_l2"] < 2.7
    assert 0.019 < tol["exit_prob_abs"] < 0.59
    assert 0.0031 < tol["loss_abs"] and tol["objective_abs"] < 0.0075
    train = held["train"]
    assert (train["param_dtype"], train["compute_dtype"], train["remat"],
            train["loss_chunk"], train["expect_attention"]) == \
        ("float32", "bfloat16", True, 256, "flash")
    mistral = load_json(os.path.join(BENCH_DIR, "configs",
                                     "mistral-7b-v0.1-d2.json"))
    assert held["kernels"] == mistral["kernels"]
    assert train["optimizer"] == mistral["train"]["optimizer"]


def test_the_job_maps_the_row_onto_the_program():
    from ray_tpu.models.configs import TransformerConfig

    job = load_module("jobs", "train_lm_looped")
    held = load_json(CONFIG)
    cfg = job.transformer_config(held, held["train"], 8192)
    assert isinstance(cfg, TransformerConfig)
    assert (cfg.loops, cfg.exit_gate, cfg.exit_entropy_coeff,
            cfg.norm_placement) == (4, True, 0.05, "both")
    assert (cfg.n_layers, cfg.d_model, cfg.ff_dim, cfg.n_heads,
            cfg.kv_heads, cfg.head_dim, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_eps, cfg.tie_embeddings) == \
        (8, 2048, 5632, 16, 16, 128, 49152, 1e6, 1e-6, False)
    assert cfg.num_params == flops_looped.total_params(held) == 612_438_017
    assert cfg.replace(n_layers=48).num_params == held["published_params"]
    with pytest.raises(ValueError, match="exceed the context"):
        job.transformer_config(held, held["train"], 65537)
    for key, value, said in (
            ("hidden_act", "gelu", "SiLU"),
            ("tie_word_embeddings", True, "tied"),
            ("use_sliding_window", True, "window"),
            ("num_key_value_heads", 4, "key/value head"),
            ("total_ut_steps", 1, "twice or more"),
            ("layer_types", ["sliding_attention"], "layer_types")):
        with pytest.raises(ValueError, match=said):
            job.refuse_what_the_program_lacks(dict(held, **{key: value}))


def test_the_job_refuses_before_any_process_starts(monkeypatch):
    import dataclasses

    from ray_tpu.models import configs

    job = load_module("jobs", "train_lm_looped")
    held = load_json(CONFIG)
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_1seq.json"))
    ctx = {"cell": {"name": CELL}, "config": held, "traffic": mix}
    started = []
    monkeypatch.setattr(job._train_lm, "run", started.append)
    for key in ("packed_documents", "segment_masks"):
        with pytest.raises(ValueError, match=key):
            job.run(dict(ctx, traffic=dict(mix, **{key: True})))
    # a program without the new fields (the parent of PR 63)
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            configs.TransformerConfig) if f.name not in job.NEEDS])
    monkeypatch.setattr(configs, "TransformerConfig", old)
    with pytest.raises(RuntimeError, match="TransformerConfig has no"):
        job.run(ctx)
    assert not started


def test_the_stand_in_weights_and_the_reference_layout():
    import jax
    import numpy as np

    job = load_module("jobs", "train_lm_looped")
    model = load_json(TINY)
    cfg = job.transformer_config(model, model["train"], 64)
    params = job.init_params(jax.random.key(3), cfg, model["init"])
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        flops_looped.total_params(model) == cfg.num_params \
        == model["published_params"] - 2 * flops_looped.layer_params(model)
    assert float(np.std(np.asarray(params["embed"]))) == \
        pytest.approx(1.0, abs=0.05)
    for name in job.GAINS:   # off 1: a norm left out or moved shows
        gain = np.asarray(params["layers"][name])
        assert 0.2 < gain.std() < 0.4 and abs(gain.mean() - 1) < 0.1, name
    assert 0.15 < np.asarray(params["final_norm"]).std() < 0.45
    # z spreads with about gate_z_std over a normed hidden state
    gate = np.asarray(params["exit_gate"])
    assert float(np.sqrt((gate ** 2).sum())) == pytest.approx(1.0, abs=0.3)
    assert float(params["exit_gate_bias"][0]) == pytest.approx(-0.6)
    w = job.to_reference_layout(params, cfg)
    assert len(w["layers"]) == 2 and set(w["layers"][1]) == {
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
        "post_attention_layernorm_2", "q_proj", "k_proj", "v_proj",
        "o_proj", "gate_proj", "up_proj", "down_proj"}
    np.testing.assert_array_equal(
        w["layers"][1]["input_layernorm_2"],
        np.asarray(params["layers"]["attn_post_norm"][1]))
    np.testing.assert_array_equal(
        w["layers"][0]["post_attention_layernorm_2"],
        np.asarray(params["layers"]["mlp_post_norm"][0]))
    assert w["early_exit_gate"]["weight"].shape == (1, 64)
    assert w["early_exit_gate"]["bias"].shape == (1,)


def test_a_step_is_sound_only_with_a_distribution():
    job = load_module("jobs", "train_lm_looped")
    step = {"loss": 7.1, "exit_entropy": 1.2,
            "exit_mass": [0.3, 0.2, 0.2, 0.3], "pass_nll": [8.0] * 4}
    assert job.step_is_sound(step)
    assert not job.step_is_sound(dict(step, loss=float("nan")))
    assert not job.step_is_sound(dict(step, pass_nll=[8.0, float("inf")]))
    assert not job.step_is_sound(dict(step,
                                      exit_mass=[0.3, 0.2, 0.2, 0.3002]))


def test_fault_reader_leaves_the_reference_plain():
    """`reference/ouro_faults.py` breaks copies of the reference, outside
    it: every fault fails the limit it is listed under (float32 here:
    each is far over rounding), and the module the job compares with is
    untouched."""
    import inspect

    faults = load_module("reference", "ouro_faults")
    model = load_json(TINY)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["variant"]: r for r in faults.read(model, mix, 7)}
    assert list(rows) == list(faults.FAULTS + faults.EQUIVALENT
                              + faults.PRECISIONS)
    for name in faults.BY_LOGITS:
        assert "logits_rel_l2" in rows[name]["fails"], rows[name]
    for name in faults.BY_EXIT:
        assert set(rows[name]["fails"]) & {
            "gate_z_rel_l2", "exit_prob_abs"}, rows[name]
    for name in faults.BY_OBJECTIVE:
        assert "objective_abs" in rows[name]["fails"], rows[name]
    assert rows["stopped_weights"]["fails"] == ["gate_grad_rel_l2"]
    assert rows["positions_run_on"]["correct"] is True
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    plain = load_module("reference", "ouro_f32")
    for name in ("linear", "gated_mlp", "forward", "attention", "passes",
                 "logits_of", "exit_gate"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name
    for name in ("rms_norm", "linear", "sublayer", "close_pass",
                 "exit_gate", "exit_distribution", "exit_loss",
                 "pass_positions"):
        assert getattr(plain, name).__module__ == plain.__name__
    source = inspect.getsource(plain)
    assert "ray_tpu" not in source.replace("`ray_tpu`", "")
    assert "lax.scan" not in source and "pallas" not in source


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """A new rehearsal spec: BENCHMARK.rehearsal.json's entries with the
    new job kind's configuration, cell and metrics appended; the
    rehearsal files that are there are not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-ouro", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-ouro.json",
        "reduced": ["num_hidden_layers"], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_ouro", "config": "tiny-ouro",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_ouro")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_ouro"]))
    path = tmp_path_factory.mktemp("ouro_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_looped_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_ouro", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True and line["failed"] == 0
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        assert "rehearsal_step_ms" in line["metrics"]
        assert "rehearsal_model_flops_util" not in line["metrics"]
        assert "rehearsal_loop_carry_share" not in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
