"""What PR 27 adds to the benchmark for `train_olmoe_d1`, checked without
a chip: `benchlib/flops_moe.py` against hand-worked numbers at the
published widths, `benchlib/moe_reduce.py` and the five new readers on a
hand-made trace (and on a program or a run that gives them nothing to
read), the spec's new entries, and the job kind `train_lm_moe` rehearsed
at a tiny size on the CPU (a rehearsal's numbers carry the `rehearsal_`
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

from benchlib import flops, flops_moe, moe_reduce, scope_reduce  # noqa: E402
from benchlib.spec import load_json, load_module, metrics_of  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "_benchmark_selfcheck", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)



def load_module_file(name):
    """A sibling test module, by file path (tests/ is no package)."""
    spec = importlib.util.spec_from_file_location(
        "_sibling_" + name, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


CELL = "train_olmoe_d1"
CONFIG = os.path.join(BENCH_DIR, "configs", "olmoe-1b-7b-0125-d1.json")
NEW_METRICS = ["moe_share", "moe_experts_share", "moe_route_share",
               "moe_experts_roofline", "expert_load_max_over_mean"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- arithmetic --------------------------------------------------------


def test_flops_moe_hand_worked():
    """OLMoE-1B-7B's published widths worked by hand (ISSUE 27)."""
    cfg = load_json(CONFIG)
    attn = 4 * 2048 * 2048                      # q, k, v, o: 16 x 128 heads
    assert attn == 16_777_216 == flops_moe.attention_params(cfg)
    expert = 3 * 2048 * 1024                    # gate, up, down
    assert expert == 6_291_456 == flops_moe.expert_params(cfg)
    router = 2048 * 64
    # + the two QK-norm gains over 2048 each, the two norm gains
    layer = attn + 2 * 2048 + 64 * expert + router + 2 * 2048
    assert layer == 419_569_664 == flops_moe.layer_params(cfg)
    assert 64 * expert == 402_653_184
    embed_and_head = 2 * 50304 * 2048
    assert embed_and_head == 206_045_184
    assert flops_moe.total_params(cfg) == \
        layer + embed_and_head + 2048 == 625_616_896
    deep = dict(cfg, num_hidden_layers=16)      # the published depth
    assert flops_moe.total_params(deep) == \
        16 * layer + embed_and_head + 2048 == 6_919_161_856
    # what one token passes: attention, router, 8 of the 64 experts, head
    per_token = attn + router + 8 * expert + 50304 * 2048
    assert per_token == 170_262_528 == \
        flops_moe.matmul_params_per_token(cfg)
    assert round(100 * 50304 * 2048 / per_token) == 61     # the head
    assert round(100 * 8 * expert / per_token) == 30       # the experts
    attn_flops = 6 * 2 * 4096 * 128 * 16 // 2   # causal, 6 matmul passes
    assert flops_moe.train_flops_per_token(cfg, 4096) == \
        6 * per_token + attn_flops == 1_071_906_816
    # the same functions give the dense numbers where there is one
    # expert and every token takes it
    assert flops.attention_train_flops_per_token(cfg, 4096) == attn_flops


def test_grouped_matmul_roofline_hand_worked():
    cfg = load_json(CONFIG)
    rows = 16384 * 8                             # token-slots a step
    gate_up = 2 * rows * 2048 * 2048
    down = 2 * rows * 1024 * 2048
    assert flops_moe.grouped_matmul_flops(rows, 2048, 2048) == gate_up
    assert flops_moe.grouped_matmul_flops(rows, 1024, 2048) == down
    # bf16: both row-sided operands and all 64 experts' matrices once
    assert flops_moe.grouped_matmul_bytes(rows, 2048, 2048, 64) == \
        2 * (2 * rows * 2048 + 64 * 2048 * 2048)
    # forward, remat's forward, d lhs, d rhs: 4 passes of each matmul
    calls = flops_moe.expert_calls_per_step(cfg, 16384, remat=True)
    assert calls == [("gate_up", 2048, 2048, 4), ("down", 1024, 2048, 4)]
    assert [c[3] for c in flops_moe.expert_calls_per_step(
        cfg, 16384, remat=False)] == [3, 3]
    least, bound = flops_moe.experts_least_time_s(cfg, 16384, True, PEAKS)
    assert bound == "compute"
    assert abs(least - 4 * (gate_up + down) / 197e12) < 1e-12
    assert 0.033 < least < 0.034
    # memory-bound where an expert sees few rows: 64 slots, 1 per expert
    t, which = flops.least_time_s(
        flops_moe.grouped_matmul_flops(64, 2048, 2048),
        flops_moe.grouped_matmul_bytes(64, 2048, 2048, 64), PEAKS)
    assert which == "memory"


# ---- the sub-scope reduction and the readers ---------------------------

STEP = "jit(_step)/"
FWD = STEP + "jvp(layers)/while/body/closed_call/checkpoint/"
BWD = STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def hand_made_trace():
    """One chip, one window of 1000 ns; op, start, duration, path."""
    ops = [
        ["%while.1 = while()", 0, 900, STEP + "jvp(layers)/while"],
        ["%fusion.1 = f", 0, 50, FWD + "moe/router/nd,de->ne/dot_general"],
        ["%sort.1 = s", 50, 30, FWD + "moe/dispatch/sort"],
        ["%gather.1 = g", 80, 70, FWD + "moe/dispatch/gather"],
        ["%gmm.1 = custom-call()", 150, 200, FWD + "moe/experts/gmm"],
        ["%fusion.2 = f", 350, 40, FWD + "moe/experts/mul"],
        ["%gather.2 = g", 390, 60, FWD + "moe/combine/gather"],
        ["%tgmm.1 = custom-call()", 450, 150,
         BWD + "moe/experts/transpose(jvp(gmm))"],
        ["%gmm.2 = custom-call()", 600, 100, REMAT + "moe/experts/gmm"],
        ["%fusion.3 = f", 700, 100, FWD + "attention/flash"],
        ["%fusion.4 = f", 800, 50, FWD + "moe/add"],       # no sub-scope
        ["%fusion.5 = f", 900, 100, STEP + "optimizer/adamw"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__step(1)", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1000]]}]}]}


def test_moe_reduce_on_a_hand_made_trace():
    trace = hand_made_trace()
    out = moe_reduce.reduce_moe(trace)
    ns = 1e-9
    want = {"router": 50, "dispatch": 100, "experts": 490, "combine": 60,
            "other": 50}
    assert set(out["sub_s"]) == set(want)
    for sub, t in want.items():
        assert abs(out["sub_s"][sub] - t * ns) < 1e-15, sub
    # the sub-scopes tile what scope_reduce books under `moe`
    scopes = scope_reduce.reduce_scopes(trace)
    assert abs(scopes["bucket_s"]["moe"] - out["moe_s"]) < 1e-15
    assert abs(out["moe_s"] - 750 * ns) < 1e-15
    assert abs(scopes["busy_s"] - 1000 * ns) < 1e-15


@pytest.mark.parametrize("path,sub", [
    (FWD + "moe/router/nd,de->ne/dot_general:", "router"),
    (BWD + "moe/combine/transpose(jvp(nkd,nk->nd))/mul", "combine"),
    (REMAT + "moe/experts/gmm", "experts"),
    (STEP + "transpose(jvp(moe/dispatch))/gather", "dispatch"),
    (FWD + "moe/add", "other"),
    (FWD + "remoe/experts/x", "other"),
])
def test_subscope_of_a_path(path, sub):
    assert moe_reduce.subscope_of(path) == sub


def _record(**over):
    cfg = load_json(CONFIG)
    record = {
        "window_started_at": time.time() - 60,
        "trace": {"devices": 1, "modules_per_device": 1},
        "static": {"peaks": PEAKS, "experts_call": {
            "model": {k: cfg[k] for k in (
                "hidden_size", "intermediate_size", "num_experts",
                "num_experts_per_tok", "num_hidden_layers")},
            "tokens": 16384, "remat": True}},
        "counters": {"expert_load_max_over_mean": [2.0, 3.5, 2.5, 9.0]},
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
    monkeypatch.setattr(moe_reduce, "_REDUCED", {})
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: trace)
    run = tmp_path / CELL / "trace" / "plugins" / "profile" / "2026_09_27"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


def test_readers_on_the_hand_made_trace(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in NEW_METRICS}
    assert abs(values["moe_share"] - 75.0) < 1e-9
    assert abs(values["moe_experts_share"] - 49.0) < 1e-9
    assert abs(values["moe_route_share"] - 21.0) < 1e-9
    assert values["expert_load_max_over_mean"] == 3.0      # the median
    # least time of one step's calls over the 490 ns under moe/experts
    least, _ = flops_moe.experts_least_time_s(
        load_json(CONFIG), 16384, True, PEAKS)
    assert abs(values["moe_experts_roofline"]
               - 100 * least / 490e-9) < 1e-3 * values["moe_experts_roofline"]
    out = load_module("layer_metrics", "moe_experts_roofline").roofline(
        _record())
    assert out["bound"] == "compute"


@pytest.mark.parametrize("name", NEW_METRICS[:4])
def test_trace_reader_with_nothing_to_read(scratch, monkeypatch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # a dense model of a program with scopes: the `moe` bucket is empty
    # (`moe_share` reads 0 as `mlp_share` does in this cell); then a
    # program with no scopes at all (every reader returns None)
    for old, new, moe_share in (("moe/", "mlp/", 0.0), ("jit", "", None)):
        bare = hand_made_trace()
        for line in bare["planes"][0]["lines"]:
            for e in line["events"]:
                if len(e) == 4:
                    e[3] = e[3].replace(old, new) if old != "jit" else ""
        monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: bare)
        monkeypatch.setattr(scope_reduce, "_REDUCED", {})
        monkeypatch.setattr(moe_reduce, "_REDUCED", {})
        assert read(_record()) == (moe_share if name == "moe_share"
                                   else None)
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    assert read(_record()) is None


def test_counter_reader_with_nothing_to_read():
    read = load_module("layer_metrics", "expert_load_max_over_mean").read
    assert read({}) is None
    assert read({"counters": {}}) is None
    assert read({"counters": {"expert_load_max_over_mean": []}}) is None


# ---- the spec ----------------------------------------------------------


def test_spec_entries_of_the_cell():
    selfcheck.check_spec_contract()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-0125-d1", "sft_4k", 1)
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].endswith("/config.json")
    # appended after the accepted entries, which keep their order (the
    # equality in test_scope_reduce.py pins the list to PR 24's and fails
    # from the first PR that adds a metric: a `benchmark` issue's to relax)
    accepted = load_module_file("test_scope_reduce")
    names = [m["name"] for m in spec["per_layer"]]
    before = (accepted.BEFORE_PR_24 + accepted.TRACE_READERS
              + accepted.GANG_READERS)
    assert names[:len(before) + 5] == before + NEW_METRICS
    assert spec["workloads"][-1]["name"] == CELL
    mine = {m["name"]: m for m in metrics_of(spec, "per_layer", CELL)}
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL], name
        assert mine[name]["moves"] == "train_tokens_per_s"
    assert mine["moe_experts_roofline"]["unit"] == "%"
    # the accepted readers without a `workloads` list apply here too
    assert {"model_flops_util", "attn_kernel_roofline", "head_share",
            "mlp_share", "peak_hbm_gb"} <= set(mine)
    # the catalog row's numbers, every key at top level, depth alone cut
    held = load_json(CONFIG)
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    differ = sorted(k for k, v in catalog.items() if held.get(k, "") != v)
    assert differ == ["num_hidden_layers"] == sorted(held["reduced"])
    assert held["reduced"]["num_hidden_layers"]["published"] == 16


# ---- what the job's checks are made of ---------------------------------


@pytest.mark.parametrize("counts,load,empty,spread", [
    ([[16] * 8], 1.0, 0, True),                     # uniform
    ([[40, 30, 20, 10, 10, 10, 4, 4]], 2.5, 0, True),
    ([[64, 64, 0, 0, 0, 0, 0, 0]], 4.0, 6, False),  # every token, same 2
    ([[64, 10, 10, 10, 10, 8, 8, 8]], 4.0, 0, False),  # one takes them all
    ([[30, 30, 30, 38, 0, 0, 0, 0, ]], 2.375, 4, True),
    ([[40, 40, 48, 0, 0, 0, 0, 0]], 3.0, 5, False),  # most get none
    ([[16] * 8, [64, 64, 0, 0, 0, 0, 0, 0]], 4.0, 6, False),  # any layer
])
def test_routing_load(counts, load, empty, spread):
    import numpy as np
    job = load_module("jobs", "train_lm_moe")
    out = job.routing_load(np.asarray(counts), top_k=2)
    assert out == {"max_over_mean": load, "empty_experts": empty,
                   "spread": spread}


def test_kernel_calls_by_pattern():
    from benchlib import checks as job   # both jobs count by pattern there
    call = ' = bf16[8,8]{1,0} custom-call(%p), custom_call_target=' \
        '"tpu_custom_call", metadata={op_name="jit(f)/moe/experts/gmm"}'
    hlo = "\n".join(
        f"  %{name}{call}" for name in (
            "gmm", "gmm.1", "gmm.12", "tgmm", "tgmm.3", "flash_attention",
            "flash_mha_bwd_dkv.2", "agmm")) \
        + '\n  %gmm.5 = bf16[8]{0} fusion(%p), kind=kLoop\n'
    patterns = load_json(CONFIG)["kernels"]
    assert job.kernel_calls(hlo, patterns["moe"]) == {"gmm": 3, "tgmm": 2}
    assert job.kernel_calls(hlo, patterns["attn"]) == {
        "fwd": 1, "bwd_dkv": 1, "bwd_dq": 0}
    assert job.kernel_calls(hlo, {}) == {}


def _mid_size(**over):
    """The cell's configuration at a width the CPU takes in seconds: 64
    experts, top-8, the published vocabulary."""
    model = dict(load_json(CONFIG), hidden_size=256, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=64)
    model["train"] = dict(model["train"], compute_dtype="float32",
                          remat=False, **over)
    return model


def test_init_params_is_the_programs_but_for_the_embedding():
    import jax
    import numpy as np

    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_moe")
    model = load_json(os.path.join(
        BENCH_DIR, "rehearsal", "configs", "tiny-olmoe.json"))
    cfg = job.transformer_config(model, model["train"], 128)
    key = jax.random.key(2700000011)
    mine = job.init_params(key, cfg, {"embed_std": 1.0})
    theirs = Transformer.init(key, cfg)
    assert abs(float(mine["embed"].std()) - 1.0) < 0.02
    assert mine["embed"].dtype == theirs["embed"].dtype
    assert abs(float(theirs["embed"].std()) - 0.02) < 0.001
    mine.pop("embed"), theirs.pop("embed")
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        mine, theirs)
    assert all(jax.tree.leaves(same)), same


@pytest.mark.parametrize("seed", [3, 2700000011])
def test_the_stand_in_weights_spread_the_routing(seed):
    """Step-0 routing of a 1,024-token sample: at the configuration's
    embedding scale the Zipf tokens spread over all 64 experts, unevenly;
    at the program's own 0.02 the router reads the context's mean and
    most token-slots take the same 8 (on the chip, in bf16 at 4,096
    tokens: all of them, PERF.md section 6, PR 27)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib.traffic import TokenBatches
    from ray_tpu.models import Transformer
    job = load_module("jobs", "train_lm_moe")
    model = _mid_size()
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_4k.json"))
    batches = TokenBatches(mix, model["vocab_size"], seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    sample = jnp.asarray(batches.reference_sample(1, 1024))

    def load(embed_std):
        params = jax.jit(lambda k: job.init_params(
            k, cfg, {"embed_std": embed_std}))(jax.random.key(seed))
        _, metrics = jax.jit(lambda p, b: Transformer.loss(
            p, b, cfg, with_metrics=True))(params, {"tokens": sample})
        return job.routing_load(
            np.asarray(metrics["moe_tokens_per_expert"]), cfg.moe_top_k)

    spread = load(model["init"]["embed_std"])
    assert spread["spread"] and spread["empty_experts"] == 0
    assert 1.5 <= spread["max_over_mean"] <= 4.0       # ISSUE 27's range
    assert load(0.02)["max_over_mean"] > 6.0


def test_precision_reading_leaves_the_reference_plain():
    """`reference/olmoe_precision.py` rounds outside the reference: the
    narrower the operands, the further from the float32 reading, and the
    module the job compares with is untouched."""
    import inspect

    precision = load_module("reference", "olmoe_precision")
    model = load_json(os.path.join(
        BENCH_DIR, "rehearsal", "configs", "tiny-olmoe.json"))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "rehearsal_tiny.json"))
    rows = {r["operands"]: r for r in precision.read(model, mix, 7)}
    assert list(rows) == list(precision.PRECISIONS)
    assert 0 < rows["bfloat16"]["rel_l2"] < 0.01 \
        < rows["float8_e4m3fn"]["rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    assert rows["bfloat16"]["correct"]
    plain = load_module("reference", "olmoe_f32")
    for name in ("linear", "expert_mlp", "sparse_moe", "forward"):
        assert not [p for p in inspect.signature(
            getattr(plain, name)).parameters if "dtype" in p], name


# ---- the job kind, rehearsed on the CPU --------------------------------


@pytest.fixture(scope="module")
def rehearsal_spec(tmp_path_factory):
    """The rehearsal spec with the new job kind's cell appended, as a later
    PR's move is rehearsed in `selfcheck.check_new_files_are_found`: new
    entries only, BENCHMARK.rehearsal.json itself is not edited."""
    spec = load_json(selfcheck.REHEARSAL_SPEC)
    spec["configs"].append({
        "name": "tiny-olmoe", "source": "none",
        "file": "benchmark/rehearsal/configs/tiny-olmoe.json",
        "reduced": [], "why": "rehearsal"})
    spec["workloads"].append({
        "name": "rehearse_train_olmoe", "config": "tiny-olmoe",
        "traffic": "rehearsal_tiny", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rehearse_train_d2" in m.get("workloads", []):
            m["workloads"].append("rehearse_train_olmoe")
    real = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            spec["per_layer"].append(
                dict(m, workloads=["rehearse_train_olmoe"]))
    path = tmp_path_factory.mktemp("olmoe_rehearsal") / "spec.json"
    path.write_text(json.dumps(spec))
    selfcheck.check_spec_contract(str(path), real=False)
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_moe_job(rehearsal_spec, trace):
    line = selfcheck.check_rehearsal_cell(
        "rehearse_train_olmoe", trace, spec_path=rehearsal_spec)
    assert line["correct"] is True
    assert all(name.startswith("rehearsal_") for name in line["metrics"])
    if trace:
        load = line["metrics"]["rehearsal_expert_load_max_over_mean"]
        assert 1.0 <= load["value"] <= 8.0 and load["unit"] == "ratio"
        assert "rehearsal_step_ms" in line["metrics"]
    else:
        assert line["metrics"]["rehearsal_train_tokens_per_s"]["value"] > 0
