"""The attention kernels read by what they compute (PR 31): the kinds of
`kernels.attn` on the pallas flash names and on splash's, a fused backward
counted as five products, K/V at their own width, the jobs' check of the
compiled text, and what a run says when a reader finds nothing. On three
hand-made traces under benchmark/fixtures/ (splash's names as a v5e trace
printed them, invented times) and on the two recorded v5e traces, where
the readings must be the ones PR 29's reader gave, to the last digit."""

import importlib.util
import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import checks, flops, scope_reduce, trace_reduce  # noqa: E402
from benchlib.spec import load_json, load_module  # noqa: E402

CELL_CONFIGS = ["mistral-7b-v0.1-d2", "mistral-7b-v0.1-d8-fsdp4",
                "olmoe-1b-7b-0125-d1"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
D2_CALL = {"batch": 4, "heads": 32, "kv_heads": 8, "seq": 4096,
           "head_dim": 128}
# one [T, hd] x [hd, T]-sized product at d2's call under the causal mask
PRODUCT_FLOPS = 2 * 4 * 32 * 4096 * 4096 * 128 // 2
KERNEL_READERS = ["attn_kernel_share", "attn_kernel_roofline",
                  "attn_glue_share"]


def config(name=CELL_CONFIGS[0]):
    return load_json(os.path.join(BENCH_DIR, "configs", name + ".json"))


_spec = importlib.util.spec_from_file_location(
    "_selfcheck_attn", os.path.join(BENCH_DIR, "selfcheck.py"))
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)


@pytest.fixture()
def record(request):
    """The record of a traced run whose trace is the fixture named by the
    test's `record` parameter (`selfcheck.traced_record`)."""
    with selfcheck.traced_record(request.param) as traced:
        yield traced


def on(*fixtures):
    return pytest.mark.parametrize("record", fixtures, indirect=True)


def read(name, record):
    return load_module("layer_metrics", name).read(record)


# ---- the patterns ------------------------------------------------------

NAMES = [
    ("flash_attention", "fwd"), ("flash_attention.17", "fwd"),
    ("%flash_attention.2", "fwd"),
    ("flash_mha_bwd_dkv_block_q_major_512_block_q_512_block_k_major_512_"
     "block_k_512.1", "bwd_dkv"),
    ("flash_mha_bwd_dq_block_q_major_512_block_k_major_512_block_k_512.1",
     "bwd_dq"),
    # as a v5e trace printed them (my chip run, PR 31)
    ("splash_mha_fwd_residuals.1", "fwd"),
    ("splash_mha_dkv_no_residuals.1", "bwd_dkv"),
    ("splash_mha_dq_no_residuals.1", "bwd_dq"),
    # the library's other variants, by `get_kernel_name`
    ("splash_mha_fwd_no_residuals", "fwd"),
    ("splash_mqa_fwd_segmented_residuals.3", "fwd"),
    ("splash_mqa_dkv_segmented_no_residuals", "bwd_dkv"),
    ("splash_mha_dq_segmented_no_residuals.12", "bwd_dq"),
    # no kernel of attention's
    ("flash_attention_bwd", None), ("fusion.12", None), ("gmm.1", None),
    ("copy.4", None), ("my_attn_fwd.17", None),
]


@pytest.mark.parametrize("cell", CELL_CONFIGS)
@pytest.mark.parametrize("name,kind", NAMES)
def test_a_name_is_one_kind(cell, name, kind):
    patterns = config(cell)["kernels"]["attn"]
    assert sorted(patterns) == ["bwd_dkv", "bwd_dq", "fwd"]
    short = trace_reduce.short_name(name + " = bf16[8] custom-call(%p)")
    found = [k for k, rx in patterns.items() if re.search(rx, short)]
    assert found == ([kind] if kind else []), (name, found)
    # the jobs match the compiled text's instruction names the same way
    assert [k for k, rx in patterns.items()
            if re.match(rx, name.lstrip("%"))] == found


# ---- a call counts as what it computed ---------------------------------


def test_products_per_kind():
    assert flops.ATTENTION_KERNEL_MATMULS == {
        "fwd": 2, "bwd_dkv": 4, "bwd_dq": 3, "bwd_fused": 5}
    for kind, products in flops.ATTENTION_KERNEL_MATMULS.items():
        assert flops.attention_call_flops(kind, 4, 32, 4096, 128) == \
            products * PRODUCT_FLOPS


@pytest.mark.parametrize("kinds,want", [
    ({"fwd": [0.2, 8], "bwd_dkv": [0.3, 4], "bwd_dq": [0.2, 4]},
     {"fwd": [0.2, 8], "bwd_dkv": [0.3, 4], "bwd_dq": [0.2, 4]}),
    ({"fwd": [0.2, 8], "bwd_dkv": [0.3, 4], "bwd_dq": [0.0, 0]},
     {"fwd": [0.2, 8], "bwd_fused": [0.3, 4]}),
    ({"fwd": [0.2, 8], "bwd_dkv": [0.3, 4]},
     {"fwd": [0.2, 8], "bwd_fused": [0.3, 4]}),
    ({"fwd": [0.1, 3], "bwd_dkv": [0.0, 0], "bwd_dq": [0.0, 0]},
     {"fwd": [0.1, 3], "bwd_dkv": [0.0, 0], "bwd_dq": [0.0, 0]}),
    ({}, {}),
])
def test_kinds_as_computed(kinds, want):
    assert flops.kinds_as_computed(kinds) == want


def test_bytes_with_kv_at_their_own_width():
    wide = 4 * 32 * 4096 * 128 * 2      # q, o, do, dq
    narrow = 4 * 8 * 4096 * 128 * 2     # k, v, dk, dv at 8 heads
    stat = 4 * 32 * 4096 * 4

    def nbytes(kind, kv_heads=8):
        return flops.attention_call_bytes(kind, 4, 32, 4096, 128, kv_heads)

    assert nbytes("fwd") == 2 * wide + 2 * narrow + 2 * stat
    assert nbytes("bwd_dkv") == 2 * wide + 4 * narrow + 3 * stat
    assert nbytes("bwd_dq") == 3 * wide + 2 * narrow + 3 * stat
    assert nbytes("bwd_fused") == 3 * wide + 4 * narrow + 3 * stat
    # without kv_heads (an MHA call, or a record of before PR 31): PR 29's
    assert nbytes("fwd", None) == 4 * wide + 2 * stat
    assert nbytes("bwd_dkv", None) == 6 * wide + 3 * stat
    assert nbytes("bwd_dq", None) == 5 * wide + 3 * stat
    with pytest.raises(KeyError):
        nbytes("bwd")


@pytest.mark.parametrize("batch,heads,kv_heads", [
    (4, 32, 8), (2, 32, 8), (4, 16, 16)])   # d2, d8 per chip, OLMoE
@pytest.mark.parametrize("kind", sorted(flops.ATTENTION_KERNEL_MATMULS))
def test_every_kind_is_compute_bound_at_the_cells_calls(batch, heads,
                                                        kv_heads, kind):
    t, bound = flops.least_time_s(
        flops.attention_call_flops(kind, batch, heads, 4096, 128),
        flops.attention_call_bytes(kind, batch, heads, 4096, 128, kv_heads),
        PEAKS)
    assert bound == "compute"
    assert t == flops.attention_call_flops(
        kind, batch, heads, 4096, 128) / 197e12


# ---- the synthetic splash traces ---------------------------------------

SPLASH = {
    # events per kind over two steps, and what the calls computed
    "synthetic_splash_separate.json": (
        {"fwd": 4, "bwd_dkv": 2, "bwd_dq": 2},
        {"fwd": 2, "bwd_dkv": 4, "bwd_dq": 3},
        {"fwd": 5e-3, "bwd_dkv": 9e-3, "bwd_dq": 7e-3}),
    "synthetic_splash_fused.json": (
        {"fwd": 4, "bwd_dkv": 2, "bwd_dq": 0},
        {"fwd": 2, "bwd_fused": 5},
        {"fwd": 5e-3, "bwd_fused": 11e-3}),
}


@on(*sorted(SPLASH))
def test_readers_on_a_synthetic_splash_trace(record, request):
    events, products, seconds_a_call = SPLASH[
        request.node.callspec.params["record"]]
    kinds = record["trace"]["kernel_s"]["attn"]
    assert {k: int(c) for k, (_s, c) in kinds.items()} == events
    out = load_module("layer_metrics", "attn_kernel_roofline").roofline(
        record)
    assert sorted(out["calls"]) == sorted(products)
    assert "bwd_dq" in out["calls"] or "bwd_fused" in out["calls"]
    least = took = 0.0
    for kind, n_products in products.items():
        assert out["bound"][kind] == "compute"
        calls = out["calls"][kind]
        want = 100.0 * n_products * PRODUCT_FLOPS / 197e12 \
            / seconds_a_call[kind]
        assert out["by_kind"][kind] == pytest.approx(want, rel=1e-12)
        assert 40.0 < want < 100.0
        least += calls * n_products * PRODUCT_FLOPS / 197e12
        took += calls * seconds_a_call[kind]
    assert out["share"] == pytest.approx(100.0 * least / took, rel=1e-12)
    assert read("attn_kernel_roofline", record) == out["share"] < 100.0
    # the share of busy time and the glue go by the events' own seconds
    busy = record["trace"]["busy_s"]
    assert read("attn_kernel_share", record) == pytest.approx(
        100.0 * took / busy, rel=1e-9)
    glue_s = 2 * (0.2 + 0.3 + 0.2 + 0.4 + (0.6 if "bwd_fused" in products
                                           else 0.0)) * 1e-3
    assert read("attn_glue_share", record) == pytest.approx(
        100.0 * glue_s / busy, rel=1e-9)


@on("synthetic_splash_fused.json")
def test_a_fused_backward_is_not_read_as_four_products(record):
    """The fault the kind is there for: the same events counted as
    `bwd_dkv` read a lower share than the kernel runs at."""
    out = load_module("layer_metrics", "attn_kernel_roofline").roofline(
        record)
    as_dkv = 100.0 * 4 * PRODUCT_FLOPS / 197e12 / 11e-3
    assert out["by_kind"]["bwd_fused"] == pytest.approx(as_dkv * 5 / 4)
    assert as_dkv < 55.0 < out["by_kind"]["bwd_fused"] < 100.0


# ---- the recorded v5e traces: PR 29's readings, to the last digit ------


@on("v5e_train_d2_two_steps.json.gz")
def test_recorded_two_steps_reads_as_before(record):
    calls = {k: c for k, (_s, c) in
             record["trace"]["kernel_s"]["attn"].items()}
    assert calls == {"fwd": 8, "bwd_dkv": 4, "bwd_dq": 4}
    assert read("attn_kernel_share", record) == 11.910001851336043
    assert read("attn_kernel_roofline", record) == 45.97752499780323
    # before PR 31 the record had no kv_heads, and K/V counted at H heads
    record["static"]["attention_call"] = {
        k: v for k, v in D2_CALL.items() if k != "kv_heads"}
    assert read("attn_kernel_roofline", record) == 45.97752499780323


@on("v5e_train_d2_scoped.json.gz")
def test_recorded_scoped_step_reads_as_before(record):
    assert read("attn_kernel_share", record) == 11.911579884536689
    assert read("attn_kernel_roofline", record) == 45.97284889072912
    assert read("attn_glue_share", record) == 2.092185880897973


# ---- the jobs' check of the compiled text ------------------------------


def _hlo(names):
    call = ' = (bf16[4,32,4096,128]{3,2,1,0}) custom-call(%p), ' \
        'custom_call_target="tpu_custom_call", frontend_attributes={' \
        'kernel_metadata={\n"xprof_metadata":"{}"\n}}, metadata={op_name=' \
        '"jit(_step)/jvp(layers)/while/body/closed_call/attention/x"}'
    return "\n".join(f"  %{name}{call}" for name in names) + \
        "\n  %splash_mha_dq_no_residuals.9 = bf16[8]{0} fusion(%p)\n"


FLASH = ["flash_attention.17", "flash_attention.18",
         "flash_mha_bwd_dkv_block_q_major_512_block_q_512.1",
         "flash_mha_bwd_dq_block_q_major_512_block_k_major_512.1"]
SEPARATE = ["splash_mha_fwd_residuals.1", "splash_mha_fwd_residuals.2",
            "splash_mha_dkv_no_residuals.1", "splash_mha_dq_no_residuals.1"]
FUSED = SEPARATE[:3]


@pytest.mark.parametrize("job", ["train_lm", "train_lm_moe"])
@pytest.mark.parametrize("names,impl,calls,ok", [
    (FLASH, "flash", {"fwd": 2, "bwd_dkv": 1, "bwd_dq": 1}, True),
    (SEPARATE, "flash", {"fwd": 2, "bwd_dkv": 1, "bwd_dq": 1}, True),
    (FUSED, "flash", {"fwd": 2, "bwd_dkv": 1, "bwd_dq": 0}, True),
    (FUSED + ["gmm.1", "tgmm"], "flash",
     {"fwd": 2, "bwd_dkv": 1, "bwd_dq": 0}, True),
    # a forward and no backward that makes dK and dV: not a train step's
    (FUSED[:2], "flash", {"fwd": 2, "bwd_dkv": 0, "bwd_dq": 0}, False),
    (SEPARATE[2:], "flash", {"fwd": 0, "bwd_dkv": 1, "bwd_dq": 1}, False),
    (["my_attn_fwd.1", "my_attn_bwd.2"], "flash",
     {"fwd": 0, "bwd_dkv": 0, "bwd_dq": 0}, False),
    ([], "flash", {"fwd": 0, "bwd_dkv": 0, "bwd_dq": 0}, False),
    # resolved to something else than the configuration expects
    (FLASH, "dense", {"fwd": 2, "bwd_dkv": 1, "bwd_dq": 1}, False),
])
def test_attention_impl_check_on_compiled_text(job, names, impl, calls, ok):
    """What `attention_impl` of both jobs decides, on the text of a
    compiled step: a fused backward (no `dq` call) passes."""
    cell = {"train_lm": CELL_CONFIGS[0], "train_lm_moe": CELL_CONFIGS[2]}[job]
    model = config(cell)
    assert model["job"] == job
    assert model["train"]["expect_attention"] == "flash"
    found = checks.kernel_calls(_hlo(names), model["kernels"]["attn"])
    assert found == calls
    assert checks.attention_as_expected(
        impl, model["train"]["expect_attention"], found) is ok


def test_dense_attention_needs_no_kernel():
    assert checks.attention_as_expected("dense", "dense", {})
    assert not checks.attention_as_expected("flash", "dense", {"fwd": 1})


@pytest.mark.parametrize("calls,ok", [
    ({"gmm": 6, "tgmm": 2}, True),     # PR 27's step: remat's forward too
    ({"gmm": 4, "tgmm": 2}, True),     # a step that recomputes no expert
    ({"gmm": 5, "tgmm": 2}, True),     # or one of the two
    ({"gmm": 3, "tgmm": 2}, False), ({"gmm": 4, "tgmm": 1}, False),
    ({}, False),
])
def test_grouped_matmul_check_leaves_remat_to_the_program(calls, ok):
    assert checks.grouped_matmul_as_expected(
        "megablox", "megablox", calls) is ok
    assert checks.grouped_matmul_as_expected("ragged_dot", "ragged_dot", {})
    assert not checks.grouped_matmul_as_expected(
        "ragged_dot", "megablox", {"gmm": 6, "tgmm": 2})


@pytest.mark.parametrize("cell", CELL_CONFIGS)
def test_remat_policy_is_the_programs(cell):
    """The cells give leave to recompute (`remat`); what is saved is not a
    value of theirs, and the jobs pass none on."""
    from ray_tpu.models.configs import TransformerConfig

    model = config(cell)
    assert model["train"]["remat"] is True
    assert "remat_policy" not in model["train"]
    job = load_module("jobs", model["job"])
    cfg = job.transformer_config(model, model["train"], 4096)
    assert cfg.remat is True
    default = {f.name: f.default for f in
               TransformerConfig.__dataclass_fields__.values()}
    assert cfg.remat_policy == default["remat_policy"]


# ---- a metric that finds nothing says why ------------------------------


@on("synthetic_unknown_kernels.json")
def test_a_run_with_no_attention_kernel_names_the_metric(record, capsys):
    for name in KERNEL_READERS:
        assert read(name, record) is None
    run = load_module(".", "run")   # benchmark/run.py
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    rc = run.emit(spec, "train_mistral7b_d2", 7, True, record, False)
    out, err = capsys.readouterr()
    assert rc == run.NOTHING_READ != 0
    assert out.strip() == "", "a last line without the cell's metrics"
    for name in KERNEL_READERS:
        line = [ln for ln in err.splitlines()
                if ln.startswith(f"[bench] NO READING of {name} ")]
        assert len(line) == 1, err
        # the calls it saw, by name, and that no kind found an event
        assert "my_attn_fwd.17 (forward" in line[0]
        assert "my_attn_fwd.18 (recompute" in line[0]
        assert "my_attn_bwd.11 (backward" in line[0]
        assert "'fwd': [0.0, 0.0]" in line[0]
    assert err.strip().splitlines()[-1].startswith("benchmark: no result")
    # the same record in a cell that does not list them prints its line
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] == "step_ms"]
    record["clock"] = {"step_s": [0.5, 0.5]}
    assert run.emit(spec, "train_mistral7b_d2", 7, True, record, False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line["metrics"]) == ["step_ms"]


def test_a_rehearsal_without_a_device_trace_still_prints_its_line(capsys):
    """On the CPU no reader of the device trace finds anything: the
    metrics are left out, as before, and the line is printed."""
    run = load_module(".", "run")   # benchmark/run.py
    spec = load_json(os.path.join(BENCH_DIR, "rehearsal",
                                  "BENCHMARK.rehearsal.json"))
    cell = spec["workloads"][0]["name"]
    record = {"trace": {"devices": 0}, "window_started_at": time.time(),
              "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                         "memory_peak_bytes": 0},
              "correct": True, "attempted": 3, "failed": 0,
              "end_to_end": {"train_tokens_per_s": 1.0}, "checks": {},
              "clock": {"step_s": [0.1, 0.1, 0.1], "gang_start_s": 1.0,
                        "window_s": 0.3, "tokens_per_step": 8},
              "static": {"chips": 1, "peaks": None, "flops_per_token": 1.0}}
    assert run.emit(spec, cell, 3, True, record, True) == 0
    out, err = capsys.readouterr()
    assert "NO READING" not in err
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metrics"] and all(
        k.startswith("rehearsal_") for k in line["metrics"])


def test_describe_attention_without_a_trace():
    said = scope_reduce.describe_attention({"trace": None})
    assert "no device trace of this run" in said
