"""Checks of the benchmark itself that need no chip.

    python3 benchmark/selfcheck.py            # everything, about two minutes
    python3 benchmark/selfcheck.py arithmetic # only what starts no process

`arithmetic`: BENCHMARK.json against the contract's limits, every file a
cell names is there, the FLOP functions against hand-worked numbers for
both Mistral depths, the trace reduction against the recorded v5e trace in
fixtures/ (each number recomputed here by rasterising the intervals, a
method that shares nothing with the reduction's sweeps), the attention
kernels read by kind on hand-made traces with splash's names (a fused
backward is five products; a trace whose kernels no pattern names makes
the run say which metric found nothing and which calls it saw, and print
no result), the traffic generator's determinism, the span-bucket
arithmetic on a hand-made ring.

`rehearsal`: every kind of cell at a tiny size on the CPU
(`JAX_PLATFORMS=cpu`, rehearsal-only configuration files, four virtual
devices for the fsdp cell): the last line's shape, metric names that carry
the `rehearsal_` prefix (a rehearsal result never goes under a device
metric's name), no result without an accelerator for a real cell, none in
a bare directory, and a configuration, a mix and a per-layer metric dropped
in as new files in a copy of the tree being found with no edit.

Each check is a function `check_<name>()` that raises AssertionError;
`CHECKS` lists them for a test runner.
"""

from __future__ import annotations

import contextlib
import gzip
import io
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import (flops, scope_reduce, span_buckets,  # noqa: E402
                      trace_reduce)
from benchlib.spec import load_json, load_module, metrics_of  # noqa: E402
from benchlib.traffic import TokenBatches  # noqa: E402

REHEARSAL_SPEC = os.path.join(BENCH_DIR, "rehearsal",
                              "BENCHMARK.rehearsal.json")
FIXTURE = os.path.join(BENCH_DIR, "fixtures",
                       "v5e_train_d2_two_steps.json.gz")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH_KEY = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"expansion|experts_per_tok", re.I)


# ---------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------


def check_spec_contract(path: str = os.path.join(ROOT, "BENCHMARK.json"),
                        real: bool = True) -> None:
    """BENCHMARK.json inside the contract's limits, and every file a cell
    needs in its place."""
    spec = load_json(path)
    assert os.path.getsize(path) <= 64 * 1024
    if real:
        assert set(spec) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}, set(spec)
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p, p
    assert 1 <= len(spec["command"]) <= 32
    for word in spec["command"]:
        assert 1 <= len(word) <= 200 and "\t" not in word and "\n" not in word
        assert not word.startswith("/") and ".." not in word, word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in spec["paths"]), word
    rs = spec["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200

    names: List[str] = []
    files = set()
    assert 1 <= len(spec["configs"]) <= 24
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert NAME.match(c["name"]), c["name"]
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH_KEY.search(key), key
        for text in (c["why"], c["source"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and \
                "\t" not in text, text
        held = load_json(os.path.join(ROOT, c["file"]))
        assert sorted(held.get("reduced", {})) == sorted(c["reduced"]), \
            (c["name"], held.get("reduced"))
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "jobs", held["job"] + ".py")), held["job"]
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "reference", held["reference"] + ".py"))
        names.append(c["name"])
    assert len(set(names)) == len(names)

    cells = spec["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = set()
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"], \
            (w["name"], len(w["why"]))
        assert w["config"] in names, w["config"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json")), w["traffic"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(names), "an unused config"
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4), four

    e2e = spec["end_to_end"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(spec["per_layer"]) <= 128
    metric_names = [m["name"] for m in e2e + spec["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in [m["name"] for m in e2e]
    for m in e2e:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}, m
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}, m
        assert m["source"] in SOURCES, m
        assert m["moves"] in [e["name"] for e in e2e], m
        assert 1 <= len(m["layer"]) <= 200
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py")), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in [c["name"] for c in cells], (m["name"], w)
    for w in cells:   # setup_s, one other end-to-end, one per-layer metric
        mine = [m["name"] for m in metrics_of(spec, "end_to_end", w["name"])]
        assert "setup_s" in mine and len(mine) >= 2, (w["name"], mine)
        layer = [m for m in metrics_of(spec, "per_layer", w["name"])
                 if m["moves"] in mine]
        assert layer, w["name"]
    for path in spec["paths"]:   # file names from a name's characters
        for base, _dirs, found in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in found:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel


def check_flops_hand_worked() -> None:
    """The published widths worked by hand, for both depths."""
    cfg = load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.1-d2.json"))
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096; gate, up, down
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808 == flops.layer_matmul_params(cfg)
    head = 4096 * 32000
    assert flops.matmul_params(cfg) == 2 * layer + head == 567_279_616
    assert flops.total_params(cfg) == \
        head + 2 * (layer + 2 * 4096) + 4096 + head == 698_372_096
    # attention: 6 matmul passes of 2*T*hd per head, causal half, 32 heads
    attn_layer = 6 * 2 * 4096 * 128 * 32 // 2
    assert attn_layer == 100_663_296
    assert flops.train_flops_per_token(cfg, 4096) == \
        6 * 567_279_616 + 2 * attn_layer == 3_605_004_288
    deep = load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.1-d8-fsdp4.json"))
    assert flops.matmul_params(deep) == 8 * layer + head == 1_875_902_464
    assert flops.total_params(deep) == 2_007_044_096
    assert flops.train_flops_per_token(deep, 4096) == \
        6 * 1_875_902_464 + 8 * attn_layer == 12_060_721_152
    # one forward attention call at 4 x 32 heads x 4096 x 128: two
    # products; dkv four, dq three, a backward in one call five
    assert flops.attention_call_flops("fwd", 4, 32, 4096, 128) == \
        2 * 2 * 4 * 32 * 4096 * 4096 * 128 // 2 == 549_755_813_888
    for kind, halves in (("bwd_dkv", 4), ("bwd_dq", 3), ("bwd_fused", 5)):
        assert flops.attention_call_flops(kind, 4, 32, 4096, 128) == \
            halves * 549_755_813_888 // 2
    # q and o at 32 heads, k and v at their own 8, two f32 statistics
    assert flops.attention_call_bytes("fwd", 4, 32, 4096, 128, 8) == \
        2 * 134_217_728 + 2 * 33_554_432 + 2 * 2_097_152 == 339_738_624
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_time_s(549_755_813_888, 339_738_624, peaks)
    assert bound == "compute" and abs(t - 549_755_813_888 / 197e12) < 1e-12
    assert abs(339_738_624 / 819e9 - 0.000415) < 1e-6 < t    # 2.79 ms
    assert flops.least_time_s(1e9, 1e9, peaks)[1] == "memory"


def _raster(intervals: List[Tuple[float, float]], lo: float, hi: float,
            tick: float):
    import numpy as np
    grid = np.zeros(int((hi - lo) / tick) + 1, bool)
    for s, e in intervals:
        a = max(0, int(round((s - lo) / tick)))
        b = min(len(grid), int(round((e - lo) / tick)))
        if b > a:
            grid[a:b] = True
    return grid


def check_trace_reduction_on_fixture() -> None:
    """The recorded v5e trace (two steps of train_mistral7b_d2): the
    reduction's numbers against a rasterised recomputation at 1 us, and
    against what the cell must show (kernel calls per step, no
    collectives on one chip)."""
    trace = load_fixture(os.path.basename(FIXTURE))
    kernels = load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.1-d2.json"))["kernels"]
    out = trace_reduce.reduce_trace(trace, kernels)
    assert out["devices"] == 1 and out["modules_per_device"] == 2

    device = [p for p in trace["planes"] if p["name"] == "/device:TPU:0"][0]
    host = [e for p in trace["planes"] if p["name"] == "/host:CPU"
            for ln in p["lines"] for e in ln["events"]]
    lo, dur = [(e[1], e[2]) for e in host if e[0] == "bench_window"][0]
    hi = lo + dur
    ops = [ln for ln in device["lines"] if ln["name"] == "XLA Ops"][0]
    tick = 1000.0   # ns
    busy = _raster([(e[1], e[1] + e[2]) for e in ops["events"]],
                   lo, hi, tick)
    busy_s = busy.sum() * tick / 1e9
    assert abs(out["window_s"] - dur / 1e9) < 1e-9
    assert abs(out["busy_s"] - busy_s) < 0.002 * busy_s, (out["busy_s"],
                                                          busy_s)
    assert abs(out["idle_s"] - (dur / 1e9 - busy_s)) < 0.003
    assert abs(out["busy_s"] + out["idle_s"] - out["window_s"]) < 1e-9
    # kernel time by name: summed straight from the events
    for kind, rx in kernels["attn"].items():
        hits = [e for e in ops["events"] if re.search(
            rx, trace_reduce.short_name(e[0])) and lo <= e[1] < hi]
        seconds, count = out["kernel_s"]["attn"][kind]
        assert count == len(hits) and abs(
            seconds - sum(e[2] for e in hits) / 1e9) < 1e-9
    # 2 layers x (forward + remat's forward) and one dq, one dkv per layer
    calls = {k: v[1] for k, v in out["kernel_s"]["attn"].items()}
    assert calls == {"fwd": 8, "bwd_dkv": 4, "bwd_dq": 4}, calls
    # self times add up to busy time (containers give up their children)
    self_sum = sum(t[3] for t in trace_reduce.self_times(
        [e for e in ops["events"] if e[1] + e[2] > lo and e[1] < hi]))
    assert abs(self_sum / 1e9 - busy_s) < 0.01 * busy_s, (self_sum, busy_s)
    assert out["collective_s"] == 0.0 and out["collective_exposed_s"] == 0.0
    # one gap between the two programs, and the idle gaps' labels add up
    modules = sorted(e for ln in device["lines"]
                     if ln["name"] == "XLA Modules" for e in ln["events"]
                     if e[1] >= lo and e[1] + e[2] <= hi)
    gap = modules[1][1] - (modules[0][1] + modules[0][2])
    assert out["module_gaps"] == 1
    assert abs(out["module_gap_median_s"] - gap / 1e9) < 1e-9
    assert abs(sum(s for _, s in out["idle_by_host_s"])
               - out["idle_s"]) < 1e-9
    assert {k for k, _ in out["idle_by_host_s"]} <= {
        "make_batch", "dispatch", "report", "unattributed"}
    bd = trace_reduce.breakdown(out)
    assert len(bd["device_ops"]) == 10 and 1 <= len(bd["idle_gaps"]) <= 10


def check_trace_reduction_collectives() -> None:
    """Exposed collective time on a hand-made trace: a blocking
    all-reduce and an asynchronous all-gather, partly under compute."""
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%while.1 = x while()", 100, 800],
                ["%fusion.1 = f", 110, 300],
                ["%all-reduce.2 = a", 420, 100],
                ["%fusion.2 = f", 530, 360],
                ["%fusion.3 = y", 1000, 100]]},
            {"name": "Async XLA Ops", "events": [
                ["%all-gather-start.1 = ", 400, 300]]},
            {"name": "XLA Modules", "events": [
                ["jit_step(1)", 100, 800], ["jit_step(1)", 1000, 100]]}]},
        {"name": "/host:CPU", "lines": [{"name": "py", "events": [
            ["bench_window", 0, 1200], ["report", 0, 950],
            ["dispatch", 950, 60]]}]}]}
    out = trace_reduce.reduce_trace(trace, {"k": {"a": r"^fusion\.1$"}})
    ns = 1e-9
    assert abs(out["busy_s"] - 900 * ns) < 1e-15      # [100,900]+[1000,1100]
    assert abs(out["idle_s"] - 300 * ns) < 1e-15
    assert abs(out["collective_s"] - 300 * ns) < 1e-15        # [400,700]
    # compute leaves cover [110,410] and [530,890]: exposed is [410,530]
    assert abs(out["collective_exposed_s"] - 120 * ns) < 1e-15
    assert abs(out["module_gap_median_s"] - 100 * ns) < 1e-15
    seconds, count = out["kernel_s"]["k"]["a"]
    assert abs(seconds - 300 * ns) < 1e-15 and count == 1
    ops = {k: s for k, s, _ in out["op_self_s"]}
    assert abs(ops["while.1"] - 40 * ns) < 1e-15     # 800 less its children
    idle = dict(out["idle_by_host_s"])
    assert abs(idle["report"] - 200 * ns) < 1e-15
    assert abs(idle["unattributed"] - 100 * ns) < 1e-15


def load_fixture(name: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, "fixtures", name)
    with (gzip.open if name.endswith(".gz") else open)(path, "rt") as f:
        return json.load(f)


@contextlib.contextmanager
def traced_record(fixture: str):
    """The record a job would hand run.py after tracing what the fixture
    holds: the worker's reduction by the d2 file's kernel names, d2's
    attention call, and, for the readers that go by scope, the trace as
    the file of `this run` (`scope_reduce` is pointed at it, and put back
    on the way out)."""
    trace = load_fixture(fixture)
    model = load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.1-d2.json"))
    saved = (scope_reduce.SCRATCH, scope_reduce._REDUCED,
             scope_reduce.from_xplane)
    with tempfile.TemporaryDirectory(prefix="bench_synth_") as scratch:
        run = os.path.join(scratch, "cell", "trace", "plugins", "profile",
                           "x")
        os.makedirs(run)
        with open(os.path.join(run, "host.xplane.pb"), "wb"):
            pass
        scope_reduce.SCRATCH, scope_reduce._REDUCED = scratch, {}
        scope_reduce.from_xplane = lambda path: trace
        try:
            yield {
                "trace": trace_reduce.reduce_trace(trace, model["kernels"]),
                "window_started_at": time.time() - 60.0,
                "static": {"peaks": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
                           "attention_call": {
                               "batch": 4, "heads": 32, "kv_heads": 8,
                               "seq": 4096, "head_dim": 128}},
                "device": {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1, "memory_peak_bytes": 1},
                "correct": True, "attempted": 2, "failed": 0, "checks": {},
                "end_to_end": {"train_tokens_per_s": 1.0}}
        finally:
            (scope_reduce.SCRATCH, scope_reduce._REDUCED,
             scope_reduce.from_xplane) = saved


def check_attention_kernels_by_kind() -> None:
    """Splash's names on hand-made traces: separate dq and dkv calls are
    `fwd`, `bwd_dkv`, `bwd_dq` with 2, 4, 3 products; a backward with no
    dq event is `bwd_fused` with 5; neither reads over 100%."""
    roofline = load_module("layer_metrics", "attn_kernel_roofline").roofline
    unit = 549_755_813_888 / 2 / 197e12      # one product at d2's call
    for name, ms in (
            ("synthetic_splash_separate.json",
             {"fwd": (2, 5.0), "bwd_dkv": (4, 9.0), "bwd_dq": (3, 7.0)}),
            ("synthetic_splash_fused.json",
             {"fwd": (2, 5.0), "bwd_fused": (5, 11.0)})):
        with traced_record(name) as record:
            out = roofline(record)
        assert sorted(out["calls"]) == sorted(ms), (name, out["calls"])
        for kind, (products, took_ms) in ms.items():
            want = 100.0 * products * unit / (took_ms / 1e3)
            assert abs(out["by_kind"][kind] - want) < 1e-9, (kind, out)
            assert out["bound"][kind] == "compute"
        assert 40.0 < out["share"] < 100.0, out


def check_nothing_read_says_why() -> None:
    """A traced run of a real cell whose attention kernels no pattern of
    `kernels.attn` names: stderr names each metric without a reading and
    the custom calls seen under the scope `attention`; no result line,
    a non-zero exit."""
    run = load_module(".", "run")   # benchmark/run.py
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out, err = io.StringIO(), io.StringIO()
    with traced_record("synthetic_unknown_kernels.json") as record, \
            contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.emit(spec, "train_mistral7b_d2", 7, True, record, False)
    assert rc == run.NOTHING_READ and not out.getvalue().strip(), out
    for metric in ("attn_kernel_share", "attn_kernel_roofline",
                   "attn_glue_share"):
        said = [ln for ln in err.getvalue().splitlines()
                if ln.startswith(f"[bench] NO READING of {metric} ")]
        assert len(said) == 1 and "my_attn_fwd.17 (forward" in said[0] \
            and "my_attn_bwd.11 (backward" in said[0], err.getvalue()


def check_traffic_deterministic() -> None:
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_4k.json"))
    a = TokenBatches(mix, 32000, 7)
    b = TokenBatches(mix, 32000, 7)
    c = TokenBatches(mix, 32000, 8)
    assert a.batch(3).shape == (4, 4097) and a.batch(3).dtype.name == "int32"
    assert (a.batch(3) == b.batch(3)).all()
    assert not (a.batch(3) == a.batch(4)).all()
    assert not (a.batch(3) == c.batch(3)).all()
    assert 0 <= a.batch(0).min() and a.batch(0).max() < 32000
    # Zipf(1.1) over 32000 ranks: far under log(32000) = 10.37 nats
    assert 5.5 < a.unigram_entropy_nats < 7.0, a.unigram_entropy_nats
    assert a.tokens_per_step == 16384


def check_span_buckets() -> None:
    """The copy of perf_report's arithmetic: priorities resolve overlap,
    the learner thread is picked, the rest is idle."""
    def span(name, ts, dur, tid="learner"):
        return {"ph": "X", "cat": "span", "name": name, "pid": "p",
                "tid": tid, "ts": ts, "dur": dur}
    events = [span("learner.step", 0, 1000), span("rpc.call", 100, 200),
              span("feed.wait", 1000, 500), span("store.get", 1200, 100),
              span("rpc.call", 0, 5000, tid="driver"),
              {"ph": "M", "name": "process_name"}]
    out = span_buckets.attribute(events)
    assert out["thread"] == "learner" and abs(out["covered_s"] - 1.5e-3) \
        < 1e-12
    s = out["seconds"]
    assert abs(s["store_rpc"] - 300e-6) < 1e-12
    assert abs(s["learner_compute"] - 800e-6) < 1e-12
    assert abs(s["rollout_wait"] - 400e-6) < 1e-12
    assert abs(s["idle"]) < 1e-12


# ---------------------------------------------------------------------
# rehearsal: processes on the CPU
# ---------------------------------------------------------------------


def _run(args: List[str], *, devices: int = 1, cwd: str = ROOT,
         run_py: str = os.path.join(BENCH_DIR, "run.py"),
         timeout: float = 300.0) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, run_py] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _last_line(proc: subprocess.CompletedProcess) -> Dict[str, Any]:
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == {
        "correct", "attempted", "failed", "metrics", "device"}, set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"], "no metric"
    for name, m in line["metrics"].items():
        assert name.startswith("rehearsal_"), name   # never a device name
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert line["correct"] is True and line["failed"] == 0, proc.stderr[-3000:]
    return line


def check_rehearsal_cell(workload: str, trace: int,
                         spec_path: str = REHEARSAL_SPEC,
                         **kw: Any) -> Dict[str, Any]:
    spec = load_json(spec_path)
    cell = [w for w in spec["workloads"] if w["name"] == workload][0]
    proc = _run(["--spec", spec_path, "--workload", workload, "--seed", "3",
                 "--seconds", "3", "--trace", str(trace)],
                devices=cell["chips"], **kw)
    line = _last_line(proc)
    assert line["device"]["count"] == cell["chips"]
    section = "per_layer" if trace else "end_to_end"
    allowed = {"rehearsal_" + m["name"]
               for m in metrics_of(spec, section, workload)}
    assert set(line["metrics"]) <= allowed, (set(line["metrics"]), allowed)
    if not trace:
        assert set(line["metrics"]) == allowed
    return line


def check_no_result_without_accelerator() -> None:
    """A real cell on a machine without a chip: non-zero, no result."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    one_chip = [w["name"] for w in spec["workloads"] if w["chips"] == 1][0]
    proc = _run(["--workload", one_chip, "--seed", "0", "--seconds", "1",
                 "--trace", "0"])
    assert proc.returncode != 0, proc.stdout
    assert not proc.stdout.strip(), proc.stdout


def check_no_result_in_bare_directory() -> None:
    """Only BENCHMARK.json and the files under `paths`: non-zero, no
    result."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    with tempfile.TemporaryDirectory(prefix="bench_bare_") as bare:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
        for p in spec["paths"]:
            shutil.copytree(os.path.join(ROOT, p), os.path.join(bare, p),
                            ignore=shutil.ignore_patterns("__pycache__"))
        proc = _run(["--workload", spec["workloads"][0]["name"], "--seed",
                     "0", "--seconds", "1", "--trace", "0"], cwd=bare,
                    run_py=os.path.join(bare, spec["command"][1]))
        assert proc.returncode != 0 and not proc.stdout.strip(), proc.stdout


def check_new_files_are_found() -> None:
    """A later PR's move: in a copy of the tree (the program linked in),
    a new configuration, a new mix and a new per-layer metric arrive as
    new files and entries in the spec; no file that was there is edited,
    and the new cell runs and reports the new metric."""
    with tempfile.TemporaryDirectory(prefix="bench_dropin_") as tree:
        os.symlink(os.path.join(ROOT, "ray_tpu"),
                   os.path.join(tree, "ray_tpu"))
        bench = os.path.join(tree, "benchmark")
        shutil.copytree(BENCH_DIR, bench,
                        ignore=shutil.ignore_patterns("__pycache__"))
        config = load_json(os.path.join(
            BENCH_DIR, "rehearsal", "configs", "tiny-d2.json"))
        config["num_hidden_layers"] = 3
        with open(os.path.join(bench, "configs", "dropin-d3.json"),
                  "w") as f:
            json.dump(config, f)
        mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                     "rehearsal_tiny.json"))
        mix["sequences_per_step"] = 2
        with open(os.path.join(bench, "traffic", "dropin_two.json"),
                  "w") as f:
            json.dump(mix, f)
        with open(os.path.join(bench, "layer_metrics",
                               "dropin_steps.py"), "w") as f:
            f.write("def read(record):\n"
                    "    return float(len(record['clock']['step_s']))\n")
        spec = load_json(REHEARSAL_SPEC)
        spec["configs"].append({
            "name": "dropin-d3", "source": "none",
            "file": "benchmark/configs/dropin-d3.json", "reduced": [],
            "why": "dropped in"})
        spec["workloads"].append({
            "name": "dropin_cell", "config": "dropin-d3",
            "traffic": "dropin_two", "chips": 1, "why": "dropped in"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "rehearse_train_d2" in m.get("workloads", []):
                m["workloads"].append("dropin_cell")   # an entry, no file
        spec["per_layer"].append({
            "name": "dropin_steps", "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "train loop",
            "moves": "train_tokens_per_s", "workloads": ["dropin_cell"]})
        spec_path = os.path.join(tree, "spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        line = check_rehearsal_cell(
            "dropin_cell", 1, spec_path=spec_path, cwd=tree,
            run_py=os.path.join(bench, "run.py"))
        assert line["metrics"]["rehearsal_dropin_steps"]["value"] >= 3
        for base, _dirs, files in os.walk(BENCH_DIR):   # nothing edited
            if "__pycache__" in base:
                continue
            for name in files:
                mine = os.path.join(base, name)
                theirs = os.path.join(
                    bench, os.path.relpath(mine, BENCH_DIR))
                with open(mine, "rb") as a, open(theirs, "rb") as b:
                    assert a.read() == b.read(), mine


def _rehearsal_cells() -> List[Tuple[str, int]]:
    spec = load_json(REHEARSAL_SPEC)
    return [(w["name"], t) for w in spec["workloads"] for t in (0, 1)]


ARITHMETIC = [check_spec_contract, check_flops_hand_worked,
              check_trace_reduction_on_fixture,
              check_trace_reduction_collectives,
              check_attention_kernels_by_kind, check_nothing_read_says_why,
              check_traffic_deterministic, check_span_buckets]
PROCESSES = [check_no_result_without_accelerator,
             check_no_result_in_bare_directory, check_new_files_are_found]
CHECKS = ARITHMETIC + PROCESSES


def main(argv: List[str]) -> int:
    only = argv[1] if len(argv) > 1 else "all"
    failed = 0

    def attempt(label: str, fn, *args: Any) -> None:
        nonlocal failed
        try:
            fn(*args)
            print(f"ok    {label}", flush=True)
        except Exception as e:  # noqa: BLE001 - reported, counted
            failed += 1
            print(f"FAIL  {label}: {type(e).__name__}: {str(e)[:2000]}",
                  flush=True)

    for fn in ARITHMETIC:
        attempt(fn.__name__, fn)
    attempt("check_spec_contract[rehearsal]", check_spec_contract,
            REHEARSAL_SPEC, False)
    if only != "arithmetic":
        for workload, trace in _rehearsal_cells():
            attempt(f"check_rehearsal_cell[{workload}, trace={trace}]",
                    check_rehearsal_cell, workload, trace)
        for fn in PROCESSES:
            attempt(fn.__name__, fn)
    print(f"{failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
