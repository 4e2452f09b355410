"""benchmark/benchlib/scope_reduce.py and the ten readers on top of it,
against the recorded v5e step in fixtures/v5e_train_d2_scoped.json.gz:
every bucket recomputed by rasterising the intervals (a method that shares
nothing with the reduction's self-time sweep, and its own reading of a
path), the file reader on a hand-encoded xplane, and what a reader does
without a trace of this run."""

import gzip
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import scope_reduce, trace_reduce  # noqa: E402
from benchlib.spec import load_json, load_module  # noqa: E402

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "v5e_train_d2_scoped.json.gz")
TRACE_READERS = ["mlp_share", "head_share", "attn_proj_share",
                 "attn_glue_share", "optimizer_share", "recompute_share",
                 "unscoped_share", "report_wait_ms"]
GANG_READERS = ["gang_workers_s", "gang_backend_s"]
BEFORE_PR_24 = ["gang_start_s", "step_gap_ms", "step_ms", "model_flops_util",
                "attn_kernel_share", "attn_kernel_roofline",
                "collective_exposed_share", "device_idle_share",
                "peak_hbm_gb"]


@pytest.fixture(scope="module")
def fixture_trace():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(fixture_trace):
    return scope_reduce.reduce_scopes(fixture_trace)


def _device_events(trace):
    plane = [p for p in trace["planes"] if p["name"] == "/device:TPU:0"][0]
    return [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"][0][
        "events"]


def _window(trace):
    host = [e for p in trace["planes"] if p["name"] == "/host:CPU"
            for ln in p["lines"] for e in ln["events"]]
    lo, dur = [(e[1], e[2]) for e in host if e[0] == "bench_window"][0]
    return lo, lo + dur


def _bucket_by_parts(name, path):
    """The test's own reading of a path: components from the innermost
    outwards, JAX's wrappers peeled off with string methods."""
    short = name.split(" = ")[0].lstrip("%")
    if short.startswith(("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-")):
        return "collectives"
    parts = []
    for part in path.rstrip(":").split("/"):
        while part.startswith(("jvp(", "transpose(", "vmap(")) and \
                part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        parts.append(part)
    for i in range(len(parts) - 1, -1, -1):
        if i and parts[i - 1] + "/" + parts[i] in scope_reduce.SCOPES:
            return parts[i - 1] + "/" + parts[i]
        if parts[i] in scope_reduce.SCOPES:
            return parts[i]
    return "unscoped"


def _rasterised(trace, label_of, tick=100.0):
    """Seconds per label: paint the events onto a grid, outer (longer)
    events first, so that a container keeps only what its children leave."""
    lo, hi = _window(trace)
    events = sorted(_device_events(trace), key=lambda e: -e[2])
    labels = sorted({label_of(e) for e in events})
    grid = np.full(int((hi - lo) / tick) + 1, -1, np.int16)
    for e in events:
        a = max(0, int(round((e[1] - lo) / tick)))
        b = min(len(grid), int(round((e[1] + e[2] - lo) / tick)))
        if b > a:
            grid[a:b] = labels.index(label_of(e))
    return {label: float((grid == i).sum()) * tick / 1e9
            for i, label in enumerate(labels)}


def test_the_fixture_is_one_step_with_every_scope(fixture_trace, reduced):
    assert reduced["devices"] == 1
    assert 0.55 < reduced["busy_s"] < 0.57          # one 565 ms step
    dense = set(scope_reduce.SCOPES) - {"moe"}
    assert dense <= set(reduced["bucket_s"]), \
        sorted(dense - set(reduced["bucket_s"]))
    assert "collectives" not in reduced["bucket_s"]      # one chip
    assert set(reduced["phase_s"]) == set(scope_reduce.PHASES)
    assert set(reduced["host_spans_s"]) == {"train.step", "train.report"}


@pytest.mark.parametrize("bucket", [s for s in scope_reduce.SCOPES
                                    if s != "moe"] + ["unscoped"])
def test_bucket_against_a_rasterised_recomputation(fixture_trace, reduced,
                                                   bucket):
    raster = _rasterised(fixture_trace,
                         lambda e: _bucket_by_parts(e[0], e[3]))
    # events under a tick are lost to rounding: 0.05% of the step
    assert abs(reduced["bucket_s"][bucket] - raster[bucket]) < \
        5e-4 * reduced["busy_s"], (reduced["bucket_s"][bucket],
                                   raster[bucket])


@pytest.mark.parametrize("phase", scope_reduce.PHASES)
def test_phase_against_a_rasterised_recomputation(fixture_trace, reduced,
                                                  phase):
    def phase_by_parts(e):
        if _bucket_by_parts("", e[3]) == "optimizer":
            return "optimizer"
        if "/rematted_computation/" in e[3]:
            return "recompute"
        return "backward" if "transpose(jvp(" in e[3] else "forward"

    raster = _rasterised(fixture_trace, phase_by_parts)
    assert abs(reduced["phase_s"][phase] - raster[phase]) < \
        5e-4 * reduced["busy_s"]


def test_buckets_and_phases_sum_to_the_self_time(fixture_trace, reduced):
    lo, hi = _window(fixture_trace)
    timed = trace_reduce.self_times(
        [e[:3] for e in _device_events(fixture_trace)
         if e[1] + e[2] > lo and e[1] < hi])
    self_s = sum(t[3] for t in timed) / 1e9
    for sums in (reduced["bucket_s"], reduced["phase_s"],
                 reduced["bucket_phase_s"]):
        assert abs(sum(sums.values()) - self_s) < 1e-3 * self_s
    assert abs(reduced["self_s"] - self_s) < 1e-9
    assert abs(reduced["busy_s"] - self_s) < 1e-3 * self_s
    top = reduced["top_ops"]
    assert top == sorted(top, key=lambda row: -row[3]) and \
        top[0][1] == "mlp/gate_up"


@pytest.mark.parametrize("path,scope,phase", [
    ("jit(_step)/jvp(layers)/while/body/closed_call/mlp/gate_up/"
     "btd,dgf->btgf/dot_general:", "mlp/gate_up", "forward"),
    ("jit(_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/jit(flash_attention)/pallas_call:",
     "attention", "recompute"),
    ("jit(_step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/"
     "head/bcd,dv->bcv/dot_general:", "head", "backward"),
    ("jit(_step)/transpose(jvp(layers))/while/body/dynamic_update_slice:",
     "layers", "backward"),
    ("jit(_step)/optimizer/add:", "optimizer", "optimizer"),
    ("jit(_step)/jvp()/slice:", None, "forward"),
    ("jit(loss)/jit(head)/mul:", None, "forward"),    # functions, no scopes
    ("", None, "forward"),
])
def test_scope_and_phase_of_a_path(path, scope, phase):
    assert scope_reduce.scope_of(path) == scope
    assert scope_reduce.phase_of(path) == phase
    assert scope_reduce.bucket_of("fusion.1", path) == (scope or "unscoped")
    assert scope_reduce.bucket_of("all-gather.113", path) == "collectives"


# ---- the file reader, on a hand-encoded xplane -------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    """One device plane with two ops (one `tf_op` as a string, one as a
    reference to a stat metadata's name, as the profiler writes both) and
    one without a path; a host plane with the window and two spans."""
    stat_meta = {1: "tf_op", 2: "flops", 3: "jit(_step)/optimizer/add:"}
    event_meta = {
        1: ("%fusion.1 = f32[8] fusion()", _field(5, _field(1, 2)
            + _field(3, 99)) + _field(5, _field(1, 1) + _field(
                5, "jit(_step)/jvp(layers)/while/body/qkv/dot_general:"))),
        2: ("%fusion.2 = f32[8] fusion()",
            _field(5, _field(1, 1) + _field(7, 3))),
        3: ("%copy.3 = f32[8] copy()", b""),
    }
    device = _field(2, "/device:TPU:0")
    for key, name in stat_meta.items():
        device += _field(5, _entry(key, _field(1, key) + _field(2, name)))
    for key, (name, stats) in event_meta.items():
        device += _field(4, _entry(
            key, _field(1, key) + _field(2, name) + stats))
    ops = _field(2, "XLA Ops") + _field(3, 1000)
    for meta_id, offset_ns, dur_ns in ((1, 100, 300), (2, 500, 200),
                                       (3, 800, 50)):
        ops += _field(4, _field(1, meta_id) + _field(2, offset_ns * 1000)
                      + _field(3, dur_ns * 1000))
    device += _field(3, ops)
    host = _field(2, "/host:CPU")
    names = {1: "bench_window", 2: "train.step", 3: "train.report",
             4: "some::Runtime thing"}
    for key, name in names.items():
        host += _field(4, _entry(key, _field(1, key) + _field(2, name)))
    line = _field(2, "python3") + _field(3, 1000)
    for meta_id, offset_ns, dur_ns in ((1, 0, 1000), (2, 10, 20),
                                       (3, 900, 40), (4, 5, 5)):
        line += _field(4, _field(1, meta_id) + _field(2, offset_ns * 1000)
                       + _field(3, dur_ns * 1000))
    host += _field(3, line)
    return _field(1, device) + _field(1, host)


def test_op_paths_reads_the_metadata_stat():
    assert scope_reduce.op_paths(_xspace()) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()":
            "jit(_step)/jvp(layers)/while/body/qkv/dot_general:",
        "%fusion.2 = f32[8] fusion()": "jit(_step)/optimizer/add:",
        "%copy.3 = f32[8] copy()": ""}}


def test_from_xplane_to_the_reduction(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_xspace())
    trace = scope_reduce.from_xplane(str(path))
    device, host = trace["planes"]
    assert [e[3] for e in device["lines"][0]["events"]] == [
        "jit(_step)/jvp(layers)/while/body/qkv/dot_general:",
        "jit(_step)/optimizer/add:", ""]
    assert sorted(e[0] for e in host["lines"][0]["events"]) == [
        "bench_window", "train.report", "train.step"]
    out = scope_reduce.reduce_scopes(trace)
    ns = 1e-9
    assert out["bucket_s"] == pytest.approx(
        {"qkv": 300 * ns, "optimizer": 200 * ns, "unscoped": 50 * ns})
    assert out["phase_s"] == pytest.approx(
        {"forward": 350 * ns, "optimizer": 200 * ns})
    assert out["host_spans_s"] == pytest.approx(
        {"train.step": [20 * ns], "train.report": [40 * ns]})


# ---- the readers -------------------------------------------------------


def _record(**kw):
    record = {"trace": {"devices": 1, "busy_s": 0.56,
                        "kernel_s": {"attn": {"fwd": [0.0246, 4],
                                              "bwd_dkv": [0.0254, 2],
                                              "bwd_dq": [0.0167, 2]}}},
              "window_started_at": time.time() - 60.0}
    record.update(kw)
    return record


@pytest.fixture()
def scratch(tmp_path, monkeypatch, fixture_trace):
    """A scratch directory with a trace file of `this run`; its content
    is the fixture (the readers' own parse is tested above)."""
    monkeypatch.setattr(scope_reduce, "SCRATCH", str(tmp_path))
    monkeypatch.setattr(scope_reduce, "_REDUCED", {})
    monkeypatch.setattr(scope_reduce, "from_xplane",
                        lambda path: fixture_trace)
    run = tmp_path / "train_mistral7b_d2" / "trace" / "plugins" / \
        "profile" / "2026_09_26_20_27_00"
    run.mkdir(parents=True)
    trace_file = run / "host.xplane.pb"
    trace_file.write_bytes(b"")
    return trace_file


@pytest.mark.parametrize("name", TRACE_READERS)
def test_reader_without_a_trace_of_this_run(scratch, name):
    read = load_module("layer_metrics", name).read
    assert read(_record()) is not None
    # a run that traced nothing (--trace 0, or a rehearsal on the CPU)
    assert read(_record(trace=None)) is None
    assert read(_record(trace={"devices": 0})) is None
    # a trace directory an earlier run left: older than this run's window
    os.utime(scratch, (time.time() - 3600, time.time() - 3600))
    assert read(_record()) is None
    scratch.unlink()
    assert read(_record()) is None


def test_reader_on_a_program_without_the_scopes(scratch, monkeypatch,
                                                fixture_trace):
    bare = json.loads(json.dumps(fixture_trace))
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [
                e[:3] + [""] if len(e) == 4 else e for e in line["events"]
                if not str(e[0]).startswith("train.")]
    monkeypatch.setattr(scope_reduce, "from_xplane", lambda path: bare)
    for name in TRACE_READERS:
        assert load_module("layer_metrics", name).read(_record()) is None


def test_readers_on_the_recorded_step(scratch):
    values = {name: load_module("layer_metrics", name).read(_record())
              for name in TRACE_READERS}
    assert 40 < values["mlp_share"] < 46
    assert 18 < values["head_share"] < 22
    assert 10 < values["attn_proj_share"] < 14
    assert 1 < values["attn_glue_share"] < 4
    assert 4 < values["optimizer_share"] < 6
    assert 15 < values["recompute_share"] < 21
    assert 0 <= values["unscoped_share"] < 5
    assert 0 < values["report_wait_ms"] < 1


@pytest.mark.parametrize("name", GANG_READERS)
def test_gang_reader(monkeypatch, name):
    import ray_tpu

    def span(name, ts, dur, gang):
        return {"ph": "X", "cat": "span", "name": name, "pid": "driver",
                "tid": 1, "ts": ts, "dur": dur, "args": {"gang": gang}}

    events = [span("train.gang.placement", 0, 50_000, "train:a"),
              span("train.gang.actors", 50_000, 900_000, "train:a"),
              span("train.gang.visibility", 950_000, 1_000, "train:a"),
              span("train.gang.backend", 951_000, 11_000_000, "train:a"),
              span("train.gang.sessions", 11_951_000, 8_000, "train:a"),
              # an elastic re-form later in the run is another gang
              span("train.gang.actors", 60_000_000, 700_000, "train:b"),
              span("train.gang.backend", 61_000_000, 9_000_000, "train:b"),
              {"ph": "M", "name": "process_name", "pid": "driver"}]
    monkeypatch.setattr(ray_tpu, "timeline", lambda **kw: events)
    read = load_module("layer_metrics", name).read
    want = {"gang_workers_s": 0.95, "gang_backend_s": 11.0}[name]
    assert read({}) == pytest.approx(want)
    # a program that records no such span, or cannot serve after shutdown
    monkeypatch.setattr(ray_tpu, "timeline", lambda **kw: events[-1:])
    assert read({}) is None

    def no_cluster(**kw):
        raise RuntimeError("ray_tpu.init() has not been called")

    monkeypatch.setattr(ray_tpu, "timeline", no_cluster)
    assert read({}) is None


def test_spec_contract_with_the_ten_new_entries():
    path = os.path.join(BENCH_DIR, "selfcheck.py")
    spec = importlib.util.spec_from_file_location("_selfcheck_scopes", path)
    selfcheck = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selfcheck)
    selfcheck.check_spec_contract()
    names = [m["name"] for m in load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]]
    # PR 24's ten are there, in order, after the nine before them; later
    # PRs add theirs behind
    first = BEFORE_PR_24 + TRACE_READERS + GANG_READERS
    assert names[:len(first)] == first
    assert len(set(names)) == len(names)
