"""benchmark/benchlib/window_spans.py and the six readers on top of it
(PR 37), on a synthetic merged timeline whose every number is set by hand:
40 whole steps of 250 ms in the window but two of 600 ms, one under a
`gc.collect` span of the worker and one under nothing."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import trace_reduce, window_spans  # noqa: E402
from benchlib.spec import load_json, load_module  # noqa: E402

WORKER, DRIVER = "worker-0a1b2c3d", "driver-99"
LOOP, OTHER, RPC = 7, 9, 11
T0 = 1_790_000_000.0          # the first window step's start, wall seconds
PERIOD, STALL = 0.25, 0.6
STALLS = {10: "gc", 25: None}  # window step -> what lies over it
WINDOW_STEPS = 41              # `train.step` starts inside the window
WINDOW_S = 38 * PERIOD + 2 * STALL   # 10.7: the 40 whole steps
READERS = ["window_stall_share", "window_stall_unnamed_share",
           "worker_gc_share", "sync_wait_max_over_median",
           "report_wait_max_ms"]


def _span(pid, tid, span_name, start_s, dur_s, **args):
    return {"ph": "X", "cat": "span", "name": span_name, "pid": pid,
            "tid": tid,
            "ts": start_s * 1e6, "dur": dur_s * 1e6, "args": args}


def synthetic_timeline():
    """Two warm-up steps, the window's 41 `train.step` starts, then five
    traced steps three seconds later (the profiler's start)."""
    events = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
               "args": {"name": pid, "dropped": 0}}
              for pid in (WORKER, DRIVER)]
    starts = [T0 - 2 * PERIOD, T0 - PERIOD]
    t = T0
    for i in range(WINDOW_STEPS):
        starts.append(t)
        t += STALL if i in STALLS else PERIOD
    window_end = starts[-1]
    starts += [window_end + 3.0 + k * PERIOD for k in range(6)]
    for n, start in enumerate(starts):
        i = n - 2                     # index among the window's steps
        wait = (STALL if i in STALLS else PERIOD) - 0.010
        events.append(_span(WORKER, LOOP, "train.step", start, 0.001,
                            cpu_s=0.005, ivcsw=3 if i - 1 == 25 else 0))
        events.append(_span(WORKER, LOOP, "host_sync.float", start + 0.002,
                            wait, region="after:train.step", bytes=4))
        events.append(_span(
            WORKER, LOOP, "train.report", start + 0.003 + wait, 0.0001,
            rank=0, blocked_s=0.0004 if i == 5 else 0.00005))
        # every step: a 2 ms handler on a server thread of the worker
        events.append(_span(WORKER, RPC, "rpc.server", start + 0.004, 0.002,
                            method="cw_push_task", sampled=1))
        if STALLS.get(i) == "gc":
            events.append(_span(WORKER, OTHER, "gc.collect", start + 0.100,
                                0.350, generation=2, collected=12,
                                thread="rpc-server-1"))
    # a wait around the whole run names no step; a short span names none
    events.append(_span(WORKER, OTHER, "task.run", T0 - 1.0, 30.0,
                        name="next_result"))
    events.append(_span(DRIVER, 1, "cw.get", T0 + 1.0, 0.0005))
    events.append(_span(WORKER, OTHER, "gc.collect", T0 - 0.3, 0.020,
                        generation=2, collected=3, thread="MainThread"))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def record_of(steps=WINDOW_STEPS):
    return {"window_started_at": T0 - 0.010,
            "clock": {"window_s": WINDOW_S + 0.020,
                      "step_s": [PERIOD] * steps}}


HAND = {
    # two stalls of 350 ms over 10.7 s of steps
    "window_stall_share": 100 * 0.70 / WINDOW_S,
    # the one under nothing; the other is the collector's to the last ms
    "window_stall_unnamed_share": 100 * 0.35 / WINDOW_S,
    "worker_gc_share": 100 * 0.35 / WINDOW_S,
    # a stall step waits 590 ms, the median step 240
    "sync_wait_max_over_median": 0.590 / 0.240,
    "report_wait_max_ms": 0.4,
}


def test_hand_computed_metrics():
    rec = record_of()
    got = window_spans.window_metrics(
        synthetic_timeline(), rec["window_started_at"],
        rec["clock"]["window_s"], WINDOW_STEPS)
    assert "why" not in got
    for name, want in HAND.items():
        assert got[name] == pytest.approx(want, rel=1e-6), name
    report = got["report"]
    assert report["marks"] == WINDOW_STEPS and len(report["steps"]) == 40
    assert report["median_period_s"] == pytest.approx(PERIOD)
    first, second = report["stalls"]
    assert (first["step"], second["step"]) == (10, 25)
    assert first["excess_s"] == pytest.approx(0.35)
    assert first["lay"]["host_sync"] == pytest.approx(0.35)
    assert abs(first["lay"]["other"]) < 1e-6
    assert first["overlapped"][0]["name"] == "gc.collect"
    assert first["overlapped"][0]["seconds"] == pytest.approx(0.35)
    assert first["overlapped"][0]["over_usual_s"] == pytest.approx(0.35)
    assert first["named_s"] == pytest.approx(0.35)
    # the wait around the run and the step's usual 2 ms handler overlap
    # the second stall and name none of it
    assert sorted(o["name"] for o in second["overlapped"]) == [
        "rpc.server:cw_push_task", "task.run:next_result"]
    assert all(o["over_usual_s"] == pytest.approx(0.0, abs=1e-6)
               for o in second["overlapped"])
    assert second["unnamed_s"] == pytest.approx(0.35)
    assert (second["cpu_s"], second["ivcsw"]) == (0.005, 3)
    text = window_spans.format_loop(report)
    assert "step 10 at 2.500 s" in text and "gc.collect" in text
    assert "unnamed 350.0 ms" in text


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_window(monkeypatch, name):
    import ray_tpu
    window_spans._CACHE.clear()
    monkeypatch.setattr(ray_tpu, "timeline",
                        lambda *a, **kw: synthetic_timeline())
    reader = load_module("layer_metrics", name)
    assert reader.read(record_of()) == pytest.approx(HAND[name], rel=1e-6)
    window_spans._CACHE.clear()


@pytest.mark.parametrize("name", READERS)
def test_reader_says_why_when_the_counts_disagree(monkeypatch, name):
    import ray_tpu
    window_spans._CACHE.clear()
    monkeypatch.setattr(ray_tpu, "timeline",
                        lambda *a, **kw: synthetic_timeline())
    reader = load_module("layer_metrics", name)
    rec = record_of(steps=WINDOW_STEPS + 4)
    assert reader.read(rec) is None
    why = reader.why_nothing(rec)
    assert "41 `train.step` spans" in why and "counted 45 steps" in why
    window_spans._CACHE.clear()


def test_a_wrapped_ring_is_named(monkeypatch):
    events = [e for e in synthetic_timeline()
              if e.get("ph") == "M" or e["ts"] / 1e6 >= T0 + 5.0]
    for e in events:
        if e.get("ph") == "M" and e["pid"] == WORKER:
            e["args"]["dropped"] = 1234
    got = window_spans.window_metrics(events, T0 - 0.01, WINDOW_S + 0.02,
                                      WINDOW_STEPS)
    assert "dropped 1234 records" in got["why"]


def test_a_program_without_the_rings_reads_nothing(monkeypatch):
    """The parent of PR 37: the driver's own ring alone."""
    import ray_tpu
    window_spans._CACHE.clear()
    driver_only = [e for e in synthetic_timeline() if e["pid"] == DRIVER]
    monkeypatch.setattr(ray_tpu, "timeline", lambda *a, **kw: driver_only)
    for name in READERS:
        reader = load_module("layer_metrics", name)
        assert reader.read(record_of()) is None
        assert "no `train.step` span" in reader.why_nothing(record_of())
    lag = load_module("layer_metrics", "host_sync_lag_ms")
    assert lag.read({"trace": None}) is None and lag.why_nothing({})
    window_spans._CACHE.clear()


def test_sync_lag_on_one_clock():
    """Three traced steps on two chips: the wait ends 0.2, 0.3 and 0.4 ms
    after the later chip's program; a second read of each step begins
    after the device is done and counts for nothing."""
    ms = 1e6
    host, chips = [["bench_window", 0.0, 1000 * ms]], ([], [])
    for k, lag in enumerate((0.2, 0.3, 0.4)):
        base = (10 + 300 * k) * ms
        chips[0].append(["jit__step", base, 250 * ms])
        chips[1].append(["jit__step", base + 1 * ms, 251 * ms])
        done = base + 252 * ms
        host.append(["host_sync.float", base + 2 * ms,
                     done + lag * ms - (base + 2 * ms)])
        host.append(["host_sync.asarray", done + 1 * ms, 0.05 * ms])
        host.append(["train.step", base - 3 * ms, 1 * ms])
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "loop", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.MODULES_LINE, "events": chips[0]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": trace_reduce.MODULES_LINE, "events": chips[1]}]}]}
    assert window_spans.sync_lag_ms(trace) == pytest.approx(0.3)
    host[:] = [e for e in host if e[0] != "host_sync.float"]
    assert window_spans.sync_lag_ms(trace) is None


def test_candidate_entries_fit_the_contract():
    """The six entries a later PR appends to BENCHMARK.json (its parent
    then has the spans: PERF.md section 7)."""
    found = load_json(os.path.join(BENCH_DIR, "candidates",
                                   "train_loop_metrics.json"))
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in found["per_layer"]]
    assert names == READERS + ["host_sync_lag_ms"]
    taken = {m["name"] for m in spec["per_layer"] + spec["end_to_end"]}
    for m in found["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] == "train loop"
        assert m["moves"] == "train_tokens_per_s"
        assert m["name"] not in taken
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))


def test_main_prints_the_table(tmp_path, capsys):
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps(synthetic_timeline()))
    assert window_spans.main(["window_spans", str(path), str(T0 - 0.01),
                              str(WINDOW_S + 0.02)]) == 0
    out = capsys.readouterr().out
    assert "40 steps over 10.700 s" in out and "step 25" in out
