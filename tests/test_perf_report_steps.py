"""`tools/perf_report.py --steps`: the loop view of a train loop, on the
synthetic merged timeline of tests/benchmark/test_window_spans.py (40
steps of 250 ms in the window but two of 600 ms, one under the worker's
`gc.collect`, one under nothing). The benchmark's copy of the arithmetic
(`benchlib/window_spans.py`) must agree with the operator's tool."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import perf_report  # noqa: E402


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


synthetic = _load("_synthetic_window", os.path.join(
    ROOT, "tests", "benchmark", "test_window_spans.py"))
def near(x):
    """Wall-clock microseconds as floats: a quarter of one is the grain."""
    return pytest.approx(x, abs=2e-6)


LO = synthetic.T0 - 0.010
HI = LO + synthetic.WINDOW_S + 0.020


@pytest.fixture(scope="module")
def report():
    return perf_report.steps_report(synthetic.synthetic_timeline(),
                                    lo_s=LO, hi_s=HI)


def test_picks_the_loop_thread_and_the_median(report):
    assert (report["process"], report["thread"]) == (
        synthetic.WORKER, str(synthetic.LOOP))
    assert report["steps"] == 40
    assert report["median_period_s"] == near(0.25)
    assert report["total_s"] == near(10.7)
    usual = report["usual_parts_s"]
    assert usual["train.step"] == near(0.001)
    assert usual["host_sync"] == near(0.240)
    assert usual["train.report"] == near(0.0001)
    assert usual["other"] == near(0.25 - 0.2411)
    assert report["usual_cpu_s"] == 0.005


def test_stall_under_the_collector_is_named(report):
    stall = report["stalls"][0]
    assert stall["step"] == 10 and stall["at_s"] == near(2.5)
    assert stall["period_s"] == near(0.6)
    assert stall["excess_s"] == near(0.35)
    assert stall["lay"]["host_sync"] == near(0.35)
    assert [(o["name"], o["process"]) for o in stall["overlapped"]] == [
        ("gc.collect", synthetic.WORKER),
        ("task.run:next_result", synthetic.WORKER),
        ("rpc.server:cw_push_task", synthetic.WORKER)]
    assert stall["overlapped"][0]["seconds"] == near(0.35)
    assert [o["over_usual_s"] for o in stall["overlapped"]] == [
        near(0.35), near(0.0), near(0.0)]
    assert stall["named_s"] == near(0.35)
    assert stall["unnamed_s"] == near(0.0)


def test_stall_under_nothing_carries_the_threads_usage(report):
    stall = report["stalls"][1]
    assert stall["step"] == 25
    # the step's usual 2 ms handler and the `task.run` that waits for the
    # loop through the whole run overlap it and name none of its excess
    assert [o["name"] for o in stall["overlapped"]] == [
        "task.run:next_result", "rpc.server:cw_push_task"]
    assert all(o["over_usual_s"] == near(0.0) for o in stall["overlapped"])
    assert stall["named_s"] == near(0.0)
    assert stall["unnamed_s"] == near(0.35)
    assert (stall["cpu_s"], stall["ivcsw"]) == (0.005, 3)
    assert report["stall_s"] == near(0.70)
    assert report["unnamed_s"] == near(0.35)


def test_agrees_with_the_benchmarks_copy(report):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from benchlib import window_spans
    events = synthetic.synthetic_timeline()
    spans = window_spans.span_events(events)
    theirs = window_spans.loop_report(
        spans, window_spans.pick_loop_thread(spans), LO, HI)
    for key in ("median_period_s", "total_s", "stall_s", "unnamed_s"):
        assert theirs[key] == near(report[key])
    assert [s["step"] for s in theirs["stalls"]] == \
        [s["step"] for s in report["stalls"]]
    assert (perf_report.STALL_FACTOR, perf_report.OVERLAP_MIN_S) == (
        window_spans.STALL_FACTOR, window_spans.OVERLAP_MIN_S)


def test_whole_ring_without_a_range_sees_the_profilers_gap():
    """Without a range the steps run on into the traced ones: the step
    that spans the profiler's start (3 s) is the worst stall."""
    whole = perf_report.steps_report(synthetic.synthetic_timeline())
    assert whole["steps"] == 2 + 40 + 6
    assert max(s["period_s"] for s in whole["stalls"]) == near(3.0)


def test_cli_steps_and_the_old_report_stay(tmp_path, capsys):
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps(synthetic.synthetic_timeline()))
    assert perf_report.main([str(path), "--steps"]) == 0
    out = capsys.readouterr().out
    assert "loop report" in out and "gc.collect" in out
    assert "nothing of 1 ms or more overlapped it" not in out
    assert perf_report.main([str(path), "--steps", "--format=json"]) == 0
    assert json.loads(capsys.readouterr().out)["stalls"]
    # the existing buckets and output: host_sync outranks the rest
    assert perf_report.main([str(path), "--process", synthetic.WORKER,
                             "--thread", str(synthetic.LOOP)]) == 0
    out = capsys.readouterr().out
    assert "perf report" in out and "host_sync" in out


def test_no_train_loop_in_the_trace(tmp_path):
    events = [e for e in synthetic.synthetic_timeline()
              if e.get("name") != "train.step"]
    assert perf_report.steps_report(events) is None
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps(events))
    with pytest.raises(SystemExit):
        perf_report.main([str(path), "--steps"])
