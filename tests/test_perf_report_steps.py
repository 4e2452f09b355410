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


# ---- a compile inside a step is named with the step (PR 55) -------------


def test_a_shape_that_changes_in_a_step_is_named_by_its_compile():
    """A loop whose batch changes its shape in the fourth step: that
    step's excess lies in `train.step` (the dispatch compiled), and what
    names it is the loop thread's own `jax.compile` of the step's
    function, with the cache's outcome; the benchmark's reader counts one
    compile in the window."""
    import os
    import sys
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import spans
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import setup_spans

    def loss(params, batch):
        x = batch["x"]
        for i in range(12):   # enough for the compiler to take a while
            x = jnp.tanh(x @ params["w"]) + jnp.cos(x + i)
        return jnp.mean(x * x), {}

    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_state, train_step = make_train_step(
        loss, {"w": (None, None)}, mesh)
    state = init_state({"w": jnp.eye(32) * 0.5})
    batches = {n: {"x": jnp.ones((n, 32))} for n in (8, 16)}
    state, metrics = train_step(state, batches[8])   # warm-up
    float(metrics["loss"])
    t_setup = time.time()
    time.sleep(0.02)
    window_started_at = time.time()
    for step in range(10):
        state, metrics = train_step(state, batches[16 if step == 3 else 8])
        float(metrics["loss"])
        time.sleep(0.02)
    window_s = time.time() - window_started_at
    events = spans.merge_snapshots([spans.snapshot()])
    me = spans.process_label()
    events = [e for e in events if e.get("ph") == "M"
              or e["ts"] / 1e6 >= t_setup]

    report = perf_report.steps_report(events, process=me)
    assert report is not None
    stall = max(report["stalls"], key=lambda s: s["excess_s"])
    assert stall["step"] == 3
    assert stall["lay"]["train.step"] > 0.5 * stall["excess_s"]
    named = [o for o in stall["overlapped"]
             if o["name"].startswith("jax.compile:")]
    assert [o["name"] for o in named] == ["jax.compile:jit(_step)[off]"]
    assert named[0]["over_usual_s"] > 0
    assert stall["named_s"] >= named[0]["over_usual_s"] - 1e-9
    assert "jax.compile:jit(_step)[off]" in perf_report.format_steps(report)

    got = setup_spans.setup_metrics(events, window_started_at,
                                    window_started_at - t_setup, window_s)
    assert got["window_compiles"] == 1
    assert got["setup_backend_compile_s"] == 0   # the warm-up was before


def test_compile_is_a_bucket_above_learner_compute():
    events = [
        {"ph": "X", "cat": "span", "name": "learner.update", "pid": "p",
         "tid": 1, "ts": 0.0, "dur": 10e6, "args": {}},
        {"ph": "X", "cat": "span", "name": "jax.trace", "pid": "p",
         "tid": 1, "ts": 1e6, "dur": 1e6, "args": {"fun": "f"}},
        {"ph": "X", "cat": "span", "name": "jax.lower", "pid": "p",
         "tid": 1, "ts": 2e6, "dur": 1e6, "args": {"fun": "jit(f)"}},
        {"ph": "X", "cat": "span", "name": "jax.compile", "pid": "p",
         "tid": 1, "ts": 3e6, "dur": 2e6,
         "args": {"fun": "jit(f)", "cache": "miss"}},
        {"ph": "X", "cat": "span", "name": "host_sync.float", "pid": "p",
         "tid": 1, "ts": 4.5e6, "dur": 1e6, "args": {}},
    ]
    report = perf_report.attribute(events)
    seconds = {b: r["seconds"] for b, r in report["buckets"].items()}
    assert seconds["compile"] == pytest.approx(3.5)   # the sync outranks
    assert seconds["host_sync"] == pytest.approx(1.0)
    assert seconds["learner_compute"] == pytest.approx(5.5)
    assert report["goodput"]["buckets"]["compile"] == pytest.approx(3.5)
