"""benchmark/benchlib/setup_spans.py and the ten readers on top of it (PR
55), on a synthetic merged timeline whose every number is set by hand: a
driver, two workers of a first gang (and a second gang that must not be
read), and rank 0's traces, lowerings, cache loads and compiles before,
inside and after the window."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import setup_spans  # noqa: E402
from benchlib.spec import load_json, load_module  # noqa: E402

DRIVER, W0, W1 = "driver-99", "worker-00000000", "worker-11111111"
LOOP, OTHER = 7, 9
T0 = 1_790_000_000.0     # the window's start, wall seconds
SETUP_S, WINDOW_S = 100.0, 10.0
START = T0 - SETUP_S


def _span(pid, tid, span_name, start_s, dur_s, **args):
    return {"ph": "X", "cat": "span", "name": span_name, "pid": pid,
            "tid": tid, "ts": start_s * 1e6, "dur": dur_s * 1e6,
            "args": args}


def _gang(events, gang, at, scale):
    """A formation: the driver's three spans and two workers' four."""
    events.append(_span(DRIVER, 1, "train.gang.placement", at, 0.1,
                        gang=gang, workers=2, tpus=1))
    events.append(_span(DRIVER, 1, "train.gang.actors", at + 0.1, 0.7,
                        gang=gang, workers=2, tpus=1))
    events.append(_span(DRIVER, 1, "train.gang.backend", at + 1.0,
                        9.0 * scale, gang=gang, workers=2, tpus=1))
    t = at + 1.1
    for pid, rank, (imp, wait, start, dist) in (
            (W0, 0, (2.0, 0.0, 5.0, 0.5)), (W1, 1, (2.5, 3.0, 4.0, 0.6))):
        events.append(_span(pid, LOOP, "train.worker.jax_import", t,
                            imp * scale, rank=rank, gang=gang, cached=False))
        events.append(_span(pid, LOOP, "train.worker.distributed_init",
                            t + 3 * scale, dist * scale, rank=rank,
                            gang=gang, processes=2))
        # a reused process: the second import costs nothing
        events.append(_span(pid, LOOP, "train.worker.jax_import",
                            t + 4 * scale, 0.0, rank=rank, gang=gang,
                            cached=True))
        events.append(_span(pid, LOOP, "train.worker.chip_wait",
                            t + 4 * scale, wait * scale + 0.001, rank=rank,
                            gang=gang, waited_s=wait * scale, busy=[]))
        events.append(_span(pid, LOOP, "train.worker.tpu_start",
                            t + 4 * scale + wait * scale + 0.001,
                            start * scale, rank=rank, gang=gang, devices=1,
                            platform="tpu"))


def synthetic_timeline():
    events = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
               "args": {"name": pid, "dropped": 0}}
              for pid in (DRIVER, W0, W1)]
    events.append(_span(DRIVER, 1, "cluster.init", START + 0.2, 0.9,
                        address="local", nodes=1))
    _gang(events, "train:a", START + 2.0, 1.0)
    _gang(events, "train:b", T0 + 60.0, 3.0)   # a later formation
    t = START + 20.0
    jax = [   # rank 0's set-up, on the thread that dispatched
        ("jax.trace", t, 2.0, dict(fun="step", region="untracked")),
        ("jax.trace", t + 0.5, 0.5, dict(fun="matmul", region="untracked")),
        ("jax.lower", t + 2.0, 1.0, dict(fun="jit(step)",
                                         region="untracked")),
        ("jax.compile", t + 3.0, 4.0, dict(
            fun="jit(step)", region="untracked", cache="hit",
            retrieval_s=3.5)),
        ("jax.compile", t + 8.0, 10.0, dict(
            fun="jit(reference)", region="untracked", cache="miss")),
        ("jax.compile", t + 19.0, 0.5, dict(
            fun="jit(convert_element_type)", region="untracked",
            cache="small")),
        ("jax.compile", t + 20.0, 0.25, dict(
            fun="jit(iota)", region="train.step", cache="off")),
        # what events under 1 ms were summed to: `dur` is where they lay
        ("jax.trace", t + 21.0, 0.9, dict(folded_n=100, folded_s=0.02)),
        ("jax.compile", t + 21.0, 0.8, dict(folded_n=7, folded_s=0.004,
                                            cache="small")),
    ]
    for span_name, start, dur, args in jax:
        events.append(_span(W0, LOOP, span_name, start, dur, **args))
    # another thread of rank 0 lowers meanwhile: its own union
    events.append(_span(W0, OTHER, "jax.lower", t + 2.5, 0.25,
                        fun="jit(other)", region="untracked"))
    # the other worker's are not rank 0's
    events.append(_span(W1, LOOP, "jax.compile", t, 50.0, fun="jit(step)",
                        region="untracked", cache="miss"))
    # the window: a shape changed in a step of the loop thread; another
    # thread's compile is not the loop's; the traced steps' is too late
    for k in range(12):
        events.append(_span(W0, LOOP, "train.step", T0 + k, 0.001))
    events.append(_span(W0, LOOP, "jax.compile", T0 + 4.1, 0.7,
                        fun="jit(step)", region="train.step", cache="hit",
                        retrieval_s=0.6))
    events.append(_span(W0, OTHER, "jax.compile", T0 + 5.0, 0.01,
                        fun="jit(other)", region="untracked", cache="off"))
    events.append(_span(W0, LOOP, "jax.compile", T0 + WINDOW_S + 3.0, 0.2,
                        fun="jit(late)", region="train.step", cache="off"))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def record_of():
    return {"window_started_at": T0, "end_to_end": {"setup_s": SETUP_S},
            "clock": {"window_s": WINDOW_S, "step_s": [1.0] * 10}}


HAND = {
    "cluster_init_s": 0.9,
    # the slowest worker of the FIRST gang, each span's own slowest
    "gang_jax_import_s": 2.5,
    "gang_chip_wait_s": 3.0,
    "gang_tpu_start_s": 5.5,     # rank 0: 5 to start, 0.5 to initialize
    # loop thread [t, t+3] (the nested trace adds nothing), the other
    # thread's quarter second, and the hundred short ones' 20 ms
    "setup_trace_lower_s": 3.0 + 0.25 + 0.02,
    "setup_cache_load_s": 4.0,
    "setup_backend_compile_s": 10.0 + 0.5 + 0.25 + 0.004,
    "setup_small_compile_s": 0.5 + 0.004,
    "setup_cache_misses": 1,
    "window_compiles": 1,
}


def test_hand_computed_split():
    got = setup_spans.setup_metrics(synthetic_timeline(), T0, SETUP_S,
                                    WINDOW_S)
    assert not [k for k in got if k.startswith("why")], got
    assert sorted(HAND) == sorted(setup_spans.NAMES)
    for name, want in HAND.items():
        assert got[name] == pytest.approx(want, rel=1e-9), name
    assert got["gang"] == "train:a" and got["workers"] == 2
    assert got["rank0"] == W0
    assert got["gang_backend_s"] == pytest.approx(9.0)
    # rank 1's spans add up to 2.5 + 0.6 + 3.001 + 4 of the driver's 9
    assert got["gang_backend_remainder_s"] == pytest.approx(9.0 - 10.101)
    assert got["compile_counts"] == {"hit": 1, "miss": 1, "small": 8,
                                     "off": 1}
    assert got["compile_events"] == 11
    assert got["compile_s"] == pytest.approx(14.754)
    assert [r["fun"] for r in got["longest_compiles"]][:2] == [
        "jit(reference)", "jit(step)"]
    text = setup_spans.format_split(got, SETUP_S)
    assert "setup_cache_misses" in text and "jit(reference)" in text


@pytest.mark.parametrize("name", sorted(HAND))
def test_reader_reads_the_set_up(monkeypatch, name):
    import ray_tpu
    setup_spans._CACHE.clear()
    monkeypatch.setattr(ray_tpu, "timeline",
                        lambda *a, **kw: synthetic_timeline())
    reader = load_module("layer_metrics", name)
    assert reader.read(record_of()) == pytest.approx(HAND[name], rel=1e-9)
    setup_spans._CACHE.clear()


@pytest.mark.parametrize("name", sorted(HAND))
def test_reader_says_why_on_a_program_without_the_spans(monkeypatch, name):
    """The parent of PR 55: its gang's three driver spans, its loop's
    `train.step`, and none of the names this reads."""
    import ray_tpu
    setup_spans._CACHE.clear()
    parent = [e for e in synthetic_timeline() if e.get("ph") == "M"
              or not e["name"].startswith(("cluster.", "train.worker.",
                                           "jax."))]
    monkeypatch.setattr(ray_tpu, "timeline", lambda *a, **kw: parent)
    reader = load_module("layer_metrics", name)
    assert reader.read(record_of()) is None
    why = reader.why_nothing(record_of())
    want = {"cluster_init_s": "no `cluster.init` span"}.get(
        name, "no `train.worker.*` span of gang train:a"
        if name.startswith("gang_") else "recorded no `jax.*` span")
    assert want in why, why
    setup_spans._CACHE.clear()


def test_zero_is_a_reading():
    """A warm run: every program loaded, nothing compiled in the window,
    no chip to wait for."""
    events = [e for e in synthetic_timeline() if e.get("ph") == "M"
              or not (e["name"] == "jax.compile"
                      and e["args"].get("cache") != "hit")
              and not (e["name"] == "jax.compile" and e["ts"] / 1e6 >= T0)]
    for e in events:
        if e.get("name") == "train.worker.chip_wait":
            e["args"]["waited_s"] = 0.0
    got = setup_spans.setup_metrics(events, T0, SETUP_S, WINDOW_S)
    for name in ("gang_chip_wait_s", "setup_backend_compile_s",
                 "setup_small_compile_s", "setup_cache_misses",
                 "window_compiles"):
        assert got[name] == 0 and got[name] is not None, name
    assert got["setup_cache_load_s"] == pytest.approx(4.0)


def test_a_gang_without_chips_reads_its_import_alone():
    events = [e for e in synthetic_timeline() if e.get("ph") == "M"
              or e["name"] not in ("train.worker.chip_wait",
                                   "train.worker.tpu_start",
                                   "train.worker.distributed_init")]
    got = setup_spans.setup_metrics(events, T0, SETUP_S, WINDOW_S)
    assert got["gang_jax_import_s"] == pytest.approx(2.5)
    assert "gang_chip_wait_s" not in got and "gang_tpu_start_s" not in got
    assert "given no chip" in got["why_gang_chip_wait_s"]


def test_a_wrapped_ring_reads_nothing_of_the_set_up():
    events = synthetic_timeline()
    for e in events:
        if e.get("ph") == "M" and e["pid"] == W0:
            e["args"]["dropped"] = 4321
    got = setup_spans.setup_metrics(events, T0, SETUP_S, WINDOW_S)
    assert "dropped 4321 records" in got["why_compile"]
    assert "setup_cache_load_s" not in got and "window_compiles" not in got
    assert got["cluster_init_s"] == pytest.approx(0.9)   # the driver's


def test_candidate_entries_fit_the_contract():
    """The ten entries the next PR appends to BENCHMARK.json (its parent
    then has the spans: PERF.md section 7). None is in it yet."""
    found = load_json(os.path.join(BENCH_DIR, "candidates",
                                   "setup_metrics.json"))
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in found["per_layer"]] == list(setup_spans.NAMES)
    taken = {m["name"] for m in spec["per_layer"] + spec["end_to_end"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in found["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] in ("train gang", "process set-up")
        assert m["moves"] in e2e
        assert m["moves"] == ("train_tokens_per_s"
                              if m["name"] == "window_compiles"
                              else "setup_s")
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
        assert m["name"] not in taken
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))


def test_window_entries_are_in_the_spec_as_their_candidates_file_holds_them():
    """PR 37's six window metrics are entries of BENCHMARK.json since PR
    55: one run of per_layer, each once, as
    candidates/train_loop_metrics.json holds them (a file of the
    benchmark that PR 55 may not edit, so it still lists them, and
    test_window_spans.test_candidate_entries_fit_the_contract, which
    pins them as NOT taken, is red until a benchmark PR lets it follow:
    ROADMAP D10 (i)). Found by name, not pinned as the last entries."""
    found = load_json(os.path.join(BENCH_DIR, "candidates",
                                   "train_loop_metrics.json"))["per_layer"]
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(found[0]["name"])
    assert spec["per_layer"][at:at + len(found)] == found
    taken = names + [m["name"] for m in spec["end_to_end"]]
    for m in found:
        assert taken.count(m["name"]) == 1 and "workloads" not in m
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))


def test_main_prints_the_split(tmp_path, capsys):
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps(synthetic_timeline()))
    assert setup_spans.main(["setup_spans", str(path), str(T0),
                             str(SETUP_S), str(WINDOW_S)]) == 0
    out = capsys.readouterr().out
    assert "setup_s 100.000" in out and "gang_tpu_start_s" in out
    assert "10.000 s  miss  jit(reference)" in out
