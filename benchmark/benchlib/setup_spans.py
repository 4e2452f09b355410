"""`setup_s` from inside the program (PR 55): the spans the program records
while a job sets up, read from `ray_tpu.timeline(spans=True)` in run.py's
process after `shutdown()`, as `scope_reduce.gang_span_s` and
`window_spans` read theirs (the driver's own ring merged with the rings it
kept of its train workers).

The set-up is `[window_started_at - setup_s, window_started_at]` of the
job's record, on the timeline's clock (the driver's wall clock). The names
read are the program's (PERF.md section 3):

    cluster.init              the driver's `ray_tpu.init()`
    train.worker.jax_import   } on every train worker, under the driver's
    train.worker.chip_wait    } `train.gang.backend`; attrs `rank`, `gang`
    train.worker.tpu_start    } (`waited_s` on chip_wait); the SLOWEST
    train.worker.distributed_init } worker of the first gang is read
    jax.trace  jax.lower      one of each for every program the process's
    jax.compile               jit cache did not hold, on the thread that
                              dispatched it; `jax.compile` carries `cache`:
                              hit | miss | small | off. Events under 1 ms
                              are summed into records with `folded_n`,
                              `folded_s` (their `dur` is the stretch they lay
                              in, not their sum): seconds are `dur` of the
                              records without `folded_n` plus `folded_s` of
                              those with, counts likewise with `folded_n`.
                              `jax.trace` nests, so trace and lowering are
                              the union of a thread's intervals. Rank 0's
                              train worker is read.

Every reader under layer_metrics/ that reads this returns a number whenever
the spans are there (0 is a reading) and None, with `why_nothing`, on a
program without them or a ring that wrapped past the gang.

`python3 -m benchlib.setup_spans <timeline.json> <window_started_at>
<setup_s> <window_s>` (from `benchmark/`) prints the split PERF.md section
5 is written from.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchlib import trace_reduce as tr
from benchlib import window_spans

CLUSTER_SPAN = "cluster.init"
GANG_PREFIX = "train.gang."
WORKER_PREFIX = "train.worker."
TRACE_SPAN, LOWER_SPAN, COMPILE_SPAN = "jax.trace", "jax.lower", "jax.compile"
COMPILED = ("miss", "small", "off")   # outcomes that ran the compiler
WORKER_METRICS = {"jax_import": "gang_jax_import_s",
                  "chip_wait": "gang_chip_wait_s",
                  "tpu_start": "gang_tpu_start_s",
                  "distributed_init": "gang_tpu_start_s"}
NAMES = (
    "cluster_init_s", "gang_jax_import_s", "gang_chip_wait_s",
    "gang_tpu_start_s", "setup_trace_lower_s", "setup_cache_load_s",
    "setup_backend_compile_s", "setup_small_compile_s",
    "setup_cache_misses", "window_compiles")


def _args(e: Dict[str, Any]) -> Dict[str, Any]:
    return e.get("args") or {}


def _start(e: Dict[str, Any]) -> float:
    return e["ts"] / 1e6


def _seconds(e: Dict[str, Any]) -> float:
    """What a `jax.*` record stands for: its own duration, or the sum it
    carries."""
    args = _args(e)
    if "folded_n" in args:
        return float(args.get("folded_s") or 0.0)
    return e.get("dur", 0.0) / 1e6


def _count(e: Dict[str, Any]) -> int:
    return int(_args(e).get("folded_n", 1))


def _dropped(events: Sequence[Dict[str, Any]], pid: Any) -> int:
    return max((int(_args(e).get("dropped") or 0) for e in events
                if e.get("ph") == "M" and e.get("pid") == pid), default=0)


def gang_metrics(spans: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The three worker spans of the first gang, the slowest worker's of
    each, with the driver's `train.gang.backend` and its remainder."""
    gang = [e for e in spans if str(e["name"]).startswith(GANG_PREFIX)]
    if not gang:
        return {"why_gang": "the timeline holds no `train.gang.*` span"}
    first = _args(min(gang, key=_start)).get("gang")
    workers = [e for e in spans if str(e["name"]).startswith(WORKER_PREFIX)
               and _args(e).get("gang") == first]
    if not workers:
        return {"why_gang": f"no `train.worker.*` span of gang {first}: a "
                            f"program that records none, or a worker's "
                            f"ring that wrapped past its gang"}
    per: Dict[Any, Dict[str, float]] = {}
    for e in workers:
        mine = per.setdefault(e.get("pid"), {"all": 0.0})
        short = str(e["name"])[len(WORKER_PREFIX):]
        dur = e.get("dur", 0.0) / 1e6
        mine["all"] += dur
        if short == "chip_wait":
            dur = float(_args(e).get("waited_s") or 0.0)
        metric = WORKER_METRICS.get(short)
        if metric:
            mine[metric] = mine.get(metric, 0.0) + dur
    backend = sum(e.get("dur", 0.0) / 1e6 for e in gang
                  if e["name"] == GANG_PREFIX + "backend"
                  and _args(e).get("gang") == first)
    rank0 = next((e.get("pid") for e in workers
                  if _args(e).get("rank") == 0), None)
    slowest = max(w["all"] for w in per.values())
    out = {"gang": first, "workers": len(per), "rank0": rank0,
           "gang_backend_s": backend,
           "gang_backend_remainder_s": backend - slowest}
    for metric in set(WORKER_METRICS.values()):
        seen = [w[metric] for w in per.values() if metric in w]
        if seen:
            out[metric] = max(seen)
        else:   # a gang without chips waits for none and starts none
            out["why_" + metric] = (
                f"no worker of gang {first} recorded the span this reads: "
                f"a gang that was given no chip")
    return out


def compile_metrics(spans: Sequence[Dict[str, Any]], pid: Any,
                    loop_tid: Any, lo: float, hi: float,
                    window_s: float) -> Dict[str, Any]:
    """The `jax.*` records of one process that start in the set-up
    [lo, hi), and the `jax.compile` records of its loop thread that start
    in the window [hi, hi + window_s]."""
    mine = [e for e in spans if e.get("pid") == pid
            and e["name"] in (TRACE_SPAN, LOWER_SPAN, COMPILE_SPAN)]
    if not mine:
        return {"why_compile": f"{pid} recorded no `jax.*` span: a program "
                               f"without them"}
    before = [e for e in mine if lo <= _start(e) < hi]
    by_thread: Dict[Any, List[Tuple[float, float]]] = {}
    folded_front = 0.0
    for e in before:
        if e["name"] == COMPILE_SPAN:
            continue
        if "folded_n" in _args(e):
            folded_front += _seconds(e)
        else:
            by_thread.setdefault(e.get("tid"), []).append(
                (_start(e), _start(e) + e.get("dur", 0.0) / 1e6))
    compiles = [e for e in before if e["name"] == COMPILE_SPAN]

    def seconds(outcomes: Sequence[str]) -> float:
        return sum(_seconds(e) for e in compiles
                   if _args(e).get("cache") in outcomes)

    def count(outcomes: Sequence[str]) -> int:
        return sum(_count(e) for e in compiles
                   if _args(e).get("cache") in outcomes)

    longest = sorted((e for e in compiles if "folded_n" not in _args(e)),
                     key=lambda e: -e.get("dur", 0.0))[:5]
    return {
        "setup_trace_lower_s": folded_front + sum(
            tr.total(tr.union(iv)) for iv in by_thread.values()),
        "setup_cache_load_s": seconds(("hit",)),
        "setup_backend_compile_s": seconds(COMPILED),
        "setup_small_compile_s": seconds(("small",)),
        "setup_cache_misses": count(("miss",)),
        "window_compiles": sum(
            _count(e) for e in mine if e["name"] == COMPILE_SPAN
            and e.get("tid") == loop_tid
            and hi <= _start(e) <= hi + window_s),
        "compile_counts": {o: count((o,))
                           for o in ("hit",) + COMPILED if count((o,))},
        "compile_events": sum(_count(e) for e in compiles),
        "compile_s": sum(_seconds(e) for e in compiles),
        "records": len(before),
        "longest_compiles": [
            {"fun": _args(e).get("fun"), "cache": _args(e).get("cache"),
             "s": e.get("dur", 0.0) / 1e6} for e in longest]}


def setup_metrics(events: Sequence[Dict[str, Any]],
                  window_started_at: float, setup_s: float,
                  window_s: float) -> Dict[str, Any]:
    """Every reading of this module from one merged timeline; a metric
    without one has a `why_<metric>` (or a `why_gang` / `why_compile`)."""
    spans = window_spans.span_events(events)
    hi, lo = window_started_at, window_started_at - setup_s
    out: Dict[str, Any] = {}
    init = [e for e in spans if e["name"] == CLUSTER_SPAN
            and lo - 1.0 <= _start(e) < hi]
    if init:
        out["cluster_init_s"] = min(init, key=_start).get("dur", 0.0) / 1e6
    else:
        out["why_cluster_init_s"] = (
            "no `cluster.init` span in this run's set-up: a program that "
            "records none")
    out.update(gang_metrics(spans))
    loop = window_spans.pick_loop_thread(spans)
    pid = out.get("rank0") or (loop[0] if loop else None)
    if pid is None:
        out["why_compile"] = ("the timeline holds no train worker's ring: a "
                              "program that keeps none past its gang")
        return out
    dropped = _dropped(events, pid)
    if dropped:
        out["why_compile"] = (
            f"the ring of {pid} had dropped {dropped} records: it wrapped, "
            f"and the set-up's sums would lack what it lost")
        return out
    loop_tid = loop[1] if loop and loop[0] == pid else None
    out.update(compile_metrics(spans, pid, loop_tid, lo, hi, window_s))
    return out


# ---- what the readers call ----------------------------------------------

_CACHE: Dict[Any, Dict[str, Any]] = {}


def for_record(record: Dict[str, Any]) -> Dict[str, Any]:
    """This run's readings, computed once a process; never raises."""
    cache_key = record.get("window_started_at")
    if cache_key in _CACHE:
        return _CACHE[cache_key]
    try:
        import ray_tpu
        events = ray_tpu.timeline(spans=True)
        out = setup_metrics(
            events, float(record["window_started_at"]),
            float(record["end_to_end"]["setup_s"]),
            float(record["clock"]["window_s"]))
    except Exception as e:  # noqa: BLE001 - a program without the accessor
        out = {"why": f"no timeline of this run: {type(e).__name__}: {e}"}
    _CACHE[cache_key] = out
    return out


def read(record: Dict[str, Any], name: str) -> Optional[float]:
    return for_record(record).get(name)


def why_nothing(record: Dict[str, Any], name: str) -> str:
    found = for_record(record)
    group = "why_gang" if name.startswith("gang_") else "why_compile"
    return found.get("why_" + name) or found.get(group) \
        or found.get("why") or "the set-up's spans gave no reading"


def format_split(found: Dict[str, Any], setup_s: float) -> str:
    lines = [f"setup_s {setup_s:.3f}"]
    for name in NAMES:
        if name in found:
            lines.append(f"  {name:26s} {found[name]:.6g}")
    for key in ("gang_backend_s", "gang_backend_remainder_s", "workers",
                "compile_events", "compile_s", "compile_counts", "records"):
        if key in found:
            lines.append(f"  ({key} {found[key]})")
    for row in found.get("longest_compiles", ()):
        lines.append(f"    {row['s']:9.3f} s  {row['cache']:5s} "
                     f"{row['fun']}")
    for key, why in found.items():
        if key.startswith("why"):
            lines.append(f"  {key}: {why}")
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        events = json.load(f)
    at, setup_s, window_s = (float(x) for x in argv[2:5])
    print(format_split(setup_metrics(events, at, setup_s, window_s),
                       setup_s))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
