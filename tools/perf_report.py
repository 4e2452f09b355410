"""Training stall attribution from a merged `--spans` Chrome trace.

Consumes the JSON that `ray_tpu timeline --spans` (or
`ray_tpu.timeline(spans=True)`) writes and attributes the training
loop's wall time into named buckets:

    learner_compute   learner.step / learner.update spans
    compile           jax.trace / jax.lower / jax.compile (the jax
                      sentinel's: a program the jit cache did not hold,
                      traced, lowered, then compiled or loaded from the
                      persistent cache), inside whatever step ran it
    device_feed       feed.stage / feed.ship / feed.xfer / feed.unfuse
    rollout_wait      feed.wait (consumer starved: upstream sampling or
                      the learner queue is the bottleneck)
    store_rpc         rpc.* / store.* / cw.* / envelope.*
    idle              window time covered by none of the above

Attribution runs over ONE thread — by default the thread with the most
learner.* span time (the IMPALA learner thread); pass --thread/--process
to pick another. Overlapping spans are resolved by specificity (a
store_rpc span nested inside learner compute counts as store_rpc), so
every wall-clock microsecond lands in exactly one bucket and the bucket
percentages sum to 100. This replaces the hand-derived
feed_xfer_stall_pct numbers in the RL bench with trace-derived ones.

`--steps` is the loop view, for a train loop (`train.step` spans, the
thread with the most of them): the period of each step (the start of one
`train.step` to the next), the median, and for each step longer than 1.1
medians its excess over the median — where on the loop thread it lay
(`train.step` the dispatch, `host_sync.*` the wait for the device,
`train.report`, none of them: the loop's own code), which spans of 1 ms
or more on any other thread or process overlapped it (`gc.collect`,
`rpc.server` by method, `cw.*`, the driver's) or, on the loop thread
itself, which compile lay in it (`jax.compile:<function>[<cache
outcome>]`: a shape that changed), and the loop thread's `cpu_s` /
`ivcsw` over the step. A stall with a span over it is named; one
with the thread off the CPU (`cpu_s` short of the period it was not
waiting, `ivcsw` up) and nothing over it is the host's; one with neither
is the device's or the runtime's.

Usage:
    python tools/perf_report.py TRACE.json [--format=json] [--out FILE]
    python tools/perf_report.py TRACE.json --steps
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.compile")

# bucket -> (priority, span-name prefixes); higher priority wins overlap.
# task.run is deliberately NOT bucketed: it is an umbrella covering a
# whole task body (including any nested learner.update), and ranking it
# would let it claim time that belongs to the spans inside it.
BUCKETS: Dict[str, Tuple[int, Tuple[str, ...]]] = {
    # elastic reconfiguration phases (train/elastic.py: detect/drain/
    # checkpoint/reform/reshard/resume) outrank everything: wall time
    # inside a re-form is recovery cost, not compute/transport, even
    # when store/rpc spans nest inside it
    "elastic_reconfig": (6, ("elastic.",)),
    # device→host syncs recorded by the jax sentinel inside step
    # regions (util/jax_sentinel.py): wall time blocked on a forced
    # transfer is stall, not compute, even though the spans nest
    # inside learner.* — so host_sync outranks every work bucket
    "host_sync": (5, ("host_sync.",)),
    "store_rpc": (4, ("rpc.", "store.", "cw.", "envelope.")),
    "device_feed": (3, ("feed.stage", "feed.ship", "feed.xfer",
                        "feed.unfuse")),
    "rollout_wait": (2, ("feed.wait", "runner.sample")),
    # a program the jit cache did not hold (util/jax_sentinel.py): its
    # trace, lowering and compile-or-load nest inside the learner.* or
    # train.step that dispatched it, and are not compute
    "compile": (1, COMPILE_SPANS),
    "learner_compute": (0, ("learner.",)),
}


def _bucket_of(name: str) -> Optional[str]:
    for bucket, (_prio, prefixes) in BUCKETS.items():
        if name.startswith(prefixes):
            return bucket
    return None


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for a, b in intervals[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _subtract(base: List[Tuple[float, float]],
              cut: List[Tuple[float, float]]
              ) -> List[Tuple[float, float]]:
    """base minus cut (both interval unions)."""
    out: List[Tuple[float, float]] = []
    for a, b in base:
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, min(c, b)))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def _length(intervals: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def pick_thread(events: List[Dict[str, Any]],
                process: Optional[str] = None,
                thread: Optional[str] = None) -> Tuple[Any, Any]:
    """(pid, tid) to attribute: the thread with the most learner.* span
    time, else the thread with the most span time overall."""
    learner_time: Dict[Tuple[Any, Any], float] = {}
    span_time: Dict[Tuple[Any, Any], float] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "span":
            continue
        if process is not None and str(e.get("pid")) != process:
            continue
        if thread is not None and str(e.get("tid")) != thread:
            continue
        key = (e.get("pid"), e.get("tid"))
        dur = float(e.get("dur", 0.0))
        span_time[key] = span_time.get(key, 0.0) + dur
        if str(e.get("name", "")).startswith("learner."):
            learner_time[key] = learner_time.get(key, 0.0) + dur
    pool = learner_time or span_time
    if not pool:
        raise SystemExit("no span events in trace (was it exported "
                         "with --spans / spans=True?)")
    return max(pool, key=pool.get)


def attribute(events: List[Dict[str, Any]],
              process: Optional[str] = None,
              thread: Optional[str] = None) -> Dict[str, Any]:
    pid, tid = pick_thread(events, process, thread)
    per_bucket: Dict[str, List[Tuple[float, float]]] = {
        b: [] for b in BUCKETS}
    t_min, t_max = None, None
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "span":
            continue
        if (e.get("pid"), e.get("tid")) != (pid, tid):
            continue
        t0 = float(e["ts"]) / 1e6
        t1 = t0 + float(e.get("dur", 0.0)) / 1e6
        t_min = t0 if t_min is None else min(t_min, t0)
        t_max = t1 if t_max is None else max(t_max, t1)
        bucket = _bucket_of(str(e.get("name", "")))
        if bucket is not None:
            per_bucket[bucket].append((t0, t1))
    window = (t_max - t_min) if t_min is not None else 0.0
    # resolve overlap by priority: each instant lands in exactly one
    # bucket (the most specific span covering it)
    unions = {b: _union(iv) for b, iv in per_bucket.items()}
    exclusive: Dict[str, List[Tuple[float, float]]] = {}
    by_prio = sorted(BUCKETS, key=lambda b: -BUCKETS[b][0])
    claimed: List[Tuple[float, float]] = []
    for b in by_prio:
        exclusive[b] = _subtract(unions[b], claimed)
        claimed = _union(claimed + unions[b])
    seconds = {b: _length(iv) for b, iv in exclusive.items()}
    attributed = sum(seconds.values())
    seconds["idle"] = max(0.0, window - attributed)
    report = {
        "process": str(pid),
        "thread": str(tid),
        "window_s": round(window, 6),
        "buckets": {
            b: {"seconds": round(s, 6),
                "pct": round(100.0 * s / window, 2) if window else 0.0}
            for b, s in seconds.items()},
        # share of the window covered by SOME span (idle excluded):
        # the flight recorder's coverage of this thread's time
        "attributed_pct": round(100.0 * attributed / window, 2)
        if window else 0.0,
    }
    report["goodput"] = goodput_view(report)
    return report


# trace bucket -> goodput ledger bucket (_private/goodput.py). The two
# accountings observe the same loop from different vantages — the trace
# from span coverage of the learner thread, the ledger from its own
# wall-clock classifier — so on a chaos-free run they must agree within
# tolerance (tests/test_goodput.py keeps that as a standing check).
# Compute spans all map to productive_step: from the ledger's vantage
# the gang is stepping whether the step-internal microsecond went to
# XLA, the feed pipeline, or a store RPC.
GOODPUT_MAP: Dict[str, str] = {
    "learner_compute": "productive_step",
    "compile": "compile",
    "device_feed": "productive_step",
    "store_rpc": "productive_step",
    "host_sync": "productive_step",
    "rollout_wait": "feed_stall",
    "elastic_reconfig": "elastic_reconfig",
    "idle": "idle",
}


def goodput_view(report: Dict[str, Any]) -> Dict[str, Any]:
    """Project the trace attribution into the goodput ledger's bucket
    taxonomy so the two can be reconciled (see README "Goodput &
    metrics history")."""
    buckets: Dict[str, float] = {}
    for b, rec in report["buckets"].items():
        gb = GOODPUT_MAP.get(b, "idle")
        buckets[gb] = buckets.get(gb, 0.0) + rec["seconds"]
    window = report.get("window_s") or 0.0
    productive = buckets.get("productive_step", 0.0)
    return {
        "window_s": window,
        "buckets": {b: round(s, 6) for b, s in sorted(buckets.items())},
        "productive_frac": round(productive / window, 4)
        if window else None,
    }


# ---------------------------------------------------------------------
# The loop view (--steps)
# ---------------------------------------------------------------------

LOOP_SPAN = "train.step"
# what a step's time on the loop thread is split into; the rest is
# "other": the loop's own code between the program's spans
LOOP_PARTS: Tuple[Tuple[str, str], ...] = (
    ("train.step", "train.step"), ("host_sync", "host_sync."),
    ("train.report", "train.report"))
STALL_FACTOR = 1.1     # a stall step: longer than this many medians
OVERLAP_MIN_S = 1e-3   # spans elsewhere shorter than this name nothing


def _span_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [e for e in events
            if e.get("ph") == "X" and e.get("cat") == "span"]


def _label(e: Dict[str, Any]) -> str:
    """`rpc.server:cw_push_task`, `task.run:next_result`, `gc.collect`,
    `jax.compile:jit(_step)[miss]`."""
    args = e.get("args") or {}
    detail = args.get("method") or args.get("name") or args.get("fun")
    cache = f"[{args['cache']}]" if args.get("cache") else ""
    return f"{e['name']}:{detail}{cache}" if detail else str(e["name"])


def pick_loop_thread(events: List[Dict[str, Any]],
                     process: Optional[str] = None,
                     thread: Optional[str] = None
                     ) -> Optional[Tuple[Any, Any]]:
    """The (pid, tid) with the most `train.step` spans."""
    count: Dict[Tuple[Any, Any], int] = {}
    for e in _span_events(events):
        if e.get("name") != LOOP_SPAN:
            continue
        if process is not None and str(e.get("pid")) != process:
            continue
        if thread is not None and str(e.get("tid")) != thread:
            continue
        key = (e.get("pid"), e.get("tid"))
        count[key] = count.get(key, 0) + 1
    return max(count, key=count.get) if count else None


def loop_steps(events: List[Dict[str, Any]], key: Tuple[Any, Any],
               lo_s: Optional[float] = None, hi_s: Optional[float] = None
               ) -> List[Dict[str, Any]]:
    """The loop thread's steps, oldest first: each from the start of one
    `train.step` to the start of the next (the last `train.step` bounds
    the step before it and makes none itself), with its seconds in each
    of LOOP_PARTS and in none, the loop thread's `cpu_s` / `ivcsw` over
    it (carried by the NEXT `train.step`: since the previous one) and
    the largest `blocked_s` of its reports. `lo_s`/`hi_s` keep the steps
    whose `train.step` starts in that range of the timeline's clock, and
    a step needs its successor in range too."""
    mine = sorted((e for e in _span_events(events)
                   if (e.get("pid"), e.get("tid")) == key),
                  key=lambda e: e["ts"])
    marks = [e for e in mine if e["name"] == LOOP_SPAN
             and (lo_s is None or e["ts"] / 1e6 >= lo_s)
             and (hi_s is None or e["ts"] / 1e6 <= hi_s)]
    steps: List[Dict[str, Any]] = []
    for cur, nxt in zip(marks, marks[1:]):
        start, end = cur["ts"] / 1e6, nxt["ts"] / 1e6
        parts = {part: 0.0 for part, _prefix in LOOP_PARTS}
        blocked = 0.0
        for e in mine:
            t0 = e["ts"] / 1e6
            if not start <= t0 < end:
                continue
            for part, prefix in LOOP_PARTS:
                if str(e["name"]).startswith(prefix):
                    parts[part] += min(end, t0 + e.get("dur", 0.0) / 1e6) - t0
                    break
            if e["name"] == "train.report":
                blocked = max(blocked, float(
                    (e.get("args") or {}).get("blocked_s") or 0.0))
        period = end - start
        parts["other"] = max(0.0, period - sum(parts.values()))
        args = nxt.get("args") or {}
        steps.append({"start_s": start, "period_s": period, "parts": parts,
                      "cpu_s": args.get("cpu_s"), "ivcsw": args.get("ivcsw"),
                      "blocked_s": blocked})
    return steps


def _overlapping(elsewhere: List[Tuple[float, float, Dict[str, Any]]],
                 start: float, end: float) -> Dict[Tuple[str, str], float]:
    """(label, process) -> seconds inside [start, end) of the spans
    elsewhere (t0, t1, event)."""
    out: Dict[Tuple[str, str], float] = {}
    for t0, t1, e in elsewhere:
        if t1 > start and t0 < end:
            k = (_label(e), str(e.get("pid")))
            out[k] = out.get(k, 0.0) + min(t1, end) - max(t0, start)
    return out


def steps_report(events: List[Dict[str, Any]],
                 process: Optional[str] = None,
                 thread: Optional[str] = None,
                 lo_s: Optional[float] = None,
                 hi_s: Optional[float] = None) -> Optional[Dict[str, Any]]:
    """The loop view: the steps' median period and every stall step with
    where its excess lay and what overlapped it. None without a thread
    that ran `train.step` at least three times in range.

    What NAMES a stall: a span elsewhere (or a `jax.trace`, `jax.lower`
    or `jax.compile` of the loop thread itself: a program compiled
    inside the step, by its function and its cache outcome) names the
    part of its seconds in
    the step that is over its usual share of a step (the median, over the
    other steps, of its seconds a second of step). A wait for the loop
    itself — the actor's `task.run:next_result`, the driver's `cw.get`
    on it — covers nine tenths of every step, stretches with a stall and
    so names none of it; a `gc.collect` that the other steps do not have
    names all it overlaps. `train.report`'s `blocked_s` over its usual
    names too (the driver's round held the loop). The sum is capped at
    the step's excess; the rest is unnamed."""
    key = pick_loop_thread(events, process, thread)
    if key is None:
        return None
    steps = loop_steps(events, key, lo_s, hi_s)
    if len(steps) < 2:
        return None
    median = statistics.median([s["period_s"] for s in steps])
    stalled = [s["period_s"] > STALL_FACTOR * median for s in steps]
    calm = [i for i, bad in enumerate(stalled) if not bad] \
        or list(range(len(steps)))
    part_names = [part for part, _ in LOOP_PARTS] + ["other"]
    usual = {part: statistics.median([steps[i]["parts"][part] for i in calm])
             for part in part_names}
    # spans of OVERLAP_MIN_S or more on any other thread or process,
    # and on the loop thread itself what compiled inside a step (it
    # lies in `train.step`'s own seconds and names them)
    elsewhere = [(e["ts"] / 1e6, (e["ts"] + e.get("dur", 0.0)) / 1e6, e)
                 for e in _span_events(events)
                 if ((e.get("pid"), e.get("tid")) != key
                     or (e["name"] in COMPILE_SPANS
                         and "folded_n" not in (e.get("args") or {})))
                 and e.get("dur", 0.0) / 1e6 >= OVERLAP_MIN_S]
    over = [_overlapping(elsewhere, s["start_s"],
                         s["start_s"] + s["period_s"]) for s in steps]
    usual_blocked = statistics.median([steps[i]["blocked_s"] for i in calm])
    stalls = []
    for i, (step, bad) in enumerate(zip(steps, stalled)):
        if not bad:
            continue
        excess = step["period_s"] - median
        rows = []
        for k, seconds in over[i].items():
            share = statistics.median([over[c].get(k, 0.0) / steps[c]["period_s"]
                             for c in calm])
            rows.append({"name": k[0], "process": k[1], "seconds": seconds,
                         "over_usual_s": max(
                             0.0, seconds - share * step["period_s"])})
        rows.sort(key=lambda r: (-r["over_usual_s"], -r["seconds"]))
        named = min(excess, sum(r["over_usual_s"] for r in rows)
                    + max(0.0, step["blocked_s"] - usual_blocked))
        stalls.append({
            "step": i,
            "at_s": step["start_s"] - steps[0]["start_s"],
            "period_s": step["period_s"], "excess_s": excess,
            "lay": {part: step["parts"][part] - usual[part]
                    for part in part_names},
            "overlapped": rows,
            "named_s": named, "unnamed_s": excess - named,
            "cpu_s": step["cpu_s"], "ivcsw": step["ivcsw"],
            "blocked_s": step["blocked_s"]})
    total = sum(s["period_s"] for s in steps)
    return {"process": str(key[0]), "thread": str(key[1]),
            "steps": len(steps), "median_period_s": median,
            "total_s": total, "usual_parts_s": usual,
            "usual_cpu_s": statistics.median(
                [steps[i]["cpu_s"] or 0.0 for i in calm]),
            "stall_s": sum(s["excess_s"] for s in stalls),
            "unnamed_s": sum(s["unnamed_s"] for s in stalls),
            "stalls": stalls}


def format_steps(report: Dict[str, Any]) -> str:
    usual = report["usual_parts_s"]
    lines = [
        f"loop report — process {report['process']} thread "
        f"{report['thread']}",
        f"{report['steps']} steps over {report['total_s']:.3f} s, median "
        f"period {report['median_period_s'] * 1e3:.2f} ms ("
        + ", ".join(f"{part} {s * 1e3:.2f}" for part, s in usual.items())
        + f"; cpu {report['usual_cpu_s'] * 1e3:.2f} ms)",
        f"stall steps (> {STALL_FACTOR} medians): {len(report['stalls'])}, "
        f"excess {report['stall_s'] * 1e3:.1f} ms = "
        f"{100 * report['stall_s'] / report['total_s']:.3f}% of the steps' "
        f"time, unnamed {report['unnamed_s'] * 1e3:.1f} ms"]
    for s in report["stalls"]:
        lay = ", ".join(f"{part} {v * 1e3:+.1f}"
                        for part, v in s["lay"].items() if abs(v) >= 5e-4)
        cpu = "n/a" if s["cpu_s"] is None else f"{s['cpu_s'] * 1e3:.1f} ms"
        lines.append(
            f"  step {s['step']} at {s['at_s']:.3f} s: period "
            f"{s['period_s'] * 1e3:.1f} ms, excess "
            f"{s['excess_s'] * 1e3:.1f} ms; lay in: {lay or 'nothing'}; "
            f"cpu {cpu}, ivcsw {s['ivcsw']}; named "
            f"{s['named_s'] * 1e3:.1f} ms")
        for o in s["overlapped"][:8]:
            lines.append(f"      {o['seconds'] * 1e3:9.1f} ms "
                         f"({o['over_usual_s'] * 1e3:+.1f} over its usual)"
                         f"  {o['name']}  [{o['process']}]")
        if not s["overlapped"]:
            lines.append("      nothing of 1 ms or more overlapped it")
    return "\n".join(lines)


def format_text(report: Dict[str, Any]) -> str:
    lines = [f"perf report — process {report['process']} "
             f"thread {report['thread']}",
             f"window: {report['window_s'] * 1e3:.1f} ms"]
    for b, rec in sorted(report["buckets"].items(),
                         key=lambda kv: -kv[1]["seconds"]):
        lines.append(f"  {b:<16} {rec['seconds'] * 1e3:10.1f} ms "
                     f"{rec['pct']:6.2f}%")
    lines.append(f"  attributed: {report['attributed_pct']:.2f}% "
                 f"(idle = {report['buckets']['idle']['pct']:.2f}%)")
    gp = report.get("goodput")
    if gp and gp.get("productive_frac") is not None:
        lines.append("  goodput: productive "
                     f"{100 * gp['productive_frac']:.1f}% of window "
                     "(ledger taxonomy; `ray_tpu goodput` compares)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="Chrome-trace JSON from "
                                  "`ray_tpu timeline --spans`")
    ap.add_argument("--process", default=None,
                    help="restrict to one process row (pid label)")
    ap.add_argument("--thread", default=None,
                    help="restrict to one thread id")
    ap.add_argument("--format", choices=["text", "json"], default="text")
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--steps", action="store_true",
                    help="the loop view: a train loop's steps and stalls")
    args = ap.parse_args(argv)

    with open(args.trace) as f:
        events = json.load(f)
    if args.steps:
        report = steps_report(events, process=args.process,
                              thread=args.thread)
        if report is None:
            raise SystemExit("no thread with three `train.step` spans in "
                             "trace (a train loop, exported with --spans?)")
        text = format_steps(report)
    else:
        report = attribute(events, process=args.process,
                           thread=args.thread)
        text = format_text(report)
    if args.format == "json":
        print(json.dumps(report, indent=1))
    else:
        print(text)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
