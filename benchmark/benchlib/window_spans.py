"""The measured window's steps from inside the program: the train worker's
flight-recorder ring, which the train driver keeps past the gang (PR 37)
and `ray_tpu.timeline(spans=True)` serves in run.py's process after
`shutdown()`, reduced to the window's stalls.

A copy of the loop arithmetic of `tools/perf_report.py --steps`, kept here
as `span_buckets.py` is of its buckets: no PR that claims a gain can change
what its own claim is read with. The names read are the program's (PERF.md
section 3): `train.step` (the dispatch; a step is the start of one to the
start of the next; attrs `cpu_s`, `ivcsw`), `host_sync.*` (the loop's wait
for the device), `train.report` (attr `blocked_s`), and on any other thread
or process whatever lasted a millisecond: `gc.collect`, `rpc.server`, ...

    steps      the `train.step` spans of the loop thread that start inside
               [window_started_at, window_started_at + window_s] of the
               job's record; N of them bound N - 1 whole steps (the last
               one's successor is a traced step, the profiler's start away)
    p_i, m     a step's period and the window's median
    stall      p_i > 1.1 m, its excess e_i = p_i - m
    named      of e_i, what spans of 1 ms or more elsewhere have in the
               step over their usual share of a step (per name and process:
               its seconds in the step less the step's period times the
               median, over the other steps, of its seconds a second of
               step), plus the report's `blocked_s` over its usual, capped
               at e_i. A wait FOR the loop (the actor's
               `task.run:next_result`, the driver's `cw.get` on it) covers
               nine tenths of every step, stretches with a stall and names
               none of it; a `gc.collect` the other steps lack names all it
               overlaps. (ISSUE 37 wrote: the union's coverage less the
               other steps' median coverage; under that the waits named
               every stall, on the CPU rehearsal already.)

Every reader under layer_metrics/ that reads this returns a number
whenever the window's spans are there (0 is a reading) and None, with
`why_nothing`, when they are not: a program without the retained rings, a
ring that wrapped past the window's first step, a count of steps that
disagrees with the job's own `step_s`.

`python3 -m benchlib.window_spans <timeline.json> [<window_started_at>
<window_s>]` (from `benchmark/`) prints the loop table PERF.md section 6 is
written from.
"""

from __future__ import annotations

import json
import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchlib import trace_reduce as tr

LOOP_SPAN = "train.step"
LOOP_PARTS: Tuple[Tuple[str, str], ...] = (
    ("train.step", "train.step"), ("host_sync", "host_sync."),
    ("train.report", "train.report"))
PART_NAMES = tuple(part for part, _ in LOOP_PARTS) + ("other",)
SYNC_PREFIX = "host_sync."
# the sentinel's forcing points, as trace_reduce.from_xplane keeps host
# events: by whole name
SYNC_NAMES = tuple(SYNC_PREFIX + via for via in (
    "float", "int", "bool", "item", "asarray", "device_get"))
GC_SPAN = "gc.collect"
STALL_FACTOR = 1.1
OVERLAP_MIN_S = 1e-3
SKEW_NS = 5e6

Key = Tuple[Any, Any]


# ---- the loop arithmetic (tools/perf_report.py --steps) -----------------


def span_events(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [e for e in events
            if e.get("ph") == "X" and e.get("cat") == "span"]


def label(e: Dict[str, Any]) -> str:
    """`rpc.server:cw_push_task`, `task.run:next_result`, `gc.collect`."""
    args = e.get("args") or {}
    detail = args.get("method") or args.get("name")
    return f"{e['name']}:{detail}" if detail else str(e["name"])


def pick_loop_thread(spans: Sequence[Dict[str, Any]]) -> Optional[Key]:
    """The (pid, tid) with the most `train.step` spans: rank 0's loop
    thread in a one-worker cell."""
    count: Dict[Key, int] = {}
    for e in spans:
        if e["name"] == LOOP_SPAN:
            key = (e.get("pid"), e.get("tid"))
            count[key] = count.get(key, 0) + 1
    return max(count, key=count.get) if count else None


def loop_steps(spans: Sequence[Dict[str, Any]], key: Key,
               lo_s: Optional[float] = None, hi_s: Optional[float] = None
               ) -> Tuple[List[Dict[str, Any]], int]:
    """The loop thread's whole steps with a `train.step` start in
    [lo_s, hi_s] (and their successor's too), oldest first, and how many
    `train.step` spans started in the range."""
    mine = sorted((e for e in spans
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
                    parts[part] += \
                        min(end, t0 + e.get("dur", 0.0) / 1e6) - t0
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
    return steps, len(marks)


def overlapping(elsewhere: Sequence[Tuple[float, float, Dict[str, Any]]],
                start: float, end: float) -> Dict[Tuple[str, str], float]:
    """(label, process) -> seconds inside [start, end) of the spans
    elsewhere (t0, t1, event; sorted by t0, each of OVERLAP_MIN_S or
    more)."""
    out: Dict[Tuple[str, str], float] = {}
    for t0, t1, e in elsewhere:
        if t0 >= end:
            break
        if t1 > start:
            k = (label(e), str(e.get("pid")))
            out[k] = out.get(k, 0.0) + min(t1, end) - max(t0, start)
    return out


def loop_report(spans: Sequence[Dict[str, Any]], key: Key,
                lo_s: Optional[float] = None,
                hi_s: Optional[float] = None) -> Optional[Dict[str, Any]]:
    """Median period, every stall step with where its excess lay and what
    overlapped it; None with fewer than two whole steps in range."""
    steps, marks = loop_steps(spans, key, lo_s, hi_s)
    if len(steps) < 2:
        return None
    median = statistics.median(s["period_s"] for s in steps)
    stalled = [s["period_s"] > STALL_FACTOR * median for s in steps]
    calm = [i for i, bad in enumerate(stalled) if not bad] \
        or list(range(len(steps)))
    usual = {part: statistics.median(steps[i]["parts"][part] for i in calm)
             for part in PART_NAMES}
    elsewhere = sorted(
        ((e["ts"] / 1e6, (e["ts"] + e.get("dur", 0.0)) / 1e6, e)
         for e in spans if (e.get("pid"), e.get("tid")) != key
         and e.get("dur", 0.0) / 1e6 >= OVERLAP_MIN_S),
        key=lambda item: item[0])
    over = [overlapping(elsewhere, s["start_s"],
                        s["start_s"] + s["period_s"]) for s in steps]
    usual_blocked = statistics.median(steps[i]["blocked_s"] for i in calm)
    stalls = []
    for i, (step, bad) in enumerate(zip(steps, stalled)):
        if not bad:
            continue
        excess = step["period_s"] - median
        rows = []
        for k, seconds in over[i].items():
            share = statistics.median(
                over[c].get(k, 0.0) / steps[c]["period_s"] for c in calm)
            rows.append({"name": k[0], "process": k[1], "seconds": seconds,
                         "over_usual_s": max(
                             0.0, seconds - share * step["period_s"])})
        rows.sort(key=lambda r: (-r["over_usual_s"], -r["seconds"]))
        named = min(excess, sum(r["over_usual_s"] for r in rows)
                    + max(0.0, step["blocked_s"] - usual_blocked))
        stalls.append({
            "step": i, "at_s": step["start_s"] - steps[0]["start_s"],
            "period_s": step["period_s"], "excess_s": excess,
            "lay": {part: step["parts"][part] - usual[part]
                    for part in PART_NAMES},
            "overlapped": rows,
            "named_s": named, "unnamed_s": excess - named,
            "cpu_s": step["cpu_s"], "ivcsw": step["ivcsw"],
            "blocked_s": step["blocked_s"]})
    cpu = [steps[i]["cpu_s"] for i in calm
           if steps[i]["cpu_s"] is not None]
    return {"process": str(key[0]), "thread": str(key[1]),
            "marks": marks, "steps": steps, "median_period_s": median,
            "total_s": sum(s["period_s"] for s in steps),
            "usual_parts_s": usual,
            "usual_cpu_s": statistics.median(cpu) if cpu else None,
            "stall_s": sum(s["excess_s"] for s in stalls),
            "unnamed_s": sum(s["unnamed_s"] for s in stalls),
            "stalls": stalls}


def format_loop(report: Dict[str, Any]) -> str:
    usual = report["usual_parts_s"]
    cpu = report["usual_cpu_s"]
    lines = [
        f"loop report — process {report['process']} thread "
        f"{report['thread']}",
        f"{len(report['steps'])} steps over {report['total_s']:.3f} s, "
        f"median period {report['median_period_s'] * 1e3:.2f} ms ("
        + ", ".join(f"{part} {s * 1e3:.2f}" for part, s in usual.items())
        + ("" if cpu is None else f"; cpu {cpu * 1e3:.2f} ms") + ")",
        f"stall steps (> {STALL_FACTOR} medians): {len(report['stalls'])}, "
        f"excess {report['stall_s'] * 1e3:.1f} ms = "
        f"{100 * report['stall_s'] / report['total_s']:.3f}% of the steps' "
        f"time, unnamed {report['unnamed_s'] * 1e3:.1f} ms"]
    for s in report["stalls"]:
        lay = ", ".join(f"{part} {v * 1e3:+.1f}"
                        for part, v in s["lay"].items() if abs(v) >= 5e-4)
        cpu_s = "n/a" if s["cpu_s"] is None \
            else f"{s['cpu_s'] * 1e3:.1f} ms"
        lines.append(
            f"  step {s['step']} at {s['at_s']:.3f} s: period "
            f"{s['period_s'] * 1e3:.1f} ms, excess "
            f"{s['excess_s'] * 1e3:.1f} ms; lay in: {lay or 'nothing'}; "
            f"cpu {cpu_s}, ivcsw {s['ivcsw']}; named "
            f"{s['named_s'] * 1e3:.1f} ms")
        for o in s["overlapped"][:8]:
            lines.append(f"      {o['seconds'] * 1e3:9.1f} ms "
                         f"({o['over_usual_s'] * 1e3:+.1f} over its usual)"
                         f"  {o['name']}  [{o['process']}]")
        if not s["overlapped"]:
            lines.append("      nothing of 1 ms or more overlapped it")
    return "\n".join(lines)


# ---- the window's metrics -----------------------------------------------


def window_metrics(events: Sequence[Dict[str, Any]],
                   window_started_at: float, window_s: float,
                   steps_in_window: int) -> Dict[str, Any]:
    """The five metrics of the measured window from a merged timeline, or
    {"why": ...} when the window's steps are not all there."""
    spans = span_events(events)
    key = pick_loop_thread(spans)
    if key is None:
        return {"why": "the timeline holds no `train.step` span: a program "
                       "that keeps no worker ring past its gang, or "
                       "RAY_TPU_SPANS=0"}
    dropped = max((int((e.get("args") or {}).get("dropped") or 0)
                   for e in events if e.get("ph") == "M"
                   and e.get("pid") == key[0]), default=0)
    report = loop_report(spans, key, window_started_at,
                         window_started_at + window_s)
    marks = report["marks"] if report else 0
    if report is None or abs(marks - steps_in_window) > 1:
        return {"why": f"{marks} `train.step` spans of {key[0]} start in "
                       f"the window, the job's clock counted "
                       f"{steps_in_window} steps"
                       + (f"; the ring had dropped {dropped} records: it "
                          f"wrapped past the window's first step"
                          if dropped else "")}
    steps, total = report["steps"], report["total_s"]
    lo, hi = steps[0]["start_s"], steps[0]["start_s"] + total
    gc_s = tr.total(tr.clip(tr.union(
        (e["ts"] / 1e6, (e["ts"] + e.get("dur", 0.0)) / 1e6)
        for e in spans if e["name"] == GC_SPAN and e.get("pid") == key[0]),
        lo, hi))
    syncs = [s["parts"]["host_sync"] for s in steps]
    sync_median = statistics.median(syncs)
    out: Dict[str, Any] = {
        "report": report,
        "window_stall_share": 100.0 * report["stall_s"] / total,
        "window_stall_unnamed_share": 100.0 * report["unnamed_s"] / total,
        "worker_gc_share": 100.0 * gc_s / total,
        "report_wait_max_ms": 1e3 * max(s["blocked_s"] for s in steps),
    }
    if sync_median > 0.0:
        out["sync_wait_max_over_median"] = max(syncs) / sync_median
    else:
        out["why_sync_wait_max_over_median"] = (
            "the median step has no `host_sync.*` second on the loop "
            "thread: a program whose loss read records no span")
    return out


def sync_lag_ms(trace: Dict[str, Any]) -> Optional[float]:
    """`host_sync_lag_ms` from a device trace in trace_reduce's neutral
    form (host events `bench_window` and `host_sync.*` kept): over the
    traced steps the median of a step's wait ending less the device
    finishing, on the trace's one clock. A step's device end is the end
    of the merged `XLA Modules` events of all chips (one program a step
    and chip; chips' programs of one step overlap, consecutive steps do
    not), and the wait that learns of it is the last `host_sync.*`
    annotation that began before it (a later read of the same step's
    results begins after it); the two clocks agree to about a
    millisecond, so a wait that ended more than SKEW_NS before the
    device did is an earlier step's and the step has no reading."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    if not windows:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    syncs = sorted((e[1], e[1] + e[2]) for e in host
                   if e[0].startswith(SYNC_PREFIX)
                   and lo <= e[1] and e[1] + e[2] <= hi)
    programs = tr.union(
        (e[1], e[1] + e[2]) for p in trace["planes"]
        if tr.DEVICE_PLANE.match(p["name"]) for line in p["lines"]
        if line["name"] == tr.MODULES_LINE for e in line["events"]
        if e[1] >= lo and e[1] + e[2] <= hi)
    lags = []
    for _start, done in programs:
        began = [(s0, s1) for s0, s1 in syncs if s0 <= done]
        if began and began[-1][1] >= done - SKEW_NS:
            lags.append((began[-1][1] - done) / 1e6)
    return statistics.median(lags) if lags else None


# ---- what the readers call ----------------------------------------------

_CACHE: Dict[Any, Dict[str, Any]] = {}


def for_record(record: Dict[str, Any]) -> Dict[str, Any]:
    """This run's window metrics, computed once a process; never raises:
    a program without the accessor, or a record without the window, gives
    {"why": ...}."""
    cache_key = record.get("window_started_at")
    if cache_key in _CACHE:
        return _CACHE[cache_key]
    try:
        import ray_tpu
        events = ray_tpu.timeline(spans=True)
        clock = record["clock"]
        out = window_metrics(events, float(record["window_started_at"]),
                             float(clock["window_s"]), len(clock["step_s"]))
    except Exception as e:  # noqa: BLE001 - a program without the accessor
        out = {"why": f"no timeline of this run: {type(e).__name__}: {e}"}
    _CACHE[cache_key] = out
    return out


def read(record: Dict[str, Any], name: str) -> Optional[float]:
    return for_record(record).get(name)


def why_nothing(record: Dict[str, Any], name: str) -> str:
    found = for_record(record)
    return found.get("why_" + name) or found.get("why") \
        or "the window's spans gave no reading"


_LAG: Dict[str, Optional[float]] = {}


def sync_lag_for_record(record: Dict[str, Any]) -> Optional[float]:
    """`host_sync_lag_ms` of the trace this record's run wrote (the file
    is the channel, as for scope_reduce); None without one, or on a
    program whose wait for the device is no annotation."""
    from benchlib import scope_reduce
    if not (record.get("trace") or {}).get("devices"):
        return None
    path = scope_reduce.newest_trace(
        record.get("window_started_at", float("inf")))
    if path is None:
        return None
    if path not in _LAG:
        _LAG[path] = sync_lag_ms(tr.from_xplane(path, SYNC_NAMES))
    return _LAG[path]


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        events = json.load(f)
    spans = span_events(events)
    key = pick_loop_thread(spans)
    if key is None:
        print("no `train.step` span in this timeline")
        return 1
    lo = float(argv[2]) if len(argv) > 3 else None
    hi = lo + float(argv[3]) if lo is not None else None
    report = loop_report(spans, key, lo, hi)
    if report is None:
        print("fewer than two whole steps in range")
        return 1
    print(format_loop(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
