"""From a profiler trace to numbers: busy and idle time, time by
operation and by kernel, collective time exposed, and what the host was
doing in the idle gaps.

The reduction works on a neutral form, so that it can be checked against a
small recorded trace kept beside it (benchmark/fixtures/):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

`from_xplane` makes that form from the `.xplane.pb` the JAX profiler
writes. What was seen on a v5e (PR 23): one plane per chip named
`/device:TPU:<n>` with the lines `Steps`, `XLA Modules` (one event per
executed program), `XLA Ops` (every HLO operation, containers such as
`while` included, children nested inside them, the name being the whole
HLO text `%name = ...`) and `Async XLA Ops` (a `*-start` event lasting
until its `*-done`); the host's threads are lines of `/host:CPU`, with
`jax.profiler.TraceAnnotation`s on the calling thread's line. The two
clocks agree to about a millisecond.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)")
WINDOW_ANNOTATION = "bench_window"

Interval = Tuple[float, float]


def short_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def from_xplane(path: str, host_names: Iterable[str] = ()
                ) -> Dict[str, Any]:
    """Read an .xplane.pb with JAX alone into the neutral form. Of the
    host plane only events named in `host_names` are kept."""
    from jax.profiler import ProfileData

    keep = set(host_names) | {WINDOW_ANNOTATION}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_host = plane.name == HOST_PLANE
        if not (is_host or DEVICE_PLANE.match(plane.name)):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if not is_host or e.name in keep]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---- interval arithmetic ----------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval],
             b: Sequence[Interval]) -> List[Interval]:
    """a minus b, both already unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def self_times(events: Sequence[Sequence[Any]]
               ) -> List[Tuple[str, float, float, float, bool]]:
    """(name, start, end, self_ns, is_leaf) for events of ONE line, where
    children nest inside their parents: self time is the duration less the
    direct children's."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List[Any]] = []
    stack: List[int] = []
    for name, start, dur in order:
        end = start + dur
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= dur
            parent[4] = False
        out.append([name, start, end, dur, True])
        stack.append(len(out) - 1)
    return [(n, s, e, max(0.0, sf), leaf) for n, s, e, sf, leaf in out]


# ---- the reduction ----------------------------------------------------


def _line(plane: Dict[str, Any], name: str) -> List[Sequence[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _host_annotations(trace: Dict[str, Any]) -> List[Sequence[Any]]:
    out: List[Sequence[Any]] = []
    for plane in trace["planes"]:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                out.extend(line["events"])
    return out


def reduce_device(plane: Dict[str, Any], window: Interval,
                  kernels: Dict[str, Dict[str, str]],
                  host: Sequence[Sequence[Any]]) -> Dict[str, Any]:
    lo, hi = window
    ops = [e[:3] for e in _line(plane, OPS_LINE)   # a 4th is the path
           if e[1] + e[2] > lo and e[1] < hi]
    timed = self_times(ops)

    busy = union(clip(((s, e) for _, s, e, _, _ in timed), lo, hi))
    idle = subtract([(lo, hi)], busy)

    by_op: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    compute_iv: List[Interval] = []
    coll_iv: List[Interval] = []
    coll_by_kind: Dict[str, float] = defaultdict(float)
    kernel_ns: Dict[str, Dict[str, List[float]]] = {
        k: {kind: [0.0, 0] for kind in kinds}
        for k, kinds in kernels.items()}
    compiled = {k: {kind: re.compile(rx) for kind, rx in kinds.items()}
                for k, kinds in kernels.items()}
    for name, s, e, self_ns, leaf in timed:
        short = short_name(name)
        by_op[short][0] += self_ns
        by_op[short][1] += 1
        m = COLLECTIVE.match(short)
        if m:
            coll_iv.append((s, e))
            coll_by_kind[m.group(1)] += e - s
        elif leaf:
            compute_iv.append((s, e))
        for k, kinds in compiled.items():
            for kind, rx in kinds.items():
                if rx.search(short):
                    kernel_ns[k][kind][0] += e - s
                    kernel_ns[k][kind][1] += 1
    for name, s, dur in _line(plane, ASYNC_LINE):
        m = COLLECTIVE.match(short_name(name))
        if m and s + dur > lo and s < hi:
            coll_iv.append((s, s + dur))
            coll_by_kind[m.group(1) + " (async)"] += dur
    coll = union(clip(coll_iv, lo, hi))
    exposed = subtract(coll, union(clip(compute_iv, lo, hi)))

    modules = sorted((e for e in _line(plane, MODULES_LINE)
                      if e[1] >= lo and e[1] + e[2] <= hi),
                     key=lambda e: e[1])
    gaps = [modules[i + 1][1] - (modules[i][1] + modules[i][2])
            for i in range(len(modules) - 1)]

    gap_by_host: Dict[str, float] = defaultdict(float)
    host_iv = [(n, (s, s + d)) for n, s, d in host
               if n != WINDOW_ANNOTATION]
    for gap in idle:
        best, best_ov = "unattributed", 0.0
        for n, iv in host_iv:
            ov = overlap(gap, iv)
            if ov > best_ov:
                best, best_ov = n, ov
        gap_by_host[best] += gap[1] - gap[0]

    return {
        "window_ns": hi - lo,
        "busy_ns": total(busy),
        "idle_ns": total(idle),
        "longest_idle_gap_ns": max((e - s for s, e in idle), default=0.0),
        "op_self_ns": {k: v for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1][0])[:200]},
        "kernel_ns": kernel_ns,
        "collective_ns": total(coll),
        "collective_exposed_ns": total(exposed),
        "collective_by_kind_ns": dict(coll_by_kind),
        "modules": len(modules),
        "module_names": sorted({short_name(m[0]) for m in modules}),
        "module_ns": sum(m[2] for m in modules),
        "module_gaps_ns": gaps,
        "idle_by_host_ns": dict(gap_by_host),
    }


def reduce_trace(trace: Dict[str, Any],
                 kernels: Optional[Dict[str, Dict[str, str]]] = None
                 ) -> Dict[str, Any]:
    """Every device plane reduced over the `bench_window` annotation's
    interval, then averaged over the chips. Seconds throughout. Without
    that annotation or a device plane (a CPU rehearsal) there is nothing
    to reduce: `{"devices": 0}`."""
    host = _host_annotations(trace)
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return {"devices": 0}
    window = (min(w[0] for w in windows), max(w[1] for w in windows))
    devices = [reduce_device(p, window, kernels or {}, host)
               for p in planes]
    n = len(devices)

    def mean(key: str) -> float:
        return sum(d[key] for d in devices) / n / 1e9

    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    idle_by: Dict[str, float] = defaultdict(float)
    coll_kind: Dict[str, float] = defaultdict(float)
    kernel: Dict[str, Dict[str, List[float]]] = {}
    gaps: List[float] = []
    for d in devices:
        for k, (ns, cnt) in d["op_self_ns"].items():
            ops[k][0] += ns / n / 1e9
            ops[k][1] += cnt
        for k, ns in d["idle_by_host_ns"].items():
            idle_by[k] += ns / n / 1e9
        for k, ns in d["collective_by_kind_ns"].items():
            coll_kind[k] += ns / n / 1e9
        for k, kinds in d["kernel_ns"].items():
            for kind, (ns, cnt) in kinds.items():
                slot = kernel.setdefault(k, {}).setdefault(kind, [0.0, 0])
                slot[0] += ns / n / 1e9
                slot[1] += cnt / n
        gaps.extend(g / 1e9 for g in d["module_gaps_ns"])
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "devices": n,
        "window_s": mean("window_ns"),
        "busy_s": mean("busy_ns"),
        "idle_s": mean("idle_ns"),
        "longest_idle_gap_s": max(
            d["longest_idle_gap_ns"] for d in devices) / 1e9,
        "collective_s": mean("collective_ns"),
        "collective_exposed_s": mean("collective_exposed_ns"),
        "collective_by_kind_s": dict(coll_kind),
        "modules_per_device": devices[0]["modules"],
        "module_names": devices[0]["module_names"],
        "module_s": mean("module_ns"),
        "module_gap_median_s": statistics.median(gaps) if gaps else None,
        "module_gaps": len(gaps),
        "kernel_s": kernel,
        "op_self_s": [[k, v[0], v[1]] for k, v in top[:60]],
        "idle_by_host_s": sorted(idle_by.items(), key=lambda kv: -kv[1]),
    }


def breakdown(reduced: Dict[str, Any]) -> Dict[str, Any]:
    """The last line's `breakdown`: at most 10 entries each."""
    return {
        "device_ops": [[k, s] for k, s, _ in reduced.get(
            "op_self_s", [])[:10]],
        "idle_gaps": [[k, s] for k, s in reduced.get(
            "idle_by_host_s", [])[:10]],
    }
