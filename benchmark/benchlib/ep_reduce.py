"""The device trace by what an expert-parallel layer adds to the scope
vocabulary (`ray_tpu/ops/moe.py`, PERF.md section 3): `moe/exchange`
beside `moe/router`, `moe/dispatch`, `moe/experts`, `moe/combine`, on
EACH chip (the fullest chip sets the pace), and the program's exchange
counters of the traced steps. The same file and window as `scope_reduce`;
an op counts by the last `moe/<name>` in its own path, WHATEVER its
opcode: `scope_reduce.bucket_of` books a collective under `collectives`
before it looks at the path, so `moe_share`-style readers leave the
exchange's all-to-alls out; here they are the point.

The exchange's time on a chip is the union of the intervals of the
collectives under `moe/exchange`: a blocking one's event on the
operations' line, an asynchronous one's on the `Async XLA Ops` line (start
to done). Its exposed part is what of that no compute operation of the
chip overlaps (`trace_reduce.reduce_device`'s rule for
`collective_exposed_s`).

The readers under layer_metrics/ call `shares`, `exchange_roofline`,
`experts_roofline` and the two counter readers; on a program without the
scope or the counters, or a run without a device trace, they return None.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict
from typing import Any, Dict, List, Optional

from benchlib import scope_reduce as sr
from benchlib import trace_reduce as tr

MOE = ("router", "dispatch", "exchange", "experts", "combine")
EXCHANGE = "exchange"
_SUB = re.compile(r"(?:^|/)moe/(" + "|".join(MOE) + r")(?=/|$)")
# a collective by its opcode in the event's HLO text: inside a `shard_map`
# XLA names an instruction after its op_name (`%all_to_all.143`, `%pmax.3`;
# seen on the v5e, PR 57), so `trace_reduce.COLLECTIVE`, which reads the
# instruction's name, knows the collectives GSPMD made and not these
_COLLECTIVE_OP = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?\(")


def is_collective(event_name: str) -> bool:
    return bool(tr.COLLECTIVE.match(tr.short_name(event_name))
                or _COLLECTIVE_OP.search(event_name))


def subscope_of(path: str) -> Optional[str]:
    found = _SUB.findall(sr.clean(path))
    return found[-1] if found else None


def from_xplane(path: str) -> Dict[str, Any]:
    """`scope_reduce.from_xplane`'s neutral form with the `Async XLA Ops`
    line of a device plane kept too, its events with their paths."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    paths = sr.op_paths(raw)
    planes = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        is_host = plane.name == tr.HOST_PLANE
        if not (is_host or plane.name in paths):
            continue
        lines = []
        for line in plane.lines:
            if is_host:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if e.name == tr.WINDOW_ANNOTATION]
            elif line.name in (tr.OPS_LINE, tr.ASYNC_LINE):
                of = paths[plane.name]
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           of.get(e.name, "")] for e in line.events]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def reduce_ep(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Inside `bench_window`, a chip each (`chips`: the planes' device
    ids, in the planes' order): `sub_s` the self time of every op under a
    `moe/<name>` by name, `busy_s`, `exchange_s` and `exposed_s` (module
    docstring), `exchange_events`; seconds. None without the window or a
    device plane."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"]
              if tr.DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    out: Dict[str, Any] = {"chips": [], "sub_s": [], "busy_s": [],
                           "exchange_s": [], "exposed_s": [],
                           "exchange_events": []}
    for plane in planes:
        def line(name):
            return [e for ln in plane["lines"] if ln["name"] == name
                    for e in ln["events"] if e[1] + e[2] > lo and e[1] < hi]

        events = line(tr.OPS_LINE)
        path_at = {(e[0], e[1]): e[3] for e in events}
        timed = tr.self_times([e[:3] for e in events])
        sub_s: Dict[str, float] = defaultdict(float)
        compute, exchange = [], []
        for name, start, end, self_ns, leaf in timed:
            sub = subscope_of(path_at[(name, start)])
            collective = is_collective(name)
            if sub:
                sub_s[sub] += self_ns / 1e9
            if collective and sub == EXCHANGE:
                exchange.append((start, end))
            elif leaf and not collective:
                compute.append((start, end))
        for name, start, dur, path in line(tr.ASYNC_LINE):
            if is_collective(name) and subscope_of(path) == EXCHANGE:
                exchange.append((start, start + dur))
        union = tr.union(tr.clip(exchange, lo, hi))
        out["chips"].append(int(plane["name"].rsplit(":", 1)[1]))
        out["sub_s"].append(dict(sub_s))
        out["busy_s"].append(tr.total(tr.union(tr.clip(
            ((s, e) for _, s, e, _, _ in timed), lo, hi))) / 1e9)
        out["exchange_s"].append(tr.total(union) / 1e9)
        out["exposed_s"].append(tr.total(tr.subtract(
            union, tr.union(tr.clip(compute, lo, hi)))) / 1e9)
        out["exchange_events"].append(len(exchange))
    return out


_REDUCED: Dict[str, Optional[Dict[str, Any]]] = {}


def for_record(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_ep` of the trace this record's run wrote; parsed once per
    process. None where `scope_reduce.for_record` is, or where no op is
    under `moe/exchange` (a program without the exchange)."""
    if not sr.for_record(record):
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        reduced = reduce_ep(from_xplane(path))
        if reduced and not any(reduced["exchange_events"]):
            reduced = None
        _REDUCED[path] = reduced
    return _REDUCED[path]


def shares(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Percent of the device's busy time, mean over the chips: `moe`
    (every op under a `moe/<name>`, the exchange's collectives among
    them), `exchange` and `exposed`; and `by_chip`, the same a chip, with
    each sub-scope's share."""
    reduced = for_record(record)
    if not reduced or not sum(reduced["busy_s"]):
        return None
    busy = reduced["busy_s"]

    def pct(values: List[float]) -> float:
        return 100.0 * sum(values) / sum(busy)

    return {
        "moe": pct([sum(s.values()) for s in reduced["sub_s"]]),
        "exchange": pct(reduced["exchange_s"]),
        "exposed": pct(reduced["exposed_s"]),
        "by_chip": [{
            "chip": chip, "busy_s": b,
            "exchange": 100.0 * x / b, "exposed": 100.0 * ex / b,
            **{name: 100.0 * sub.get(name, 0.0) / b for name in MOE}}
            for chip, b, x, ex, sub in zip(
                reduced["chips"], busy, reduced["exchange_s"],
                reduced["exposed_s"], reduced["sub_s"]) if b]}


def _call(record: Dict[str, Any]):
    static = record.get("static", {})
    return static.get("ep_call"), static.get("peaks"), \
        record.get("counters") or {}


def exchange_roofline(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The least time the traced steps' exchanges could take on a chip
    (`flops_ep_moe.exchange_least_time_s`: the distinct (token, other
    chip) pairs' rows out of the chip four times a layer and step, at the
    published ICI rate) over the time its exchange's collectives took:
    the worst chip's share, and every chip's."""
    from benchlib import flops_ep_moe

    call, peaks, counters = _call(record)
    pairs = counters.get("traced_exchange_pairs")
    reduced = for_record(record)
    if not (call and peaks and pairs and reduced):
        return None
    least = flops_ep_moe.exchange_least_time_s(call["model"], pairs, peaks)
    place = {dev: i for i, dev in enumerate(call["shard_device_ids"])}
    by_chip = {}
    for chip, took in zip(reduced["chips"], reduced["exchange_s"]):
        if took and chip in place:
            by_chip[chip] = 100.0 * least[place[chip]] / took
    if not by_chip:
        return None
    return {"share": min(by_chip.values()), "by_chip": by_chip}


def experts_roofline(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The least time the traced steps' grouped matmuls could take at the
    rows each chip's experts really received
    (`flops_ep_moe.experts_least_time_s`) over the time of the `gmm` and
    `tgmm` kernels' events (the configuration's `kernels.moe`), both mean
    over the chips."""
    from benchlib import flops_ep_moe

    call, peaks, counters = _call(record)
    rows = counters.get("traced_rows_received")
    kinds = ((record.get("trace") or {}).get("kernel_s") or {}).get("moe")
    if not (call and peaks and rows and kinds):
        return None
    took = sum(seconds for seconds, _ in kinds.values())
    if not took:
        return None
    least, bound = flops_ep_moe.experts_least_time_s(
        call["model"], rows, call["held"], call["remat"], peaks)
    return {"share": 100.0 * least / took, "bound": bound,
            "events": {k: v[1] for k, v in kinds.items()}}


def rows_sent_over_needed(record: Dict[str, Any]) -> Optional[float]:
    """Rows that left a chip, padding included, over the slots routed to
    another chip: the window's steps, every layer and chip."""
    return (record.get("counters") or {}).get("rows_sent_over_needed")


def chip_rows_max_over_mean(record: Dict[str, Any]) -> Optional[float]:
    """The fullest chip's received rows over the mean, median over the
    window's steps and layers."""
    skew = (record.get("counters") or {}).get("chip_rows_max_over_mean")
    return statistics.median(skew) if skew else None
