"""The device trace by the sub-scopes of `moe` (`moe/router`,
`moe/dispatch`, `moe/experts`, `moe/combine`: `ray_tpu/ops/moe.py`, PERF.md
section 3), on top of `scope_reduce`: the same file, window, self times
and bucket rule, one level finer for the ops whose bucket is `moe`.

The readers under layer_metrics/ call `share`; on a program without the
sub-scopes, or a run without a device trace, it returns None.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence

from benchlib import scope_reduce as sr
from benchlib import trace_reduce as tr

SUBSCOPES = ("router", "dispatch", "experts", "combine")
OTHER = "other"   # under `moe` and none of the four
_SUB = re.compile(r"(?:^|/)moe/(" + "|".join(SUBSCOPES) + r")(?=/|$)")


def subscope_of(path: str) -> str:
    found = _SUB.findall(sr.clean(path))
    return found[-1] if found else OTHER


def reduce_moe(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Self time, inside `bench_window`, of every op that
    `scope_reduce.bucket_of` books under `moe`, by sub-scope; seconds,
    mean over the chips. None without the window or a device plane."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"]
              if tr.DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    sub_s: Dict[str, float] = defaultdict(float)
    for plane in planes:
        events = [e for line in plane["lines"] if line["name"] == tr.OPS_LINE
                  for e in line["events"] if e[1] + e[2] > lo and e[1] < hi]
        path_at = {(e[0], e[1]): e[3] for e in events}
        for name, start, _end, self_ns, _leaf in tr.self_times(
                [e[:3] for e in events]):
            path = path_at[(name, start)]
            if sr.bucket_of(tr.short_name(name), path) == "moe":
                sub_s[subscope_of(path)] += self_ns / len(planes) / 1e9
    return {"sub_s": dict(sub_s), "moe_s": sum(sub_s.values())}


_REDUCED: Dict[str, Optional[Dict[str, Any]]] = {}


def for_record(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_moe` of the trace this record's run wrote, with the busy
    time of `scope_reduce`'s own reduction; parsed once per process. None
    where `scope_reduce.for_record` is, or where no op is under `moe`."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        reduced = reduce_moe(sr.from_xplane(path))
        _REDUCED[path] = reduced if reduced and reduced["moe_s"] else None
    reduced = _REDUCED[path]
    return reduced and dict(reduced, busy_s=scopes["busy_s"])


def share(record: Dict[str, Any], subscopes: Sequence[str]
          ) -> Optional[float]:
    """Percent of the device's busy time under these sub-scopes."""
    reduced = for_record(record)
    if not reduced:
        return None
    return 100.0 * sum(reduced["sub_s"].get(s, 0.0)
                       for s in subscopes) / reduced["busy_s"]
