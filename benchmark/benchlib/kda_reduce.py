"""The device trace by the scopes of a Kimi Delta Attention sublayer
(`kda_norm`, `kda/qkv_proj`, `kda/conv`, `kda/gates`, `kda/delta`,
`kda/out_norm`, `kda/out_proj`: `ray_tpu/ops/kda.py`, PERF.md section 3):
`ssm_reduce`'s reduction under other names. `kda` is no bucket of
`scope_reduce`'s vocabulary (its ops are booked under `layers` there), so
an op counts here by the last of these names in its own path.

The readers under layer_metrics/ call `seconds` and `share`; on a program
without the scopes, or a run without a device trace, they return None.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import scope_reduce as sr
from benchlib import trace_reduce as tr

SUBSCOPES = ("qkv_proj", "conv", "gates", "delta", "out_norm", "out_proj")
NORM = "kda_norm"
SCOPES = (NORM,) + tuple("kda/" + name for name in SUBSCOPES)
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")


def scope_of(path: str) -> Optional[str]:
    found = _SCOPE.findall(sr.clean(path))
    return found[-1] if found else None


def reduce_kda(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Self time, inside `bench_window`, of every op under one of
    `SCOPES`, by scope; seconds, mean over the chips. None without the
    window or a device plane."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"]
              if tr.DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    scope_s: Dict[str, float] = defaultdict(float)
    for plane in planes:
        events = [e for line in plane["lines"] if line["name"] == tr.OPS_LINE
                  for e in line["events"] if e[1] + e[2] > lo and e[1] < hi]
        path_at = {(e[0], e[1]): e[3] for e in events}
        for name, start, _end, self_ns, _leaf in tr.self_times(
                [e[:3] for e in events]):
            scope = scope_of(path_at[(name, start)])
            if scope:
                scope_s[scope] += self_ns / len(planes) / 1e9
    return dict(scope_s)


_REDUCED: Dict[str, Optional[Dict[str, float]]] = {}


def seconds(record: Dict[str, Any], names: Sequence[str] = SCOPES
            ) -> Optional[Tuple[float, float]]:
    """(seconds under these scopes, the device's busy seconds) in the
    trace this record's run wrote; parsed once per process. None where
    `scope_reduce.for_record` is, or where no op carries one of the names
    (a program without the sublayer)."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        _REDUCED[path] = reduce_kda(sr.from_xplane(path))
    reduced = _REDUCED[path]
    if not reduced or not any(reduced.get(n) for n in names):
        return None
    return sum(reduced.get(n, 0.0) for n in names), scopes["busy_s"]


def share(record: Dict[str, Any], names: Sequence[str] = SCOPES
          ) -> Optional[float]:
    """Percent of the device's busy time under these scopes."""
    found = seconds(record, names)
    return None if found is None else 100.0 * found[0] / found[1]
