"""The device trace one level under a scope of the vocabulary:
`qkv/q_down`, `qkv/kv_down`, `qkv/q_up`, `qkv/kv_up`, `qkv/assemble`
(latent attention, `ray_tpu/models/transformer.py`) and `moe/shared`,
`moe/router`, ... (`ray_tpu/ops/moe.py`), as `moe_reduce` does for four
names under `moe`: the same file, window, self times and bucket rule as
`scope_reduce`, the ops whose bucket is the scope split by the path's
last `<scope>/<name>`.

The readers under layer_metrics/ call `share`; on a program without the
sub-scopes, or a run without a device trace, it returns None.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import scope_reduce as sr
from benchlib import trace_reduce as tr

OTHER = "other"   # under the scope and under none of its sub-scopes


def subscope_of(path: str, scope: str) -> str:
    found = re.findall(r"(?:^|/)" + re.escape(scope) + r"/(\w+)(?=/|$)",
                       sr.clean(path))
    return found[-1] if found else OTHER


def reduce_sub(trace: Dict[str, Any], scope: str
               ) -> Optional[Dict[str, float]]:
    """Self time, inside `bench_window`, of every op that
    `scope_reduce.bucket_of` books under `scope`, by the name that follows
    the scope in its path (`other` where none does: an einsum's own name
    counts as none only if the program opened no scope there, so callers
    ask for the names they know); seconds, mean over the chips. None
    without the window or a device plane."""
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
            if sr.bucket_of(tr.short_name(name), path) == scope:
                sub_s[subscope_of(path, scope)] += self_ns / len(planes) / 1e9
    return dict(sub_s)


_REDUCED: Dict[Tuple[str, str], Optional[Dict[str, float]]] = {}


def seconds(record: Dict[str, Any], scope: str, names: Sequence[str]
            ) -> Optional[Tuple[float, float]]:
    """(seconds under `<scope>/<name>` for these names, the device's busy
    seconds) in the trace this record's run wrote; parsed once per process
    and scope. None where `scope_reduce.for_record` is, or where no op
    carries one of the names (a program without them)."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if (path, scope) not in _REDUCED:
        _REDUCED[(path, scope)] = reduce_sub(sr.from_xplane(path), scope)
    reduced = _REDUCED[(path, scope)]
    if not reduced or not any(reduced.get(n) for n in names):
        return None
    return sum(reduced.get(n, 0.0) for n in names), scopes["busy_s"]


def share(record: Dict[str, Any], scope: str, names: Sequence[str]
          ) -> Optional[float]:
    """Percent of the device's busy time under these sub-scopes."""
    found = seconds(record, scope, names)
    return None if found is None else 100.0 * found[0] / found[1]
