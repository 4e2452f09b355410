"""The device trace by what a SambaY decoder-hybrid-decoder adds to the
scope vocabulary (`ray_tpu/models/transformer.py`, `ray_tpu/ops/ssm.py`,
PERF.md section 3): a Mamba-1 mixer's `ssm/in_proj`, `ssm/conv`,
`ssm/x_proj`, `ssm/scan`, `ssm/gate`, `ssm/out_proj`, a gated memory
unit's `gmu/in_proj`, `gmu/gate`, `gmu/out_proj`, differential attention's
`attention/diff`, and the attention kernels' events told apart by the
`attention/window`, `attention/full` or `attention/cross` in their paths.
The same file, window and self times as `scope_reduce`; an op counts by
the last such name in its own path.

The readers under layer_metrics/ call `seconds`, `share` and
`attention_kernels`; on a program without the scopes, or a run without a
device trace, they return None.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import scope_reduce as sr
from benchlib import trace_reduce as tr

SSM = tuple("ssm/" + n for n in ("in_proj", "conv", "x_proj", "scan",
                                 "gate", "out_proj"))
GMU = tuple("gmu/" + n for n in ("in_proj", "gate", "out_proj"))
DIFF = "attention/diff"
ATTENTION_KINDS = ("window", "full", "cross")
_NAMED = re.compile(
    r"(?:^|/)(" + "|".join(SSM + GMU + (DIFF,) + tuple(
        "attention/" + k for k in ATTENTION_KINDS)) + r")(?=/|$)")


def named(path: str) -> Optional[str]:
    found = _NAMED.findall(sr.clean(path))
    return found[-1] if found else None


def reduce_sambay(trace: Dict[str, Any], kernels: Dict[str, str]
                  ) -> Optional[Dict[str, Any]]:
    """Inside `bench_window`, mean over the chips: `sub_s`, the self time
    of every op under one of the names, by name, in seconds; `kernel_s`,
    `attention kind -> kernel kind -> [seconds, events]` of the events
    whose name one of `kernels`' patterns matches (the configuration's
    `kernels.attn`: fwd, bwd_dkv, bwd_dq) by the `attention/<kind>` of
    their path, each event's whole duration as `trace_reduce` counts a
    kernel's. None without the window or a device plane."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"]
              if tr.DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    patterns = {kind: re.compile(rx) for kind, rx in kernels.items()}
    sub_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, Dict[str, list]] = {}
    for plane in planes:
        events = [e for line in plane["lines"] if line["name"] == tr.OPS_LINE
                  for e in line["events"] if e[1] + e[2] > lo and e[1] < hi]
        path_at = {(e[0], e[1]): e[3] for e in events}
        for name, start, end, self_ns, _leaf in tr.self_times(
                [e[:3] for e in events]):
            sub = named(path_at[(name, start)])
            if not sub:
                continue
            sub_s[sub] += self_ns / len(planes) / 1e9
            kind = sub.split("/", 1)[1]
            if kind in ATTENTION_KINDS:
                short = tr.short_name(name)
                for call, rx in patterns.items():
                    if rx.search(short):
                        slot = kernel_s.setdefault(kind, {}).setdefault(
                            call, [0.0, 0])
                        slot[0] += (end - start) / len(planes) / 1e9
                        slot[1] += 1
    return {"sub_s": dict(sub_s), "kernel_s": kernel_s}


_REDUCED: Dict[str, Optional[Dict[str, Any]]] = {}


def for_record(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """(the reduction, the device's busy seconds) of the trace this
    record's run wrote; parsed once per process. None where
    `scope_reduce.for_record` is."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        _REDUCED[path] = reduce_sambay(
            sr.from_xplane(path),
            record.get("static", {}).get("attention_kernels") or {})
    reduced = _REDUCED[path]
    return reduced and dict(reduced, busy_s=scopes["busy_s"])


def seconds(record: Dict[str, Any], names: Sequence[str]
            ) -> Optional[Tuple[float, float]]:
    """(seconds under these names, the device's busy seconds); None where
    no op carries one of them (a program without such layers)."""
    reduced = for_record(record)
    if not reduced or not any(reduced["sub_s"].get(n) for n in names):
        return None
    return sum(reduced["sub_s"].get(n, 0.0) for n in names), \
        reduced["busy_s"]


def share(record: Dict[str, Any], names: Sequence[str]) -> Optional[float]:
    """Percent of the device's busy time under these names."""
    found = seconds(record, names)
    return None if found is None else 100.0 * found[0] / found[1]


def attention_kernels(record: Dict[str, Any]
                      ) -> Optional[Dict[str, Dict[str, list]]]:
    """`attention kind -> kernel kind -> [seconds, events]`, None where no
    kernel's event carries an `attention/<kind>`."""
    reduced = for_record(record)
    return (reduced or {}).get("kernel_s") or None
