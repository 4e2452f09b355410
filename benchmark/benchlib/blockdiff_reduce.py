"""The device trace by what a block-diffusion model adds to the scope
vocabulary (`ray_tpu/models/transformer.py`, `ray_tpu/models/diffusion.py`,
PERF.md section 3): `diffusion/noise` (the draws, the replacement, the
weights), `diffusion/stream` (the doubled stream's concatenation and
positions, the split before the final norm), and the attention kernels'
events under `attention/block_diffusion`. The same file, window and self
times as `scope_reduce`; an op counts by the last such name in its own
path. `moe_share` is `subscope_reduce`'s reading of every `moe/*` scope.

The readers under layer_metrics/ call `share`, `moe_share`,
`attention_kernels` and `pairs_computed_over_needed`; on a program without
the scopes or counters, or a run without a device trace, they return None.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import scope_reduce as sr
from benchlib import subscope_reduce
from benchlib import trace_reduce as tr

DIFFUSION = ("diffusion/noise", "diffusion/stream")
KERNEL_SCOPE = "attention/block_diffusion"
MOE = ("router", "dispatch", "experts", "combine")
_NAMED = re.compile(r"(?:^|/)(" + "|".join(DIFFUSION + (KERNEL_SCOPE,))
                    + r")(?=/|$)")


def named(path: str) -> Optional[str]:
    found = _NAMED.findall(sr.clean(path))
    return found[-1] if found else None


def reduce_blockdiff(trace: Dict[str, Any], kernels: Dict[str, str]
                     ) -> Optional[Dict[str, Any]]:
    """Inside `bench_window`, mean over the chips: `sub_s`, the self time
    of every op under one of the names, by name, in seconds; `kernel_s`,
    `kernel kind -> [seconds, events]` of the events under
    `attention/block_diffusion` whose name one of `kernels`' patterns
    matches (the configuration's `kernels.attn`: fwd, bwd_dkv, bwd_dq),
    each event's whole duration as `trace_reduce` counts a kernel's. None
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
    patterns = {kind: re.compile(rx) for kind, rx in kernels.items()}
    sub_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, list] = {}
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
            if sub != KERNEL_SCOPE:
                continue
            short = tr.short_name(name)
            for call, rx in patterns.items():
                if rx.search(short):
                    slot = kernel_s.setdefault(call, [0.0, 0])
                    slot[0] += (end - start) / len(planes) / 1e9
                    slot[1] += 1
    return {"sub_s": dict(sub_s), "kernel_s": kernel_s}


_REDUCED: Dict[str, Optional[Dict[str, Any]]] = {}


def for_record(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction with the device's busy seconds, of the trace this
    record's run wrote; parsed once per process. None where
    `scope_reduce.for_record` is."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        _REDUCED[path] = reduce_blockdiff(
            sr.from_xplane(path),
            record.get("static", {}).get("attention_kernels") or {})
    reduced = _REDUCED[path]
    return reduced and dict(reduced, busy_s=scopes["busy_s"])


def seconds(record: Dict[str, Any], names: Sequence[str] = DIFFUSION
            ) -> Optional[Tuple[float, float]]:
    """(seconds under these names, the device's busy seconds); None where
    no op carries one of them (a program without the scopes)."""
    reduced = for_record(record)
    if not reduced or not any(reduced["sub_s"].get(n) for n in names):
        return None
    return sum(reduced["sub_s"].get(n, 0.0) for n in names), \
        reduced["busy_s"]


def share(record: Dict[str, Any], names: Sequence[str] = DIFFUSION
          ) -> Optional[float]:
    """Percent of the device's busy time under these names."""
    found = seconds(record, names)
    return None if found is None else 100.0 * found[0] / found[1]


def moe_share(record: Dict[str, Any]) -> Optional[float]:
    """Percent of the device's busy time under `moe/router`,
    `moe/dispatch`, `moe/experts` and `moe/combine`."""
    return subscope_reduce.share(record, "moe", MOE)


def attention_kernels(record: Dict[str, Any]) -> Optional[Dict[str, list]]:
    """`kernel kind -> [seconds, events]` of the kernels' events under
    `attention/block_diffusion`; None where there is none."""
    reduced = for_record(record)
    return (reduced or {}).get("kernel_s") or None


def pairs_computed_over_needed(record: Dict[str, Any]) -> Optional[float]:
    """The (query, key) pairs the compiled kernel call computes, its
    non-empty blocks times a block's area, over the pairs the mask needs:
    from the mask's block table as the job read it at trace time
    (`static.mask_blocks`); 1 were no block partial."""
    table = record.get("static", {}).get("mask_blocks")
    if not table or not table.get("pairs_needed"):
        return None
    return table["non_empty"] * table["block_pairs"] / table["pairs_needed"]
