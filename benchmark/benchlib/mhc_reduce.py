"""The device trace by the scopes of a residual path of several streams
(`mhc/maps`, `mhc/pre`, `mhc/post`, `mhc/expand`, `mhc/collapse`:
`ray_tpu/ops/mhc.py`, PERF.md section 3). None of them is a bucket of
`scope_reduce`'s vocabulary (there `mhc/post` is booked under the scope
that closes its sublayer, `attn_out`, `mlp/down` or `moe`, and the others
under `layers`, `embed` or none), so an op counts here by the last of these
names in its own path.

The reduction is `kda_reduce.reduce_kda`'s loop, imported and not copied:
it asks its module's `scope_of` for each op's scope when it runs, so
`reduce_mhc` lends it this module's for the call (as `gdn_reduce` and
`loop_reduce` do).

The readers under layer_metrics/ call `seconds` and `share`; on a program
without the scopes (the parent of the PR that added them), or a run
without a device trace, they return None.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import kda_reduce
from benchlib import scope_reduce as sr

MAPS = "mhc/maps"
MIXES = ("mhc/pre", "mhc/post")
SCOPES = (MAPS,) + MIXES + ("mhc/expand", "mhc/collapse")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")


def scope_of(path: str) -> Optional[str]:
    found = _SCOPE.findall(sr.clean(path))
    return found[-1] if found else None


def reduce_mhc(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Self time, inside `bench_window`, of every op under one of
    `SCOPES`, by scope; seconds, mean over the chips. None without the
    window or a device plane."""
    theirs = kda_reduce.scope_of
    kda_reduce.scope_of = scope_of
    try:
        return kda_reduce.reduce_kda(trace)
    finally:
        kda_reduce.scope_of = theirs


_REDUCED: Dict[str, Optional[Dict[str, float]]] = {}


def seconds(record: Dict[str, Any], names: Sequence[str] = SCOPES
            ) -> Optional[Tuple[float, float]]:
    """(seconds under these scopes, the device's busy seconds) in the
    trace this record's run wrote; parsed once per process. None where
    `scope_reduce.for_record` is, or where no op carries one of `SCOPES`
    (a program with one residual stream)."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        _REDUCED[path] = reduce_mhc(sr.from_xplane(path))
    reduced = _REDUCED[path]
    if not reduced or not any(reduced.get(n) for n in SCOPES):
        return None
    return sum(reduced.get(n, 0.0) for n in names), scopes["busy_s"]


def share(record: Dict[str, Any], names: Sequence[str] = SCOPES
          ) -> Optional[float]:
    """Percent of the device's busy time under these scopes."""
    found = seconds(record, names)
    return None if found is None else 100.0 * found[0] / found[1]
