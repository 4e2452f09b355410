"""The device trace by the scopes of a looped stack (`loops`,
`loop/exit_gate`, `loop/exit_loss`: `ray_tpu/models/transformer.py`,
PERF.md section 3). None of them is a bucket of `scope_reduce`'s
vocabulary, so an op counts here by its own path:

    loop/exit_gate, loop/exit_loss   the last of the two names in the path
    loops                            the path lies under `loops` and under
                                     NO scope of the vocabulary nested in
                                     it (`layers`, `final_norm`, `qkv`,
                                     ...): the loop's own ops, what the
                                     passes save stacked and sliced, the
                                     passes' weight gradients summed

The reduction is `kda_reduce.reduce_kda`'s loop, imported and not copied a
sixth time: it asks its module's `scope_of` for each op's scope when it
runs, so `reduce_loop` lends it this module's for the call (as
`gdn_reduce` does).

The readers under layer_metrics/ call `share`; on a program without the
scopes (the parent of the PR that added them), or a run without a device
trace, it returns None.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

from benchlib import kda_reduce
from benchlib import scope_reduce as sr

CARRY = "loops"
EXIT = ("loop/exit_gate", "loop/exit_loss")
SCOPES = (CARRY,) + EXIT
_EXIT = re.compile(r"(?:^|/)(" + "|".join(EXIT) + r")(?=/|$)")
_CARRY = re.compile(r"(?:^|/)" + CARRY + r"(?=/|$)")


def scope_of(path: str) -> Optional[str]:
    cleaned = sr.clean(path)
    found = _EXIT.findall(cleaned)
    if found:
        return found[-1]
    if _CARRY.search(cleaned) and sr.scope_of(path) is None:
        return CARRY
    return None


def reduce_loop(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
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


def seconds(record: Dict[str, Any], names: Sequence[str]
            ) -> Optional[Tuple[float, float]]:
    """(seconds under these scopes, the device's busy seconds) in the
    trace this record's run wrote; parsed once per process. None where
    `scope_reduce.for_record` is, or where no op carries one of
    `SCOPES` (a program without a looped stack)."""
    scopes = sr.for_record(record)
    if not scopes or not scopes["busy_s"]:
        return None
    path = sr.newest_trace(record.get("window_started_at", float("inf")))
    if path not in _REDUCED:
        _REDUCED[path] = reduce_loop(sr.from_xplane(path))
    reduced = _REDUCED[path]
    if not reduced or not any(reduced.get(n) for n in SCOPES):
        return None
    return sum(reduced.get(n, 0.0) for n in names), scopes["busy_s"]


def share(record: Dict[str, Any], names: Sequence[str]) -> Optional[float]:
    """Percent of the device's busy time under these scopes."""
    found = seconds(record, names)
    return None if found is None else 100.0 * found[0] / found[1]
