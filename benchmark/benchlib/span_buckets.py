"""The program's flight-recorder spans (`ray_tpu.timeline(spans=True)`)
split into buckets on one thread's wall time. A copy of the bucket
arithmetic of `tools/perf_report.py` (`attribute`): the thread with the
most `learner.*` span time, each instant in the highest-priority bucket
whose span covers it, the uncovered rest idle. Kept here so that no PR
that claims a gain can change it; the original is listed in PERF.md for a
later PR to fold onto this one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchlib.trace_reduce import subtract, total, union

# bucket -> (priority, span-name prefixes); higher priority wins overlap
BUCKETS: Dict[str, Tuple[int, Tuple[str, ...]]] = {
    "elastic_reconfig": (5, ("elastic.",)),
    "host_sync": (4, ("host_sync.",)),
    "store_rpc": (3, ("rpc.", "store.", "cw.", "envelope.")),
    "device_feed": (2, ("feed.stage", "feed.ship", "feed.xfer",
                        "feed.unfuse")),
    "rollout_wait": (1, ("feed.wait", "runner.sample")),
    "learner_compute": (0, ("learner.",)),
}


def _bucket_of(name: str) -> Optional[str]:
    for bucket, (_prio, prefixes) in BUCKETS.items():
        if name.startswith(prefixes):
            return bucket
    return None


def _spans(events: List[Dict[str, Any]]):
    for e in events:
        if e.get("ph") == "X" and e.get("cat") == "span":
            yield e


def pick_thread(events: List[Dict[str, Any]]) -> Optional[Tuple[Any, Any]]:
    learner: Dict[Tuple[Any, Any], float] = {}
    every: Dict[Tuple[Any, Any], float] = {}
    for e in _spans(events):
        key = (e.get("pid"), e.get("tid"))
        dur = float(e.get("dur", 0.0))
        every[key] = every.get(key, 0.0) + dur
        if str(e.get("name", "")).startswith("learner."):
            learner[key] = learner.get(key, 0.0) + dur
    pool = learner or every
    return max(pool, key=pool.get) if pool else None


def attribute(events: List[Dict[str, Any]],
              since_us: float = 0.0) -> Optional[Dict[str, Any]]:
    """Seconds per bucket on the picked thread over the part of the ring
    that starts at or after `since_us`, and how many seconds that was."""
    key = pick_thread(events)
    if key is None:
        return None
    per: Dict[str, List[Tuple[float, float]]] = {b: [] for b in BUCKETS}
    lo = hi = None
    for e in _spans(events):
        if (e.get("pid"), e.get("tid")) != key or e["ts"] < since_us:
            continue
        t0 = float(e["ts"]) / 1e6
        t1 = t0 + float(e.get("dur", 0.0)) / 1e6
        lo = t0 if lo is None else min(lo, t0)
        hi = t1 if hi is None else max(hi, t1)
        bucket = _bucket_of(str(e.get("name", "")))
        if bucket is not None:
            per[bucket].append((t0, t1))
    if lo is None:
        return None
    window = hi - lo
    claimed: List[Tuple[float, float]] = []
    seconds: Dict[str, float] = {}
    for b in sorted(BUCKETS, key=lambda b: -BUCKETS[b][0]):
        mine = union(per[b])
        seconds[b] = total(subtract(mine, claimed))
        claimed = union(claimed + mine)
    seconds["idle"] = max(0.0, window - sum(seconds.values()))
    return {"process": str(key[0]), "thread": str(key[1]),
            "covered_s": window, "seconds": seconds}
