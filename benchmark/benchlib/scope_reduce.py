"""From this run's device trace to time by the program's own names: the
`jax.named_scope` vocabulary of `ray_tpu/models/transformer.py` and
`ray_tpu/parallel/train_step.py` on the device, and the program's
`train.*` spans on the host (PERF.md section 3 lists both).

The readers under layer_metrics/ get only the record, and the record
holds no scope, so the file on disk is the channel: the `.xplane.pb` the
run's traced steps wrote under `.bench_scratch/<cell>/trace/`, read in
run.py's process after the job has returned. A trace an earlier run left
there is never read: without a file written after this run's window the
readers return nothing and their metrics are absent, as they are on a
program that has no scopes (every reader returns None, none raises).

Where the path is (seen on a v5e, PR 24). An `XLA Ops` event carries no
scope itself: its name is the HLO text without metadata, and its own stats
are `device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`,
which is all `jax.profiler.ProfileData` shows. The HLO `op_name` is the
stat `tf_op` of the event's METADATA (`XEventMetadata.stats`), e.g.

    jit(_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/
        rematted_computation/mlp/gate_up/btd,dgf->btgf/dot_general:

so `op_paths` reads that one stat from the file's bytes (the protobuf wire
format of xplane.proto, four messages deep), and `ProfileData` gives the
events as it does for `trace_reduce.from_xplane`. Forward, backward and
recomputation need no scope of the program's: JAX wraps the scope path in
`jvp(...)` on the way forward, `transpose(jvp(...))` on the way back, and
puts `rematted_computation` under `checkpoint` where remat runs a forward
again.

The neutral form is trace_reduce's with a fourth element per device
event, the scope path ('' where XLA made an op with no `op_name`):

    [name, start_ns, duration_ns, path]
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchlib import trace_reduce as tr
from benchlib.spec import ROOT

SCRATCH = os.path.join(ROOT, ".bench_scratch")
# the vocabulary, as PERF.md section 3 has it
SCOPES = ("embed", "layers", "attn_norm", "qkv", "attention", "attn_out",
          "mlp_norm", "mlp/gate_up", "mlp/down", "moe", "final_norm",
          "head", "loss", "optimizer")
COLLECTIVES = "collectives"
UNSCOPED = "unscoped"
PHASES = ("forward", "backward", "recompute", "optimizer")
SPAN_PREFIX = "train."
_TRANSFORM = re.compile(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)")
_SCOPE = re.compile(
    r"(?:^|/)(" + "|".join(sorted(map(re.escape, SCOPES), key=len,
                                  reverse=True)) + r")(?=/|$)")


# ---- the file ----------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: a varint as an int,
    a length-delimited field as its bytes, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf: bytes) -> bytes:
    """The value of a map<int64, Message> entry."""
    return next((v for f, v in _fields(buf) if f == 2), b"")


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """device plane -> event name -> the `tf_op` stat of the event's
    metadata. xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (the id of a stat metadata whose name
    is the value)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, stat_names, events = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 5:
                meta = dict(_fields(_map_entry(v)))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
            elif f == 4:
                events.append(_map_entry(v))
        if not tr.DEVICE_PLANE.match(name):
            continue
        paths = out[name] = {}
        for meta in events:
            event_name, path = "", ""
            for f, v in _fields(meta):
                if f == 2:
                    event_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        path = stat[5].decode() if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            paths[event_name] = path
    return out


def from_xplane(path: str) -> Dict[str, Any]:
    """The neutral form with scope paths: of a device plane the `XLA Ops`
    line, of the host plane the benchmark's window and the program's
    `train.*` annotations."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    paths = op_paths(raw)
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
                          if e.name == tr.WINDOW_ANNOTATION
                          or e.name.startswith(SPAN_PREFIX)]
            elif line.name == tr.OPS_LINE:
                of = paths[plane.name]
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           of.get(e.name, "")] for e in line.events]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def newest_trace(after: float) -> Optional[str]:
    """The newest .xplane.pb under the scratch directory written after
    `after` (seconds since the epoch: the start of this run's window)."""
    fresh = [p for p in glob.glob(os.path.join(
        SCRATCH, "*", "trace", "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(p) >= after]
    return max(fresh, key=os.path.getmtime) if fresh else None


# ---- the reduction -----------------------------------------------------


def clean(path: str) -> str:
    """`transpose(jvp(layers))/while/...:` -> `layers/while/...`."""
    path = path.rstrip(":")
    while _TRANSFORM.search(path):
        path = _TRANSFORM.sub(r"\1", path)
    return path


def scope_of(path: str) -> Optional[str]:
    """The innermost scope of the vocabulary in an op's path."""
    found = _SCOPE.findall(clean(path))
    return found[-1] if found else None


def bucket_of(short: str, path: str) -> str:
    if tr.COLLECTIVE.match(short):
        return COLLECTIVES
    return scope_of(path) or UNSCOPED


def phase_of(path: str) -> str:
    """An op with no path at all (a copy or convert XLA made itself)
    counts as forward: nothing says otherwise."""
    if scope_of(path) == "optimizer":
        return "optimizer"
    if "rematted_computation" in path:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


def is_custom_call(name: str) -> bool:
    """Whether a device event is a kernel the program called (a pallas
    call) and no op of XLA's own, by the event's HLO text. (Not by the
    path: a layout copy XLA puts before a kernel inherits the kernel's
    `.../pallas_call` path; seen on a v5e, PR 31.) The two recorded
    fixtures cut their names short of the opcode: nothing is marked there."""
    return " custom-call(" in name


def reduce_scopes(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Every `XLA Ops` event's self time inside `bench_window` to exactly
    one bucket (collectives first, else the innermost scope, else
    `unscoped`) and one phase; seconds, mean over the chips. None without
    the window or a device plane."""
    host = [e for p in trace["planes"] if p["name"] == tr.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    windows = [(e[1], e[1] + e[2]) for e in host
               if e[0] == tr.WINDOW_ANNOTATION]
    planes = [p for p in trace["planes"]
              if tr.DEVICE_PLANE.match(p["name"])]
    if not windows or not planes:
        return None
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    n = len(planes)
    self_s = busy_s = 0.0
    cells: Dict[Tuple[str, str], float] = defaultdict(float)
    ops: Dict[Tuple[str, str, str], List[float]] = defaultdict(
        lambda: [0.0, 0])
    custom = set()
    for plane in planes:
        events = [e for line in plane["lines"] if line["name"] == tr.OPS_LINE
                  for e in line["events"] if e[1] + e[2] > lo and e[1] < hi]
        path_at = {(e[0], e[1]): e[3] for e in events}
        timed = tr.self_times([e[:3] for e in events])
        busy_s += tr.total(tr.union(tr.clip(
            ((s, e) for _, s, e, _, _ in timed), lo, hi))) / n / 1e9
        for name, start, _end, self_ns, _leaf in timed:
            short, path = tr.short_name(name), path_at[(name, start)]
            key = (bucket_of(short, path), phase_of(path))
            seconds = self_ns / n / 1e9
            self_s += seconds
            cells[key] += seconds
            slot = ops[(short,) + key]
            slot[0] += seconds
            slot[1] += 1
            if is_custom_call(name):
                custom.add(short)
    bucket_s: Dict[str, float] = defaultdict(float)
    phase_s: Dict[str, float] = defaultdict(float)
    for (bucket, phase), seconds in cells.items():
        bucket_s[bucket] += seconds
        phase_s[phase] += seconds
    if abs(sum(bucket_s.values()) - self_s) > 1e-3 * self_s:
        raise ValueError(f"buckets {sum(bucket_s.values())} s do not sum "
                         f"to the ops' self time {self_s} s")
    spans: Dict[str, List[float]] = defaultdict(list)
    for e in host:
        if e[0].startswith(SPAN_PREFIX) and lo <= e[1] and e[1] + e[2] <= hi:
            spans[e[0]].append(e[2] / 1e9)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])
    top = ranked[:40]
    return {
        "devices": n,
        "self_s": self_s,
        "busy_s": busy_s,
        "bucket_s": dict(bucket_s),
        "phase_s": dict(phase_s),
        "bucket_phase_s": {f"{b}|{p}": s for (b, p), s in cells.items()},
        "top_ops": [[short, bucket, phase, s, int(c)]
                    for (short, bucket, phase), (s, c) in top],
        # what `describe_attention` prints: the scope's largest ops, the
        # program's kernels (custom calls) marked
        "attention_ops": [[short, phase, s, int(c), short in custom]
                          for (short, bucket, phase), (s, c) in ranked
                          if bucket == "attention"][:16],
        "host_spans_s": dict(spans),
    }


# ---- what the readers call ---------------------------------------------

_REDUCED: Dict[str, Optional[Dict[str, Any]]] = {}


def for_record(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The scope reduction of the trace this record's run wrote, parsed
    once per process; None for a run without a device trace, a trace
    directory an earlier run left, or a program without the scopes."""
    if not (record.get("trace") or {}).get("devices"):
        return None
    path = newest_trace(record.get("window_started_at", float("inf")))
    if path is None:
        return None
    if path not in _REDUCED:
        reduced = reduce_scopes(from_xplane(path))
        if reduced and not any(reduced["bucket_s"].get(s) for s in SCOPES):
            reduced = None
        _REDUCED[path] = reduced
    return _REDUCED[path]


def share(record: Dict[str, Any], buckets: Sequence[str] = (),
          phases: Sequence[str] = ()) -> Optional[float]:
    """Percent of the device's busy time in these buckets, or phases."""
    reduced = for_record(record)
    if not reduced or not reduced["busy_s"]:
        return None
    seconds = sum(reduced["bucket_s"].get(b, 0.0) for b in buckets) + \
        sum(reduced["phase_s"].get(p, 0.0) for p in phases)
    return 100.0 * seconds / reduced["busy_s"]


def describe_attention(record: Dict[str, Any]) -> str:
    """For a kernel reader that found nothing: what ran under the scope
    `attention` in this run's trace, the custom calls by their names, so
    that whoever changed the kernels sees which names the configuration's
    `kernels.attn` patterns had to match."""
    kinds = ((record.get("trace") or {}).get("kernel_s") or {}).get("attn")
    head = f"events per kind of `kernels.attn` [seconds, count]: {kinds}; "
    reduced = for_record(record)
    if not reduced:
        return head + "no device trace of this run with the program's scopes"
    found = reduced.get("attention_ops") or []
    if not found:
        return head + "no op ran under the scope `attention`"
    calls = [f"{short} ({phase}, {s * 1e3:.2f} ms x{count})"
             for short, phase, s, count, is_call in found if is_call]
    rest = [short for short, _p, _s, _c, is_call in found if not is_call]
    return (head + "custom calls under the scope `attention`: "
            + ("; ".join(calls) or "none")
            + "; the scope's other ops: " + (", ".join(rest[:8]) or "none"))


def span_median_ms(record: Dict[str, Any], name: str) -> Optional[float]:
    """Median of one of the program's `train.*` annotations over the
    traced steps."""
    reduced = for_record(record)
    durations = (reduced or {}).get("host_spans_s", {}).get(name)
    return statistics.median(durations) * 1e3 if durations else None


def gang_span_s(names: Sequence[str]) -> Optional[float]:
    """Seconds in these `train.gang.*` spans of the first gang this
    process formed, from its own flight recorder: run.py's process is the
    train driver, and the ring outlives `shutdown()`. None on a program
    whose `timeline` cannot serve there, or that records no such span."""
    try:
        import ray_tpu
        events = ray_tpu.timeline(spans=True)
    except Exception:  # noqa: BLE001 - a program without the accessor
        return None
    gang = [e for e in events if e.get("ph") == "X"
            and str(e.get("name", "")).startswith(SPAN_PREFIX + "gang.")]
    if not gang:
        return None
    first = min(gang, key=lambda e: e["ts"])["args"].get("gang")
    mine = [e["dur"] / 1e6 for e in gang
            if e["name"] in names and e["args"].get("gang") == first]
    return sum(mine) if mine else None


def main(argv: List[str]) -> int:
    """`cd benchmark && python3 -m benchlib.scope_reduce <file.xplane.pb>
    <traced steps>`: the table PERF.md section 5 is written from — ms a
    step and chip by bucket and phase, then the largest ops."""
    reduced = reduce_scopes(from_xplane(argv[1]))
    if not reduced:
        print("no `bench_window` annotation or no device plane")
        return 1
    steps, busy = float(argv[2]) if len(argv) > 2 else 1.0, reduced["busy_s"]
    print(f"{reduced['devices']} chip(s), busy {busy / steps * 1e3:.1f} ms "
          f"a step\n{'bucket':14s}{'ms':>9s}{'share':>8s}  "
          + "".join(f"{p:>10s}" for p in PHASES))
    cells = reduced["bucket_phase_s"]
    for bucket, s in sorted(reduced["bucket_s"].items(),
                            key=lambda kv: -kv[1]):
        print(f"{bucket:14s}{s / steps * 1e3:9.2f}{100 * s / busy:7.2f}%  "
              + "".join(f"{cells.get(f'{bucket}|{p}', 0) / steps * 1e3:10.2f}"
                        for p in PHASES))
    for short, bucket, phase, s, count in reduced["top_ops"][:16]:
        print(f"  {short[:46]:46s} {bucket:12s} {phase:9s} "
              f"{s / steps * 1e3:8.2f} ms {100 * s / busy:5.2f}% x{count}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv))
