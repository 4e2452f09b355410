"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and prints,
as the last line of its standard output, one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`). Without an accelerator, with fewer chips than the cell asks
for, or in a directory without the program, it exits non-zero and prints no
result.

Everything that belongs to one configuration, one traffic mix, one kind of
job or one per-layer metric is a file of its own that this program finds by
the name in BENCHMARK.json (see benchlib/spec.py):

    configs/<config>.json         sizes as run, source, reduced, layout, job
    traffic/<mix>.json            every parameter of the mix
    jobs/<job>.py                 run(ctx) -> record: set-up, warm-up, window
    layer_metrics/<metric>.py     read(record) -> number or None, and
                                  optionally why_nothing(record) -> str
    reference/<name>.py           the plain float32 reference

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. A traced run of a real cell in which a
reader finds nothing for a metric the cell lists says on stderr which
metric and, where the reader can tell, what it saw instead, and prints no
result (exit code 4): a last line without the metric is refused as
malformed, and says less. This process imports no JAX itself: a job decides
which process holds the chip.
"""

from __future__ import annotations

import time

_STARTED_AT = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
REHEARSAL_PREFIX = "rehearsal_"


NOTHING_READ = 4   # exit code: a listed per-layer metric had no reading


def build_metrics(spec: Dict[str, Any], workload: str, trace: bool,
                  record: Dict[str, Any],
                  nothing: Dict[str, str]) -> Dict[str, Any]:
    """The last line's `metrics`. A per-layer metric whose reader returned
    None is left out, and `nothing` gets its name with what the reader's
    `why_nothing`, if it has one, says it saw."""
    from benchlib.spec import load_module, metrics_of

    e2e = {m["name"]: m for m in metrics_of(spec, "end_to_end", workload)}
    out: Dict[str, Any] = {}
    if not trace:
        for name, meta in e2e.items():
            value = record["end_to_end"].get(name)
            if value is not None:
                out[name] = {"value": value, "unit": meta["unit"]}
        return out
    for meta in metrics_of(spec, "per_layer", workload):
        if meta["moves"] not in e2e:
            continue   # reported only where the metric it moves is
        reader = load_module("layer_metrics", meta["name"])
        value = reader.read(record)
        if value is not None:
            out[meta["name"]] = {"value": value, "unit": meta["unit"]}
        else:
            why = getattr(reader, "why_nothing", None)
            nothing[meta["name"]] = why(record) if why else \
                "its reader found nothing to read"
    return out


def emit(spec: Dict[str, Any], workload: str, seed: int, trace: bool,
         record: Dict[str, Any], rehearsal: bool) -> int:
    """From a job's record to the result line; the exit code."""
    from benchlib import trace_reduce

    device = record["device"]
    if device.get("platform") != "tpu" and not rehearsal:
        print(f"benchmark: not an accelerator: {device}", file=sys.stderr)
        return 3
    record["end_to_end"]["setup_s"] = \
        record["window_started_at"] - _STARTED_AT
    nothing: Dict[str, str] = {}
    metrics = build_metrics(spec, workload, trace, record, nothing)
    if rehearsal:   # never under a device metric's name
        metrics = {REHEARSAL_PREFIX + k: v for k, v in metrics.items()}
    line: Dict[str, Any] = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and record.get("trace"):
        line["breakdown"] = trace_reduce.breakdown(record["trace"])
    failed_checks = {k: v for k, v in record.get("checks", {}).items()
                     if not v["ok"]}
    detail = {"workload": workload, "seed": seed,
              "checks_failed": failed_checks,
              "setup_phases_s": record.get("clock", {}).get(
                  "setup_phases_s"),
              "counters": record.get("counters"),
              "static": record.get("static")}
    print("[bench] " + json.dumps(detail, default=str), file=sys.stderr,
          flush=True)
    if nothing and not rehearsal:   # a CPU rehearsal has no device trace
        for name, why in nothing.items():
            print(f"[bench] NO READING of {name} in {workload}: {why}",
                  file=sys.stderr)
        print(f"benchmark: no result: {workload} lists {sorted(nothing)} "
              f"and this run has no reading of them", file=sys.stderr,
              flush=True)
        return NOTHING_READ
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--spec", default=None,
                        help="another spec than <root>/BENCHMARK.json "
                             "(the rehearsal's)")
    args = parser.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "ray_tpu", "__init__.py")):
        print(f"benchmark: no program here: {ROOT} holds no ray_tpu/",
              file=sys.stderr)
        return 2
    for path in (ROOT, BENCH_DIR):
        if path not in sys.path:
            sys.path.insert(0, path)
    # workers of the program import benchlib.entry by name
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (BENCH_DIR, os.environ.get("PYTHONPATH")) if p)
    from benchlib.spec import load_module, load_spec, resolve_cell

    spec = load_spec(args.spec)
    ctx = resolve_cell(spec, args.workload)
    rehearsal = bool(ctx["config"].get("rehearsal"))
    scratch = os.path.join(ROOT, ".bench_scratch", args.workload)
    os.makedirs(scratch, exist_ok=True)
    ctx.update(
        bench_dir=BENCH_DIR, root=ROOT, scratch_dir=scratch,
        seed=args.seed, trace=bool(args.trace),
        seconds=float(args.seconds if args.seconds is not None
                      else spec["run_seconds"]))
    try:
        record = load_module("jobs", ctx["config"]["job"]).run(ctx)
    except Exception:  # noqa: BLE001 - reported, then a non-zero exit
        traceback.print_exc(file=sys.stderr)
        return 1

    return emit(spec, args.workload, args.seed, bool(args.trace), record,
                rehearsal)


if __name__ == "__main__":
    sys.exit(main())
