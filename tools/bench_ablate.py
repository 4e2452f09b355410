"""Ablation harness for the task path. Not part of the driver flow — dev
tool; run it from the repo root.

Usage: python tools/bench_ablate.py --suite lease [--n 1500]
           [--merge BENCH_CORE_r06.json]

`--suite lease` ablates the task-path lease transport (ROADMAP item
1): serialized lease requests (MAX_PENDING_LEASE_REQUESTS=1), the r05
pipelined default (=4), and the batched control plane (async lease
requester + multi-grant nm_lease_request_batch). Each variant runs in
a fresh subprocess — the flags are read at init. `--merge` writes the
table under "ablations"/"lease" of an existing bench JSON.
"""

from __future__ import annotations

import os
import sys

# ----------------------------------------------------------------------
# --suite lease: task-path lease-transport ablation
# ----------------------------------------------------------------------

_LEASE_RUNNER = r"""
import json, sys, time
import ray_tpu
from ray_tpu._private.core_worker import CoreWorker
CoreWorker.MAX_PENDING_LEASE_REQUESTS = int(sys.argv[1])
n = int(sys.argv[2])
ray_tpu.init(num_cpus=4)

@ray_tpu.remote
def tiny():
    return b"ok"

ray_tpu.get([tiny.remote() for _ in range(n)])  # warm the worker pool
t0 = time.perf_counter()
ray_tpu.get([tiny.remote() for _ in range(n)])
dt = time.perf_counter() - t0
print("RESULT " + json.dumps(
    {"tasks_per_sec": round(n / dt, 1), "seconds": round(dt, 3)}))
ray_tpu.shutdown()
"""

# (name, RAY_TPU_TASK_LEASE_BATCHING, MAX_PENDING_LEASE_REQUESTS)
LEASE_VARIANTS = [
    ("pending1", "0", 1),   # serialized: one lease round trip at a time
    ("pending4", "0", 4),   # r05 default: pipelined singleton requests
    ("batched", "1", 4),    # async requester + multi-grant batch RPCs
]


def run_lease_suite(n: int, merge_path: str) -> None:
    import json
    import subprocess

    table = {}
    for name, batching, pending in LEASE_VARIANTS:
        env = dict(os.environ,
                   RAY_TPU_TASK_LEASE_BATCHING=batching,
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _LEASE_RUNNER, str(pending), str(n)],
            env=env, capture_output=True, text=True, timeout=600)
        row = None
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                row = json.loads(line[len("RESULT "):])
        if row is None:
            print(f"{name:10s} FAILED rc={proc.returncode}\n"
                  f"{proc.stderr[-2000:]}", flush=True)
            continue
        table[name] = row
        print(f"{name:10s} {row['tasks_per_sec']:>9,.0f} tasks/s "
              f"({row['seconds']:.3f}s / {n})", flush=True)
    if merge_path:
        with open(merge_path, encoding="utf-8") as f:
            doc = json.load(f)
        doc.setdefault("ablations", {})["lease"] = {
            "ops": n, "variants": table}
        with open(merge_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        print(f"merged into {merge_path}", flush=True)


def main():
    argv = sys.argv[1:]
    suite = argv[argv.index("--suite") + 1] if "--suite" in argv else None
    if suite != "lease":
        raise SystemExit(f"unknown suite: {suite} (usage: --suite lease "
                         f"[--n N] [--merge FILE])")
    n = int(argv[argv.index("--n") + 1]) if "--n" in argv else 1500
    merge = argv[argv.index("--merge") + 1] if "--merge" in argv else ""
    run_lease_suite(n, merge)


if __name__ == "__main__":
    main()
