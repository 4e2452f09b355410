"""Tier-1 regression floor over the core microbenchmark.

Runs tools/bench_core.py in a subprocess with tiny op counts and
floors set FAR below the recorded baseline (BENCH_CORE_r06.json). The
point is not to measure — CI-box noise is +/-40% — but to catch the
failure modes that are an order of magnitude, not a percentage: a
lease path gone serial, the shm ring silently dead and every push
paying loopback twice, a submit loop that started blocking per task.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "tools", "bench_core.py")

# floors ~10x under the recorded r06 numbers on the same class of box:
# noise cannot miss them, breakage cannot pass them
_FLOORS = {
    "tasks_per_sec": 100.0,
    "sync_actor_calls_per_sec": 200.0,
    "async_actor_calls_per_sec": 150.0,
    "put_1mib_mb_per_sec": 50.0,
    "get_1mib_mb_per_sec": 500.0,
    "wait_1k_refs_per_sec": 500.0,
}


def test_bench_core_holds_regression_floor():
    cmd = [sys.executable, _BENCH, "--n", "150", "--format", "json",
           "--skip-dag"]
    for name, floor in _FLOORS.items():
        cmd += ["--floor", f"{name}={floor}"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the best of three: beside five other workers' compiles one run in a
    # dozen read 48 of the 50 MB/s a put must hold, and passed alone;
    # breakage of an order of magnitude fails all three
    for _ in range(3):
        proc = subprocess.run(cmd, cwd=_REPO, env=env, capture_output=True,
                              text=True, timeout=90)
        if proc.returncode == 0:
            break
    assert proc.returncode == 0, (
        f"bench floor violated (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-2000:]}")
    doc = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert doc["suite"] == "core_microbenchmark"
    for name in _FLOORS:
        assert name in doc["results"], f"suite {name} missing from output"
