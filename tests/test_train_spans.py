"""The program's `train.*` spans (PERF.md section 3): the gang's phases in
the driver's flight recorder, `train.step` / `train.report` in the worker's,
and `spans.traced` putting the same name on the host line of a device
trace without ever importing JAX itself. Since PR 37 also what accounts
for a step from inside: the loop's wait for the device (`host_sync.*`
past its region), what can hold the interpreter (`gc.collect`, the
`rpc.server` tail), `train.step`'s `cpu_s` / `ivcsw`, and the workers'
rings kept past their gang."""

import gc
import glob
import os
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from ray_tpu._private import spans

# PR 55 took `train.gang.visibility` and `.sessions` away (no reader; under
# 0.04 s together): the formation is these three and a remainder
GANG = ["train.gang.placement", "train.gang.actors", "train.gang.backend"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def fitted(ray_session):
    """One one-worker fit on the CPU: its wall time, the driver's ring
    and the cluster's merged timeline afterwards."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def tiny_loop():   # a closure: sent to the worker by value
        import jax
        import jax.numpy as jnp

        import ray_tpu.train as train
        from ray_tpu.models import TINY, Transformer
        from ray_tpu.parallel import MeshConfig, make_mesh
        from ray_tpu.parallel.train_step import make_train_step

        cfg = TINY.replace(n_layers=1, max_seq_len=16)
        mesh = make_mesh(MeshConfig(data=-1))
        init_state, train_step = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
            Transformer.param_specs(cfg), mesh)
        state = init_state(Transformer.init(jax.random.key(0), cfg))
        batch = {"tokens": jnp.zeros((len(jax.devices()), 17), jnp.int32)}
        for _ in range(3):
            state, metrics = train_step(state, batch)
            train.report({"loss": float(metrics["loss"])})

    if not ray_session.is_initialized():
        ray_session.init(num_cpus=4, ignore_reinit_error=True)
    with tempfile.TemporaryDirectory(prefix="train_spans_") as storage:
        t0 = time.perf_counter()   # the ring's clock
        result = JaxTrainer(
            tiny_loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="train_spans",
                                 storage_path=storage)).fit()
        wall = time.perf_counter() - t0
    assert result.error is None, result.error
    ring = [r for r in spans.ring().snapshot_records()
            if r[2] >= t0 and r[1].startswith("train.gang.")]
    return wall, ring, ray_session.timeline(spans=True)


def test_gang_phases_in_the_drivers_ring(fitted):
    wall, ring, _ = fitted
    assert [r[1] for r in ring] == GANG
    ends = [r[2] + r[3] for r in ring]
    for end, nxt in zip(ends, ring[1:]):
        assert end <= nxt[2] + 1e-6, "phases overlap"
    assert sum(r[3] for r in ring) <= wall
    attrs = [r[6] for r in ring]
    assert len({a["gang"] for a in attrs}) == 1 and \
        attrs[0]["gang"].startswith("train:")
    assert all(a["workers"] == 1 and a["tpus"] == 0 for a in attrs)


def _worker_spans(events, name):
    return [e for e in events if e.get("ph") == "X"
            and str(e.get("name", "")) == name
            and str(e["pid"]).startswith("worker-")]


def test_loss_read_is_a_span_between_step_and_report(fitted):
    """The loop's `float(metrics["loss"])` is past `train_step`'s region:
    `host_sync.float{region="after:train.step"}` on the loop thread,
    after the step's dispatch and before its report."""
    _, _, events = fitted
    syncs = [e for e in _worker_spans(events, "host_sync.float")
             if e["args"].get("region") == "after:train.step"]
    steps = _worker_spans(events, "train.step")
    reports = _worker_spans(events, "train.report")
    assert len(syncs) >= 3 and len(steps) >= 3 and len(reports) >= 3
    loop = {(e["pid"], e["tid"]) for e in steps}
    assert len(loop) == 1
    assert {(e["pid"], e["tid"]) for e in syncs + reports} == loop
    for step, sync, report in zip(steps[-3:], syncs[-3:], reports[-3:]):
        assert step["ts"] + step["dur"] <= sync["ts"] + 1.0
        assert sync["ts"] + sync["dur"] <= report["ts"] + 1.0
        assert sync["args"]["bytes"] == 4
    # inside a region the label is the region's own, as before
    assert not [e for e in events
                if str(e.get("name", "")).startswith("host_sync.")
                and e["args"].get("region") == "untracked"]


def test_train_step_carries_the_loop_threads_usage(fitted):
    _, _, events = fitted
    steps = _worker_spans(events, "train.step")
    assert steps and all(
        e["args"]["cpu_s"] >= 0.0 and e["args"]["ivcsw"] >= 0
        and isinstance(e["args"]["ivcsw"], int) for e in steps)
    # since the previous step: no more CPU than the wall between them
    for prev, cur in zip(steps, steps[1:]):
        assert cur["args"]["cpu_s"] <= (cur["ts"] - prev["ts"]) / 1e6 + 0.05


@pytest.mark.parametrize("name", ["train.step", "train.report"])
def test_worker_spans_reach_the_timeline(fitted, name):
    _, _, events = fitted
    # from the train worker's ring (this process's own may hold spans of
    # the same name from tests that stepped a model in-process)
    mine = [e for e in events if e.get("name") == name
            and str(e["pid"]).startswith("worker-")]
    assert len(mine) >= 3, [e.get("name") for e in events][:50]
    if name == "train.report":
        assert all(0.0 <= e["args"]["blocked_s"] <= e["dur"] / 1e6 + 1e-6
                   for e in mine)


def test_traced_name_is_on_the_host_line_of_a_device_trace(tmp_path):
    """The case `tpu_profiler.annotate()` had, on the helper that
    replaced it."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    t0 = time.perf_counter()   # the ring's clock
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.traced("matmul-region", rows=64):
            jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    host = ProfileData.from_file(found[0]).find_plane_with_name("/host:CPU")
    assert any(e.name == "matmul-region"
               for line in host.lines for e in line.events)
    ring = [r for r in spans.ring().snapshot_records() if r[2] >= t0]
    assert [(r[1], r[6]) for r in ring if r[1] == "matmul-region"] == \
        [("matmul-region", {"rows": 64})]


def test_host_sync_is_on_the_host_line_of_a_device_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from ray_tpu.util import jax_sentinel

    x = jnp.ones((8,)).sum()
    with jax_sentinel.step_region("train.step"):
        pass
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert float(x) == 8.0
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = ProfileData.from_file(found[0]).find_plane_with_name("/host:CPU")
    assert any(e.name == "host_sync.float"
               for line in host.lines for e in line.events)
    ring = [r for r in spans.ring().snapshot_records()
            if r[2] >= t0 and r[1] == "host_sync.float"]
    assert [r[6] for r in ring] == [
        {"region": "after:train.step", "bytes": 4}]


def test_full_collection_is_a_span_and_a_young_one_is_none():
    t0 = time.perf_counter()
    gc.collect(0)

    def collect():
        gc.collect()

    thread = threading.Thread(target=collect, name="collector")
    thread.start()
    thread.join()
    found = [r for r in spans.ring().snapshot_records()
             if r[2] >= t0 and r[1] == "gc.collect"]
    full = [r for r in found if r[6]["generation"] == 2]
    assert len(full) == 1, found
    assert full[0][4] == thread.ident and full[0][3] > 0.0
    assert full[0][6]["thread"] == "collector"
    assert full[0][6]["collected"] >= 0
    # a generation-0 pass is recorded only if it took a millisecond
    assert all(r[3] >= spans.GC_MIN_S for r in found
               if r[6]["generation"] != 2)


def test_slow_rpc_handler_is_recorded_on_every_call():
    from ray_tpu._private import rpc

    def slow():
        time.sleep(0.01)
        return 1

    server = rpc.RpcServer({"slow": slow, "fast": lambda: 1})
    try:
        client = rpc.RpcClient(server.address, timeout=10)
        t0 = time.perf_counter()
        for _ in range(20):
            assert client.call("slow") == 1
        for _ in range(32):
            assert client.call("fast") == 1
        client.close()
    finally:
        server.stop()
    mine = [r for r in spans.ring().snapshot_records()
            if r[2] >= t0 and r[1] == "rpc.server"]
    slow_spans = [r for r in mine if r[6]["method"] == "slow"]
    assert len(slow_spans) == 20
    assert all(r[3] >= 0.01 and r[6]["sampled"] in (
        1, rpc._SERVER_SPAN_SAMPLE_K) for r in slow_spans)
    # the fast ones keep the 1-in-K sampling
    fast = [r for r in mine if r[6]["method"] == "fast"]
    assert 1 <= len(fast) <= 4 and all(
        r[6]["sampled"] == rpc._SERVER_SPAN_SAMPLE_K for r in fast)


def _python(code, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, **env))


def test_traced_never_imports_jax():
    proc = _python(
        "import sys\n"
        "from ray_tpu._private import spans\n"
        "with spans.traced('train.step', k=1) as sp:\n"
        "    sp['x'] = 2\n"
        "rec = spans.ring().snapshot_records()[-1]\n"
        "assert rec[1] == 'train.step' and rec[6] == {'k': 1, 'x': 2}, rec\n"
        "assert type(spans.traced('a')) is type(spans.span('a'))\n"
        "print('jax' in sys.modules)\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


def test_spans_off_makes_traced_the_shared_noop():
    proc = _python(
        "import jax\n"
        "from ray_tpu._private import spans\n"
        "assert spans.traced('train.step') is spans.NOOP\n"
        "with spans.traced('train.step'):\n"
        "    pass\n"
        "assert spans.ring().snapshot_records() == []\n"
        # and everything PR 37 hangs on the recorder
        "import gc, time\n"
        "import jax.numpy as jnp\n"
        "from ray_tpu._private import rpc\n"
        "from ray_tpu.util import jax_sentinel\n"
        "assert spans._on_gc not in gc.callbacks\n"
        "gc.collect()\n"
        "assert spans.thread_usage() == {}\n"
        "with jax_sentinel.step_region('train.step'):\n"
        "    pass\n"
        "assert jax_sentinel._sync_span('float') is spans.NOOP\n"
        "assert float(jnp.ones(())) == 1.0\n"
        "server = rpc.RpcServer({'slow': lambda: time.sleep(0.01)})\n"
        "client = rpc.RpcClient(server.address, timeout=10)\n"
        "client.call('slow')\n"
        "client.close(); server.stop()\n"
        "spans.retain('g', [])\n"
        "assert spans.ring().snapshot_records() == []\n",
        RAY_TPU_SPANS="0", JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_timeline_after_shutdown_serves_the_drivers_ring():
    """What the benchmark's gang readers do: the driver's own ring, read
    in-process after the cluster is gone, in a driver that never
    imported JAX."""
    proc = _python(
        "import sys, tempfile\n"
        "import ray_tpu\n"
        "from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig\n"
        "import ray_tpu.train as train\n"
        "ray_tpu.init(num_cpus=2)\n"
        "with tempfile.TemporaryDirectory() as d:\n"
        "    r = JaxTrainer(lambda: train.report({'ok': 1}),\n"
        "                   scaling_config=ScalingConfig(num_workers=1),\n"
        "                   run_config=RunConfig(name='t', storage_path=d)\n"
        "                   ).fit()\n"
        "assert r.error is None, r.error\n"
        "ray_tpu.shutdown()\n"
        "names = [e['name'] for e in ray_tpu.timeline(spans=True)\n"
        "         if e.get('name', '').startswith('train.gang.')]\n"
        "print(','.join(names))\n"
        "print('jax' in sys.modules)\n",
        JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    names, jax_imported = proc.stdout.strip().splitlines()[-2:]
    assert names.split(",") == GANG
    assert jax_imported == "False"


_FIT_THEN_SHUTDOWN = """
import gc, sys, tempfile
import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

def loop():
    import gc
    import jax, jax.numpy as jnp
    import ray_tpu.train as train
    from ray_tpu.models import TINY, Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step
    cfg = TINY.replace(n_layers=1, max_seq_len=16)
    mesh = make_mesh(MeshConfig(data=-1))
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh)
    state = init_state(Transformer.init(jax.random.key(0), cfg))
    batch = {"tokens": jnp.zeros((len(jax.devices()), 17), jnp.int32)}
    for _ in range(3):
        state, metrics = train_step(state, batch)
        train.report({"loss": float(metrics["loss"])})
    gc.collect()

ray_tpu.init(num_cpus=2)
with tempfile.TemporaryDirectory() as d:
    r = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1),
                   run_config=RunConfig(name='t', storage_path=d)).fit()
assert r.error is None, r.error
ray_tpu.shutdown()
events = [e for e in ray_tpu.timeline(spans=True) if e.get('ph') == 'X']
def of(name, worker):
    return [e for e in events if e['name'] == name
            and str(e['pid']).startswith('worker-') == worker]
backend = of('train.gang.backend', False)[0]
rings = of('train.rings', False)[0]
assert rings['args']['pulled'] == 1 and rings['args']['records'] > 0
lo, hi = backend['ts'] + backend['dur'], rings['ts'] + rings['dur']
# what the worker did under the driver's one backend span is in ITS ring,
# inside that span's interval on the driver's timebase (a CPU gang is
# given no chip: it waits for none and starts none)
imports = of('train.worker.jax_import', True)
assert len(imports) == 1 and imports[0]['args']['rank'] == 0
assert imports[0]['args']['gang'] == backend['args']['gang']
assert backend['ts'] - 5e3 <= imports[0]['ts'] and \
    imports[0]['ts'] + imports[0]['dur'] <= lo + 5e3, (backend, imports)
assert not of('train.worker.chip_wait', True)
assert not of('train.worker.tpu_start', True)
# the set-up's compiles are spans from the worker's first jit on: the
# sentinel is installed with the import, not with the first step region
compiles = of('jax.compile', True)
assert compiles and all(e['ts'] >= lo - 5e3 for e in compiles)
assert {e['args'].get('region') for e in compiles
        if 'folded_n' not in e['args']} >= {'untracked', 'train.step'}
init = of('cluster.init', False)
assert len(init) == 1 and init[0]['args'] == {'address': 'local', 'nodes': 1}
assert init[0]['ts'] + init[0]['dur'] <= backend['ts']
for name in ('train.step', 'host_sync.float', 'train.report', 'gc.collect'):
    mine = of(name, True)
    assert len(mine) >= (1 if name == 'gc.collect' else 3), (name, len(mine))
    # one timebase: the loop ran between the backend's set-up ending
    # and the driver pulling the rings (same host: a millisecond of slack)
    if name == 'gc.collect':   # the worker collected while starting too
        mine = [e for e in mine if e['ts'] >= lo][-1:]
        assert mine and mine[0]['args']['generation'] == 2
    assert all(lo - 1e3 <= e['ts'] and e['ts'] + e['dur'] <= hi + 1e3
               for e in mine), (name, lo, hi, [e['ts'] for e in mine])
assert len({e['pid'] for e in of('train.step', True)}) == 1
print('jax' in sys.modules)
"""


def test_workers_ring_outlives_the_gang_on_the_drivers_timebase():
    """After `fit()` and `shutdown()` the driver's timeline holds the
    train worker's loop (`BackendExecutor._retain_rings`), in a driver
    that never imported JAX."""
    proc = _python(_FIT_THEN_SHUTDOWN, JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "False"


def test_retained_rings_are_bounded():
    kept = dict(spans._retained)
    try:
        for g in range(spans.RETAINED_GANGS + 2):
            for generation in range(2):   # the newest per label wins
                spans.retain(f"gang-{g}", [
                    {"proc_uid": f"{g}-{w}-{generation}",
                     "label": f"worker-{w}", "spans": []}
                    for w in range(3)])
        snaps = spans.retained_snapshots()
        assert len(snaps) == 3 * spans.RETAINED_GANGS
        assert {s["proc_uid"].split("-")[0] for s in snaps} == {
            str(g) for g in range(2, spans.RETAINED_GANGS + 2)}
        assert all(s["proc_uid"].endswith("-1") for s in snaps)
    finally:
        spans._retained.clear()
        spans._retained.update(kept)
