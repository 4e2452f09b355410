"""The program's `train.*` spans (PERF.md section 3): the gang's phases in
the driver's flight recorder, `train.step` / `train.report` in the worker's,
and `spans.traced` putting the same name on the host line of a device
trace without ever importing JAX itself."""

import glob
import os
import subprocess
import sys
import tempfile
import time

import pytest

from ray_tpu._private import spans

GANG = ["train.gang.placement", "train.gang.actors", "train.gang.visibility",
        "train.gang.backend", "train.gang.sessions"]
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
