"""Device profiling wrappers (jax.profiler integration).

reference parity: profiling surface (dashboard reporter py-spy/memray +
ray timeline); the TPU-native counterpart captures XLA device traces.
Runs on the chip-free CPU backend — jax.profiler works there too.
"""

import os

import numpy as np

from ray_tpu._private import spans
from ray_tpu.util import tpu_profiler


def test_trace_produces_xplane_capture(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.asarray(np.random.randn(64, 64), jnp.float32)
    with tpu_profiler.trace(str(tmp_path)) as d:
        # named regions inside a trace: the span helper (its name is
        # then on the host line, tests/test_train_spans.py)
        with spans.traced("matmul-region"):
            jax.block_until_ready(f(x))
        assert d == str(tmp_path)
    run = tpu_profiler.latest_trace_dir(str(tmp_path))
    assert run is not None
    assert any(name.endswith(".xplane.pb") for name in os.listdir(run))


def test_profile_step_returns_result_and_dir(tmp_path):
    import jax.numpy as jnp

    out, d = tpu_profiler.profile_step(
        lambda a, b: a + b, jnp.ones(4), jnp.ones(4),
        log_dir=str(tmp_path / "p"))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert tpu_profiler.latest_trace_dir(d) is not None


def test_device_memory_profile_bytes(tmp_path):
    path = str(tmp_path / "mem.pprof")
    blob = tpu_profiler.device_memory_profile(path)
    assert isinstance(blob, bytes) and len(blob) > 0
    assert os.path.getsize(path) == len(blob)
