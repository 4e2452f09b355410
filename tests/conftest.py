"""Test config: chip-free TPU fake ladder (jax on CPU, 8 virtual devices).

reference parity for the testing idea: SURVEY.md §4 — every process boundary
has an in-process fake; jax runs on an 8-device virtual CPU mesh so all
sharding/collective code paths compile and execute without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere in the test process. Force,
# don't setdefault: a machine with a chip presets JAX_PLATFORMS to it, and
# unit tests must stay on the virtual CPU mesh. Worker processes inherit
# this environment.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Pretend there are no TPU chips so the runtime under test doesn't claim a
# real chip during unit tests.
os.environ.setdefault("RAY_TPU_FAKE_NUM_CHIPS", "0")
# Train workers and learners place a persistent compile cache
# (_private/compile_cache.py); the ladder compiles cold, as it always has,
# so no test's result depends on what an earlier run left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

# The environment is read when jax is first imported; a plugin or a
# sitecustomize that imported it earlier would have missed the lines
# above, so pin the platform through jax.config as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@pytest.fixture(scope="session")
def ray_session():
    """One shared local cluster for the whole test session (worker spawn is
    expensive on small CI machines)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture()
def ray_start(ray_session):
    """Per-test alias; the session cluster is reused (re-initialized if a
    multinode/cluster test shut the previous one down)."""
    if not ray_session.is_initialized():
        ray_session.init(num_cpus=4, ignore_reinit_error=True)
    return ray_session


def assert_ownership_drains(timeout_s: float = 15.0) -> None:
    """Post-test leak canary (ownership protocol): with the test's work
    done, the driver's lease request slots, pipeline depths and running
    sets must drain to zero (_private/ownership.py — the ADVICE-r5
    stall-leak class). Cheap (no cluster fan-out); used as a teardown
    assertion by the fault-injection suites, where a leak would
    otherwise hide until some later test stalls."""
    import gc
    import time

    import ray_tpu
    from ray_tpu._private import ownership
    from ray_tpu._private import worker as worker_mod

    if not ray_tpu.is_initialized():
        return  # the test tore its cluster down; nothing to leak into
    w = worker_mod.global_worker_or_none()
    if w is None or w.core_worker is None:
        return
    cw = w.core_worker
    deadline = time.monotonic() + timeout_s
    leaks = []
    while time.monotonic() < deadline:
        gc.collect()
        with cw._lock:
            leaks = ownership.lease_drain_report(cw._ltab)
        if not leaks:
            return
        time.sleep(0.25)
    pytest.fail("ownership drains-to-zero canary failed: "
                + "; ".join(leaks))
