"""Test config: chip-free TPU fake ladder (jax on CPU, 8 virtual devices).

reference parity for the testing idea: SURVEY.md §4 — every process boundary
has an in-process fake; jax runs on an 8-device virtual CPU mesh so all
sharding/collective code paths compile and execute without TPU hardware.
"""

import contextlib
import gc
import os
import signal

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


# What one tier-1 test may take, in each of setup, call and teardown: more
# than twice what the dearest test that passes takes beside five other
# workers (a whole step compiled for the v5e, or a rehearsal under
# tests/benchmark/: about two minutes) and far below the limit around the
# whole run, so that a test that hangs costs five minutes and one red with
# its name, not the run.
TEST_LIMIT_S = 300.0


@contextlib.contextmanager
def time_limit(name):
    """Fail `name` if the body runs past TEST_LIMIT_S: SIGALRM on the main
    thread, where pytest and xdist's workers run tests. Leaves the handler
    and the timer as it found them."""
    def expired(signum, frame):
        pytest.fail(f"{name} ran past the {TEST_LIMIT_S:g} s a tier-1 test "
                    "may take (tests/conftest.py)", pytrace=False)

    handler = signal.signal(signal.SIGALRM, expired)
    timer = signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *timer)
        signal.signal(signal.SIGALRM, handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with time_limit(item.nodeid):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(item.nodeid):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with time_limit(item.nodeid):
        return (yield)


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


@pytest.fixture(autouse=True, scope="module")
def _drop_what_the_module_compiled():
    """A test file's compiled programs and traces end with it. A worker of
    the whole run lives through twenty files, and everything jax keeps for
    the files before (its jit and tracing caches, and what they hold alive
    for the collector to walk) slowed the files after: op by op the same
    test took 54 s behind three model files where it takes 37 s behind
    them with this."""
    yield
    jax.clear_caches()
    gc.collect()
