"""Actor concurrency groups: named per-group thread pools.

reference parity: core_worker concurrency_group_manager.h +
thread_pool.h:36 — methods assigned to a named group execute on that
group's dedicated pool, so a saturated group (long compute) never
blocks another group's calls (health probes, IO); ray.method
(concurrency_group=...) assigns, options(concurrency_groups={...})
declares (tests/test_concurrency_group.py in the reference).
"""

import time

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_start):
    """Shared session cluster."""


def test_busy_group_does_not_block_other_group():
    @ray_tpu.remote
    class Worker:
        def __init__(self):
            self.release = False

        @ray_tpu.method(concurrency_group="compute")
        def busy(self):
            # occupies the single "compute" slot until released
            while not self.release:
                time.sleep(0.01)
            return "done"

        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            return "pong"

        def set_release(self):
            # default group: also must run while compute is saturated
            self.release = True
            return True

    a = Worker.options(
        concurrency_groups={"compute": 1, "io": 2}).remote()
    busy_ref = a.busy.remote()
    # with compute saturated, io and default-group calls still run
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
    assert ray_tpu.get(a.set_release.remote(), timeout=30) is True
    assert ray_tpu.get(busy_ref, timeout=30) == "done"
    ray_tpu.kill(a)


def test_method_level_group_override():
    @ray_tpu.remote
    class Worker:
        def which(self):
            import threading
            return threading.current_thread().name

    a = Worker.options(concurrency_groups={"g1": 1}).remote()
    default_thread = ray_tpu.get(a.which.remote(), timeout=30)
    grouped = ray_tpu.get(
        a.which.options(concurrency_group="g1").remote(), timeout=30)
    assert grouped.startswith("exec-g1")
    assert not default_thread.startswith("exec-g1")
    ray_tpu.kill(a)


def test_undeclared_group_rejected():
    @ray_tpu.remote
    class Bad:
        @ray_tpu.method(concurrency_group="nope")
        def f(self):
            return 1

    with pytest.raises(ValueError, match="undeclared"):
        Bad.remote()


def test_call_time_undeclared_group_rejected():
    @ray_tpu.remote
    class W:
        def f(self):
            return 1

    a = W.options(concurrency_groups={"io": 1}).remote()
    with pytest.raises(ValueError, match="no concurrency group"):
        a.f.options(concurrency_group="helath").remote()  # typo
    assert ray_tpu.get(
        a.f.options(concurrency_group="io").remote(), timeout=30) == 1
    ray_tpu.kill(a)


def test_empty_group_name_rejected():
    @ray_tpu.remote
    class W:
        def f(self):
            return 1

    with pytest.raises(ValueError, match="non-empty"):
        W.options(concurrency_groups={"": 1}).remote()


def test_named_actor_handle_carries_method_groups():
    @ray_tpu.remote
    class Named:
        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            import threading
            return threading.current_thread().name

    a = Named.options(name="cg-named",
                      concurrency_groups={"io": 1}).remote()
    ray_tpu.get(a.ping.remote(), timeout=30)
    b = ray_tpu.get_actor("cg-named")
    thread = ray_tpu.get(b.ping.remote(), timeout=30)
    assert thread.startswith("exec-io")
    ray_tpu.kill(a)


def test_max_pending_calls_backpressure():
    """reference max_pending_calls (_private/ray_option_utils.py):
    submitting past the bound raises PendingCallsLimitExceeded."""
    import time

    import ray_tpu.exceptions as exc

    @ray_tpu.remote
    class Slow:
        def work(self, marker):
            time.sleep(2.0)
            return marker

        def fast(self):
            return "ok"

    a = Slow.options(max_pending_calls=2).remote()
    r1 = a.work.remote(1)
    r2 = a.work.remote(2)
    with pytest.raises(exc.PendingCallsLimitExceeded):
        a.work.remote(3)
    # the limit clears as calls finish
    assert ray_tpu.get(r1, timeout=120) == 1
    assert ray_tpu.get(r2, timeout=120) == 2
    r4 = a.work.remote(4)
    assert ray_tpu.get(r4, timeout=120) == 4
    ray_tpu.kill(a)


def test_unsupported_runtime_env_rejected():
    @ray_tpu.remote
    def f():
        return 1

    # conda is IMPLEMENTED now (test_runtime_env_conda_container.py);
    # malformed specs still fail fast at submission
    with pytest.raises(ValueError, match="conda must be"):
        f.options(runtime_env={"conda": ["python=3.11"]}).remote()

    @ray_tpu.remote
    class A:
        def g(self):
            return 1

    with pytest.raises(ValueError, match="unsupported runtime_env"):
        A.options(runtime_env={"docker": {"image": "x"}}).remote()


def test_named_lookup_carries_max_pending_calls(tmp_path):
    import os
    import uuid

    import ray_tpu.exceptions as exc

    @ray_tpu.remote
    class Slow2:
        def work(self, release):
            # pending until the test says so: the second submit below is
            # refused whatever the machine's load, not inside 1.5 s of it
            while not os.path.exists(release):
                time.sleep(0.01)
            return 1

    name = f"bounded-{uuid.uuid4().hex}"     # no neighbour's actor
    release = str(tmp_path / "release")
    a = Slow2.options(name=name, max_pending_calls=1).remote()
    b = ray_tpu.get_actor(name)
    assert b._max_pending_calls == 1
    r = b.work.remote(release)
    with pytest.raises(exc.PendingCallsLimitExceeded):
        b.work.remote(release)
    open(release, "w").close()
    assert ray_tpu.get(r, timeout=120) == 1
    ray_tpu.kill(a)
