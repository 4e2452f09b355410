"""Unit tests for runtime internals: ids, resources, scheduler policies,
object store, serialization. No cluster needed.

reference parity: C++ gtest suites (scheduling_policy_test.cc,
cluster_task_manager_test.cc, plasma tests) in python form.
"""

import os
import tempfile

import numpy as np
import pytest

from ray_tpu._private import serialization as ser
from ray_tpu._private.ids import ActorID, JobID, ObjectID, TaskID
from ray_tpu._private.object_store import StoreClient, StoreServer
from ray_tpu._private.scheduler import pack_bundles, pick_node
from ray_tpu._private.state import (NodeAffinitySchedulingStrategy,
                                    DefaultSchedulingStrategy, ResourceSet,
                                    SpreadSchedulingStrategy)


class TestIDs:
    def test_object_id_embeds_task(self):
        t = TaskID.of(JobID(b"\x00\x00\x00\x01"))
        o = ObjectID.for_task_return(t, 3)
        assert o.task_id() == t
        assert o.return_index() == 3
        assert not o.is_put()

    def test_put_id(self):
        t = TaskID.of(JobID(b"\x00\x00\x00\x01"))
        o = ObjectID.for_put(t, 7)
        assert o.is_put()
        assert o.return_index() == 7

    def test_actor_task_job(self):
        j = JobID(b"\x00\x00\x00\x05")
        a = ActorID.of(j)
        assert a.job_id() == j
        assert TaskID.for_actor_creation(a).job_id() == j

    def test_hex_roundtrip(self):
        t = TaskID.of(JobID.nil())
        assert TaskID.from_hex(t.hex()) == t


class TestResources:
    def test_subset(self):
        a = ResourceSet({"CPU": 2, "TPU": 1})
        b = ResourceSet({"CPU": 4, "TPU": 4})
        assert a.is_subset_of(b)
        assert not b.is_subset_of(a)

    def test_fixed_point(self):
        a = ResourceSet({"CPU": 0.0001})
        b = ResourceSet({"CPU": 1})
        for _ in range(10000):
            b.subtract(a)
        assert b.get("CPU") == pytest.approx(0.0, abs=1e-9)

    def test_add_subtract(self):
        a = ResourceSet({"CPU": 4})
        a.subtract(ResourceSet({"CPU": 1.5}))
        assert a.get("CPU") == 2.5
        a.add(ResourceSet({"CPU": 1.5}))
        assert a.get("CPU") == 4


class TestSchedulingPolicies:
    VIEW = {
        "n1": {"CPU": 4.0, "TPU": 0},
        "n2": {"CPU": 2.0, "TPU": 4.0},
        "n3": {"CPU": 0.0, "TPU": 0},
    }
    TOTALS = {
        "n1": {"CPU": 4.0}, "n2": {"CPU": 8.0, "TPU": 4.0}, "n3": {"CPU": 8.0},
    }

    def test_infeasible(self):
        assert pick_node(self.VIEW, ResourceSet({"GPU": 1}),
                         DefaultSchedulingStrategy()) is None

    def test_tpu_goes_to_tpu_node(self):
        assert pick_node(self.VIEW, ResourceSet({"TPU": 2}),
                         DefaultSchedulingStrategy()) == "n2"

    def test_local_preferred_under_threshold(self):
        chosen = pick_node(self.VIEW, ResourceSet({"CPU": 1}),
                           DefaultSchedulingStrategy(), local_node_id="n1",
                           totals=self.TOTALS)
        assert chosen == "n1"

    def test_node_affinity_hard(self):
        s = NodeAffinitySchedulingStrategy(node_id="n2", soft=False)
        assert pick_node(self.VIEW, ResourceSet({"CPU": 1}), s) == "n2"
        s_bad = NodeAffinitySchedulingStrategy(node_id="n3", soft=False)
        assert pick_node(self.VIEW, ResourceSet({"CPU": 1}), s_bad) is None

    def test_node_affinity_soft_falls_back(self):
        s = NodeAffinitySchedulingStrategy(node_id="n3", soft=True)
        assert pick_node(self.VIEW, ResourceSet({"CPU": 1}), s) is not None

    def test_spread(self):
        s = SpreadSchedulingStrategy()
        chosen = pick_node(self.VIEW, ResourceSet({"CPU": 1}), s,
                           totals=self.TOTALS)
        assert chosen in ("n1", "n2")


class TestBundlePacking:
    VIEW = {"a": {"CPU": 4.0}, "b": {"CPU": 4.0}}

    def test_strict_pack_fits_one_node(self):
        out = pack_bundles(self.VIEW, [{"CPU": 2}, {"CPU": 2}], "STRICT_PACK")
        assert out is not None and len(set(out)) == 1

    def test_strict_pack_infeasible(self):
        assert pack_bundles(self.VIEW, [{"CPU": 3}, {"CPU": 3}],
                            "STRICT_PACK") is None

    def test_strict_spread(self):
        out = pack_bundles(self.VIEW, [{"CPU": 1}, {"CPU": 1}],
                           "STRICT_SPREAD")
        assert out is not None and len(set(out)) == 2

    def test_strict_spread_infeasible(self):
        assert pack_bundles(self.VIEW, [{"CPU": 1}] * 3, "STRICT_SPREAD") is None

    def test_pack_overflows_to_second_node(self):
        out = pack_bundles(self.VIEW, [{"CPU": 3}, {"CPU": 3}], "PACK")
        assert out is not None and len(set(out)) == 2


class TestSerialization:
    def test_roundtrip_simple(self):
        blob = ser.pack({"a": 1, "b": [1, 2, 3]})
        assert ser.unpack(memoryview(blob)) == {"a": 1, "b": [1, 2, 3]}

    def test_numpy_zero_copy(self):
        x = np.arange(1000, dtype=np.float64)
        blob = ser.pack(x)
        y = ser.unpack(memoryview(blob))
        np.testing.assert_array_equal(x, y)

    def test_lambda_via_cloudpickle(self):
        blob = ser.pack(lambda x: x + 1)  # noqa: E731
        fn = ser.unpack(memoryview(blob))
        assert fn(1) == 2


class TestObjectStore:
    def test_create_seal_get_delete(self):
        with tempfile.TemporaryDirectory() as d:
            srv = StoreServer(d, capacity_bytes=1 << 20)
            try:
                client = StoreClient(srv.address)
                buf = client.create("ab" * 10, 100)
                buf[:5] = b"hello"
                client.seal("ab" * 10)
                got = client.get(["ab" * 10], timeout=5)
                assert bytes(got["ab" * 10][:5]) == b"hello"
                assert client.contains("ab" * 10)
                client.delete(["ab" * 10])
                assert not client.contains("ab" * 10)
            finally:
                srv.shutdown()

    def test_lru_eviction(self):
        with tempfile.TemporaryDirectory() as d:
            srv = StoreServer(d, capacity_bytes=1000)
            try:
                client = StoreClient(srv.address)
                client.put_raw("aa", b"x" * 400)
                client.put_raw("bb", b"y" * 400)
                client.put_raw("cc", b"z" * 400)  # evicts aa (LRU)
                assert not client.contains("aa")
                assert client.contains("cc")
            finally:
                srv.shutdown()

    def test_pull_between_stores(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            s1 = StoreServer(d1, capacity_bytes=1 << 20)
            s2 = StoreServer(d2, capacity_bytes=1 << 20)
            try:
                c1 = StoreClient(s1.address)
                data = os.urandom(50_000)
                c1.put_raw("obj1", data)
                c2 = StoreClient(s2.address)
                view = c2.pull("obj1", s1.address, len(data))
                assert bytes(view) == data
            finally:
                s1.shutdown()
                s2.shutdown()


def test_a_test_that_runs_past_its_limit_fails_by_name(monkeypatch):
    """The limit tests/conftest.py arms around every test: a sleep past it
    ends as a failure that names the test, and the timer and the handler of
    the test around it (this one's) are back afterwards."""
    import signal
    import time

    from tests import conftest

    outer = signal.getsignal(signal.SIGALRM)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0   # this test's own
    monkeypatch.setattr(conftest, "TEST_LIMIT_S", 0.05)
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"tests/test_x\.py::test_sleeps ran past"):
        with conftest.time_limit("tests/test_x.py::test_sleeps"):
            time.sleep(30)
    assert time.monotonic() - began < 5
    assert signal.getsignal(signal.SIGALRM) is outer
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 60
    with conftest.time_limit("tests/test_x.py::test_returns"):
        pass                                  # inside the limit: nothing
    time.sleep(0.1)                           # and no alarm left behind
