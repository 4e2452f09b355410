"""Round-3 parity batch: GCS persistence, locality/label scheduling,
runtime_env working_dir/py_modules, dag, workflow, long-poll gets.

reference parity: redis_store_client.h (GCS persistence),
lease_policy.h:56 (locality), node_label_scheduling_policy.h (labels),
_private/runtime_env (working_dir/py_modules), python/ray/dag,
python/ray/workflow.
"""

import os
import time

import pytest

import ray_tpu


def test_gcs_persistence_survives_restart(tmp_path):
    from ray_tpu._private.gcs import GcsServer

    path = str(tmp_path / "gcs_state.pkl")
    g1 = GcsServer(persist_path=path)
    g1.kv_put("fn:abc", b"function blob")
    g1.kv_put("ckpt:latest", b"/some/path")
    jid1 = g1.next_job_id()
    g1.shutdown()

    g2 = GcsServer(persist_path=path)
    assert g2.kv_get("fn:abc") == b"function blob"
    assert g2.kv_get("ckpt:latest") == b"/some/path"
    jid2 = g2.next_job_id()
    assert jid2.binary() != jid1.binary(), "job ids must stay unique"
    g2.shutdown()


def test_locality_hint_scheduling_unit():
    from ray_tpu._private.scheduler import pick_node
    from ray_tpu._private.state import (DefaultSchedulingStrategy,
                                        ResourceSet)

    view = {"aa": {"CPU": 4.0}, "bb": {"CPU": 4.0}}
    required = ResourceSet({"CPU": 1.0})
    # without hints the local node wins; with bytes resident on bb, bb wins
    assert pick_node(view, required, DefaultSchedulingStrategy(),
                     local_node_id="aa") == "aa"
    chosen = pick_node(view, required, DefaultSchedulingStrategy(),
                       local_node_id="aa",
                       locality_hints={"bb": 10_000_000.0})
    assert chosen == "bb"


def test_node_label_scheduling_unit():
    from ray_tpu._private.scheduler import pick_node
    from ray_tpu._private.state import (NodeLabelSchedulingStrategy,
                                        ResourceSet)

    view = {"aa": {"CPU": 4.0}, "bb": {"CPU": 4.0}}
    labels = {"aa": {"zone": "us-1", "tier": "spot"},
              "bb": {"zone": "us-2"}}
    required = ResourceSet({"CPU": 1.0})
    s = NodeLabelSchedulingStrategy(hard={"zone": ["us-2"]})
    assert pick_node(view, required, s, labels=labels) == "bb"
    s = NodeLabelSchedulingStrategy(hard={"tier": [""]})  # key exists
    assert pick_node(view, required, s, labels=labels) == "aa"
    s = NodeLabelSchedulingStrategy(hard={"zone": ["eu-9"]})
    assert pick_node(view, required, s, labels=labels) is None
    # soft prefers but degrades
    s = NodeLabelSchedulingStrategy(soft={"zone": ["us-2"]})
    assert pick_node(view, required, s, labels=labels) == "bb"
    s = NodeLabelSchedulingStrategy(soft={"zone": ["eu-9"]})
    assert pick_node(view, required, s, labels=labels) in ("aa", "bb")


def test_runtime_env_working_dir_and_py_modules(ray_start, tmp_path):
    workdir = tmp_path / "wd"
    workdir.mkdir()
    (workdir / "data.txt").write_text("from-working-dir")
    module_dir = tmp_path / "extra_mod"
    module_dir.mkdir()
    (module_dir / "__init__.py").write_text("MAGIC = 'from-py-module'\n")

    @ray_tpu.remote(runtime_env={
        "working_dir": str(workdir),
        "py_modules": [str(module_dir)],
    })
    def probe():
        import extra_mod
        with open("data.txt") as f:
            return f.read(), extra_mod.MAGIC

    data, magic = ray_tpu.get(probe.remote())
    assert data == "from-working-dir"
    assert magic == "from-py-module"


def test_dag_function_graph(ray_start):
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def plus(a, b):
        return a + b

    @ray_tpu.remote
    def times(a, b):
        return a * b

    with InputNode() as inp:
        dag = times.bind(plus.bind(inp, 10), 2)
    assert ray_tpu.get(dag.execute(5)) == 30
    assert ray_tpu.get(dag.execute(0)) == 20


def test_dag_diamond_executes_shared_node_once(ray_start):
    counter = f"/tmp/dag_count_{os.getpid()}"
    if os.path.exists(counter):
        os.unlink(counter)

    @ray_tpu.remote
    def base(path):
        with open(path, "a") as f:
            f.write("x")
        return 3

    @ray_tpu.remote
    def add(a, b):
        return a + b

    shared = base.bind(counter)
    dag = add.bind(shared, shared)
    assert ray_tpu.get(dag.execute()) == 6
    assert os.path.getsize(counter) == 1, "shared node ran twice"
    os.unlink(counter)


def test_dag_actor_graph(ray_start):
    @ray_tpu.remote
    class Acc:
        def __init__(self, start):
            self.v = start

        def add(self, x):
            self.v += x
            return self.v

    node = Acc.options(num_cpus=0.1).bind(100)
    dag = node.add.bind(5)
    assert ray_tpu.get(dag.execute()) == 105


def test_workflow_resume_skips_completed_steps(ray_start, tmp_path):
    from ray_tpu import workflow
    from ray_tpu.dag import InputNode

    marker = str(tmp_path / "exec_count")

    @ray_tpu.remote
    def expensive(path, x):
        with open(path, "a") as f:
            f.write("x")
        return x * 2

    @ray_tpu.remote
    def flaky(path, x):
        if not os.path.exists(path + ".fixed"):
            raise RuntimeError("transient failure")
        return x + 1

    with InputNode() as inp:
        dag = flaky.bind(marker, expensive.bind(marker, inp))

    with pytest.raises(Exception):
        workflow.run(dag, workflow_id="wf1", storage=str(tmp_path),
                     dag_input=21)
    assert os.path.getsize(marker) == 1  # expensive completed once

    open(marker + ".fixed", "w").write("1")
    result = workflow.resume(dag, workflow_id="wf1",
                             storage=str(tmp_path), dag_input=21)
    assert result == 43
    assert os.path.getsize(marker) == 1, \
        "resume must not re-run the checkpointed step"
    assert workflow.get_output("wf1", storage=str(tmp_path)) == 43


def test_borrower_longpoll_get(ray_start):
    """A borrower blocked on a pending object wakes via the owner's
    long-poll, without ObjectLostError or timeout."""

    @ray_tpu.remote
    def slow_value():
        time.sleep(2)
        return "finally"

    @ray_tpu.remote
    def consume(refs):
        return ray_tpu.get(refs[0])  # borrower waits on pending object

    ref = slow_value.remote()
    t0 = time.time()
    assert ray_tpu.get(consume.remote([ref]), timeout=60) == "finally"
    assert time.time() - t0 < 30


def test_idle_workers_reaped():
    """Workers idle past idle_worker_kill_timeout_s are killed
    (reference worker_pool.cc idle-worker reaping)."""
    import subprocess
    import sys
    script = """
import gc
import time
import ray_tpu
from ray_tpu.util import state as state_api
ray_tpu.init(num_cpus=4)
@ray_tpu.remote
def f():
    return 1
@ray_tpu.remote
def put_owned():
    return [ray_tpu.put(list(range(1000)))]  # worker owns the inner obj
inner = ray_tpu.get(put_owned.remote())[0]
assert ray_tpu.get([f.remote() for _ in range(3)]) == [1, 1, 1]
# the owner of a still-referenced object must SURVIVE reaping: wait for
# at least one reap cycle past the idle timeout, then verify
deadline = time.time() + 45
while time.time() < deadline and len(state_api.list_workers()) > 1:
    time.sleep(0.5)
time.sleep(3)  # a further full timeout window under a live owner pin
assert len(state_api.list_workers()) >= 1, "object owner was reaped"
assert sum(ray_tpu.get(inner)) == 499500
# release the ref: now everything reaps to zero
del inner
gc.collect()
deadline = time.time() + 90  # generous: reap cycles crawl when the
while time.time() < deadline and len(state_api.list_workers()) > 0:
    time.sleep(0.5)          # full suite loads the 1-core CI box
assert len(state_api.list_workers()) == 0, state_api.list_workers()
# pool refills on demand after reaping
assert ray_tpu.get(f.remote()) == 1
ray_tpu.shutdown()
print("REAP_OK")
"""
    env = dict(os.environ)
    env["RAY_TPU_idle_worker_kill_timeout_s"] = "2"
    env["RAY_TPU_idle_worker_pool_floor"] = "0"
    # this test measures reap TIMING semantics; inherited chaos delays
    # (full-suite chaos sweeps) would squeeze its fixed windows
    env.pop("RAY_TPU_testing_rpc_delay_us", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=180,
                         cwd=repo)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "REAP_OK" in out.stdout


def test_arg_prefetch_across_nodes():
    """The dispatching node pulls a task's remote args into its local
    store before execution (reference DependencyManager/PullManager)."""
    import numpy as np

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"resources": {"CPU": 2}})
    try:
        node2 = cluster.add_node(resources={"CPU": 2})
        ray_tpu.init(cluster.address)

        big = ray_tpu.put(np.arange(300_000, dtype=np.float64))

        @ray_tpu.remote
        def consume(x):
            return float(np.asarray(x).sum())

        strat = NodeAffinitySchedulingStrategy(node_id=node2.node_id_hex)
        out = ray_tpu.get(
            consume.options(scheduling_strategy=strat).remote(big),
            timeout=120)
        assert out == float(np.arange(300_000).sum())

        from ray_tpu._private import rpc as rpc_lib
        host, port = node2.node_manager_address.rsplit(":", 1)
        nm = rpc_lib.RpcClient((host, int(port)), timeout=30)
        # the prefetch daemon increments after its pull returns — the
        # worker's dedup'd pull may deliver the result first, so poll
        import time as _t
        deadline = _t.time() + 20
        info = {}
        while _t.time() < deadline:
            info = nm.call("nm_get_info")
            if info.get("num_args_prefetched", 0) >= 1:
                break
            _t.sleep(0.2)
        assert info.get("num_args_prefetched", 0) >= 1, info
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_dynamic_generator_returns(ray_start):
    """num_returns="dynamic" (reference ObjectRefGenerator): a generator
    task stores each yielded value as its own object; the handle
    resolves to the list of refs."""
    import numpy as np

    @ray_tpu.remote(num_returns="dynamic")
    def gen(n):
        for i in range(n):
            yield np.full(4, i)

    handle = gen.remote(5)
    refs = ray_tpu.get(handle)
    assert len(refs) == 5
    for i, r in enumerate(refs):
        np.testing.assert_array_equal(ray_tpu.get(r), np.full(4, i))
    # children are first-class objects: usable as args to other tasks
    @ray_tpu.remote
    def total(x):
        return float(np.asarray(x).sum())
    assert ray_tpu.get(total.remote(refs[3])) == 12.0


def test_dynamic_child_recovers_via_lineage(ray_start):
    """A lost dynamic-return child reconstructs by re-executing the
    generator task (lineage covers dynamic children too)."""
    import numpy as np

    @ray_tpu.remote(num_returns="dynamic")
    def gen():
        for i in range(3):
            yield np.full(64 * 1024, i, dtype=np.float64)  # STORE-sized

    refs = ray_tpu.get(gen.remote())
    first = np.asarray(ray_tpu.get(refs[1])).copy()
    w = ray_tpu._private.worker.global_worker()
    w.core_worker.store.delete([refs[1].id.hex()])
    again = ray_tpu.get(refs[1], timeout=60)
    np.testing.assert_array_equal(first, np.asarray(again))


def test_streaming_generator_iterates_before_completion(ray_start):
    """num_returns="streaming" (reference StreamingObjectRefGenerator):
    children are consumable while the generator task is still running."""

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen(n):
        import time as _t
        for i in range(n):
            yield i * 3
            _t.sleep(0.8)

    t0 = time.time()
    gen = slow_gen.remote(4)
    first = next(iter(gen))
    first_at = time.time() - t0
    assert ray_tpu.get(first) == 0
    # the first child arrived well before the ~3.2s total runtime
    assert first_at < 2.5, f"first child only after {first_at:.1f}s"
    rest = [ray_tpu.get(r) for r in gen]
    assert rest == [3, 6, 9]


def test_max_calls_recycles_worker(ray_start):
    """max_calls (reference option surface §8.1): the worker process
    exits after N executions; fresh workers carry on."""

    @ray_tpu.remote(max_calls=2)
    def whoami():
        return os.getpid()

    pids = [ray_tpu.get(whoami.remote()) for _ in range(6)]
    assert len(set(pids)) >= 3, f"worker never recycled: {pids}"
    # the contract: no process executes this function more than max_calls
    # times (exact rotation order depends on pool scheduling)
    from collections import Counter
    assert max(Counter(pids).values()) <= 2, pids


def test_max_calls_counts_failing_executions(ray_start):
    """Failing executions count toward max_calls too — the recycle
    exists for leaky native libs, which leak on errors as well."""

    @ray_tpu.remote(max_calls=2, max_retries=0)
    def flaky_pid(fail):
        if fail:
            raise ValueError("boom")
        return os.getpid()

    pid1 = ray_tpu.get(flaky_pid.remote(False))
    with pytest.raises(ValueError):
        ray_tpu.get(flaky_pid.remote(True))  # execution #2 → recycle
    pid3 = ray_tpu.get(flaky_pid.remote(False))
    assert pid3 != pid1, "failing execution didn't count toward max_calls"
