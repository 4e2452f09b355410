"""A host's chips have one owner and one question (PR 49):
`_private/accelerators/tpu.py` alone spells their device nodes, whether
they can be opened, the bounded wait for that and the env that makes them
visible; `NodeManager.shutdown()` asks before it returns the chips it
granted, and a gang's worker asks before it starts its TPU backend. CPU
only: a temporary directory stands in for `/dev/vfio` and a fake `os.open`
for the kernel."""

import errno
import logging
import os
import time

import pytest

import ray_tpu
from ray_tpu._private import node_manager as nm_mod
from ray_tpu._private.accelerators import tpu
from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
from ray_tpu.train import jax_backend


@pytest.fixture
def vfio(tmp_path, monkeypatch):
    """A v5e host's `/dev/vfio`: four groups and the container node."""
    d = tmp_path / "vfio"
    d.mkdir()
    for name in ("0", "1", "2", "3", "vfio"):
        (d / name).write_bytes(b"")
    monkeypatch.setattr(tpu, "_VFIO_DIR", str(d))
    monkeypatch.setattr(tpu, "_ACCEL_GLOB", str(tmp_path / "accel*"))
    monkeypatch.delenv(tpu.TPU_FAKE_CHIPS_ENV, raising=False)
    return d


class Opener:
    """`os.open` for the nodes under `root`: raises `err` for the first
    `fails` calls on each node (every call where `fails` is None), then
    opens; any other path goes to the real `os.open`."""

    def __init__(self, root, err, fails=None):
        self.root, self.err, self.fails = str(root), err, fails
        self.calls = {}
        self._open = os.open

    def __call__(self, path, flags, *a, **kw):
        if not str(path).startswith(self.root):
            return self._open(path, flags, *a, **kw)
        n = self.calls[path] = self.calls.get(path, 0) + 1
        if self.fails is None or n <= self.fails:
            raise OSError(self.err, os.strerror(self.err), path)
        assert flags == os.O_RDWR
        return self._open(path, flags, *a, **kw)


# ---- the listing ------------------------------------------------------

def test_listing_counts_the_groups_and_leaves_vfio_out(vfio):
    assert tpu._chip_device_nodes() == [str(vfio / n) for n in "0123"]
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4


def test_listing_prefers_accel_nodes(vfio, tmp_path, monkeypatch):
    for name in ("accel0", "accel1"):
        (tmp_path / name).write_bytes(b"")
    assert tpu._chip_device_nodes() == [str(tmp_path / "accel0"),
                                        str(tmp_path / "accel1")]
    # nothing is asked of /dev/accel* hosts
    monkeypatch.setattr(os, "open", Opener(tmp_path, errno.EBUSY))
    assert TPUAcceleratorManager.get_busy_chip_nodes() == []


def test_listing_a_missing_directory_reads_as_no_chips(vfio, monkeypatch):
    monkeypatch.setattr(tpu, "_VFIO_DIR", str(vfio / "absent"))
    assert tpu._chip_device_nodes() == []
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 0


def test_listing_a_permission_error_reads_as_no_chips(vfio, monkeypatch):
    def denied(path):
        raise PermissionError(errno.EACCES, "Permission denied", path)

    monkeypatch.setattr(tpu.os, "listdir", denied)
    assert tpu._chip_device_nodes() == []
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 0


def test_a_faked_count_has_no_node_behind_it(vfio, monkeypatch):
    monkeypatch.setenv(tpu.TPU_FAKE_CHIPS_ENV, "8")
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 8
    assert tpu._chip_device_nodes() == []
    monkeypatch.setattr(os, "open", Opener(vfio, errno.EBUSY))
    assert TPUAcceleratorManager.get_busy_chip_nodes() == []


# ---- the busy question ------------------------------------------------

def test_busy_then_free(vfio, monkeypatch):
    opener = Opener(vfio, errno.EBUSY, fails=2)
    monkeypatch.setattr(os, "open", opener)
    every = [str(vfio / n) for n in "0123"]
    busy = TPUAcceleratorManager.get_busy_chip_nodes
    assert busy() == every
    assert busy() == every
    assert busy() == []
    assert opener.calls == {p: 3 for p in every}   # `vfio` is never opened


def test_only_ebusy_means_busy(vfio, monkeypatch):
    monkeypatch.setattr(os, "open", Opener(vfio, errno.EACCES))
    assert TPUAcceleratorManager.get_busy_chip_nodes() == []


def test_an_opened_group_is_closed_at_once(vfio, monkeypatch):
    closed = []
    real_close = os.close

    def close(fd):
        closed.append(fd)
        real_close(fd)

    monkeypatch.setattr(tpu.os, "close", close)
    assert TPUAcceleratorManager.get_busy_chip_nodes() == []
    assert len(closed) == 4


def test_a_set_of_ids_asks_only_about_those(vfio, monkeypatch):
    opener = Opener(vfio, errno.EBUSY)
    monkeypatch.setattr(os, "open", opener)
    busy = TPUAcceleratorManager.get_busy_chip_nodes
    assert busy(["1", "3"]) == [str(vfio / "1"), str(vfio / "3")]
    assert busy(range(2, 3)) == [str(vfio / "2")]
    assert set(opener.calls) == {str(vfio / n) for n in "123"}
    # an id with no node behind it, or none at all, is libtpu's to refuse
    assert busy(["7", "x", "-1"]) == []
    assert busy([]) == []
    assert set(opener.calls) == {str(vfio / n) for n in "123"}


def test_ids_index_the_groups_in_numeric_order(vfio, monkeypatch):
    for name in ("10", "11"):
        (vfio / name).write_bytes(b"")
    monkeypatch.setattr(os, "open", Opener(vfio, errno.EBUSY))
    assert TPUAcceleratorManager.get_busy_chip_nodes([2, 4]) == [
        str(vfio / "2"), str(vfio / "10")]


# ---- the one bounded wait ---------------------------------------------

@pytest.fixture
def quick(monkeypatch):
    monkeypatch.setattr(tpu, "_CHIP_WAIT_POLL_S", 0.05)


def test_wait_returns_once_none_is_busy(vfio, quick, monkeypatch):
    opener = Opener(vfio, errno.EBUSY, fails=3)
    monkeypatch.setattr(os, "open", opener)
    waited, busy = TPUAcceleratorManager.wait_for_chips()
    assert busy == [] and 0.15 <= waited < 30
    assert opener.calls == {str(vfio / n): 4 for n in "0123"}


def test_wait_on_free_chips_asks_once_and_waits_nothing(vfio, monkeypatch):
    opener = Opener(vfio, errno.EBUSY, fails=0)
    monkeypatch.setattr(os, "open", opener)
    monkeypatch.setattr(tpu.time, "sleep", lambda s: pytest.fail("slept"))
    assert TPUAcceleratorManager.wait_for_chips(["0"]) == (0.0, [])
    assert opener.calls == {str(vfio / "0"): 1}


def test_wait_gives_up_at_the_bound_and_names_the_busy(vfio, quick,
                                                       monkeypatch):
    monkeypatch.setattr(tpu, "_CHIP_WAIT_BOUND_S", 0.3)
    monkeypatch.setattr(os, "open", Opener(vfio, errno.EBUSY))
    waited, busy = TPUAcceleratorManager.wait_for_chips([1])
    assert busy == [str(vfio / "1")] and 0.3 <= waited < 30


def test_one_bound_and_one_poll_for_both_ends():
    assert (tpu._CHIP_WAIT_BOUND_S, tpu._CHIP_WAIT_POLL_S) == (60.0, 0.25)
    for mod in (nm_mod, jax_backend):
        assert not [n for n in vars(mod) if "BOUND_S" in n or "POLL_S" in n]


# ---- the visibility contract ------------------------------------------

# what `BackendExecutor._share_tpu_visibility` sent each worker at PR 45
_SENT_BEFORE = {
    1: {"TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1"},
    2: {"TPU_VISIBLE_CHIPS": "2,3", "TPU_CHIPS_PER_HOST_BOUNDS": "1,2,1",
        "TPU_HOST_BOUNDS": "1,1,1"},
    4: {"TPU_VISIBLE_CHIPS": "2,3,4,5"},
}


@pytest.mark.parametrize("n", [1, 2, 4])
def test_visibility_env_is_what_the_gang_sent_before(n):
    env = TPUAcceleratorManager.get_visibility_env(range(2, 2 + n))
    assert env == _SENT_BEFORE[n]


def test_set_visible_ids_applies_that_env(monkeypatch):
    for k in _SENT_BEFORE[1]:
        monkeypatch.delenv(k, raising=False)
    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(
        ["0", "1"])
    try:
        assert {k: os.environ[k] for k in _SENT_BEFORE[2]} == {
            **_SENT_BEFORE[2], "TPU_VISIBLE_CHIPS": "0,1"}
        assert TPUAcceleratorManager \
            .get_current_process_visible_accelerator_ids() == ["0", "1"]
    finally:
        for k in _SENT_BEFORE[2]:
            os.environ.pop(k, None)


def test_the_gang_sends_the_modules_env(monkeypatch):
    """`_share_tpu_visibility` numbers each node's chips from 0 and sends
    the accelerator module's env, key for key."""
    from ray_tpu.train.backend_executor import BackendExecutor

    class Scaling:
        num_tpus_per_worker = 2

    class Group:
        node_ids = ["a", "a", "b"]
        sent = None

        def setup_env(self, envs):
            self.sent = envs

    ex = BackendExecutor.__new__(BackendExecutor)
    ex._scaling = Scaling()
    wg = Group()
    ex._share_tpu_visibility(wg)
    assert wg.sent == [
        {**_SENT_BEFORE[2], "TPU_VISIBLE_CHIPS": "0,1"},
        {**_SENT_BEFORE[2], "TPU_VISIBLE_CHIPS": "2,3"},
        {**_SENT_BEFORE[2], "TPU_VISIBLE_CHIPS": "0,1"},
    ]


# ---- NodeManager.shutdown() returns the chips --------------------------

@pytest.fixture
def cluster(vfio):
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, resources={"TPU": 4})
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def _hold(tpus):
    @ray_tpu.remote(num_cpus=1, resources={"TPU": tpus} if tpus else None)
    def held():
        return os.getpid()

    assert ray_tpu.get(held.remote(), timeout=120) != os.getpid()


def _hold_as_a_gang_does(tpus):
    """A train worker asks for nothing itself: it sits in the bundle of
    its gang's placement group, and the bundle holds the chips."""
    from ray_tpu.train.worker_group import WorkerGroup

    wg = WorkerGroup(1, {"CPU": 1, "TPU": tpus})
    assert wg.execute(os.getpid) != [os.getpid()]
    wg.shutdown()


@pytest.mark.parametrize("hold", [_hold, _hold_as_a_gang_does])
def test_shutdown_returns_once_the_groups_read_free(cluster, vfio, hold,
                                                    quick, monkeypatch,
                                                    caplog):
    hold(4)
    opener = Opener(vfio, errno.EBUSY, fails=3)
    monkeypatch.setattr(os, "open", opener)
    with caplog.at_level(logging.INFO, logger=nm_mod.__name__):
        ray_tpu.shutdown()
    # three busy answers, then the one that let shutdown() return
    assert set(opener.calls.values()) == {4} and len(opener.calls) == 4
    said = [r for r in caplog.records if "chips to be released" in r.message]
    assert len(said) == 1 and said[0].levelno == logging.INFO
    assert said[0].args[1] == 4 and said[0].args[0] >= 0.15


def test_shutdown_returns_at_the_bound_with_a_warning(cluster, vfio, quick,
                                                      monkeypatch, caplog):
    _hold(1)
    opener = Opener(vfio, errno.EBUSY)
    monkeypatch.setattr(os, "open", opener)
    monkeypatch.setattr(tpu, "_CHIP_WAIT_BOUND_S", 0.5)
    t0 = time.monotonic()
    with caplog.at_level(logging.INFO, logger=nm_mod.__name__):
        ray_tpu.shutdown()      # never raises for this
    assert not ray_tpu.is_initialized()
    warned = [r for r in caplog.records if "still busy" in r.getMessage()]
    assert len(warned) == 1 and warned[0].levelno == logging.WARNING
    assert str(vfio / "2") in warned[0].getMessage()
    assert min(opener.calls.values()) >= 2
    assert 0.5 <= time.monotonic() - t0 < 30


@pytest.mark.parametrize("case", ["no_tpu_granted", "faked_count"])
def test_shutdown_does_not_poll(cluster, vfio, monkeypatch, caplog, case):
    """Another tenant's chips are not this node's to wait for, and a
    faked count has no node to ask."""
    if case == "faked_count":
        monkeypatch.setenv(tpu.TPU_FAKE_CHIPS_ENV, "4")
        _hold(4)
    else:
        _hold(0)
    opener = Opener(vfio, errno.EBUSY)
    monkeypatch.setattr(os, "open", opener)
    with caplog.at_level(logging.INFO, logger=nm_mod.__name__):
        ray_tpu.shutdown()
    assert opener.calls == {}
    assert not [r for r in caplog.records
                if "busy" in r.getMessage() or "released" in r.getMessage()]


# ---- a gang's worker waits for its chips before its TPU start ----------

class _Device:
    platform = "tpu"


@pytest.fixture
def worker(vfio, quick, monkeypatch):
    """`_setup_worker` in this process: the chips' nodes are the fake
    ones, `jax.local_devices()` records what `os.open` had answered by
    the time it was called, and the compile cache stays where it is."""
    import jax
    from ray_tpu._private import compile_cache

    seen = {}

    def local_devices():
        seen["opens"] = dict(seen["opener"].calls)
        return [_Device()] * seen["devices"]

    def run(num_tpus, opener, visible=None, devices=None):
        seen.update(opener=opener,
                    devices=num_tpus if devices is None else devices)
        monkeypatch.setattr(os, "open", opener)
        if visible is None:
            monkeypatch.delenv(tpu.TPU_VISIBLE_CHIPS_ENV, raising=False)
        else:
            monkeypatch.setenv(tpu.TPU_VISIBLE_CHIPS_ENV, visible)
        jax_backend._setup_worker(num_tpus)
        return seen.get("opens")

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(jax, "local_devices", local_devices)
    return run


def test_worker_waits_while_its_chips_read_busy(worker, vfio, caplog):
    opener = Opener(vfio, errno.EBUSY, fails=3)
    with caplog.at_level(logging.INFO, logger=jax_backend.__name__):
        opens = worker(4, opener, visible="0,1,2,3")
    # the backend was touched only after the answer that read free
    assert opens == {str(vfio / n): 4 for n in "0123"}
    said = [r for r in caplog.records if "waited" in r.getMessage()]
    assert len(said) == 1 and said[0].levelno == logging.WARNING
    assert said[0].args[0] >= 0.15 and said[0].args[1] == 4
    assert "still busy" not in said[0].getMessage()


def test_worker_goes_on_at_the_bound(worker, vfio, monkeypatch, caplog):
    """No new error type: past the bound libtpu says what it said before
    (here the fake backend answers, so the worker's own check speaks)."""
    monkeypatch.setattr(tpu, "_CHIP_WAIT_BOUND_S", 0.3)
    opener = Opener(vfio, errno.EBUSY)
    with caplog.at_level(logging.INFO, logger=jax_backend.__name__):
        with pytest.raises(RuntimeError, match="sees 0 local device"):
            worker(2, opener, visible="2,3", devices=0)
    assert set(opener.calls) == {str(vfio / "2"), str(vfio / "3")}
    said = [r.getMessage() for r in caplog.records
            if r.levelno == logging.WARNING]
    assert len(said) == 1 and f"still busy: {vfio / '2'}, {vfio / '3'}" \
        in said[0]


def _worker_spans(t0):
    from ray_tpu._private import spans
    return {r[1]: r for r in spans.ring().snapshot_records()
            if r[2] >= t0 and r[1].startswith("train.worker.")}


def test_worker_records_what_it_did_under_the_backend_span(worker, vfio):
    """PR 55: the wait and the runtime's start are spans of the worker's
    own ring (the driver's `train.gang.backend` is one span around the
    RPC), the wait with what it waited, recorded at 0 s too."""
    from ray_tpu._private import spans
    t0 = spans.begin()
    worker(4, Opener(vfio, errno.EBUSY, fails=3), visible="0,1,2,3")
    got = _worker_spans(t0)
    assert set(got) == {"train.worker.jax_import", "train.worker.chip_wait",
                        "train.worker.tpu_start"}
    wait, start = got["train.worker.chip_wait"], got["train.worker.tpu_start"]
    assert wait[6]["waited_s"] >= 0.15 and wait[6]["busy"] == []
    assert wait[3] >= wait[6]["waited_s"] and wait[6]["rank"] == 0
    assert start[6] == {"rank": 0, "gang": "", "devices": 4,
                        "platform": "tpu"}
    assert got["train.worker.jax_import"][6]["cached"] is True
    assert wait[2] + wait[3] <= start[2] + 1e-6

    t0 = spans.begin()
    worker(1, Opener(vfio, errno.EBUSY, fails=0), visible="1")
    free = _worker_spans(t0)["train.worker.chip_wait"]
    assert free[6]["waited_s"] == 0.0 and free[6]["busy"] == []


def test_worker_still_busy_at_the_bound_says_which(worker, vfio,
                                                   monkeypatch):
    from ray_tpu._private import spans
    monkeypatch.setattr(tpu, "_CHIP_WAIT_BOUND_S", 0.3)
    t0 = spans.begin()
    with pytest.raises(RuntimeError, match="sees 0 local device"):
        worker(2, Opener(vfio, errno.EBUSY), visible="2,3", devices=0)
    got = _worker_spans(t0)
    assert got["train.worker.chip_wait"][6]["busy"] == [
        str(vfio / "2"), str(vfio / "3")]
    assert got["train.worker.chip_wait"][6]["waited_s"] >= 0.3
    assert got["train.worker.tpu_start"][6]["devices"] == 0


def test_worker_without_tpus_asks_nothing(worker, vfio):
    opener = Opener(vfio, errno.EBUSY)
    assert worker(0, opener, visible="0") is None   # jax never touched
    assert opener.calls == {}


@pytest.mark.parametrize("visible, asked", [("1", "1"), (None, "0123")])
def test_worker_asks_about_the_chips_it_can_see(worker, vfio, caplog,
                                                visible, asked):
    """One chip where one is visible; all of the host's where none is
    set. Free chips cost one open each and not a line of log."""
    opener = Opener(vfio, errno.EBUSY, fails=0)
    with caplog.at_level(logging.INFO, logger=jax_backend.__name__):
        opens = worker(len(asked), opener, visible=visible)
    assert opens == {str(vfio / n): 1 for n in asked}
    assert not caplog.records
