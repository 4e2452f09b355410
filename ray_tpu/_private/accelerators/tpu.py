"""TPU accelerator manager: chip discovery, visibility, release, pod-slice
resources. The one module that spells a host's chip device nodes, whether
they can be opened right now, and the env contract that makes a subset of
them visible to a process.

reference parity: python/ray/_private/accelerators/tpu.py:75-398
(TPUAcceleratorManager) — chip detection via /dev/accel* or /dev/vfio
(tpu.py:110-117), TPU_VISIBLE_CHIPS + TPU_CHIPS_PER_HOST_BOUNDS /
TPU_HOST_BOUNDS env plumbing for 1/2/4-chip slicing (tpu.py:157-196),
pod type from GCE metadata / GKE env (tpu.py:199-229), and the
`{tpu_name: 1, "TPU-<type>-head": 1}` pod-slice custom resources on worker 0
used for multi-host SPMD gang targeting (tpu.py:335-398).
"""

from __future__ import annotations

import errno
import glob
import logging
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

logger = logging.getLogger(__name__)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
TPU_SINGLE_HOST_BOUNDS = "1,1,1"
# Valid per-task chip slices on one host (reference tpu.py:13,143-155).
VALID_TPU_CHIP_COUNTS = (1, 2, 4)
# Test hook: pretend this many chips exist (the chip-free fake ladder).
TPU_FAKE_CHIPS_ENV = "RAY_TPU_FAKE_NUM_CHIPS"
TPU_FAKE_POD_TYPE_ENV = "RAY_TPU_FAKE_POD_TYPE"
TPU_FAKE_WORKER_ID_ENV = "RAY_TPU_FAKE_WORKER_ID"

# Sub-host slices need their bounds spelled; four chips are the whole host,
# which libtpu finds by itself.
_CHIPS_PER_HOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}

_ACCEL_GLOB = "/dev/accel*"
_VFIO_DIR = "/dev/vfio"

# How long either end waits for chips a dead holder has not let go yet
# (the four chips of a v5e host took some 25 s), and how often it asks.
# `NodeManager.shutdown()` waits so before it returns the chips it granted,
# a train worker before it starts its TPU backend: the same question, the
# same patience.
_CHIP_WAIT_BOUND_S = 60.0
_CHIP_WAIT_POLL_S = 0.25


def _chip_device_nodes() -> List[str]:
    """The host's chip device nodes in chip order: /dev/accel* (PCIe),
    else one VFIO group /dev/vfio/<n> a chip (reference tpu.py:110-117).
    A listing that fails reads as no chips, and a faked count has no node
    behind it."""
    if TPU_FAKE_CHIPS_ENV in os.environ:
        return []
    nodes = glob.glob(_ACCEL_GLOB)
    if not nodes:
        try:
            nodes = [os.path.join(_VFIO_DIR, e)
                     for e in os.listdir(_VFIO_DIR) if e != "vfio"]
        except OSError:
            return []
    return sorted(nodes, key=lambda n: (len(n), n))     # 2 before 10


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        fake = os.environ.get(TPU_FAKE_CHIPS_ENV)
        if fake is not None:
            return int(fake)
        return len(_chip_device_nodes())

    @staticmethod
    def get_busy_chip_nodes(
            ids: Optional[Sequence[Union[int, str]]] = None) -> List[str]:
        """The VFIO groups, of all the host's chips or of the chips `ids`,
        that cannot be opened right now. A group opens for one process at
        a time, and the kernel goes on unpinning a dead holder's memory
        for a while after it died, so whoever hands chips on and whoever
        is about to open them asks this first. `/dev/vfio/<n>` is the
        layout the v5e hosts have and the only one the busy state was seen
        on: nothing is asked of `/dev/accel*` hosts. EBUSY alone means
        busy; any other error is libtpu's to report and reads as free."""
        nodes = [n for n in _chip_device_nodes()
                 if os.path.dirname(n) == _VFIO_DIR]
        if ids is not None:
            # an id with no node behind it is libtpu's to refuse
            nodes = [nodes[int(i)] for i in ids
                     if str(i).isdigit() and int(i) < len(nodes)]
        busy = []
        for node in nodes:
            try:
                os.close(os.open(node, os.O_RDWR))
            except OSError as e:
                if e.errno == errno.EBUSY:
                    busy.append(node)
        return busy

    @staticmethod
    def wait_for_chips(ids: Optional[Sequence[Union[int, str]]] = None
                       ) -> Tuple[float, List[str]]:
        """Poll until none of those chips is busy or the bound has passed.
        Returns the seconds waited (0.0 where the first answer was "none")
        and the nodes still busy at the bound; what to say about either is
        the caller's."""
        busy = TPUAcceleratorManager.get_busy_chip_nodes(ids)
        if not busy:
            return 0.0, busy
        started = time.monotonic()
        while busy and time.monotonic() - started < _CHIP_WAIT_BOUND_S:
            time.sleep(_CHIP_WAIT_POLL_S)
            busy = TPUAcceleratorManager.get_busy_chip_nodes(ids)
        return time.monotonic() - started, busy

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        pod_type = TPUAcceleratorManager._get_tpu_pod_type()
        if pod_type is None:
            return None
        # 'v5p-16' -> 'TPU-V5P'
        return "TPU-" + pod_type.split("-")[0].upper()

    @staticmethod
    def _get_tpu_pod_type() -> Optional[str]:
        # GKE env, fake env, or GCE metadata (reference tpu.py:199-229; the
        # metadata server is unreachable in tests so env wins).
        for var in (TPU_FAKE_POD_TYPE_ENV, "TPU_ACCELERATOR_TYPE"):
            v = os.environ.get(var)
            if v:
                return v
        return None

    @staticmethod
    def _get_tpu_worker_id() -> Optional[int]:
        for var in (TPU_FAKE_WORKER_ID_ENV, "TPU_WORKER_ID"):
            v = os.environ.get(var)
            if v is not None:
                try:
                    return int(v)
                except ValueError:
                    return None
        return None

    @staticmethod
    def _get_tpu_name() -> Optional[str]:
        return os.environ.get("TPU_NAME")

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Pod-slice resources for multi-host gangs: every host of slice
        `name` gets {name: 1}; worker 0 additionally gets
        {"TPU-<pod_type>-head": 1} so a trainer can target one actor per
        slice head (reference tpu.py:335-398)."""
        resources: Dict[str, float] = {}
        name = TPUAcceleratorManager._get_tpu_name()
        pod_type = TPUAcceleratorManager._get_tpu_pod_type()
        worker_id = TPUAcceleratorManager._get_tpu_worker_id()
        if name:
            resources[name] = 1.0
        if pod_type is not None and worker_id == 0:
            resources[f"TPU-{pod_type}-head"] = 1.0
        return resources

    @staticmethod
    def validate_resource_request_quantity(quantity: float
                                           ) -> Tuple[bool, Optional[str]]:
        if quantity != int(quantity) or int(quantity) not in \
                VALID_TPU_CHIP_COUNTS:
            # >4 means multi-host: must use whole hosts (reference
            # tpu.py:143-155 allows only 1, 2 or 4 chips per request).
            if quantity == int(quantity) and int(quantity) % 4 == 0:
                return (True, None)
            return (False,
                    f"TPU request must be 1, 2, 4 or a multiple of 4 chips, "
                    f"got {quantity}")
        return (True, None)

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[str]]:
        v = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if v is None:
            return None
        return [s for s in v.split(",") if s]

    @staticmethod
    def get_visibility_env(ids: Sequence[Union[int, str]]) -> Dict[str, str]:
        """The env that shows a process these chips of its host and no
        others: the chip ids, and for a sub-host slice the topology bounds
        libtpu needs to carve it (reference tpu.py:157-196)."""
        env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(i) for i in ids)}
        bounds = _CHIPS_PER_HOST_BOUNDS.get(len(ids))
        if bounds is not None:
            env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
            env[TPU_HOST_BOUNDS_ENV] = TPU_SINGLE_HOST_BOUNDS
        return env

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: List[str]) -> None:
        os.environ.update(TPUAcceleratorManager.get_visibility_env(ids))
