"""JaxBackend: the TPU-native replacement for _TorchBackend.

reference parity: python/ray/train/torch/config.py:22,148-200 —
_TorchBackend.on_start broadcasts rank-0's address and runs
dist.init_process_group(nccl|gloo) on every worker, plus torchelastic env
(:129-145). Here the "process group" is jax's distributed runtime: worker
0 hosts the coordinator, every worker calls jax.distributed.initialize
(coordinator_address, num_processes=world_size, process_id=rank), after
which jax.devices() spans the whole slice and pjit/shard_map collectives
ride ICI. (SURVEY.md §7.1 translation table, row 1.)
"""

from __future__ import annotations

import dataclasses
import logging
import sys
from typing import Optional, Type

from ray_tpu._private import spans
from ray_tpu.train.backend import Backend, BackendConfig
from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """distributed=None (default): initialize jax.distributed only when
    the gang spans more than one process AND TPU chips are attached —
    single-worker and chip-free CI runs skip the coordinator entirely.

    coordinator_port=0 picks a fresh free port on worker 0's node for
    EVERY gang formation. Elastic gangs always do this — the
    coordinator is re-hosted each re-form while the previous
    formation's port may still sit in TIME_WAIT, so a fixed value is
    ignored there (with a warning)."""

    distributed: Optional[bool] = None
    coordinator_port: int = 8476

    @property
    def backend_cls(self) -> Type["Backend"]:
        return _JaxBackend


def _get_node_ip() -> str:
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def _import_jax(need_jax: bool, **attrs) -> None:
    """The compile cache's placement and the process's first `import
    jax` (the placement makes it where the environment names no
    directory) as `train.worker.jax_import`; `cached` where a reused
    pool worker had it."""
    from ray_tpu._private.compile_cache import enable_compile_cache
    with spans.span("train.worker.jax_import",
                    cached="jax" in sys.modules, **attrs):
        enable_compile_cache()
        if need_jax:
            import jax  # noqa: F401


def _init_jax_distributed(coordinator_address: str, num_processes: int,
                          process_id: int, gang: str = "") -> None:
    import os

    _import_jax(True, rank=process_id, gang=gang)
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # XLA's CPU backend refuses cross-process computations unless
        # collectives go through gloo — needed for the chip-free ladder
        # to run real multi-process gang collectives.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    with spans.span("train.worker.distributed_init", rank=process_id,
                    gang=gang, processes=num_processes):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)


def _setup_worker(num_tpus: int, rank: int = 0, gang: str = "") -> None:
    """Last step of gang set-up on every worker, before the train loop
    jits anything: place the compile cache, wait (bounded) for chips a
    predecessor still holds, and hold the worker to the chips its
    ScalingConfig asked for. TPU visibility env is applied to
    a live process, so a worker whose JAX was already pinned elsewhere
    (a reused pool worker, a missing libtpu) would otherwise train on
    the CPU and nobody would notice.

    What the driver's one `train.gang.backend` span waits for is told
    apart here, in the worker's own ring (which outlives the gang):
    `train.worker.jax_import`, `.chip_wait`, `.tpu_start`, each with the
    worker's `rank` and the formation's `gang`."""
    _import_jax(bool(num_tpus), rank=rank, gang=gang)
    if num_tpus:
        _start_tpu_runtime(num_tpus, rank, gang)
    if "jax" in sys.modules:
        # from here on, before the loop's first jit, the set-up's traces,
        # lowerings, cache loads and compiles are `jax.*` spans, not only
        # those after the first step region. Installed after the
        # runtime's start: libtpu starts in a process that nothing has
        # patched, as it always did
        from ray_tpu.util import jax_sentinel
        jax_sentinel.install()


def _start_tpu_runtime(num_tpus: int, rank: int, gang: str) -> None:
    import jax
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
    # A predecessor that could not give its chips back (SIGKILL, a gang
    # killed inside a live cluster) leaves them busy for a while, and a
    # TPU backend that failed once stays failed in this process. Asked as
    # late as the first touch of the backend allows: the time spent
    # getting here counts towards the release. At the bound libtpu speaks.
    # recorded when it waited 0 s too: 0 says the predecessor released
    with spans.span("train.worker.chip_wait", rank=rank, gang=gang) as sp:
        waited, busy = TPUAcceleratorManager.wait_for_chips(
            TPUAcceleratorManager
            .get_current_process_visible_accelerator_ids())
        sp["waited_s"], sp["busy"] = waited, list(busy)
    if waited:
        logger.warning(
            "waited %.1f s for this worker's %d chips, which a "
            "predecessor had not let go%s", waited, num_tpus,
            f"; still busy: {', '.join(busy)}" if busy else "")
    with spans.span("train.worker.tpu_start", rank=rank, gang=gang) as sp:
        local = jax.local_devices()   # libtpu's start
        sp["devices"] = len(local)
        sp["platform"] = ",".join(sorted({d.platform for d in local}))
    if len(local) != num_tpus or \
            any(d.platform != "tpu" for d in local):
        raise RuntimeError(
            f"train worker was given TPU: {num_tpus} but its JAX sees "
            f"{len(local)} local device(s) of platform "
            f"{sorted({d.platform for d in local})}: {local}")


from ray_tpu.train.elastic import free_port as _free_port


class _JaxBackend(Backend):
    def gang_env(self, backend_config: JaxConfig,
                 num_workers: int = 1) -> Optional[dict]:
        """Fresh worker processes per gang formation when jax.distributed
        is requested: initialize() must run before any other jax use in
        the process, which reused pool workers cannot guarantee — and an
        elastic re-form (new world size, new coordinator) needs a clean
        runtime in every member. The unique key gives each formation its
        own worker-pool bucket; one host CPU device per process keeps
        chip-free meshes 1 device/rank (the virtual-device test flag
        would otherwise leak in).

        distributed=None (auto) must be treated as POSSIBLY distributed
        for any multi-worker gang: on_start only resolves the TPU probe
        after the workers exist, and a re-form that reuses pool workers
        because gang_env guessed "not distributed" would re-run
        jax.distributed.initialize in a process that already used jax."""
        if backend_config.distributed is False or \
                (backend_config.distributed is None and num_workers <= 1):
            return None
        from ray_tpu.train.elastic import gang_runtime_env
        return gang_runtime_env("RAY_TPU_TRAIN_GANG")

    def on_start(self, worker_group: WorkerGroup,
                 backend_config: JaxConfig) -> None:
        distributed = backend_config.distributed
        if distributed is None:
            # Probe on worker 0, not the driver: the driver may sit on a
            # CPU-only head node while workers hold the TPU slice.
            distributed = len(worker_group) > 1 and \
                worker_group.execute_single(0, _worker_has_tpu)
        if distributed:
            self._init_distributed(worker_group, backend_config)
        else:
            logger.debug("JaxBackend: single-process mode, no coordinator")
        import ray_tpu
        num_tpus = int(worker_group.resources_per_worker.get("TPU", 0))
        gang = getattr(worker_group, "gang", "")
        ray_tpu.get([
            w.apply.remote(_setup_worker, num_tpus, rank, gang)
            for rank, w in enumerate(worker_group.workers)
        ], timeout=300)

    @staticmethod
    def _init_distributed(worker_group: WorkerGroup,
                          backend_config: JaxConfig) -> None:
        # Rank 0's node hosts the coordinator (reference
        # torch/config.py:106-112 picks MASTER_ADDR from worker 0).
        ip = worker_group.execute_single(0, _get_node_ip)
        port = backend_config.coordinator_port
        if port and getattr(worker_group, "elastic", False):
            # a re-form re-hosts the coordinator while the previous
            # formation's socket may still sit in TIME_WAIT — a fixed
            # port would fail the reconfiguration with EADDRINUSE and
            # spend FailureConfig budget on a port collision. Only an
            # explicitly pinned (non-default) port is worth a warning.
            if port != JaxConfig.coordinator_port:
                logger.warning(
                    "JaxConfig.coordinator_port=%d ignored for the "
                    "elastic gang: each formation picks a fresh free "
                    "port", port)
            port = 0
        port = port or worker_group.execute_single(0, _free_port)
        coordinator = f"{ip}:{port}"
        gang = getattr(worker_group, "gang", "")
        import ray_tpu
        ray_tpu.get([
            w.apply.remote(_init_jax_distributed, coordinator,
                           len(worker_group), rank, gang)
            for rank, w in enumerate(worker_group.workers)
        ], timeout=300)


def _worker_has_tpu() -> bool:
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
    return TPUAcceleratorManager.get_current_node_num_accelerators() > 0
