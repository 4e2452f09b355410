"""JaxTrainer: the flagship trainer (BASELINE.json north star).

reference parity: slots into the trainer inventory exactly where the
reference's torch trainer does (python/ray/train/torch/torch_trainer.py over
DataParallelTrainer, SURVEY.md §8.4) — a DataParallelTrainer subclass
whose backend wires jax.distributed over the gang instead of NCCL.

The per-worker loop is plain jax: build a Mesh (which spans the whole
slice once jax.distributed is initialized), make_train_step over it,
report() metrics/checkpoints. See tests/test_train.py for the canonical
loop shape.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer
from ray_tpu.train.jax_backend import JaxConfig


class JaxTrainer(DataParallelTrainer):
    _backend_config_cls = JaxConfig

    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 jax_config: Optional[JaxConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            backend_config=jax_config or JaxConfig(),
            scaling_config=scaling_config,
            run_config=run_config,
            datasets=datasets,
            resume_from_checkpoint=resume_from_checkpoint)
