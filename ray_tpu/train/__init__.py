"""ray_tpu.train: distributed training orchestration (Ray Train parity).

reference: python/ray/train — BaseTrainer/DataParallelTrainer +
BackendExecutor + _TrainSession (SURVEY.md §2.3, §3.6), rebuilt with a
jax.distributed/ICI-mesh backend instead of NCCL process groups.
"""

from ray_tpu.train.checkpoint import Checkpoint  # noqa: F401
from ray_tpu.train.config import (CheckpointConfig, FailureConfig,  # noqa: F401
                                  RunConfig, ScalingConfig)
from ray_tpu.train.data_parallel_trainer import (DataParallelTrainer,  # noqa: F401
                                                 Result)
from ray_tpu.train.jax_backend import JaxConfig  # noqa: F401
from ray_tpu.train.jax_trainer import JaxTrainer  # noqa: F401
from ray_tpu.train.session import (TrainContext, get_checkpoint,  # noqa: F401
                                   get_context, get_dataset_shard, report)

__all__ = [
    "Checkpoint", "CheckpointConfig", "FailureConfig", "RunConfig",
    "ScalingConfig", "DataParallelTrainer", "Result", "JaxConfig",
    "JaxTrainer", "TrainContext", "report", "get_checkpoint",
    "get_context", "get_dataset_shard",
]

from ray_tpu._private.usage import record_library_usage as _rlu
_rlu('train')
del _rlu
