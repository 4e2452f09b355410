"""ray_tpu.models: TPU-first model zoo.

The reference ships no in-tree language models (its models live in RLlib's
policy nets, reference: python/ray/rllib/models/ — torch/tf MLP+CNN
catalogs); LLM training flows through user-supplied torch modules (e.g.
the DeepSpeed 7B fine-tune example,
reference: train/examples/deepspeed/deepspeed_torch_trainer.py). The TPU
rebuild makes the flagship model family first-class: a decoder-only
transformer (Llama-style: RMSNorm/RoPE/SwiGLU/GQA, optional QK-norm and
routed experts; GPT-2-125M through Llama-2-7B and OLMoE-1B-7B), written as pure
pytrees + jax functions with logical sharding specs so one definition runs
dense, FSDP, TP, sequence-parallel (ring/Ulysses) and their combinations.
"""

from ray_tpu.models.configs import (GPT2_125M, LLAMA2_7B,  # noqa: F401
                                    OLMOE_1B_7B, TINY, TransformerConfig)
from ray_tpu.models.transformer import Transformer  # noqa: F401

__all__ = [
    "TransformerConfig", "Transformer", "TINY", "GPT2_125M", "LLAMA2_7B",
    "OLMOE_1B_7B",
]
