"""Transformer configurations.

Named scales: GPT-2 125M (BASELINE.json's data-parallel config),
Llama-2 7B (its FSDP config) and OLMoE-1B-7B (the sparse-expert decoder of
BENCHMARK.json's `train_olmoe_d1`), each at its published depth. The
benchmark's own configurations are the published `config.json` files
under benchmark/configs/, mapped onto TransformerConfig by its jobs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None      # None -> = n_heads (MHA)
    d_ff: Optional[int] = None            # None -> 4*d_model (8/3 for swiglu
                                          # users should set explicitly)
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # RMSNorm with a learned gain over the whole q and the whole k
    # projection (all heads together), before the split into heads and
    # before RoPE: OLMoE's / OLMo-2's QK-norm.
    qk_norm: bool = False
    # "auto" | "dense" | "flash" | "ring" | "ulysses". auto = pallas
    # flash kernel on TPU when the seq axis is unsharded (ring when it
    # is), dense elsewhere; dense = materialized-scores attention with
    # GSPMD-managed layout; ring/ulysses = explicit shard_map SP.
    attention_impl: str = "dense"
    # dtypes: params kept in param_dtype, compute runs in dtype (bf16 on
    # TPU keeps the MXU fed; accumulation is f32 via preferred_element_type)
    dtype: Any = "bfloat16"
    param_dtype: Any = "float32"
    remat: bool = False                   # jax.checkpoint each layer
    # "full": recompute the whole layer in bwd (min memory, +1 fwd pass);
    # "dots": save matmul outputs, recompute only elementwise chains
    # (near-zero recompute FLOPs — fastest when activations fit). Any
    # other value raises.
    remat_policy: str = "full"
    # chunk the lm-head + cross-entropy over the sequence axis so the
    # [B,T,vocab] f32 logits (+grad) never materialize at once; 0 = off.
    loss_chunk: int = 256
    # unroll factor for the layer scan. True unrolls fully: XLA sees
    # static weight slices (no dynamic-slice bookkeeping per layer) and
    # can fuse across layer boundaries; costs compile time, wins step
    # time for shallow stacks. Keep 1 (rolled) for deep models and for
    # the pipeline axis.
    scan_unroll: int = 1
    # Mixture-of-Experts FFN (ops/moe.py): 0 = dense FFN; >0 replaces
    # every layer's FFN with a softmax top-k router over moe_experts
    # gated experts of width d_ff, dropless (sorted dispatch) unless the
    # mesh has an "expert" axis above 1, over which the expert weights
    # shard. The router aux (load-balancing) loss is added to the LM loss
    # with moe_aux_coeff (`router_aux_loss_coef`).
    moe_experts: int = 0
    moe_top_k: int = 2
    # renormalise a token's top-k router weights to sum to 1
    # (`norm_topk_prob`; OLMoE publishes false)
    moe_norm_topk: bool = True
    moe_aux_coeff: float = 0.01

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)

    @property
    def num_params(self) -> int:
        d, l, f, v = self.d_model, self.n_layers, self.ff_dim, self.vocab_size
        hd, nh, nkv = self.head_dim, self.n_heads, self.kv_heads
        attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
        if self.qk_norm:
            attn += nh * hd + nkv * hd
        mlp = 3 * d * f
        if self.moe_experts:   # every expert, and the router
            mlp = self.moe_experts * mlp + d * self.moe_experts
        norms = 2 * d
        head = 0 if self.tie_embeddings else d * v
        return v * d + l * (attn + mlp + norms) + d + head


TINY = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128)

# GPT-2 small scale (125M): 12L/768d, 50k vocab, learned-pos in the
# original — here RoPE (TPU-first redesign, not a port). Head shape is
# 6 heads x 128 head_dim rather than the original 12 x 64: identical
# parameter count and FLOPs (d_total = 768 either way), but head_dim
# 128 fills the MXU's 128-lane contraction on the QK^T/PV matmuls where
# 64 leaves half the array idle, and 6 heads halve the softmax VPU work
# — measured +30% train-step throughput on v5e-class chips.
GPT2_125M = TransformerConfig(
    vocab_size=50304,  # 50257 padded to a multiple of 128 for the MXU
    d_model=768, n_layers=12, n_heads=6, d_ff=3072, max_seq_len=1024,
    tie_embeddings=True)

LLAMA2_7B = TransformerConfig(
    vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=32, d_ff=11008, max_seq_len=4096, norm_eps=1e-5,
    remat=True)

# OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json; arXiv
# 2409.02060): 16 layers of MHA with QK-norm and 64 SiLU-gated experts of
# width 1024, top-8 routing with unnormalised weights, no shared expert.
# 6.92B parameters, 1.3B of them active for a token.
OLMOE_1B_7B = TransformerConfig(
    vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
    n_kv_heads=16, d_ff=1024, max_seq_len=4096, rope_theta=10000.0,
    norm_eps=1e-5, qk_norm=True, moe_experts=64, moe_top_k=8,
    moe_norm_topk=False, moe_aux_coeff=0.01, remat=True)
