"""Operations, bytes and parameter counts of a latent-attention decoder
with shared and routed experts behind leading dense layers whose residual
path is several streams under manifold-constrained hyper-connections (the
catalog row `Xing4.0-29B-A4B`, `model_type: xing4_0`), from its published
`config.json` keys as the configuration file holds them. Pure Python, no
JAX. Everything but the streams is `benchlib.flops_mla_moe`'s count at the
heads, experts and rows held here (imported, not copied): this file adds a
sublayer's maps to the parameters and to the FLOPs, and the bytes the
streams' mixing must move.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: `flops_mla_moe.train_flops_per_token` plus, a sublayer, the maps'
product `[n*C] x [n*C, n*n + 2n]` (2 per multiply-add, 3x the forward:
688,128 forward at n 4 and C 3,584). The statistic, the sigmoids, the
Sinkhorn rounds and the two mixes are elementwise and counted by their
bytes, not as model FLOPs. Recomputation is not counted.
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import flops_mla_moe as base

SUBLAYERS_A_LAYER = 2   # attention, then the dense MLP or the experts
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}

router_experts = base.router_experts
qk_head_dim = base.qk_head_dim


def streams(cfg: Dict[str, Any]) -> int:
    return int(cfg["hc_mult"])


def maps_a_token(cfg: Dict[str, Any]) -> int:
    """Numbers a sublayer's three maps hold a token: H_pre, H_post,
    H_res."""
    n = streams(cfg)
    return n * n + 2 * n


def maps_matmul_params(cfg: Dict[str, Any]) -> int:
    """phi: the one matrix product of a sublayer's maps."""
    return streams(cfg) * cfg["hidden_size"] * maps_a_token(cfg)


def hc_params(cfg: Dict[str, Any]) -> int:
    """A sublayer's phi, b and three alphas."""
    return maps_matmul_params(cfg) + maps_a_token(cfg) + 3


def total_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter as held here: `flops_mla_moe`'s count and
    two sublayers' hyper-connections a layer."""
    return base.total_params(cfg) + cfg["num_hidden_layers"] \
        * SUBLAYERS_A_LAYER * hc_params(cfg)


def published_params(cfg: Dict[str, Any]) -> int:
    """`total_params` at the published depth, heads, experts and
    vocabulary, without the multi-token prediction module."""
    published = dict(cfg)
    for key, cut in cfg.get("reduced", {}).items():
        published[key] = cut["published"]
    return total_params(published)


def matmul_params_per_token(cfg: Dict[str, Any],
                            routed_slots_per_token: float) -> float:
    return base.matmul_params_per_token(cfg, routed_slots_per_token) \
        + cfg["num_hidden_layers"] * SUBLAYERS_A_LAYER \
        * maps_matmul_params(cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          routed_slots_per_token: float) -> float:
    return 6.0 * matmul_params_per_token(cfg, routed_slots_per_token) \
        + base.attention_train_flops_per_token(cfg, seq)


def attention_call(cfg: Dict[str, Any], batch: int, seq: int
                   ) -> Dict[str, Any]:
    """The record's `static.attention_call`, as the existing readers take
    it: the held heads, keys as wide as queries."""
    return {"batch": batch, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": seq,
            "head_dim": qk_head_dim(cfg)}


# ---- the streams' mixing -------------------------------------------------


def mhc_bytes(tokens: int, streams: int, width: int, sublayers: int,
              dtype: str, remat: bool = True) -> int:
    """The bytes the streams' mixing MUST move in one train step, whatever
    implements it, the stream, `h` and `y` in `dtype`. A sublayer's forward
    reads the stream once (the statistic, the product with phi and both
    mixes can share one pass) and writes it once, writes `h` [width] and
    reads `y` [width]: (2n + 2) x width values a token; under remat the
    same again; the backward reads the stream's gradient once, writes it
    once and reads the stream once: 3n x width (what `h`'s and `y`'s
    gradients move is the sublayer's). The maps themselves (n*n + 2n
    floats a token) and phi are not counted: under 0.2% of it."""
    forward = (2 * streams + 2) * width
    passes = (2 if remat else 1) * forward + 3 * streams * width
    return tokens * sublayers * passes * DTYPE_BYTES[dtype]


def mhc_call(cfg: Dict[str, Any], tokens: int, dtype: str, remat: bool
             ) -> Dict[str, Any]:
    """The record's `static.mhc_call`: the streams, the sublayers that mix
    them, the tokens a step, the Sinkhorn rounds, and the bytes a step by
    `mhc_bytes`."""
    sublayers = cfg["num_hidden_layers"] * SUBLAYERS_A_LAYER
    return {"streams": streams(cfg), "width": cfg["hidden_size"],
            "sublayers": sublayers, "tokens": tokens,
            "rounds": cfg["hc_sinkhorn_iters"], "dtype": dtype,
            "remat": remat,
            "bytes_a_step": mhc_bytes(tokens, streams(cfg),
                                      cfg["hidden_size"], sublayers, dtype,
                                      remat)}


def mhc_least_time_s(call: Dict[str, Any], steps: int,
                     peaks: Dict[str, Any]) -> float:
    """The least time `steps` steps' mixing takes: its bytes at the chip's
    memory bandwidth (its FLOPs, 0.7 M a token and sublayer, are four
    orders under the compute bound)."""
    return steps * call["bytes_a_step"] / peaks["hbm_bytes_per_s"]
