"""Operations, bytes and parameter counts of a sparse-expert decoder
(`model_type: olmoe`) from its published `config.json` keys. Pure Python,
no JAX; the dense pieces (attention, the flash kernels, the roofline) are
`benchlib.flops`'s.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters
the token passes — attention, the router, its `num_experts_per_tok`
experts (not all `num_experts`), the output head — plus causal
attention. Recomputation is not counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchlib import flops


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def expert_params(cfg: Dict[str, Any]) -> int:
    """One gated expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of one layer: attention, the QK-norm gains
    over the whole q and k projections, all experts, the router, the two
    norm gains."""
    hd = flops.head_dim(cfg)
    qk_gains = hd * (cfg["num_attention_heads"]
                     + cfg["num_key_value_heads"])
    return (attention_params(cfg) + qk_gains
            + cfg["num_experts"] * expert_params(cfg) + router_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d + head


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    """Matmul parameters one token passes: per layer attention, the
    router and its k experts; then the output head."""
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + cfg["num_experts_per_tok"] * expert_params(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    return 6.0 * matmul_params_per_token(cfg) \
        + flops.attention_train_flops_per_token(cfg, seq)


# ---- the grouped matmuls of the expert FFN -----------------------------
# rows = tokens x num_experts_per_tok token-slots, sorted by expert; one
# call multiplies every row by its own expert's [k, n] matrix.


def grouped_matmul_flops(rows: int, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def grouped_matmul_bytes(rows: int, k: int, n: int, groups: int,
                         itemsize: int = 2) -> float:
    """Least HBM traffic of one call, forward or either transpose: the
    `[rows, k]` and `[rows, n]` sides and every group's `[k, n]` matrix
    once each (two of the three are read, one is written)."""
    return float(itemsize) * (rows * k + rows * n + groups * k * n)


def expert_calls_per_step(cfg: Dict[str, Any], tokens: int,
                          remat: bool) -> List[Tuple[str, int, int, int]]:
    """(name, k, n, calls per layer and step) of the expert FFN's grouped
    matmuls: forward, under remat the same forward a second time, and the
    backward's two products per matmul (d lhs and d rhs), each of the
    forward's size."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    passes = (2 if remat else 1) + 2
    return [("gate_up", d, 2 * f, passes), ("down", f, d, passes)]


def experts_least_time_s(cfg: Dict[str, Any], tokens: int, remat: bool,
                         peaks: Dict[str, Any]) -> Tuple[float, str]:
    """Roofline of one step's grouped matmuls, all layers: the sum over
    the calls of the larger of FLOPs over peak and bytes over peak, and
    which bound holds for the largest call."""
    rows = tokens * cfg["num_experts_per_tok"]
    total, bound = 0.0, "compute"
    for _name, k, n, calls in expert_calls_per_step(cfg, tokens, remat):
        t, which = flops.least_time_s(
            grouped_matmul_flops(rows, k, n),
            grouped_matmul_bytes(rows, k, n, cfg["num_experts"]), peaks)
        total += calls * t
        if k * n == 2 * cfg["hidden_size"] * cfg["intermediate_size"]:
            bound = which
    return cfg["num_hidden_layers"] * total, bound
