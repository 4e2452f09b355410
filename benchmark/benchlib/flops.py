"""Operations and bytes from shapes. Pure Python, no JAX.

A decoder config here is the published `config.json` as the benchmark's
configuration file holds it (Hugging Face key names). Model FLOPs are the
operations the forward and backward passes REQUIRE: 2 per multiply-add, 3x
the forward for forward + backward, recomputation (remat, the chunked
head's second pass) not counted. Embedding lookups are gathers and count
nothing; the untied output head is a matmul and counts.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one decoder layer that sit in matmuls: q, k, v, o
    projections and the gated MLP (gate, up, down). Norm gains excluded."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp


def matmul_params(cfg: Dict[str, Any]) -> int:
    """All matmul parameters a token passes: the layers and the output
    head (tied or not, the head matmul is executed)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter: embedding, layers with their two norm
    gains, final norm, and the head when untied."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    per_layer = layer_matmul_params(cfg) + 2 * d
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + cfg["num_hidden_layers"] * per_layer + d + head


def attention_matmul_flops(batch: int, heads: int, seq: int, hd: int,
                           n_matmuls: int, causal: bool = True) -> float:
    """`n_matmuls` [seq, hd] x [hd, seq]-sized products per head: 2*T*T*hd
    each, halved under a causal mask (the lower triangle is what the
    algorithm needs)."""
    full = 2.0 * batch * heads * seq * seq * hd * n_matmuls
    return full / 2 if causal else full


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward QK^T and PV (2 matmuls), backward twice that: 6 matmul
    passes over the causal triangle, per layer, per token."""
    per_seq = attention_matmul_flops(
        1, cfg["num_attention_heads"], seq, head_dim(cfg), 6)
    return cfg["num_hidden_layers"] * per_seq / seq


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """6 x matmul parameters + causal attention, per trained token."""
    return 6.0 * matmul_params(cfg) + attention_train_flops_per_token(
        cfg, seq)


# ---- the attention kernels' calls -----------------------------------
# What one call of a kind computes, in [T, hd] x [hd, T]-sized products,
# whichever library's kernel it is (pallas flash, splash):
#   fwd        S = QK^T, O = PV                                 -> 2
#   bwd_dkv    S, dP = dO V^T, dV = P^T dO, dK = dS^T Q         -> 4
#   bwd_dq     S, dP, dQ = dS K                                 -> 3
#   bwd_fused  S, dP, dV, dK, dQ: the whole backward in one     -> 5
ATTENTION_KERNEL_MATMULS = {"fwd": 2, "bwd_dkv": 4, "bwd_dq": 3,
                            "bwd_fused": 5}
FUSED = "bwd_fused"


def kinds_as_computed(kinds: Dict[str, Any]) -> Dict[str, Any]:
    """`kind -> [seconds, events]` as a window's events were found
    (`fwd`, `bwd_dkv`, `bwd_dq`), by what their calls computed: where
    there are `bwd_dkv` events and no `bwd_dq` event, each `bwd_dkv` call
    made dQ too and is `bwd_fused`."""
    def events(kind: str) -> float:
        return kinds.get(kind, (0.0, 0))[1]

    if not events("bwd_dkv") or events("bwd_dq"):
        return dict(kinds)
    out = {k: v for k, v in kinds.items() if k not in ("bwd_dkv", "bwd_dq")}
    out[FUSED] = kinds["bwd_dkv"]
    return out


def attention_call_flops(kind: str, batch: int, heads: int, seq: int,
                         hd: int) -> float:
    return attention_matmul_flops(batch, heads, seq, hd,
                                  ATTENTION_KERNEL_MATMULS[kind])


def attention_call_bytes(kind: str, batch: int, heads: int, seq: int,
                         hd: int, kv_heads: Optional[int] = None,
                         itemsize: int = 2) -> float:
    """Least HBM traffic of one call: each operand or result once, q, o
    and their cotangents at `[B, H, T, hd]`, k, v, dk and dv at their own
    `kv_heads` (the call that does not repeat them is the one that moves
    least; `heads` where not given), f32 `[B, H, T]` softmax statistics
    once each."""
    wide = batch * heads * seq * hd * itemsize
    narrow = batch * (kv_heads or heads) * seq * hd * itemsize
    stat = batch * heads * seq * 4
    if kind == "fwd":        # read q, k, v; write o, l, m
        return 2 * wide + 2 * narrow + 2 * stat
    if kind == "bwd_dkv":    # read q, do, k, v, l, m, di; write dk, dv
        return 2 * wide + 4 * narrow + 3 * stat
    if kind == "bwd_dq":     # read q, do, k, v, l, m, di; write dq
        return 3 * wide + 2 * narrow + 3 * stat
    if kind == "bwd_fused":  # read q, do, k, v, l, m, di; write dq, dk, dv
        return 3 * wide + 4 * narrow + 3 * stat
    raise KeyError(kind)


def least_time_s(flops: float, nbytes: float, peaks: Dict[str, Any]):
    """Roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two bounds."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
