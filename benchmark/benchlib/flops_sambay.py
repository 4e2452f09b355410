"""Operations, bytes and parameter counts of a SambaY decoder-hybrid-decoder
(`model_type: phi4flash`): Mamba-1 mixers, differential attention under a
window, a full causal mask or as cross-attention, gated memory units, each
followed by a dense MLP, from the published `config.json` keys as the
configuration file holds them (`layer_kinds` says which layers are held,
`mamba` the mixer's sizes). Pure Python, no JAX; the roofline is
`benchlib.flops.least_time_s`.

Model FLOPs are what the forward and backward passes REQUIRE for one token:
2 per multiply-add, 3x the forward, over the matmul parameters the token
passes (the tied head among them), plus attention, plus the mixers' scans.
Recomputation is not counted.

**Attention**, each call by ITS mask and widths. A head pair's two maps
are two query heads of hd = hidden / heads; a map's QK^T is hd wide and its
PV 2·hd (a value head is a pair's). Pairs of (query, key) positions a map
computes: a causal call T²/2 (the convention of `benchlib.flops`), a window
call T·W - W²/2. What one kernel call computes per pair and map, in
multiply-adds (2 operations each):

    fwd        S (hd), O = PV (2hd)                                -> 3 hd
    bwd_dkv    S (hd), dP = dO V^T (2hd), dV (2hd), dK (hd)        -> 6 hd
    bwd_dq     S (hd), dP (2hd), dQ (hd)                           -> 4 hd
    bwd_fused  S, dP, dV, dK, dQ in one                            -> 7 hd

The model REQUIRES the forward's two and the backward's four (dP, dV, dK,
dQ: 6 hd): 9 hd a pair and map.

**The scan** of one Mamba-1 mixer, per token, forward: every one of the
C·N state elements takes `dt·A`, an exponential, the decay's product, `dt x
B`, the sum, the product with C and its sum: 7 operations, none of them a
matmul. Its least traffic: x in the compute dtype, dt, B and C in float32
read and y written once a pass. The scan is bound by memory on any chip
whose matmul peak is the yardstick; `scan_least_time_s` says so.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchlib import flops

ATTENTION_KINDS = {"w": "window", "f": "full", "c": "cross"}
# per pair and map, in multiply-adds of hd (module docstring)
KERNEL_WIDTHS = {"fwd": 3, "bwd_dkv": 6, "bwd_dq": 4, "bwd_fused": 7}
REQUIRED_WIDTHS = 9


def kinds(cfg: Dict[str, Any]) -> str:
    return cfg["layer_kinds"]


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ---- parameters -------------------------------------------------------------


def mixer_matmul_params(cfg: Dict[str, Any]) -> int:
    """in_proj ([x | z]), x_proj ([delta | B | C]), dt_proj, out_proj."""
    d, m = cfg["hidden_size"], cfg["mamba"]
    c, n, r = m["d_inner"], m["d_state"], m["dt_rank"]
    return d * 2 * c + c * (r + 2 * n) + r * c + c * d


def mixer_params(cfg: Dict[str, Any]) -> int:
    """And the convolution with its bias, dt_proj's bias, A_log, D."""
    m = cfg["mamba"]
    c = m["d_inner"]
    return mixer_matmul_params(cfg) + c * m["d_conv"] + c + c \
        + c * m["d_state"] + c


def attention_matmul_params(cfg: Dict[str, Any], kind: str) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = d * cfg["num_attention_heads"] * hd
    kv = 0 if kind == "c" else 2 * d * cfg["num_key_value_heads"] * hd
    return q + kv + cfg["num_attention_heads"] * hd * d


def attention_params(cfg: Dict[str, Any], kind: str) -> int:
    """And the projections' biases, lambda's four vectors, the pair norm."""
    hd = head_dim(cfg)
    heads = cfg["num_attention_heads"] + (
        0 if kind == "c" else 2 * cfg["num_key_value_heads"])
    return attention_matmul_params(cfg, kind) + heads * hd \
        + cfg["hidden_size"] + 4 * hd + 2 * hd


def gmu_params(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["hidden_size"] * cfg["mamba"]["d_inner"]


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sublayer_params(cfg: Dict[str, Any], kind: str) -> int:
    if kind in "ms":
        return mixer_params(cfg)
    if kind == "g":
        return gmu_params(cfg)
    return attention_params(cfg, kind)


def sublayer_matmul_params(cfg: Dict[str, Any], kind: str) -> int:
    if kind in "ms":
        return mixer_matmul_params(cfg)
    if kind == "g":
        return gmu_params(cfg)
    return attention_matmul_params(cfg, kind)


def layer_params(cfg: Dict[str, Any], kind: str) -> int:
    """A layer: its sublayer, the MLP, two LayerNorms' gains and biases."""
    return sublayer_params(cfg, kind) + mlp_params(cfg) \
        + 4 * cfg["hidden_size"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter: the (tied) embedding, the layers, the final
    LayerNorm. lambda_init is a constant of the layer's place, no
    parameter."""
    d = cfg["hidden_size"]
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("the family ties its head to the embedding")
    return cfg["vocab_size"] * d + 2 * d \
        + sum(layer_params(cfg, kind) for kind in kinds(cfg))


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    return sum(sublayer_matmul_params(cfg, kind) + mlp_params(cfg)
               for kind in kinds(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]


# ---- attention ----------------------------------------------------------------


def attention_pairs(kind: str, seq: int, window: int) -> float:
    """(query, key) pairs one map of a call of this kind computes."""
    if kind == "window" and window < seq:
        return seq * window - window * window / 2.0
    return seq * seq / 2.0


def attention_call_flops(call: str, kind: str, cfg: Dict[str, Any],
                         seq: int, batch: int = 1) -> float:
    """One kernel call (`call`: fwd, bwd_dkv, bwd_dq, bwd_fused) of one
    attention layer of `kind` (window, full, cross)."""
    return 2.0 * KERNEL_WIDTHS[call] * head_dim(cfg) * batch \
        * cfg["num_attention_heads"] \
        * attention_pairs(kind, seq, cfg["sliding_window"])


def attention_call_bytes(call: str, cfg: Dict[str, Any], seq: int,
                         batch: int = 1, itemsize: int = 2) -> float:
    """Least HBM traffic of one call: q and dq at `[H, T, hd]`, k and dk at
    the key heads, v and dv at HALF as many heads of 2·hd, o and do at
    `[H, T, 2hd]`, f32 `[H, T]` statistics once each (a window changes
    nothing: every row is read)."""
    hd = head_dim(cfg)
    q = batch * cfg["num_attention_heads"] * seq * hd * itemsize
    k = batch * cfg["num_key_value_heads"] * seq * hd * itemsize
    stat = batch * cfg["num_attention_heads"] * seq * 4
    o = 2 * q
    if call == "fwd":          # read q, k, v; write o, l, m
        return q + 2 * k + o + 2 * stat
    if call == "bwd_dkv":      # read q, do, k, v, l, m, di; write dk, dv
        return q + o + 4 * k + 3 * stat
    if call == "bwd_dq":       # read q, do, k, v, l, m, di; write dq
        return 2 * q + o + 2 * k + 3 * stat
    if call == "bwd_fused":
        return 2 * q + o + 4 * k + 3 * stat
    raise KeyError(call)


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    total = 0.0
    for kind in kinds(cfg):
        if kind in ATTENTION_KINDS:
            total += 2.0 * REQUIRED_WIDTHS * head_dim(cfg) \
                * cfg["num_attention_heads"] * attention_pairs(
                    ATTENTION_KINDS[kind], seq, cfg["sliding_window"])
    return total / seq


def attention_call_not_above(cfg: Dict[str, Any], seq: int) -> Dict[str, int]:
    """The `static.attention_call` for readers that know ONE causal shape
    with both products hd wide (`layer_metrics/attn_kernel_roofline.py`
    counts every event as `benchlib.flops.attention_call_flops` of it):
    the true heads and head width, and the longest sequence, a multiple of
    128, at which that count over this model's calls of a step is not above
    what they computed, for the forward calls and for either form of the
    backward. The reading it gives falls short of the true one
    (`masked_attn_kernel_roofline`) by the ratio of the two counts."""
    hd, heads = head_dim(cfg), cfg["num_attention_heads"]
    calls = [ATTENTION_KINDS[k] for k in kinds(cfg) if k in ATTENTION_KINDS]
    for t in range(seq, 0, -128):
        if all(len(calls) * flops.attention_call_flops(
                call, 1, heads, t, hd) <= sum(
                    attention_call_flops(call, kind, cfg, seq)
                    for kind in calls)
               for call in ("fwd", "bwd_dkv", "bwd_dq", "bwd_fused")):
            return {"batch": 1, "heads": heads,
                    "kv_heads": cfg["num_key_value_heads"], "seq": t,
                    "head_dim": hd}
    raise ValueError("no sequence length keeps the one-shape count under "
                     "what the calls computed")


# ---- the selective scan --------------------------------------------------------


def scan_flops_per_token(cfg: Dict[str, Any]) -> float:
    """One mixer's scan, forward, per token (module docstring)."""
    m = cfg["mamba"]
    return 7.0 * m["d_inner"] * m["d_state"]


def scan_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> float:
    m = cfg["mamba"]
    return itemsize * m["d_inner"] + 4.0 * (
        2 * m["d_inner"] + 2 * m["d_state"])


def scan_passes_per_step(remat: bool) -> int:
    """Forward, under remat the forward again, and the backward at twice a
    forward: in forwards (`flops_ssm_moe`'s count)."""
    return (2 if remat else 1) + 2


def mixers(cfg: Dict[str, Any]) -> int:
    return sum(kind in "ms" for kind in kinds(cfg))


def scan_least_time_s(cfg: Dict[str, Any], tokens: int, steps: int,
                      remat: bool, peaks: Dict[str, Any]
                      ) -> Tuple[float, str]:
    """Roofline of every mixer's scan over `steps` steps of `tokens`
    tokens: per pass the larger of FLOPs over peak and bytes over peak, and
    which of the two bounds."""
    t, bound = flops.least_time_s(tokens * scan_flops_per_token(cfg),
                                  tokens * scan_bytes_per_token(cfg), peaks)
    return steps * mixers(cfg) * scan_passes_per_step(remat) * t, bound


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    return (6.0 * matmul_params_per_token(cfg)
            + attention_train_flops_per_token(cfg, seq)
            + 3.0 * mixers(cfg) * scan_flops_per_token(cfg))
