"""Operations, bytes and parameter counts of a sparse-expert decoder with
plain GQA and a per-head QK-norm (`model_type: sdar_moe`) trained as a
block-diffusion model, from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the roofline and the
attention calls' bytes are `benchlib.flops`'s.

A configuration that holds a chip's share says so itself: its top-level
`num_experts` and `vocab_size` are what is held here, and
`reduced.num_experts.published` is the router's width.

**The step.** One sequence of L data tokens is a stream of 2L positions:
the noised copy and the clean one behind it. Model FLOPs are what the
forward and backward passes REQUIRE for one DATA token (2 per
multiply-add, 3x the forward; recomputation not counted; a rate in
tokens/s counts data tokens, never positions):

- the four projections and the router over the 2L positions of every
  layer, the routed experts over the slots THIS CHIP computed (the
  program's counter), the head over the L read positions;
- attention over the pairs the mask leaves, `L^2 + L*B` of the `4 L^2`
  (`mask_pairs`): QK^T and PV at the head width;
- **less what nothing reads**: the last layer's clean half feeds nothing
  but its keys and values (the final norm and the head read the noised
  half alone), so its query and output projections, its rows of the
  attention (`(L^2 + L*B) / 2` pairs), its router and its experts are not
  required. The program computes them today (one scan body for every
  layer); counting them would let that waste raise the utilization. The
  counter does not say which half a slot came from: half of the last
  layer's held slots are taken as the noised half's.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchlib import flops


def router_experts(cfg: Dict[str, Any]) -> int:
    """The router's width: the published expert count."""
    cut = cfg.get("reduced", {}).get("num_experts")
    return int(cut["published"] if cut else cfg["num_experts"])


def qkv_params(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(q and o, k and v): the matmul parameters of one attention block
    by who reads their output."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd,
            2 * d * cfg["num_key_value_heads"] * hd)


def attention_params(cfg: Dict[str, Any]) -> int:
    return sum(qkv_params(cfg))


def expert_params(cfg: Dict[str, Any]) -> int:
    """One gated expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * router_experts(cfg)


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a layer as held here: attention, the two
    QK-norm gains of one head's width, the router, the held experts, the
    two norm gains."""
    return (attention_params(cfg) + 2 * cfg["head_dim"]
            + router_params(cfg) + cfg["num_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d + head


# ---- the mask -----------------------------------------------------------


def mask_pairs(seq: int, block: int) -> float:
    """(query, key) pairs the block-diffusion mask leaves of a doubled
    stream of `seq` data tokens: the noised blocks' diagonal `L*B`, the
    clean blocks strictly before a noised one `(L^2 - L*B) / 2`, the clean
    half causal by block `(L^2 + L*B) / 2`."""
    return float(seq) * seq + float(seq) * block


def read_pairs(seq: int, block: int) -> float:
    """Those of them whose query is a noised position: all that the last
    layer needs."""
    return seq * block + (float(seq) * seq - seq * block) / 2.0


def attention_flops(pairs: float, cfg: Dict[str, Any],
                    n_matmuls: int) -> float:
    """`n_matmuls` products of the head width over `pairs` pairs, every
    query head."""
    return 2.0 * n_matmuls * pairs * cfg["num_attention_heads"] \
        * cfg["head_dim"]


# ---- the step -----------------------------------------------------------


def forward_flops_per_sequence(cfg: Dict[str, Any], seq: int,
                               held_slots: Sequence[float]) -> float:
    """The forward pass of one sequence of `seq` data tokens (module
    docstring). `held_slots[layer]`: the token-slots this chip's experts
    computed in that layer, of the stream's `2 * seq * k`."""
    layers, block = cfg["num_hidden_layers"], cfg["block_length"]
    if len(held_slots) != layers:
        raise ValueError(f"{len(held_slots)} layers' slots for {layers}")
    read, kept = qkv_params(cfg)
    router, expert = router_params(cfg), expert_params(cfg)
    total = 0.0
    for layer, slots in enumerate(held_slots):
        last = layer == layers - 1
        positions = seq if last else 2 * seq
        total += 2.0 * (positions * (read + router) + 2 * seq * kept
                        + (slots / 2.0 if last else slots) * expert)
        total += attention_flops(
            read_pairs(seq, block) if last else mask_pairs(seq, block),
            cfg, 2)
    return total + 2.0 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          held_slots: Sequence[float]) -> float:
    """Forward and backward, per DATA token."""
    return 3.0 * forward_flops_per_sequence(cfg, seq, held_slots) / seq


# ---- the attention kernel's calls ---------------------------------------


def attention_call_flops(call: str, cfg: Dict[str, Any], seq: int,
                         batch: int = 1) -> float:
    """One kernel call (`call`: fwd, bwd_dkv, bwd_dq, bwd_fused) counted
    as what the mask leaves, whatever block sizes or mask form compute
    it."""
    return batch * attention_flops(
        mask_pairs(seq, cfg["block_length"]), cfg,
        flops.ATTENTION_KERNEL_MATMULS[call])


def attention_call_bytes(call: str, cfg: Dict[str, Any], seq: int,
                         batch: int = 1) -> float:
    """Least HBM traffic of one call over the 2 * seq positions: every
    row is read whatever the mask hides."""
    return flops.attention_call_bytes(
        call, batch, cfg["num_attention_heads"], 2 * seq, cfg["head_dim"],
        cfg["num_key_value_heads"])


def attention_least_time_s(call: str, cfg: Dict[str, Any], seq: int,
                           peaks: Dict[str, Any], batch: int = 1):
    return flops.least_time_s(attention_call_flops(call, cfg, seq, batch),
                              attention_call_bytes(call, cfg, seq, batch),
                              peaks)


def attention_call_not_above(cfg: Dict[str, Any], seq: int,
                             batch: int = 1) -> Dict[str, int]:
    """The `static.attention_call` for readers that know ONE causal shape
    (`layer_metrics/attn_kernel_roofline.py` counts every event as
    `benchlib.flops.attention_call_flops` of it): the true heads and head
    width, and the longest causal sequence, a multiple of 128, whose
    `T^2 / 2` pairs are not above the mask's `L^2 + L*B`
    (`flops_sambay.attention_call_not_above`'s way). The reading it gives
    falls short of `blockdiff_attn_kernel_roofline` by the ratio of the
    two counts."""
    pairs = mask_pairs(seq, cfg["block_length"])
    for t in range(2 * seq, 0, -128):
        if t * t / 2.0 <= pairs:
            return {"batch": batch, "heads": cfg["num_attention_heads"],
                    "kv_heads": cfg["num_key_value_heads"], "seq": t,
                    "head_dim": cfg["head_dim"]}
    raise ValueError("no causal length keeps the one-shape count under "
                     "what the mask leaves")
