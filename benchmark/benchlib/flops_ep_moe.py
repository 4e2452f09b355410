"""Operations, bytes and parameter counts of a sparse-expert decoder with
plain GQA, a per-head QK-norm and window and full attention layers mixed
(`model_type: mellum`) whose experts are ALL present, spread by expert
over the chips of one host, from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the roofline and the
attention calls' bytes are `benchlib.flops`'s, the grouped matmuls'
`benchlib.flops_moe`'s.

Model FLOPs are what the forward and backward passes REQUIRE for one token
(2 per multiply-add, 3x the forward; recomputation not counted): the four
projections, the router and a token's k experts in every layer, the head,
and attention over the pairs each layer's mask leaves: a full layer
`T^2 / 2`, a sliding layer `T W - W^2 / 2` (1,024 keys a query, not T / 2),
QK^T and PV at the head width.

What travels: a token's row of `hidden_size` in the compute dtype goes to
every OTHER chip that holds one of its k experts and its results come
back, forward and backward: four passes a layer and step
(`exchange_least_time_s`). Counted from the program's
`moe_exchange_pairs` (the distinct (token, other chip) pairs) and the
model's shapes alone, so it reads the same work whether rows go a slot or
a chip at a time, padded or ragged, sent again under remat or kept.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

from benchlib import flops, flops_moe

KINDS = {"sliding_attention": "window", "full_attention": "full"}
EXCHANGE_PASSES = 4   # dispatch and combine, forward and backward


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """`window` or `full` for each of the layers kept."""
    return [KINDS[kind]
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def expert_params(cfg: Dict[str, Any]) -> int:
    """One gated expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a layer: attention, the two QK-norm
    gains of one head's width, the router, all the experts, the two norm
    gains."""
    return (attention_params(cfg) + 2 * cfg["head_dim"]
            + router_params(cfg) + cfg["num_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d + head


def active_matmul_params(cfg: Dict[str, Any]) -> int:
    """The matmul parameters one token passes in a layer."""
    return attention_params(cfg) + router_params(cfg) \
        + cfg["num_experts_per_tok"] * expert_params(cfg)


# ---- attention ------------------------------------------------------------


def attention_pairs(kind: str, seq: int, window: int) -> float:
    """(query, key) pairs the mask of a layer of this kind leaves."""
    if kind == "window" and window < seq:
        return seq * window - window * window / 2.0
    return seq * seq / 2.0


def attention_call_flops(call: str, kind: str, cfg: Dict[str, Any],
                         seq: int, batch: int = 1) -> float:
    """One kernel call (`call`: fwd, bwd_dkv, bwd_dq, bwd_fused) of a
    layer of `kind`, counted as what its mask leaves."""
    return 2.0 * flops.ATTENTION_KERNEL_MATMULS[call] * batch \
        * cfg["num_attention_heads"] * cfg["head_dim"] \
        * attention_pairs(kind, seq, cfg["sliding_window"])


def attention_call_bytes(call: str, cfg: Dict[str, Any], seq: int,
                         batch: int = 1) -> float:
    """Least HBM traffic of one call: every row once, whatever the mask
    hides."""
    return flops.attention_call_bytes(
        call, batch, cfg["num_attention_heads"], seq, cfg["head_dim"],
        cfg["num_key_value_heads"])


def attention_call_not_above(cfg: Dict[str, Any], seq: int,
                             batch: int = 1) -> Dict[str, int]:
    """The `static.attention_call` for readers that know ONE causal shape
    (`layer_metrics/attn_kernel_roofline.py` counts every event as
    `benchlib.flops.attention_call_flops` of it): the true heads and head
    width, and the longest causal sequence, a multiple of 128 (of 8 under
    1,024 tokens: a rehearsal), at which that count over this model's
    calls of a step is not above what they computed (`flops_sambay.attention_call_not_above`'s way). The reading
    it gives falls short of `swa_attn_kernel_roofline` by the ratio of the
    two counts."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kinds = layer_kinds(cfg)
    for t in range(seq, 0, -128 if seq >= 1024 else -8):
        if all(len(kinds) * flops.attention_call_flops(
                call, batch, heads, t, hd) <= sum(
                    attention_call_flops(call, kind, cfg, seq, batch)
                    for kind in kinds)
               for call in flops.ATTENTION_KERNEL_MATMULS):
            return {"batch": batch, "heads": heads,
                    "kv_heads": cfg["num_key_value_heads"], "seq": t,
                    "head_dim": hd}
    raise ValueError("no causal length keeps the one-shape count under "
                     "what the calls computed")


# ---- the step -------------------------------------------------------------


def forward_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    pairs = sum(attention_pairs(kind, seq, cfg["sliding_window"])
                for kind in layer_kinds(cfg))
    return 2.0 * (cfg["num_hidden_layers"] * active_matmul_params(cfg)
                  + cfg["hidden_size"] * cfg["vocab_size"]) \
        + 2.0 * 2 * pairs * cfg["num_attention_heads"] \
        * cfg["head_dim"] / seq


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward, per token."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def forward_flops_shares(cfg: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Where a token's forward FLOPs go, as shares of 1."""
    total = forward_flops_per_token(cfg, seq)
    layers = cfg["num_hidden_layers"]
    kernels = total - 2.0 * (layers * active_matmul_params(cfg)
                             + cfg["hidden_size"] * cfg["vocab_size"])
    return {
        "experts": 2.0 * layers * cfg["num_experts_per_tok"]
        * expert_params(cfg) / total,
        "head": 2.0 * cfg["hidden_size"] * cfg["vocab_size"] / total,
        "attention_projections": 2.0 * layers * attention_params(cfg)
        / total,
        "router": 2.0 * layers * router_params(cfg) / total,
        "attention_kernels": kernels / total}


# ---- the experts' grouped matmuls ------------------------------------------


def experts_least_time_s(cfg: Dict[str, Any],
                         rows: Sequence[Sequence[Sequence[int]]],
                         held: int, remat: bool, peaks: Dict[str, Any]
                         ) -> Tuple[float, str]:
    """Roofline of the experts' grouped matmuls over some steps, mean over
    the chips: `rows[step][layer][chip]` the rows that chip's `held`
    experts ran (the program's `moe_rows_received`). Per call the larger
    of FLOPs over peak and bytes over peak, `flops_moe`'s calls a step
    (forward, under remat the forward again, the backward's two products
    per matmul); and which bound holds for most of the time."""
    calls = flops_moe.expert_calls_per_step(
        {"hidden_size": cfg["hidden_size"],
         "intermediate_size": cfg["moe_intermediate_size"]}, 0, remat)
    total, chips = 0.0, 1
    by_bound = {"compute": 0.0, "memory": 0.0}
    for step in rows:
        for layer in step:
            chips = len(layer)
            for received in layer:
                for _name, k, n, passes in calls:
                    t, which = flops.least_time_s(
                        flops_moe.grouped_matmul_flops(received, k, n),
                        flops_moe.grouped_matmul_bytes(received, k, n, held),
                        peaks)
                    total += passes * t
                    by_bound[which] += passes * t
    return total / chips, max(by_bound, key=by_bound.get)


# ---- the exchange ----------------------------------------------------------


def exchange_bytes_out(cfg: Dict[str, Any], pairs: float,
                       itemsize: int = 2) -> float:
    """The least bytes that leave a chip in a layer and step whose tokens
    make `pairs` distinct (token, other chip) pairs: each pair's row of
    `hidden_size` once a pass."""
    return EXCHANGE_PASSES * pairs * cfg["hidden_size"] * itemsize


def exchange_least_time_s(cfg: Dict[str, Any],
                          pairs: Sequence[Sequence[Sequence[int]]],
                          peaks: Dict[str, Any]) -> List[float]:
    """Per chip, the least seconds its exchanges of some steps could take:
    `pairs[step][layer][chip]` (the program's `moe_exchange_pairs`), the
    bytes out of the chip at the published ICI rate a chip."""
    rate = peaks["ici_bits_per_s"] / 8.0
    chips = len(pairs[0][0])
    return [sum(exchange_bytes_out(cfg, layer[c])
                for step in pairs for layer in step) / rate
            for c in range(chips)]
