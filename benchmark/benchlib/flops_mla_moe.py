"""Operations, bytes and parameter counts of a latent-attention decoder
with shared and routed experts behind leading dense layers
(`model_type: glm4_moe_lite`), from its published `config.json` keys as
the configuration file holds them. Pure Python, no JAX; the attention
kernels' calls and the roofline are `benchlib.flops`'s, the grouped
matmul's operations and bytes `benchlib.flops_moe`'s.

A configuration that holds a chip's share says so itself: its top-level
`n_routed_experts` and `vocab_size` are what is held here, and
`reduced.n_routed_experts.published` is the router's width (all E
experts are scored, k a token chosen).

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters the
token passes: latent attention's four projections and `o_proj`, the dense
layers' MLP, in an expert layer the router, the shared expert and the
routed experts THIS CHIP computed for it (`routed_slots_per_token`, from
the program's counter: about k x held / E, not k), the output head; plus
causal attention at the query/key and value head widths. Recomputation is
not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchlib import flops, flops_moe


def router_experts(cfg: Dict[str, Any]) -> int:
    """The router's width: the published expert count."""
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return int(cut["published"] if cut else cfg["n_routed_experts"])


def qk_head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: Dict[str, Any]) -> int:
    """Matmul parameters of one latent-attention block: q down and up, kv
    down (with the shared rotary key head) and up, o."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * nh * qk_head_dim(cfg)
            + d * (kvr + cfg["qk_rope_head_dim"])
            + kvr * nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + nh * cfg["v_head_dim"] * d)


def latent_norm_params(cfg: Dict[str, Any]) -> int:
    return cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def gated_params(cfg: Dict[str, Any], width: int) -> int:
    """One gated MLP: gate, up, down."""
    return 3 * cfg["hidden_size"] * width


def expert_params(cfg: Dict[str, Any]) -> int:
    return gated_params(cfg, cfg["moe_intermediate_size"])


def shared_params(cfg: Dict[str, Any]) -> int:
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * router_experts(cfg)


def expert_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def dense_layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a leading dense layer."""
    return (attention_params(cfg) + latent_norm_params(cfg)
            + gated_params(cfg, cfg["intermediate_size"])
            + 2 * cfg["hidden_size"])


def expert_layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of an expert layer as held here: attention,
    the shared expert, the router, the held experts, the two norm gains.
    The router's choice bias is a buffer and not counted."""
    return (attention_params(cfg) + latent_norm_params(cfg)
            + shared_params(cfg) + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return (v * d + cfg["first_k_dense_replace"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg) + d + head)


def matmul_params_per_token(cfg: Dict[str, Any],
                            routed_slots_per_token: float) -> float:
    """Matmul parameters one token passes on this chip.
    `routed_slots_per_token`: the token-slots this chip's experts
    computed, summed over the expert layers, per token."""
    dense = cfg["first_k_dense_replace"] * gated_params(
        cfg, cfg["intermediate_size"])
    per_expert_layer = router_params(cfg) + shared_params(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg) + dense
            + expert_layers(cfg) * per_expert_layer
            + routed_slots_per_token * expert_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward QK^T (at the query/key width) and PV (at the value width),
    backward twice that, over the causal triangle, per layer, per token."""
    nh = cfg["num_attention_heads"]
    per_seq = sum(flops.attention_matmul_flops(1, nh, seq, width, 3)
                  for width in (qk_head_dim(cfg), cfg["v_head_dim"]))
    return cfg["num_hidden_layers"] * per_seq / seq


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          routed_slots_per_token: float) -> float:
    return 6.0 * matmul_params_per_token(cfg, routed_slots_per_token) \
        + attention_train_flops_per_token(cfg, seq)


# ---- the held experts' grouped matmuls ---------------------------------
# One call multiplies the rows of the held experts' groups by each group's
# own [k, n] matrix; rows of groups held elsewhere are not visited.


def held_experts_least_time_s(cfg: Dict[str, Any],
                              held_rows: Sequence[Sequence[int]],
                              remat: bool, peaks: Dict[str, Any]
                              ) -> Tuple[float, str]:
    """Roofline of the held experts' grouped matmuls over some steps:
    `held_rows[step][layer]` the token-slots the held experts of that
    layer received in that step (the program's counter). Per call the
    larger of FLOPs over peak and bytes over peak, `flops_moe`'s calls a
    step (forward, under remat the forward again, the backward's two
    products per matmul), the weights of the held experts only; and which
    bound holds for most of the time."""
    held = cfg["n_routed_experts"]
    total = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    calls = flops_moe.expert_calls_per_step(
        {"hidden_size": cfg["hidden_size"],
         "intermediate_size": cfg["moe_intermediate_size"]}, 0, remat)
    for step in held_rows:
        for rows in step:
            for _name, k, n, passes in calls:
                t, which = flops.least_time_s(
                    flops_moe.grouped_matmul_flops(rows, k, n),
                    flops_moe.grouped_matmul_bytes(rows, k, n, held), peaks)
                total += passes * t
                by_bound[which] += passes * t
    return total, max(by_bound, key=by_bound.get)
