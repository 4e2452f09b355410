"""Operations, bytes and parameter counts of a hybrid of Mamba-2 mixers,
attention and latent expert layers (`model_type: nemotron_h`), from its
published `config.json` keys as the configuration file holds them. Pure
Python, no JAX; the attention kernels' calls and the roofline are
`benchlib.flops`'s, the grouped matmul's operations and bytes
`benchlib.flops_moe`'s.

A configuration that holds a chip's share says so itself: its top-level
`mamba_num_heads`, `n_groups`, `num_attention_heads`,
`num_key_value_heads`, `n_routed_experts` and `vocab_size` are what is
held here, and `reduced.<key>.published` is the model's own
(`reduced.n_routed_experts.published` is the router's width: all E
experts are scored, k a token chosen).

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters the
token passes (a mixer's two projections; attention's four; in an expert
layer the router, the two latent projections, the shared expert and the
routed experts THIS CHIP computed for it, `routed_slots_per_token` from
the program's counter: about k x held / E, not k; the output head), plus
causal attention, plus the mixers' scans. Recomputation is not counted.

**The scan** (`scan_flops_per_token`): the selective scan in chunks of Q
(`chunk_size`), per token and mixer, forward: the chunk's C.B scores over
the causal half of its Q x Q block (2·G·N·(Q+1)/2), those weights times x
(2·H·P·(Q+1)/2), the chunk's contribution to the state and the state's to
the outputs (2·H·P·N each). The convolution, the decays and the norm are
elementwise and not counted as operations; their bytes are the scan's
least traffic: x, B, C and dt read and y written once a pass.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchlib import flops, flops_moe


def router_experts(cfg: Dict[str, Any]) -> int:
    """The router's width: the published expert count."""
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return int(cut["published"] if cut else cfg["n_routed_experts"])


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


def mixer_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def mixer_conv_dim(cfg: Dict[str, Any]) -> int:
    return mixer_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mixer_matmul_params(cfg: Dict[str, Any]) -> int:
    """in_proj ([z | xBC | dt]) and out_proj."""
    d, inner = cfg["hidden_size"], mixer_inner(cfg)
    return d * (inner + mixer_conv_dim(cfg) + cfg["mamba_num_heads"]) \
        + inner * d


def mixer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a mixer without its norm: the two
    projections, the convolution with its bias, dt_bias, A_log, D, the
    gated norm's gain."""
    conv = mixer_conv_dim(cfg)
    return (mixer_matmul_params(cfg) + conv * cfg["conv_kernel"] + conv
            + 3 * cfg["mamba_num_heads"] + mixer_inner(cfg))


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: latent -> width -> latent, no gate."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Dict[str, Any]) -> int:
    return cfg["n_shared_experts"] * 2 * cfg["hidden_size"] \
        * cfg["moe_shared_expert_intermediate_size"]


def latent_params(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_latent_size"]


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * router_experts(cfg)


def expert_layer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of an expert layer as held here, without
    its norm; the choice bias is a buffer and not counted."""
    return (router_params(cfg) + latent_params(cfg) + shared_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg))


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    each = {"M": mixer_params(cfg), "*": attention_params(cfg),
            "E": expert_layer_params(cfg)}
    return v * d + sum(each[kind] + d for kind in
                       cfg["hybrid_override_pattern"]) + d + head


def matmul_params_per_token(cfg: Dict[str, Any],
                            routed_slots_per_token: float) -> float:
    """Matmul parameters one token passes on this chip.
    `routed_slots_per_token`: the token-slots this chip's experts
    computed, summed over the expert layers, per token."""
    per_expert_layer = router_params(cfg) + latent_params(cfg) \
        + shared_params(cfg)
    return (layers_of(cfg, "M") * mixer_matmul_params(cfg)
            + layers_of(cfg, "*") * attention_params(cfg)
            + layers_of(cfg, "E") * per_expert_layer
            + routed_slots_per_token * expert_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    per_seq = flops.attention_matmul_flops(
        1, cfg["num_attention_heads"], seq, cfg["head_dim"], 6)
    return layers_of(cfg, "*") * per_seq / seq


# ---- the selective scan ---------------------------------------------------


def scan_flops_per_token(cfg: Dict[str, Any]) -> float:
    """One mixer's scan, forward, per token (module docstring)."""
    q = cfg["chunk_size"]
    hp = mixer_inner(cfg)
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return (2.0 * gn + 2.0 * hp) * (q + 1) / 2 \
        + 4.0 * hp * cfg["ssm_state_size"]


def scan_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> float:
    """Least HBM traffic of one mixer's scan, forward, per token: x, B
    and C read in the compute dtype, dt read and y written in float32."""
    hp = mixer_inner(cfg)
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return itemsize * (hp + 2 * gn) + 4.0 * (cfg["mamba_num_heads"] + hp)


def scan_passes_per_step(remat: bool) -> int:
    """Forward, under remat the forward again, and the backward at twice
    a forward (each product has two transposes): in forwards."""
    return (2 if remat else 1) + 2


def scan_least_time_s(cfg: Dict[str, Any], tokens: int, steps: int,
                      remat: bool, peaks: Dict[str, Any]
                      ) -> Tuple[float, str]:
    """Roofline of every mixer's scan over `steps` steps of `tokens`
    tokens: per pass the larger of FLOPs over peak and bytes over peak,
    and which of the two bounds."""
    t, bound = flops.least_time_s(tokens * scan_flops_per_token(cfg),
                                  tokens * scan_bytes_per_token(cfg), peaks)
    return steps * layers_of(cfg, "M") * scan_passes_per_step(remat) * t, \
        bound


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          routed_slots_per_token: float) -> float:
    return (6.0 * matmul_params_per_token(cfg, routed_slots_per_token)
            + attention_train_flops_per_token(cfg, seq)
            + 3.0 * layers_of(cfg, "M") * scan_flops_per_token(cfg))


# ---- the held experts' grouped matmuls ---------------------------------


def held_experts_least_time_s(cfg: Dict[str, Any],
                              held_rows: Sequence[Sequence[int]],
                              remat: bool, peaks: Dict[str, Any]
                              ) -> Tuple[float, str]:
    """Roofline of the held experts' grouped matmuls over some steps:
    `held_rows[step][layer]` the token-slots the held experts of that
    layer received in that step (the program's counter). Two calls a
    pass, latent -> width and width -> latent; per call the larger of
    FLOPs over peak and bytes over peak, the passes `flops_moe`'s
    (forward, under remat the forward again, the backward's two products
    per matmul), the weights of the held experts only; and which bound
    holds for most of the time."""
    held = cfg["n_routed_experts"]
    r, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    passes = (2 if remat else 1) + 2
    total = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for step in held_rows:
        for rows in step:
            for k, n in ((r, f), (f, r)):
                t, which = flops.least_time_s(
                    flops_moe.grouped_matmul_flops(rows, k, n),
                    flops_moe.grouped_matmul_bytes(rows, k, n, held), peaks)
                total += passes * t
                by_bound[which] += passes * t
    return total, max(by_bound, key=by_bound.get)
