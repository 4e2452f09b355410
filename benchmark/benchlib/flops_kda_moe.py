"""Operations, bytes and parameter counts of a hybrid of Kimi Delta
Attention layers, latent attention without a query latent and experts
under a group-limited router (the catalog row `Ling-3.0-flash-VL`'s
language model), from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the roofline is
`benchlib.flops`'s.

A configuration that holds a chip's share says so itself: its top-level
`num_attention_heads` (the KDA and the MLA heads alike), `num_experts`,
`vocab_size`, `num_hidden_layers` and `first_k_dense_replace` are what is
held here, `reduced.<key>.published` is the model's own
(`reduced.num_experts.published` is the router's width), and
`share.layer_offset` is the published index of the first layer held: a
layer is latent attention where its published index + 1 is a multiple of
`layer_group_size`, else KDA, and carries the dense MLP while it is among
the first `first_k_dense_replace` held here, else experts.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters the
token passes (the routed experts THIS CHIP computed for it,
`routed_slots_per_token` from the program's counter), plus causal
attention (QK^T at 192 columns, PV at 128), plus the delta rule.
Recomputation is not counted.

**The delta rule** (`delta_flops_per_token`): the gated delta rule in
chunks of C steps, per token and head, forward, counted as the chunked
algorithm needs them whatever implements it: the key-key and query-key
products over the causal half of a chunk (2 x C x D_k), the inverse of
one unit lower-triangular [C, C] matrix by substitution (2 C^2 / 3), that
inverse times [V | K] (C (D_k + D_v)), the two products with the state
entering the chunk and the chunk's own contribution to it (6 D_k D_v), the
query-key block times the corrected values (C D_v). The L2 norms, the
decays and the running sums are elementwise and count nothing; their
bytes are the op's least traffic: q, k, v read in the compute dtype, the
log-decays and beta read and the output written in float32, once a pass.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchlib import flops


def router_experts(cfg: Dict[str, Any]) -> int:
    """The router's width: the published expert count."""
    cut = cfg.get("reduced", {}).get("num_experts")
    return int(cut["published"] if cut else cfg["num_experts"])


def layer_pattern(cfg: Dict[str, Any]) -> str:
    """One character a held layer, as `TransformerConfig.layer_pattern`
    names them: `k` / `K` KDA, `l` / `L` latent attention; lower case
    with the dense MLP, upper case with experts."""
    first = cfg.get("share", {}).get("layer_offset", 0)
    out = ""
    for j in range(cfg["num_hidden_layers"]):
        kind = "l" if (first + j + 1) % cfg["layer_group_size"] == 0 \
            else "k"
        out += kind if j < cfg["first_k_dense_replace"] else kind.upper()
    return out


def layers_of(cfg: Dict[str, Any], kinds: str) -> int:
    return sum(layer_pattern(cfg).count(kind) for kind in kinds)


def qk_head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def kda_matmul_params(cfg: Dict[str, Any]) -> int:
    """q, k, v, the decay's projection, beta's and the output gate's,
    W_o."""
    d, h, hd = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    return 4 * d * h * hd + 2 * d * h + h * hd * d


def kda_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a KDA sublayer without its norm: the
    matmuls, three convolutions, A and the decay's bias, the head norm."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return (kda_matmul_params(cfg) + 3 * h * hd * cfg["short_conv_kernel_size"]
            + h + h * hd + hd)


def mla_matmul_params(cfg: Dict[str, Any]) -> int:
    d, h, kvr = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    return (d * h * qk_head_dim(cfg) + d * (kvr + cfg["qk_rope_head_dim"])
            + kvr * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def mla_params(cfg: Dict[str, Any]) -> int:
    """With the key/value latent's norm and the QK-norm's two gains."""
    qk = 2 * qk_head_dim(cfg) if cfg.get("use_qk_norm") else 0
    return mla_matmul_params(cfg) + cfg["kv_lora_rank"] + qk


def dense_mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * router_experts(cfg)


def expert_layer_params(cfg: Dict[str, Any]) -> int:
    """As held here; the choice bias is a buffer and not counted."""
    return (router_params(cfg) + shared_params(cfg)
            + cfg["num_experts"] * expert_params(cfg))


def total_params(cfg: Dict[str, Any]) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    each = {"k": kda_params(cfg) + dense_mlp_params(cfg),
            "K": kda_params(cfg) + expert_layer_params(cfg),
            "l": mla_params(cfg) + dense_mlp_params(cfg),
            "L": mla_params(cfg) + expert_layer_params(cfg)}
    return 2 * v * d + d + sum(each[kind] + 2 * d
                               for kind in layer_pattern(cfg))


def matmul_params_per_token(cfg: Dict[str, Any],
                            routed_slots_per_token: float) -> float:
    """Matmul parameters one token passes on this chip."""
    return (layers_of(cfg, "kK") * kda_matmul_params(cfg)
            + layers_of(cfg, "lL") * mla_matmul_params(cfg)
            + layers_of(cfg, "kl") * dense_mlp_params(cfg)
            + layers_of(cfg, "KL") * (router_params(cfg)
                                      + shared_params(cfg))
            + routed_slots_per_token * expert_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward QK^T over the 192 query/key columns and PV over the 128
    value columns, backward twice that, over the causal triangle."""
    per_seq = sum(flops.attention_matmul_flops(
        1, cfg["num_attention_heads"], seq, width, 3)
        for width in (qk_head_dim(cfg), cfg["v_head_dim"]))
    return layers_of(cfg, "lL") * per_seq / seq


def attention_call_head_dim(cfg: Dict[str, Any]) -> float:
    """The one width `flops.attention_call_flops` takes, for a call whose
    keys are 192 wide and whose values 128: their mean. Exact for the
    forward call (one product of each) and for every call's bytes (q and
    o, k and v); 3.8% under for the fused backward (three products at
    192, two at 128)."""
    return (qk_head_dim(cfg) + cfg["v_head_dim"]) / 2


# ---- the delta rule ------------------------------------------------------


def delta_flops_per_token(call: Dict[str, Any]) -> float:
    """One KDA layer's delta rule, forward, per token (module docstring);
    `call`: heads, d_k, d_v, chunk."""
    c, dk, dv = call["chunk"], call["d_k"], call["d_v"]
    per_head = (2.0 * c * dk + 2.0 * c * c / 3 + c * (dk + dv)
                + 6.0 * dk * dv + c * dv)
    return call["heads"] * per_head


def delta_bytes_per_token(call: Dict[str, Any], itemsize: int = 2) -> float:
    dk, dv = call["d_k"], call["d_v"]
    return call["heads"] * (itemsize * (2 * dk + dv)
                            + 4.0 * (dk + 1 + dv))


def delta_passes_per_step(remat: bool) -> int:
    """Forward, under remat the forward again, and the backward at twice
    a forward: in forwards."""
    return (2 if remat else 1) + 2


def delta_least_time_s(call: Dict[str, Any], steps: int,
                       peaks: Dict[str, Any]) -> Tuple[float, str]:
    """Roofline of every KDA layer's delta rule over `steps` steps:
    `call` holds `tokens` (a step's), `layers`, `heads`, `d_k`, `d_v`,
    `chunk` and `remat`; per pass the larger of FLOPs over peak and bytes
    over peak, and which of the two bounds."""
    t, bound = flops.least_time_s(
        call["tokens"] * delta_flops_per_token(call),
        call["tokens"] * delta_bytes_per_token(call), peaks)
    return steps * call["layers"] * delta_passes_per_step(
        call["remat"]) * t, bound


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          routed_slots_per_token: float,
                          chunk: int) -> float:
    call = {"heads": cfg["num_attention_heads"], "d_k": cfg["head_dim"],
            "d_v": cfg["head_dim"], "chunk": chunk}
    return (6.0 * matmul_params_per_token(cfg, routed_slots_per_token)
            + attention_train_flops_per_token(cfg, seq)
            + 3.0 * layers_of(cfg, "kK") * delta_flops_per_token(call))
