"""Operations, bytes and parameter counts of a dense hybrid of Gated
DeltaNet mixers and full attention under the reordered norm (the catalog
row `Olmo-Hybrid-7B`), from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the roofline is
`benchlib.flops`'s and the delta rule's operations are
`benchlib.flops_kda_moe`'s (one chunked algorithm, whichever gate).

A configuration that holds a chip's share says so itself: its top-level
`num_attention_heads`, `num_key_value_heads`, `linear_num_key_heads`,
`linear_num_value_heads`, `vocab_size` and `num_hidden_layers` are what is
held here, `reduced.<key>.published` is the model's own, `head_dim` is the
published hidden_size over the published head count, and the layers held
are the first `num_hidden_layers` of `layer_types` (kept whole) from
`share.layer_offset` on.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters the
token passes, plus causal attention (QK^T and PV, both 128 wide), plus the
delta rule (`flops_kda_moe.delta_flops_per_token` at keys of 96 beside
values of 192). Recomputation is not counted.

The delta rule's least bytes differ from KDA's by the gate: q, k, v read
in the compute dtype, ONE log-decay and one beta a head read and the
output written in float32, once a pass.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchlib import flops
from benchlib.flops_kda_moe import (delta_flops_per_token,
                                    delta_passes_per_step)

KIND_OF = {"linear_attention": "d", "full_attention": "a"}


def layer_pattern(cfg: Dict[str, Any]) -> str:
    """One character a held layer, as `TransformerConfig.layer_pattern`
    names them: `d` a Gated DeltaNet mixer, `a` full attention, each then
    a dense MLP, under the reordered norm."""
    first = cfg.get("share", {}).get("layer_offset", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types holds fewer layers than are held")
    return "".join(KIND_OF[kind] for kind in kinds)


def layers_of(cfg: Dict[str, Any], kinds: str) -> int:
    return sum(layer_pattern(cfg).count(kind) for kind in kinds)


def mixer_matmul_params(cfg: Dict[str, Any]) -> int:
    """q, k (keys' width), v, the output gate and W_o (values' width), the
    decay's and beta's projections."""
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2 * d * hk * dk + 3 * d * hv * dv + 2 * d * hv


def mixer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a mixer without the norm on its output:
    the matmuls, three convolutions, A and dt, the head norm's gain."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return (mixer_matmul_params(cfg)
            + (2 * hk * dk + hv * dv) * cfg["linear_conv_kernel_dim"]
            + 2 * hv + dv)


def attention_matmul_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def attention_params(cfg: Dict[str, Any]) -> int:
    """With the QK-norm's gains over the whole held projections."""
    hd = cfg["head_dim"]
    return attention_matmul_params(cfg) + hd * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Embedding, head and final norm, and every held layer with the two
    norms on its sublayers' outputs."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    each = {"d": mixer_params(cfg), "a": attention_params(cfg)}
    return 2 * v * d + d + sum(each[kind] + mlp_params(cfg) + 2 * d
                               for kind in layer_pattern(cfg))


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    return (layers_of(cfg, "d") * mixer_matmul_params(cfg)
            + layers_of(cfg, "a") * attention_matmul_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward QK^T and PV, backward twice that, both 128 wide, over the
    causal triangle, in the full-attention layers."""
    per_seq = flops.attention_matmul_flops(
        1, cfg["num_attention_heads"], seq, cfg["head_dim"], 6)
    return layers_of(cfg, "a") * per_seq / seq


# ---- the delta rule ------------------------------------------------------


def delta_call(cfg: Dict[str, Any], tokens: int, chunk: int,
               remat: bool, implementation: str) -> Dict[str, Any]:
    """The record's `static.delta_call`: what every mixer's delta rule is
    called with in a step."""
    return {"tokens": tokens, "layers": layers_of(cfg, "d"),
            "heads": cfg["linear_num_value_heads"],
            "d_k": cfg["linear_key_head_dim"],
            "d_v": cfg["linear_value_head_dim"], "chunk": chunk,
            "remat": remat, "implementation": implementation}


def delta_bytes_per_token(call: Dict[str, Any], itemsize: int = 2) -> float:
    """q, k, v in the compute dtype; a log-decay and a beta a head and
    the output in float32."""
    dk, dv = call["d_k"], call["d_v"]
    return call["heads"] * (itemsize * (2 * dk + dv) + 4.0 * (2 + dv))


def delta_least_time_s(call: Dict[str, Any], steps: int,
                       peaks: Dict[str, Any]) -> Tuple[float, str]:
    """Roofline of every mixer's delta rule over `steps` steps, from the
    model's shapes alone: per pass the larger of the chunked algorithm's
    FLOPs over peak and its least bytes over peak (and which of the two
    bounds); per layer and step a forward, under remat the forward again,
    and a backward of two forwards."""
    t, bound = flops.least_time_s(
        call["tokens"] * delta_flops_per_token(call),
        call["tokens"] * delta_bytes_per_token(call), peaks)
    return steps * call["layers"] * delta_passes_per_step(
        call["remat"]) * t, bound


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          chunk: int) -> float:
    call = delta_call(cfg, 1, chunk, False, "")
    return (6.0 * matmul_params_per_token(cfg)
            + attention_train_flops_per_token(cfg, seq)
            + 3.0 * call["layers"] * delta_flops_per_token(call))
