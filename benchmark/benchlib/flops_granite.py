"""Operations, bytes and parameter counts of a Granite 4.0-H hybrid
(`model_type: granitemoehybrid` with no routed experts): every layer a
Mamba-2 mixer or GQA attention without positions FOLLOWED by a gated MLP,
a tied embedding, from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the roofline is
`benchlib.flops`'s.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward, over the matmul parameters the
token passes (a mixer's two projections or attention's four, the MLP's
three, the tied head's product), plus attention over the pairs the
DOCUMENT mask needs (`attention_train_flops`: the step's own counter, the
sum over documents of n (n + 1) / 2, not the causal triangle of the whole
sequence), plus the mixers' scans. Recomputation is not counted.

**The scan** (`scan_flops_per_token`), whatever implements it: the
selective scan in chunks of Q (`mamba_chunk_size`), per token and mixer,
forward: the chunk's C.B scores over the causal half of its Q x Q block
(2·G·N·(Q+1)/2), those weights times x (2·H·P·(Q+1)/2), the chunk's
contribution to the state and the state's to the outputs (2·H·P·N each).
Document boundaries take pairs away inside a chunk and are not counted
off: a chunk's products run whole. The convolution, the decays and the
norm are elementwise and not counted as operations; the scan's least
bytes are x, B, C and dt read and y written once a pass
(`scan_bytes_per_token`). The passes that run (`scan_passes_per_step`):
the forward, under remat the forward again (the scan's residuals are not
among what `Transformer._remat` keeps), and the backward at two forwards.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchlib import flops

KINDS = {"mamba": "n", "attention": "l"}


def layer_pattern(cfg: Dict[str, Any]) -> str:
    """`layer_types` as the program's `layer_pattern`: `n` a Mamba-2
    mixer then an MLP, `l` attention then an MLP."""
    return "".join(KINDS[kind] for kind in cfg["layer_types"])


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    return list(cfg["layer_types"]).count(kind)


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mixer_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def mixer_conv_dim(cfg: Dict[str, Any]) -> int:
    return mixer_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mixer_matmul_params(cfg: Dict[str, Any]) -> int:
    """in_proj ([z | xBC | dt]) and out_proj."""
    d, inner = cfg["hidden_size"], mixer_inner(cfg)
    return d * (inner + mixer_conv_dim(cfg) + cfg["mamba_n_heads"]) \
        + inner * d


def mixer_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter of a mixer without its norm: the two
    projections, the convolution with its bias, dt_bias, A_log, D, the
    gated norm's gain."""
    conv = mixer_conv_dim(cfg)
    return (mixer_matmul_params(cfg) + conv * cfg["mamba_d_conv"] + conv
            + 3 * cfg["mamba_n_heads"] + mixer_inner(cfg))


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter: the tied embedding once, each layer's
    mixer, MLP and two norm gains, the final norm."""
    d = cfg["hidden_size"]
    each = {"mamba": mixer_params(cfg), "attention": attention_params(cfg)}
    return cfg["vocab_size"] * d + sum(
        each[kind] + mlp_params(cfg) + 2 * d
        for kind in cfg["layer_types"]) + d


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    return (layers_of(cfg, "mamba") * mixer_matmul_params(cfg)
            + layers_of(cfg, "attention") * attention_params(cfg)
            + len(cfg["layer_types"]) * mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_train_flops(cfg: Dict[str, Any], pairs_needed: float) -> float:
    """Every attention layer's required operations over `pairs_needed`
    (query, key) pairs a head: forward QK^T and PV, backward twice that,
    2 · head_dim a pair and product."""
    return layers_of(cfg, "attention") * 6 * 2.0 * head_dim(cfg) \
        * cfg["num_attention_heads"] * pairs_needed


# ---- the selective scan ---------------------------------------------------


def scan_flops_per_token(cfg: Dict[str, Any]) -> float:
    """One mixer's scan, forward, per token (module docstring)."""
    q = cfg["mamba_chunk_size"]
    hp = mixer_inner(cfg)
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (2.0 * gn + 2.0 * hp) * (q + 1) / 2 \
        + 4.0 * hp * cfg["mamba_d_state"]


def scan_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> float:
    """Least HBM traffic of one mixer's scan, forward, per token: x, B
    and C read in the compute dtype, dt read and y written in float32."""
    hp = mixer_inner(cfg)
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return itemsize * (hp + 2 * gn) + 4.0 * (cfg["mamba_n_heads"] + hp)


def scan_passes_per_step(remat: bool) -> int:
    """In forwards: the forward, under remat the forward again, and the
    backward at twice a forward (each product has two transposes)."""
    return (2 if remat else 1) + 2


def scan_least_time_s(cfg: Dict[str, Any], tokens: int, steps: int,
                      remat: bool, peaks: Dict[str, Any]
                      ) -> Tuple[float, str]:
    """Roofline of every mixer's scan over `steps` steps of `tokens`
    tokens: per pass the larger of FLOPs over peak and bytes over peak,
    and which of the two bounds."""
    t, bound = flops.least_time_s(tokens * scan_flops_per_token(cfg),
                                  tokens * scan_bytes_per_token(cfg), peaks)
    return steps * layers_of(cfg, "mamba") * scan_passes_per_step(remat) \
        * t, bound


def train_flops_per_token(cfg: Dict[str, Any], tokens_per_step: int,
                          pairs_needed_per_step: float) -> float:
    """`pairs_needed_per_step`: the window's mean of the step's counter
    `packed_attn_pairs_needed`."""
    return (6.0 * matmul_params_per_token(cfg)
            + attention_train_flops(cfg, pairs_needed_per_step)
            / tokens_per_step
            + 3.0 * layers_of(cfg, "mamba") * scan_flops_per_token(cfg))
