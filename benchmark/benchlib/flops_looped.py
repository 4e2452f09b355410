"""Operations and parameter counts of a looped decoder (the catalog row
`Ouro-2.6B`: one stack of dense layers under a sandwich norm run
`total_ut_steps` times through the same weights, a head and an exit gate
after every pass), from its published `config.json` keys as the
configuration file holds them. Pure Python, no JAX; the layer's matmuls,
attention's products and the roofline are `benchlib.flops`'s.

Model FLOPs are what the forward and backward passes REQUIRE for one
token: 2 per multiply-add, 3x the forward. Every layer's products, the
head's and the gate's run once a PASS, and attention's QK^T and PV over
the causal pairs once a pass and layer, so all of them count
`total_ut_steps` times: a count from the parameters alone
(`flops.train_flops_per_token`) would read a quarter of the truth.
Recomputation is not counted. The parameters are counted once: the passes
share them.
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import flops


def passes(cfg: Dict[str, Any]) -> int:
    return int(cfg["total_ut_steps"])


def layer_params(cfg: Dict[str, Any]) -> int:
    """A layer's matmuls and the sandwich norm's four gains."""
    return flops.layer_matmul_params(cfg) + 4 * cfg["hidden_size"]


def gate_params(cfg: Dict[str, Any]) -> int:
    """The exit gate: a gain over the hidden state and a bias."""
    return cfg["hidden_size"] + 1


def total_params(cfg: Dict[str, Any]) -> int:
    """Every stored parameter: the embedding, the untied head, the held
    layers, the final norm, the exit gate."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the published head is untied")
    return (2 * v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d
            + gate_params(cfg))


def published_params(cfg: Dict[str, Any]) -> int:
    """`total_params` at the published depth and vocabulary."""
    published = dict(cfg)
    for key, cut in cfg.get("reduced", {}).items():
        published[key] = cut["published"]
    return total_params(published)


def matmul_params_per_pass(cfg: Dict[str, Any]) -> int:
    """The matmul parameters a token passes in ONE pass: the layers', the
    head's and the gate's gain."""
    return flops.matmul_params(cfg) + cfg["hidden_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """6 x the matmul parameters of a pass + causal attention over the
    layers, both times the passes, per trained token."""
    return passes(cfg) * (
        6.0 * matmul_params_per_pass(cfg)
        + flops.attention_train_flops_per_token(cfg, seq))


def attention_call(cfg: Dict[str, Any], batch: int, seq: int
                   ) -> Dict[str, Any]:
    """The record's `static.attention_call`, as the existing readers take
    it (`attn_kernel_roofline` counts the trace's events, so the
    `total_ut_steps x num_hidden_layers` calls a step count themselves)."""
    return {"batch": batch, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": seq,
            "head_dim": flops.head_dim(cfg)}


def loop_call(cfg: Dict[str, Any], tokens: int, form: str
              ) -> Dict[str, Any]:
    """The record's `static.loop_call`: the passes, the layers each runs,
    the tokens a step, the loop's form in the program, and the layer
    applications (and attention kernel calls each way) a step."""
    r, n = passes(cfg), cfg["num_hidden_layers"]
    return {"passes": r, "layers": n, "tokens": tokens, "form": form,
            "layer_applications": r * n, "heads_a_step": r}
