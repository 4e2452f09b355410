"""What `tests/test_mellum2_reference.py` (the model against its
reference, YaRN, the window) and `tests/test_mellum2_exchange.py` (the
exchange, the scopes, the faults) both read: the small configuration, its
published keys, the weights, the meshes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_json, load_module  # noqa: E402

ref = load_module("reference", "mellum2_f32")
faults = load_module("reference", "mellum2_faults")
job = load_module("jobs", "train_lm_ep_moe")

RTOL = 1e-4
E = 8
VOCAB = 128
WINDOW = 16
ROPE = {"full_attention": {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
    "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
RULES = ShardingRules().replace(expert="fsdp", expert_embed=None)


def config(k=2, **kw):
    base = dict(
        vocab_size=VOCAB, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        attn_head_dim=16, d_ff=32, max_seq_len=64, dtype="float32",
        rope_theta=5e5, norm_eps=1e-6, loss_chunk=0, qk_norm=True,
        qk_norm_per_head=True, moe_experts=E, moe_top_k=k,
        moe_norm_topk=True, moe_scoring="softmax", moe_aux_coeff=0.0,
        layer_pattern="WWWL", attn_window=WINDOW, rope_yarn_factor=16.0,
        rope_yarn_original_len=32,
        rope_yarn_attention_factor=1.2772588722239782)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    kinds = {"W": "sliding_attention", "L": "full_attention"}
    out = {"hidden_act": "silu", "attention_bias": False,
           "hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.kv_heads,
           "num_hidden_layers": cfg.n_layers,
           "layer_types": [kinds[c] for c in cfg.layer_pattern],
           "mlp_layer_types": ["sparse"] * cfg.n_layers,
           "num_experts": cfg.moe_experts,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk,
           "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.attn_window,
           "rope_parameters": ROPE}
    out.update(over)
    return out


def weights(cfg, seed):
    """Random weights with every gain off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place or left out), heads of unlike scale (a
    QK-norm over the whole projection then differs from one a head) and
    router logits of order 1 as at the published width."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))
    for block in params["runs"]:
        for lay in block:
            n = lay["wq"].shape[0]
            for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
                lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                          lay[name].shape)
            lay["wq"] = lay["wq"] * jnp.exp(0.5 * jax.random.normal(
                next(keys), (n, 1, cfg.n_heads, 1)))
            lay["wkv"] = lay["wkv"] * jnp.exp(0.5 * jax.random.normal(
                next(keys), (n, 1, 1, cfg.kv_heads, 1)))
            lay["w_router"] = lay["w_router"] * 6.0
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    return params


def mesh_of(devices):
    return None if devices == 1 else make_mesh(
        MeshConfig(data=1, fsdp=devices), devices=jax.devices()[:devices])


def tokens_of(seed, rows=4, length=64):
    return jax.random.randint(jax.random.key(100 + seed), (rows, length + 1),
                              0, VOCAB)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert scale > 0, what
    err = np.abs(got - want).max() / scale
    assert err <= rtol, f"{what}: {err:.2e} of the largest entry"
