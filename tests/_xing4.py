"""What `tests/test_xing4_reference.py` and `tests/test_xing4_faults.py`
share: the small configuration, its published keys, seeded stand-in
weights, and the system's and the reference's programs, one compile each
(`tests/_programs.py`)."""

import functools
import os
import sys

import jax
import numpy as np

from ray_tpu.models import TransformerConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402

ref = load_module("reference", "xing4_f32")
faults = load_module("reference", "xing4_faults")
job = load_module("jobs", "train_lm_mhc_moe")

RTOL = 1e-4
SEQ = 48
E, K, HEADS, STREAMS = 16, 4, 4, 4
YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
INIT = {"embed_std": 1.0, "q_latent_gain": 2.0, "kv_latent_gain_std": 0.3,
        "balance_rounds": 0, "router_bias_max": 0.08, "norm_gain_std": 0.3,
        "hc_alpha": 1.0, "hc_phi_gain": 1.0, "hc_bias_std": 1.0,
        "hc_res_spread": 0.5}


def config(held=0, offset=0, heads=HEADS, **kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=3, n_heads=heads, d_ff=32,
        max_seq_len=SEQ, dtype="float32", rope_theta=1e4, norm_eps=1e-6,
        loss_chunk=0, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=20, moe_experts=E, moe_top_k=K,
        moe_norm_topk=True, moe_scoring="sigmoid", moe_routed_scale=2.0,
        moe_shared_experts=1, moe_dense_layers=1, moe_dense_ff=96,
        moe_experts_held=held, moe_expert_offset=offset, moe_aux_coeff=0.0,
        residual_streams=STREAMS, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_res_clamp=30.0, rope_yarn_factor=64.0,
        rope_yarn_original_len=16, rope_yarn_attention_factor=1.0,
        rope_yarn_mscale_all_dim=1.0, remat=True)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"hidden_act": "silu", "attention_bias": False,
           "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_heads,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
           "q_lora_rank": cfg.q_lora_rank,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk, "n_group": 1,
           "topk_group": 1, "topk_method": "noaux_tc",
           "scoring_func": "sigmoid",
           "routed_scaling_factor": cfg.moe_routed_scale,
           "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
           "rope_scaling": dict(YARN), "hc_mult": cfg.residual_streams,
           "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
           "hc_eps": cfg.hc_eps,
           "mhc_h_res_clamp_min": -cfg.hc_res_clamp,
           "mhc_h_res_clamp_max": cfg.hc_res_clamp}
    out.update(over)
    return out


@functools.lru_cache(maxsize=None)
def weights(cfg, seed):
    """The job's stand-in weights (every gain off 1, maps that move with
    the token, a choice bias that is not zero) with router logits of order
    1 as at the published width."""
    params = job.init_params(jax.random.key(seed), cfg, INIT)
    params["layers"]["w_router"] = params["layers"]["w_router"] * 6.0
    return params


def share_of(params, held, offset, heads=None):
    """The leaves a chip holding experts offset..offset+held and the heads
    `heads` = (lo, hi) keeps."""
    out = dict(params)
    lay = dict(params["layers"])
    for name in ("w_moe_gateup", "w_moe_down"):
        lay[name] = lay[name][:, offset:offset + held]
    out["layers"] = lay
    if heads is not None:
        lo, hi = heads
        for run in ("dense_layers", "layers"):
            lay = dict(out[run])
            lay["wq_b"] = lay["wq_b"][:, :, lo:hi]
            lay["wkv_b"] = lay["wkv_b"][:, :, lo:hi]
            lay["wo"] = lay["wo"][:, lo:hi]
            out[run] = lay
    return out


def batch(cfg, seed, rows=2):
    return jax.random.randint(jax.random.key(100 + seed),
                              (rows, SEQ + 1), 0, cfg.vocab_size)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@functools.lru_cache(maxsize=None)
def system(cfg):
    """`.forward(params, tokens)` -> (logits, maps [sublayers, B, T, 24])
    from one forward pass, under one `jax.jit`."""
    return jax.jit(lambda p, x: job.system_forward(p, x, cfg, None))


def reference(cfg):
    """`.forward(w, tokens)` -> (logits, chosen, maps) and
    `.loss_and_grads(w, tokens)`, each under one `jax.jit`."""
    return _programs.reference(ref, published, cfg, with_routing=True,
                               with_maps=True)
