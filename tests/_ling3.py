"""What `tests/test_ling3_reference.py` (the model against its reference,
the faults, what is refused) and `tests/test_ling3_ops.py` (the delta rule
against the recurrence, the router against a loop, the shares adding up)
both read: the small configuration, its published keys, the weights, a
share of the experts and of the heads."""

import os
import sys

import jax
import numpy as np

from ray_tpu.models import Transformer, TransformerConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402

ref = load_module("reference", "ling3_f32")
faults = load_module("reference", "ling3_faults")
job = load_module("jobs", "train_lm_kda_moe")

RTOL = 1e-4
SEQ = 80          # two chunks of 32 and a half
E, K, GROUPS, KEPT = 16, 3, 4, 2
PATTERN = "kKKLK"
HEADS, HD = 4, 8


def config(held=0, offset=0, heads=HEADS, **kw):
    base = dict(
        vocab_size=128, d_model=48, n_layers=len(PATTERN),
        layer_pattern=PATTERN, n_heads=heads, n_kv_heads=heads,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, qk_norm=True, rope_theta=1e4, d_ff=20,
        moe_dense_ff=64, max_seq_len=SEQ, dtype="float32", loss_chunk=0,
        norm_eps=1e-6, kda_heads=heads, kda_head_dim=HD, kda_chunk=32,
        moe_experts=E, moe_top_k=K, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_routed_scale=2.5, moe_groups=GROUPS,
        moe_topk_groups=KEPT, moe_shared_experts=1, moe_shared_ff=20,
        moe_experts_held=held, moe_expert_offset=offset, moe_aux_coeff=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
           "use_qk_norm": cfg.qk_norm, "head_dim": cfg.kda_head_dim,
           "kda_lower_bound": cfg.kda_gate_lower,
           "n_group": cfg.moe_groups, "topk_group": cfg.moe_topk_groups,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk,
           "routed_scaling_factor": cfg.moe_routed_scale}
    out.update(over)
    return out


def reference(cfg):
    """The reference at `cfg`'s published keys under `jax.jit`
    (`tests/_programs.reference`): `.forward(w, tokens)` -> (logits,
    chosen)."""
    return _programs.reference(ref, published, cfg, with_routing=True)


def subs_of(params):
    return [sub for run in params["runs"] for sub in run]


GAINS = ("kda_norm", "attn_norm", "mlp_norm", "kda_out_norm", "kv_a_norm",
         "q_norm", "k_norm")


def weights(cfg, seed):
    """Random weights with every gain off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place or left out), a decay bias that is
    not zero, router logits of order 1 as at the published width, and a
    choice bias that is not zero."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))
    for sub in subs_of(params):
        for name in GAINS:
            if name in sub:
                sub[name] = 1.0 + 0.3 * jax.random.normal(
                    next(keys), sub[name].shape)
        if "w_router" in sub:
            sub["w_router"] = sub["w_router"] * 6.0
            sub["router_bias"] = 0.2 * jax.random.normal(
                next(keys), sub["router_bias"].shape)
        if "kda_a_bias" in sub:
            sub["kda_a_bias"] = jax.random.normal(
                next(keys), sub["kda_a_bias"].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    return params


def expert_share(params, held, offset):
    """The leaves a chip holding experts offset..offset+held keeps."""
    runs = [[dict(sub, **{name: sub[name][:, offset:offset + held]
                          for name in ("w_moe_gateup", "w_moe_down")
                          if name in sub}) for sub in run]
            for run in params["runs"]]
    return dict(params, runs=runs)


# a head's leaves and the axis its heads lie on (after the layers' axis)
HEAD_AXES = {"w_kda_qkv": 3, "w_kda_a": 2, "kda_a_bias": 1, "kda_A_log": 1,
             "w_kda_bg": 3, "w_kda_out": 1, "wq": 2, "wkv_b": 2, "wo": 1}


def head_share(params, lo, hi, hd=HD):
    """The leaves a chip holding heads lo..hi of every layer keeps."""
    def cut(name, leaf):
        if name == "kda_conv":     # channels: heads x head width
            return leaf[:, :, lo * hd:hi * hd]
        if name in HEAD_AXES:
            return jax.lax.slice_in_dim(leaf, lo, hi, axis=HEAD_AXES[name])
        return leaf
    runs = [[{name: cut(name, leaf) for name, leaf in sub.items()}
             for sub in run] for run in params["runs"]]
    return dict(params, runs=runs)


def batch(cfg, seed, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.key(100 + seed),
                              (rows, seq + 1), 0, cfg.vocab_size)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def rel_diff(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())
