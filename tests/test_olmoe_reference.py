"""OLMoE through the normal path (`Transformer.loss`: QK-norm, dropless
top-k routing over gated experts, the published aux loss) against the
plain float32 reference `benchmark/reference/olmoe_f32.py`, which shares
no code with `ray_tpu`: seeded random weights, small sizes, on the CPU,
float32 against float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (fused qkv and gate/up matmuls, a grouped matmul over sorted
rows against a masked loop over every expert): 1e-4 relative to the
largest entry of each compared array allows that and nothing else. A
renormalised gate moves the logits by 1e-1 of their size, a dropped token,
a missing QK-norm or an ungated expert by more, a per-layer instead of an
all-layers aux loss by 1e-2 of it.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.train_step import make_train_step

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference", "olmoe_f32.py")
_spec = importlib.util.spec_from_file_location("_olmoe_f32", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

RTOL = 1e-4
SEQ = 64
ROUTINGS = {"top2_of_8": (8, 2), "top8_of_16": (16, 8)}


def config(experts, top_k, **kw):
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=32,
        max_seq_len=SEQ, dtype="float32", qk_norm=True,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=False,
        moe_aux_coeff=0.01, loss_chunk=0, **kw)


def published(cfg):
    """The HF config.json keys the reference reads."""
    return {"hidden_act": "silu", "clip_qkv": None,
            "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.kv_heads,
            "num_experts": cfg.moe_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk,
            "router_aux_loss_coef": cfg.moe_aux_coeff,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def to_reference_layout(params, cfg):
    lay, d = params["layers"], cfg.d_model
    layers = []
    for i in range(cfg.n_layers):
        q, k, v = (lay["wqkv"][i][:, j] for j in range(3))
        layers.append({
            "input_layernorm": lay["attn_norm"][i],
            "q_proj": q.reshape(d, -1).T, "k_proj": k.reshape(d, -1).T,
            "v_proj": v.reshape(d, -1).T,
            "o_proj": lay["wo"][i].reshape(-1, d).T,
            "q_norm": lay["q_norm"][i], "k_norm": lay["k_norm"][i],
            "post_attention_layernorm": lay["mlp_norm"][i],
            "mlp.gate": lay["w_router"][i].T,
            "experts": [
                {"gate_proj": lay["w_moe_gateup"][i][e][:, 0].T,
                 "up_proj": lay["w_moe_gateup"][i][e][:, 1].T,
                 "down_proj": lay["w_moe_down"][i][e].T}
                for e in range(cfg.moe_experts)],
        })
    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": params["lm_head"].T}


def from_reference_layout(grads, cfg):
    """The reference's gradients back in the program's fused layout."""
    d, nh, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    stack = lambda f: jnp.stack([f(g) for g in grads["layers"]])  # noqa: E731
    heads = lambda w: w.T.reshape(d, nh, hd)  # noqa: E731
    return {
        "embed": grads["embed_tokens"], "final_norm": grads["norm"],
        "lm_head": grads["lm_head"].T,
        "layers": {
            "attn_norm": stack(lambda g: g["input_layernorm"]),
            "mlp_norm": stack(lambda g: g["post_attention_layernorm"]),
            "q_norm": stack(lambda g: g["q_norm"]),
            "k_norm": stack(lambda g: g["k_norm"]),
            "wqkv": stack(lambda g: jnp.stack(
                [heads(g[n]) for n in ("q_proj", "k_proj", "v_proj")], 1)),
            "wo": stack(lambda g: g["o_proj"].T.reshape(nh, hd, d)),
            "w_router": stack(lambda g: g["mlp.gate"].T),
            "w_moe_gateup": stack(lambda g: jnp.stack([jnp.stack(
                [e["gate_proj"].T, e["up_proj"].T], 1)
                for e in g["experts"]])),
            "w_moe_down": stack(lambda g: jnp.stack(
                [e["down_proj"].T for e in g["experts"]])),
        }}


def weights(cfg, seed, skew=False):
    """Random weights with the gains off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place). `skew`: a component shared by every
    embedding survives the norms and the router reads it like a bias, so
    the same few experts are in nearly every token's top-k and some
    experts get no token at all."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 8))
    lay = params["layers"]
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                  lay[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    # logits of order 1, as at the published width (0.02 x sqrt(2048))
    lay["w_router"] = lay["w_router"] * 6.0
    if skew:
        params["embed"] = params["embed"] + 0.03
    return params


def batch(cfg, seed, rows=2):
    return jax.random.randint(jax.random.key(100 + seed),
                              (rows, SEQ + 1), 0, cfg.vocab_size)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def system(params, tokens, cfg, **kw):
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: Transformer.loss(p, {"tokens": tokens}, cfg,
                                   with_metrics=True, **kw),
        has_aux=True)(params)
    return loss, metrics, grads


@functools.lru_cache(maxsize=None)
def compiled_system(cfg):
    """`system` at `cfg` under one `jax.jit` a process: the five variants
    below are held to one base, compiled once."""
    return jax.jit(lambda p, t: system(p, t, cfg))


@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "skewed"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_loss_logits_and_every_gradient_match_the_reference(routing, skew):
    cfg = config(*ROUTINGS[routing])
    params = weights(cfg, seed=3, skew=skew)
    tokens = batch(cfg, seed=3)
    hf = published(cfg)
    ref_w = to_reference_layout(params, cfg)

    logits = Transformer.apply(params, tokens[:, :-1], cfg)
    ref_logits, router_logits = jax.jit(lambda w, t: ref.forward(
        w, t, hf, with_router_logits=True))(ref_w, tokens[:, :-1])
    assert_close(logits, ref_logits, "logits")

    loss, metrics, grads = compiled_system(cfg)(params, tokens)
    (ref_total, ref_ce, ref_aux), ref_grads = jax.jit(
        lambda w, t: ref.loss_and_grads(w, t, hf))(ref_w, tokens)
    assert_close(metrics["moe_aux_loss"], ref_aux, "aux loss")
    assert_close(loss - cfg.moe_aux_coeff * metrics["moe_aux_loss"],
                 ref_ce, "cross-entropy")
    assert_close(loss, ref_total, "loss")

    want = from_reference_layout(ref_grads, cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert len(flat) == len(want_flat) == 12
    for path, leaf in flat:
        assert_close(leaf, want_flat[path],
                     "gradient of " + jax.tree_util.keystr(path))

    # the counters: dropless, every slot counted, the reference's counts
    n_slots = tokens[:, :-1].size * cfg.moe_top_k
    counts = np.asarray(metrics["moe_tokens_per_expert"])
    assert counts.shape == (cfg.n_layers, cfg.moe_experts)
    assert counts.dtype == np.int32
    assert (counts.sum(axis=1) == n_slots).all(), counts
    assert int(metrics["moe_dropped"]) == 0
    np.testing.assert_array_equal(
        counts, np.asarray(ref.tokens_per_expert(router_logits, hf)))
    if skew:
        n_tokens = tokens[:, :-1].size
        assert ((counts == 0).sum(axis=1) >= 1).all(), counts  # empty groups
        top2 = np.sort(counts, axis=1)[:, -2:].sum(axis=1)
        assert (top2 > 0.8 * 2 * n_tokens).all(), counts
        assert (counts.max(axis=1)
                > 1.9 * n_slots / cfg.moe_experts).all(), counts


def test_norm_topk_follows_the_config():
    cfg = config(8, 2).replace(moe_norm_topk=True)
    params, tokens = weights(cfg, seed=5), batch(cfg, seed=5)
    hf = published(cfg)
    logits = Transformer.apply(params, tokens[:, :-1], cfg)
    ref_w = to_reference_layout(params, cfg)
    assert_close(logits, ref.forward(ref_w, tokens[:, :-1], hf), "logits")
    other = ref.forward(ref_w, tokens[:, :-1],
                        dict(hf, norm_topk_prob=False))
    assert np.abs(np.asarray(logits - other)).max() > \
        100 * RTOL * np.abs(np.asarray(other)).max()


@pytest.mark.parametrize("variant", ["remat", "remat_full", "remat_dots",
                                     "remat_unrolled", "chunked"])
def test_remat_scan_and_chunked_head_change_nothing(variant):
    base = config(8, 2)
    cfg = {"remat": base.replace(remat=True),
           "remat_full": base.replace(remat=True, remat_policy="full"),
           "remat_dots": base.replace(remat=True, remat_policy="dots"),
           "remat_unrolled": base.replace(remat=True, scan_unroll=2),
           "chunked": base.replace(remat=True, loss_chunk=16)}[variant]
    params, tokens = weights(base, seed=7, skew=True), batch(base, seed=7)
    loss, metrics, grads = compiled_system(base)(params, tokens)
    loss2, metrics2, grads2 = compiled_system(cfg)(params, tokens)
    assert_close(loss2, loss, "loss", rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(metrics2["moe_tokens_per_expert"]),
        np.asarray(metrics["moe_tokens_per_expert"]))
    for a, b in zip(jax.tree.leaves(grads2), jax.tree.leaves(grads)):
        assert_close(a, b, "gradient", rtol=1e-5)


def test_train_step_carries_the_counters():
    """`make_train_step` hands the loss function's metrics to the loop:
    the counts come with the loss, from the same forward pass."""
    import optax

    cfg = config(8, 2, remat=True)
    mesh = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True),
        Transformer.param_specs(cfg), mesh, optimizer=optax.adamw(1e-2))
    state = init_state(weights(cfg, seed=9))
    tokens = batch(cfg, seed=9, rows=4)
    losses = []
    for _ in range(4):
        state, metrics = train_step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        counts = np.asarray(metrics["moe_tokens_per_expert"])
        assert counts.shape == (2, 8)
        assert (counts.sum(1) == 4 * SEQ * 2).all()
        assert int(metrics["moe_dropped"]) == 0
    assert losses[-1] < losses[0], losses


def test_no_tokens_by_experts_by_anything_tensor_at_the_cell_size():
    """The whole train-step jaxpr at N = 4096 tokens, E = 64, top-8 (one
    sequence of the cell, published expert count): outside the router's
    own [N, E] logits and probabilities, no intermediate has both a
    tokens-sized and an experts-sized dimension with anything else."""
    n, e, k = 4096, 64, 8
    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=1, n_heads=4, d_ff=96,
        max_seq_len=n, dtype="float32", qk_norm=True, moe_experts=e,
        moe_top_k=k, moe_norm_topk=False, remat=True, loss_chunk=256)
    params = jax.eval_shape(lambda: Transformer.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, n + 1), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: Transformer.loss(
        p, {"tokens": t}, cfg)))(params, tokens)

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert (n * k, cfg.d_model) in seen     # the sorted slots are there
    assert (n, e) in seen                   # and the router's own [N, E]
    sized = {n, n * k, k * n}
    bad = [s for s in seen if e in s and any(d in sized for d in s)
           and len([d for d in s if d != 1]) > 2]
    assert not bad, bad
    biggest = max(int(np.prod(s)) for s in seen if s)
    assert biggest <= max(n * k * cfg.d_model, n * cfg.vocab_size,
                          n * n * cfg.n_heads), biggest
