"""Xing4.0-29B-A4B's block through the normal path (`Transformer.loss`: a
residual path of four streams under manifold-constrained hyper-connections
around latent attention under YaRN, a leading dense layer, a shared expert,
the sigmoid router with its choice bias, a held share of the experts and of
the heads) against the plain float32 reference
`benchmark/reference/xing4_f32.py`, which shares no code with `ray_tpu`:
seeded stand-in weights, small sizes, on the CPU, float32 against float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the maps' product after the statistic's scale or before it,
the mixes as four scaled slices or as einsums, fused gate/up matmuls, a
grouped matmul against a masked loop, attention whole against attention by
blocks): 1e-4 relative to the largest entry of each compared array allows
that and nothing else. The faults are in `tests/test_xing4_faults.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer
from ray_tpu.models.transformer import _rope_tables
from ray_tpu.ops import mhc

from tests._programs import programs
from tests._xing4 import (E, HEADS, SEQ, STREAMS, assert_close, batch,
                          config, job, published, ref, reference, share_of,
                          system, weights)

# experts held and their offset, heads held
SHARES = {"all_held": (0, 0, None), "share_4_of_16_2_heads": (4, 8, (2, 4))}


def side(share, seed):
    """(cfg, the share's params, its weights in the reference's layout)."""
    held, offset, heads = SHARES[share]
    whole = weights(config(), seed)
    cfg = config(held, offset, heads=heads[1] - heads[0] if heads else HEADS)
    params = share_of(whole, held or E, offset, heads)
    return cfg, params, job.to_reference_layout(params, cfg)


def yarn_tables(cfg):
    """The program's cos and sin under cfg's YaRN, as `_stack` asks."""
    return _rope_tables(
        jnp.arange(SEQ)[None], cfg.rope_dim, cfg.rope_theta,
        (cfg.rope_yarn_factor, cfg.rope_yarn_original_len,
         cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow,
         cfg.yarn_attention_factor))


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("seed", [0, 3])
def test_logits_maps_and_loss_match_the_reference(share, seed):
    cfg, params, w = side(share, seed)
    tokens = batch(cfg, seed)
    logits, maps = system(cfg)(params, tokens[:, :-1])
    ref_logits, chosen, ref_maps = reference(cfg).forward(w, tokens[:, :-1])
    assert maps.shape == ref_maps.shape == (
        2 * cfg.n_layers, 2, SEQ, STREAMS * STREAMS + 2 * STREAMS)
    assert_close(logits, ref_logits, "logits")
    assert_close(maps, ref_maps, "maps")
    loss, metrics = programs(cfg).loss(params, {"tokens": tokens})
    assert_close(loss, ref.next_token_loss(ref_logits, tokens[:, 1:]),
                 "loss")
    # the step's two readings, from the same forward pass
    want = mhc.marginal_error(ref_maps, STREAMS)
    assert float(metrics["mhc_res_marginal_err"]) == pytest.approx(
        float(want), abs=2e-6)
    assert float(metrics["mhc_res_marginal_err"]) < 1e-3
    assert float(metrics["mhc_stream_gain"]) > 0
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    held = cfg.held_experts
    np.testing.assert_array_equal(
        metrics["moe_tokens_per_expert"],
        counts[:, cfg.moe_expert_offset:cfg.moe_expert_offset + held])


@pytest.mark.parametrize("share", SHARES)
def test_every_gradient_matches_the_reference(share):
    """phi's, b's and alpha's among them; the choice bias has none."""
    cfg, params, w = side(share, 1)
    tokens = batch(cfg, 1)
    loss, grads = programs(cfg).grads(params, {"tokens": tokens})
    ref_loss, ref_grads = reference(cfg).loss_and_grads(w, tokens)
    assert_close(loss, ref_loss, "loss")
    got = jax.tree_util.tree_leaves_with_path(
        job.to_reference_layout(grads, cfg))
    want = jax.tree.leaves(ref_grads)
    assert len(got) == len(want)
    seen = set()
    for (path, g), r in zip(got, want):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            continue
        assert float(jnp.abs(r).max()) > 0, name
        assert_close(g, r, name)
        seen.add(name.split("'")[-2])
    assert {"phi", "b", "alpha"} <= seen


def test_the_clamp_at_logits_above_30():
    """b of H_res at +-100: both sides cut A to +-30 before exp and agree;
    unclamped, the reference's exp overflows."""
    cfg, params, _ = side("all_held", 2)
    n = STREAMS
    big = jnp.where(jnp.arange(n * n) % 3 == 0, 100.0, -100.0)
    for run in ("dense_layers", "layers"):
        lay = dict(params[run])
        for name in ("attn_hc_b", "mlp_hc_b"):
            lay[name] = lay[name].at[:, 2 * n:].set(big)
        params = dict(params, **{run: lay})
    tokens = batch(cfg, 2)
    logits, maps = system(cfg)(params, tokens[:, :-1])
    w = job.to_reference_layout(params, cfg)
    ref_logits, _, ref_maps = reference(cfg).forward(w, tokens[:, :-1])
    assert bool(jnp.isfinite(logits).all())
    assert_close(logits, ref_logits, "logits")
    assert_close(maps, ref_maps, "maps")
    loose = published(cfg, mhc_h_res_clamp_min=-1e9, mhc_h_res_clamp_max=1e9)
    overflowed = ref.forward(w, tokens[:, :-1], loose)
    assert not bool(jnp.isfinite(overflowed).all())


def test_yarn_is_the_familys_reading():
    """The ramp on the tables, no factor on them, the factor squared on
    the whole softmax scale: at the published numbers 192^-1/2 x 2.0047."""
    cfg = config()
    model = published(cfg)
    cos, sin = yarn_tables(cfg)
    ref_cos, ref_sin = ref.rope_tables(SEQ, cfg.rope_dim, cfg.rope_theta,
                                       model["rope_scaling"])
    half = cfg.rope_dim // 2
    assert_close(cos[0], ref_cos[:, :half], "cos")
    assert_close(sin[0], ref_sin[:, :half], "sin")
    plain = ref.rope_tables(SEQ, cfg.rope_dim, cfg.rope_theta)[0]
    assert float(jnp.abs(ref_cos - plain).max()) > 0.1   # the ramp moves it
    assert cfg.softmax_scale == pytest.approx(ref.softmax_scale(model))
    at_width = config(qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert at_width.softmax_scale == pytest.approx(
        192 ** -0.5 * 2.0047, rel=1e-4)
    assert config(rope_yarn_mscale_all_dim=0.0).softmax_scale == \
        cfg.head_dim ** -0.5


# ---- the share tied to the model ---------------------------------------


def stream(seed):
    return jax.random.normal(jax.random.key(seed), (2, SEQ, STREAMS, 64))


def layer_program(cfg):
    """One layer of `cfg` on a stream, the sublayers its leaves name."""
    cos, sin = yarn_tables(cfg)
    return jax.jit(lambda x, sub: Transformer._make_layer_fn(
        cfg, None, None, cos, sin, seq_len=SEQ)(x, sub)[0])


def test_the_heads_shares_add_up_to_the_uncut_sublayer():
    """Four shares of one head: what each share's attention adds to the
    stream, `H_post (x) y_share`, summed, with `H_res X` counted once, is
    the uncut reference's X'; each share is the reference's same share."""
    whole_cfg = config()
    params = weights(whole_cfg, 4)
    x = stream(11)
    model = published(whole_cfg)
    lw = job.to_reference_layout(params, whole_cfg)["layers"][1]
    cos, sin = ref.rope_tables(SEQ, whole_cfg.rope_dim, whole_cfg.rope_theta,
                               model["rope_scaling"])

    def uncut(lw_, heads):
        m = dict(model, num_attention_heads=heads)
        with jax.default_matmul_precision("highest"):
            return ref.hyper_connected(
                x, lw_["attn_hc"], lw_["input_layernorm"], m,
                lambda n: ref.latent_attention(n, lw_, m, cos, sin))[0]

    want = uncut(lw, HEADS)
    share_cfg = config(heads=1)
    layer = layer_program(share_cfg)
    attention = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                 "kv_a_norm", "wkv_b", "wo", "attn_hc_phi", "attn_hc_b",
                 "attn_hc_alpha")
    parts, passed = 0.0, None
    for lo in range(HEADS):
        mine = share_of(params, E, 0, (lo, lo + 1))
        sub = {k: mine["layers"][k][0] for k in attention}
        got = layer(x, sub)
        if passed is None:   # y = 0: H_res X alone
            passed = layer(x, dict(sub, wo=jnp.zeros_like(sub["wo"])))
        assert_close(got, uncut(job.to_reference_layout(
            mine, share_cfg)["layers"][1], 1), ("share", lo))
        parts = parts + (got - passed)
    assert_close(parts + passed, want, "heads")


def test_the_experts_shares_add_up_to_the_uncut_sublayer():
    """Eight shares of two experts: the routed parts summed, with the
    shared expert and `H_res X` counted once, are the uncut reference's
    X'."""
    whole_cfg = config()
    params = weights(whole_cfg, 5)
    x = stream(12)
    model = published(whole_cfg)
    lw = job.to_reference_layout(params, whole_cfg)["layers"][1]

    def ffn(lw_):
        def run(m):
            flat = m.reshape(-1, m.shape[-1])
            y = ref.routed_experts(flat, lw_, model)[0] \
                + ref.shared_experts(flat, lw_)
            return y.reshape(m.shape)
        return run

    with jax.default_matmul_precision("highest"):
        want = ref.hyper_connected(x, lw["mlp_hc"],
                                   lw["post_attention_layernorm"], model,
                                   ffn(lw))[0]
    experts = ("mlp_norm", "w_router", "router_bias", "w_moe_gateup",
               "w_moe_down", "w_shared_gateup", "w_shared_down",
               "mlp_hc_phi", "mlp_hc_b", "mlp_hc_alpha")
    parts, once = 0.0, None
    for offset in range(0, E, 2):
        cfg = config(2, offset)
        mine = share_of(params, 2, offset)
        sub = {k: mine["layers"][k][0] for k in experts}
        got = layer_program(cfg)(x, sub)
        if once is None:   # no routed part: H_res X + H_post (x) shared
            once = layer_program(cfg)(x, dict(
                sub, w_moe_down=jnp.zeros_like(sub["w_moe_down"])))
        parts = parts + (got - once)
    assert_close(parts + once, want, "experts")


# ---- what one stream keeps, and what several streams refuse -------------


def test_one_stream_traces_nothing_of_this():
    cfg = config(residual_streams=1, remat=False)
    params = jax.eval_shape(lambda k: Transformer.init(k, cfg),
                            jax.random.key(0))
    assert not [k for k in params["layers"] if "_hc_" in k]
    assert not [k for k in Transformer.param_specs(cfg)["layers"]
                if "_hc_" in k]
    tokens = jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: Transformer.loss(
        p, {"tokens": t}, cfg, with_metrics=True))(params, tokens)
    assert "mhc" not in str(jaxpr)
    assert len(jax.eval_shape(lambda p, t: Transformer.hidden(
        p, t, cfg, with_aux=True), params, jax.ShapeDtypeStruct(
            (2, SEQ), jnp.int32))) == 3
    assert cfg.num_params + 2 * cfg.n_layers * (
        (STREAMS * 64 + 1) * 24 + 3) == config().num_params


def test_param_count_and_specs():
    cfg = config(4, 8)
    params = jax.eval_shape(lambda k: Transformer.init(k, cfg),
                            jax.random.key(0))
    frozen = Transformer.frozen(cfg)
    counted = sum(int(np.prod(x.shape)) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    assert counted == cfg.num_params
    specs = Transformer.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda _: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
    for run in ("dense_layers", "layers"):
        assert params[run]["attn_hc_phi"].shape[1:] == (STREAMS * 64, 24)
        assert params[run]["mlp_hc_b"].shape[1:] == (24,)
        assert params[run]["mlp_hc_alpha"].shape[1:] == (3,)


def test_the_program_init_is_the_one_stream_layer():
    """`Transformer.init`'s own start (alpha 0.01, H_pre 1/n, H_post 1,
    H_res near the identity): on n equal streams every stream is the
    one-stream model's, so the logits are those of the same weights on
    one stream (the final norm takes the n of the sum)."""
    cfg = config(remat=False)
    params = Transformer.init(jax.random.key(6), cfg)
    for run in ("dense_layers", "layers"):   # static maps: alpha 0
        for name in ("attn_hc_alpha", "mlp_hc_alpha"):
            params[run][name] = jnp.zeros_like(params[run][name])
    tokens = batch(cfg, 6)[:, :-1]
    logits, maps = system(cfg)(params, tokens)
    n = STREAMS
    np.testing.assert_allclose(maps[..., :n], 1.0 / n, rtol=1e-5)
    np.testing.assert_allclose(maps[..., n:2 * n], 1.0, rtol=1e-5)
    eye = np.eye(n).reshape(-1)
    np.testing.assert_allclose(maps[..., 2 * n:], np.broadcast_to(
        eye, maps[..., 2 * n:].shape), atol=2e-3)
    one = config(residual_streams=1, remat=False)
    plain = {k: ({n_: v_ for n_, v_ in v.items() if "_hc_" not in n_}
                 if isinstance(v, dict) else v) for k, v in params.items()}
    assert_close(logits, programs(one).logits(plain, tokens), "logits",
                 rtol=2e-2)


@pytest.mark.parametrize("what,kw", [
    ("a layer_pattern", dict(layer_pattern="LLL", moe_dense_layers=0)),
    ("a looped stack", dict(loops=2, moe_experts=0, moe_shared_experts=0,
                            moe_dense_layers=0, moe_experts_held=0)),
    ("block diffusion", dict(block_length=8)),
    ("ring or ulysses", dict(attention_impl="ring")),
    ("ring or ulysses", dict(attention_impl="ulysses")),
])
def test_what_several_streams_refuse_by_name(what, kw):
    with pytest.raises(ValueError, match="residual_streams above 1.*"
                       + what.split()[-1]):
        config(**kw)


def test_pipeline_loss_refuses_several_streams():
    cfg = config(moe_experts=0, moe_shared_experts=0, moe_dense_layers=0,
                 moe_experts_held=0, n_layers=2)
    tokens = jnp.zeros((2, SEQ + 1), jnp.int32)
    with pytest.raises(ValueError, match="residual_streams above 1"):
        Transformer.pipeline_loss(
            jax.eval_shape(lambda k: Transformer.init(k, cfg),
                           jax.random.key(0)), {"tokens": tokens}, cfg,
            mesh=None, n_stages=2, n_micro=2)
