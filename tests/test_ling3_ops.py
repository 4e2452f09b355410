"""Ling-3.0-flash's ops against what defines them, apart from the model
(`tests/test_ling3_reference.py`; both read `tests/_ling3.py`): the delta
rule in chunks (`kda.gated_delta_rule`) against the recurrence of
`benchmark/reference/ling3_f32.py` step by step, forward and five
gradients, with gates at their bound; the group-limited router against a
loop over tokens, ties included; the experts' and the heads' shares adding
up to the uncut layer. Tolerances as there: 1e-4 of the largest entry."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer
from ray_tpu.ops import kda, moe

from tests._ling3 import (E, GROUPS, HEADS, K, KEPT, SEQ, assert_close,
                          config, head_share, job, published, ref, weights)


# ---- the delta rule in chunks against the recurrence --------------------


def delta_inputs(seed, t, heads=3, d=16, at_the_bound=()):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (2, t, heads, d)) for i in range(3))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3],
                                                       (2, t, heads, d)))
    for lo, hi in at_the_bound:    # every channel's gate at the bound
        g = g.at[:, lo:hi].set(-5.0 * (1 - 1e-7))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, heads)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(ref.l2_norm(q), ref.l2_norm(k), v, g, beta)


@functools.lru_cache(maxsize=None)
def chunked(chunk):
    """The op under one `jax.jit` a chunk length: the runs at the bound of
    one shape are one program."""
    return jax.jit(functools.partial(kda.gated_delta_rule, chunk=chunk))


# more than 18 steps at the bound, in a row: 5 x 18 > 88, where
# (k e^G)(k e^-G)^T overflows float32; inside one chunk, across sub-blocks
# and across a chunk's end
BOUND_RUNS = {"none": (), "20_in_a_chunk": ((3, 23),),
              "40_over_subblocks": ((10, 50),),
              "across_chunks": ((50, 90),), "all": ((0, 10_000),)}


@pytest.mark.parametrize("run", BOUND_RUNS)
@pytest.mark.parametrize("t,chunk", [(128, 64), (100, 64), (64, 32),
                                     (37, 16), (200, 64)])
def test_chunked_delta_rule_matches_the_recurrence(t, chunk, run):
    args = delta_inputs(t, t, at_the_bound=BOUND_RUNS[run])
    got = chunked(chunk)(*args)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert_close(got, recurrence(*args), (t, chunk, run))


@functools.lru_cache(maxsize=None)
def delta_grads(rule):
    return jax.jit(jax.grad(lambda w, *a: (rule(*a) * w).sum(),
                            argnums=(1, 2, 3, 4, 5)))


@pytest.mark.parametrize("run", ["none", "40_over_subblocks", "all"])
def test_chunked_delta_rule_gradients_match_the_recurrences(run):
    args = delta_inputs(7, 100, at_the_bound=BOUND_RUNS[run])
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got = delta_grads(chunked(32))(w, *args)
    want = delta_grads(recurrence)(w, *args)
    for name, a, b in zip("qkvgb", got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert_close(a, b, (name, run))


def test_a_naive_factoring_overflows_where_the_op_does_not():
    """What the sub-blocks are for: the whole-chunk factors (k e^G) and
    (k e^-G) of a chunk with 20 steps at the bound are not finite."""
    _, k, _, g, _ = delta_inputs(1, 64, at_the_bound=((0, 20),))
    cum = jnp.cumsum(g, axis=1)
    assert not bool(jnp.isfinite(k * jnp.exp(-cum)).all())


def test_a_chunk_that_is_no_whole_sub_blocks_is_refused():
    with pytest.raises(ValueError, match="sub-blocks"):
        kda.gated_delta_rule(*delta_inputs(0, 48), chunk=24)


# ---- the group-limited router ---------------------------------------------


def route_by_hand(scores, bias, k, n_group, topk_group, scale):
    """A loop over tokens: groups by the sum of their two largest
    score + bias, ties to the lower index; the top k among the kept
    groups' experts, ties to the lower index."""
    n, e = scores.shape
    size = e // n_group
    ids, weights_ = [], []
    for row in range(n):
        choice = scores[row] + bias
        rank = [sum(sorted(choice[g * size:(g + 1) * size])[-2:])
                for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-rank[g], g))[
            :topk_group]
        allowed = [i for g in sorted(kept)
                   for i in range(g * size, (g + 1) * size)]
        top = sorted(allowed, key=lambda i: (-choice[i], i))[:k]
        w = np.asarray([scores[row][i] for i in top], np.float64)
        ids.append(top)
        weights_.append(scale * w / w.sum())
    return np.asarray(ids), np.asarray(weights_)


@pytest.mark.parametrize("ties", [False, True])
def test_group_limited_route_against_a_loop_over_tokens(ties):
    n, d = 96, 24
    x = jax.random.normal(jax.random.key(0), (n, d))
    w_router = jax.random.normal(jax.random.key(1), (d, E))
    bias = 0.3 * jax.random.normal(jax.random.key(2), (E,))
    if ties:
        # equal scores and equal group ranks: whole tokens of zeros, two
        # experts that are copies of each other, two groups that are too
        x = x.at[:8].set(0.0)
        w_router = w_router.at[:, 5].set(w_router[:, 4])
        w_router = w_router.at[:, 8:12].set(w_router[:, 12:16])
        bias = bias.at[5].set(bias[4]).at[8:12].set(bias[12:16])
    probs, top_w, top_e, kept = moe.route(
        w_router, x, K, True, scoring="sigmoid", bias=bias,
        routed_scale=2.5, n_group=GROUPS, topk_group=KEPT)
    ids, w = route_by_hand(np.asarray(probs, np.float64),
                              np.asarray(bias, np.float64), K, GROUPS,
                              KEPT, 2.5)
    np.testing.assert_array_equal(np.asarray(top_e), ids)
    np.testing.assert_allclose(np.asarray(top_w), w, rtol=1e-5)
    assert int(kept.sum()) == KEPT * n and kept.shape == (GROUPS,)
    # the reference's router makes the same choice
    lw = {"mlp.gate": w_router.T, "e_score_correction_bias": bias}
    ref_w, ref_e, _ = ref.route(x, lw, published(config()))
    np.testing.assert_array_equal(np.asarray(ref_e), ids)
    np.testing.assert_allclose(np.asarray(ref_w), w, rtol=1e-5)
    # without groups the same call is the router the other cells run
    plain = moe.route(w_router, x, K, True, scoring="sigmoid", bias=bias,
                      routed_scale=2.5)
    assert len(plain) == 3


# ---- the shares add up ------------------------------------------------------


def layer_weights(seed, heads=HEADS):
    """One layer of each kind in the reference's layout, all experts and
    `heads` heads held."""
    cfg = config(n_layers=2, layer_pattern="KL", heads=heads)
    params = weights(cfg, seed)
    return cfg, params, job.to_reference_layout(params, cfg)["layers"]


def test_the_experts_shares_add_up_to_the_uncut_layer():
    """Every chip's part of the routed sum (its own experts, every
    offset) plus the shared expert ONCE is the uncut reference's expert
    FFN; and the system's held share is the reference's same share."""
    cfg, params, (lw, _) = layer_weights(1)
    m = jax.random.normal(jax.random.key(3), (40, cfg.d_model))
    model = published(cfg)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_experts(m, lw, model)
        parts = []
        for offset in range(0, E, 4):
            mine = dict(lw, experts={e: lw["experts"][e]
                                     for e in range(offset, offset + 4)})
            part, _ = ref.routed_experts(m, mine, model)
            parts.append(part)
        assert_close(sum(parts), whole, "routed")
        uncut = whole + ref.shared_experts(m, lw)
    # the system, one share at a time, the shared expert counted once
    sub = jax.tree.map(lambda leaf: leaf[0], params["runs"][0][0])
    total = 0.0
    for offset in range(0, E, 4):
        held = {"w_router": sub["w_router"],
                "router_bias": sub["router_bias"],
                "w_gateup": sub["w_moe_gateup"][offset:offset + 4],
                "w_down": sub["w_moe_down"][offset:offset + 4]}
        y, routing = moe.moe_ffn(
            held, m, num_selected=K, norm_topk=True, scoring="sigmoid",
            routed_scale=2.5, expert_offset=offset, n_group=GROUPS,
            topk_group=KEPT)
        total = total + y
        assert_close(y, parts[offset // 4], ("share", offset))
    shared = moe.shared_ffn(sub["w_shared_gateup"], sub["w_shared_down"], m)
    assert_close(total + shared, uncut, "shares + shared once")


@pytest.mark.parametrize("layer,name", [(0, "kda"), (1, "mla")])
def test_the_heads_shares_add_up_to_the_uncut_layer(layer, name):
    """The heads' parts of W_o's sum, four shares of two heads, give the
    uncut layer's attention block: in the reference and in the system."""
    heads = 8
    cfg, params, layers = layer_weights(2, heads=heads)
    lw = layers[layer]
    n = jax.random.normal(jax.random.key(4), (2, SEQ, cfg.d_model))
    model = published(cfg)
    cos, sin = ref.rope_tables(SEQ, cfg.qk_rope_head_dim, cfg.rope_theta)

    def block(w):
        with jax.default_matmul_precision("highest"):
            return ref.kda_attention(n, w, model) if name == "kda" \
                else ref.latent_attention(n, w, model, cos, sin)

    whole = block(lw)
    parts = 0.0
    share_cfg = config(n_layers=2, layer_pattern="KL", heads=2)
    # one program for the four shares: the same leaves at the same shapes
    layer_fn = jax.jit(lambda x, sub: Transformer._make_layer_fn(
        share_cfg, None, None, *rope(share_cfg), seq_len=SEQ)(x, sub)[0])
    for lo in range(0, heads, 2):
        shared = head_share(params, lo, lo + 2)
        mine = job.to_reference_layout(shared, share_cfg)["layers"][layer]
        part = block(mine)
        parts = parts + part
        # the system given the same share: the sublayer's residual branch
        sub = jax.tree.map(lambda leaf: leaf[0],
                           shared["runs"][0][layer])
        sub = dict(sub, **{name_: jnp.ones_like(sub[name_])
                           for name_ in ("kda_norm", "attn_norm")
                           if name_ in sub})
        keep = {k: v for k, v in sub.items()
                if k not in ("mlp_norm", "w_router", "router_bias",
                             "w_moe_gateup", "w_moe_down",
                             "w_shared_gateup", "w_shared_down")}
        # x = 0 would be normed to 0: hand the block its normed input as
        # the stream of unit RMS (gain 1, eps 1e-6)
        x = n / jnp.sqrt(jnp.mean(n * n, -1, keepdims=True))
        want = block_of_normed(x, mine, model, name, cos, sin)
        got = layer_fn(x, keep) - x
        assert_close(got, want, ("system share", lo))
    assert_close(parts, whole, "heads")


def rope(cfg):
    from ray_tpu.models.transformer import _rope_tables
    return _rope_tables(jnp.arange(SEQ)[None], cfg.rope_dim, cfg.rope_theta)


def block_of_normed(x, lw, model, name, cos, sin):
    """The reference's block on RMSNorm(x; 1)."""
    n = ref.rms_norm(x, 1.0, model["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        return ref.kda_attention(n, lw, model) if name == "kda" \
            else ref.latent_attention(n, lw, model, cos, sin)
