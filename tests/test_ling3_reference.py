"""Ling-3.0-flash's layers through the normal path (`Transformer.loss`:
Kimi Delta Attention, latent attention without a query latent and with
the QK-norm, a dense MLP or experts under a group-limited sigmoid router,
a held share of the heads and of the experts) against the plain float32
reference `benchmark/reference/ling3_f32.py`, which shares no code with
`ray_tpu`: seeded random weights, small sizes, on the CPU, float32 against
float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the delta rule in chunks with a triangular inverse against
the recurrence step by step, a grouped matmul over sorted rows against a
masked loop over the resident experts, attention whole against attention
by blocks of queries): 1e-4 relative to the largest entry of each compared
array allows that and nothing else. Every published term has a case below
that fails without it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import kda, moe

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_json, load_module  # noqa: E402

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


# ---- the model against the reference --------------------------------------

SHARES = {"all_held": (0, 0), "share_4_of_16": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("seed", [0, 3])
def test_logits_and_loss_match_the_reference(share, seed):
    held, offset = SHARES[share]
    cfg = config(held, offset)
    params = weights(config(), seed)
    if held:
        params = expert_share(params, held, offset)
    tokens = batch(cfg, seed)
    w = job.to_reference_layout(params, cfg)
    logits = Transformer.apply(params, tokens[:, :-1], cfg)
    want, chosen = ref.forward(w, tokens[:, :-1], published(cfg),
                               with_routing=True)
    assert_close(logits, want, "logits")
    loss, metrics = Transformer.loss(params, {"tokens": tokens}, cfg,
                                     with_metrics=True)
    assert abs(float(loss) - float(ref.loss(w, tokens, published(cfg)))) \
        <= RTOL
    # the counters: the held experts' columns of the reference's counts,
    # nothing dropped, held + elsewhere = tokens x k, and every token
    # inside exactly KEPT groups
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    n = tokens[:, :-1].size
    first = offset
    np.testing.assert_array_equal(
        np.asarray(metrics["moe_tokens_per_expert"]),
        counts[:, first:first + (held or E)])
    assert int(metrics["moe_dropped"]) == 0
    groups = np.asarray(metrics["moe_groups_chosen"])
    assert groups.shape == (PATTERN.count("K") + PATTERN.count("L"), GROUPS)
    assert (groups.sum(-1) == KEPT * n).all() and (groups <= n).all()
    for top_e in chosen:    # the reference's choice: KEPT groups a token
        in_groups = np.asarray(top_e) // (E // GROUPS)
        assert max(len(set(row)) for row in in_groups.tolist()) <= KEPT
    if held:
        np.testing.assert_array_equal(
            np.asarray(metrics["moe_slots_elsewhere"]),
            n * K - counts[:, first:first + held].sum(-1))


def test_gradients_match_the_reference():
    cfg = config()
    params = weights(cfg, 5)
    tokens = batch(cfg, 5)
    loss, grads = jax.value_and_grad(
        lambda p: Transformer.loss(p, {"tokens": tokens}, cfg))(params)
    want_loss, want = ref.loss_and_grads(
        job.to_reference_layout(params, cfg), tokens, published(cfg))
    assert abs(float(loss) - float(want_loss)) <= RTOL
    got = job.to_reference_layout(grads, cfg)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want)
    scale = max(float(jnp.abs(g).max()) for g in flat_want.values())
    for path, leaf in flat_got:
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:   # a buffer: no gradient
            assert not np.asarray(flat_want[path]).any()
            continue
        # against the largest gradient of the model: a leaf whose own
        # gradient is tiny (a gain behind a saturated gate) has rounding's
        # noise at the model's scale
        err = float(jnp.abs(leaf - flat_want[path]).max())
        assert err <= RTOL * max(scale * 1e-2, float(
            jnp.abs(flat_want[path]).max())), (name, err, scale)


def test_remat_and_the_chunked_head_change_nothing():
    cfg = config()
    params = weights(cfg, 2)
    tokens = {"tokens": batch(cfg, 2)}
    base, base_g = jax.value_and_grad(
        lambda p: Transformer.loss(p, tokens, cfg))(params)
    for over in (dict(remat=True), dict(remat=True, remat_policy="full"),
                 dict(loss_chunk=16)):
        other = config(**over)
        loss, g = jax.value_and_grad(
            lambda p: Transformer.loss(p, tokens, other))(params)
        assert abs(float(loss) - float(base)) <= 1e-6, over
        # two programs that sum in other orders, through the chunks'
        # inverses: float32's rounding, not the tolerance of a term
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(base_g)):
            assert_close(a, b, over, rtol=5e-4)


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
    got = kda.gated_delta_rule(*args, chunk=chunk)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert_close(got, recurrence(*args), (t, chunk, run))


@pytest.mark.parametrize("run", ["none", "40_over_subblocks", "all"])
def test_chunked_delta_rule_gradients_match_the_recurrences(run):
    args = delta_inputs(7, 100, at_the_bound=BOUND_RUNS[run])
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got = jax.grad(lambda *a: (kda.gated_delta_rule(*a, chunk=32) * w).sum(),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (recurrence(*a) * w).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)
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


def test_the_group_limit_changes_the_choice():
    cfg = config()
    params = weights(cfg, 0)
    tokens = batch(cfg, 0)
    with_limit = Transformer.apply(params, tokens[:, :-1], cfg)
    without = Transformer.apply(params, tokens[:, :-1], config(
        moe_groups=1, moe_topk_groups=1))
    assert rel_diff(without, with_limit) > 1e-2


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
    for lo in range(0, heads, 2):
        share_cfg = config(n_layers=2, layer_pattern="KL", heads=2)
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
        layer_fn = Transformer._make_layer_fn(
            share_cfg, None, None, *rope(share_cfg), seq_len=SEQ)
        keep = {k: v for k, v in sub.items()
                if k not in ("mlp_norm", "w_router", "router_bias",
                             "w_moe_gateup", "w_moe_down",
                             "w_shared_gateup", "w_shared_down")}
        # x = 0 would be normed to 0: hand the block its normed input as
        # the stream of unit RMS (gain 1, eps 1e-6)
        x = n / jnp.sqrt(jnp.mean(n * n, -1, keepdims=True))
        want = block_of_normed(x, mine, model, name, cos, sin)
        got = layer_fn(x, keep)[0] - x
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


# ---- every term is held -------------------------------------------------------


@pytest.fixture(scope="module")
def faulty():
    cfg = config(4, 4)
    params = expert_share(weights(config(), 4), 4, 4)
    tokens = batch(cfg, 4)
    w = job.to_reference_layout(params, cfg)
    model = published(cfg)
    return cfg, params, tokens, w, model, ref.forward(
        w, tokens[:, :-1], model)


@pytest.mark.parametrize("name", faults.FAULTS)
def test_every_fault_moves_the_logits(faulty, name):
    """Each term left out of the reference moves the logits by far more
    than the 1e-4 the model is held to, and by more than the cell's limit
    on the relative L2 (benchmark/configs/ling-3.0-flash-ep64-tp4-d7.json;
    on the chip at the published widths too: its `tolerance.why`)."""
    cfg, params, tokens, w, model, base = faulty
    broken, model_, w_ = faults.variant(name, model, w)
    logits = broken.forward(w_, tokens[:, :-1], model_)
    assert rel_diff(logits, base) > 50 * RTOL, name
    limit = load_json(os.path.join(
        BENCH_DIR, "configs", "ling-3.0-flash-ep64-tp4-d7.json"))[
            "tolerance"]["logits_rel_l2"]
    diff = np.asarray(logits - base, np.float64)
    assert np.sqrt((diff ** 2).sum() / (np.asarray(
        base, np.float64) ** 2).sum()) > limit, name
    # and the unchanged copy is the reference
    plain, model_, w_ = faults.variant(None, model, w)
    assert rel_diff(plain.forward(w_, tokens[:, :-1], model_), base) == 0.0


def test_the_system_fails_with_a_term_left_out():
    """The same from the system's side: what it computes without a term
    is off the reference by far more than the tolerance."""
    cfg = config()
    params = weights(cfg, 6)
    tokens = batch(cfg, 6)
    w = job.to_reference_layout(params, cfg)
    want = ref.forward(w, tokens[:, :-1], published(cfg))
    for over in (dict(qk_norm=False), dict(moe_routed_scale=1.0),
                 dict(kda_gate_lower=-1.0), dict(moe_topk_groups=GROUPS)):
        other = config(**over)
        mine = params
        if "qk_norm" in over:
            mine = dict(params, runs=[[
                {k: v for k, v in sub.items()
                 if k not in ("q_norm", "k_norm")} for sub in run]
                for run in params["runs"]])
        got = Transformer.apply(mine, tokens[:, :-1], other)
        assert rel_diff(got, want) > 50 * RTOL, over


# ---- what the configuration refuses, by name ----------------------------------


@pytest.mark.parametrize("over,why", [
    (dict(layer_pattern="kKK*K"), "latent attention in the kinds"),
    (dict(kda_heads=0), "kda_heads"),
    (dict(moe_groups=3), "group-limited"),
    (dict(moe_groups=4, moe_topk_groups=5), "group-limited"),
    (dict(moe_groups=16), "group-limited"),
    (dict(moe_scoring="softmax", moe_aux_coeff=0.01), "group-limited"),
    (dict(moe_dense_layers=1), "leading dense run"),
    (dict(layer_pattern="kkklk", moe_experts=E), "expert layers where"),
    (dict(n_kv_heads=2), "one key/value head"),
    (dict(qk_rope_head_dim=3), "even"),
])
def test_what_is_refused_is_refused_by_name(over, why):
    with pytest.raises(ValueError, match=why):
        config(**over)


def test_param_count_and_runs():
    cfg = config(4, 4)
    params = expert_share(weights(config(), 0), 4, 4)
    frozen = Transformer.frozen(cfg)
    n = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    assert n == cfg.num_params
    assert cfg.pattern_runs == [("k", 1), ("K", 2), ("LK", 1)]
    from ray_tpu.models.configs import pattern_runs
    assert pattern_runs("kKKKLKK") == [("k", 1), ("K", 3), ("L", 1),
                                       ("K", 2)]
    assert sum(jax.tree.leaves(frozen)) == 3      # a choice bias a run's sublayer
    specs = Transformer.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
