"""Nemotron-3-Super's layers through the normal path (`Transformer.loss`:
Mamba-2 mixers, attention without rotary embedding, latent expert layers
of plain `relu^2` experts with a sigmoid router, a held share of the heads
and of the experts, a published pattern of single sublayers) against the
plain float32 reference `benchmark/reference/nemotron_h_f32.py`, which
shares no code with `ray_tpu`: seeded random weights, small sizes, on the
CPU, float32 against float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the scan in chunks against the recurrence step by step, a
grouped matmul over sorted rows against a masked loop over the resident
experts, attention whole against attention by blocks of queries): 1e-4
relative to the largest entry of each compared array allows that and
nothing else. Every published term has a case below that fails without
it.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.models.configs import pattern_runs
from ray_tpu.ops import moe, ssm

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402
from tests._programs import programs, value_and_grads  # noqa: E402

ref = load_module("reference", "nemotron_h_f32")
job = load_module("jobs", "train_lm_ssm_moe")

RTOL = 1e-4
SEQ = 64
E, K = 16, 6
PATTERN = "MEMEM*E"
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*E"
                     "MEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def config(held=0, offset=0, **kw):
    base = dict(
        vocab_size=128, d_model=48, n_layers=len(PATTERN),
        layer_pattern=PATTERN, n_heads=4, n_kv_heads=2, attn_head_dim=8,
        rope=False, d_ff=20, max_seq_len=SEQ, dtype="float32", loss_chunk=0,
        ssm_heads=8, ssm_head_dim=4, ssm_groups=4, ssm_state=8,
        ssm_chunk=16, moe_experts=E, moe_top_k=K, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_routed_scale=5.0, moe_shared_experts=1,
        moe_shared_ff=40, moe_latent=24, moe_act="relu2", moe_gated=False,
        moe_experts_held=held, moe_expert_offset=offset, moe_aux_coeff=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"hybrid_override_pattern": cfg.layer_pattern,
           "mamba_head_dim": cfg.ssm_head_dim,
           "ssm_state_size": cfg.ssm_state, "head_dim": cfg.head_dim,
           "layer_norm_epsilon": cfg.norm_eps,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk, "n_group": 1,
           "topk_group": 1,
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


def weights(cfg, seed):
    """Random weights with every gain off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place or left out), a convolution bias and a
    D that are not their initial values, router logits of order 1 as at
    the published width, and a choice bias that is not zero."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))
    for sub in subs_of(params):
        for name in ("ssm_norm", "attn_norm", "mlp_norm", "gate_norm", "D"):
            if name in sub:
                sub[name] = 1.0 + 0.3 * jax.random.normal(
                    next(keys), sub[name].shape)
        if "w_router" in sub:
            sub["w_router"] = sub["w_router"] * 6.0
            sub["router_bias"] = 0.2 * jax.random.normal(
                next(keys), sub["router_bias"].shape)
        if "dt_bias" in sub:    # dt of order 0.1 to 1: the state matters
            sub["dt_bias"] = sub["dt_bias"] + 3.0
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    return params


def share_of(params, held, offset):
    """The leaves a chip holding experts offset..offset+held keeps."""
    runs = [[dict(sub, **{name: sub[name][:, offset:offset + held]
                          for name in ("w_moe_up", "w_moe_down")
                          if name in sub}) for sub in run]
            for run in params["runs"]]
    return dict(params, runs=runs)


def batch(cfg, seed, rows=2):
    return jax.random.randint(jax.random.key(100 + seed),
                              (rows, SEQ + 1), 0, cfg.vocab_size)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def rel_diff(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---- the model against the reference --------------------------------------

SHARES = {"all_held": (0, 0), "share_4_of_16": (4, 8)}


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("seed", [0, 3])
def test_logits_and_loss_match_the_reference(share, seed):
    held, offset = SHARES[share]
    cfg = config(held, offset)
    params = weights(config(), seed)
    if held:
        params = share_of(params, held, offset)
    tokens = batch(cfg, seed)
    logits = programs(cfg).logits(params, tokens[:, :-1])
    loss, metrics = programs(cfg).loss(params, {"tokens": tokens})
    w = job.to_reference_layout(params, cfg)
    experts = [lw for lw in w["layers"] if "experts" in lw]
    assert sorted(experts[0]["experts"]) == list(
        range(offset, offset + cfg.held_experts))
    want, chosen = reference(cfg).forward(w, tokens[:, :-1])
    assert_close(logits, want, "logits")
    assert abs(float(loss) - float(ref.next_token_loss(
        want, tokens[:, 1:]))) < RTOL
    # the counters are the reference's choices of the held experts
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    got = np.asarray(metrics["moe_tokens_per_expert"])
    assert got.shape == (PATTERN.count("E"), cfg.held_experts)
    np.testing.assert_array_equal(
        got, counts[:, offset:offset + cfg.held_experts])
    assert int(metrics["moe_dropped"]) == 0
    if held:
        np.testing.assert_array_equal(
            np.asarray(metrics["moe_slots_elsewhere"]),
            tokens[:, :-1].size * K - got.sum(-1))


def from_reference_layout(grads, cfg):
    """The reference's gradients in the program's stacked layout."""
    d = cfg.d_model
    layers = iter(grads["layers"])
    runs = []
    for block, repeats in cfg.pattern_runs:
        per_repeat = []
        for _ in range(repeats):
            subs = []
            for kind in block:
                g = next(layers)
                if kind == "M":
                    subs.append({
                        "ssm_norm": g["norm"], "w_in": g["in_proj"].T,
                        "conv_w": g["conv1d"], "conv_b": g["conv1d_bias"],
                        "dt_bias": g["dt_bias"], "A_log": g["A_log"],
                        "D": g["D"], "gate_norm": g["mixer_norm"],
                        "w_out": g["out_proj"].T})
                elif kind == "*":
                    hd = cfg.head_dim
                    subs.append({
                        "attn_norm": g["norm"],
                        "wq": g["q_proj"].T.reshape(d, -1, hd),
                        "wkv": jnp.stack(
                            [g["k_proj"].T.reshape(d, -1, hd),
                             g["v_proj"].T.reshape(d, -1, hd)], axis=1),
                        "wo": g["o_proj"].T.reshape(-1, hd, d)})
                else:
                    ids = sorted(g["experts"])
                    subs.append({
                        "mlp_norm": g["norm"], "w_router": g["gate"].T,
                        "w_latent_down": g["fc1_latent_proj"].T,
                        "w_latent_up": g["fc2_latent_proj"].T,
                        "w_moe_up": jnp.stack(
                            [g["experts"][e]["up_proj"].T for e in ids]),
                        "w_moe_down": jnp.stack(
                            [g["experts"][e]["down_proj"].T for e in ids]),
                        "w_shared_up": g["shared_experts"]["up_proj"].T,
                        "w_shared_down":
                            g["shared_experts"]["down_proj"].T})
            per_repeat.append(subs)
        runs.append([jax.tree.map(lambda *x: jnp.stack(x),
                                  *[r[i] for r in per_repeat])
                     for i in range(len(block))])
    return {"embed": grads["embed_tokens"], "final_norm": grads["norm_f"],
            "lm_head": grads["lm_head"].T, "runs": runs}


@functools.lru_cache(maxsize=None)
def gradient_case():
    """(weights, tokens, the reference's gradients): remat changes the
    program, not what it is held to."""
    cfg = config()
    params = weights(cfg, 1)
    tokens = batch(cfg, 1)
    w = job.to_reference_layout(params, cfg)
    return params, tokens, reference(cfg).loss_and_grads(w, tokens)[1]


@pytest.mark.parametrize("remat", [False, True])
def test_gradients_match_jax_grad_of_the_reference(remat):
    cfg = config(remat=remat)
    params, tokens, ref_grads = gradient_case()
    _, grads = programs(cfg).grads(params, {"tokens": tokens})
    # the bias enters the choice only: no gradient on either side
    for sub in subs_of(grads):
        if "router_bias" in sub:
            assert not np.asarray(sub.pop("router_bias")).any()
    for g in ref_grads["layers"]:
        if "e_score_correction_bias" in g:
            assert not np.asarray(g["e_score_correction_bias"]).any()
    want = from_reference_layout(ref_grads, cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), exp in zip(flat, jax.tree.leaves(want)):
        assert np.abs(np.asarray(exp)).max() > 0, path
        assert_close(got, exp, jax.tree_util.keystr(path), rtol=2e-4)


# ---- the chunked scan against the recurrence ------------------------------


def scan_inputs(seed, t=64, h=8, p=4, g=4, n=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (2, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (2, t, g, n))
    c = jax.random.normal(ks[4], (2, t, g, n))
    return x, dt, a, b, c


@functools.lru_cache(maxsize=None)
def chunked(chunk):
    """`ssd_scan` under one `jax.jit` a chunk length."""
    return jax.jit(lambda *v: ssm.ssd_scan(*v, chunk))


@jax.jit
def recurrence(x, dt, a, b, c):
    """The reference's step-by-step scan, without the D skip."""
    rep = x.shape[2] // b.shape[2]
    with jax.default_matmul_precision("highest"):
        return ref.selective_scan(
            x, dt, a, jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2),
            jnp.zeros(x.shape[2]))


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("seed", [0, 1])
def test_chunked_scan_is_the_recurrence_forward(chunk, seed):
    args = scan_inputs(seed)
    assert_close(chunked(chunk)(*args), recurrence(*args), "y")


@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_scan_is_the_recurrence_backward(chunk):
    args = scan_inputs(2)
    probe = jax.random.normal(jax.random.key(7), args[0].shape)
    _, got = value_and_grads(chunked(chunk))(probe, *args)
    _, want = value_and_grads(recurrence)(probe, *args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert_close(g, w, "d" + name, rtol=2e-4)


def test_a_long_decay_does_not_overflow_above_the_diagonal():
    """dt x |A| of 40 a step: exp(+sum) above the diagonal would be inf,
    and inf x 0 a NaN in the value or the gradient."""
    x, dt, a, b, c = scan_inputs(3)
    dt, a = dt * 0 + 5.0, a * 0 - 8.0
    y, grads = jax.jit(jax.value_and_grad(
        lambda x: jnp.sum(ssm.ssd_scan(x, dt, a, b, c, 16))))(x)
    assert np.isfinite(float(y)) and np.isfinite(np.asarray(grads)).all()
    assert_close(chunked(16)(x, dt, a, b, c),
                 recurrence(x, dt, a, b, c), "y")


def test_a_length_that_is_no_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="whole chunks"):
        ssm.ssd_scan(*scan_inputs(0, t=40), 16)
    with pytest.raises(ValueError, match="whole chunks"):
        job.transformer_config(dict(
            _tiny_model(), max_position_embeddings=4096), _tiny_model()[
                "train"], 100)


def _tiny_model():
    from benchlib.spec import load_json
    return load_json(os.path.join(BENCH_DIR, "rehearsal", "configs",
                                  "tiny-nemotron-h.json"))


def test_causal_conv_is_the_references():
    x = jax.random.normal(jax.random.key(0), (2, 20, 6))
    w = jax.random.normal(jax.random.key(1), (6, 4))
    bias = jax.random.normal(jax.random.key(2), (6,))
    want = ref.causal_conv1d(x, w, bias)
    assert_close(ssm.causal_conv(x, w, bias), want, "conv")
    # causal: the first output sees the first input through the last tap
    np.testing.assert_allclose(want[:, 0], bias + x[:, 0] * w[:, 3],
                               rtol=1e-6)


# ---- the shares add up ------------------------------------------------------


def mixer_leaves(params, i=0):
    sub = params["runs"][0][0]
    assert "ssm_norm" in sub
    return {name: leaf[i] for name, leaf in sub.items()}


def mixer_share(lp, cfg, s, ways):
    """Share s of `ways` of a mixer: its groups with their heads: columns
    of w_in, channels of the convolution and the norm, rows of w_out."""
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    inner = h * p
    heads = np.arange(s * h // ways, (s + 1) * h // ways)
    chan = (heads[:, None] * p + np.arange(p)).reshape(-1)
    groups = np.arange(s * g // ways, (s + 1) * g // ways)
    state = (groups[:, None] * n + np.arange(n)).reshape(-1)
    conv = np.concatenate([chan, inner + state, inner + g * n + state])
    cols = np.concatenate([chan, inner + conv,
                           2 * inner + 2 * g * n + heads])
    return {"w_in": lp["w_in"][:, cols], "conv_w": lp["conv_w"][conv],
            "conv_b": lp["conv_b"][conv], "dt_bias": lp["dt_bias"][heads],
            "A_log": lp["A_log"][heads], "D": lp["D"][heads],
            "gate_norm": lp["gate_norm"][chan], "w_out": lp["w_out"][chan]}


def test_the_head_shares_of_a_mixer_add_up_to_the_uncut_mixer():
    """8 heads in 4 groups over 4 shares of 2 heads and a group each: the
    parts of out_proj's sum the four shares give are the uncut
    reference's mixer output, and each is the reference's given the same
    share."""
    cfg = config()
    params = weights(cfg, 5)
    lp = mixer_leaves(params)
    x = jax.random.normal(jax.random.key(9), (2, SEQ, cfg.d_model))
    kw = dict(head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
              chunk=cfg.ssm_chunk, eps=cfg.norm_eps)
    lw = job.to_reference_layout(params, cfg)["layers"][0]
    pub = published(cfg)
    with jax.default_matmul_precision("highest"):
        whole = ref.mamba2_mixer(x, lw, pub)
    assert_close(ssm.mamba2_mixer(x, lp, **kw), whole, "uncut")
    total = 0.0
    for s in range(4):
        share = mixer_share(lp, cfg, s, 4)
        part = ssm.mamba2_mixer(x, share, **kw)
        with jax.default_matmul_precision("highest"):
            want = ref.mamba2_mixer(x, {
                "in_proj": share["w_in"].T, "conv1d": share["conv_w"],
                "conv1d_bias": share["conv_b"],
                "dt_bias": share["dt_bias"], "A_log": share["A_log"],
                "D": share["D"], "mixer_norm": share["gate_norm"],
                "out_proj": share["w_out"].T}, pub)
        assert_close(part, want, f"share {s}")
        assert rel_diff(part, whole) > 0.1      # a part, not the whole
        total = total + part
    assert_close(total, whole, "the shares' sum")


def test_the_head_shares_of_attention_add_up_to_the_uncut_block():
    """4 query heads over 2 key/value heads, 2 shares of a key/value head
    with its 2 query heads: through the model's own layer function."""
    cfg = config(n_layers=1, layer_pattern="*", moe_experts=0,
                 moe_shared_experts=0, moe_latent=0)
    params = Transformer.init(jax.random.key(4), cfg)
    sub = {k: v[0] for k, v in params["runs"][0][0].items()}
    x = jax.random.normal(jax.random.key(9), (2, SEQ, cfg.d_model))
    from ray_tpu.parallel.sharding import ShardingRules

    def block(leaves, cfg):
        layer = Transformer._make_layer_fn(cfg, None, ShardingRules(), None,
                                           None, seq_len=SEQ)
        return layer(x, leaves)[0] - x

    whole = block(sub, cfg)
    lw = job.to_reference_layout(params, cfg)["layers"][0]
    n = ref.rms_norm(x, lw["norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        assert_close(whole, ref.attention(n, lw, published(cfg)), "uncut")
    half = cfg.replace(n_heads=2, n_kv_heads=1)
    total = 0.0
    for s in range(2):
        part = block({"attn_norm": sub["attn_norm"],
                      "wq": sub["wq"][:, 2 * s:2 * s + 2],
                      "wkv": sub["wkv"][:, :, s:s + 1],
                      "wo": sub["wo"][2 * s:2 * s + 2]}, half)
        assert rel_diff(part, whole) > 0.1
        total = total + part
    assert_close(total, whole, "the shares' sum")


def expert_layer(params, i=0):
    """The program's leaves of an expert sublayer, as moe_ffn takes them."""
    sub = params["runs"][0][1]
    assert "w_router" in sub
    return {name.replace("w_moe_", "w_"): leaf[i]
            for name, leaf in sub.items() if name != "mlp_norm"}


ROUTE = dict(num_selected=K, norm_topk=True, scoring="sigmoid",
             routed_scale=5.0, act="relu2")


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """E = 16 over 4 shares of 4, fewer than the 6 a token picks: the
    routed parts the four shares give (each through its own up-projection
    out of the latent), plus the shared expert counted once, are the uncut
    reference's layer output; the held slots of the shares sum to N x k
    and no slot is in two shares."""
    cfg = config()
    params = weights(cfg, 5)
    x = jax.random.normal(jax.random.key(9), (96, cfg.d_model))
    lw = job.to_reference_layout(params, cfg)["layers"][1]
    pub = published(cfg)
    with jax.default_matmul_precision("highest"):
        whole, top_e = ref.latent_experts(x, lw, pub)
    layer = expert_layer(params)
    shared = moe.shared_ffn(layer["w_shared_up"], layer["w_shared_down"], x,
                            "relu2")
    total = shared                     # what every chip computes alike: once
    held_counts, elsewhere = [], []
    for offset in range(0, E, 4):
        share = {k: v for k, v in layer.items()
                 if not k.startswith("w_shared")}
        share.update(w_up=layer["w_up"][offset:offset + 4],
                     w_down=layer["w_down"][offset:offset + 4])
        y, routing = moe.moe_ffn(share, x, expert_offset=offset, **ROUTE)
        total = total + y
        held_counts.append(np.asarray(routing["tokens_per_expert"]))
        elsewhere.append(int(routing["slots_elsewhere"]))
        assert int(routing["dropped"]) == 0
        with jax.default_matmul_precision("highest"):
            part, _ = ref.latent_experts(x, dict(lw, experts={
                e: w for e, w in lw["experts"].items()
                if offset <= e < offset + 4}), pub)
        assert_close(y + shared, part, f"share at {offset}")
    assert_close(total, whole, "the shares' sum")
    counts = np.concatenate(held_counts)
    assert counts.sum() == x.shape[0] * K
    want = np.bincount(np.asarray(top_e).reshape(-1), minlength=E)
    np.testing.assert_array_equal(counts, want)
    for share_counts, rest in zip(held_counts, elsewhere):
        assert share_counts.sum() + rest == x.shape[0] * K


@pytest.mark.parametrize("held,offset", [(4, 0), (4, 12), (2, 6)])
def test_the_bound_never_drops_a_held_slot(held, offset):
    """Every token on the held experts (a bias that puts them on top): all
    N x min(k, held) rows past the sort are held slots, none is dropped,
    and the result is the reference's."""
    cfg = config()
    params = weights(cfg, 6)
    layer = {k: v for k, v in expert_layer(params).items()
             if not k.startswith("w_shared")}
    layer.update(w_up=layer["w_up"][offset:offset + held],
                 w_down=layer["w_down"][offset:offset + held],
                 router_bias=jnp.zeros(E).at[offset:offset + held].set(4.0))
    x = jax.random.normal(jax.random.key(2), (80, cfg.d_model))
    y, routing = moe.moe_ffn(layer, x, expert_offset=offset, **ROUTE)
    counts = np.asarray(routing["tokens_per_expert"])
    np.testing.assert_array_equal(counts, np.full(held, 80))
    assert int(routing["slots_elsewhere"]) == 80 * (K - held)
    assert int(routing["dropped"]) == 0
    lw = job.to_reference_layout(params, cfg)["layers"][1]
    lw = dict(lw, e_score_correction_bias=layer["router_bias"],
              experts={e: w for e, w in lw["experts"].items()
                       if offset <= e < offset + held})
    with jax.default_matmul_precision("highest"):
        want, _ = ref.latent_experts(x, lw, published(cfg))
        want = want - ref.plain_mlp(x, lw["shared_experts"]["up_proj"],
                                    lw["shared_experts"]["down_proj"])
    assert_close(y, want, "held part")


def test_the_bounded_path_moves_min_k_held_rows():
    """Past the sort the rows are N x min(k, held): the jaxpr of a share of
    4 at k = 6 has no array of N x k rows of the latent's width."""
    cfg = config()
    layer = {k: v for k, v in expert_layer(weights(cfg, 6)).items()
             if not k.startswith("w_shared")}
    layer.update(w_up=layer["w_up"][:4], w_down=layer["w_down"][:4])
    x = jnp.zeros((80, cfg.d_model))
    text = str(jax.make_jaxpr(lambda x: moe.moe_ffn(layer, x, **ROUTE))(x))
    assert f"f32[{80 * 4},{cfg.moe_latent}]" in text
    assert f"[{80 * K},{cfg.moe_latent}]" not in text
    assert f"[{80 * K},{cfg.ff_dim}]" not in text


# ---- each published term has a case that fails without it ---------------

TERMS = {
    "routed_scaling_factor": dict(cfg=dict(moe_routed_scale=1.0)),
    "norm_topk_prob": dict(cfg=dict(moe_norm_topk=False)),
    "relu2_not_silu": dict(cfg=dict(moe_act="silu")),
    "no_rotary_embedding": dict(cfg=dict(rope=True)),
    "bias_in_the_choice": dict(zero="router_bias"),
    "shared_expert": dict(zero="w_shared_down"),
    "latent_up_projection": dict(scale=("w_latent_up", 2.0)),
    "conv_bias": dict(zero="conv_b"),
    "D_skip": dict(zero="D"),
    "dt_bias": dict(zero="dt_bias"),
    "gated_norm_gain": dict(scale=("gate_norm", 0.0, 1.0)),
    "A_log": dict(scale=("A_log", 0.0)),
}


@functools.lru_cache(maxsize=None)
def term_case():
    """(weights, tokens, the reference's logits) every term is read at."""
    cfg = config()
    params = weights(cfg, 2)
    tokens = batch(cfg, 2)
    w = job.to_reference_layout(params, cfg)
    return params, tokens, reference(cfg).forward(w, tokens[:, :-1])[0]


@pytest.mark.parametrize("term", TERMS)
def test_a_term_left_out_of_the_program_fails(term):
    """The comparison sees each term: the program with the term changed
    is far from the reference with it."""
    how = TERMS[term]
    cfg = config()
    params, tokens, want = term_case()
    changed = jax.tree.map(lambda x: x, params)
    for sub in subs_of(changed):
        if how.get("zero") in sub:
            sub[how["zero"]] = jnp.zeros_like(sub[how["zero"]])
        if "scale" in how and how["scale"][0] in sub:
            name, factor, *shift = how["scale"]
            sub[name] = sub[name] * factor + (shift[0] if shift else 0.0)
    got = programs(cfg.replace(**how.get("cfg", {}))).logits(
        changed, tokens[:, :-1])
    assert rel_diff(got, want) > 30 * RTOL, term


def test_the_choice_bias_is_a_buffer():
    cfg = config(4, 8)
    frozen = Transformer.frozen(cfg)
    flagged = [path for path, keep in
               jax.tree_util.tree_flatten_with_path(frozen)[0] if keep]
    assert len(flagged) == sum(len(b.replace("M", "").replace("*", ""))
                               for b, _ in cfg.pattern_runs)
    assert all("router_bias" in jax.tree_util.keystr(p) for p in flagged)


# ---- the pattern, the count, what is refused --------------------------------


@pytest.mark.parametrize("pattern,want", [
    ("MEMEMEMEM*E", [("ME", 4), ("M*E", 1)]),
    ("MEMEM*E", [("ME", 2), ("M*E", 1)]),
    ("M", [("M", 1)]), ("MMMM", [("M", 4)]), ("ME*", [("ME*", 1)]),
    (PUBLISHED_PATTERN, [("MEMEMEM*E", 3), ("MEMEMEMEM*E", 4), ("ME", 3),
                         ("M*", 1), ("EM", 4), ("E", 1)]),
])
def test_pattern_runs(pattern, want):
    runs = pattern_runs(pattern)
    assert runs == want
    assert "".join(block * repeats for block, repeats in runs) == pattern


PUBLISHED = dict(
    d_model=4096, attn_head_dim=128, d_ff=2688, max_seq_len=8192, rope=False,
    ssm_head_dim=64, ssm_state=128, ssm_chunk=128, moe_top_k=22,
    moe_scoring="sigmoid", moe_routed_scale=5.0, moe_shared_experts=1,
    moe_shared_ff=5376, moe_latent=1024, moe_act="relu2", moe_gated=False,
    moe_experts=512)


@pytest.mark.parametrize("name,kw,want", [
    ("published", dict(vocab_size=131072, n_layers=88, n_heads=32,
                       n_kv_heads=2, layer_pattern=PUBLISHED_PATTERN,
                       ssm_heads=128, ssm_groups=8), 120_668_687_360),
    ("the_cells_cut", dict(vocab_size=16384, n_layers=11, n_heads=8,
                           n_kv_heads=1, layer_pattern="MEMEMEMEM*E",
                           ssm_heads=32, ssm_groups=2, moe_experts_held=8),
     773_579_744),
])
def test_num_params_at_the_published_widths(name, kw, want):
    """The published 88 layers count 120.67B (the name's 120B; PERF.md
    section 4 prints this number), the cell's cut ISSUE 35's table."""
    cfg = TransformerConfig(**PUBLISHED, **kw)
    shapes = jax.eval_shape(lambda k: Transformer.init(k, cfg),
                            jax.random.key(0))
    counted = sum(int(np.prod(x.shape)) for x, keep in zip(
        jax.tree.leaves(shapes), jax.tree.leaves(Transformer.frozen(cfg)))
        if not keep)
    assert counted == cfg.num_params == want
    if name == "the_cells_cut":
        per = {kind: sum(int(np.prod(x.shape[1:])) for n, x in sub.items()
                         if n != "router_bias")
               for kind, sub in zip("ME", shapes["runs"][0])}
        assert per == {"M": 27_413_088, "E": 98_570_240}


@pytest.mark.parametrize("kw,why", [
    (dict(layer_pattern="MEX"), "characters"),
    (dict(layer_pattern="ME"), "characters"),
    (dict(ssm_heads=0), "ssm_heads"),
    (dict(ssm_heads=6), "ssm_heads"),
    (dict(moe_experts=0, moe_shared_experts=0), "expert layers"),
    (dict(moe_act="gelu"), "moe_act"),
])
def test_config_refuses(kw, why):
    with pytest.raises(ValueError, match=why):
        config(**kw)


def test_param_specs_cover_every_leaf_and_shard_on_a_mesh():
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import shard_pytree

    cfg = config(4, 8)
    shapes = jax.eval_shape(lambda k: Transformer.init(k, cfg),
                            jax.random.key(0))
    specs = Transformer.param_specs(cfg)
    is_spec = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.tree.map(lambda _: 0, specs, is_leaf=is_spec))
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs, is_leaf=is_spec)):
        assert len(spec) == len(leaf.shape), (spec, leaf.shape)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    shard_pytree(specs, mesh)


def test_a_train_step_runs_and_leaves_the_bias_bit_identical():
    import optax

    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    cfg = config(4, 8, remat=True)
    params = share_of(weights(config(), 4), 4, 8)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_state, step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True),
        Transformer.param_specs(cfg), mesh,
        optimizer=optax.adamw(1e-2, weight_decay=0.1),
        frozen=Transformer.frozen(cfg))
    before = job.router_bias(params)
    state = init_state(params)
    losses = []
    for i in range(3):
        state, metrics = step(state, {"tokens": batch(cfg, 0)})
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    after = job.router_bias(state["params"])
    assert before.any() and before.tobytes() == after.tobytes()
    assert metrics["moe_tokens_per_expert"].shape == (3, 4)
    assert metrics["moe_slots_elsewhere"].shape == (3,)
