"""Olmo-Hybrid-7B's layers through the normal path (`Transformer.loss`:
Gated DeltaNet mixers and full attention with a QK-norm over the whole
projection and no rotary embedding, each then a dense MLP, every sublayer
under the reordered norm `x + norm(f(x))`, a held share of the heads)
against the plain float32 reference
`benchmark/reference/olmo_hybrid_f32.py`, which shares no code with
`ray_tpu`: seeded random weights, small sizes, on the CPU, float32 against
float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the delta rule in chunks with a triangular inverse against
the recurrence step by step, attention whole against attention by blocks
of queries): 1e-4 relative to the largest entry of each compared array
allows that and nothing else. Every published term has a case below that
fails without it.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import kda

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402
from tests._programs import programs  # noqa: E402

ref = load_module("reference", "olmo_hybrid_f32")
faults = load_module("reference", "olmo_hybrid_faults")
job = load_module("jobs", "train_lm_gdn")

RTOL = 1e-4
SEQ = 80          # two chunks of 32 and a half
PATTERN = "dddad"
HEADS, HD, DK, DV = 4, 8, 12, 24
INIT = {"embed_std": 1.0, "norm_gain_std": 0.3, "gdn_A_range": [0.25, 2.0],
        "gdn_dt_bias_range": [-4.0, -1.0]}


def config(heads=HEADS, pattern=PATTERN, **kw):
    base = dict(
        vocab_size=128, d_model=48, n_layers=len(pattern),
        layer_pattern=pattern, n_heads=heads, n_kv_heads=heads,
        attn_head_dim=HD, qk_norm=True, rope=False, d_ff=64,
        max_seq_len=SEQ, dtype="float32", loss_chunk=0, norm_eps=1e-6,
        gdn_heads=heads, gdn_key_dim=DK, gdn_value_dim=DV,
        gdn_neg_eigval=True, gdn_chunk=32)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"rms_norm_eps": cfg.norm_eps, "head_dim": cfg.head_dim,
           "linear_allow_neg_eigval": cfg.gdn_neg_eigval,
           "linear_value_head_dim": cfg.gdn_value_dim,
           "rope_parameters": {"rope_theta": None}}
    out.update(over)
    return out


def reference(cfg):
    """The reference at `cfg`'s published keys under `jax.jit`
    (`tests/_programs.reference`)."""
    return _programs.reference(ref, published, cfg)


def weights(cfg, seed):
    """The job's stand-in weights (every gain off 1, the decays spread)
    with the final norm's gain off 1 too."""
    params = job.init_params(jax.random.key(seed), cfg, INIT)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.key(seed + 1), params["final_norm"].shape)
    return params


def tokens(seed, batch=2):
    return jax.random.randint(jax.random.key(seed), (batch, SEQ + 1), 0, 128)


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


@pytest.fixture(scope="module")
def both_sides():
    cfg = config()
    params, toks = weights(cfg, 0), tokens(1)
    with jax.default_matmul_precision("highest"):
        loss, grads = programs(cfg).grads(params, {"tokens": toks})
        ref_loss, ref_grads = reference(cfg).loss_and_grads(
            job.to_reference_layout(params, cfg), toks)
    return cfg, params, toks, (loss, job.to_reference_layout(grads, cfg)), \
        (ref_loss, ref_grads)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_logits_match_the_reference(seed):
    cfg = config()
    params, toks = weights(cfg, seed), tokens(seed + 10)
    with jax.default_matmul_precision("highest"):
        got = programs(cfg).logits(params, toks[:, :-1])
        want = reference(cfg).forward(job.to_reference_layout(params, cfg),
                                      toks[:, :-1])
    close(got, want)


def test_loss_matches_the_reference(both_sides):
    _, _, _, (loss, _), (ref_loss, _) = both_sides
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)


MIXER_LEAVES = ["q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d",
                "v_conv1d", "a_proj", "b_proj", "A_log", "dt_bias", "g_proj",
                "o_norm", "o_proj"]
ATTENTION_LEAVES = ["q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
                    "o_proj"]
BOTH = ["post_attention_layernorm", "gate_proj", "up_proj", "down_proj",
        "post_feedforward_layernorm"]
LEAVES = [(i, name) for i, kind in enumerate(PATTERN)
          for name in (MIXER_LEAVES if kind == "d" else ATTENTION_LEAVES)
          + BOTH]


@pytest.mark.parametrize("layer,name", LEAVES)
def test_a_layers_gradient_matches_the_references(both_sides, layer, name):
    _, _, _, (_, grads), (_, ref_grads) = both_sides
    assert set(grads["layers"][layer]) == set(ref_grads["layers"][layer])
    close(grads["layers"][layer][name], ref_grads["layers"][layer][name])


@pytest.mark.parametrize("name", ["embed_tokens", "norm", "lm_head"])
def test_an_outer_gradient_matches_the_references(both_sides, name):
    _, _, _, (_, grads), (_, ref_grads) = both_sides
    close(grads[name], ref_grads[name])


def test_gradients_match_under_remat(both_sides):
    cfg, params, toks, (_, grads), _ = both_sides
    with jax.default_matmul_precision("highest"):
        _, again = programs(cfg.replace(remat=True)).grads(
            params, {"tokens": toks})
    for got, want in zip(jax.tree.leaves(job.to_reference_layout(again, cfg)),
                         jax.tree.leaves(grads)):
        close(got, want)


@functools.lru_cache(maxsize=None)
def variants_base():
    """(configuration, tokens, weights in the reference's layout, its
    logits) every variant below is read against: op by op, as the
    variants run."""
    cfg = config()
    params, toks = weights(cfg, 3), tokens(4)
    layout = job.to_reference_layout(params, cfg)
    with jax.default_matmul_precision("highest"):
        return cfg, toks, layout, ref.forward(layout, toks[:, :-1],
                                              published(cfg))


@pytest.mark.parametrize("name", faults.FAULTS)
def test_each_fault_moves_the_logits(name):
    """What the system matches to 1e-4 a term left out misses by a
    hundred times that or more: every term is in the comparison."""
    cfg, toks, layout, base = variants_base()
    with jax.default_matmul_precision("highest"):
        module, model, w = faults.variant(name, published(cfg), layout)
        moved = module.forward(w, toks[:, :-1], model)
    rel = float(jnp.sqrt(jnp.sum((moved - base) ** 2) / jnp.sum(base ** 2)))
    # (without the L2 norm beta k k^T passes 1 and the state leaves float32)
    assert not rel <= 100 * RTOL, rel
    # the variant is made on a copy: the reference itself stays plain
    assert module is not ref and ref.rotary(1, 2) == (1, 2)


@pytest.mark.parametrize("name,passes", [("bfloat16", True),
                                         ("float8_e4m3fn", False),
                                         ("float8_e5m2", False)])
def test_narrower_operands_read_as_they_should(name, passes):
    cfg, toks, layout, base = variants_base()
    with jax.default_matmul_precision("highest"):
        module, model, w = faults.variant(name, published(cfg), layout)
        moved = module.forward(w, toks[:, :-1], model)
    rel = float(jnp.sqrt(jnp.sum((moved - base) ** 2) / jnp.sum(base ** 2)))
    assert (rel < 0.2) == passes, rel   # a tiny model: 0.07 | 0.4 | 0.6


# ---- a share of the heads ------------------------------------------------

MIXER_AXIS = {"w_gdn_qkv": 1, "gdn_conv": 0, "w_gdn_ab": 2, "gdn_A_log": 0,
              "gdn_dt_bias": 0, "w_gdn_g": 1, "w_gdn_out": 0}


def mixer_share(sub, lo, hi):
    return {name: jax.lax.slice_in_dim(leaf, lo, hi, axis=MIXER_AXIS[name])
            if name in MIXER_AXIS else leaf for name, leaf in sub.items()}


def test_two_shares_of_the_mixers_heads_add_up_to_the_whole_mixer():
    """Heads 0-1 and heads 2-3 of the mixer, each as the program computes
    its share, add up to what the REFERENCE gives for all four heads,
    before the norm on the sublayer's output (which a deployment applies
    after its all-reduce)."""
    cfg = config(pattern="d")
    params = weights(cfg, 5)
    sub = jax.tree.map(lambda x: x[0], params["runs"][0][0])
    x = jax.random.normal(jax.random.key(6), (2, SEQ, cfg.d_model))
    layer = job.to_reference_layout(params, cfg)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want = ref.gated_delta_net(x, layer, published(cfg))
        mixer = jax.jit(lambda lp: kda.gdn_mixer(
            x, lp, chunk=32, beta_scale=2.0, eps=cfg.norm_eps))
        parts = [mixer(mixer_share(sub, lo, hi))
                 for lo, hi in ((0, 2), (2, 4))]
    close(parts[0] + parts[1], want)
    assert float(jnp.abs(parts[0]).max()) > 0.1 * float(jnp.abs(want).max())


def test_a_share_of_the_heads_is_the_reference_given_the_same_share():
    """Two of four heads of both kinds of layer through the whole model,
    against the reference given the same leaves: the QK-norm's mean of
    squares and the norm on the sublayer's output then see the held
    columns and the held part of W_o's sum, on both sides alike (the
    configuration's two stated departures)."""
    whole = config()
    held = config(heads=2)
    params = weights(whole, 7)
    axis = dict(MIXER_AXIS, wqkv=2, wo=0)

    def cut(name, leaf):   # after the layers' axis
        if name in axis:
            return jax.lax.slice_in_dim(leaf, 0, 2, axis=axis[name] + 1)
        if name in ("q_norm", "k_norm"):
            return leaf[:, :2 * HD]
        return leaf

    share = dict(params, runs=[[{n: cut(n, leaf) for n, leaf in sub.items()}
                                for sub in run] for run in params["runs"]])
    toks = tokens(8)
    with jax.default_matmul_precision("highest"):
        got = programs(held).logits(share, toks[:, :-1])
        want = reference(held).forward(job.to_reference_layout(share, held),
                                       toks[:, :-1])
        full = programs(whole).logits(params, toks[:, :-1])
    close(got, want)
    assert float(jnp.abs(got - full).max()) > 0.01   # a share is no whole


# ---- the program's side ----------------------------------------------------


def test_the_kinds_leaves_and_the_parameter_count():
    cfg = config()
    params = Transformer.init(jax.random.key(0), cfg)
    assert cfg.pattern_runs == [("d", 3), ("ad", 1)]
    mixer, attention = params["runs"][0][0], params["runs"][1][0]
    # the reordered norm: no norm opens a sublayer, one closes it
    assert {"gdn_post_norm", "mlp_post_norm"} <= set(mixer)
    assert {"attn_post_norm", "mlp_post_norm"} <= set(attention)
    assert not [n for sub in (mixer, attention) for n in sub
                if n in ("gdn_norm", "attn_norm", "mlp_norm")]
    assert mixer["w_gdn_qkv"].shape == (3, 48, HEADS, 2 * DK + DV)
    assert attention["q_norm"].shape == (1, HEADS * HD)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params
    specs = Transformer.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(
        lambda _: 0, specs, is_leaf=lambda x: isinstance(x, tuple))) \
        == jax.tree.structure(params)
    for spec, leaf in zip(
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.leaves(params)):
        assert len(spec) == leaf.ndim


def test_the_published_widths_count_as_the_issue_counts_them():
    cfg = TransformerConfig(
        vocab_size=12544, d_model=3840, n_layers=4, layer_pattern="ddda",
        n_heads=15, n_kv_heads=15, attn_head_dim=128, d_ff=11008,
        qk_norm=True, rope=False, gdn_heads=15, gdn_key_dim=96,
        gdn_value_dim=192, gdn_neg_eigval=True, norm_eps=1e-6)
    assert cfg._gdn_params == 44_375_262
    assert cfg.num_params == 766_241_946


@pytest.mark.parametrize("kw,why", [
    (dict(gdn_heads=0), "gdn_heads"),
    (dict(kv_lora_rank=16, qk_rope_head_dim=4, v_head_dim=8,
          qk_nope_head_dim=8), "latent"),
    (dict(layer_pattern="dddaz"), "layer_pattern"),
])
def test_a_config_that_cannot_run_is_refused(kw, why):
    with pytest.raises(ValueError, match=why):
        config(**kw)


def test_the_reordered_norm_is_told_by_the_leaves():
    """The one rule of a sublayer's residual: the same mixer leaves with
    the norm's gain under `gdn_norm` run as `x + f(norm(x))`, under
    `gdn_post_norm` as `x + norm(f(x))`."""
    cfg = config(pattern="d")
    params = weights(cfg, 9)
    toks = tokens(2)[:, :-1]
    post = Transformer.apply(params, toks, cfg)
    sub = dict(params["runs"][0][0])
    sub["gdn_norm"] = sub.pop("gdn_post_norm")
    sub["mlp_norm"] = sub.pop("mlp_post_norm")
    pre = Transformer.apply(dict(params, runs=[[sub]]), toks, cfg)
    layout = job.to_reference_layout(params, cfg)
    with jax.default_matmul_precision("highest"):
        module, model, w = faults.variant("norm_before", published(cfg),
                                          layout)
        close(pre, module.forward(w, toks, model), 1e-3)
    assert float(jnp.abs(pre - post).max()) > 0.1
