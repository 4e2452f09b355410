"""Mellum 2's layers through the normal path (`Transformer.loss` with a
`layer_pattern` of the kinds `W` and `L`: window and full attention, each
followed by experts; plain RoPE on the window layers and YaRN on the full
ones; GQA with a per-head QK-norm; the softmax router over experts that
are ALL present) against the plain float32 reference
`benchmark/reference/mellum2_f32.py`, which shares no code with `ray_tpu`
and knows no mesh: seeded random weights, small sizes, on the CPU, float32
against float32, on one device and on virtual meshes of 2 and 4 whose
`fsdp` axis carries the experts (`ops/moe._exchange_ffn`).

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (fused k/v and gate/up matmuls, a grouped matmul over sorted
and exchanged rows against a masked loop over the experts, attention
whole against attention by blocks of queries): 1e-4 relative to the
largest entry of each compared array allows that and nothing else. Every
fault of `benchmark/reference/mellum2_faults.py` has a case below that
moves logits or loss by far more.
"""

import contextlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig, head
from ray_tpu.models.transformer import _rope_tables
from ray_tpu.ops import attention, moe
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules
from ray_tpu.parallel.train_step import make_train_step

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


# ---- the model against the reference --------------------------------------


@pytest.mark.parametrize("devices,k,pattern", [
    (1, 2, "WWWL"), (2, 6, "WL"), (4, 1, "WL"),
    pytest.param(4, 4, "WL", marks=pytest.mark.slow)])
def test_logits_loss_and_gradients_against_the_reference(devices, k,
                                                         pattern):
    """On one device (the cell's four layers) and on meshes of 2 and 4
    (the experts 4 and 2 a shard, top-k below and above that; a window
    and a full layer), with remat as the cell runs it."""
    cfg = config(k=k, remat=True, layer_pattern=pattern,
                 n_layers=len(pattern))
    model = published(cfg)
    mesh = mesh_of(devices)
    params = weights(cfg, 7)
    sample = tokens_of(7)
    tokens, targets = sample[:, :-1], sample[:, 1:]
    got = jax.jit(lambda p: head.logits(p, Transformer.hidden(
        p, tokens, cfg, mesh=mesh, rules=RULES), cfg, mesh=mesh,
        rules=RULES))(params)
    ref_w = job.to_reference_layout(params, cfg)
    want, chosen = ref.forward(ref_w, tokens, model, with_routing=True)
    assert_close(got, want, "logits")
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: Transformer.loss(p, {"tokens": sample}, cfg, mesh=mesh,
                                   rules=RULES, with_metrics=True),
        has_aux=True))(params)
    want_loss, want_grads = jax.jit(lambda w: ref.loss_and_grads(
        w, tokens, targets, model))(ref_w)
    assert abs(float(loss) - float(want_loss)) <= RTOL * float(want_loss)
    got_grads = job.to_reference_layout(grads, cfg)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got_grads)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat_got) == len(flat_want)
    largest = max(float(jnp.abs(leaf).max()) for leaf in flat_want.values())
    for path, leaf in flat_got:
        want_leaf = flat_want[path]
        if float(jnp.abs(want_leaf).max()) < 1e-6 * largest:
            # a gradient that is zero but for rounding (top-1 weights
            # normalised to 1 leave the router none)
            assert float(jnp.abs(leaf).max()) < 1e-6 * largest
        else:
            assert_close(leaf, want_leaf, jax.tree_util.keystr(path))
    np.testing.assert_array_equal(
        metrics["moe_tokens_per_expert"],
        ref.tokens_per_expert(chosen, E))
    assert int(metrics["moe_dropped"]) == 0
    if devices > 1:
        received = np.asarray(metrics["moe_rows_received"])
        assert received.shape == (cfg.n_layers, devices)
        np.testing.assert_array_equal(
            received, np.asarray(metrics["moe_tokens_per_expert"]).reshape(
                cfg.n_layers, devices, -1).sum(-1))
        needed = np.asarray(metrics["moe_exchange_rows_needed"])
        pairs = np.asarray(metrics["moe_exchange_pairs"])
        assert (pairs <= needed).all() and (pairs > 0).all()
        assert (np.asarray(metrics["moe_exchange_rows_sent"])
                >= needed).all()


@pytest.mark.slow
def test_the_mesh_trains_what_one_device_trains():
    """Three adamw steps through `make_train_step` on the 4-device layout
    and on one device: the same losses, the experts' leaves in quarters
    by expert and a projection in quarters along d_model."""
    import optax

    cfg = config(remat=True, loss_chunk=16)
    params = weights(cfg, 3)
    batch = {"tokens": tokens_of(3)}
    losses = {}
    for devices in (1, 4):
        mesh = make_mesh(MeshConfig(data=1, fsdp=devices),
                         devices=jax.devices()[:devices])
        init_state, step = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh, rules=RULES),
            Transformer.param_specs(cfg), mesh, rules=RULES,
            optimizer=optax.adamw(1e-2))
        state = init_state(params)
        losses[devices] = []
        for _ in range(3):
            state, m = step(state, batch)
            losses[devices].append(float(m["loss"]))
    lay = state["params"]["runs"][0][0]
    assert lay["w_moe_gateup"].sharding.spec[1] == "fsdp"
    assert lay["w_moe_gateup"].sharding.spec[2] is None
    assert lay["wq"].sharding.spec[1] == "fsdp"
    assert losses[4][-1] < losses[4][0]
    np.testing.assert_allclose(losses[4], losses[1], rtol=2e-4)


# ---- YaRN -----------------------------------------------------------------


def yarn_numpy(dim, base, factor, original, beta_fast, beta_slow,
               attention_factor=None, truncate=True):
    """The family's `_compute_yarn_parameters`, transcribed: (inv_freq
    [dim / 2], attention_factor, low, high)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low, high = find_correction_dim(beta_fast), find_correction_dim(
        beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    hi = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (hi - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor
                                                           * pos_freqs)
    extrapolation_factor = 1 - ramp
    inv_freq = interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, attention_factor, low, high


def test_yarn_bounds_at_the_published_sizes():
    inv_freq, factor, low, high = yarn_numpy(128, 5e5, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    assert factor == pytest.approx(1.2772588722239782, rel=1e-12)
    plain = 5e5 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-6)
    assert (np.diff(inv_freq) < 0).all()
    assert ref.yarn_bounds(128, dict(ROPE["full_attention"],
                                     original_max_position_embeddings=8192)
                           ) == (18, 35)


@pytest.mark.parametrize("dim,original,given", [(128, 8192, 0.0),
                                                (128, 8192, 1.5),
                                                (16, 32, 0.0),
                                                (64, 4096, 0.0)])
def test_yarn_table_against_the_familys_function(dim, original, given):
    positions = jnp.arange(0, original, 3)[None, :]
    inv_freq, factor, _, _ = yarn_numpy(dim, 5e5, 16, original, 32, 1,
                                        given or None)
    cfg = config(rope_yarn_original_len=original,
                 rope_yarn_attention_factor=given)
    cos, sin = _rope_tables(positions, dim, 5e5, (
        16.0, original, 32.0, 1.0, cfg.yarn_attention_factor))
    angles = np.asarray(positions, np.float64)[..., None] * inv_freq
    # f32 angles of thousands of radians: absolute, not relative
    np.testing.assert_allclose(cos, np.cos(angles) * factor, atol=2e-3)
    np.testing.assert_allclose(sin, np.sin(angles) * factor, atol=2e-3)
    rope = dict(ROPE["full_attention"],
                original_max_position_embeddings=original,
                attention_factor=given or None)
    ref_cos, ref_sin = ref.rope_tables(positions[0], dim, rope)
    np.testing.assert_allclose(cos[0], ref_cos[:, :dim // 2], atol=2e-3)
    np.testing.assert_allclose(sin[0], ref_sin[:, :dim // 2], atol=2e-3)


def test_plain_rope_is_untouched_by_yarn():
    positions = jnp.arange(200)[None, :]
    cos, sin = _rope_tables(positions, 128, 5e5)
    freqs = 5e5 ** (-np.arange(64) / 64.0)
    angles = np.arange(200)[:, None] * freqs
    np.testing.assert_allclose(cos[0], np.cos(angles), atol=1e-4)
    np.testing.assert_allclose(sin[0], np.sin(angles), atol=1e-4)
    same = _rope_tables(positions, 128, 5e5, None)
    np.testing.assert_array_equal(cos, same[0])


def test_a_layer_takes_the_table_its_kind_says():
    """A model of window layers alone reads the plain table whatever
    YaRN's sizes; a model of full layers alone does not."""
    tokens = tokens_of(5, rows=1)[:, :-1]
    for pattern, moved in (("W", False), ("L", True)):
        cfg = config(n_layers=1, layer_pattern=pattern)
        params = weights(cfg, 5)
        with_yarn = Transformer.hidden(params, tokens, cfg)
        without = Transformer.hidden(
            params, tokens, cfg.replace(rope_yarn_factor=0.0))
        differs = float(jnp.abs(with_yarn - without).max()) > 1e-3
        assert differs == moved, pattern


# ---- the window -------------------------------------------------------------


@pytest.mark.parametrize("window", [
    pytest.param(128, marks=pytest.mark.slow), 1024])
def test_window_kernel_in_interpret_mode_against_dense(window):
    """The splash call a `W` layer makes (GQA, `LocalMask`) against
    `dense_attention(window=)`, forward and the three gradients."""
    t, h, hkv, d = 2048, 4, 2, 128
    keys = jax.random.split(jax.random.key(window), 4)
    q = jax.random.normal(keys[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, t, hkv, d), jnp.float32)
    g = jax.random.normal(keys[3], (1, t, h, d), jnp.float32)
    scale = d ** -0.5

    def kernel(q, k, v):
        return attention._splash_attention(
            q, k, v, causal=True, scale=scale, window=window,
            interpret=True)

    def dense(q, k, v):
        return attention.dense_attention(q, k, v, causal=True, scale=scale,
                                         window=window)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    assert_close(out, want, "out", 2e-2)
    for got, exp, name in zip(vjp(g), want_vjp(g), ("dq", "dk", "dv")):
        assert_close(got, exp, name, 2e-2)
    # the mask is the reference's: window keys, the query's own among them
    mask = np.asarray(ref.sliding_mask(t, window))
    assert mask[window + 5].sum() == window and mask[3].sum() == 4


def test_window_of_the_pattern_is_the_references_mask():
    """Key `window` back is out of sight, key `window - 1` back in it:
    moving a token just outside a layer's window leaves the last position
    alone, one just inside does not (one `W` layer, no experts' noise)."""
    cfg = config(n_layers=1, layer_pattern="W")
    params = weights(cfg, 11)
    tokens = tokens_of(11, rows=1)[:, :-1]
    last = Transformer.hidden(params, tokens, cfg)[0, -1]
    at = tokens.shape[1] - 1
    outside = tokens.at[0, at - WINDOW].set((tokens[0, at - WINDOW] + 1)
                                            % VOCAB)
    inside = tokens.at[0, at - WINDOW + 1].set(
        (tokens[0, at - WINDOW + 1] + 1) % VOCAB)
    assert float(jnp.abs(Transformer.hidden(params, outside, cfg)[0, -1]
                         - last).max()) == 0.0
    assert float(jnp.abs(Transformer.hidden(params, inside, cfg)[0, -1]
                         - last).max()) > 1e-4


# ---- the exchange -------------------------------------------------------------

N, D, F, K = 256, 16, 32, 2


def layer_and_rows(seed=0):
    params = moe.init_moe_params(jax.random.key(seed), D, F, E)
    x = jax.random.normal(jax.random.key(seed + 1), (N, D))
    top_w = jax.nn.softmax(jax.random.normal(jax.random.key(seed + 2),
                                             (N, K)))
    return params, x, top_w


def spread_choice(seed=3):
    """Two distinct experts a token, near uniform."""
    first = jax.random.randint(jax.random.key(seed), (N, 1), 0, E)
    step = 1 + jax.random.randint(jax.random.key(seed + 1), (N, 1), 0, E - 1)
    return jnp.concatenate([first, (first + step) % E], axis=1)


def loop_reference(params, x, top_w, top_e):
    """The uncut layer as the reference computes it, given the choice."""
    y = jnp.zeros_like(x)
    for e in range(E):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * ref.expert_mlp(
            x, params["w_gateup"][e][:, 0].T, params["w_gateup"][e][:, 1].T,
            params["w_down"][e].T)
    return y


LOADS = {
    # every token of every chip to chip 0's experts: the worst load
    "all_to_one_chip": lambda: jnp.tile(jnp.array([[0, 1]]), (N, 1)),
    # chip 0's tokens (the first N / 4) all to chip 3: one chip overflows
    "one_chip_overflows": lambda: spread_choice().at[:N // 4].set(
        jnp.array([6, 7])),
    "spread": spread_choice,
    # chip 0 sends chip 1 exactly the bucket's 64 rows: just under
    "at_the_bound": lambda: spread_choice().at[:N // 4].set(
        jnp.array([0, 4])).at[:N // 8].set(jnp.array([2, 3])),
}
BOUNDED = {"all_to_one_chip": 0, "one_chip_overflows": 0, "spread": 1,
           "at_the_bound": 1}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_dropless_at_any_load_and_the_branch_is_agreed(load):
    """The exchange against the one-device sorted path and the reference's
    loop, output and gradients, under loads that take the fast branch and
    loads that take the slow one; a step in which ONE chip overflows takes
    the slow branch on all four (it returns: nothing hangs)."""
    with jax.default_matmul_precision("highest"):
        params, x, top_w = layer_and_rows()
        top_e = LOADS[load]()
        mesh = mesh_of(4)
        assert moe.exchange_bound(N // 4 * K, 4) == 64

        def weigh(y):
            return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

        def one_device(p, x, w):
            y = moe._sorted_ffn(p, x, w, top_e, None)[0]
            return weigh(y), y

        def exchanged(p, x, w):
            y, record = moe._exchange_ffn(p, x, w, top_e, mesh, RULES)
            return weigh(y), (y, record)

        (_, want), want_grads = jax.jit(jax.value_and_grad(
            one_device, argnums=(0, 1, 2), has_aux=True))(params, x, top_w)
        (_, (got, record)), grads = jax.jit(jax.value_and_grad(
            exchanged, argnums=(0, 1, 2), has_aux=True))(params, x, top_w)
        assert_close(got, want, "y")
        assert_close(got, loop_reference(params, x, top_w, top_e),
                     "y against the loop")
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(want_grads)):
            if np.abs(np.asarray(b)).max() > 0:
                assert_close(a, b, "gradient")
    assert np.asarray(record["exchange_bounded"]).tolist() == \
        [BOUNDED[load]] * 4
    counts = np.asarray(record["tokens_per_expert"])
    assert counts.sum() == N * K
    np.testing.assert_array_equal(
        record["rows_received"], counts.reshape(4, -1).sum(-1))
    rounds = 1 if BOUNDED[load] else 2
    assert np.asarray(record["exchange_rows_sent"]).tolist() == \
        [rounds * 3 * 64] * 4
    if load == "all_to_one_chip":
        assert np.asarray(record["rows_received"]).tolist() == \
            [N * K, 0, 0, 0]
        assert np.asarray(record["exchange_rows_needed"]).tolist() == \
            [0, 128, 128, 128]
        assert np.asarray(record["exchange_pairs"]).tolist() == \
            [0, 64, 64, 64]


def ragged_all_to_all_from_gathers(operand, output, input_offsets,
                                   send_sizes, output_offsets, recv_sizes,
                                   *, axis_name, axis_index_groups=None):
    """`jax.lax.ragged_all_to_all` as its documentation defines it, for
    XLA:CPU, which has none: every shard gathers every shard's operand and
    offsets, and row q of its output is the row of the source whose run
    covers q (`recv_sizes`: the receiver's own word for how long each run
    is), or `output`'s where nothing lands."""
    me = jax.lax.axis_index(axis_name)
    runs = recv_sizes.size // jax.lax.axis_size(axis_name)   # a pair

    def for_me(told):       # [P, P * runs] -> the senders' runs for me
        return jax.lax.dynamic_slice_in_dim(
            jax.lax.all_gather(told, axis_name), me * runs, runs,
            axis=1).reshape(-1)

    operands = jax.lax.all_gather(operand, axis_name)        # [P, rows, d]
    starts, lands = for_me(input_offsets), for_me(output_offsets)
    q = jnp.arange(output.shape[0])
    covers = (q >= lands[:, None]) & (q < (lands + recv_sizes)[:, None])
    run = covers.argmax(0)
    row = jnp.clip(starts[run] + q - lands[run], 0, operand.shape[0] - 1)
    return jnp.where(covers.any(0)[:, None], operands[run // runs, row],
                     output)


RAGGED_BOUNDED = {"all_to_one_chip": 0, "one_chip_overflows": 1, "spread": 1,
                  "at_the_bound": 1}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_ragged_exchange_sends_the_rows_it_has(load, monkeypatch):
    """The TPU's lowering of the one bounded round (`moe.exchange_impl`:
    each shard's sorted rows through `ragged_all_to_all`) forced onto the
    CPU's mesh with the collective emulated: output and gradients against
    the one-device sorted path and the reference's loop, the rows sent
    are the rows needed, and the branch by what a chip RECEIVES (chip 3's
    1.75 of the mean fits the receive buffer of twice the mean; every row
    to one chip does not)."""
    monkeypatch.setattr(moe, "exchange_impl", lambda mesh: "ragged")
    monkeypatch.setattr(jax.lax, "ragged_all_to_all",
                        ragged_all_to_all_from_gathers)
    with jax.default_matmul_precision("highest"):
        params, x, top_w = layer_and_rows()
        top_e = LOADS[load]()
        mesh = mesh_of(4)

        def weigh(y):
            return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

        def one_device(p, x, w):
            y = moe._sorted_ffn(p, x, w, top_e, None)[0]
            return weigh(y), y

        def exchanged(p, x, w):
            y, record = moe._exchange_ffn(p, x, w, top_e, mesh, RULES)
            return weigh(y), (y, record)

        (_, want), want_grads = jax.jit(jax.value_and_grad(
            one_device, argnums=(0, 1, 2), has_aux=True))(params, x, top_w)
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda *a: exchanged(*a)[0], argnums=(0, 1, 2)))(
                params, x, top_w))
        (_, (got, record)), grads = jax.jit(jax.value_and_grad(
            exchanged, argnums=(0, 1, 2), has_aux=True))(params, x, top_w)
        assert_close(got, want, "y")
        assert_close(got, loop_reference(params, x, top_w, top_e),
                     "y against the loop")
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(want_grads)):
            if np.abs(np.asarray(b)).max() > 0:
                assert_close(a, b, "gradient")
    # every permutation a gather in the backward pass too
    assert "scatter" not in jaxpr
    bounded = RAGGED_BOUNDED[load]
    assert np.asarray(record["exchange_bounded"]).tolist() == [bounded] * 4
    counts = np.asarray(record["tokens_per_expert"])
    assert counts.sum() == N * K
    np.testing.assert_array_equal(
        record["rows_received"], counts.reshape(4, -1).sum(-1))
    needed = np.asarray(record["exchange_rows_needed"])
    # chip c's slots for its own experts stay: its tokens are rows c*64..
    own = [int(((np.asarray(top_e)[c * 64:(c + 1) * 64] // 2) == c).sum())
           for c in range(4)]
    assert needed.tolist() == [N // 4 * K - o for o in own]
    sent = np.asarray(record["exchange_rows_sent"])
    if bounded:
        np.testing.assert_array_equal(sent, needed)
    else:       # the dense rounds that take any load, as on the CPU
        assert sent.tolist() == [2 * 3 * 64] * 4
    if load == "one_chip_overflows":
        # chip 0's 128 slots on top of its near-uniform share of the rest
        assert 192 < np.asarray(record["rows_received"])[3] <= 256


def test_exchange_impl_by_what_the_mesh_says():
    assert moe.exchange_impl(mesh_of(4)) == "buckets"


def test_the_held_shares_add_up_to_the_exchange_and_the_uncut_layer():
    """`moe_ffn` as a held share at each of the four offsets (the one-chip
    path of the share cells) sums to what the exchange gives and to the
    reference's uncut layer."""
    with jax.default_matmul_precision("highest"):
        params, x, _ = layer_and_rows(4)
        params["w_router"] = params["w_router"] * 40.0
        def ffn(p, x, **kw):
            return jax.jit(lambda p, x: moe.moe_ffn(p, x, **kw))(p, x)

        whole, routing = ffn(params, x, num_selected=K)
        held = E // 4
        shares = []
        for c in range(4):
            share = dict(params,
                         w_gateup=params["w_gateup"][c * held:(c + 1) * held],
                         w_down=params["w_down"][c * held:(c + 1) * held])
            y, r = ffn(share, x, num_selected=K, expert_offset=c * held)
            shares.append(y)
            np.testing.assert_array_equal(
                r["tokens_per_expert"],
                routing["tokens_per_expert"][c * held:(c + 1) * held])
        exchanged, record = ffn(params, x, num_selected=K, mesh=mesh_of(4),
                                rules=RULES)
        assert_close(sum(shares), exchanged, "shares against the exchange")
        assert_close(exchanged, whole, "exchange against one device")
        _, top_w, top_e = moe.route(params["w_router"], x, K, True)
        assert_close(exchanged, loop_reference(params, x, top_w, top_e),
                     "exchange against the uncut layer")
    assert int(record["dropped"]) == 0
    np.testing.assert_array_equal(record["tokens_per_expert"],
                                  routing["tokens_per_expert"])


def test_exchange_bound_is_twice_the_uniform_share_in_tiles():
    assert moe.exchange_bound(8192 * 8, 4) == 32768          # the cell's
    assert 4 * moe.exchange_bound(8192 * 8, 4) % moe.GMM_ROWS == 0
    assert moe.exchange_bound(256, 4) == 128
    assert moe.exchange_bound(128, 4) == 64
    assert moe.exchange_bound(128, 2) is None     # would hold every slot
    assert moe.exchange_bound(100, 4) == 56       # whole sublane tiles


def test_an_expert_mesh_refuses_what_it_cannot_lay_out():
    params, x, _ = layer_and_rows()
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="partly"):
        moe.moe_ffn(params, x, num_selected=K, mesh=mesh,
                    rules=ShardingRules().replace(
                        expert=("data", "fsdp"), batch="fsdp",
                        expert_embed=None))
    share = dict(params, w_gateup=params["w_gateup"][:2],
                 w_down=params["w_down"][:2])
    with pytest.raises(ValueError, match="held share"):
        moe.moe_ffn(share, x, num_selected=K, mesh=mesh_of(4), rules=RULES)


# ---- scopes ---------------------------------------------------------------------


def stripped(hlo_text):
    """`tests/test_model_scopes.stripped`, and every instruction's name by
    its first place in the text: inside a `shard_map` an instruction is
    named after its op_name (`%jvp_jit_take_along_axis__` with the scopes,
    `%jit_take_along_axis_` without), so the names themselves differ where
    the programs do not."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"(?ms)^FileNames$.*?^StackFrames$.*?\n\n", "", text)
    places = {}
    return re.sub(r"%[\w\-.]+", lambda m: places.setdefault(
        m.group(0), f"%{len(places)}"), text)


def test_the_new_scopes_are_metadata_only(monkeypatch):
    cfg = config(remat=True, loss_chunk=16, layer_pattern="WL", n_layers=2)
    mesh = mesh_of(4)

    def lowered():
        params = jax.eval_shape(
            lambda: Transformer.init(jax.random.key(0), cfg))
        return jax.jit(jax.grad(lambda p, b: Transformer.loss(
            p, b, cfg, mesh=mesh, rules=RULES))).lower(
                params, {"tokens": jax.ShapeDtypeStruct((4, 65), jnp.int32)})

    def compiled():
        return lowered().compile().as_text()

    # the tables of constant positions are folded by XLA:CPU: their
    # scopes are read off the lowered module
    names = lowered().as_text(debug_info=True)
    for scope in ("rope/plain", "rope/yarn"):
        assert re.search(rf"[/(]{scope}[/)]", names), scope
    with_scopes = compiled()
    for scope in ("moe/exchange", "moe/dispatch", "moe/experts",
                  "moe/combine", "attention/window", "attention/full"):
        assert f"/{scope}/" in with_scopes, scope
    exchanges = [line for line in with_scopes.splitlines()
                 if re.search(r" all-to-all(-start)?\(", line)]
    assert exchanges and all("moe/exchange" in line for line in exchanges)

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = compiled()
    assert "moe/exchange" not in without
    assert stripped(with_scopes) == stripped(without)


# ---- the faults -------------------------------------------------------------------

TINY = load_json(os.path.join(BENCH_DIR, "rehearsal", "configs",
                              "tiny-mellum2.json"))
MIX = load_json(os.path.join(BENCH_DIR, "traffic", "rehearsal_tiny.json"))


@pytest.fixture(scope="module")
def fault_rows():
    return {row["variant"]: row for row in faults.read(TINY, MIX, 3)}


@pytest.mark.parametrize("name", faults.FAULTS + faults.PRECISIONS)
def test_every_fault_is_caught_at_the_small_size(fault_rows, name):
    """Each fault moves logits or loss by more than the limits (the
    rehearsal's are looser than the cell's: what passes them passes the
    cell's); bf16 operands pass."""
    row = fault_rows[name]
    assert row["correct"] == (name == "bfloat16"), row
    if name != "bfloat16":
        assert row["rel_l2"] > 0.05 or row["loss_diff"] > 0.05, row
