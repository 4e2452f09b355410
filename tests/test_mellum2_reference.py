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

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, head
from ray_tpu.models.transformer import _rope_tables
from ray_tpu.ops import attention
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.train_step import make_train_step

from tests._mellum2 import (E, ROPE, RTOL, RULES, VOCAB, WINDOW, assert_close,
                            config, job, mesh_of, published, ref, tokens_of,
                            weights)


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
    want, chosen = jax.jit(lambda w: ref.forward(
        w, tokens, model, with_routing=True))(ref_w)
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
