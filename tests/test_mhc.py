"""`ray_tpu/ops/mhc.py`, a residual path of several streams, op by op on
the CPU in float32: the Sinkhorn rounds (what 20 of them reach, and their
backward against plain autodiff of a Python loop written here in the other
layout), the maps' ranges and the clamp, the two mixes against einsums,
the one-stream layer as the case H_pre = e_1, H_post = e_1, H_res = I, the
entry and the exit, and the record the step's metrics read."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mhc

N, D, B, T = 4, 32, 2, 24
MAPS = N * N + 2 * N
KW = dict(rounds=20, norm_eps=1e-6, hc_eps=1e-6, clamp=30.0)


def loop_rounds(m, rounds, eps):
    """The rounds as the published description has them, `[..., n, n]`
    with the matrix on the LAST two axes: rows, then columns."""
    for _ in range(rounds):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def tokens_last(m):
    """`[..., n, n]` -> `[n, n, ...]`, the ops' layout."""
    return jnp.moveaxis(m, (-2, -1), (0, 1))


@functools.lru_cache(maxsize=None)
def case(seed):
    keys = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(keys[0], (B, T, N, D))
    phi = jax.random.normal(keys[1], (N * D, MAPS)) * (N * D) ** -0.5
    b = jax.random.normal(keys[2], (MAPS,))
    alpha = 1.0 + 0.2 * jax.random.normal(keys[3], (3,))
    y = jax.random.normal(keys[4], (B, T, D))
    return x, phi, b, alpha, y


@pytest.mark.parametrize("spread,rows_within", [(0.5, 1e-5), (0.7, 1e-4),
                                                (30.0, None)])
def test_twenty_rounds(spread, rows_within):
    """After every round the columns sum to 1 within hc_eps whatever A in
    [-30, 30]; the rows within 1e-5 where A's spread is 0.5 (the stand-in
    weights' 0.7: 1e-4). At a spread of 30 twenty rounds do NOT bring the
    rows home (exp(A) spans e^60): what `mhc_res_marginal_err` is for."""
    if rows_within is None:
        a = jax.random.uniform(jax.random.key(1), (4096, N, N),
                               minval=-spread, maxval=spread)
    else:
        a = spread * jax.random.normal(jax.random.key(1), (4096, N, N))
    m = mhc.sinkhorn(tokens_last(jnp.exp(a)), 20, 1e-6)
    assert float(jnp.abs(m.sum(axis=0) - 1.0).max()) <= 1e-5
    rows = float(jnp.abs(m.sum(axis=1) - 1.0).max())
    if rows_within is None:
        assert rows > 0.1
    else:
        assert rows <= rows_within
    assert float(m.min()) >= 0.0 and float(m.max()) <= 1.0 + 1e-6


@pytest.mark.parametrize("rounds", [0, 1, 20])
def test_rounds_forward_and_backward_match_the_python_loop(rounds):
    a = 0.7 * jax.random.normal(jax.random.key(2), (B, T, N, N))
    probe = jax.random.normal(jax.random.key(3), (B, T, N, N))

    def ours(a):
        m = mhc.sinkhorn(tokens_last(jnp.exp(a)), rounds, 1e-6)
        return jnp.sum(jnp.moveaxis(m, (0, 1), (-2, -1)) * probe)

    def loop(a):
        return jnp.sum(loop_rounds(jnp.exp(a), rounds, 1e-6) * probe)

    got, got_grad = jax.jit(jax.value_and_grad(ours))(a)
    want, want_grad = jax.value_and_grad(loop)(a)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-4,
                               atol=1e-6 * float(jnp.abs(want_grad).max()))


def test_maps_ranges_and_layout():
    x, phi, b, alpha, _ = case(0)
    pre, post, res = jax.jit(functools.partial(mhc.stream_maps, **KW))(
        x, phi, b, alpha)
    assert pre.shape == post.shape == (N, B, T)
    assert res.shape == (N, N, B, T)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    assert 0.0 < float(pre.min()) and float(pre.max()) < 1.0
    assert 0.0 < float(post.min()) and float(post.max()) < 2.0
    assert 0.0 <= float(res.min()) and float(res.max()) <= 1.0
    # the maps move with the token: no two tokens share them
    assert float(jnp.std(pre, axis=(1, 2)).min()) > 0.01


def test_maps_match_the_written_equations():
    """u = vec(X) / sqrt(mean(vec(X)^2) + eps) over ALL n*d values, m = u
    phi, the sigmoids, mat(m[2n:]) row-major, rows before columns."""
    x, phi, b, alpha, _ = case(1)
    pre, post, res = mhc.stream_maps(x, phi, b, alpha, **KW)
    flat = np.asarray(x, np.float64).reshape(B, T, N * D)
    u = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
    m = u @ np.asarray(phi, np.float64)
    al, bb = np.asarray(alpha, np.float64), np.asarray(b, np.float64)

    def sigmoid(z):
        return 1.0 / (1.0 + np.exp(-z))

    want_pre = sigmoid(al[0] * m[..., :N] + bb[:N])
    want_post = 2.0 * sigmoid(al[1] * m[..., N:2 * N] + bb[N:2 * N])
    a = (al[2] * m[..., 2 * N:] + bb[2 * N:]).reshape(B, T, N, N)
    want_res = np.exp(a)
    for _ in range(20):
        want_res = want_res / (want_res.sum(-1, keepdims=True) + 1e-6)
        want_res = want_res / (want_res.sum(-2, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.moveaxis(pre, 0, -1), want_pre, atol=2e-6)
    np.testing.assert_allclose(np.moveaxis(post, 0, -1), want_post,
                               atol=4e-6)
    np.testing.assert_allclose(np.moveaxis(res, (0, 1), (-2, -1)), want_res,
                               atol=2e-6)
    record = mhc.maps_by_token(pre, post, res)
    assert record.shape == (B, T, MAPS)
    np.testing.assert_allclose(
        record, np.concatenate([want_pre, want_post,
                                want_res.reshape(B, T, N * N)], -1),
        atol=4e-6)
    assert float(mhc.marginal_error(record[None], N)) == pytest.approx(
        max(np.abs(want_res.sum(-1) - 1).max(),
            np.abs(want_res.sum(-2) - 1).max()), abs=2e-6)


def test_the_clamp_holds_the_exponential():
    """|A| above 30 is cut to 30 before exp: with b at +-100 the maps are
    finite and are those of b at +-30; without the clamp exp overflows."""
    x, phi, _, alpha, _ = case(2)
    big = jnp.concatenate([jnp.zeros(2 * N), jnp.where(
        jnp.arange(N * N) % 3 == 0, 100.0, -100.0)])
    at_30 = jnp.clip(big, -30.0, 30.0)
    zero_phi = jnp.zeros_like(phi)
    res = mhc.stream_maps(x, zero_phi, big, alpha, **KW)[2]
    want = mhc.stream_maps(x, zero_phi, at_30, alpha, **KW)[2]
    assert bool(jnp.isfinite(res).all())
    np.testing.assert_array_equal(res, want)
    unclamped = mhc.stream_maps(x, zero_phi, big, alpha,
                                **dict(KW, clamp=1e9))[2]
    assert not bool(jnp.isfinite(unclamped).all())


def test_mixes_match_einsums_forward_and_backward():
    x, phi, b, alpha, y = case(3)
    pre, post, res = mhc.stream_maps(x, phi, b, alpha, **KW)

    def ours(x, y):
        return mhc.read(x, pre), mhc.write(x, y, post, res)

    def plain(x, y):
        return (jnp.einsum("ibt,btid->btd", pre, x),
                jnp.einsum("ijbt,btjd->btid", res, x)
                + jnp.einsum("ibt,btd->btid", post, y))

    probes = (jax.random.normal(jax.random.key(5), (B, T, D)),
              jax.random.normal(jax.random.key(6), (B, T, N, D)))
    got, pull = jax.vjp(ours, x, y)
    want, want_pull = jax.vjp(plain, x, y)
    for g, w in zip(got + pull(probes), want + want_pull(probes)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_one_stream_layer_is_the_case_of_unit_maps():
    """H_pre = e_1, H_post = e_1, H_res = I: the sublayer reads stream 1
    and adds to it alone, `x + f(x)`; the other streams pass."""
    x, _, _, _, y = case(4)
    e1 = jnp.zeros((N, B, T)).at[0].set(1.0)
    eye = jnp.broadcast_to(jnp.eye(N)[:, :, None, None], (N, N, B, T))
    np.testing.assert_array_equal(mhc.read(x, e1), x[:, :, 0])
    out = mhc.write(x, y, e1, eye)
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0] + y, rtol=1e-6)
    np.testing.assert_array_equal(out[:, :, 1:], x[:, :, 1:])


def test_entry_and_exit():
    e = jax.random.normal(jax.random.key(7), (B, T, D))
    x = mhc.expand(e, N)
    assert x.shape == (B, T, N, D)
    for i in range(N):
        np.testing.assert_array_equal(x[:, :, i], e)
    np.testing.assert_allclose(mhc.collapse(x), N * e, rtol=1e-6)


def test_bf16_stream_keeps_its_dtype_and_sums_in_f32():
    x, phi, b, alpha, y = case(5)
    pre, post, res = mhc.stream_maps(x.astype(jnp.bfloat16), phi, b, alpha,
                                     **KW)
    assert pre.dtype == jnp.float32
    h = mhc.read(x.astype(jnp.bfloat16), pre)
    out = mhc.write(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16), post,
                    res)
    assert h.dtype == out.dtype == jnp.bfloat16
    exact = mhc.write(x.astype(jnp.bfloat16).astype(jnp.float32),
                      y.astype(jnp.bfloat16).astype(jnp.float32), post, res)
    # one rounding, of the f32 sum
    np.testing.assert_array_equal(out, exact.astype(jnp.bfloat16))
