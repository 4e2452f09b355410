"""`ray_tpu/ops/mhc.py`, a residual path of several streams, op by op on
the CPU in float32: the Sinkhorn rounds (what 20 of them reach, and their
backward against plain autodiff of a Python loop written here in the other
layout), the maps' ranges and the clamp, the two mixes against einsums,
the one-stream layer as the case H_pre = e_1, H_post = e_1, H_res = I, the
entry and the exit, and the record the step's metrics read.

Since PR 67 the model runs `enter` and `leave` (two `jax.custom_vjp`s with
backwards of their own, their passes over the stream pallas kernels or
`jax.numpy`): the second half holds them, the kernels in interpret mode,
to `stream_maps` / `read` / `write` composed under `jax.vjp` on the same
bfloat16 stream in float32, at shapes on each side of what the kernels
tile; the one-pass product with phi to the `Precision.HIGHEST` einsum; and
what a layer under `Transformer._remat` keeps of them
(`mhc.MAPS_RESIDUALS`)."""

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


# ---- `enter` and `leave`, what the model runs (PR 67) --------------------

# (n, d, B, T): Xing4's streams at a short length; two streams; two blocks
# of tokens; three streams; a width that is no whole lane tile and tokens
# that are no whole block, which take the `jax.numpy` passes
SHAPES = {"xing4": (4, 3584, 1, 128), "two_streams": (2, 256, 2, 128),
          "two_blocks": (4, 128, 1, 256), "three_streams": (3, 128, 1, 128),
          "untiled": (4, 96, 2, 40)}
BF16_ROUNDING = 2.0 ** -8       # half an ulp of a bfloat16, relative


def composed(x, y, phi, b, alpha):
    """A sublayer's mixing as the three plain functions compose it."""
    pre, post, res = mhc.stream_maps(x, phi, b, alpha, **KW)
    return (mhc.read(x, pre), mhc.write(x, y, post, res),
            mhc.maps_by_token(pre, post, res))


def entered_and_left(x, y, phi, b, alpha, interpret=True):
    h, (pre, post, res), through = mhc.enter(x, phi, b, alpha,
                                             interpret=interpret, **KW)
    return (h, mhc.leave(through, y, post, res, interpret=interpret),
            mhc.maps_by_token(pre, post, res))


@functools.lru_cache(maxsize=None)
def mixed(shape, seed=0):
    """(outputs, gradients) of `entered_and_left` on a bfloat16 stream and
    of `composed` on the same values in float32, one program each: (h, X',
    the maps' record) and the cotangents of (x, y, phi, b, alpha) under
    one probe an output."""
    n, d, bsz, t = SHAPES[shape]
    maps = n * n + 2 * n
    keys = jax.random.split(jax.random.key(seed), 8)
    bf16 = jnp.bfloat16
    operands = (jax.random.normal(keys[0], (bsz, t, n, d)).astype(bf16),
                jax.random.normal(keys[1], (bsz, t, d)).astype(bf16),
                jax.random.normal(keys[2], (n * d, maps)) * (n * d) ** -0.5,
                jax.random.normal(keys[3], (maps,)),
                1.0 + 0.2 * jax.random.normal(keys[4], (3,)))
    # bfloat16 values, for both sides
    probes = (jax.random.normal(keys[5], (bsz, t, d)).astype(bf16),
              jax.random.normal(keys[6], (bsz, t, n, d)).astype(bf16),
              jax.random.normal(keys[7], (bsz, t, maps)))

    def both(fn, *operands):
        out, pull = jax.vjp(fn, *operands)
        return out, pull(tuple(p.astype(o.dtype)
                               for p, o in zip(probes, out)))

    got = jax.jit(functools.partial(both, entered_and_left))(*operands)
    want = jax.jit(functools.partial(both, composed))(
        *(a.astype(jnp.float32) for a in operands))
    return got, want


def test_the_kernels_take_what_they_tile():
    """One TPU device, a bfloat16 stream, d whole lane tiles, whole blocks
    of tokens and n up to 4 -> the kernels; anything else the same passes
    in `jax.numpy`."""
    from tests.test_kda_kernel import FourTpus, OneTpu

    bf16 = jnp.bfloat16
    assert mhc.stream_mix_impl(OneTpu(), 8192, 4, 3584, bf16) == "pallas"
    assert mhc.stream_mix_impl(OneTpu(), 256, 2, 128, bf16) == "pallas"
    for mesh, tokens, n, d, dtype in (
            (None, 8192, 4, 3584, bf16), (FourTpus(), 8192, 4, 3584, bf16),
            (OneTpu(), 8192, 4, 3584, jnp.float32),
            (OneTpu(), 8192, 4, 3584 + 64, bf16),
            (OneTpu(), 8192 + 64, 4, 3584, bf16),
            (OneTpu(), 8192, 5, 3584, bf16)):
        assert mhc.stream_mix_impl(mesh, tokens, n, d, dtype) == "xla"
    for name, (n, d, bsz, t) in SHAPES.items():
        assert mhc.mix_shape_ok(bsz * t, n, d, bf16) == (name != "untiled")


@pytest.mark.parametrize("shape", SHAPES)
def test_entering_and_leaving_match_the_composed_functions(shape):
    """h and X' within one bfloat16 rounding of the float32 sums, the maps
    within float32's order of addition: 4.1e-7 of the largest entry read
    over these shapes and two seeds, held to 2e-6."""
    (got, _), (want, _) = mixed(shape)
    for g, w in zip(got[:2], want[:2]):
        assert g.dtype == jnp.bfloat16 and g.shape == w.shape
        np.testing.assert_allclose(g.astype(jnp.float32), w,
                                   rtol=BF16_ROUNDING,
                                   atol=1e-6 * float(jnp.abs(w).max()))
    assert got[2].dtype == jnp.float32
    np.testing.assert_allclose(got[2], want[2],
                               atol=2e-6 * float(jnp.abs(want[2]).max()))


@pytest.mark.parametrize("shape", SHAPES)
def test_their_backwards_match_autodiff_of_the_composed_functions(shape):
    """The stream's gradient is ONE bfloat16 array, the sum in float32 of
    `leave`'s share (rounded once, as the cotangent of a bfloat16 value)
    and `enter`'s three terms, rounded once: within two roundings of the
    float32 gradient (3.0e-3 to 4.1e-3 of the largest entry read, where
    the three plain functions under `jax.grad` on the bfloat16 stream read
    3.8e-3 to 5.8e-3: held to 2^-7); dy within one; dphi, db and dalpha,
    which no rounded value enters, within float32's order of addition
    (4.2e-7, 4.2e-7, 1.4e-6 of the largest entry read: held to 2e-6, 2e-6,
    5e-6)."""
    (_, got), (_, want) = mixed(shape)
    dx, dy, dphi, db, dalpha = got
    assert dx.dtype == dy.dtype == jnp.bfloat16

    def largest(a):
        return float(jnp.abs(a).max())

    np.testing.assert_allclose(dx.astype(jnp.float32), want[0], rtol=0,
                               atol=2.0 ** -7 * largest(want[0]))
    np.testing.assert_allclose(dy.astype(jnp.float32), want[1],
                               rtol=BF16_ROUNDING,
                               atol=1e-6 * largest(want[1]))
    for g, w, within in ((dphi, want[2], 2e-6), (db, want[3], 2e-6),
                         (dalpha, want[4], 5e-6)):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=0, atol=within * largest(w))


def test_a_float32_stream_takes_the_plain_product():
    """In float32 (the reference tests' dtype) the passes are the plain
    functions' sums in another order: outputs and gradients at 2e-6 of the
    largest entry."""
    x, phi, b, alpha, y = case(6)
    probes = (jax.random.normal(jax.random.key(8), (B, T, D)),
              jax.random.normal(jax.random.key(9), (B, T, N, D)),
              jax.random.normal(jax.random.key(10), (B, T, MAPS)))
    got, pull = jax.vjp(functools.partial(entered_and_left, interpret=False),
                        x, y, phi, b, alpha)
    want, want_pull = jax.vjp(composed, x, y, phi, b, alpha)
    for g, w in zip(got + pull(probes), want + want_pull(probes)):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2e-6 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("kernel", [False, True], ids=["numpy", "kernel"])
def test_one_pass_over_phis_pieces_is_the_highest_product(kernel):
    """A bfloat16 stream is its own first piece and has no second: X times
    `[phi_hi | phi_mid | phi_lo]` in one bfloat16 pass, the three groups of
    columns added in float32, is the `Precision.HIGHEST` einsum of
    `stream_maps`' products (4.6e-7 of the largest entry apart read, held
    to 2e-6), where ONE bfloat16 piece of phi is 2e-3 away. The pieces add
    up to phi to float32's last bit."""
    n, d, bsz, t = SHAPES["two_blocks"]
    x = jax.random.normal(jax.random.key(11), (bsz, t, n * d)).astype(
        jnp.bfloat16)
    phi = jax.random.normal(jax.random.key(12), (n * d, n * n + 2 * n))
    pieces = mhc._pieces(phi)
    assert pieces.shape == (3,) + phi.shape
    np.testing.assert_array_equal(
        pieces, pieces.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(pieces.sum(0), phi, rtol=2.0 ** -23)
    want = jnp.einsum("btk,km->btm", x.astype(jnp.float32), phi,
                      precision=jax.lax.Precision.HIGHEST)
    m, r = mhc._passes("pallas" if kernel else "xla", n, kernel)[0](
        x, phi, 1e-6)
    np.testing.assert_allclose(r, jax.lax.rsqrt(jnp.mean(
        x.astype(jnp.float32) ** 2, -1) + 1e-6), rtol=2e-6)
    got = jnp.moveaxis(m, 0, -1) / r[..., None]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * scale)
    one_piece = jnp.einsum("btk,km->btm", x, phi.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
    assert float(jnp.abs(one_piece - want).max()) > 5e-4 * scale


# ---- what a layer under `Transformer._remat` keeps -----------------------


def sublayers(policy=None, interpret=True, **cfg_kw):
    """The loss and gradients of a scan of two mixed sublayers, each under
    `Transformer._remat`, as a function of the stacked leaves, the stream
    and the sublayer's weight. `policy`: what stands in for `jax.
    checkpoint_policies.save_only_these_names` while the layer is wrapped
    (called with it, then the names)."""
    from ray_tpu.models import Transformer, TransformerConfig

    def layer(x, lp):
        h, (_, post, res), x = mhc.enter(x, lp["phi"], lp["b"], lp["alpha"],
                                         interpret=interpret, **KW)
        return mhc.leave(x, jnp.tanh(h @ lp["w"]), post, res,
                         interpret=interpret), None

    cfg = TransformerConfig(vocab_size=8, d_model=32, n_layers=2, n_heads=2,
                            d_ff=8, remat=True, **cfg_kw)
    policies = jax.checkpoint_policies
    theirs = policies.save_only_these_names
    if policy is not None:
        policies.save_only_these_names = functools.partial(policy, theirs)
    try:
        wrapped = Transformer._remat(layer, cfg)
    finally:
        policies.save_only_these_names = theirs
    return jax.value_and_grad(lambda lps, x: jnp.sum(
        jax.lax.scan(wrapped, x, lps)[0].astype(jnp.float32) ** 2),
        argnums=(0, 1))


def without_the_maps(save_only_these_names, *names):
    """`_remat`'s policy with the maps' name taken out."""
    assert mhc.MAPS_RESIDUALS in names
    return save_only_these_names(
        *(n for n in names if n != mhc.MAPS_RESIDUALS))


@functools.lru_cache(maxsize=None)
def stacked_sublayers():
    n, d, bsz, t = SHAPES["two_streams"]
    maps = n * n + 2 * n
    keys = jax.random.split(jax.random.key(13), 5)
    return {"phi": jax.random.normal(keys[0], (2, n * d, maps))
            * (n * d) ** -0.5,
            "b": jax.random.normal(keys[1], (2, maps)),
            "alpha": 1.0 + 0.2 * jax.random.normal(keys[2], (2, 3)),
            "w": (jax.random.normal(keys[3], (2, d, d)) * d ** -0.5).astype(
                jnp.bfloat16)}, \
        jax.random.normal(keys[4], (bsz, t, n * d)).astype(jnp.bfloat16)


def kernel_calls(fn):
    from tests.test_moe_routing_residuals import equations

    counts = {}
    for eqn in equations(jax.make_jaxpr(fn)(*stacked_sublayers()).jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
    return counts


def test_a_rematerialised_layer_keeps_the_maps_product():
    """A scan's body is one sublayer: the forward scan holds
    `mhc_enter_fwd` and `mhc_leave_fwd`, the backward scan the two
    backward kernels and `leave`'s forward never (its output is the next
    layer's carry); `enter`'s forward kernel, the statistic and the
    product with phi, is not run again: m and r are kept. With their name
    out of the policy, or under "full", it is."""
    kept = {"mhc_enter_fwd": 1, "mhc_leave_fwd": 1, "mhc_enter_bwd": 1,
            "mhc_leave_bwd": 1}
    assert kernel_calls(sublayers()) == kept
    again = dict(kept, mhc_enter_fwd=2)
    assert kernel_calls(sublayers(without_the_maps)) == again
    assert kernel_calls(sublayers(remat_policy="full")) == again


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "numpy"])
def test_the_kept_maps_are_the_ones_made_again(interpret):
    """The loss and every gradient with the maps' name in `_remat`'s
    policy and with it taken out: the same values made once or twice, so
    equal up to what `jax.checkpoint`'s `reduce_precision` on a saved
    residual lets XLA fuse otherwise (PR 65 read 2.3e-6 to 1.6e-5 of the
    loss on the chip, in a whole step; here 0 was read, held to 2e-5 of
    the loss and of each gradient's largest entry)."""
    got = jax.jit(sublayers(interpret=interpret))(*stacked_sublayers())
    want = jax.jit(sublayers(without_the_maps, interpret=interpret))(
        *stacked_sublayers())
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        w32 = w.astype(jnp.float32)
        np.testing.assert_allclose(
            g.astype(jnp.float32), w32, rtol=0,
            atol=2e-5 * float(jnp.abs(w32).max()))
    assert all(bool(jnp.any(g != 0)) for g in jax.tree.leaves(got[1]))
