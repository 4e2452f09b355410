"""`ops/moe.experts_ffn` through `megablox` at tiles of 896 (PR 62: an
expert width of 7 x 128 takes the widest multiple of 128 that divides it,
`gmm_tiles`), interpreted on the CPU, against `ragged_dot`. The smallest
shape that takes the decision: k = 896 and n = 1,792 in the gated first
matmul, two row tiles, three held groups of which one is empty and the
trailing group of no expert: the forward `gmm`, its transpose and `tgmm`
(which visits the empty group and writes its zeros) each look their tiles
up by their own shapes inside `megablox`'s `custom_vjp`. And, as traces
(PR 64): where `megablox` puts its zeroing select behind a kernel and
where it does not, which `ops/moe._exchange_ffn`'s ragged round leans on."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox import ops

from ray_tpu.ops import moe

D = F = 896
M, SIZES = 2 * moe.GMM_ROWS, (300, 0, 500, 224)    # the last: no expert's
HELD = len(SIZES) - 1


@functools.lru_cache(maxsize=None)
def case(impl: str):
    """`experts_ffn`'s output and the cotangents of the rows and of both
    weights under one fixed cotangent, float32 so that the two lowerings
    differ by the order of their sums alone."""
    kx, k1, k2, kg = jax.random.split(jax.random.key(62), 4)
    xs = jax.random.normal(kx, (M, D), jnp.float32)
    w_gateup = jax.random.normal(k1, (HELD, D, 2, F), jnp.float32) * D ** -0.5
    w_down = jax.random.normal(k2, (HELD, F, D), jnp.float32) * F ** -0.5
    g = jax.random.normal(kg, (M, D), jnp.float32)
    sizes = jnp.asarray(SIZES, jnp.int32)

    def ffn(xs, w_gateup, w_down):
        return moe.experts_ffn(xs, w_gateup, w_down, sizes, impl)

    ys, vjp = jax.vjp(ffn, xs, w_gateup, w_down)
    return (ys, *vjp(g))


@pytest.fixture
def interpreted(monkeypatch):
    """`megablox` as `experts_ffn` calls it, its kernels interpreted."""
    gmm = ops.gmm
    monkeypatch.setattr(ops, "gmm", lambda *args: gmm(*args, interpret=True))


def test_the_calls_take_tiles_of_896():
    assert moe.gmm_tiles(M, D, 2 * F) == (moe.GMM_ROWS, 896, 896)
    assert moe.gmm_tiles(M, 2 * F, D) == (moe.GMM_ROWS, 896, 896)
    assert moe.gmm_tiles(M, F, D) == (moe.GMM_ROWS, 896, 896)


RESULTS = ("ys", "d_rows", "d_w_gateup", "d_w_down")


@pytest.mark.parametrize("at", range(len(RESULTS)), ids=RESULTS)
def test_megablox_at_896_agrees_with_ragged_dot(interpreted, at):
    got, want = case("megablox")[at], case("ragged_dot")[at]
    assert got.shape == want.shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    if RESULTS[at] in ("ys", "d_rows"):     # rows of no held group: zero
        held_rows = sum(SIZES[:HELD])
        assert not np.asarray(got[held_rows:]).any()
        assert np.asarray(got[:held_rows]).any(axis=1).all()
    else:                                   # the empty group's weights: zero
        assert not np.asarray(got[1]).any()
        assert np.asarray(got[0]).any() and np.asarray(got[2]).any()


def equations(jaxpr):
    """The trace's equations in order, those of the jaxprs they call
    behind them, the kernels' own bodies apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


@pytest.mark.parametrize("pass_", ["forward", "backward"])
@pytest.mark.parametrize("named", [False, True],
                         ids=["held_groups_only", "a_group_of_no_expert"])
def test_megablox_zeroes_behind_its_kernels_only_for_a_group_it_is_told_of(
        named, pass_):
    """The library behaviour `_exchange_ffn`'s ragged round leans on (PR
    64), by name, so that a JAX that changes it fails here: `megablox.gmm`
    follows its kernel with a select over the WHOLE output, forward and in
    its transpose, exactly where it is handed more groups than weights
    (`_rows_ffn`'s run, whose `token_sums` needs those zeros); handed the
    held groups alone, which may sum to fewer rows than there are, it
    traces no pass behind the kernel, and the rows past the groups are
    whatever the memory held."""
    sizes = jax.ShapeDtypeStruct((HELD + named,), jnp.int32)
    rows = jax.ShapeDtypeStruct((M, D), jnp.bfloat16)
    weights = jax.ShapeDtypeStruct((HELD, D, 2 * F), jnp.bfloat16)

    def gmm(rows, weights, sizes):
        return ops.gmm(rows, weights, sizes, rows.dtype, moe.gmm_tiles)

    def summed(rows, weights, sizes):
        return gmm(rows, weights, sizes).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(
        gmm if pass_ == "forward" else jax.grad(summed, argnums=(0, 1)))(
            rows, weights, sizes)
    eqns = list(equations(traced.jaxpr))
    kernels = [i for i, eqn in enumerate(eqns)
               if eqn.primitive.name == "pallas_call"]
    # the forward `gmm`; behind it in the gradient its transpose and `tgmm`
    assert len(kernels) == (1 if pass_ == "forward" else 3)
    # a select over a whole `[rows, n]` array behind the first kernel
    selects = [eqn for eqn in eqns[kernels[0]:]
               if eqn.primitive.name == "select_n"
               and eqn.outvars[0].aval.ndim == 2]
    # one behind the forward `gmm`, one behind its transpose; `tgmm` none
    assert len(selects) == named * (1 if pass_ == "forward" else 2)
