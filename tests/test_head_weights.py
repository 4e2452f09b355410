"""The vocab head's differentiable per-token weights (`head.nll_sum(...,
weights=)`, PR 63: a looped stack's exit distribution) against plain
autodiff through whole-sequence logits: the weighted sum, the reading of
the tokens' own cross-entropy, and BOTH cotangents (the hidden states' and
the head's as before, and the weights', `g * nll`), chunked and
unchunked, with and without a mask, tied and untied; `mask` alone is
unchanged: data, with no cotangent. float32 on the CPU; the chunked scan
adds its chunks in another order than the whole-sequence sum."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, head

B, T, D, V = 3, 64, 32, 96
CHUNKS = {"chunked": 16, "whole": 0, "chunk_too_long": 64}


def config(chunk, tied=False):
    return TransformerConfig(vocab_size=V, d_model=D, n_layers=1, n_heads=4,
                             d_ff=64, max_seq_len=T, dtype="float32",
                             loss_chunk=chunk, tie_embeddings=tied)


@functools.lru_cache(maxsize=None)
def operands(seed, tied=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    w = jax.random.normal(ks[0], (V, D) if tied else (D, V)) * D ** -0.5
    return (w, jax.random.normal(ks[1], (B, T, D)),
            jax.random.randint(ks[2], (B, T), 0, V),
            (jax.random.uniform(ks[3], (B, T)) > 0.3).astype(jnp.float32),
            jax.nn.softmax(jax.random.normal(ks[4], (B, T, 4)))[..., 1])


def plain(w, x, targets, mask, weights, tied):
    """Whole-sequence logits, plain autodiff."""
    logits = jnp.einsum("btd,vd->btv" if tied else "btd,dv->btv", x, w)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0]
    if mask is not None:
        nll = nll * mask
    return jnp.sum(nll if weights is None else nll * weights), nll


def close(got, want, rtol=2e-6):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), \
        np.abs(got - want).max() / np.abs(want).max()


@functools.lru_cache(maxsize=None)
def program(chunk, tied, masked, weighted):
    """(value, gradients) of the head's sum under a cotangent of 0.7, one
    program a case: by the head and by plain autodiff."""
    cfg = config(CHUNKS[chunk], tied)

    def of(fn):
        def total(w, x, weights, targets, mask):
            out = fn(w, x, targets, mask if masked else None,
                     weights if weighted else None)
            out = out if weighted else (out, jnp.zeros(()))
            return 0.7 * out[0], out[1]
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2),
                                          has_aux=True))

    return (of(lambda w, x, t, m, wt: head.nll_sum(
                w, x, t, cfg, mask=m, weights=wt)),
            of(lambda w, x, t, m, wt: plain(w, x, t, m, wt, tied)
               if wt is not None else plain(w, x, t, m, wt, tied)[0]))


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_weighted_sum_and_both_cotangents(chunk, masked, tied):
    w, x, targets, mask, weights = operands(0, tied)
    ours, theirs = program(chunk, tied, masked, True)
    (got, nll), (d_w, d_x, d_weights) = ours(w, x, weights, targets, mask)
    (want, want_nll), (w_w, w_x, w_weights) = theirs(w, x, weights, targets,
                                                     mask)
    close(got, want)
    close(nll, want_nll)        # the reading: the tokens' own (masked) nll
    close(d_w, w_w)
    close(d_x, w_x)
    close(d_weights, w_weights)
    # the weights' cotangent is the sum's times the tokens' cross-entropy
    close(d_weights, 0.7 * np.asarray(want_nll))
    assert float(jnp.abs(d_weights).max()) > 0.1


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_without_weights_the_sum_is_a_scalar_as_before(chunk, masked):
    w, x, targets, mask, weights = operands(1)
    ours, theirs = program(chunk, False, masked, False)
    (got, _), (d_w, d_x, d_weights) = ours(w, x, weights, targets, mask)
    (want, _), (w_w, w_x, _) = theirs(w, x, weights, targets, mask)
    close(got, want)
    close(d_w, w_w)
    close(d_x, w_x)
    assert not np.asarray(d_weights).any()     # nothing read them


@pytest.mark.parametrize("chunk", ["chunked", "whole"])
def test_the_mask_is_data(chunk):
    """`mask` gets no gradient, alone or beside weights: differentiating
    by it gives zeros from the chunked head (`None` in its backward) and
    the plain product's nll from the whole-sequence one, which is why a
    learned weighting goes in as `weights`."""
    w, x, targets, mask, weights = operands(2)
    cfg = config(CHUNKS[chunk])
    d_mask = jax.grad(lambda m: head.nll_sum(
        w, x, targets, cfg, mask=m, weights=weights)[0])(mask)
    d_weights = jax.grad(lambda wt: head.nll_sum(
        w, x, targets, cfg, mask=mask, weights=wt)[0])(weights)
    assert float(jnp.abs(d_weights).max()) > 0.1
    if chunk == "chunked":
        assert not np.asarray(d_mask).any()
    # a masked-out token's weight gets no gradient either way
    assert not np.asarray(d_weights)[np.asarray(mask) == 0].any()


def test_the_reading_carries_no_gradient():
    w, x, targets, _, weights = operands(3)
    for chunk in ("chunked", "whole"):
        cfg = config(CHUNKS[chunk])
        d_x = jax.grad(lambda x: jnp.sum(head.nll_sum(
            w, x, targets, cfg, weights=weights)[1]))(x)
        assert not np.asarray(d_x).any()


def test_per_chip_chunks_give_the_weights_their_cotangent():
    """Where the mesh splits only the batch the chunks run per chip
    (`shard_map`): the weights and the reading go in and come out by
    rows."""
    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=-1))
    rows = len(jax.devices())
    ks = jax.random.split(jax.random.key(4), 4)
    w = jax.random.normal(ks[0], (D, V)) * D ** -0.5
    x = jax.random.normal(ks[1], (rows, T, D))
    targets = jax.random.randint(ks[2], (rows, T), 0, V)
    weights = jax.random.uniform(ks[3], (rows, T))
    cfg = config(16)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda w, x, wt: fn(w, x, wt), argnums=(0, 1, 2), has_aux=True))

    (got, nll), grads = both(lambda w, x, wt: head.nll_sum(
        w, x, targets, cfg, weights=wt, mesh=mesh))(w, x, weights)
    (want, want_nll), want_grads = both(lambda w, x, wt: plain(
        w, x, targets, None, wt, False))(w, x, weights)
    close(got, want)
    close(nll, want_nll)
    for g, wg in zip(grads, want_grads):
        close(g, wg)
