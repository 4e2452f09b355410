"""The chosen scores of a router whose choice is not its scores (a bias
added before `top_k`, a group limit): `ops/moe._chosen_scores` reads them
off the selection by compares, and `route` with it is `route` with
`take_along_axis(probs, top_e)` bit for bit, forward and in both
gradients, ties and k = E included; neither program holds a gather from
or a scatter into the `[N, E]` scores. A router without bias and group
limit keeps `_scores_at` (`top_k`'s own values). On the CPU in float32,
the three routers as the benchmark's cells configure them at a cut N."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from ray_tpu.ops import moe

N, D = 256, 64
# as train_nemotron3super_ep64_d11, train_ling3flash_ep64_d7 and
# train_glm47flash_ep8_d5 route (benchmark/configs), less the widths
ROUTERS = {
    "top22_of_512": dict(experts=512, k=22, groups={}),
    "top8_of_512_in_4_of_8_groups": dict(
        experts=512, k=8, groups=dict(n_group=8, topk_group=4)),
    "top4_of_64": dict(experts=64, k=4, groups={}),
}
WEIGHTINGS = {"as_chosen": dict(norm_topk=False, routed_scale=1.0),
              "normalised_scaled": dict(norm_topk=True, routed_scale=2.5)}


def inputs(experts, n=N, seed=0):
    """A router's weight, a step's tokens and a choice bias that moves
    the choice."""
    k_w, k_x, k_b = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k_w, (D, experts), jnp.float32) * 0.2,
            jax.random.normal(k_x, (n, D), jnp.float32),
            jax.random.normal(k_b, (experts,), jnp.float32) * 0.1)


def gather_form(probs, top_e):
    return jnp.take_along_axis(probs, top_e, axis=-1)


def routed(w, x, bias, k, gathered=False, **how):
    """(`top_w`, `top_e`) of the sigmoid router with its bias; `gathered`
    puts the gather where the compares are."""
    with mock.patch.object(moe, "_chosen_scores", gather_form if gathered
                           else moe._chosen_scores):
        return moe.route(w, x, k, scoring="sigmoid", bias=bias, **how)[1:3]


def weighted_sum_grads(w, x, bias, k, gathered, **how):
    cotangent = jax.random.normal(jax.random.key(7), (x.shape[0], k))
    return jax.grad(lambda w, x: jnp.sum(
        routed(w, x, bias, k, gathered, **how)[0] * cotangent),
        argnums=(0, 1))(w, x)


def bits(a):
    return np.asarray(a).view(np.int32)


def same_bits(w, x, bias, k, **how):
    top_w, top_e = routed(w, x, bias, k, **how)
    want_w, want_e = routed(w, x, bias, k, gathered=True, **how)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_array_equal(bits(top_w), bits(want_w))
    for got, want in zip(weighted_sum_grads(w, x, bias, k, False, **how),
                         weighted_sum_grads(w, x, bias, k, True, **how)):
        assert np.any(np.asarray(want) != 0)
        np.testing.assert_array_equal(bits(got), bits(want))
    return top_w, top_e


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, jex_core.ClosedJaxpr):
                    yield from equations(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    yield from equations(sub)


def reads_of_the_scores(jaxpr, n, experts, k):
    """Gathers from and scatters into an `[n, experts]` value, and values
    `[n, k, experts]`, among a program's equations."""
    seen = {"gather": 0, "scatter": 0, "slots_by_experts": 0}
    for eqn in equations(jaxpr):
        name = eqn.primitive.name
        if name == "gather" and eqn.invars[0].aval.shape == (n, experts):
            seen["gather"] += 1
        elif name.startswith("scatter") and \
                eqn.outvars[0].aval.shape == (n, experts):
            seen["scatter"] += 1
        seen["slots_by_experts"] += any(
            getattr(v.aval, "shape", None) == (n, k, experts)
            for v in eqn.outvars)
    return seen


@pytest.mark.parametrize("weighting", list(WEIGHTINGS))
@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_compares_are_the_gather_bit_for_bit(router, weighting):
    """(a) `top_w`, (b) the gradients of a weighted sum of it with respect
    to the router's weight and to x: equal as int32 views."""
    spec = ROUTERS[router]
    w, x, bias = inputs(spec["experts"])
    how = {**WEIGHTINGS[weighting], **spec["groups"]}
    top_w, top_e = same_bits(w, x, bias, spec["k"], **how)
    probs = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x, w, precision=jax.lax.Precision.HIGHEST))
    if weighting == "as_chosen":    # and the scores, not the choice
        np.testing.assert_array_equal(
            bits(top_w), bits(gather_form(probs, top_e)))
    # the bias did move the choice: not the top-k of the scores alone
    assert np.any(np.asarray(top_e) != np.asarray(
        jax.lax.top_k(probs, spec["k"])[1]))


@pytest.mark.parametrize("weighting", list(WEIGHTINGS))
@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_tie_at_the_last_slot_and_every_expert_chosen(router, weighting):
    """(c) Two columns with one score and one bias, the lower chosen and
    the upper not: the compares read `top_e`, they do not choose. And
    k = E, where every column of a row is some slot's."""
    spec = ROUTERS[router]
    k, experts = spec["k"], spec["experts"]
    how = {**WEIGHTINGS[weighting], **spec["groups"]}
    w, x, bias = inputs(experts, seed=1)
    # k - 1 columns no other can pass, then the tied pair, all in the
    # first group so that a group limit keeps them
    first, second = k + 1, k + 4
    w = w.at[:, second].set(w[:, first])
    bias = bias.at[:k - 1].set(8.0).at[jnp.array([first, second])].set(4.0)
    _, top_e = same_bits(w, x, bias, k, **how)
    chosen = np.asarray(top_e)
    assert np.all((chosen == first).sum(-1) == 1)
    assert not np.any(chosen == second)

    w, x, bias = inputs(experts, n=16, seed=2)
    _, top_e = same_bits(w, x, bias, experts, **how)
    np.testing.assert_array_equal(np.sort(np.asarray(top_e), axis=-1),
                                  np.tile(np.arange(experts), (16, 1)))


@pytest.mark.parametrize("router", list(ROUTERS))
def test_no_gather_from_and_no_scatter_into_the_scores(router):
    """(d) `route` and its gradient: the compares' `[N, k, E]` values are
    there (inside one fusion on the chip), a gather or a scatter is not;
    with the gather in its place the reading finds both."""
    spec = ROUTERS[router]
    k, experts = spec["k"], spec["experts"]
    w, x, bias = inputs(experts)
    how = {**WEIGHTINGS["normalised_scaled"], **spec["groups"]}

    def programs(gathered):
        forward = jax.make_jaxpr(
            lambda w, x: routed(w, x, bias, k, gathered, **how))(w, x)
        gradient = jax.make_jaxpr(lambda w, x: weighted_sum_grads(
            w, x, bias, k, gathered, **how))(w, x)
        return (reads_of_the_scores(forward.jaxpr, N, experts, k),
                reads_of_the_scores(gradient.jaxpr, N, experts, k))

    forward, gradient = programs(gathered=False)
    assert (forward["gather"], forward["scatter"]) == (0, 0)
    assert (gradient["gather"], gradient["scatter"]) == (0, 0)
    assert forward["slots_by_experts"] and gradient["slots_by_experts"]
    forward, gradient = programs(gathered=True)
    assert (forward["gather"], forward["scatter"]) == (1, 0)
    assert (gradient["gather"], gradient["scatter"]) == (1, 1)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_without_bias_and_group_limit_the_values_are_top_ks(scoring):
    """(e) Nothing added to the scores: `_scores_at` hands on `top_k`'s
    own values, so the forward holds neither the compares nor a gather,
    and the backward is the gather's transpose as before."""
    experts, k = 64, 8
    w, x, _ = inputs(experts)

    def weights(w, x):
        return moe.route(w, x, k, True, scoring=scoring)[1]

    forward = reads_of_the_scores(
        jax.make_jaxpr(weights)(w, x).jaxpr, N, experts, k)
    assert forward == {"gather": 0, "scatter": 0, "slots_by_experts": 0}
    gradient = reads_of_the_scores(jax.make_jaxpr(jax.grad(
        lambda w, x: weights(w, x).sum(), argnums=(0, 1)))(w, x).jaxpr,
        N, experts, k)
    assert gradient == {"gather": 0, "scatter": 1, "slots_by_experts": 0}
