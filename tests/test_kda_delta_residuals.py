"""What a rematerialised KDA layer keeps of the delta rule
(`ops/kda.DELTA_RESIDUALS`, saved by `Transformer._remat`'s one policy):
the forward kernel's output, the states entering the chunks and the
chunks' inverses, so the gradient's program holds `kda_delta_fwd` once a
layer where `remat_policy="full"` holds it twice, and the saved values are
the ones made again, bit for bit. Only the pallas rule names anything:
`gated_delta_rule` (the CPU, a mesh above one device) names nothing. Read
off the jaxpr of a scan of two KDA sublayers under `_remat`, the kernels
forced in interpret mode, beside `tests/test_moe_routing_residuals.py`
(the routing) and `tests/test_attention_kernels.py` (the flash kernel)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import kda

from tests.test_kda_kernel import CHUNK, FourTpus, OneTpu, mixer_inputs
from tests.test_moe_routing_residuals import equations

LAYERS = 2


@functools.lru_cache(maxsize=None)
def stacked():
    """Two KDA sublayers' leaves, stacked for a scan, and the stream."""
    lp, x = mixer_inputs()
    return {name: jnp.stack([leaf, 0.5 * leaf])
            for name, leaf in lp.items()}, x


def gradient(mesh, **policy):
    """The loss and gradients of a scan of `LAYERS` KDA sublayers with
    their residual, each under `Transformer._remat`, as a function of the
    stacked leaves and the stream."""
    def layer(x, lp):
        return x + kda.kda_mixer(x, lp, chunk=CHUNK, lower=-5.0, eps=1e-6,
                                 mesh=mesh), None

    wrapped = Transformer._remat(layer, TransformerConfig(
        vocab_size=8, d_model=32, n_layers=LAYERS, n_heads=2, d_ff=8,
        remat=True, **policy))
    return jax.value_and_grad(
        lambda p, x: jnp.sum(jax.lax.scan(wrapped, x, p)[0] ** 2),
        argnums=(0, 1))


def readings(fn):
    """How often the gradient's program holds each kernel, and how many
    values carry the delta rule's name."""
    counts = {"kda_delta_fwd": 0, "kda_delta_bwd": 0, "named": 0}
    for eqn in equations(jax.make_jaxpr(fn)(*stacked()).jaxpr):
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        elif eqn.primitive.name == "name" \
                and eqn.params["name"] == kda.DELTA_RESIDUALS:
            counts["named"] += 1
    return counts


@pytest.fixture
def interpreted(monkeypatch):
    """The pallas rule on the CPU: `kda_mixer` takes it for `OneTpu()`."""
    monkeypatch.setattr(kda, "gated_delta_rule_pallas", functools.partial(
        kda.gated_delta_rule_pallas, interpret=True))


def test_the_gradient_runs_the_forward_kernel_once_a_layer(interpreted):
    """A scan's body is one layer: under the default policy the forward
    scan holds `kda_delta_fwd` and the backward scan `kda_delta_bwd`
    alone; under "full" the backward scan runs the forward kernel again.
    The three named values are the forward's, once."""
    assert TransformerConfig(vocab_size=8, d_model=32, n_layers=1, n_heads=2,
                             d_ff=8).remat_policy == "attention"
    default = readings(gradient(OneTpu()))
    full = readings(gradient(OneTpu(), remat_policy="full"))
    assert default == {"kda_delta_fwd": 1, "kda_delta_bwd": 1, "named": 3}
    # "full" saves nothing: its forward keeps of the three the output
    # alone, and the forward it runs again names all three
    assert full == {"kda_delta_fwd": 2, "kda_delta_bwd": 1, "named": 4}


def test_the_kept_values_are_the_ones_made_again(interpreted):
    """The same loss and every gradient as "full", to the bit."""
    got = jax.jit(gradient(OneTpu()))(*stacked())
    want = jax.jit(gradient(OneTpu(), remat_policy="full"))(*stacked())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    assert all(bool(jnp.any(g != 0)) for g in jax.tree.leaves(got[1]))


@pytest.mark.parametrize("mesh", [None, FourTpus()], ids=["cpu", "four_tpus"])
def test_the_xla_rule_names_nothing_and_its_program_stays(mesh):
    """`gated_delta_rule` keeps what autodiff keeps: no kernel, no named
    value, and the default policy's program is "full"'s."""
    default, full = gradient(mesh), gradient(mesh, remat_policy="full")
    assert readings(default) == {"kda_delta_fwd": 0, "kda_delta_bwd": 0,
                                 "named": 0}
    assert jax.jit(default).lower(*stacked()).as_text() \
        == jax.jit(full).lower(*stacked()).as_text()
