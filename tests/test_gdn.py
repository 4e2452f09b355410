"""The chunked delta rule behind ONE decay a head (`ops/kda.py`: Gated
DeltaNet's gate, `g` `[B, T, H]`) against the recurrence step by step
(`benchmark/reference/olmo_hybrid_f32.delta_rule`, which shares no code
with `ray_tpu`): keys and values of unlike widths, beta above 1, gates
down to -30 a step, sequences that are no whole chunks; forward and the
five gradients, at cotangents of 1 and of 1e-6 (an underflow shows at the
second). Float32 against float32 on the CPU: 1e-4 relative to the largest
entry of each compared array allows the order of the sums and nothing
else.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

ref = load_module("reference", "olmo_hybrid_f32")

RTOL = 1e-4
B, H, DK, DV, CHUNK = 2, 3, 24, 40, 32


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


def operands(seed, t, gate="spread", beta_scale=2.0, shared=0.0):
    """q, k, v as a convolution leaves them, g a head's log-decay, beta.
    `gate`: `spread` from -1e-3 to -30 a step, `hard` -30 everywhere,
    `none` 0 (a plain delta rule), `runs` long runs at -30 among mild
    steps. `shared`: a direction every key has in common (what silu
    leaves), which makes the key-key block large."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, t, H, DK))
    k = jax.random.normal(ks[1], (B, t, H, DK)) + shared
    v = jax.random.normal(ks[2], (B, t, H, DV))
    if gate == "spread":
        g = -jnp.exp(jax.random.uniform(ks[3], (B, t, H), jnp.float32,
                                        np.log(1e-3), np.log(30.0)))
    elif gate == "hard":
        g = jnp.full((B, t, H), -30.0)
    elif gate == "none":
        g = jnp.zeros((B, t, H))
    else:
        g = -0.05 * jnp.ones((B, t, H))
        g = g.at[:, t // 4:t // 4 + 20].set(-30.0)
    beta = beta_scale * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[4], (B, t, H)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    return ref.delta_rule(ref.l2_norm(q), ref.l2_norm(k), v, g, beta)


def chunked(q, k, v, g, beta):
    return kda.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)


# under `jax.jit`: one compile a length, not one an op and case
SIDES = {fn: (jax.jit(fn), jax.jit(jax.grad(
    lambda ct, *a, fn=fn: jnp.sum(fn(*a) * ct), argnums=range(1, 6))))
    for fn in (chunked, recurrence)}


# one length for most cases: op by op, a shape compiles once a process
CASES = [
    pytest.param(64, "spread", 0.0, id="two_chunks"),
    pytest.param(80, "spread", 0.0, id="two_chunks_and_a_half"),
    pytest.param(7, "spread", 0.0, id="shorter_than_a_chunk"),
    pytest.param(80, "hard", 0.0, id="every_gate_at_minus_30"),
    pytest.param(80, "none", 0.0, id="no_decay"),
    pytest.param(80, "runs", 0.0, id="runs_at_minus_30"),
    pytest.param(80, "spread", 1.0, id="keys_share_a_direction"),
]


@pytest.mark.parametrize("t,gate,shared", CASES)
def test_chunked_rule_matches_the_recurrence(t, gate, shared):
    with jax.default_matmul_precision("highest"):
        args = operands(len(gate) + t, t, gate, shared=shared)
        assert float(args[4].max()) > 1.5      # beta above 1 is in the case
        got = SIDES[chunked][0](*args)
        assert got.shape == (B, t, H, DV) and got.dtype == jnp.float32
        close(got, SIDES[recurrence][0](*args))


@pytest.mark.parametrize("cotangent", [1.0, 1e-6], ids=["ct_1", "ct_1e-6"])
@pytest.mark.parametrize("t,gate,shared", CASES)
def test_chunked_rule_gradients_match_the_recurrences(t, gate, shared,
                                                      cotangent):
    with jax.default_matmul_precision("highest"):
        args = operands(100 + len(gate) + t, t, gate, shared=shared)
        ct = cotangent * jax.random.normal(jax.random.key(9),
                                           (B, t, H, DV))
        got = SIDES[chunked][1](ct, *args)
        want = SIDES[recurrence][1](ct, *args)
        for name, g_, w_ in zip("q k v g beta".split(), got, want):
            if gate == "none" and name == "g":
                # at g = 0 both are exact derivatives of the same function
                close(g_, w_, 5 * RTOL)
            else:
                close(g_, w_)


def test_a_gate_a_head_is_the_gate_a_channel_held_constant():
    """One algorithm, two gates: a head's decay broadcast over its
    channels goes down the channel path and gives the same output (inside
    that path's bound of -5 a step)."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = operands(3, 80, "none", beta_scale=1.0)
        g = -5.0 * jax.random.uniform(jax.random.key(4), g.shape)
        per_head = kda.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)
        per_channel = kda.gated_delta_rule(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
            chunk=CHUNK)
        close(per_head, per_channel)


@pytest.mark.parametrize("c", [2, 16, 64])
@pytest.mark.parametrize("size", [0.3, 1.0, 2.0])
def test_inverse_by_halves(c, size):
    """Against numpy's inverse in float64, on strictly lower blocks as
    large as beta = 2 on keys that share a direction makes them, where
    the power series loses the inverse altogether."""
    n = -size * jnp.tril(jax.random.uniform(
        jax.random.key(c), (3, 2, c, c), jnp.float32, 0.2, 0.6), -1)
    want = np.linalg.inv(np.eye(c) - np.asarray(n, np.float64))
    got = np.asarray(kda._unit_lower_inverse_by_halves(n))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(np.triu(got, 1)).max() == 0.0
    if c == 64 and size == 2.0:
        series = np.asarray(kda._unit_lower_inverse(n))
        assert np.abs(series - want).max() > 0.1 * np.abs(want).max()


@pytest.mark.parametrize("per_channel", [False, True])
def test_head_norm_under_either_gate(per_channel):
    heads, d = 3, 8
    o = jax.random.normal(jax.random.key(0), (2, 5, heads * d))
    gain = 1.0 + 0.3 * jax.random.normal(jax.random.key(1), (d,))
    gate = jax.random.normal(jax.random.key(2),
                             (2, 5, heads * (d if per_channel else 1)))
    got = kda.head_norm(o, gate, gain, 1e-6)
    oh = o.reshape(2, 5, heads, d)
    want = ref.rms_norm(oh, gain, 1e-6) * gate.reshape(2, 5, heads, -1)
    close(got, want.reshape(o.shape), 1e-6)


def test_head_log_decay_is_unbounded_and_float32():
    a = jnp.asarray([[[-50.0, 0.0, 50.0]]], jnp.bfloat16)
    g = kda.head_log_decay(a, jnp.log(jnp.asarray([8.0, 1.0, 2.0])),
                           jnp.asarray([0.0, 1.0, 0.0]))
    assert g.dtype == jnp.float32
    close(g, ref.decay_gate(a.astype(jnp.float32),
                            jnp.log(jnp.asarray([8.0, 1.0, 2.0])),
                            jnp.asarray([0.0, 1.0, 0.0])), 1e-6)
    assert float(g[0, 0, 2]) == pytest.approx(-100.0) and float(g.max()) <= 0


@pytest.mark.parametrize("shape", [(8192, 15, 96, 192), (8192, 16, 128, 128),
                                   (100, 3, 24, 40)])
def test_a_gate_a_head_takes_the_xla_path(shape):
    """The kernels take a decay a channel at keys and values 128 wide:
    whatever the mesh and the shapes, a decay a head is the XLA path's."""
    assert kda.kda_delta_impl(None, *shape, 64, per_head=True) == "xla"


def test_the_mixer_reads_its_heads_off_its_leaves():
    """Two shares of the heads add up to the whole mixer's output (before
    the norm on it): a share is a slice of every leaf's head axis."""
    from ray_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, layer_pattern="d", n_heads=2,
        d_ff=32, gdn_heads=4, gdn_key_dim=8, gdn_value_dim=16,
        gdn_neg_eigval=True, gdn_chunk=16, dtype="float32")
    sub = jax.tree.map(lambda x: x[0], Transformer.init(
        jax.random.key(0), cfg)["runs"][0][0])
    h = jax.random.normal(jax.random.key(1), (2, 40, 32))
    axis = {"w_gdn_qkv": 1, "gdn_conv": 0, "w_gdn_ab": 2, "gdn_A_log": 0,
            "gdn_dt_bias": 0, "w_gdn_g": 1, "w_gdn_out": 0}

    def share(lo, hi):
        lp = {name: jax.lax.slice_in_dim(leaf, lo, hi, axis=axis[name])
              if name in axis else leaf for name, leaf in sub.items()}
        return jax.jit(lambda lp: kda.gdn_mixer(
            h, lp, chunk=16, beta_scale=2.0, eps=1e-6))(lp)

    with jax.default_matmul_precision("highest"):
        close(share(0, 1) + share(1, 4), share(0, 4))


@pytest.mark.parametrize("t,h,dk,dv,per_head,limit_mb", [
    # Olmo-Hybrid's cell, `train_olmohybrid7b_tp2_d4`: 562 MB (1,190 with
    # the batched stage kept: nine float32 `[15, 128, 64, 64]` blocks,
    # `rhs` twice, every level of the inverse five times over)
    pytest.param(8192, 15, 96, 192, True, 600, id="a_head_olmo_hybrid"),
    # Ling's shape on the XLA path (a mesh, the CPU): 826 MB (2,705 with
    # three column factors `[8, 256, 4, 64, 128]` of 268 MB each)
    pytest.param(16384, 8, 128, 128, False, 900, id="a_channel_ling"),
])
def test_the_backward_is_not_handed_the_batched_stage(t, h, dk, dv,
                                                      per_head, limit_mb):
    """`gated_delta_rule` makes its batched stage (A and B, the masks, the
    chunks' inverses, the solve) again in its backward (PR 70): what
    autodiff keeps of one bfloat16 sequence of t steps is the chain's six
    per-chunk tensors, the entering states and the chunked operands, none
    of it a `[.., C, C]` float32 block or a decay a channel's `[.., C/16,
    C, D]` column factor. Read from the jaxpr of abstract inputs: no array
    is made."""
    from jax._src.ad_checkpoint import saved_residuals

    def of(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    c = 64
    kept = [a for a, _ in saved_residuals(
        lambda *a: kda.gated_delta_rule(*a, chunk=c),
        of(1, t, h, dk), of(1, t, h, dk), of(1, t, h, dv),
        of(*((1, t, h) if per_head else (1, t, h, dk)), dtype=jnp.float32),
        of(1, t, h, dtype=jnp.float32))]
    total_mb = sum(a.size * a.dtype.itemsize for a in kept) / 1e6
    assert total_mb <= limit_mb, total_mb
    for a in kept:
        if a.dtype == jnp.float32:
            assert a.shape[-2:] != (c, c), a.shape    # a block, a mask's where
            assert a.shape[-3:] != (c // kda.SUB, c, dk), a.shape   # factors
    # the entering states are what the chain's checkpoint keeps
    assert (t // c, 1, h, dk, dv) in [a.shape for a in kept]
