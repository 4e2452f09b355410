"""The delta rule's pallas kernels (`ops/kda.gated_delta_rule_pallas`) in
interpret mode on the CPU: forward and all five gradients against
`gated_delta_rule` (the XLA path) and against the step-by-step float32
recurrence of `benchmark/reference/ling3_f32.py`, at a cotangent of order
1 and at one of a loss's size; and the trace-time choice between the two
(`kda_delta_impl`). What the chip's compiler makes of the kernels is
`tests/test_chip_compile.py`'s."""

import functools
import os
import sys
import types

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

from tests._programs import value_and_grads  # noqa: E402

ref = load_module("reference", "ling3_f32")

RTOL = 1e-4                   # tests/test_ling3_reference.py's
GRADS = ("q", "k", "v", "g", "beta")
D = kda.DELTA_LANES
CHUNK = kda.DELTA_CHUNK
# [B, T, H]: one pair of heads over two chunks; two pairs, a grid step
# each way (the default block of four heads and the tests' of two), over
# four chunks
SHAPES = [(1, 128, 2), (1, 256, 4)]
IDS = ["b{}-t{}-h{}".format(*shape) for shape in SHAPES]
# a cotangent of order 1, and one of a loss's size: a factor of e^-80
# times the second is flushed to zero (PERF.md section 6, PR 50 (1))
COTANGENTS = [1.0, 1e-6]


def delta_inputs(seed, b, t, h, dtype=jnp.float32, gate=None, beta=None):
    """q, k, v `[B, T, H, D]` as a convolution and silu would leave them,
    g in (-5, 0), beta in (0, 1); `gate` / `beta`: that value at every
    step instead."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (b, t, h, D)).astype(dtype)
               for i in range(3))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, t, h, D)))
    bt = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    if beta is not None:
        bt = jnp.full_like(bt, beta)
    probe = jax.random.normal(ks[5], (b, t, h, D))
    return (q, k, v, g, bt), probe


def recurrence(q, k, v, g, beta):
    """The reference's step-by-step rule in float32."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(ref.l2_norm(q.astype(f32)),
                              ref.l2_norm(k.astype(f32)), v.astype(f32), g,
                              beta)


def xla(q, k, v, g, beta):
    return kda.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)


@functools.lru_cache(maxsize=None)
def kernel(head_block=None):
    """`gated_delta_rule_pallas` in interpret mode, on `[B, T, H, D]`
    operands like the other two."""
    def rule(q, k, v, g, beta):
        b, t, h, d = q.shape
        return kda.gated_delta_rule_pallas(
            *(a.reshape(b, t, h * d) for a in (q, k, v, g)), beta,
            chunk=CHUNK, head_block=head_block, interpret=True
        ).reshape(b, t, h, d)
    return jax.jit(rule)


@functools.lru_cache(maxsize=None)
def case(shape, seed, cotangent=1.0, dtype=jnp.float32, edge=None,
         head_block=None):
    """The inputs at (shape, seed, dtype, edge) and what the kernel, the
    XLA path and the float32 recurrence give at them, outputs and the
    gradients under `cotangent` times the probe: computed once, read by
    the forward, the backward, the ends' and the bfloat16 tests."""
    args, probe = delta_inputs(seed, *shape, dtype, **EDGES.get(edge, {}))
    probe = probe * cotangent
    o, grads = value_and_grads(kernel(head_block))(probe, *args)
    xla_o, xla_grads = value_and_grads(xla)(probe, *args)
    want_o, want_grads = value_and_grads(recurrence)(probe, *args)
    return types.SimpleNamespace(
        args=args, o=o, grads=grads, xla_o=xla_o, xla_grads=xla_grads,
        want_o=want_o, want_grads=want_grads)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.isfinite(got).all(), what
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def assert_forward_close(at):
    assert at.o.dtype == jnp.float32 and at.o.shape == at.args[2].shape
    assert_close(at.o, at.xla_o, "o against gated_delta_rule")
    assert_close(at.o, at.want_o, "o against the recurrence")


def assert_grads_close(at):
    """The kernel's five gradients against the XLA path's and the float32
    recurrence's, shapes and dtypes the XLA path's."""
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, o, f"d{name} against gated_delta_rule")
        assert_close(g, w, f"d{name} against the recurrence")


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_forward_is_the_xla_rule_and_the_recurrence(shape, seed):
    assert_forward_close(case(shape, seed))


@pytest.mark.parametrize("cotangent", COTANGENTS)
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_backward_is_the_xla_rules_and_the_recurrences(
        shape, seed, cotangent):
    assert_grads_close(case(shape, seed, cotangent))


# the gate at its bound at every step and channel (a chunk's running sum
# reaches -320: `(k e^G)(k e^-G)^T` would overflow at the 18th step) and
# at 0 (no decay: every factor is 1); beta at 0 (nothing is written: the
# output is zero, its gradients are not) and at 1
EDGES = {"gate_at_the_bound": dict(gate=-5.0), "gate_at_0": dict(gate=0.0),
         "beta_0": dict(beta=0.0), "beta_1": dict(beta=1.0)}


@pytest.mark.parametrize("cotangent", COTANGENTS)
@pytest.mark.parametrize("edge", EDGES)
def test_the_gates_and_betas_ends(edge, cotangent):
    at = case((1, 192, 2), 2, cotangent, edge=edge)
    if edge == "beta_0":
        assert not np.asarray(at.o).any() and not np.asarray(at.want_o).any()
    else:
        assert_forward_close(at)
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        if not np.asarray(w).any():      # beta 0: nothing reaches k, v, g
            assert not np.asarray(g).any(), name
            continue
        assert_close(g, o, f"d{name} against gated_delta_rule")
        assert_close(g, w, f"d{name} against the recurrence")


@pytest.mark.parametrize("head_block", [None, 2], ids=["default", "hb2"])
def test_batch_rows_and_head_blocks(head_block):
    """Every row of a batch and every block of heads starts from a zero
    state and keeps its own: the state's scratch is set to zero at each
    row's and block's first chunk. Four heads are one grid step by
    default and two at a block of two."""
    assert kda.DELTA_HEADS == 4
    at = case((2, 128, 4), 3, 1e-6, head_block=head_block)
    assert_forward_close(at)
    # a row alone, and a pair of heads alone, give what they give in the
    # batch
    alone = kernel(head_block)(*(a[-1:] for a in at.args))
    assert_close(alone, at.o[-1:], "the last row alone", 1e-6)
    pair = kernel()(*(a[:, :, 2:] for a in at.args))
    assert_close(pair, at.o[:, :, 2:], "the second pair alone", 1e-6)
    assert_grads_close(at)


def states_entering(q, k, v, g, beta, every):
    """The float32 recurrence's state before the steps 0, `every`,
    2 `every`, ...: `[B, T/every, H·Dv, D]`, transposed as the kernels
    hold it."""
    q, k = ref.l2_norm(q), ref.l2_norm(k)

    def step(state, at):                     # state [B, H, D, Dv]
        k_t, v_t, a_t, b_t = at
        decayed = state * jnp.exp(a_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, decayed)
        return decayed + jnp.einsum(
            "bhk,bhv->bhkv", k_t, (v_t - seen) * b_t[..., None]), state

    b, _, h, d = q.shape
    with jax.default_matmul_precision("highest"):
        _, before = jax.lax.scan(
            step, jnp.zeros((b, h, d, d), jnp.float32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (k, v, g, beta)))
    before = jnp.moveaxis(before[::every], 0, 1)     # [B, nc, H, D, Dv]
    return jnp.swapaxes(before, 3, 4).reshape(b, -1, h * d, d)


def test_the_forwards_residual_is_the_state_entering_each_chunk(monkeypatch):
    """What the forward kernel keeps for the backward besides its inputs:
    the recurrence's state as it enters each chunk (zero at the first),
    float32, `[Dv, D]` a head."""
    kept, made = [], kda._delta_calls

    def spy(*key):
        rule = made(*key)

        def call(*operands):
            kept.append(rule.fwd(*operands)[1][1])
            return rule(*operands)
        return call

    monkeypatch.setattr(kda, "_delta_calls", spy)
    (q, k, v, g, beta), _ = delta_inputs(5, 2, 192, 2)
    b, t, h, d = q.shape
    kda.gated_delta_rule_pallas(
        *(a.reshape(b, t, h * d) for a in (q, k, v, g)), beta,
        interpret=True)
    entering, = kept
    assert entering.dtype == jnp.float32
    assert entering.shape == (b, t // CHUNK, h * d, d)
    assert not np.asarray(entering[:, 0]).any()
    assert_close(entering, states_entering(q, k, v, g, beta, CHUNK),
                 "the entering states")


def flat_operands(q, k, v, g, beta):
    """The kernels' own operands: `[B, T, H·D]` and beta's two columns a
    pair of heads (`gated_delta_rule_pallas`'s turn)."""
    b, t, h, d = q.shape
    return (*(a.reshape(b, t, h * d) for a in (q, k, v, g)),
            jnp.swapaxes(beta.reshape(b, t, h // 2, 2), 1, 2))


def kernels_in(jaxpr):
    """name -> the primitives of each pallas call's body in a jaxpr."""
    return {eqn.params["name"]: [e.primitive.name
                                 for e in eqn.params["jaxpr"].eqns]
            for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"}


def whole_inverses(q, k, v, g, beta):
    """`_per_chunk`'s `inv` of every chunk of ONE pair of heads, whole:
    `[B, T/C, 128, 128]` float32, by a kernel of this file that calls it
    as the delta rule's own kernels do."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, pair = beta.shape[0], beta.shape[2], 2 * CHUNK

    def kernel_body(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, g_scr):
        out_ref[0, 0] = kda._per_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref,
                                       g_scr, 0).inv

    wide = pl.BlockSpec((1, CHUNK, 2 * D), lambda bi, ci: (bi, ci, 0))
    return pl.pallas_call(
        kernel_body, grid=(b, t // CHUNK),
        in_specs=[wide] * 4 + [pl.BlockSpec((1, 1, CHUNK, 2),
                                            lambda bi, ci: (bi, 0, ci, 0))],
        out_specs=pl.BlockSpec((1, 1, pair, pair),
                               lambda bi, ci: (bi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t // CHUNK, pair, pair),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((pair, D), jnp.float32)],
        interpret=True)(q, k, v, g, beta)


def test_the_forward_keeps_each_chunks_inverse():
    """The forward kernel's third output: a pair's two `[64, 64]` diagonal
    blocks of `_per_chunk`'s `[128, 128]` inverse, side by side, float32
    and bit for bit; what lies between the heads, which is not kept, is
    zero; and it is the inverse the XLA path makes of the same chunk."""
    args, _ = delta_inputs(6, 2, 192, 2)
    flat = flat_operands(*args)
    b, t, h = args[4].shape
    _, (_, _, kept) = kda._delta_calls(b, t, h, 2, True).fwd(*flat)
    assert kept.dtype == jnp.float32
    assert kept.shape == (b, t // CHUNK, h // 2 * CHUNK, 2 * CHUNK)
    whole = np.asarray(whole_inverses(*flat))
    np.testing.assert_array_equal(kept[..., :CHUNK],
                                  whole[..., :CHUNK, :CHUNK])
    np.testing.assert_array_equal(kept[..., CHUNK:],
                                  whole[..., CHUNK:, CHUNK:])
    assert not whole[..., :CHUNK, CHUNK:].any()
    assert not whole[..., CHUNK:, :CHUNK].any()
    # the XLA path's, from `_channel_blocks`: [B, H, nc, c, c]
    q, k, _, g, beta = args

    def chunks(a):
        return jnp.moveaxis(a.reshape(b, t // CHUNK, CHUNK, h, -1), 3, 1)

    kc = chunks(kda.l2norm(k))
    a_mat, _ = kda._channel_blocks(chunks(kda.l2norm(q)), kc,
                                   jnp.cumsum(chunks(g), axis=3))
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)
    want = kda._unit_lower_inverse(
        -chunks(beta[..., None]) * jnp.where(strict, a_mat, 0.0))
    for head in range(h):
        assert_close(kept[..., head * CHUNK:(head + 1) * CHUNK],
                     want[:, head], f"head {head}'s inverses", 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_backward_handed_the_inverse_is_the_one_that_makes_it(
        monkeypatch, dtype):
    """`kda_delta_bwd` reads the inverse the forward wrote where it made
    one by doubling (`_per_chunk(..., inv)`): its body holds no loop, the
    forward's holds one a pair, and its five outputs are those of the
    backward that makes the inverse again, bit for bit."""
    args, probe = delta_inputs(7, 1, 256, 4, dtype)
    flat = flat_operands(*args)
    d_o = 1e-6 * probe.reshape(flat[0].shape)
    made, per_chunk, handed = kda._delta_calls.__wrapped__, kda._per_chunk, []

    def gradients():
        rule = made(1, 256, 4, kda.DELTA_HEADS, True)
        jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(rule, *a)[1](d_o))(*flat)
        return jax.vjp(rule, *flat)[1](d_o), kernels_in(jaxpr.jaxpr)

    got, bodies = gradients()
    assert bodies["kda_delta_fwd"].count("scan") == kda.DELTA_HEADS // 2
    assert "scan" not in bodies["kda_delta_bwd"]

    def parents(*operands):          # the chunk with the inverse left out
        handed.append(len(operands) == 8)
        return per_chunk(*operands[:7])

    monkeypatch.setattr(kda, "_per_chunk", parents)
    want, bodies = gradients()
    assert any(handed)
    assert bodies["kda_delta_bwd"].count("scan") == kda.DELTA_HEADS // 2
    for name, g, w in zip(GRADS, got, want):
        assert g.dtype == w.dtype and np.asarray(w).any(), name
        np.testing.assert_array_equal(g, w, err_msg="d" + name)


# bfloat16 q, k and v (and so bfloat16 dq, dk, dv), float32 gates, sums,
# blocks, inverse and state: the two paths round the same operands of the
# same products (T, T beta V, T beta K e^G, U, the state), so each is held
# to the float32 recurrence at one tolerance, the band the XLA path keeps
BF16_RTOL = {"o": 2e-2, "q": 3e-2, "k": 3e-2, "v": 3e-2, "g": 3e-2,
             "beta": 3e-2}


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_operands_stay_in_the_xla_paths_band(seed):
    at = case((1, 256, 2), seed, dtype=jnp.bfloat16)
    assert at.o.dtype == jnp.float32
    assert_close(at.o, at.want_o, "o", BF16_RTOL["o"])
    assert_close(at.xla_o, at.want_o, "gated_delta_rule's o", BF16_RTOL["o"])
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, w, "d" + name, BF16_RTOL[name])
        assert_close(o, w, "gated_delta_rule's d" + name, BF16_RTOL[name])


def test_a_shape_the_kernels_do_not_tile_is_refused():
    (q, k, v, g, beta), _ = delta_inputs(0, 1, 96, 2)
    flat = [a.reshape(1, 96, 2 * D) for a in (q, k, v, g)]
    with pytest.raises(ValueError, match="do not tile"):
        kda.gated_delta_rule_pallas(*flat, beta, interpret=True)
    with pytest.raises(ValueError, match="do not tile"):
        kda.gated_delta_rule_pallas(*(a[:, :64] for a in flat),
                                    beta[:, :64], chunk=32, interpret=True)


class OneTpu:
    """What `kda_delta_impl` reads of a mesh: its size and a device."""

    size = 1

    class devices:
        flat = [type("D", (), {"platform": "tpu"})()]


class FourTpus(OneTpu):
    size = 4


# [T, heads, d_k, d_v, chunk]
CELL = (16384, 8, 128, 128, 64)


@pytest.mark.parametrize("shape,want", [
    (CELL, "pallas"),
    ((64, 2, 128, 128, 64), "pallas"),         # the smallest
    ((4096, 32, 128, 128, 64), "pallas"),      # an uncut layer's heads
    ((16384 + 32, 8, 128, 128, 64), "xla"),    # no whole chunks: padded
    ((16384, 8, 128, 128, 32), "xla"),         # another chunk
    ((16384, 8, 128, 128, 128), "xla"),
    ((16384, 7, 128, 128, 64), "xla"),         # heads that are no pairs
    ((16384, 8, 64, 64, 64), "xla"),           # half a lane tile
    ((16384, 8, 128, 256, 64), "xla"),         # wider values
    ((16384, 8, 256, 256, 64), "xla"),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v)
def test_delta_impl_by_shape(shape, want):
    assert kda.kda_delta_impl(OneTpu(), *shape) == want
    assert kda.delta_shape_ok(*shape) == (want == "pallas")
    assert kda.kda_delta_impl(None, *shape) == "xla"          # the CPU
    assert kda.kda_delta_impl(FourTpus(), *shape) == "xla"    # GSPMD's


def test_delta_impl_on_a_mesh_of_cpu_devices_is_xla():
    from ray_tpu.parallel import MeshConfig, make_mesh

    one = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    many = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:2])
    assert kda.kda_delta_impl(one, *CELL) == "xla"
    assert kda.kda_delta_impl(many, *CELL) == "xla"


def mixer_inputs(dtype=jnp.float32, heads=2, t=128):
    d, taps = 32, 4
    ks = jax.random.split(jax.random.key(0), 8)
    lp = {"w_kda_qkv": 0.3 * jax.random.normal(ks[0], (d, 3, heads, D)),
          "w_kda_a": 0.3 * jax.random.normal(ks[1], (d, heads, D)),
          "w_kda_bg": 0.3 * jax.random.normal(ks[2], (d, 2, heads)),
          "w_kda_out": 0.1 * jax.random.normal(ks[3], (heads, D, d)),
          "kda_conv": 0.5 * jax.random.normal(ks[4], (3, heads * D, taps)),
          "kda_A_log": jnp.log(jax.random.uniform(
              ks[5], (heads,), minval=1.0, maxval=16.0)),
          "kda_a_bias": 0.1 * jax.random.normal(ks[6], (heads, D)),
          "kda_out_norm": jnp.ones((D,))}
    lp = {k: v.astype(dtype) if k.startswith("w_") else v
          for k, v in lp.items()}
    return lp, jax.random.normal(ks[7], (2, t, d)).astype(dtype)


def mix(x, lp, mesh):
    return kda.kda_mixer(x, lp, chunk=CHUNK, lower=-5.0, eps=1e-6,
                         mesh=mesh)


def test_the_mixer_takes_the_xla_rule_on_the_cpu():
    """`kda_mixer` asks `kda_delta_impl` and, here, traces no pallas call:
    the CPU's path is the one it was."""
    lp, x = mixer_inputs()
    for mesh in (None, FourTpus()):
        jaxpr = str(jax.make_jaxpr(lambda x: mix(x, lp, mesh))(x))
        assert "pallas_call" not in jaxpr
        assert "cumsum" in jaxpr
    # one TPU device and these shapes: the kernels, by name
    jaxpr = str(jax.make_jaxpr(lambda x: mix(x, lp, OneTpu()))(x))
    assert "kda_delta_fwd" in jaxpr and "cumsum" not in jaxpr
    # a length of no whole chunks: the XLA path pads it
    jaxpr = str(jax.make_jaxpr(lambda x: mix(x[:, :100], lp, OneTpu()))(x))
    assert "pallas_call" not in jaxpr


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, RTOL),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_the_mixer_with_the_kernels_is_the_mixer_without(monkeypatch, dtype,
                                                         rtol):
    """`kda_mixer` on one TPU device (the kernels, here in interpret mode)
    against itself on the CPU's path: the output and the gradient of every
    leaf and of the stream, at a cotangent of a loss's size."""
    lp, x = mixer_inputs(dtype)
    monkeypatch.setattr(kda, "gated_delta_rule_pallas", functools.partial(
        kda.gated_delta_rule_pallas, interpret=True))
    probe = 1e-6 * jax.random.normal(jax.random.key(1), x.shape)

    def both(mesh):
        def loss(lp, x):
            out = mix(x, lp, mesh)
            assert out.dtype == dtype
            return jnp.sum(out.astype(jnp.float32) * probe), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(lp, x)

    (_, out_k), (dlp_k, dx_k) = both(OneTpu())
    (_, out_x), (dlp_x, dx_x) = both(None)
    assert_close(out_k, out_x, "the mixer's output", rtol)
    assert_close(dx_k, dx_x, "the stream's gradient", rtol)
    for name in lp:
        assert dlp_k[name].dtype == dlp_x[name].dtype, name
        assert_close(dlp_k[name], dlp_x[name], "d" + name, rtol)
