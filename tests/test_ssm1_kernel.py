"""The Mamba-1 scan's pallas kernels (`ops/ssm.selective_scan_pallas`) in
interpret mode on the CPU: forward and all six gradients against
`selective_scan` (the XLA path, with the `D x` skip the mixer adds to it)
and against the step-by-step float32 recurrence of
`benchmark/reference/phi4flash_f32.py`; and the trace-time choice between
the two (`selective_scan_impl`). What the chip's compiler makes of the
kernels is `tests/test_chip_compile.py`'s."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests._programs import value_and_grads  # noqa: E402

ref = load_module("reference", "phi4flash_f32")

RTOL = 1e-4                   # tests/test_phi4flash_reference.py's
GRADS = ("x", "dt", "a", "b", "c", "d")
N = 16
CHUNK = 64                    # what T must divide by, no tiling
# [T, channels], tiled as on the chip (`scan1_channel_block`,
# `_scan1_group`): one lane tile; three time blocks and three channel
# blocks of 896, each walked in seven groups of a tile (2,688 is the least
# channel count above `SCAN1_CHANNELS`, so the least with several blocks);
# two blocks of 1,536 in three groups of four tiles (two blocks against
# three, a group of several tiles walked several times; the cell's own
# 5,120, blocks and groups at `SCAN1_CHANNELS` and `SCAN1_GROUP`, is
# compiled for the v5e at the cell's size by
# `tests/test_chip_compile.py::test_mamba1_scan_kernels_fwd_bwd`); a group
# of three lane tiles, and of two. Interpreted, a kernel costs by the
# channel: what needs no second channel block (a batch above one,
# bfloat16 operands) runs at the small shapes
SHAPES = [(128, 128), (384, 2688), (256, 3072), (128, 384), (256, 256)]
IDS = ["t{}-c{}".format(*shape) for shape in SHAPES]


def scan_inputs(seed, t, ch, dtype=jnp.float32, batch=1):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (batch, t, ch)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, t, ch)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (ch, N)))
    b = jax.random.normal(ks[3], (batch, t, N)).astype(dtype)
    c = jax.random.normal(ks[4], (batch, t, N)).astype(dtype)
    d = 1.0 + 0.3 * jax.random.normal(ks[5], (ch,))
    probe = jax.random.normal(ks[6], x.shape)
    return (x, dt, a, b, c, d), probe


def recurrence(x, dt, a, b, c, d):
    """The reference's step-by-step scan in float32, and the skip."""
    f32 = jnp.float32
    x, b, c = x.astype(f32), b.astype(f32), c.astype(f32)
    return jnp.stack([ref.recurrence(x[i], dt[i], a, b[i], c[i])
                      for i in range(x.shape[0])]) + x * d


def xla(x, dt, a, b, c, d):
    """The mixer's XLA path: `selective_scan`, the skip, float32 here."""
    return ssm.selective_scan(x, dt, a, b, c, CHUNK) \
        + x.astype(jnp.float32) * d


@functools.lru_cache(maxsize=None)
def kernel(chunk=CHUNK):
    """`selective_scan_pallas` in interpret mode."""
    return jax.jit(lambda *v: ssm.selective_scan_pallas(
        *v, chunk, interpret=True))


@functools.lru_cache(maxsize=None)
def case(shape, seed, dtype=jnp.float32, batch=1):
    """The inputs at (shape, seed, dtype, batch) and what the kernel, the
    XLA path and the float32 recurrence give at them, outputs and
    gradients: computed once, read by the forward, the backward, the
    batch's and the bfloat16 tests."""
    args, probe = scan_inputs(seed, *shape, dtype, batch)
    y, grads = value_and_grads(kernel())(probe, *args)
    xla_y, xla_grads = value_and_grads(xla)(probe, *args)
    want_y, want_grads = value_and_grads(recurrence)(probe, *args)
    return types.SimpleNamespace(
        args=args, probe=probe, y=y, grads=grads, xla_y=xla_y,
        xla_grads=xla_grads, want_y=want_y, want_grads=want_grads)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def assert_forward_close(at):
    assert at.y.dtype == jnp.float32 and at.y.shape == at.args[0].shape
    assert_close(at.y, at.xla_y, "y against selective_scan")
    assert_close(at.y, at.want_y, "y against the recurrence")


def assert_grads_close(at):
    """The kernel's six gradients against the XLA path's and the float32
    recurrence's, shapes and dtypes the XLA path's."""
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, o, f"d{name} against selective_scan")
        assert_close(g, w, f"d{name} against the recurrence")


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_forward_is_the_xla_scan_and_the_recurrence(shape, seed):
    assert_forward_close(case(shape, seed))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_backward_is_the_xla_scans_and_the_recurrences(shape, seed):
    assert_grads_close(case(shape, seed))


@pytest.mark.parametrize("shape,batch", [(SHAPES[3], 3), (SHAPES[4], 2)],
                         ids=[IDS[3] + "-b3", IDS[4] + "-b2"])
def test_kernel_with_a_batch_above_one(shape, batch):
    """Every row of a batch starts from a zero state and keeps its own:
    the state's scratch is set to zero at each row's first time block, and
    a and D (the operands without a batch axis) gather their gradients
    over the rows. (Two rows over three channel blocks:
    `test_the_forwards_residual_is_the_state_entering_each_block`.)"""
    at = case(shape, batch, batch=batch)
    assert_forward_close(at)
    # a row alone gives what it gives in the batch
    alone = kernel()(*(v[-1:] if v.ndim == 3 else v for v in at.args))
    assert_close(alone, at.y[-1:], "the last row alone", 1e-6)
    assert_grads_close(at)


def states_entering(x, dt, a, b, every):
    """The float32 recurrence's state before the steps 0, `every`,
    2 `every`, ...: `[B, T/every, N, C]`, n before the channels as the
    kernels hold it."""
    f32 = jnp.float32

    def step(s, at):
        x_t, dt_t, b_t = at                     # [B, C] x2, [B, N]
        return jnp.exp(dt_t[:, None] * a.T) * s \
            + b_t[..., None] * (dt_t * x_t)[:, None], s

    by_step = [jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b)]
    _, before = jax.lax.scan(
        step, jnp.zeros((x.shape[0],) + a.T.shape, f32), by_step)
    return jnp.moveaxis(before[::every], 0, 1)


@pytest.mark.parametrize("shape,batch,dtype", [
    (SHAPES[1], 2, jnp.float32), (SHAPES[2], 1, jnp.float32),
    (SHAPES[4], 1, jnp.bfloat16)],
    ids=[IDS[1] + "-b2", IDS[2], IDS[4] + "-bf16"])
def test_the_forwards_residual_is_the_state_entering_each_block(
        monkeypatch, shape, batch, dtype):
    """What the forward kernel keeps for the backward besides its inputs:
    the recurrence's state as it enters each block of `SCAN1_STEPS` steps
    (zero at the first), channel blocks and lane groups back in place."""
    t, ch = shape
    kept, made = [], ssm._scan1_calls

    def spy(*key):
        scan = made(*key)

        def call(*operands):
            kept.append(scan.fwd(*operands)[1][1])
            return scan(*operands)
        return call

    monkeypatch.setattr(ssm, "_scan1_calls", spy)
    (x, dt, a, b, c, d), _ = scan_inputs(5, t, ch, dtype, batch)
    ssm.selective_scan_pallas(x, dt, a, b, c, d, CHUNK, interpret=True)
    entering, = kept
    assert entering.dtype == jnp.float32
    assert entering.shape == (batch, t // ssm.SCAN1_STEPS, N, ch)
    assert not np.asarray(entering[:, 0]).any()
    assert_close(entering, states_entering(x, dt, a, b, ssm.SCAN1_STEPS),
                 "the entering states")


# bfloat16 x, b and c (and so a bfloat16 `y + D x` and dx), float32 dt,
# decays, state and sums: the two paths round the same operands, so each
# is held to the float32 recurrence at one tolerance
BF16_RTOL = {"y": 1e-2, "x": 2e-2, "dt": 2e-2, "a": 2e-2, "b": 2e-2,
             "c": 2e-2, "d": 2e-2}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", [SHAPES[3], SHAPES[4]],
                         ids=[IDS[3], IDS[4]])
def test_bf16_operands_are_held_to_the_f32_recurrence(shape, seed):
    at = case(shape, seed, jnp.bfloat16)
    assert at.y.dtype == jnp.bfloat16       # rounded once, in the kernel
    assert_close(at.y, at.want_y, "y", BF16_RTOL["y"])
    assert_close(at.xla_y, at.want_y, "selective_scan's y", BF16_RTOL["y"])
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, w, "d" + name, BF16_RTOL[name])
        assert_close(o, w, "selective_scan's d" + name, BF16_RTOL[name])


def test_a_long_decay_does_not_overflow():
    """dt x |A| of 40 a step: every decay is the exponential of a
    non-positive number, forward and backward, so nothing is inf and no
    inf x 0 a NaN in the value or a gradient."""
    (x, dt, a, b, c, d), probe = scan_inputs(3, 256, 128)
    dt, a = dt * 0 + 5.0, a * 0 - 8.0
    y, grads = value_and_grads(kernel())(probe, x, dt, a, b, c, d)
    assert np.isfinite(np.asarray(y)).all()
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    want_y, want = value_and_grads(recurrence)(probe, x, dt, a, b, c, d)
    assert_close(y, want_y, "y")
    assert_close(grads[0], want[0], "dx")


@pytest.mark.parametrize("t,chunk", [(192, 64), (256, 96)],
                         ids=["no-whole-time-blocks", "no-whole-chunks"])
def test_a_length_of_no_whole_blocks_is_refused_not_padded(t, chunk):
    args, _ = scan_inputs(0, t, 128)
    with pytest.raises(ValueError, match="whole chunks"):
        kernel(chunk=chunk)(*args)


class OneTpu:
    """What `selective_scan_impl` reads of a mesh: its size and a
    device."""

    size = 1

    class devices:
        flat = [type("D", (), {"platform": "tpu"})()]


class FourTpus(OneTpu):
    size = 4


# [T, channels, state, chunk]
CELL = (16384, 5120, 16, 1024)


@pytest.mark.parametrize("shape,want", [
    (CELL, "pallas"),
    ((128, 128, 8, 128), "pallas"),          # the smallest
    ((4096, 8192, 16, 64), "pallas"),        # a wider model's, chunk 64
    ((16384 + 64, 5120, 16, 64), "xla"),     # no whole time blocks
    ((16384, 5120, 16, 3072), "xla"),        # no whole chunks: refused
    ((16384, 5120 + 64, 16, 1024), "xla"),   # channels of no whole tiles
    ((16384, 5120, 4, 1024), "xla"),         # a state under a sublane tile
    ((16384, 5120, 12, 1024), "xla"),        # a state of no whole tiles
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v)
def test_scan_impl_by_shape(shape, want):
    assert ssm.selective_scan_impl(OneTpu(), *shape) == want
    assert ssm.scan1_shape_ok(*shape) == (want == "pallas")
    assert ssm.selective_scan_impl(None, *shape) == "xla"        # the CPU
    assert ssm.selective_scan_impl(FourTpus(), *shape) == "xla"  # GSPMD's


def test_scan_impl_on_a_mesh_of_cpu_devices_is_xla():
    from ray_tpu.parallel import MeshConfig, make_mesh

    one = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    many = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:2])
    assert ssm.selective_scan_impl(one, *CELL) == "xla"
    assert ssm.selective_scan_impl(many, *CELL) == "xla"


@pytest.mark.parametrize("channels,want", [
    (5120, 2560), (128, 128), (384, 384), (2560, 2560), (8192, 2048),
    (5248, 128), (5120 + 64, None), (64, None)])
def test_scan1_channel_block(channels, want):
    assert ssm.scan1_channel_block(channels) == want


def mixer_inputs(dtype=jnp.float32):
    inner, d, rank = 128, 32, 4
    ks = jax.random.split(jax.random.key(0), 9)
    lp = {"w_in": 0.1 * jax.random.normal(ks[0], (d, 2 * inner)),
          "w_x": 0.1 * jax.random.normal(ks[1], (inner, rank + 2 * N)),
          "w_dt": 0.1 * jax.random.normal(ks[2], (rank, inner)),
          "w_out": 0.1 * jax.random.normal(ks[3], (inner, d)),
          "conv_w": 0.3 * jax.random.normal(ks[4], (inner, 4)),
          "conv_b": jnp.zeros(inner),
          "dt_bias": jax.random.normal(ks[6], (inner,)) - 1.0,
          "A_log": 0.5 * jax.random.normal(ks[7], (inner, N)),
          "D": 1.0 + 0.3 * jax.random.normal(ks[8], (inner,))}
    lp = {k: v.astype(dtype) if k.startswith(("w_", "conv")) else v
          for k, v in lp.items()}
    return lp, jax.random.normal(ks[5], (2, 256, d)).astype(dtype)


def test_the_mixer_takes_the_xla_scan_on_the_cpu():
    """`mamba1_mixer` asks `selective_scan_impl` and, here, traces no
    pallas call: the CPU's path is the one it was."""
    lp, x = mixer_inputs()
    for mesh in (None, FourTpus()):
        jaxpr = str(jax.make_jaxpr(lambda x: ssm.mamba1_mixer(
            x, lp, chunk=128, mesh=mesh))(x))
        assert "pallas_call" not in jaxpr
        assert "cumsum" in jaxpr
    # one TPU device and these shapes: the kernels, by name
    jaxpr = str(jax.make_jaxpr(lambda x: ssm.mamba1_mixer(
        x, lp, chunk=128, mesh=OneTpu()))(x))
    assert "selective_scan_fwd" in jaxpr and "cumsum" not in jaxpr


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, RTOL),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_the_mixer_with_the_kernels_is_the_mixer_without(monkeypatch, dtype,
                                                         rtol):
    """`mamba1_mixer` on one TPU device (the kernels, here in interpret
    mode) against itself on the CPU's path: both outputs and the gradient
    of every leaf and of the stream. Both paths compute `y + D x` in
    float32 and round it once, so bfloat16 differs by that rounding."""
    lp, x = mixer_inputs(dtype)
    monkeypatch.setattr(ssm, "selective_scan_pallas", functools.partial(
        ssm.selective_scan_pallas, interpret=True))
    probes = jax.random.normal(jax.random.key(1), (2,) + x.shape[:2] + (1,))

    def both(mesh):
        def loss(lp, x):
            out, y = ssm.mamba1_mixer(x, lp, chunk=128, mesh=mesh)
            assert out.dtype == y.dtype == dtype
            f32 = jnp.float32
            return jnp.sum(out.astype(f32) * probes[0]) \
                + jnp.sum(y.astype(f32) * probes[1]), (out, y)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(lp, x)

    (_, (out_k, y_k)), (dlp_k, dx_k) = both(OneTpu())
    (_, (out_x, y_x)), (dlp_x, dx_x) = both(None)
    assert_close(out_k, out_x, "the mixer's output", rtol)
    assert_close(y_k, y_x, "the scan's output", rtol)
    assert_close(dx_k, dx_x, "the stream's gradient", rtol)
    for name in lp:
        assert dlp_k[name].dtype == dlp_x[name].dtype, name
        assert_close(dlp_k[name], dlp_x[name], "d" + name, rtol)
