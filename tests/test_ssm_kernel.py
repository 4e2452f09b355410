"""The selective scan's pallas kernels (`ops/ssm.ssd_scan_pallas`) in
interpret mode on the CPU: forward and all five gradients against
`ssd_scan`, the XLA path, and against the step-by-step recurrence of
`benchmark/reference/nemotron_h_f32.py`; and the trace-time choice
between the two (`ssd_scan_impl`). What the chip's compiler makes of the
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

ref = load_module("reference", "nemotron_h_f32")

GRADS = ("x", "dt", "a", "b", "c")
# [chunk, heads a group, groups, chunks, head width, head block]: every
# chunk, group size and group count of the issue's list, a state carried
# over one chunk's end and over two, a head that is a whole lane tile, and
# a group in two head blocks (whose parts of dB and dC are summed outside
# the kernel), each at the smallest size that has it: interpreted, a kernel
# costs by the step. The head block is given where `scan_head_block` has
# none for the chip (4 heads a group). The cell's own size is compiled for
# the v5e by `tests/test_chip_compile.py::test_scan_kernels_fwd_bwd`
SHAPES = [(64, 4, 1, 2, 64, 4), (64, 16, 2, 2, 64, None),
          (64, 4, 2, 3, 64, 4), (128, 4, 2, 2, 64, 4),
          (128, 16, 1, 2, 64, None), (128, 4, 1, 3, 64, 4),
          (128, 4, 1, 2, 128, 4), (128, 4, 2, 2, 64, 2)]
IDS = ["q{}-r{}-g{}-c{}-p{}-hb{}".format(*shape) for shape in SHAPES]


def scan_inputs(seed, chunk, per_group, groups, chunks, p, dtype=jnp.float32,
                batch=1):
    t, h, n = chunk * chunks, per_group * groups, 128
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (batch, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    b = (0.3 * jax.random.normal(ks[3], (batch, t, groups, n))).astype(dtype)
    c = (0.3 * jax.random.normal(ks[4], (batch, t, groups, n))).astype(dtype)
    probe = jax.random.normal(ks[5], x.shape)
    return (x, dt, a, b, c), probe


def recurrence(x, dt, a, b, c):
    """The reference's step-by-step scan in float32, without the D skip."""
    f32 = jnp.float32
    rep = x.shape[2] // b.shape[2]
    with jax.default_matmul_precision("highest"):
        return ref.selective_scan(
            x.astype(f32), dt, a, jnp.repeat(b.astype(f32), rep, axis=2),
            jnp.repeat(c.astype(f32), rep, axis=2), jnp.zeros(x.shape[2]))


@functools.lru_cache(maxsize=None)
def kernel(chunk, head_block=None):
    """`ssd_scan_pallas` in interpret mode behind `ssd_scan`'s shapes: it
    takes and gives the convolution's layouts, `[B, T, H·P]` and
    `[B, T, G·N]`."""
    def flat(v):
        return v.reshape(v.shape[:2] + (-1,))

    return jax.jit(lambda x, dt, a, b, c: ssm.ssd_scan_pallas(
        flat(x), dt, a, flat(b), flat(c), chunk, b.shape[2],
        head_block=head_block, interpret=True).reshape(x.shape))


@functools.lru_cache(maxsize=None)
def xla_scan(chunk):
    return jax.jit(lambda *v: ssm.ssd_scan(*v, chunk))


@functools.lru_cache(maxsize=None)
def case(shape, seed, dtype=jnp.float32, batch=1):
    """The inputs at (shape, seed, dtype, batch) and what the kernel, the
    XLA path and the float32 recurrence give at them, outputs and
    gradients: computed once, read by the forward, the backward, the
    batch's and the bfloat16 tests."""
    chunk, per_group, groups, chunks, p, head_block = shape
    args, probe = scan_inputs(seed, chunk, per_group, groups, chunks, p,
                              dtype, batch)
    y, grads = value_and_grads(kernel(chunk, head_block))(probe, *args)
    xla_y, xla_grads = value_and_grads(xla_scan(chunk))(probe, *args)
    want_y, want_grads = value_and_grads(recurrence)(probe, *args)
    return types.SimpleNamespace(
        args=args, probe=probe, y=y, grads=grads, xla_y=xla_y,
        xla_grads=xla_grads, want_y=want_y, want_grads=want_grads)


def assert_forward_close(at):
    assert at.y.dtype == jnp.float32 and at.y.shape == at.args[0].shape
    assert_close(at.y, at.xla_y, "y against ssd_scan", 1e-5)
    assert_close(at.y, at.want_y, "y against the recurrence", 1e-4)


def assert_grads_close(at):
    """The kernel's five gradients against `ssd_scan`'s and the float32
    recurrence's, shapes and dtypes `ssd_scan`'s."""
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, o, f"d{name} against ssd_scan", 1e-4)
        assert_close(g, w, f"d{name} against the recurrence", 2e-4)


def assert_close(got, want, what, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_forward_is_the_xla_scan_and_the_recurrence(shape, seed):
    assert_forward_close(case(shape, seed))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_backward_is_the_xla_scans_and_the_recurrences(shape, seed):
    assert_grads_close(case(shape, seed))


@pytest.mark.parametrize("shape,batch", [(SHAPES[0], 3), (SHAPES[7], 2)],
                         ids=[IDS[0] + "-b3", IDS[7] + "-b2"])
def test_kernel_with_a_batch_above_one(shape, batch):
    """Every row of a batch starts from a zero state and keeps its own:
    the state's scratch is set to zero at each row's first chunk, and a
    (the one operand without a batch axis) gathers its gradient over the
    rows."""
    at = case(shape, batch, batch=batch)
    assert_forward_close(at)
    # a row alone gives what it gives in the batch
    alone = kernel(shape[0], shape[5])(
        *(v[-1:] if v.ndim > 1 else v for v in at.args))
    assert_close(alone, at.y[-1:], "the last row alone", 1e-6)
    assert_grads_close(at)


# bfloat16 operands, float32 decays and accumulation: the two paths round
# the same products, so each is held to the float32 recurrence at one
# tolerance, and the kernel is no further from it than the XLA path is
# (whose own tests run in float32: 1e-4 forward, 2e-4 backward)
BF16_RTOL = {"y": 1e-2, "x": 2e-2, "dt": 2e-2, "a": 2e-2, "b": 2e-2,
             "c": 2e-2}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", [SHAPES[1], SHAPES[3], SHAPES[7]],
                         ids=[IDS[1], IDS[3], IDS[7]])
def test_bf16_operands_are_held_to_the_f32_recurrence(shape, seed):
    at = case(shape, seed, jnp.bfloat16)
    assert at.y.dtype == jnp.float32
    assert_close(at.y, at.want_y, "y", BF16_RTOL["y"])
    assert_close(at.xla_y, at.want_y, "ssd_scan's y", BF16_RTOL["y"])
    for name, g, o, w in zip(GRADS, at.grads, at.xla_grads, at.want_grads):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert_close(g, w, "d" + name, BF16_RTOL[name])
        assert_close(o, w, "ssd_scan's d" + name, BF16_RTOL[name])


def test_a_long_decay_does_not_overflow_above_the_diagonal():
    """dt x |A| of 40 a step, through the kernel: exp(+sum) above the
    diagonal would be inf, and inf x 0 a NaN in the value or a gradient
    (`tests/test_nemotron_h_reference.py` holds the XLA path to it)."""
    (x, dt, a, b, c), probe = scan_inputs(3, 64, 4, 2, 2, 64)
    dt, a = dt * 0 + 5.0, a * 0 - 8.0
    y, grads = value_and_grads(kernel(64, 4))(probe, x, dt, a, b, c)
    assert np.isfinite(np.asarray(y)).all()
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    want_y, want = value_and_grads(recurrence)(probe, x, dt, a, b, c)
    assert_close(y, want_y, "y", 1e-4)
    assert_close(grads[0], want[0], "dx", 2e-4)


def test_a_length_that_is_no_whole_chunks_is_refused_not_padded():
    args, _ = scan_inputs(0, 64, 4, 1, 2, 64)
    with pytest.raises(ValueError, match="whole chunks"):
        kernel(48, 4)(*args)


class OneTpu:
    """What `ssd_scan_impl` reads of a mesh: its size and a device."""

    size = 1

    class devices:
        flat = [type("D", (), {"platform": "tpu"})()]


class FourTpus(OneTpu):
    size = 4


# [T, heads, head width, groups, state, chunk]
CELL = (8192, 32, 64, 2, 128, 128)


@pytest.mark.parametrize("shape,want", [
    (CELL, "pallas"),
    ((128, 8, 64, 1, 128, 128), "pallas"),      # the smallest: one chunk
    ((512, 8, 128, 1, 128, 256), "pallas"),     # a head a tile, chunk 256
    ((8192, 128, 64, 8, 128, 128), "pallas"),   # the uncut model's mixer
    ((8192 + 64, 32, 64, 2, 128, 128), "xla"),  # no whole chunks: refused
    ((8192, 32, 64, 2, 128, 64), "xla"),        # a chunk under a lane tile
    ((8192, 32, 64, 2, 64, 128), "xla"),        # a state under a lane tile
    ((8192, 32, 32, 2, 128, 128), "xla"),       # a head width not tiled
    ((8192, 8, 64, 2, 128, 128), "xla"),        # 4 heads a group: under 8
    ((8192, 32, 64, 3, 128, 128), "xla"),       # groups that do not divide
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v)
def test_scan_impl_by_shape(shape, want):
    assert ssm.ssd_scan_impl(OneTpu(), *shape) == want
    assert ssm.scan_shape_ok(*shape) == (want == "pallas")
    assert ssm.ssd_scan_impl(None, *shape) == "xla"          # the CPU
    assert ssm.ssd_scan_impl(FourTpus(), *shape) == "xla"    # GSPMD's


def test_scan_impl_on_a_mesh_of_cpu_devices_is_xla():
    from ray_tpu.parallel import MeshConfig, make_mesh

    one = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    many = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:2])
    assert ssm.ssd_scan_impl(one, *CELL) == "xla"
    assert ssm.ssd_scan_impl(many, *CELL) == "xla"


@pytest.mark.parametrize("per_group,p,want", [
    (16, 64, 16), (32, 64, 16), (80, 64, 16), (8, 64, 8), (24, 64, 8),
    (4, 64, None), (12, 64, None), (8, 128, 8), (16, 128, 8),
    (4, 128, None)])
def test_scan_head_block(per_group, p, want):
    assert ssm.scan_head_block(per_group, p) == want


def test_the_mixer_takes_the_xla_scan_on_the_cpu():
    """`mamba2_mixer` asks `ssd_scan_impl` and, here, traces no pallas
    call: the CPU's path is the one it was."""
    h, p, g, n, d = 4, 64, 2, 128, 32
    ks = jax.random.split(jax.random.key(0), 4)
    conv_dim = h * p + 2 * g * n
    lp = {"w_in": 0.1 * jax.random.normal(ks[0], (d, 2 * h * p + 2 * g * n
                                                  + h)),
          "w_out": 0.1 * jax.random.normal(ks[1], (h * p, d)),
          "conv_w": 0.3 * jax.random.normal(ks[2], (conv_dim, 4)),
          "conv_b": jnp.zeros(conv_dim), "dt_bias": jnp.zeros(h),
          "A_log": jnp.zeros(h), "D": jnp.ones(h),
          "gate_norm": jnp.ones(h * p)}
    x = jax.random.normal(ks[3], (1, 256, d))
    jaxpr = jax.make_jaxpr(lambda x: ssm.mamba2_mixer(
        x, lp, head_dim=p, state=n, chunk=128, eps=1e-5))(x)
    assert "pallas_call" not in str(jaxpr)
    assert "cumsum" in str(jaxpr)


def reshaped_gated_norm(y, z, gain, groups, eps):
    """`gated_norm` as it was before the kernel: the groups as a
    `[..., groups, C / groups]` reshape."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    grouped = v.reshape(v.shape[:-1] + (groups, -1))
    scale = jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped * scale).reshape(v.shape) * gain.astype(f32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("groups,width", [(1, 256), (2, 512), (8, 128)])
def test_gated_norm_by_slices_is_the_reshaped_one(groups, width, dtype):
    """A group taken as a slice of the last axis sums the same squares in
    the same order as a row of the reshape: the value is equal to the bit,
    the gradients to float32's rounding (a concatenation's transpose is
    not a reshape's)."""
    ks = jax.random.split(jax.random.key(groups), 4)
    shape = (2, 24, groups * width)
    y = jax.random.normal(ks[0], shape)              # float32, as the scan's
    z = jax.random.normal(ks[1], shape).astype(dtype)
    gain = 1.0 + 0.3 * jax.random.normal(ks[2], shape[-1:])
    probe = jax.random.normal(ks[3], shape)
    got = ssm.gated_norm(y, z, gain, groups, 1e-5)
    want = reshaped_gated_norm(y, z, gain, groups, 1e-5)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def grads(fn):
        return jax.grad(lambda *v: jnp.sum(fn(*v, groups, 1e-5) * probe),
                        argnums=(0, 1, 2))(y, z, gain)

    for name, g, w in zip(("y", "z", "gain"), grads(ssm.gated_norm),
                          grads(reshaped_gated_norm)):
        assert g.dtype == w.dtype, name
        assert_close(g, w, "d" + name, 1e-6)
