"""`ops/attention.flash_attention`'s kernels (JAX's pallas splash attention)
held to `dense_attention` on the CPU, in pallas interpret mode: the block
geometry the function derives, the causal mask the kernel knows, K and V at
their own head count in `gqa_scores`' head order, the scale folded into q,
and the one backward kernel. What the v5e compiler makes of the same calls
is `tests/test_chip_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import (_splash_attention, _splash_block_sizes,
                                   dense_attention, flash_attention,
                                   flash_shape_ok)

# 640 tokens tile into five blocks of 128, q and kv: blocks above, on and
# below the diagonal, and a backward that sums dQ over five kv blocks
SEQ, HEAD_DIM = 640, 128


def _rel_l2(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _qkv_do(heads, kv_heads, dtype, batch=2):
    keys = jax.random.split(jax.random.key(heads * 31 + kv_heads), 4)
    shapes = [(batch, SEQ, heads, HEAD_DIM), (batch, SEQ, kv_heads, HEAD_DIM),
              (batch, SEQ, kv_heads, HEAD_DIM), (batch, SEQ, heads, HEAD_DIM)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


def test_geometry_follows_from_the_shape():
    sizes = _splash_block_sizes(SEQ, HEAD_DIM)
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == \
        (128, 128, 128)
    sizes = _splash_block_sizes(4096, 128)   # the benchmark's cells
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == \
        (1024, 1024, 512)
    assert (sizes.block_q_dkv, sizes.block_kv_dkv,
            sizes.block_kv_dkv_compute) == (1024, 1024, 1024)
    assert sizes.use_fused_bwd_kernel and sizes.block_q_dq is None
    assert _splash_block_sizes(1536, 128).block_q == 512
    assert _splash_block_sizes(4096, 256).block_q == 512
    assert _splash_block_sizes(4096, 64).block_q == 1024


def _grads_and_out(attend, q, k, v, do):
    """(dq, dk, dv, out) of `attend` under the cotangent `do`."""
    def run(q, k, v, do):
        out, vjp = jax.vjp(attend, q, k, v)
        return vjp(do) + (out,)
    return jax.jit(run)(q, k, v, do)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4), (4, 1)],
                         ids=["gqa8_2", "mha4_4", "mqa4_1"])
def test_output_and_gradients_match_dense_in_f32(heads, kv_heads, causal):
    args = _qkv_do(heads, kv_heads, jnp.float32)
    scale = 0.11     # not head_dim ** -0.5: the scale is the caller's
    got = _grads_and_out(lambda q, k, v: _splash_attention(
        q, k, v, causal=causal, scale=scale, interpret=True), *args)
    want = _grads_and_out(lambda q, k, v: dense_attention(
        q, k, v, causal=causal, scale=scale), *args)
    for name, a, b in zip(("dq", "dk", "dv", "out"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel_l2(a, b) < 1e-5, (name, _rel_l2(a, b))


def test_bf16_operands_stay_within_bf16_of_dense_f32():
    args = _qkv_do(8, 2, jnp.bfloat16, batch=1)
    scale = HEAD_DIM ** -0.5
    got = _grads_and_out(lambda q, k, v: _splash_attention(
        q, k, v, causal=True, scale=scale, interpret=True), *args)
    want = _grads_and_out(
        lambda q, k, v: dense_attention(q, k, v, causal=True, scale=scale),
        *(x.astype(jnp.float32) for x in args))
    for name, a, b in zip(("dq", "dk", "dv", "out"), got, want):
        assert a.dtype == jnp.bfloat16, name
        assert _rel_l2(a, b) < 0.02, (name, _rel_l2(a, b))


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1)])
def test_a_query_head_reads_its_own_kv_head(heads, kv_heads):
    """V of kv head g is the constant g + 1, so whatever the scores are a
    query head's output is the number of the kv head it read: h // rep."""
    q, k, _, _ = _qkv_do(heads, kv_heads, jnp.float32, batch=1)
    v = jnp.broadcast_to(
        jnp.arange(1.0, kv_heads + 1)[None, None, :, None], k.shape)
    out = _splash_attention(q, k, v, causal=True, scale=1.0, interpret=True)
    rep = heads // kv_heads
    want = jnp.broadcast_to(
        (jnp.arange(heads) // rep + 1.0)[None, None, :, None], out.shape)
    assert jnp.allclose(out, want, atol=1e-5)


@pytest.mark.parametrize("seq,head_dim", [(100, 128), (64, 128), (192, 128),
                                          (4000, 128), (128, 16), (256, 96)])
def test_an_untileable_shape_is_a_value_error(seq, head_dim):
    assert not flash_shape_ok(seq, head_dim)
    x = jnp.zeros((1, seq, 4, head_dim), jnp.bfloat16)
    with pytest.raises(ValueError, match="flash attention needs"):
        flash_attention(x, x, x)


def test_kv_heads_must_divide_query_heads():
    q = jnp.zeros((1, 128, 6, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 128, 4, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, kv, kv)
