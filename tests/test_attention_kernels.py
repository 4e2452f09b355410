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


def _pallas_calls(jaxpr):
    """The `pallas_call` equations of a jaxpr, those of its sub-jaxprs
    (scan bodies, remat's recomputation) included."""
    def subjaxprs(value):
        if isinstance(value, (list, tuple)):
            for item in value:
                yield from subjaxprs(item)
        elif hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield value.jaxpr

    return sum(
        (eqn.primitive.name == "pallas_call") + sum(
            _pallas_calls(sub) for value in eqn.params.values()
            for sub in subjaxprs(value))
        for eqn in jaxpr.eqns)


def test_the_layers_remat_keeps_the_forward_kernels_output():
    """Two layers of q/k/v projections, the kernel and `wo` as one scan
    under `Transformer._remat`: the default policy saves what the forward
    kernel names (`FLASH_RESIDUALS`), so the gradient holds the forward
    and the backward kernel and not the forward a second time, and the
    saved output is the recomputed one, bit for bit."""
    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig

    layers, d_model, heads, kv_heads, seq = 2, 64, 2, 1, 256
    keys = jax.random.split(jax.random.key(34), 5)
    shapes = {"wq": (layers, d_model, heads, HEAD_DIM),
              "wk": (layers, d_model, kv_heads, HEAD_DIM),
              "wv": (layers, d_model, kv_heads, HEAD_DIM),
              "wo": (layers, heads, HEAD_DIM, d_model)}
    params = {name: jax.random.normal(k, s, jnp.float32) * s[1] ** -0.5
              for k, (name, s) in zip(keys, shapes.items())}
    x = jax.random.normal(keys[4], (1, seq, d_model), jnp.float32)

    def layer(x, lp):
        q, k, v = (jnp.einsum("btd,dhk->bthk", x, lp[w])
                   for w in ("wq", "wk", "wv"))
        o = _splash_attention(q, k, v, causal=True, scale=HEAD_DIM ** -0.5,
                              interpret=True)
        return x + jnp.einsum("bthk,hkd->btd", o, lp["wo"]), None

    def run(**policy):
        """((loss, gradients), pallas calls in their jaxpr)."""
        wrapped = Transformer._remat(layer, TransformerConfig(
            vocab_size=8, d_model=d_model, n_layers=layers, n_heads=heads,
            d_ff=8, remat=True, **policy))
        fn = jax.value_and_grad(
            lambda p, x: jnp.sum(jax.lax.scan(wrapped, x, p)[0] ** 2),
            argnums=(0, 1))
        return (jax.jit(fn)(params, x),
                _pallas_calls(jax.make_jaxpr(fn)(params, x).jaxpr))

    got, got_calls = run()
    want, want_calls = run(remat_policy="full")
    assert (got_calls, want_calls) == (2, 3)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
