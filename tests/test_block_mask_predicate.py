"""The form of the block-diffusion mask that the splash kernels evaluate
(`ops/attention._visible_from_bounds` over `_query_bounds`, handed over as
the mask's `q_sequence`) against its definition, `block_visible` over the
positions' own ids: every pair, as numpy and as the kernels' int32 tiles;
and the guard that keeps it cheap, since the library calls it on every
block the kernels run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention

# (positions, block_length, noised): doubled streams of (length, block) =
# (64, 4), (256, 32), (1024, 4), (384, 12), (128, 128), then plain ones
STREAMS = [(128, 4, 64), (512, 32, 256), (2048, 4, 1024), (768, 12, 384),
           (256, 128, 128), (256, 4, 0), (256, 12, 0), (768, 4, 0),
           (768, 12, 0)]


def _definition(t, block, noised):
    ids = np.arange(t)
    return attention.block_visible(ids[:, None], ids[None, :], block, noised)


def _numpy(t, block, noised):
    bounds = attention._query_bounds(t, block, noised)
    assert bounds.dtype == np.int32 and bounds.shape == (t,)
    return attention._visible_from_bounds(
        bounds[:, None], np.arange(t)[None, :], block, noised)


def _tiles(t, block, noised, k_in_lanes):
    """The whole mask from tiles built as `_apply_mask_and_soft_cap`
    builds them: forward, `q_sequence` as `[bq, 128]` tiled along the
    lanes beside an iota of the keys; backward, `q_sequence` as a row
    broadcast over `[bkv, bq]` beside an iota down the sublanes."""
    bq, bkv = 128, 256 if t % 256 == 0 else 128
    bounds = jnp.asarray(attention._query_bounds(t, block, noised))
    fn = jax.jit(lambda q, k: attention._visible_from_bounds(
        q, k, block, noised))
    out = np.zeros((t, t), bool)
    for qs in range(0, t, bq):
        ref = bounds[qs:qs + bq]
        for ks in range(0, t, bkv):
            if k_in_lanes:
                q = jnp.tile(jnp.broadcast_to(ref[:, None], (bq, 128)),
                             (1, bkv // 128))
                k = ks + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
                got = fn(q, k)
            else:
                q = jnp.broadcast_to(ref[None, :], (bkv, bq))
                k = ks + jax.lax.broadcasted_iota(jnp.int32, (bkv, bq), 0)
                got = fn(q, k).T
            assert got.dtype == jnp.bool_
            out[qs:qs + bq, ks:ks + bkv] = np.asarray(got)
    return out


def _getitem(t, block, noised):
    """The library's own reading, `mask[q slice, kv slice]`: what its
    block tables and `block_table` classify blocks with."""
    mask = attention._block_diffusion_mask(t, block, noised)
    out = np.zeros((t, t), bool)
    for qs in range(0, t, 128):
        for ks in range(0, t, 128):
            out[qs:qs + 128, ks:ks + 128] = mask[slice(qs, qs + 128),
                                                 slice(ks, ks + 128)]
    whole = mask[slice(None), slice(None)]
    assert whole.dtype == bool
    np.testing.assert_array_equal(whole, out)
    return out


FORMS = {"numpy": _numpy,
         "jit_forward_tiles": lambda *a: _tiles(*a, k_in_lanes=True),
         "jit_backward_tiles": lambda *a: _tiles(*a, k_in_lanes=False),
         "getitem": _getitem}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("t,block,noised", STREAMS)
def test_the_kernels_predicate_is_block_visible(t, block, noised, form):
    np.testing.assert_array_equal(FORMS[form](t, block, noised),
                                  _definition(t, block, noised))


@pytest.mark.parametrize("block,noised", [(4, 8192), (12, 8192), (4, 0)])
def test_the_kernel_is_handed_no_division(block, noised):
    """The predicate runs on every element of every block the kernels
    run: at most 16 elementwise equations on a tile, none of them a
    division, a remainder or a multiplication, none a call that could
    hide one (`floor_divide` is a nested jaxpr)."""
    mask = attention._block_diffusion_mask(2 * noised or 16384, block,
                                           noised)
    tile = jnp.zeros((8, 128), jnp.int32)
    eqns = jax.make_jaxpr(mask.mask_function)(tile, tile).eqns
    names = [str(e.primitive) for e in eqns]
    assert len(names) <= 16, names
    assert not {"div", "rem", "mul", "integer_pow", "dot_general"} \
        & set(names), names
    assert not [e for e in eqns if any(
        hasattr(v, "jaxpr") or hasattr(v, "eqns")
        for v in e.params.values())], names
    # and the definition is what it was: two floor divisions among 26
    defined = [str(e.primitive) for e in jax.make_jaxpr(
        lambda q, k: attention.block_visible(q, k, block, noised))(
            tile, tile).eqns]
    assert len(defined) > len(names) and "mul" in defined


def test_the_library_hands_the_kernel_the_bounds():
    """splash's `process_mask` takes `q_sequence` and `mask_function` off
    the mask object for the kernel and classifies blocks through
    `__getitem__`: one encoding, one function, on both of its sides."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        MultiHeadMask, splash_attention_mask_info as mask_info_lib)

    mask = attention._block_diffusion_mask(512, 4, 256)
    info, fn = mask_info_lib.process_mask(MultiHeadMask([mask] * 2),
                                          (128, 128))
    assert fn is mask.mask_function
    np.testing.assert_array_equal(info.q_sequence,
                                  attention._query_bounds(512, 4, 256))
    # a noised query carries its block's start, a clean one the end of
    # its clean run under the sign bit
    assert info.q_sequence[:8].tolist() == [0, 0, 0, 0, 4, 4, 4, 4]
    assert (info.q_sequence[256:264] & 0x7FFFFFFF).tolist() \
        == [4] * 4 + [8] * 4
    assert (info.q_sequence[256:] < 0).all()
    assert info.partial_mask_blocks is None   # computed, not loaded
    assert mask == attention._block_diffusion_mask(512, 4, 256)
    assert mask != attention._block_diffusion_mask(512, 8, 256)
    assert len({mask, attention._block_diffusion_mask(512, 4, 256)}) == 1


def test_the_cells_block_table_after_the_change():
    assert attention.block_table(16384, 128, 4, 8192) == {
        "blocks": 256, "non_empty": 80, "partial": 24,
        "block_pairs": 1024 * 1024, "pairs_needed": 8192 * 8192 + 8192 * 4}
