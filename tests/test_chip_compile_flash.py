"""`ops/attention.flash_attention` forward and backward compiled for a
described v5e over a grid of shapes (49 cases of one test, a minute of one
worker): a file of its own since PR 61, so that `--dist loadfile` runs it
beside `tests/test_chip_compile.py`, which keeps the fixture."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_chip_compile import v5e  # noqa: F401


# [B, T, Hq, Hkv, D]: what `flash_shape_ok` says yes to, from one block of
# 128 to eight of 1024 (sixteen of 512 at head_dim 256), head_dim below, at
# and above the lane width, GQA, MHA and MQA; then the calls the benchmark's
# cells make (d2, d8 per chip, OLMoE) and GPT2_125M at batch 16
FLASH_CALLS = [(2, t, h, hkv, d)
               for t in (128, 640, 1024, 4096, 8192)
               for d in (64, 128, 256)
               for h, hkv in ((32, 8), (16, 16), (8, 1))] + [
    (4, 4096, 32, 8, 128), (2, 4096, 32, 8, 128), (4, 4096, 16, 16, 128),
    (16, 1024, 6, 6, 128)]


@pytest.mark.parametrize(
    "b,t,h,hkv,d", FLASH_CALLS,
    ids=["x".join(map(str, call)) for call in FLASH_CALLS])
def test_flash_kernels_fwd_bwd(v5e, b, t, h, hkv, d):
    """Forward and backward of `flash_attention` for the v5e compiler: the
    kernels by kind, by splash's names (one forward call, one call that
    makes dK and dV, and dQ with them); K and V reach both at their own
    head count, so no GQA repeat widened them on the way (the only
    operands at q's width are q and dO); and `flash_shape_ok` said yes to
    what compiled."""
    import re

    from ray_tpu.ops.attention import flash_attention, flash_shape_ok

    assert v5e.device_kind == "TPU v5 lite"
    assert flash_shape_ok(t, d)
    chip = SingleDeviceSharding(v5e)
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = {re.sub(r"\.\d+$", "", name): operands
             for name, operands in re.findall(
                 r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call", '
                 r'operand_layout_constraints=\{(.*?)\}\}', hlo)}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    fwd = calls["splash_mha_fwd_residuals"]
    dkv = calls["splash_mha_dkv_no_residuals"]      # dQ with them: no dq call
    # at q's width: q into the forward, q and dO into the backward, and
    # K and V only where they have q's heads
    wide, narrow = f"bf16[{b},{h},{t},{d}]", f"bf16[{b},{hkv},{t},{d}]"
    kv_wide = 2 if h == hkv else 0
    assert fwd.count(wide) == 1 + kv_wide, fwd
    assert dkv.count(wide) == 2 + kv_wide, dkv
    if h != hkv:
        assert fwd.count(narrow) == 2 and dkv.count(narrow) == 2, (fwd, dkv)
