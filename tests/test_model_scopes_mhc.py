"""The scopes that a residual path of several streams adds to the
vocabulary of models/transformer.py (PERF.md section 3: `mhc/maps`,
`mhc/pre`, `mhc/post`, `mhc/expand`, `mhc/collapse`; ops/mhc.py), the way
tests/test_model_scopes.py holds the others: every name reaches the lowered
module's `op_name`s, the write lies inside the scope that closes its
sublayer, forward, backward and recomputation are read off JAX's own
wrappers, and one stream has none of the new names."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig

_spec = importlib.util.spec_from_file_location(
    "_test_model_scopes_mhc_base", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "test_model_scopes.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=32,
    max_seq_len=64, remat=True, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20, moe_experts=8,
    moe_top_k=2, moe_scoring="sigmoid", moe_aux_coeff=0.0,
    moe_shared_experts=1, moe_dense_layers=1, moe_dense_ff=96,
    moe_experts_held=4, residual_streams=4, rope_yarn_factor=64.0,
    rope_yarn_original_len=16, rope_yarn_attention_factor=1.0,
    rope_yarn_mscale_all_dim=1.0)
NEW = {"mhc/maps", "mhc/pre", "mhc/post", "mhc/expand", "mhc/collapse"}
FFN = {"mlp/gate_up", "mlp/down", "moe/router", "moe/experts",
       "moe/combine", "moe/shared"}
SCOPE = re.compile(r"(?:^|/)(mhc/\w+)(?=/|$)")


def mhc_scopes(hlo):
    found = set()
    for n in base.op_names(hlo):
        while base.TRANSFORMS.search(n):
            n = base.TRANSFORMS.sub(r"\1", n)
        found.update(SCOPE.findall(n))
    return found


@pytest.mark.parametrize("chunk", base.CHUNKS)
@pytest.mark.parametrize("program", base.LOWER)
def test_the_new_scopes_reach_the_lowered_op_names(program, chunk):
    lower, extra = base.LOWER[program]
    cfg = CFG.replace(loss_chunk=base.CHUNKS[chunk])
    hlo = lower(cfg).as_text(debug_info=True)
    assert NEW <= mhc_scopes(hlo), sorted(NEW - mhc_scopes(hlo))
    found = base.scopes_in(hlo)
    want = (base.BLOCKS | extra | {"mlp/gate_up", "mlp/down"}) - {"moe"}
    assert want <= found, sorted(want - found)
    assert "rematted_computation" in hlo
    if program != "train_step":   # one compile a chunking is enough
        return
    compiled = lower(cfg).compile()
    cleaned = []
    for n in re.findall(r'op_name="([^"]+)"', compiled.as_text()):
        while base.TRANSFORMS.search(n):
            n = base.TRANSFORMS.sub(r"\1", n)
        cleaned.append(n)
    body = "layers/while/body/closed_call/"
    # the maps and the read open a sublayer, before its norm's scope; the
    # write lies inside the scope that closes it
    for inside in ("attn_out/mhc/post", "mlp/down/mhc/post",
                   "moe/combine/mhc/post"):
        assert any(body in n and inside in n for n in cleaned), inside
    for scope in ("mhc/maps", "mhc/pre"):
        assert any(body + scope in n or body + "checkpoint/" + scope in n
                   for n in cleaned), scope
        assert any(body + "checkpoint/rematted_computation/" + scope in n
                   for n in cleaned), scope
    # the product with phi and the rounds' divisions are the maps'
    assert any("mhc/maps" in n and "dot_general" in n for n in cleaned)
    assert any("mhc/maps" in n and "div" in n for n in cleaned)
    assert not any("mhc/pre" in n and "dot_general" in n for n in cleaned)
    # entry and exit lie outside the layers' scans
    assert not any("layers" in n and ("mhc/expand" in n
                                      or "mhc/collapse" in n)
                   for n in cleaned)


def test_one_stream_has_none_of_them():
    one = CFG.replace(residual_streams=1)
    params = jax.eval_shape(lambda: base.Transformer.init(
        jax.random.key(0), one))
    hlo = jax.jit(jax.grad(
        lambda p, b: base.Transformer.loss(p, b, one))).lower(
            params, {"tokens": jnp.zeros((2, 65), jnp.int32)}).as_text(
                debug_info=True)
    assert not mhc_scopes(hlo)
    assert {"layers", "final_norm", "attn_norm", "mlp_norm"} <= \
        base.scopes_in(hlo)
