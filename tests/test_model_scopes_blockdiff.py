"""The scopes that a block-diffusion model adds to the vocabulary of
models/transformer.py (PERF.md section 3: `diffusion/noise`,
`diffusion/stream`, `attention/block_diffusion`, `qkv/qk_norm`), the way
tests/test_model_scopes.py holds the others: every name reaches the
lowered module's `op_name`s, and the names are metadata only."""

import contextlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig

_spec = importlib.util.spec_from_file_location(
    "_test_model_scopes_blockdiff_base", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "test_model_scopes.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    attn_head_dim=16, d_ff=32, max_seq_len=128, remat=True, qk_norm=True,
    qk_norm_per_head=True, moe_experts=16, moe_top_k=4,
    moe_experts_held=4, moe_expert_offset=4, moe_aux_coeff=0.0,
    block_length=4)
NEW = {"diffusion/noise", "diffusion/stream", "attention/block_diffusion",
       "qkv/qk_norm"}
EXPERTS = {"moe/router", "moe/dispatch", "moe/experts", "moe/combine"}


def batch_for(cfg, rows):
    """What the host hands over: tokens and a key a sequence."""
    return {"tokens": jnp.zeros((rows, cfg.max_seq_len // 2), jnp.int32),
            "noise_key": jnp.zeros((rows, 2), jnp.uint32)}


base.batch_for = batch_for      # `lower_grad` and `lower_step` call it


@pytest.mark.parametrize("chunk", base.CHUNKS)
@pytest.mark.parametrize("program", base.LOWER)
def test_the_new_scopes_reach_the_lowered_op_names(program, chunk):
    lower, extra = base.LOWER[program]
    hlo = lower(CFG.replace(loss_chunk=base.CHUNKS[chunk])).as_text(
        debug_info=True)
    found = base.scopes_in(hlo)
    want = base.BLOCKS | NEW | EXPERTS | extra
    assert want <= found, sorted(want - found)
    assert "transpose(jvp(layers))" in hlo
    assert "rematted_computation" in hlo
    # the draws are the noise's, the concatenation the stream's
    names = base.op_names(hlo)
    assert any("diffusion/noise" in n and "threefry" in n for n in names)
    assert any("diffusion/stream" in n and "concatenate" in n
               for n in names)


def test_a_causal_model_has_none_of_them():
    causal = CFG.replace(block_length=0, max_seq_len=64)
    params = jax.eval_shape(lambda: base.Transformer.init(
        jax.random.key(0), causal))
    hlo = jax.jit(jax.grad(
        lambda p, b: base.Transformer.loss(p, b, causal))).lower(
            params, {"tokens": jnp.zeros((2, 65), jnp.int32)}).as_text(
                debug_info=True)
    found = base.scopes_in(hlo)
    assert not {"diffusion/noise", "diffusion/stream",
                "attention/block_diffusion"} & found
    assert {"qkv/qk_norm", "attention"} <= found


def renamed(text):
    """Instructions by their order of appearance: XLA:CPU names one after
    its op_name (`%jvp_vmap_jit__uniform___` under a scope JAX wraps in
    `jvp(...)`, `%vmap_jit__uniform__` without one), and the name is all
    that differs."""
    order = {}
    return re.sub(r"%[\w.\-]+", lambda m: order.setdefault(
        m.group(0), f"%{len(order)}"), text)


def test_the_new_scopes_change_metadata_only(monkeypatch):
    with_scopes = base.lower_step(CFG).compile().as_text()
    assert "diffusion/noise" in with_scopes
    assert "attention/block_diffusion" in with_scopes

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = base.lower_step(CFG).compile().as_text()
    assert "diffusion/noise" not in without
    assert renamed(base.stripped(with_scopes)) \
        == renamed(base.stripped(without))
