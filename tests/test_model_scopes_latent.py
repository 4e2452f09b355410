"""The scopes that latent attention, the shared expert and a held share
add to the vocabulary of models/transformer.py (PERF.md section 3), the
way tests/test_model_scopes.py holds the others: every name reaches the
lowered module's `op_name`s, under `qkv` and `moe`, and the names are
metadata only."""

import contextlib
import importlib.util
import os

import jax
import pytest

from ray_tpu.models import TransformerConfig

_spec = importlib.util.spec_from_file_location(
    "_test_model_scopes", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "test_model_scopes.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=32,
    max_seq_len=128, remat=True, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20, moe_experts=8,
    moe_top_k=2, moe_scoring="sigmoid", moe_routed_scale=1.8,
    moe_shared_experts=1, moe_dense_layers=1, moe_dense_ff=96,
    moe_experts_held=4, moe_expert_offset=4, moe_aux_coeff=0.0)
LATENT = {"qkv/q_down", "qkv/kv_down", "qkv/q_up", "qkv/kv_up",
          "qkv/assemble"}
EXPERTS = {"moe/router", "moe/dispatch", "moe/experts", "moe/combine",
           "moe/shared"}
DENSE_FIRST = {"mlp/gate_up", "mlp/down"}


@pytest.mark.parametrize("chunk", base.CHUNKS)
@pytest.mark.parametrize("program", base.LOWER)
def test_the_new_scopes_reach_the_lowered_op_names(program, chunk):
    lower, extra = base.LOWER[program]
    hlo = lower(CFG.replace(loss_chunk=base.CHUNKS[chunk])).as_text(
        debug_info=True)
    found = base.scopes_in(hlo)
    want = base.BLOCKS | LATENT | EXPERTS | DENSE_FIRST | extra
    assert want <= found, sorted(want - found)
    assert "transpose(jvp(layers))" in hlo
    assert "rematted_computation" in hlo
    # the sub-scopes are inside `qkv`: attn_proj_share reads the whole
    names = [n for n in base.op_names(hlo) if "q_down" in n]
    assert names and all("qkv/q_down" in base.TRANSFORMS.sub(r"\1", n)
                         or "qkv" in n for n in names)


def test_the_new_scopes_change_metadata_only(monkeypatch):
    with_scopes = base.lower_step(CFG).compile().as_text()
    assert "/qkv/assemble/" in with_scopes and "moe/shared" in with_scopes

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = base.lower_step(CFG).compile().as_text()
    assert "assemble" not in without
    assert base.stripped(with_scopes) == base.stripped(without)
