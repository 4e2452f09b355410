"""The scopes that Kimi Delta Attention, latent attention without a query
latent and the group-limited router add to the vocabulary of
models/transformer.py (PERF.md section 3), the way
tests/test_model_scopes.py holds the others: every name reaches the
lowered module's `op_name`s, and the names are metadata only."""

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
    vocab_size=256, d_model=64, n_layers=5, layer_pattern="kKKLK",
    n_heads=2, d_ff=32, moe_dense_ff=96, max_seq_len=128, remat=True,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, qk_norm=True, kda_heads=2, kda_head_dim=16,
    kda_chunk=32, moe_experts=16, moe_top_k=3, moe_scoring="sigmoid",
    moe_routed_scale=2.5, moe_groups=4, moe_topk_groups=2,
    moe_shared_experts=1, moe_experts_held=4, moe_expert_offset=4,
    moe_aux_coeff=0.0)
KDA = {"kda_norm", "kda/qkv_proj", "kda/conv", "kda/gates", "kda/delta",
       "kda/out_norm", "kda/out_proj"}
LATENT = {"qkv/q_proj", "qkv/kv_down", "qkv/kv_up", "qkv/assemble"}
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
    want = (base.BLOCKS | KDA | LATENT | EXPERTS | DENSE_FIRST
            | extra)
    assert want <= found, sorted(want - found)
    assert not {"qkv/q_down", "qkv/q_up"} & found   # no query latent
    assert "transpose(jvp(layers))" in hlo
    assert "rematted_computation" in hlo
    # the group choice is the router's: its `top_k`s are under moe/router
    names = [n for n in base.op_names(hlo) if "top_k" in n]
    assert names and all("moe/router" in base.TRANSFORMS.sub(r"\1", n)
                         or "moe/dispatch" in n for n in names)


def test_the_new_scopes_change_metadata_only(monkeypatch):
    with_scopes = base.lower_step(CFG).compile().as_text()
    assert "kda/delta" in with_scopes and "qkv/q_proj" in with_scopes

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = base.lower_step(CFG).compile().as_text()
    assert "kda/delta" not in without
    assert base.stripped(with_scopes) == base.stripped(without)
