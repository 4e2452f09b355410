"""The scopes that a Mamba-2 mixer and a latent expert layer add to the
vocabulary of models/transformer.py (PERF.md section 3), the way
tests/test_model_scopes.py holds the others: every name reaches the
lowered module's `op_name`s, under `ssm` and `moe`, and the names are
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
    vocab_size=256, d_model=64, n_layers=7, layer_pattern="MEMEM*E",
    n_heads=2, n_kv_heads=1, attn_head_dim=16, rope=False, d_ff=24,
    max_seq_len=128, remat=True, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
    ssm_state=16, ssm_chunk=32, moe_experts=16, moe_top_k=6,
    moe_scoring="sigmoid", moe_routed_scale=5.0, moe_shared_experts=1,
    moe_shared_ff=48, moe_latent=32, moe_act="relu2", moe_gated=False,
    moe_experts_held=4, moe_expert_offset=4, moe_aux_coeff=0.0)
MIXER = {"ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
         "ssm/out_proj"}
EXPERTS = {"moe/router", "moe/dispatch", "moe/experts", "moe/combine",
           "moe/shared", "moe/latent"}
# a hybrid has no dense MLP: the blocks without `mlp/*`
BLOCKS = {b for b in base.BLOCKS if not b.startswith("mlp/")}


@pytest.mark.parametrize("chunk", base.CHUNKS)
@pytest.mark.parametrize("program", base.LOWER)
def test_the_new_scopes_reach_the_lowered_op_names(program, chunk):
    lower, extra = base.LOWER[program]
    hlo = lower(CFG.replace(loss_chunk=base.CHUNKS[chunk])).as_text(
        debug_info=True)
    found = base.scopes_in(hlo)
    want = BLOCKS | MIXER | EXPERTS | extra
    assert want <= found, sorted(want - found)
    assert "ssm_norm" in hlo
    assert "transpose(jvp(layers))" in hlo
    assert "rematted_computation" in hlo
    # the chunk states' scan is inside `ssm/scan`
    assert any("ssm/scan" in n and "while" in n for n in base.op_names(hlo))


def test_the_new_scopes_change_metadata_only(monkeypatch):
    with_scopes = base.lower_step(CFG).compile().as_text()
    assert "ssm/scan" in with_scopes and "moe/latent" in with_scopes

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = base.lower_step(CFG).compile().as_text()
    assert "ssm/scan" not in without and "moe/latent" not in without
    assert base.stripped(with_scopes) == base.stripped(without)
