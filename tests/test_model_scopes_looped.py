"""The scopes that a looped stack adds to the vocabulary of
models/transformer.py (PERF.md section 3: `loops`, `loop/exit_gate`,
`loop/exit_loss`, and under the sandwich norm `attn_post_norm` and
`mlp_post_norm` beside `attn_norm` and `mlp_norm`), the way
tests/test_model_scopes.py holds the others: every name reaches the
lowered module's `op_name`s, the nested scans keep their own, and a
stack that runs once has none of the new names."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig

_spec = importlib.util.spec_from_file_location(
    "_test_model_scopes_looped_base", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "test_model_scopes.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=96,
    max_seq_len=64, remat=True, loops=3, exit_gate=True,
    norm_placement="both")
NEW = {"loops", "loop/exit_gate", "loop/exit_loss", "attn_post_norm",
       "mlp_post_norm"}
DENSE = {"mlp/gate_up", "mlp/down"}


@pytest.mark.parametrize("chunk", base.CHUNKS)
@pytest.mark.parametrize("program", base.LOWER)
def test_the_new_scopes_reach_the_lowered_op_names(program, chunk):
    lower, extra = base.LOWER[program]
    hlo = lower(CFG.replace(loss_chunk=base.CHUNKS[chunk])).as_text(
        debug_info=True)
    found = base.scopes_in(hlo)
    want = base.BLOCKS | NEW | DENSE | extra
    assert want <= found, sorted(want - found)
    assert "rematted_computation" in hlo
    # in the compiled program an op's name is its whole path: the layers'
    # scan lies inside the passes', the final norm closes a pass inside
    # the loop, the norm on a sublayer's output lies in the scope that
    # closes the sublayer
    compiled = lower(CFG.replace(loss_chunk=base.CHUNKS[chunk])).compile()
    cleaned = []
    for n in re.findall(r'op_name="([^"]+)"', compiled.as_text()):
        while base.TRANSFORMS.search(n):
            n = base.TRANSFORMS.sub(r"\1", n)
        cleaned.append(n)
    inner = "loops/while/body/closed_call/layers/while/body/closed_call/"
    assert any(inner in n and "mlp/down/mlp_post_norm" in n
               for n in cleaned)
    assert any(inner in n and "attn_out/attn_post_norm" in n
               for n in cleaned)
    assert any(inner + "checkpoint/rematted_computation/" in n
               for n in cleaned)
    assert any("loops/while/body/closed_call/final_norm" in n
               for n in cleaned)
    # the gate's product and the exit loss's log-sigmoids are theirs
    assert any("loop/exit_gate" in n and "reduce_sum" in n for n in cleaned)
    assert any("loop/exit_loss" in n and "log" in n for n in cleaned)
    assert not any("loops" in n and "exit_" in n for n in cleaned)


def test_a_stack_that_runs_once_has_none_of_them():
    once = CFG.replace(loops=1, exit_gate=False, norm_placement="pre")
    params = jax.eval_shape(lambda: base.Transformer.init(
        jax.random.key(0), once))
    hlo = jax.jit(jax.grad(
        lambda p, b: base.Transformer.loss(p, b, once))).lower(
            params, {"tokens": jnp.zeros((2, 65), jnp.int32)}).as_text(
                debug_info=True)
    found = base.scopes_in(hlo)
    assert not NEW & found, sorted(NEW & found)
    assert {"layers", "final_norm", "attn_norm", "mlp_norm"} <= found
