"""The scopes that a residual path of several streams adds to the
vocabulary of models/transformer.py (PERF.md section 3: `mhc/maps`,
`mhc/pre`, `mhc/post`, `mhc/expand`, `mhc/collapse`; ops/mhc.py), the way
tests/test_model_scopes.py holds the others: every name reaches the lowered
module's `op_name`s, the write lies inside the scope that closes its
sublayer, forward, backward and recomputation are read off JAX's own
wrappers, and one stream has none of the new names. Since PR 67 the mixing
is two `jax.custom_vjp`s (`ops/mhc.enter`, `leave`), whose backward rules
carry no scope unless given one: every equation of the gradient's program
that `ops/mhc.py` wrote, the backward rules' and the kernels' among them,
lies under an `mhc/*` scope, and remat's forward holds `h` and the rounds
but not the product with phi (`mhc.MAPS_RESIDUALS` keeps it)."""

import functools
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
    # (since PR 67 the read is written inside `enter`, whose call lies
    # under `mhc/maps`: `mhc/maps/mhc/pre`, and the readers book an op by
    # the LAST of the names in its path)
    for scope in ("mhc/maps", "mhc/maps/mhc/pre"):
        assert any(body + scope in n or body + "checkpoint/" + scope in n
                   for n in cleaned), scope
        assert any(body + "checkpoint/rematted_computation/" + scope in n
                   for n in cleaned), scope
    # the product with phi and the rounds' divisions are the maps'
    assert any("mhc/maps" in n and "dot_general" in n for n in cleaned)
    assert any("mhc/maps" in n and "div" in n for n in cleaned)
    # until PR 67 remat's forward made the product again and this line
    # held that there was one; the layer's remat now keeps it (m and r,
    # `mhc.MAPS_RESIDUALS`) and makes the rounds again on what it kept
    remat = [n for n in cleaned if "rematted_computation/mhc/maps" in n]
    assert any("div" in n for n in remat)
    assert not [n for n in remat if "dot_general" in n]
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


def test_every_equation_of_the_mixing_lies_under_one_of_them():
    """The gradient's jaxpr of a step, by who wrote each equation: what
    `ops/mhc.py` wrote (the two forward rules, the two backward rules,
    the passes in `jax.numpy`, the kernels under interpretation) carries
    an `mhc/*` scope in its name stack; `benchmark/benchlib/mhc_reduce.py`
    reads those scopes and nothing else."""
    from jax._src import source_info_util

    from ray_tpu.ops import mhc
    from tests.test_moe_routing_residuals import subjaxprs

    n, d = 4, 128
    keys = jax.random.split(jax.random.key(0), 5)
    operands = (jax.random.normal(keys[0], (1, 128, n * d)).astype(
        jnp.bfloat16), jax.random.normal(keys[1], (n * d, n * n + 2 * n)),
        jnp.zeros((n * n + 2 * n,)), jnp.ones((3,)),
        jax.random.normal(keys[2], (d, d)).astype(jnp.bfloat16))

    def sublayer(interpret, x, phi, b, alpha, w):
        h, (_, post, res), x = mhc.enter(
            x, phi, b, alpha, rounds=2, norm_eps=1e-6, hc_eps=1e-6,
            clamp=30.0, interpret=interpret)
        out = mhc.leave(x, jnp.tanh(h @ w), post, res, interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def walk(jaxpr, outer):
        """(equation, its name stack under the equations that hold its
        jaxpr: a `pjit`'s, a `custom_vjp_call`'s or a kernel's body names
        itself from its caller on)."""
        for eqn in jaxpr.eqns:
            stack = outer + "/" + str(eqn.source_info.name_stack)
            yield eqn, stack
            for value in eqn.params.values():
                for sub in subjaxprs(value):
                    yield from walk(sub, stack)

    for interpret in (False, True):
        jaxpr = jax.make_jaxpr(jax.grad(
            functools.partial(sublayer, interpret), argnums=(0, 1, 2, 3, 4)))(
                *operands)
        written, kernels = 0, set()
        for eqn, stack in walk(jaxpr.jaxpr, ""):
            frame = source_info_util.user_frame(eqn.source_info.traceback)
            if frame is None or not frame.file_name.endswith("ops/mhc.py"):
                continue
            written += 1
            while base.TRANSFORMS.search(stack):
                stack = base.TRANSFORMS.sub(r"\1", stack)
            assert SCOPE.search(stack), (eqn.primitive.name, stack,
                                         frame.function_name)
            if eqn.primitive.name == "pallas_call":
                kernels.add(eqn.params["name"])
        assert written > 100
        assert kernels == ({"mhc_enter_fwd", "mhc_enter_bwd",
                            "mhc_leave_fwd", "mhc_leave_bwd"}
                           if interpret else set())
