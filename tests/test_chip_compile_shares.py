"""The latent-attention share step (GLM-4.7-Flash's widths) compiled for a
described v5e over what `remat=True` saves, and the helper the other share
steps read. Moved out of `tests/test_chip_compile.py` by PR 57 so that the
files run on different workers (its fixture and helpers stay there); the
hybrid share (Nemotron-3-Super's) is `tests/test_chip_compile_hybrid_share.py`
since PR 61."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import (REMAT_POLICIES,  # noqa: F401
                               assert_saved_residuals, v5e)

def assert_chosen_scores_read_off_the_selection(hlo, tokens, picked,
                                                experts):
    """A biased router's chosen scores as the v5e's compiler leaves them
    (`ops/moe._chosen_scores`): compare, select and sum are one fusion, so
    nothing `[N, k, E]` is a buffer in memory (a value of a computation
    that no fusion calls); no gather makes an `[N, k]` and none runs under
    `moe/router`; no scatter fills the `[N, E]` scores (XLA flattens that
    one to `[N·E]` and gives it no `op_name`). Returns the fusions under
    the router that hold an `[N, k, E]` value: one forward and one
    backward a scan at least, or the check read nothing."""
    import re

    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    wide = re.compile(rf"\[{tokens},({picked},{experts}|{experts},{picked})\]")
    chosen = re.compile(rf"\[({tokens},{picked}|{picked},{tokens})\]")
    scores = re.compile(rf"f32\[({tokens},{experts}|{tokens * experts})\]")
    inside, holding = None, set()
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
        if head or " = " not in line:
            continue
        shape, op = line.split(" = ", 1)[1].split(" ", 1)
        if wide.search(shape):
            assert inside in fused, line[:300]
            if "moe/router" in line:
                holding.add(inside)
        if op.startswith("gather("):
            assert not chosen.search(shape) and "moe/router" not in line, \
                line[:300]
        if op.startswith("scatter("):
            assert not scores.match(shape) and "moe/router" not in line, \
                line[:300]
    assert len(holding) >= 2, holding
    return holding


@pytest.mark.parametrize("policy,fwd_calls", REMAT_POLICIES)
def test_latent_attention_share_step_compiles_for_the_v5e(v5e, policy,
                                                          fwd_calls):
    """One dense and two expert layers of GLM-4.7-Flash's widths (latent
    attention at 20 heads of 192 + 64 / 256, 8 of 64 experts of width 1536
    held, a shared expert) + head, as one train step for the v5e: splash's
    kernels at head_dim 256, `megablox` at the width 1024 does not divide
    (tiles from each call's shapes), held weights only in the grouped
    matmuls, the run of rows a share's path is bounded to behind a `cond`,
    and the new scopes on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.moe import grouped_matmul_impl, row_bound
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq, rows = 1024, 1
    cfg = TransformerConfig(
        vocab_size=19360, d_model=2048, n_layers=3, n_heads=20, d_ff=1536,
        max_seq_len=seq, rope_theta=1e6, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        moe_experts=64, moe_top_k=4, moe_scoring="sigmoid",
        moe_routed_scale=1.8, moe_shared_experts=1, moe_dense_layers=1,
        moe_dense_ff=10240, moe_experts_held=8, moe_aux_coeff=0.0,
        attention_impl="auto", dtype="bfloat16", param_dtype="float32",
        remat=True, loss_chunk=256, **policy)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    slots = rows * seq * cfg.moe_top_k
    assert grouped_matmul_impl(mesh, slots, cfg.d_model, cfg.ff_dim) == \
        "megablox"
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True),
        Transformer.param_specs(cfg), mesh, optimizer=optimizer,
        frozen=Transformer.frozen(cfg))

    def init(key):
        params = Transformer.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)}
    hlo = train_step.lower(state, batch).compile().as_text()
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}", hlo)

    kernels = re.findall(
        r'%([\w.\-]+) = ([^\n]*)custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    names = [name for name, _, _ in kernels]
    grouped = [(n, text, op) for n, text, op in kernels
               if re.match(r"t?gmm(\.\d+)?$", n)]   # text: the call's type
    # the kernels run over `row_bound`'s run of 1,024 rows, not the 4,096
    # slots; the path over every row is the other branch of one `cond` a
    # pass (forward, remat's forward, backward), on `ragged_dot`
    assert sum(n.startswith("gmm") for n, _, _ in grouped) == 6, names
    assert sum(n.startswith("tgmm") for n, _, _ in grouped) == 2, names
    assert all("moe/experts" in op for _, _, op in grouped), grouped
    assert row_bound(rows * seq, cfg.moe_top_k, 8, 64, slots) == 1024
    assert {int(re.match(r"bf16\[(\d+),", text).group(1))
            for n, text, _ in grouped if n.startswith("gmm")} == {1024}
    assert len(re.findall(r" conditional\(", hlo)) == 3
    # the weights of the 8 held experts reach the kernels, never 64
    assert re.search(r"bf16\[8,2048,3072\]", hlo)
    assert re.search(r"bf16\[8,1536,2048\]", hlo)
    assert not re.search(r"\[64,(2048|1536),", hlo)
    # two scans (the dense layer, the two expert layers): the forward
    # (under "full" remat's too) and the fused backward, each
    assert sum(n.startswith("splash_mha_fwd") for n in names) == \
        2 * fwd_calls, names
    assert sum(n.startswith("splash_mha_dkv") for n in names) == 2, names
    assert_saved_residuals(hlo, policy, (2, rows, 20, seq, 256))
    assert_chosen_scores_read_off_the_selection(hlo, rows * seq, 4, 64)
    for scope in ("qkv/q_down", "qkv/kv_down", "qkv/q_up", "qkv/kv_up",
                  "qkv/assemble", "moe/shared", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "mlp/gate_up", "mlp/down"):
        assert re.search(r'op_name="[^"]*[/(]' + scope + r'[/)]', hlo), scope


# [B, T, heads, head width, groups, state, chunk]: the Nemotron cell's
# mixer as one chip holds it, the uncut model's (128 heads in 8 groups,
# two batch rows), a head that is a whole lane tile with a chunk of 256,
# and the smallest shape `scan_shape_ok` says yes to
