"""The KDA share step compiled for a described v5e: a file of its own
since PR 61 (a minute or more of one worker; `--dist loadfile` runs it
beside `test_chip_compile_steps.py`, the SambaY step, and
`test_chip_compile_blockdiff_step.py`). The fixture and the helpers stay
in `tests/test_chip_compile.py` and `tests/test_chip_compile_shares.py`."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from test_chip_compile import v5e  # noqa: F401
from test_chip_compile_shares import (  # noqa: F401
    assert_chosen_scores_read_off_the_selection)


def test_kda_share_step_compiles_and_fits_the_v5e(v5e):
    """A dense, an expert and a latent-attention layer of Ling-3.0-flash's
    widths as one chip holds them (`kKL`: 8 KDA heads of 128, 8 latent-
    attention heads of 192 / 128 with no query latent and the QK-norm, a
    dense MLP of 6,144, 8 of 512 experts of 768 under the group-limited
    router, top-8 in 4 of 8 groups, a shared expert of 768) + an eighth of
    the head, as one train step of 16,384 tokens for the v5e (the
    benchmark's `train_ling3flash_ep64_d7` has four more `K` layers):
    splash takes keys 192 wide beside values 128 wide, unpadded, in blocks
    of 1,024; `megablox` over `row_bound`'s run of 4,096 rows; the delta
    rule is the pallas kernels under `kda/delta` (`kda_delta_impl` says
    "pallas" for this mesh and these shapes: per KDA layer one
    `kda_delta_fwd`, whose output, entering states and chunk inverses the
    layer's remat keeps (`ops.kda.DELTA_RESIDUALS`, PR 65: none in remat's
    forward), and one `kda_delta_bwd` that reads them, both inside
    `vmem_limit_bytes`) and nothing there is as large as a sub-block's
    factors; the new scopes are on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.attention import _splash_block_sizes
    from ray_tpu.ops.kda import kda_delta_impl
    from ray_tpu.ops.moe import gmm_tiles, grouped_matmul_impl, row_bound
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 16384
    cfg = TransformerConfig(
        vocab_size=19648, d_model=2560, n_layers=3, layer_pattern="kKL",
        n_heads=8, n_kv_heads=8, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, qk_norm=True, rope_theta=6e6,
        d_ff=768, moe_dense_ff=6144, max_seq_len=seq, norm_eps=1e-6,
        kda_heads=8, kda_head_dim=128, kda_chunk=64, moe_experts=512,
        moe_top_k=8, moe_scoring="sigmoid", moe_routed_scale=2.5,
        moe_groups=8, moe_topk_groups=4, moe_shared_experts=1,
        moe_shared_ff=768, moe_experts_held=8, moe_aux_coeff=0.0,
        attention_impl="auto", dtype="bfloat16", param_dtype="float32",
        remat=True, loss_chunk=256)
    assert cfg.pattern_runs == [("kKL", 1)]
    assert (cfg.head_dim, cfg.v_dim) == (192, 128)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    assert _splash_block_sizes(seq, 192).block_kv == 1024
    assert _splash_block_sizes(seq, 256).block_kv == 512     # GLM's
    bound = row_bound(seq, 8, 8, 512, seq * 8)
    assert bound == 4096
    assert gmm_tiles(bound, 2560, 2 * 768) == (512, 640, 768)
    assert gmm_tiles(bound, 768, 2560) == (512, 768, 640)
    assert grouped_matmul_impl(mesh, bound, 2560, 768) == "megablox"
    assert kda_delta_impl(mesh, seq, 8, 128, 128, cfg.kda_chunk) == "pallas"
    optimizer = optax.adamw(3e-7, weight_decay=0.01)
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
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq + 1), jnp.int32)}
    compiled = train_step.lower(state, batch).compile()
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                 compiled.as_text())
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    # the program's kernels (the fallback branch's `ragged-dot-*` calls
    # are XLA's own)
    names = sorted(re.sub(r"\.\d+$", "", n) for n, _ in kernels
                   if re.match(r"t?gmm|splash", n))
    # two expert layers: per matmul the forward, remat's forward and the
    # transpose for the rows, one for the weights; splash once each way
    assert names == ["gmm"] * 12 + ["splash_mha_dkv_no_residuals",
                                    "splash_mha_fwd_residuals"] \
        + ["tgmm"] * 4, names
    # two KDA layers: each a forward and a backward of the delta rule,
    # under `kda/delta` and nowhere else, and no forward in remat's
    under_kda = sorted(
        (re.search(r"kda_delta_(fwd|bwd)", n).group(0),
         "rematted_computation" in op, "transpose(jvp" in op)
        for n, op in kernels if "kda" in n or "kda/" in op)
    assert under_kda == [("kda_delta_bwd", False, True)] * 2 \
        + [("kda_delta_fwd", False, False)] * 2, under_kda
    assert all("/kda/delta/" in op for n, op in kernels if "kda" in n)
    # what the forward kernel writes and the backward one reads: o, the
    # states entering the 256 chunks and the chunks' inverses, a pair of
    # heads' two `[64, 64]` blocks side by side, all float32
    kept = ["f32[1,16384,1024]", "f32[1,256,1024,128]", "f32[1,256,256,128]"]
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*%kda_delta_(fwd|bwd)[\w.]* = ", line)]
    assert len(calls) == 4
    for line in calls:
        result, operands = line.split("custom-call(", 1)
        if "kda_delta_fwd" in result:
            assert all(shape in result for shape in kept), line[:400]
        else:
            assert all(shape in operands for shape in kept[1:]), line[:400]
    assert not [op for n, op in kernels
                if "rematted_computation" in op and "splash" in n]
    assert_chosen_scores_read_off_the_selection(hlo, seq, 8, 512)
    for scope in ("kda_norm", "kda/qkv_proj", "kda/conv", "kda/gates",
                  "kda/delta", "kda/out_norm", "kda/out_proj", "qkv/q_proj",
                  "qkv/kv_down", "qkv/kv_up", "qkv/assemble", "moe/router",
                  "moe/shared", "mlp/gate_up", "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    # under `kda/delta` the largest tensor is the entering states,
    # `[T/C, H·Dv, D]` float32: the sub-blocks' column factors
    # `[H, T/C, C/16, C, D]`, four times that, stay in VMEM
    for line in hlo.splitlines():
        if not re.search(r'op_name="[^"]*[/(]kda/delta[/)"]', line):
            continue
        for dims in re.findall(r"\b(?:f32|bf16|s32)\[([\d,]+)\]", line):
            assert np.prod([int(v) for v in dims.split(",")]) \
                <= 8 * seq * 2 * 128, line[:300]
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes
             + ma.generated_code_size_in_bytes)
    assert total < 12e9, total
