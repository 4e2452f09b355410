"""Whole benchmark steps compiled for a described v5e: the SambaY step, the
KDA share step and the block-diffusion share step. Moved out of
`tests/test_chip_compile.py` by PR 57, unchanged, so that the three files
run on different workers (its fixture and helpers stay there)."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from test_chip_compile import v5e  # noqa: F401
from test_chip_compile_shares import (  # noqa: F401
    assert_chosen_scores_read_off_the_selection)

def test_sambay_step_compiles_and_fits_the_v5e(v5e):
    """Published layers 14-19 of Phi-4-mini-flash-reasoning at their
    widths and an eighth of the vocabulary (the benchmark's
    `train_phi4miniflash_d6`) as one train step of 16,384 tokens for the
    v5e: splash's kernels once forward and once backward for each of the
    three attention layers, under `attention/window`, `attention/full` and
    `attention/cross`; the Mamba-1 scans under `ssm/scan` as their pallas
    kernels (`ops/ssm.selective_scan_impl` says "pallas" for this mesh:
    each mixer's forward, remat's forward and the backward, the state
    never in HBM but for the `[T/Q, N, C]` entering states); and the
    compiler's memory report no higher than it was with the scans as
    plain XLA loops, 13.98 GB, under the 15.75 GB the runtime gives a
    program."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.ssm import SCAN1_STEPS, selective_scan_impl
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 16384
    cfg = TransformerConfig(
        vocab_size=25008, d_model=2560, n_layers=6, layer_pattern="mwsfgc",
        layer_index_offset=14, n_heads=40, n_kv_heads=20, rope=False,
        diff_attention=True, attn_bias=True, attn_window=512, d_ff=10240,
        max_seq_len=seq, norm="layernorm", tie_embeddings=True,
        ssm_d_inner=5120, ssm_state=16, ssm_dt_rank=160, ssm_chunk=1024,
        attention_impl="auto", dtype="bfloat16", param_dtype="float32",
        remat=True, loss_chunk=256)
    assert cfg.num_params == 697_094_272
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    assert selective_scan_impl(mesh, seq, 5120, 16, 1024) == "pallas"
    assert selective_scan_impl(None, seq, 5120, 16, 1024) == "xla"  # the CPU
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh, optimizer=optimizer,
        frozen=Transformer.frozen(cfg))

    def init(key):
        params = Transformer.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq + 1), jnp.int32)}
    compiled = train_step.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes
             + ma.generated_code_size_in_bytes)
    assert 12e9 < total <= 13.98e9, total
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                 compiled.as_text())
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    for kind in ("window", "full", "cross"):
        mine = [n for n, op in kernels if f"attention/{kind}" in op]
        assert sorted(re.sub(r"\.\d+$", "", n) for n in mine) == [
            "splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"], \
            (kind, kernels)
    # each mixer's scan forward, in remat's forward and backward
    scans = [(re.sub(r"\.\d+$", "", n), op) for n, op in kernels
             if "ssm/scan" in op]
    assert sorted(n for n, _ in scans) == \
        ["selective_scan_bwd"] * 2 + ["selective_scan_fwd"] * 4, kernels
    assert sum("rematted_computation" in op for _, op in scans) == 2
    assert len(kernels) == 12
    # remat keeps the attention kernels' outputs: none runs again
    assert not [op for n, op in kernels
                if "rematted_computation" in op and "splash" in n]
    # under `ssm/scan` the state is in HBM only as it enters a time block:
    # nothing state-shaped beyond `[T/Q, N, C]`, nothing above `[T, C]`
    for line in hlo.splitlines():
        if not re.search(r'op_name="[^"]*[/(]ssm/scan[/)"]', line):
            continue
        for dims in re.findall(r"\b(?:f32|bf16|s32)\[([\d,]+)\]", line):
            dims = [int(v) for v in dims.split(",")]
            assert np.prod(dims) <= seq * 5120, line[:300]
            if dims[-2:] == [16, 5120]:
                assert np.prod(dims[:-2]) <= seq // SCAN1_STEPS, line[:300]
    for scope in ("ssm/scan", "ssm/x_proj", "ssm/gate", "gmu/gate",
                  "attention/diff", "mlp/gate_up", "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope


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
    "pallas" for this mesh and these shapes: per KDA layer a
    `kda_delta_fwd` in the forward, one in remat's forward and a
    `kda_delta_bwd`) and nothing there is as large as a sub-block's
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
    assert gmm_tiles(bound, 2560, 2 * 768) == (512, 512, 768)
    assert gmm_tiles(bound, 768, 2560) == (512, 768, 512)
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
    # two KDA layers: each a forward, remat's forward and a backward of
    # the delta rule, all under `kda/delta` and nowhere else
    under_kda = sorted(
        (re.search(r"kda_delta_(fwd|bwd)", n).group(0),
         "rematted_computation" in op, "transpose(jvp" in op)
        for n, op in kernels if "kda" in n or "kda/" in op)
    assert under_kda == [("kda_delta_bwd", False, True)] * 2 \
        + [("kda_delta_fwd", False, False)] * 2 \
        + [("kda_delta_fwd", True, True)] * 2, under_kda
    assert all("/kda/delta/" in op for n, op in kernels if "kda" in n)
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


def test_blockdiff_share_step_compiles_and_fits_the_v5e(v5e):
    """Two layers of SDAR-30B-A3B's widths as one chip holds them (GQA 32
    / 4 heads of 128 with the per-head QK-norm, 16 of 128 experts of 768
    under the softmax router, top-8) + an eighth of the head, as one
    block-diffusion train step of 8,192 data tokens for the v5e (the
    benchmark's `train_sdar30b_ep8_d4` has two layers more): the noise in
    the step, the stream of 16,384 positions through splash under the
    block-diffusion mask, computed in the kernel from the positions'
    indices (one forward and one fused backward call under
    `attention/block_diffusion`, none of them remat's), `megablox` over
    `row_bound`'s run of 32,768 rows of the stream's 131,072, and the new
    scopes on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer, diffusion
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.attention import _splash_block_sizes, block_table
    from ray_tpu.ops.moe import gmm_tiles, grouped_matmul_impl, row_bound
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 8192
    cfg = TransformerConfig(
        vocab_size=18992, d_model=2048, n_layers=2, n_heads=32,
        n_kv_heads=4, attn_head_dim=128, d_ff=768, max_seq_len=2 * seq,
        rope_theta=1e6, norm_eps=1e-6, qk_norm=True, qk_norm_per_head=True,
        moe_experts=128, moe_top_k=8, moe_scoring="softmax",
        moe_aux_coeff=0.0, moe_experts_held=16, block_length=4,
        mask_token_id=18991, attention_impl="auto", dtype="bfloat16",
        param_dtype="float32", remat=True, loss_chunk=256)
    assert cfg.num_params == 2 * 94_638_336 + 2 * 18992 * 2048 + 2048
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, 2 * seq) == "flash"
    assert _splash_block_sizes(2 * seq, 128).block_kv == 1024
    table = block_table(2 * seq, 128, 4, seq)
    assert (table["non_empty"], table["partial"]) == (80, 24)
    bound = row_bound(2 * seq, 8, 16, 128, 2 * seq * 8)
    assert bound == 32768
    assert gmm_tiles(bound, 2048, 2 * 768) == (512, 1024, 768)
    assert grouped_matmul_impl(mesh, bound, 2048, 768) == "megablox"
    optimizer = optax.adamw(3e-7, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, diffusion.noised(b, cfg), cfg,
                                      mesh=mesh, with_metrics=True),
        Transformer.param_specs(cfg), mesh, optimizer=optimizer)

    def init(key):
        params = Transformer.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32),
             "noise_key": jax.ShapeDtypeStruct((1, 2), jnp.uint32)}
    compiled = train_step.lower(state, batch).compile()
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                 compiled.as_text())
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    names = sorted(re.sub(r"\.\d+$", "", n) for n, _ in kernels
                   if re.match(r"t?gmm|splash", n))
    # per matmul the forward, remat's forward and the transpose for the
    # rows, one for the weights; splash once each way
    assert names == ["gmm"] * 6 + ["splash_mha_dkv_no_residuals",
                                   "splash_mha_fwd_residuals"] \
        + ["tgmm"] * 2, names
    splash = [(n, op) for n, op in kernels if "splash" in n]
    assert all("attention/block_diffusion" in op for _, op in splash)
    assert not [op for _, op in splash if "rematted_computation" in op]
    # no [positions, positions] mask reaches the device: the kernel's
    # mask operands are its block tables alone
    assert "pred[16384,16384]" not in hlo and "s8[16384,16384]" not in hlo
    for scope in ("diffusion/noise", "diffusion/stream", "qkv/qk_norm",
                  "attention/block_diffusion", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    # state and the step's temporaries within the chip's 16.9 GB
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13.5e9
