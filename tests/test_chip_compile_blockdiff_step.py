"""The block-diffusion share step compiled for a described v5e: a file of
its own since PR 61 (a minute or more of one worker; `--dist loadfile` runs
it beside `test_chip_compile_steps.py`, the SambaY step, and
`test_chip_compile_kda_step.py`). The fixture stays in
`tests/test_chip_compile.py`."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from test_chip_compile import v5e  # noqa: F401


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
