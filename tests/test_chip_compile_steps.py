"""The benchmark's whole SambaY step compiled for a described v5e. Each
whole step is a minute or more of one worker, so each has a file of its own
(`--dist loadfile` then runs them beside each other): the KDA share step is
`test_chip_compile_kda_step.py`'s and the block-diffusion share step
`test_chip_compile_blockdiff_step.py`'s since PR 61. The fixture and the
helpers stay in `tests/test_chip_compile.py` and
`tests/test_chip_compile_shares.py`."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from test_chip_compile import v5e  # noqa: F401


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
