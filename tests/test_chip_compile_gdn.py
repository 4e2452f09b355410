"""The Olmo-Hybrid share step compiled for a described v5e (PR 59): a
file of its own, so that it runs beside `tests/test_chip_compile_steps.py`
on another worker (the fixture stays in `tests/test_chip_compile.py`)."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from test_chip_compile import v5e  # noqa: F401


def test_gdn_share_step_compiles_and_fits_the_v5e(v5e):
    """Published layers 0-3 of Olmo-Hybrid-7B at their widths as one of 2
    chips that share the heads holds them (`ddda`: 15 Gated DeltaNet heads
    with keys of 96 beside values of 192, 15 attention heads of 128 with the
    QK-norm over the whole projection and no RoPE, MLPs of 11,008, every
    sublayer under the reordered norm) + an eighth of the head, as one
    train step of 8,192 tokens for the v5e (the benchmark's
    `train_olmohybrid7b_tp2_d4`): the delta rule behind a decay a head is
    plain XLA under `gdn/delta` (`kda_delta_impl(..., per_head=True)`
    says "xla" whatever the mesh: no `kda_delta_*` kernel anywhere),
    splash runs once forward and once backward for the one attention
    layer, the norms on the sublayers' outputs lie inside the scopes that
    close them, the compiler's temporaries are no more than PR 70 left
    them (9.265 GB, where the chip reads `peak_hbm_gb` 15.94 of 16.91), and
    no instruction carries the `.remat` name XLA gives what it clones to
    fit the memory: a step that holds more at its peak shows there first
    (with 1.19 GB a layer of the rule's residuals held, the gate/up matmul
    ran a third time, 23.4 ms a step: PERF.md section 6, PR 70)."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.kda import kda_delta_impl
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 8192
    cfg = TransformerConfig(
        vocab_size=12544, d_model=3840, n_layers=4, layer_pattern="ddda",
        n_heads=15, n_kv_heads=15, attn_head_dim=128, qk_norm=True,
        rope=False, d_ff=11008, max_seq_len=seq, norm_eps=1e-6,
        gdn_heads=15, gdn_key_dim=96, gdn_value_dim=192, gdn_conv_kernel=4,
        gdn_neg_eigval=True, gdn_chunk=64, attention_impl="auto",
        dtype="bfloat16", param_dtype="float32", remat=True, loss_chunk=256)
    assert cfg.pattern_runs == [("d", 3), ("a", 1)]
    assert cfg.num_params == 766_241_946
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    assert kda_delta_impl(mesh, seq, 15, 96, 192, 64, per_head=True) == "xla"
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh, optimizer=optimizer)

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
    names = sorted(re.sub(r"\.\d+$", "", n) for n, _ in kernels)
    assert names == ["splash_mha_dkv_no_residuals",
                     "splash_mha_fwd_residuals"], names
    assert not [op for _, op in kernels if "rematted_computation" in op]
    for scope in ("gdn/qkv_proj", "gdn/conv", "gdn/gates", "gdn/delta",
                  "gdn/out_norm", "gdn/out_proj", "gdn_post_norm",
                  "attn_post_norm", "mlp_post_norm", "qkv/qk_norm",
                  "attention", "attn_out", "mlp/gate_up", "mlp/down",
                  "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    # no norm opens a sublayer, and the norms on their outputs lie inside
    # the scope that closes each
    for scope in ("attn_norm", "gdn_norm", "mlp_norm"):
        assert not re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    for inside in ("gdn/out_proj/gdn_post_norm", "attn_out/attn_post_norm",
                   "mlp/down/mlp_post_norm"):
        assert inside in hlo, inside
    # under `gdn/delta` nothing is as large as a decay a channel's column
    # factors `[H, T/C, C/16, C, D]` would be: the largest are a chunk's
    # `[C, 2C]` scores a head, `[H, T/C, 2C, C]`
    for line in hlo.splitlines():
        if not re.search(r'op_name="[^"]*[/(]gdn/delta[/)"]', line):
            continue
        for dims in re.findall(r"\b(?:f32|bf16|s32)\[([\d,]+)\]", line):
            assert np.prod([int(v) for v in dims.split(",")]) \
                <= 15 * seq * 2 * 192, line[:300]
    # XLA's own clones are `<name>.remat`, `.remat2`, ...: JAX's remat
    # lives in `op_name`, not in an instruction's name
    clones = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+\.remat\d*) = ", hlo, re.M)
    assert not clones, clones
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes < 9.2e9
    assert ma.temp_size_in_bytes < 9.30e9, ma.temp_size_in_bytes
