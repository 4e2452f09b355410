"""The Ouro-2.6B looped step compiled for a described v5e (PR 63): a file
of its own, so that it runs beside the other step files on another worker
(the fixture stays in `tests/test_chip_compile.py`)."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from test_chip_compile import v5e  # noqa: F401


def test_looped_step_compiles_and_fits_the_v5e(v5e):
    """Published layers 0-7 of Ouro-2.6B at their widths (16 / 16 heads of
    128, MLPs of 5,632, the sandwich norm's four gains a layer) run four
    times through the same weights + the whole head of 49,152 rows and
    the exit gate, as one train step of 8,192 tokens for the v5e (the
    benchmark's `train_ouro26b_d8`): the passes are ONE loop around the
    layers' scan (two whiles forward and two backward, and the chunked
    head's), so splash is called once each way in the text and 4 x 8
    times a step, the forward kernel not run again under `_remat`; the
    norms on the sublayers' outputs lie inside the scopes that close
    them; the exit gate and the exit loss have their scopes; and the
    compiler's memory report is what it was when the cell's first chip
    run read `peak_hbm_gb` under the chip's 16.91."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 8192
    cfg = TransformerConfig(
        vocab_size=49152, d_model=2048, n_layers=8, n_heads=16,
        n_kv_heads=16, d_ff=5632, max_seq_len=seq, rope_theta=1e6,
        norm_eps=1e-6, loops=4, exit_gate=True, exit_entropy_coeff=0.05,
        norm_placement="both", attention_impl="auto", dtype="bfloat16",
        param_dtype="float32", remat=True, loss_chunk=256)
    assert cfg.num_params == 612_438_017
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True),
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
    # both kernels lie in the layers' scan inside the passes' scan
    for _, op in kernels:
        assert "loops" in op and "layers" in op and op.index("loops") \
            < op.index("/while/body/closed_call/layers/while/body/"), op
    for scope in ("loops", "loop/exit_gate", "loop/exit_loss", "attn_norm",
                  "attn_post_norm", "mlp_norm", "mlp_post_norm", "qkv",
                  "attention", "attn_out", "mlp/gate_up", "mlp/down",
                  "final_norm", "head", "loss", "optimizer"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    for inside in ("attn_out/attn_post_norm", "mlp/down/mlp_post_norm",
                   "closed_call/final_norm"):
        assert inside in hlo, inside
    # the passes' hidden states are stacked once: [4, 1, 8192, 2048] bf16
    assert "bf16[4,1,8192,2048]" in hlo
    # no [tokens, vocab] f32 block larger than the head's chunk of
    # 4 rows x 256 tokens
    assert "f32[4,256,49152]" in hlo
    assert not re.search(r"f32\[\d+,(?:8192|4096|2048|1024|512),49152\]",
                         hlo)
    ma = compiled.memory_analysis()
    # 12 B a parameter resident
    assert abs(ma.argument_size_in_bytes - 612_438_017 * 12) < 1e6
    # 13.83 GB where the chip read `peak_hbm_gb` 15.88 of 16.91
    assert ma.temp_size_in_bytes < 14.0e9, ma.temp_size_in_bytes
