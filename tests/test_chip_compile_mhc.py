"""The Xing4.0-29B-A4B step compiled for a described v5e (PR 66): a file
of its own, so that it runs beside the other step files on another worker
(the fixture stays in `tests/test_chip_compile.py`)."""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from test_chip_compile import v5e  # noqa: F401

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)


def test_streams_share_step_compiles_and_fits_the_v5e(v5e):
    """Published layers 1-5 of Xing4.0-29B-A4B at their widths (a residual
    path of four streams of 3,584, latent attention with 8 of 32 heads of
    192 / 128 under YaRN, a dense MLP of 9,216, then four layers of a
    shared expert and 8 of 64 experts of 1,024) + an eighth of the
    vocabulary, as one train step of 8,192 tokens for the v5e (the
    benchmark's `train_xing4_ep8_d5`, built from its configuration file by
    its job's mapping): splash once each way in each run's scan and not
    again under `_remat`, `megablox` over the held experts' run of rows,
    the five `mhc/*` scopes with the write inside the scope that closes
    its sublayer, the carry a layer `[1, 8192, 4, 3584]` in bf16 and
    unpadded, no `[tokens, 4, 4]` array of the maps, and the compiler's
    memory report what it was when the cell's first chip run read
    `peak_hbm_gb` under the chip's 16.91."""
    import re

    import optax

    from benchlib.spec import load_json, load_module
    from ray_tpu.models import Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    model = load_json(os.path.join(
        BENCH_DIR, "configs", "xing4.0-29b-a4b-ep8-tp4-d5.json"))
    job = load_module("jobs", model["job"])
    seq = 8192
    cfg = job.transformer_config(model, model["train"], seq)
    assert cfg.num_params == 670_872_590
    assert (cfg.residual_streams, cfg.hc_sinkhorn_iters) == (4, 20)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 2.0047) < 1e-5
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
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
    names = sorted({re.sub(r"\.\d+$", "", n) for n, _ in kernels})
    # `ragged-dot-*`: the path over every row, the other branch of
    # `row_bound`'s one `cond` a pass
    assert [n for n in names if not n.startswith("ragged-dot")] == [
        "gmm", "splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals",
        "tgmm"], names
    # the forward attention kernel is not run again under remat: one call
    # each way in the dense run's scan and in the expert run's
    splash = [op for n, op in kernels if n.startswith("splash")]
    assert len(splash) == 4 and not [
        op for op in splash if "rematted_computation" in op], splash
    for scope in ("mhc/maps", "mhc/pre", "mhc/post", "mhc/expand",
                  "mhc/collapse", "attn_norm", "mlp_norm", "qkv",
                  "attention", "attn_out", "mlp/gate_up", "mlp/down",
                  "moe/router", "moe/experts", "moe/combine", "moe/shared",
                  "final_norm", "head", "loss", "optimizer"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    for inside in ("attn_out/mhc/post", "mlp/down/mhc/post",
                   "moe/combine/mhc/post",
                   "rematted_computation/mhc/maps"):
        assert inside in hlo, inside
    # the carry a layer, unpadded: four bytes a token and column in bf16
    assert re.search(r"bf16\[4,1,8192,4,3584\]", hlo)
    assert not re.search(r"f32\[4,1,8192,4,3584\]", hlo)
    # the maps live with the tokens last: no [.., 8192, 4, 4] array
    assert not re.search(r"f32\[(1,)?8192,4,4\]", hlo)
    ma = compiled.memory_analysis()
    # 12 B a parameter resident (and the choice bias, a buffer)
    assert abs(ma.argument_size_in_bytes - 670_872_590 * 12) < 1e6
    # 11.07 GB where the compiler's own usage report read 15.44 GB and the
    # chip `peak_hbm_gb` under 16.91
    assert ma.temp_size_in_bytes < 11.3e9, ma.temp_size_in_bytes
