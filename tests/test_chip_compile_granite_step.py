"""The Granite 4.0-H packed step (`train_granite4hmicro_d10_packed`'s own
configuration file, published widths, ten layers, one 8,192-token
sequence) compiled for a described v5e, with `segment_ids` in the batch: a
file of its own, as the other whole steps have
(`--dist loadfile` runs it beside them). The fixture stays in
`tests/test_chip_compile.py`."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import v5e  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

SEQ = 8192


@pytest.fixture(scope="module")
def step(v5e):  # noqa: F811
    """(cfg, mesh, lower): `lower()` compiles the packed train step for
    the v5e and returns the compiled program."""
    import optax

    from benchlib.spec import load_json, load_module
    from ray_tpu.models import Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    model = load_json(os.path.join(
        BENCH_DIR, "configs", "granite-4.0-h-micro-d10-v8.json"))
    job = load_module("jobs", model["job"])
    cfg = job.transformer_config(model, model["train"], SEQ)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    optimizer = optax.adamw(1e-5, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                      with_metrics=True),
        Transformer.param_specs(cfg), mesh, optimizer=optimizer)

    def init(key):
        params = job.init_params(key, cfg, model["init"])
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))

    def lower():
        tokens = jax.ShapeDtypeStruct((1, SEQ + 1), jnp.int32)
        return train_step.lower(
            state, {"tokens": tokens, "segment_ids": tokens}).compile()

    return cfg, mesh, lower


def kernels_of(hlo):
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}", hlo)
    return re.findall(
        r'%([\w.\-]+) = ([^\n]*)custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)


def test_granite_step_compiles_and_fits_the_v5e(step):
    """Nine mixers' scans as the pallas kernels at 64 heads, ONE group and
    chunk 256 (8 heads a grid step), splash at GQA 32 / 8 of 64, the tied
    head chunked, in the memory of one chip; the kernels take the
    documents' marks and splash's `SegmentIds`, and the boundary work
    outside them stands under `segments`. (Without `segment_ids` none of
    it is traced: `tests/test_packed_documents.py` holds the kernels'
    operands, `tests/test_accepted_programs.py` the accepted cells'
    texts.)"""
    from ray_tpu.models import Transformer
    from ray_tpu.ops.ssm import ssd_scan_impl

    cfg, mesh, lower = step
    assert Transformer.resolve_attention_impl(cfg, mesh, SEQ) == "flash"
    assert ssd_scan_impl(mesh, SEQ, cfg.ssm_heads, cfg.ssm_head_dim,
                         cfg.ssm_groups, cfg.ssm_state,
                         cfg.ssm_chunk) == "pallas"
    compiled = lower()
    ma = compiled.memory_analysis()
    held = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert 12.0e9 < held < 16.9e9, held / 1e9
    hlo = compiled.as_text()
    kernels = kernels_of(hlo)
    names = [name for name, _, _ in kernels]
    # three runs of layers (n x 5, l, n x 4), two of them of mixers: the
    # scan's forward in the forward and in remat's forward, its backward
    # once
    assert cfg.pattern_runs == [("n", 5), ("l", 1), ("n", 4)]
    assert sum(n.startswith("ssd_scan_fwd") for n in names) == 4, names
    assert sum(n.startswith("ssd_scan_bwd") for n in names) == 2, names
    assert all("ssm/scan" in op for n, _, op in kernels
               if n.startswith("ssd_scan")), kernels
    assert sum(n.startswith("splash_mha_fwd_segmented_residuals")
               for n in names) == 1, names
    assert sum(n.startswith("splash_mha_dkv_segmented_no_residuals")
               for n in names) == 1, names
    # the marks [B, 8, T] reach the scan kernels
    assert f"f32[1,8,{SEQ}]" in hlo
    assert re.search(r'op_name="[^"]*/segments/', hlo)
    # nothing under the scan is as large as one [T/Q, H, Q, Q] block
    block = SEQ * cfg.ssm_chunk * cfg.ssm_heads
    for shape, op in re.findall(
            r'= \w+\[([\d,]+)\][^\n]*op_name="([^"]*ssm/scan[^"]*)"', hlo):
        size = 1
        for dim in shape.split(","):
            size *= int(dim)
        assert size < block, (shape, op)
    for scope in ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                  "ssm/out_proj", "ssm_norm", "mlp/gate_up", "mlp/down",
                  "attention", "qkv", "attn_out", "head", "loss"):
        assert re.search(r'op_name="[^"]*[/(]' + scope + r'[/)]', hlo), scope
