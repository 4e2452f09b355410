"""The hybrid share step (Nemotron-3-Super's widths) compiled for a
described v5e: a file of its own since PR 61 (a minute or more of one
worker; `--dist loadfile` runs it beside `test_chip_compile_shares.py`, the
latent-attention share). The fixture and the helpers stay in
`tests/test_chip_compile.py` and `tests/test_chip_compile_shares.py`."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from test_chip_compile import v5e  # noqa: F401
from test_chip_compile_shares import (  # noqa: F401
    assert_chosen_scores_read_off_the_selection)


def test_hybrid_share_step_compiles_for_the_v5e(v5e):
    """Two `ME` blocks and one `M*E` block of Nemotron-3-Super's widths as
    one chip holds them (32 mixer heads of 64 in 2 groups of state 128, 8
    query heads over 1 key/value head of 128 with no rotary embedding, 8
    of 512 `relu^2` experts of width 2688 in a latent of 1024, top-22, a
    shared expert of 5376) + head, as one train step for the v5e: the
    chunked scan as its pallas kernels (`ops/ssm.ssd_scan_impl` says
    `"pallas"` for the described device: under `ssm/scan` the forward
    kernel in the forward and in remat's forward, the backward kernel in
    the backward, and no `[T/Q, H, Q, Q]` block left there), splash's
    kernels at GQA 8 / 1, `megablox` with tiles from each call's shapes
    (2688 = 3 x 896), past the sort tokens x min(22, 8) rows and never
    tokens x 22, and the new scopes on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.moe import gmm_tiles, grouped_matmul_impl, row_bound
    from ray_tpu.ops.ssm import ssd_scan_impl
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq, rows = 1024, 1
    cfg = TransformerConfig(
        vocab_size=16384, d_model=4096, n_layers=7,
        layer_pattern="MEMEM*E", n_heads=8, n_kv_heads=1, attn_head_dim=128,
        rope=False, d_ff=2688, max_seq_len=seq, ssm_heads=32,
        ssm_head_dim=64, ssm_groups=2, ssm_state=128, ssm_chunk=128,
        moe_experts=512, moe_top_k=22, moe_scoring="sigmoid",
        moe_routed_scale=5.0, moe_shared_experts=1, moe_shared_ff=5376,
        moe_latent=1024, moe_act="relu2", moe_gated=False,
        moe_experts_held=8, moe_aux_coeff=0.0, attention_impl="auto",
        dtype="bfloat16", param_dtype="float32", remat=True, loss_chunk=256)
    assert cfg.pattern_runs == [("ME", 2), ("M*E", 1)]
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    held_rows = rows * seq * 8
    assert gmm_tiles(held_rows, 1024, 2688) == (512, 1024, 896)
    assert gmm_tiles(held_rows, 2688, 1024) == (512, 896, 1024)
    assert grouped_matmul_impl(mesh, held_rows, cfg.moe_latent, cfg.ff_dim,
                               gated=False) == "megablox"
    scan_shape = (seq, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state, cfg.ssm_chunk)
    assert ssd_scan_impl(mesh, *scan_shape) == "pallas"
    assert ssd_scan_impl(None, *scan_shape) == "xla"            # the CPU
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
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)}
    hlo = train_step.lower(state, batch).compile().as_text()
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}", hlo)

    kernels = re.findall(
        r'%([\w.\-]+) = ([^\n]*)custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    names = [name for name, _, _ in kernels]
    grouped = [(n, op) for n, _, op in kernels
               if re.match(r"t?gmm(\.\d+)?$", n)]
    # two scans with an expert sublayer each: per matmul the forward,
    # remat's forward and the transpose for the rows; one for the weights:
    # over `row_bound`'s run of 1,024 rows. The path over all 8,192 is the
    # other branch of a `cond` a pass and scan, on `ragged_dot`
    assert sum(n.startswith("gmm") for n, _ in grouped) == 12, names
    assert sum(n.startswith("tgmm") for n, _ in grouped) == 4, names
    assert row_bound(rows * seq, 22, 8, 512, held_rows) == 1024
    assert len(re.findall(r" conditional\(", hlo)) == 6
    assert all("moe/experts" in op for _, op in grouped), grouped
    assert sum(n.startswith("splash_mha_fwd") for n in names) == 1, names
    assert sum(n.startswith("splash_mha_dkv") for n in names) == 1, names
    # two scans with a mixer each: the scan's forward kernel in the
    # forward and in remat's forward, its backward kernel in the backward
    scan_fwd = [op for n, _, op in kernels if n.startswith("ssd_scan_fwd")]
    scan_bwd = [op for n, _, op in kernels if n.startswith("ssd_scan_bwd")]
    assert len(scan_fwd) == 4 and len(scan_bwd) == 2, names
    assert all("ssm/scan" in op for op in scan_fwd + scan_bwd), kernels
    assert sorted(("rematted_computation" in op, "transpose(jvp" in op)
                  for op in scan_fwd) == [(False, False)] * 2 + \
        [(True, True)] * 2, scan_fwd
    assert all("transpose(jvp" in op and "rematted_computation" not in op
               for op in scan_bwd), scan_bwd
    # what the kernel keeps in VMEM: nothing under the scope is as large
    # as one [T/Q, H, Q, Q] block of decays or weights (the XLA path's
    # temporaries), whatever its layout
    block = seq * cfg.ssm_chunk * cfg.ssm_heads
    for shape, op in re.findall(
            r'= \w+\[([\d,]+)\][^\n]*op_name="([^"]*ssm/scan[^"]*)"', hlo):
        size = 1
        for dim in shape.split(","):
            size *= int(dim)
        assert size < block, (shape, op)
    # the weights of the 8 held experts reach the kernels, never 512
    assert re.search(r"bf16\[8,1024,2688\]", hlo)
    assert re.search(r"bf16\[8,2688,1024\]", hlo)
    assert not re.search(r"\[512,(1024|2688),", hlo)
    # past the sort: tokens x 8 rows of the latent's and the experts'
    # widths, nothing of tokens x 22 rows that wide
    assert re.search(rf"bf16\[{held_rows},1024\]", hlo)
    assert re.search(rf"bf16\[{held_rows},2688\]", hlo)
    assert not re.search(rf"\[{rows * seq * 22},(1024|2688|4096)\]", hlo)
    assert_chosen_scores_read_off_the_selection(hlo, rows * seq, 22, 512)
    for scope in ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                  "ssm/out_proj", "ssm_norm", "moe/latent", "moe/shared",
                  "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
                  "attention", "qkv", "attn_out"):
        assert re.search(r'op_name="[^"]*[/(]' + scope + r'[/)]', hlo), scope
