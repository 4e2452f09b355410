"""The Xing4.0-29B-A4B step compiled for a described v5e (PR 66): a file
of its own, so that it runs beside the other step files on another worker
(the fixture stays in `tests/test_chip_compile.py`). Since PR 67 the
mixing of the streams is `ops/mhc.enter` / `leave`: four pallas kernels
under the `mhc/*` scopes, the stream flat and in bf16 wherever it is
written, the product with phi kept for the backward."""

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
    its sublayer, the carry a layer flat, `[1, 8192, 14336]` in bf16
    (until PR 67 `[1, 8192, 4, 3584]`, which XLA laid out tokens-minor:
    the kernels tile whole token rows), no `[tokens, 4, 4]` array of the
    maps, and the compiler's memory report under the chip's 16.91. Since
    PR 67: the mixing's four kernels, each under an `mhc/*` scope in the
    forward, under remat and in the backward; no float32 array of the
    stream's shape; no product with phi under remat."""
    import re

    import optax

    from benchlib.spec import load_json, load_module
    from ray_tpu.models import Transformer
    from ray_tpu.ops import mhc
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
        "gmm", "mhc_enter_bwd", "mhc_enter_fwd", "mhc_leave_bwd",
        "mhc_leave_fwd", "splash_mha_dkv_no_residuals",
        "splash_mha_fwd_residuals", "tgmm"], names
    # the mixing's kernels (`ops/mhc.stream_mix_impl` said "pallas"): a
    # scan's body is one layer of two sublayers, and the step has two
    # scans each way (the dense run's and the expert run's). Forward:
    # `enter`'s product and `leave`, twice a body. Backward: both backward
    # kernels twice a body, and under remat `leave`'s forward ONCE (the
    # second sublayer's X' is the next layer's carry, which is kept) and
    # `enter`'s forward kernel never: the layer's remat keeps m and r
    # (`mhc.MAPS_RESIDUALS`). Every one under the scope the readers book
    # it by (`benchlib/mhc_reduce.py`): `enter`'s under `mhc/maps`,
    # `leave`'s under `mhc/post` inside the scope that closes the sublayer
    assert mhc.stream_mix_impl(mesh, seq, 4, 3584, jnp.bfloat16) == "pallas"
    calls = {}
    for name, op in kernels:
        if not name.startswith("mhc_"):
            continue
        phase = "remat" if "rematted_computation" in op \
            else "backward" if "transpose(" in op else "forward"
        kernel = re.sub(r"\.\d+$", "", name)
        calls[kernel, phase] = calls.get((kernel, phase), 0) + 1
        scope = "mhc/maps" if "enter" in kernel else "mhc/post"
        assert re.search(rf"[/(]{scope}[/)]", op), (name, op)
        if "leave" in kernel:
            assert re.search(r"(attn_out|mlp/down|moe/combine)\)?/mhc/post",
                             op), (name, op)
    assert calls == {("mhc_enter_fwd", "forward"): 4,
                     ("mhc_leave_fwd", "forward"): 4,
                     ("mhc_leave_fwd", "remat"): 2,
                     ("mhc_enter_bwd", "backward"): 4,
                     ("mhc_leave_bwd", "backward"): 4}, calls
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
                   "rematted_computation/mhc/maps",
                   "rematted_computation/mhc/maps/mhc/pre"):
        assert inside in hlo, inside
    # until PR 67 remat's forward made the product with phi again (a
    # `dot_general` under `rematted_computation/mhc/maps`); now m and r
    # are kept, what is left there are the rounds on the kept m and the
    # read of `h`; the product itself is inside `mhc_enter_fwd`, and the
    # backward's two (`(dm r) phi^T`, `dphi`) inside `mhc_enter_bwd`
    op_names = re.findall(r'op_name="([^"]+)"', hlo)
    assert [n for n in op_names if "rematted_computation/mhc/maps" in n
            and n.endswith("/div")]
    assert not [n for n in op_names if "mhc/maps" in n
                and "dot_general" in n]
    # the carry a layer, flat and unpadded: two bytes a token and column
    assert re.search(r"bf16\[4,1,8192,14336\]", hlo)
    # nowhere the stream in float32 (the parent's backward held `[8192, 4,
    # 3584]` f32 transients, 470 MB) and nowhere as `[.., 4, 3584]`, the
    # shape XLA lays out tokens-minor or pads (a relayout at every
    # kernel's door; the MoE's `[8192 tokens, 4 chosen, 3584]` is not the
    # stream). Arrays, that is: what an instruction outside a fusion's
    # body gives (inside one a value is registers: the entry's
    # concatenate and the exit's backward pass through float32 there)
    written, fused = [], False
    for line in hlo.splitlines():
        if line.endswith("{") and "->" in line:
            fused = "fused_computation" in line.split("(")[0]
        elif not fused:
            written.append(line)
    assert not re.search(r"= f32\[(\d+,)*8192,14336\]", "\n".join(written))
    assert not re.search(r"\[(\d+,)*1,8192,4,3584\]", hlo)
    # the maps live with the tokens last: no [.., 8192, 4, 4] array
    assert not re.search(r"f32\[(1,)?8192,4,4\]", hlo)
    ma = compiled.memory_analysis()
    # 12 B a parameter resident (and the choice bias, a buffer)
    assert abs(ma.argument_size_in_bytes - 670_872_590 * 12) < 1e6
    # 10.82 GB where the compiler's own usage report reads 15.39 GB
    # (`XLA_FLAGS=--xla_dump_to`, `Total bytes used`); PR 66's step read
    # 11.07 and 15.44, and the chip `peak_hbm_gb` 15.435 of 16.91. (With
    # the kernels' records handed over by token, `[B*T, rows]`, XLA laid
    # the maps' own arithmetic out rows-minor and the report read 15.80.)
    assert ma.temp_size_in_bytes < 10.9e9, ma.temp_size_in_bytes
