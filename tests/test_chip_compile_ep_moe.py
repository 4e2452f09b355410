"""The expert-parallel step compiled for the FOUR described devices of a
v5e host (`tests/test_chip_compile.py`'s way, in a file of its own so that
the two run on different workers): what the chip's compiler refuses, and
whether one sequence a chip fits, found here at no chip time."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def v5e_host():
    """The four described devices of one v5e host (a 2x2), with the
    persistent compile cache off (`tests/test_chip_compile.v5e`'s
    reason)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_expert_parallel_step_compiles_and_fits_the_v5e_host(v5e_host):
    """Mellum2-12B-A2.5B's layers 0-3 at the published widths (three
    window-1024 layers to one full layer under YaRN, GQA 32 / 4 heads of
    128 with the per-head QK-norm, every layer 64 experts of 896, top-8)
    with the whole vocabulary, as one train step of 4 x 8,192 tokens for
    the FOUR described devices of a v5e host (the benchmark's
    `train_mellum2_ep4_d4`): the experts 16 a device behind the exchange
    (`ops/moe._exchange_ffn`: the rows through `ragged-all-to-all`, the
    counts through all-to-alls in the compiled step, `megablox` inside the
    `shard_map` over the 131,072 rows of the receive buffer and no pass
    behind its kernels),
    everything else sharded four ways and gathered for use, splash under
    a window and under the causal mask by their scopes, and the compile's
    memory report: whether one sequence a chip fits, at no chip time."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.moe import exchange_bound, exchange_impl, gmm_tiles
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import make_train_step

    seq = 8192
    cfg = TransformerConfig(
        vocab_size=98304, d_model=2304, n_layers=4, n_heads=32,
        n_kv_heads=4, attn_head_dim=128, d_ff=896, max_seq_len=seq,
        rope_theta=5e5, norm_eps=1e-6, qk_norm=True, qk_norm_per_head=True,
        moe_experts=64, moe_top_k=8, moe_norm_topk=True, moe_aux_coeff=0.0,
        layer_pattern="WWWL", attn_window=1024, rope_yarn_factor=16.0,
        rope_yarn_original_len=8192, attention_impl="auto",
        dtype="bfloat16", param_dtype="float32", remat=True, loss_chunk=256)
    assert cfg.num_params == 2_123_977_984
    mesh = make_mesh(MeshConfig(data=1, fsdp=4), devices=v5e_host)
    rules = ShardingRules().replace(expert="fsdp", expert_embed=None)
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    bucket = exchange_bound(seq * 8, 4)
    assert bucket == 32768
    assert gmm_tiles(4 * bucket, 2304, 2 * 896) == (512, 1152, 896)
    assert gmm_tiles(4 * bucket, 896, 2304) == (512, 896, 1152)
    optimizer = optax.adamw(1e-5, weight_decay=0.01)
    _, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh, rules=rules,
                                      with_metrics=True),
        Transformer.param_specs(cfg), mesh, rules=rules,
        optimizer=optimizer)

    def init(key):
        params = Transformer.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, seq + 1), jnp.int32)}
    compiled = train_step.lower(state, batch).compile()
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                 compiled.as_text())
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    names = sorted(re.sub(r"\.\d+$", "", n) for n, _ in kernels
                   if re.match(r"t?gmm|splash", n))
    # two scans (3 x W, 1 x L), each: per matmul the forward, remat's and
    # the transpose for the rows, one for the weights; splash once each way
    assert names == ["gmm"] * 12 + ["splash_mha_dkv_no_residuals"] * 2 \
        + ["splash_mha_fwd_residuals"] * 2 + ["tgmm"] * 4, names
    assert all("moe/experts" in op for n, op in kernels
               if re.match(r"t?gmm", n))
    # the grouped matmuls are handed the held experts' groups and nothing
    # else, so `megablox` follows no kernel with its zeroing select over
    # the receive buffer's static 131,072 rows (PR 64: five passes a layer
    # of `[131072, 1792]` / `[131072, 2304]` and a sixth fused into a
    # neighbour, `moe/experts/jit(gmm)/jit(_where)/select_n`). The selects
    # over those rows that stay are the dense fallback's fills
    whole_selects = [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in hlo.splitlines()
        if re.search(r"= bf16\[131072,\d+\]\S* select\(", line)]
    assert whole_selects and not [
        op for op in whole_selects if "moe/experts" in op], whole_selects
    assert all("branch_0_fun" in op and "jit(_take)" in op
               for op in whole_selects), whole_selects
    splash = sorted({re.search(r"attention/(\w+)", op).group(1)
                     for n, op in kernels if "splash" in n})
    assert splash == ["full", "window"], splash
    # the rows travel ragged (`ops/moe.exchange_impl`: these are TPUs), out
    # and back in the forward, in remat's forward and in the backward of
    # each scan; the counts, the offsets and the rounds that take any load
    # through dense all-to-alls
    assert exchange_impl(mesh) == "ragged"
    ragged = [line for line in hlo.splitlines()
              if re.search(r" ragged-all-to-all(-start)?\(", line)]
    assert len(ragged) == 12, len(ragged)
    exchanges = [line for line in hlo.splitlines()
                 if re.search(r" all-to-all(-start)?\(", line)]
    assert exchanges and all("moe/exchange" in line
                             for line in exchanges + ragged)
    for scope in ("rope/plain", "rope/yarn", "qkv/qk_norm", "moe/router",
                  "moe/dispatch", "moe/exchange", "moe/experts",
                  "moe/combine", "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    # a device's share of the state (params and adamw's moments: 12 B a
    # parameter over four devices) and the step's peak within the chip's
    # 16.9 GB: one sequence a chip fits (15.45 GB when this was written;
    # 15.44 with the rows sent ragged, PR 58)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == pytest.approx(
        12 * cfg.num_params / 4, rel=1e-3)
    assert ma.peak_memory_in_bytes < 15.8e9, ma.peak_memory_in_bytes
