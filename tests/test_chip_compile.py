"""Main-path kernels and steps compiled for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2): what the chip's
compiler refuses is found here, at no chip time. A compile is not a run."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, with the persistent compile cache off:
    an entry written for a described chip cannot be read back without
    one, and the next compile would warn about it."""
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
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_auto_attention_resolves_flash_at_the_cells_shapes(v5e):
    """What the benchmark's three cells say (`attention_impl="auto"` on a
    TPU device at 4096 tokens) still resolves to the name "flash"."""
    from ray_tpu.models import OLMOE_1B_7B, Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    mistral = TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=2, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=4096, attention_impl="auto")
    for cfg in (mistral, mistral.replace(n_layers=8),
                OLMOE_1B_7B.replace(n_layers=1, max_seq_len=4096,
                                    attention_impl="auto")):
        assert cfg.head_dim == 128
        assert Transformer.resolve_attention_impl(cfg, mesh) == "flash"
        # a sequence the kernel cannot tile, or no TPU: dense, not an error
        assert Transformer.resolve_attention_impl(
            cfg, mesh, seq_len=4000) == "dense"
        assert Transformer.resolve_attention_impl(cfg) == "dense"


def test_impala_learner_update_at_the_minipong_shape(v5e):
    from ray_tpu.rllib.algorithms.impala import ImpalaConfig
    from ray_tpu.rllib.algorithms.impala.impala import ImpalaLearner
    from ray_tpu.rllib.core.catalog import default_module_for
    from ray_tpu.rllib.env.base import make_env

    env = make_env("MiniPong-v0", {})
    assert env.observation_space.shape == (84, 84, 4)
    config = ImpalaConfig().training(train_batch_size=256, lr=6e-4)
    learner = ImpalaLearner(
        default_module_for(env.observation_space, env.action_space,
                           config.model_hiddens), config)
    learner.build(seed=0)

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e))

    def like(tree):
        return jax.tree.map(
            lambda x: described(jnp.shape(x), jnp.result_type(x)), tree)

    t, b = 32, 8  # fragment 32 x (2 runners x 4 envs) = batch 256
    batch = {
        "obs": described((t, b, 84, 84, 4), jnp.uint8),
        "actions": described((t, b), jnp.int32),
        "rewards": described((t, b), jnp.float32),
        "dones": described((t, b), jnp.bool_),
        "behaviour_logp": described((t, b), jnp.float32),
        "bootstrap_value": described((b,), jnp.float32),
    }
    compiled = learner._update_fn.lower(
        like(learner._params), like(learner._opt_state), batch,
        learner.extra_inputs()).compile()
    used = compiled.memory_analysis()
    assert used.temp_size_in_bytes + used.argument_size_in_bytes < 16e9
    assert "convolution" in compiled.as_text()


def test_explicit_flash_on_an_unsupported_shape_raises():
    """No silent dense fallback for attention_impl="flash": TINY's
    head_dim 16 is a shape the kernel cannot tile."""
    from ray_tpu.models import TINY, Transformer

    cfg = TINY.replace(attention_impl="flash")
    params = Transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    with pytest.raises(ValueError, match="flash attention needs"):
        Transformer.apply(params, tokens, cfg)
    # "auto" off the TPU is dense, decided in one place and readable
    auto = cfg.replace(attention_impl="auto")
    assert Transformer.resolve_attention_impl(auto) == "dense"
    assert jnp.isfinite(Transformer.apply(params, tokens, auto)).all()


# what `remat=True` saves (Transformer._remat): the default policy and the
# save-nothing baseline, with the forward kernel's calls per layer scan
REMAT_POLICIES = [pytest.param({}, 1, id="default"),
                  pytest.param({"remat_policy": "full"}, 2, id="full")]


def assert_saved_residuals(hlo, policy, stacked):
    """Under the default policy the scan stacks the forward kernel's output
    `[L, B, H, T, Dv]` in the compute dtype and its logsumexp `[L, B, H, T]`
    in f32, the narrow form (a JAX that named the logsumexp before its
    slice would stack `[..., T, 128]`); under "full" neither exists."""
    import re

    layers, b, h, t, dv = stacked
    out = re.escape(f"bf16[{layers},{b},{h},{t},{dv}]")
    lse = re.escape(f"f32[{layers},{b},{h},{t}]")
    wide = re.escape(f"f32[{layers},{b},{h},{t},128]")
    saved = not policy
    assert bool(re.search(out + r"[{ ]", hlo)) == saved, out
    assert bool(re.search(lse + r"[{ ]", hlo)) == saved, lse
    assert not re.search(wide + r"[{ ]", hlo), wide


@pytest.mark.parametrize("policy,fwd_calls", REMAT_POLICIES)
def test_scopes_survive_the_v5e_compiler(v5e, policy, fwd_calls):
    """Two Mistral-width layers (one scan) and the head, the whole train
    step: after the TPU compiler's fusion the ops still carry the
    program's scope vocabulary (PERF.md section 3) in their op_name — at
    least 90% of the fusions that run as ops of their own, and every
    Pallas kernel call.
    The layer's remat keeps the forward kernel's output: one forward call
    and the one backward call, where "full" runs the forward twice."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 512   # the widths are what fusion decides on; compiles in 12 s
    cfg = TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=2, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=seq, norm_eps=1e-5,
        tie_embeddings=False, attention_impl="flash", dtype="bfloat16",
        param_dtype="float32", remat=True, loss_chunk=256, **policy)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
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
    hlo = train_step.lower(state, batch).compile().as_text()
    # splash writes its block sizes into the call as three lines of JSON:
    # back onto one line, so that an instruction is a line again
    hlo = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}", hlo)

    scopes = ("embed", "layers", "attn_norm", "qkv", "attention",
              "attn_out", "mlp_norm", "mlp/gate_up", "mlp/down",
              "final_norm", "head", "loss", "optimizer")

    def scope_in(op_name):
        return [s for s in scopes
                if re.search(r"[/(]" + s + r"[/)]", op_name + "/")]

    # an instruction inside a computation that a fusion calls is part of
    # that fusion, not an op that runs (and shows in a trace) by itself
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", hlo))
    kernels, fusions, computation = {}, [], None   # kernels: name -> op_name
    for line in hlo.splitlines():
        header = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if header:
            computation = header.group(1)
        if computation in fused:
            continue
        found = re.search(r'op_name="([^"]*)"', line)
        op_name = found.group(1) if found else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels[re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)[1]] = \
                op_name
        elif " fusion(" in line:
            fusions.append(op_name)
    # the forward (under "full" remat's too) and the one backward call
    # (dK, dV, dQ), and no other kernel
    assert sum(n.startswith("splash_mha_fwd") for n in kernels) == \
        fwd_calls, kernels
    assert sum(n.startswith("splash_mha_dkv") for n in kernels) == 1, kernels
    assert len(kernels) == fwd_calls + 1, kernels
    assert_saved_residuals(hlo, policy, (2, 1, 32, seq, 128))
    assert all("attention" in scope_in(k) for k in kernels.values()), kernels
    kept = [f for f in fusions if scope_in(f)]
    share = len(kept) / len(fusions)
    print(f"fusions with a vocabulary scope: {len(kept)} of {len(fusions)} "
          f"({100 * share:.1f}%); the others: "
          f"{sorted({f for f in fusions if not scope_in(f)})}")
    assert share >= 0.9
    seen = {s for op_name in kept + list(kernels.values())
            for s in scope_in(op_name)}
    assert seen == set(scopes), sorted(set(scopes) - seen)


def test_olmoe_layer_step_compiles_with_the_grouped_matmul_kernels(v5e):
    """One OLMoE-width layer and the head, the whole train step
    (`train_olmoe_d1`'s widths, a shorter sequence): on one TPU device the
    expert FFN takes the pallas grouped matmul, forward (`gmm`) and both
    transposes (`gmm`, `tgmm`); every kernel and the fusions of the expert
    layer keep the `moe/*` sub-scopes after the v5e compiler; and no
    buffer of the program is a `[tokens, experts, anything]` one-hot."""
    import re

    import optax

    from ray_tpu.models import OLMOE_1B_7B, Transformer
    from ray_tpu.ops.moe import grouped_matmul_impl
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq, rows = 512, 3     # 1,536 tokens: a size no width of the model has
    cfg = OLMOE_1B_7B.replace(n_layers=1, max_seq_len=seq,
                              attention_impl="flash", loss_chunk=256)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    slots = rows * seq * cfg.moe_top_k
    assert grouped_matmul_impl(mesh, slots, cfg.d_model, cfg.ff_dim) == \
        "megablox"
    assert grouped_matmul_impl(None, slots, cfg.d_model, cfg.ff_dim) == \
        "ragged_dot"          # this process's own devices are CPUs
    assert grouped_matmul_impl(mesh, slots + 8, cfg.d_model,
                               cfg.ff_dim) == "ragged_dot"
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
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)}
    hlo = train_step.lower(state, batch).compile().as_text()

    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', hlo)
    grouped = [(name, op) for name, op in kernels
               if re.match(r"t?gmm(\.\d+)?$", name)]
    # gate/up and down: forward, remat's forward, d lhs (gmm); d rhs (tgmm)
    assert sum(n.startswith("gmm") for n, _ in grouped) == 6, kernels
    assert sum(n.startswith("tgmm") for n, _ in grouped) == 2, kernels
    assert all("moe/experts" in op for _, op in grouped), grouped
    for sub in ("moe/router", "moe/dispatch", "moe/experts", "moe/combine"):
        assert re.search(r'op_name="[^"]*[/(]' + sub + r'[/)]', hlo), sub
    # nothing has a tokens-sized and an experts-sized dimension and a third
    tokens = rows * seq
    shapes = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"\w+\[([\d,]+)\]", hlo)}
    assert (tokens, cfg.moe_experts) in shapes           # the router's own
    bad = [s for s in shapes if cfg.moe_experts in s and len(s) > 2
           and (tokens in s or slots in s)]
    assert not bad, bad


# the last: Granite 4.0-H's mixer, 64 heads of 64 in ONE group at chunk 256
# (8 heads a grid step: sixteen overran the scoped VMEM in the backward)
SCAN_CALLS = [(1, 8192, 32, 64, 2, 128, 128), (2, 1024, 128, 64, 8, 128, 128),
              (1, 512, 8, 128, 1, 128, 256), (1, 128, 8, 64, 1, 128, 128),
              (1, 8192, 64, 64, 1, 128, 256)]
SCAN_CASES = [call + (False,) for call in SCAN_CALLS] + [
    call + (True,) for call in (SCAN_CALLS[0], SCAN_CALLS[4])]


@pytest.mark.parametrize(
    "b,t,h,p,g,n,q,packed", SCAN_CASES,
    ids=["x".join(map(str, call[:-1])) + "-packed" * call[-1]
         for call in SCAN_CASES])
def test_scan_kernels_fwd_bwd(v5e, b, t, h, p, g, n, q, packed):
    """Forward and backward of `ops/ssm.ssd_scan_pallas` alone for the v5e
    compiler: one `ssd_scan_fwd` and one `ssd_scan_bwd` call, x and dy
    reaching them in the convolution's `[B, T, H·P]` layout (no four-way
    copy of either around the calls), the entering states `[T/Q, N, H·P]`
    float32 the only residual the forward writes, and `scan_shape_ok` said
    yes to what compiled. `packed`: with `segment_ids`, whose marks
    `[B, 8, T]` float32 are one operand more of both calls."""
    import re

    from ray_tpu.ops.ssm import scan_shape_ok, ssd_scan_pallas

    assert scan_shape_ok(t, h, p, g, n, q)
    chip = SingleDeviceSharding(v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, dt, a, bm, cm, *ids):
        return ssd_scan_pallas(x, dt, a, bm, cm, q, g,
                               segment_ids=ids[0] if ids else None).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((b, t, h * p), jnp.bfloat16), arg((b, t, h), jnp.float32),
        arg((h,), jnp.float32), arg((b, t, g * n), jnp.bfloat16),
        arg((b, t, g * n), jnp.bfloat16),
        *([arg((b, t), jnp.int32)] * packed)).compile().as_text()
    assert (f"f32[{b},8,{t}]" in hlo) == packed
    calls = {re.search(r"ssd_scan_(fwd|bwd)", name).group(0): (out, operands)
             for name, out, operands in re.findall(
                 r'%([\w.\-]+) = (\([^\n]*?\)) custom-call\(([^\n]*?)\), '
                 r'custom_call_target="tpu_custom_call"', hlo)}
    assert sorted(calls) == ["ssd_scan_bwd", "ssd_scan_fwd"], sorted(calls)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    fwd_out, fwd_in = calls["ssd_scan_fwd"]
    bwd_out, _ = calls["ssd_scan_bwd"]
    assert f"f32[{b},{t},{h * p}]" in fwd_out
    assert f"f32[{b},{t // q},{n},{h * p}]" in fwd_out
    assert f"bf16[{b},{t},{h * p}]" in bwd_out
    # x, B and C as the program's arguments hold them: no copy before
    assert fwd_in.startswith("%x.1, %bm.1, %cm.1, "), fwd_in
    assert not re.search(rf"\[{b},{t},{h},{p}\]", hlo)


# [B, T, channels, state]: the Phi-4-mini-flash cell's mixer, and a
# smaller one in two batch rows whose channels are one block
SCAN1_CALLS = [(1, 16384, 5120, 16), (2, 512, 384, 16)]


@pytest.mark.parametrize(
    "b,t,ch,n", SCAN1_CALLS,
    ids=["x".join(map(str, call)) for call in SCAN1_CALLS])
def test_mamba1_scan_kernels_fwd_bwd(v5e, b, t, ch, n):
    """Forward and backward of `ops/ssm.selective_scan_pallas` alone for
    the v5e compiler: one `selective_scan_fwd` and one `selective_scan_bwd`
    call, x, dt and dy reaching them in the `[B, T, C]` layout they arrive
    in, `y + D x` and dx leaving in the compute dtype, the entering states
    `[T/Q, N, C]` float32 the only residual the forward writes, and
    `scan1_shape_ok` said yes to what compiled."""
    import re

    from ray_tpu.ops.ssm import (SCAN1_STEPS, scan1_shape_ok,
                                 selective_scan_pallas)

    assert scan1_shape_ok(t, ch, n, 128)
    chip = SingleDeviceSharding(v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, dt, a, bm, cm, d):
        return selective_scan_pallas(x, dt, a, bm, cm, d, 128).astype(
            jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg((b, t, ch), jnp.bfloat16), arg((b, t, ch), jnp.float32),
        arg((ch, n), jnp.float32), arg((b, t, n), jnp.float32),
        arg((b, t, n), jnp.float32), arg((ch,), jnp.float32)
    ).compile().as_text()
    calls = {re.search(r"selective_scan_(fwd|bwd)", name).group(0):
             (out, operands) for name, out, operands in re.findall(
                 r'%([\w.\-]+) = (\([^\n]*?\)) custom-call\(([^\n]*?)\), '
                 r'custom_call_target="tpu_custom_call"', hlo)}
    assert sorted(calls) == ["selective_scan_bwd", "selective_scan_fwd"], \
        sorted(calls)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    fwd_out, fwd_in = calls["selective_scan_fwd"]
    bwd_out, bwd_in = calls["selective_scan_bwd"]
    assert f"bf16[{b},{t},{ch}]" in fwd_out
    assert f"f32[{b},{t // SCAN1_STEPS},{n},{ch}]" in fwd_out
    assert f"bf16[{b},{t},{ch}]" in bwd_out and f"f32[{b},{t},{ch}]" in bwd_out
    # x and dt as the program's arguments hold them (small ones the
    # compiler prefetches, a `copy-start` / `copy-done`): no other layout
    ours = r"%(x\.1|copy-done[.\d]*), %(dt\.1|copy-done[.\d]*), "
    assert re.match(ours, fwd_in) and re.match(ours, bwd_in), (fwd_in, bwd_in)
    assert not re.search(rf"\[{b},{t},{ch}\]\S* (copy|transpose)\(", hlo)
    # no state of every step anywhere: nothing has T, N and C at once
    assert not re.search(rf"\[({b},)?{t},({n},{ch}|{ch},{n})\]", hlo)


# [B, T, heads]: the Ling cell's call (8 heads of 128, chunk 64), and a
# small one in two batch rows and one block of heads
KDA_CALLS = [(1, 16384, 8), (2, 256, 4)]


@pytest.mark.parametrize(
    "b,t,h", KDA_CALLS, ids=["x".join(map(str, call)) for call in KDA_CALLS])
def test_kda_kernels_fwd_bwd(v5e, b, t, h):
    """Forward and backward of `ops/kda.gated_delta_rule_pallas` alone for
    the v5e compiler: one `kda_delta_fwd` and one `kda_delta_bwd` call, q,
    k, v and g reaching them as the program's arguments hold them, in the
    `[B, T, H·D]` layout of the convolution and the gates (no head-major
    copy before the call), dq, dk, dv leaving in the compute dtype, the
    entering states `[T/64, H·Dv, D]` and the chunks' inverses
    `[T/64, H/2·64, 128]`, float32, all the forward writes beside o and
    both read by the backward (PR 65), nothing of a sub-block's factors
    (`[.., 4, 64, 128]`) in the compiled text, and `delta_shape_ok` said
    yes to what compiled."""
    import re

    from ray_tpu.ops.kda import (DELTA_CHUNK, delta_shape_ok,
                                 gated_delta_rule_pallas)

    d, c = 128, DELTA_CHUNK
    assert delta_shape_ok(t, h, d, d, c)
    chip = SingleDeviceSharding(v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(q, k, v, g, beta):
        return gated_delta_rule_pallas(q, k, v, g, beta, chunk=c).sum()

    wide = (b, t, h * d)
    hlo = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        arg(wide, jnp.bfloat16), arg(wide, jnp.bfloat16),
        arg(wide, jnp.bfloat16), arg(wide, jnp.float32),
        arg((b, t, h), jnp.float32)).compile().as_text()
    calls = {re.search(r"kda_delta_(fwd|bwd)", name).group(0):
             (out, operands) for name, out, operands in re.findall(
                 r'%([\w.\-]+) = (\([^\n]*?\)) custom-call\(([^\n]*?)\), '
                 r'custom_call_target="tpu_custom_call"', hlo)}
    assert sorted(calls) == ["kda_delta_bwd", "kda_delta_fwd"], sorted(calls)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    fwd_out, fwd_in = calls["kda_delta_fwd"]
    bwd_out, bwd_in = calls["kda_delta_bwd"]
    states = f"f32[{b},{t // c},{h * d},{d}]"
    inverses = f"f32[{b},{t // c},{h // 2 * c},{2 * c}]"
    assert f"f32[{b},{t},{h * d}]" in fwd_out and states in fwd_out
    assert inverses in fwd_out
    assert fwd_out.count("[") == 3, fwd_out    # o, the states, the inverses
    # the backward's operands: the forward's five, the two it kept, do
    assert bwd_in.count("%") == 8 and fwd_in.count("%") == 5, bwd_in
    assert bwd_out.count(f"bf16[{b},{t},{h * d}]") == 3, bwd_out
    assert bwd_out.count(f"f32[{b},{t},{h * d}]") == 1, bwd_out
    # q, k, v, g as the program's arguments hold them (small ones the
    # compiler prefetches, a `copy-start` / `copy-done`): no other layout
    ours = "".join(rf"%({name}\.1|copy-done[.\d]*), " for name in "qkvg")
    assert re.match(ours, fwd_in) and re.match(ours, bwd_in), (fwd_in, bwd_in)
    assert not re.search(rf"\[{b},{t},{h * d}\]\S* (copy|transpose)\(", hlo)
    assert not re.search(rf"\[{b},{h},{t // c},{c},{d}\]", hlo)
    # a sub-block's factors never reach HBM
    assert not re.search(r"f32\[[\d,]*4,64,128\]", hlo)


@pytest.mark.parametrize("window", [0, 512], ids=["causal", "window512"])
def test_masked_differential_kernels_fwd_bwd(v5e, window):
    """The call differential attention makes at Phi-4-mini-flash's widths
    (40 query heads of 64 in the order key pair, map, query pair over 20
    key heads, the 10 value heads of 128 repeated for their two maps)
    under the causal mask and under a 512 window, forward and backward for
    the v5e compiler: one forward call and one that makes dK, dV and dQ,
    V reaching both 128 wide."""
    import re

    from ray_tpu.ops.attention import flash_attention

    t = 4096
    chip = SingleDeviceSharding(v5e)
    q = jax.ShapeDtypeStruct((1, t, 40, 64), jnp.bfloat16, sharding=chip)
    k = jax.ShapeDtypeStruct((1, t, 20, 64), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((1, t, 20, 128), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, window=window).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile()
    hlo = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert sorted(re.sub(r"\.\d+$", "", n) for n in names) == [
        "splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"], names
    # V and dV at their width, 20 heads; the maps' outputs at 40
    assert re.search(rf"bf16\[(1,)?20,{t},128\]", hlo)
    assert re.search(rf"bf16\[(1,)?40,{t},128\]", hlo)
