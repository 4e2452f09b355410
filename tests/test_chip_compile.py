"""Main-path kernels and steps compiled for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2): what the chip's
compiler refuses is found here, at no chip time. A compile is not a run."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
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


# [B, T, Hq, Hkv, D]: what `flash_shape_ok` says yes to, from one block of
# 128 to eight of 1024 (sixteen of 512 at head_dim 256), head_dim below, at
# and above the lane width, GQA, MHA and MQA; then the calls the benchmark's
# cells make (d2, d8 per chip, OLMoE) and GPT2_125M at batch 16
FLASH_CALLS = [(2, t, h, hkv, d)
               for t in (128, 640, 1024, 4096, 8192)
               for d in (64, 128, 256)
               for h, hkv in ((32, 8), (16, 16), (8, 1))] + [
    (4, 4096, 32, 8, 128), (2, 4096, 32, 8, 128), (4, 4096, 16, 16, 128),
    (16, 1024, 6, 6, 128)]


@pytest.mark.parametrize(
    "b,t,h,hkv,d", FLASH_CALLS,
    ids=["x".join(map(str, call)) for call in FLASH_CALLS])
def test_flash_kernels_fwd_bwd(v5e, b, t, h, hkv, d):
    """Forward and backward of `flash_attention` for the v5e compiler: the
    kernels by kind, by splash's names (one forward call, one call that
    makes dK and dV, and dQ with them); K and V reach both at their own
    head count, so no GQA repeat widened them on the way (the only
    operands at q's width are q and dO); and `flash_shape_ok` said yes to
    what compiled."""
    import re

    from ray_tpu.ops.attention import flash_attention, flash_shape_ok

    assert v5e.device_kind == "TPU v5 lite"
    assert flash_shape_ok(t, d)
    chip = SingleDeviceSharding(v5e)
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = {re.sub(r"\.\d+$", "", name): operands
             for name, operands in re.findall(
                 r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call", '
                 r'operand_layout_constraints=\{(.*?)\}\}', hlo)}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    fwd = calls["splash_mha_fwd_residuals"]
    dkv = calls["splash_mha_dkv_no_residuals"]      # dQ with them: no dq call
    # at q's width: q into the forward, q and dO into the backward, and
    # K and V only where they have q's heads
    wide, narrow = f"bf16[{b},{h},{t},{d}]", f"bf16[{b},{hkv},{t},{d}]"
    kv_wide = 2 if h == hkv else 0
    assert fwd.count(wide) == 1 + kv_wide, fwd
    assert dkv.count(wide) == 2 + kv_wide, dkv
    if h != hkv:
        assert fwd.count(narrow) == 2 and dkv.count(narrow) == 2, (fwd, dkv)


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


def assert_chosen_scores_read_off_the_selection(hlo, tokens, picked,
                                                experts):
    """A biased router's chosen scores as the v5e's compiler leaves them
    (`ops/moe._chosen_scores`): compare, select and sum are one fusion, so
    nothing `[N, k, E]` is a buffer in memory (a value of a computation
    that no fusion calls); no gather makes an `[N, k]` and none runs under
    `moe/router`; no scatter fills the `[N, E]` scores (XLA flattens that
    one to `[N·E]` and gives it no `op_name`). Returns the fusions under
    the router that hold an `[N, k, E]` value: one forward and one
    backward a scan at least, or the check read nothing."""
    import re

    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    wide = re.compile(rf"\[{tokens},({picked},{experts}|{experts},{picked})\]")
    chosen = re.compile(rf"\[({tokens},{picked}|{picked},{tokens})\]")
    scores = re.compile(rf"f32\[({tokens},{experts}|{tokens * experts})\]")
    inside, holding = None, set()
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
        if head or " = " not in line:
            continue
        shape, op = line.split(" = ", 1)[1].split(" ", 1)
        if wide.search(shape):
            assert inside in fused, line[:300]
            if "moe/router" in line:
                holding.add(inside)
        if op.startswith("gather("):
            assert not chosen.search(shape) and "moe/router" not in line, \
                line[:300]
        if op.startswith("scatter("):
            assert not scores.match(shape) and "moe/router" not in line, \
                line[:300]
    assert len(holding) >= 2, holding
    return holding


@pytest.mark.parametrize("policy,fwd_calls", REMAT_POLICIES)
def test_latent_attention_share_step_compiles_for_the_v5e(v5e, policy,
                                                          fwd_calls):
    """One dense and two expert layers of GLM-4.7-Flash's widths (latent
    attention at 20 heads of 192 + 64 / 256, 8 of 64 experts of width 1536
    held, a shared expert) + head, as one train step for the v5e: splash's
    kernels at head_dim 256, `megablox` at the width 1024 does not divide
    (tiles from each call's shapes), held weights only in the grouped
    matmuls, the run of rows a share's path is bounded to behind a `cond`,
    and the new scopes on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.moe import grouped_matmul_impl, row_bound
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq, rows = 1024, 1
    cfg = TransformerConfig(
        vocab_size=19360, d_model=2048, n_layers=3, n_heads=20, d_ff=1536,
        max_seq_len=seq, rope_theta=1e6, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        moe_experts=64, moe_top_k=4, moe_scoring="sigmoid",
        moe_routed_scale=1.8, moe_shared_experts=1, moe_dense_layers=1,
        moe_dense_ff=10240, moe_experts_held=8, moe_aux_coeff=0.0,
        attention_impl="auto", dtype="bfloat16", param_dtype="float32",
        remat=True, loss_chunk=256, **policy)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    slots = rows * seq * cfg.moe_top_k
    assert grouped_matmul_impl(mesh, slots, cfg.d_model, cfg.ff_dim) == \
        "megablox"
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
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
    grouped = [(n, text, op) for n, text, op in kernels
               if re.match(r"t?gmm(\.\d+)?$", n)]   # text: the call's type
    # the kernels run over `row_bound`'s run of 1,024 rows, not the 4,096
    # slots; the path over every row is the other branch of one `cond` a
    # pass (forward, remat's forward, backward), on `ragged_dot`
    assert sum(n.startswith("gmm") for n, _, _ in grouped) == 6, names
    assert sum(n.startswith("tgmm") for n, _, _ in grouped) == 2, names
    assert all("moe/experts" in op for _, _, op in grouped), grouped
    assert row_bound(rows * seq, cfg.moe_top_k, 8, 64, slots) == 1024
    assert {int(re.match(r"bf16\[(\d+),", text).group(1))
            for n, text, _ in grouped if n.startswith("gmm")} == {1024}
    assert len(re.findall(r" conditional\(", hlo)) == 3
    # the weights of the 8 held experts reach the kernels, never 64
    assert re.search(r"bf16\[8,2048,3072\]", hlo)
    assert re.search(r"bf16\[8,1536,2048\]", hlo)
    assert not re.search(r"\[64,(2048|1536),", hlo)
    # two scans (the dense layer, the two expert layers): the forward
    # (under "full" remat's too) and the fused backward, each
    assert sum(n.startswith("splash_mha_fwd") for n in names) == \
        2 * fwd_calls, names
    assert sum(n.startswith("splash_mha_dkv") for n in names) == 2, names
    assert_saved_residuals(hlo, policy, (2, rows, 20, seq, 256))
    assert_chosen_scores_read_off_the_selection(hlo, rows * seq, 4, 64)
    for scope in ("qkv/q_down", "qkv/kv_down", "qkv/q_up", "qkv/kv_up",
                  "qkv/assemble", "moe/shared", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "mlp/gate_up", "mlp/down"):
        assert re.search(r'op_name="[^"]*[/(]' + scope + r'[/)]', hlo), scope


# [B, T, heads, head width, groups, state, chunk]: the Nemotron cell's
# mixer as one chip holds it, the uncut model's (128 heads in 8 groups,
# two batch rows), a head that is a whole lane tile with a chunk of 256,
# and the smallest shape `scan_shape_ok` says yes to
SCAN_CALLS = [(1, 8192, 32, 64, 2, 128, 128), (2, 1024, 128, 64, 8, 128, 128),
              (1, 512, 8, 128, 1, 128, 256), (1, 128, 8, 64, 1, 128, 128)]


@pytest.mark.parametrize(
    "b,t,h,p,g,n,q", SCAN_CALLS,
    ids=["x".join(map(str, call)) for call in SCAN_CALLS])
def test_scan_kernels_fwd_bwd(v5e, b, t, h, p, g, n, q):
    """Forward and backward of `ops/ssm.ssd_scan_pallas` alone for the v5e
    compiler: one `ssd_scan_fwd` and one `ssd_scan_bwd` call, x and dy
    reaching them in the convolution's `[B, T, H·P]` layout (no four-way
    copy of either around the calls), the entering states `[T/Q, N, H·P]`
    float32 the only residual the forward writes, and `scan_shape_ok` said
    yes to what compiled."""
    import re

    from ray_tpu.ops.ssm import scan_shape_ok, ssd_scan_pallas

    assert scan_shape_ok(t, h, p, g, n, q)
    chip = SingleDeviceSharding(v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, dt, a, bm, cm):
        return ssd_scan_pallas(x, dt, a, bm, cm, q, g).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((b, t, h * p), jnp.bfloat16), arg((b, t, h), jnp.float32),
        arg((h,), jnp.float32), arg((b, t, g * n), jnp.bfloat16),
        arg((b, t, g * n), jnp.bfloat16)).compile().as_text()
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
    entering states `[T/64, H·Dv, D]` float32 the only residual the
    forward writes, nothing of a sub-block's factors (`[.., 4, 64, 128]`)
    in the compiled text, and `delta_shape_ok` said yes to what compiled."""
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
    assert f"f32[{b},{t},{h * d}]" in fwd_out and states in fwd_out
    assert fwd_out.count("[") == 2, fwd_out             # o and the states
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
    (2688 = 7 x 384), past the sort tokens x min(22, 8) rows and never
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
    assert gmm_tiles(held_rows, 1024, 2688) == (512, 1024, 384)
    assert gmm_tiles(held_rows, 2688, 1024) == (512, 384, 1024)
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


def test_kda_share_step_compiles_and_fits_the_v5e(v5e):
    """A dense, an expert and a latent-attention layer of Ling-3.0-flash's
    widths as one chip holds them (`kKL`: 8 KDA heads of 128, 8 latent-
    attention heads of 192 / 128 with no query latent and the QK-norm, a
    dense MLP of 6,144, 8 of 512 experts of 768 under the group-limited
    router, top-8 in 4 of 8 groups, a shared expert of 768) + an eighth of
    the head, as one train step of 16,384 tokens for the v5e (the
    benchmark's `train_ling3flash_ep64_d7` has four more `K` layers):
    splash takes keys 192 wide beside values 128 wide, unpadded, in blocks
    of 1,024; `megablox` over `row_bound`'s run of 4,096 rows; the delta
    rule is the pallas kernels under `kda/delta` (`kda_delta_impl` says
    "pallas" for this mesh and these shapes: per KDA layer a
    `kda_delta_fwd` in the forward, one in remat's forward and a
    `kda_delta_bwd`) and nothing there is as large as a sub-block's
    factors; the new scopes are on what the compiler leaves."""
    import re

    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.configs import TransformerConfig
    from ray_tpu.ops.attention import _splash_block_sizes
    from ray_tpu.ops.kda import kda_delta_impl
    from ray_tpu.ops.moe import gmm_tiles, grouped_matmul_impl, row_bound
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    seq = 16384
    cfg = TransformerConfig(
        vocab_size=19648, d_model=2560, n_layers=3, layer_pattern="kKL",
        n_heads=8, n_kv_heads=8, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, qk_norm=True, rope_theta=6e6,
        d_ff=768, moe_dense_ff=6144, max_seq_len=seq, norm_eps=1e-6,
        kda_heads=8, kda_head_dim=128, kda_chunk=64, moe_experts=512,
        moe_top_k=8, moe_scoring="sigmoid", moe_routed_scale=2.5,
        moe_groups=8, moe_topk_groups=4, moe_shared_experts=1,
        moe_shared_ff=768, moe_experts_held=8, moe_aux_coeff=0.0,
        attention_impl="auto", dtype="bfloat16", param_dtype="float32",
        remat=True, loss_chunk=256)
    assert cfg.pattern_runs == [("kKL", 1)]
    assert (cfg.head_dim, cfg.v_dim) == (192, 128)
    mesh = make_mesh(MeshConfig(data=-1), devices=[v5e])
    assert Transformer.resolve_attention_impl(cfg, mesh, seq) == "flash"
    assert _splash_block_sizes(seq, 192).block_kv == 1024
    assert _splash_block_sizes(seq, 256).block_kv == 512     # GLM's
    bound = row_bound(seq, 8, 8, 512, seq * 8)
    assert bound == 4096
    assert gmm_tiles(bound, 2560, 2 * 768) == (512, 512, 768)
    assert gmm_tiles(bound, 768, 2560) == (512, 768, 512)
    assert grouped_matmul_impl(mesh, bound, 2560, 768) == "megablox"
    assert kda_delta_impl(mesh, seq, 8, 128, 128, cfg.kda_chunk) == "pallas"
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
    # the program's kernels (the fallback branch's `ragged-dot-*` calls
    # are XLA's own)
    names = sorted(re.sub(r"\.\d+$", "", n) for n, _ in kernels
                   if re.match(r"t?gmm|splash", n))
    # two expert layers: per matmul the forward, remat's forward and the
    # transpose for the rows, one for the weights; splash once each way
    assert names == ["gmm"] * 12 + ["splash_mha_dkv_no_residuals",
                                    "splash_mha_fwd_residuals"] \
        + ["tgmm"] * 4, names
    # two KDA layers: each a forward, remat's forward and a backward of
    # the delta rule, all under `kda/delta` and nowhere else
    under_kda = sorted(
        (re.search(r"kda_delta_(fwd|bwd)", n).group(0),
         "rematted_computation" in op, "transpose(jvp" in op)
        for n, op in kernels if "kda" in n or "kda/" in op)
    assert under_kda == [("kda_delta_bwd", False, True)] * 2 \
        + [("kda_delta_fwd", False, False)] * 2 \
        + [("kda_delta_fwd", True, True)] * 2, under_kda
    assert all("/kda/delta/" in op for n, op in kernels if "kda" in n)
    assert not [op for n, op in kernels
                if "rematted_computation" in op and "splash" in n]
    assert_chosen_scores_read_off_the_selection(hlo, seq, 8, 512)
    for scope in ("kda_norm", "kda/qkv_proj", "kda/conv", "kda/gates",
                  "kda/delta", "kda/out_norm", "kda/out_proj", "qkv/q_proj",
                  "qkv/kv_down", "qkv/kv_up", "qkv/assemble", "moe/router",
                  "moe/shared", "mlp/gate_up", "head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)"]', hlo), scope
    # under `kda/delta` the largest tensor is the entering states,
    # `[T/C, H·Dv, D]` float32: the sub-blocks' column factors
    # `[H, T/C, C/16, C, D]`, four times that, stay in VMEM
    for line in hlo.splitlines():
        if not re.search(r'op_name="[^"]*[/(]kda/delta[/)"]', line):
            continue
        for dims in re.findall(r"\b(?:f32|bf16|s32)\[([\d,]+)\]", line):
            assert np.prod([int(v) for v in dims.split(",")]) \
                <= 8 * seq * 2 * 128, line[:300]
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes
             + ma.generated_code_size_in_bytes)
    assert total < 12e9, total


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
