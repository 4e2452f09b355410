"""Pipeline parallelism + MoE/expert parallelism (SURVEY §2.4 PP/EP rows).

Runs on the chip-free 8-device CPU mesh (conftest). Pipeline: 2-stage
microbatched spmd pipeline must match the unpipelined model's loss and
gradients. MoE: the sorted dropless dispatch and the expert-parallel
capacity dispatch must match the dense reference (the latter when capacity
is ample), shard over the expert axis, and train.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.moe import (init_moe_params, load_balancing_loss,
                             moe_ffn, moe_ffn_dense_reference)
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.pipeline import make_pipeline_fn, stack_stage_params


def _mlp_stage(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _stage_params(key, d):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d, d), jnp.float32) * 0.5,
        "b1": jnp.zeros((d,)),
        "w2": jax.random.normal(k2, (d, d), jnp.float32) * 0.5,
        "b2": jnp.zeros((d,)),
    }


class TestPipeline:
    def test_matches_unpipelined_loss_and_grads(self):
        d, mb, n_micro, n_stages = 8, 4, 4, 2
        mesh = make_mesh(MeshConfig(data=1, fsdp=1, pipe=n_stages,
                                    seq=1, tensor=4))
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, n_stages + 2)
        stages = [_stage_params(ks[i], d) for i in range(n_stages)]
        stacked = stack_stage_params(stages)
        x = jax.random.normal(ks[-2], (n_micro, mb, d))
        y = jax.random.normal(ks[-1], (n_micro, mb, d))

        def loss_fn(out, target):
            return jnp.mean((out - target) ** 2)

        pipe = make_pipeline_fn(_mlp_stage, n_stages, n_micro, mesh,
                                loss_fn=loss_fn)

        def ref_loss(stacked_params, x, y):
            losses = []
            for m in range(n_micro):
                h = x[m]
                for s in range(n_stages):
                    sp = jax.tree.map(lambda a: a[s], stacked_params)
                    h = _mlp_stage(sp, h)
                losses.append(loss_fn(h, y[m]))
            return jnp.mean(jnp.stack(losses))

        loss_p = jax.jit(pipe)(stacked, x, y)
        loss_r = ref_loss(stacked, x, y)
        np.testing.assert_allclose(np.asarray(loss_p), np.asarray(loss_r),
                                   rtol=1e-5)

        g_p = jax.jit(jax.grad(pipe))(stacked, x, y)
        g_r = jax.grad(ref_loss)(stacked, x, y)
        for a, b in zip(jax.tree_util.tree_leaves(g_p),
                        jax.tree_util.tree_leaves(g_r)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_tiny_model_trains_pipe2(self):
        """VERDICT item 10 acceptance: training under pipe=2 matches the
        single-device loss trajectory."""
        import optax

        d, mb, n_micro, n_stages = 8, 4, 4, 2
        mesh = make_mesh(MeshConfig(data=1, fsdp=1, pipe=n_stages,
                                    seq=1, tensor=4))
        key = jax.random.PRNGKey(1)
        ks = jax.random.split(key, n_stages + 2)
        stacked = stack_stage_params(
            [_stage_params(ks[i], d) for i in range(n_stages)])
        x = jax.random.normal(ks[-2], (n_micro, mb, d))
        y = x * 0.5  # learnable linear-ish target

        pipe = make_pipeline_fn(
            _mlp_stage, n_stages, n_micro, mesh,
            loss_fn=lambda o, t: jnp.mean((o - t) ** 2))
        opt = optax.adam(1e-2)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(pipe)(params, x, y)
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        opt_state = opt.init(stacked)
        losses = []
        params = stacked
        for _ in range(30):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::10]


def _aux(routing, k):
    return load_balancing_loss(routing["tokens_per_expert"],
                               routing["router_prob"], k)


class TestMoE:
    """`moe_ffn` on the same gated experts on one device and on meshes
    whose `expert` axis is above 1: the sorted dropless path everywhere
    (under `shard_map` there: the shares summed where the expert shards
    hold the same tokens, the tokens exchanged where they are divided
    over that axis)."""

    @pytest.mark.parametrize("path", ["sorted", "mesh", "exchange"])
    def test_matches_dense_reference_with_ample_capacity(self, path):
        from ray_tpu.parallel.sharding import ShardingRules

        mesh = None if path == "sorted" else make_mesh(
            MeshConfig(data=2, expert=4))
        rules = ShardingRules().replace(batch=("data", "expert")) \
            if path == "exchange" else None
        key = jax.random.PRNGKey(0)
        params = init_moe_params(key, d_model=16, d_ff=32, n_experts=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
        # one program: op by op every op under the `shard_map` compiles
        # alone for the eight devices
        y, routing = jax.jit(lambda p, x: moe_ffn(
            p, x, num_selected=2, mesh=mesh, rules=rules))(params, x)
        y_ref = moe_ffn_dense_reference(params, x, num_selected=2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        assert float(_aux(routing, 2)) > 0.0
        assert int(routing["dropped"]) == 0
        assert int(routing["tokens_per_expert"].sum()) == 24 * 2

    @pytest.mark.parametrize("batch", [("data",), ("data", "expert")],
                             ids=["shares_summed", "tokens_exchanged"])
    def test_nothing_dropped_at_the_load_that_used_to_drop(self, batch):
        """The skew under which the capacity path (gone: ROADMAP D9)
        dropped tokens at a tight factor: an expert mesh drops nothing,
        zeroes no token's output and equals the one-device path."""
        from ray_tpu.parallel.sharding import ShardingRules

        mesh = make_mesh(MeshConfig(data=4, expert=2))
        rules = ShardingRules().replace(batch=batch)
        key = jax.random.PRNGKey(2)
        params = init_moe_params(key, d_model=8, d_ff=16, n_experts=2)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
        y_mesh, r_mesh = jax.jit(lambda p, x: moe_ffn(
            p, x, num_selected=1, mesh=mesh, rules=rules))(params, x)
        y_sorted, r_sorted = jax.jit(lambda p, x: moe_ffn(
            p, x, num_selected=1))(params, x)
        counts = np.asarray(r_sorted["tokens_per_expert"])
        # the capacity path's buffer at its tight factor 0.25 held
        # int(0.25 * 16 * 1 / 2) = 2 slots an expert, and dropped the rest
        assert counts.min() > 2
        assert int(r_mesh["dropped"]) == int(r_sorted["dropped"]) == 0
        np.testing.assert_array_equal(r_mesh["tokens_per_expert"], counts)
        assert not np.all(np.asarray(y_mesh) == 0.0, axis=-1).any()
        np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_sorted),
                                   rtol=1e-4, atol=1e-5)

    def test_sharded_over_expert_axis(self):
        """The sorted path runs under jit with params sharded on the
        expert mesh axis (`shard_map` over it; the tokens are not divided
        over it here, so the shards' shares are summed)."""
        from ray_tpu.parallel.sharding import shard_pytree
        from ray_tpu.ops.moe import MOE_PARAM_SPECS

        mesh = make_mesh(MeshConfig(data=1, fsdp=1, expert=4, tensor=2))
        key = jax.random.PRNGKey(4)
        params = init_moe_params(key, d_model=16, d_ff=32, n_experts=4)
        shardings = shard_pytree(dict(MOE_PARAM_SPECS), mesh)
        params_sharded = jax.device_put(params, shardings)
        x = jax.random.normal(jax.random.PRNGKey(5), (32, 16))

        @jax.jit
        def f(p, x):
            return moe_ffn(p, x, num_selected=2, mesh=mesh)

        y, _routing = f(params_sharded, x)
        y_ref = moe_ffn_dense_reference(params, x, num_selected=2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_moe_trains_with_aux_loss(self):
        import optax

        key = jax.random.PRNGKey(6)
        params = init_moe_params(key, d_model=8, d_ff=16, n_experts=4)
        x = jax.random.normal(jax.random.PRNGKey(7), (64, 8))
        target = jnp.tanh(x @ jax.random.normal(jax.random.PRNGKey(8),
                                                (8, 8)))

        def loss_fn(p):
            y, routing = moe_ffn(p, x, num_selected=2)
            return jnp.mean((y - target) ** 2) + 0.01 * _aux(routing, 2)

        opt = optax.adam(3e-3)
        opt_state = opt.init(params)
        step = jax.jit(lambda p, s: (lambda l, g: (
            optax.apply_updates(p, opt.update(g, s)[0]),
            opt.update(g, s)[1], l))(*jax.value_and_grad(loss_fn)(p)))
        losses = []
        for _ in range(40):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, losses[::10]


class TestFlagshipIntegration:
    """Round-5: MoE and pipeline integrated into the flagship model
    (models/transformer.py), not just standalone engines — the
    beyond-reference EP/PP rows exercised end-to-end (SURVEY §2.4)."""

    def test_transformer_moe_layers_train_on_expert_mesh(self):
        import optax

        from ray_tpu.models import TINY, Transformer
        from ray_tpu.parallel.train_step import make_train_step

        cfg = TINY.replace(dtype="float32", moe_experts=4, moe_top_k=2,
                           loss_chunk=0)
        mesh = make_mesh(MeshConfig(data=2, fsdp=1, expert=4))
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        assert "w_router" in params["layers"]
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)
        init_state, train_step = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
            Transformer.param_specs(cfg), mesh,
            optimizer=optax.adamw(1e-2))
        state = init_state(params)
        losses = []
        for _ in range(5):
            state, m = train_step(state, {"tokens": tokens})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        # expert weights actually sharded over the expert axis
        up = state["params"]["layers"]["w_moe_gateup"]
        spec = up.sharding.spec
        assert "expert" in str(spec), spec

    @pytest.mark.parametrize("loss_chunk", [0, 16])
    def test_transformer_pipeline_loss_matches_scan(self, loss_chunk):
        """The stages run the stack `hidden` scans and the last one the
        head `loss` takes, chunked where the config says so."""
        from ray_tpu.models import TINY, Transformer

        cfg = TINY.replace(dtype="float32", attention_impl="dense",
                           loss_chunk=loss_chunk)
        mesh = make_mesh(MeshConfig(data=4, pipe=2))
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)
        ref, ref_grads = jax.value_and_grad(
            lambda p: Transformer.loss(p, {"tokens": tokens}, cfg))(params)
        pl, grads = jax.jit(jax.value_and_grad(
            lambda p: Transformer.pipeline_loss(
                p, {"tokens": tokens}, cfg, mesh=mesh,
                n_stages=2, n_micro=4)))(params)
        assert abs(float(ref) - float(pl)) < 1e-4, (ref, pl)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)

    def test_transformer_pipeline_trains(self):
        import optax

        from ray_tpu.models import TINY, Transformer
        from ray_tpu.parallel.train_step import make_train_step

        cfg = TINY.replace(dtype="float32", attention_impl="dense",
                           loss_chunk=0)
        mesh = make_mesh(MeshConfig(data=4, pipe=2))
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)
        init_state, train_step = make_train_step(
            lambda p, b: Transformer.pipeline_loss(
                p, b, cfg, mesh=mesh, n_stages=2, n_micro=4),
            Transformer.param_specs(cfg), mesh,
            optimizer=optax.adamw(1e-2))
        state = init_state(params)
        losses = []
        for _ in range(5):
            state, m = train_step(state, {"tokens": tokens})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
