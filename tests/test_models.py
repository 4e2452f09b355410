"""Flagship transformer tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import TINY, Transformer
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.train_step import make_train_step


@pytest.fixture(scope="module")
def tiny_params():
    return Transformer.init(jax.random.PRNGKey(0), TINY)


class TestForward:
    def test_shapes_and_dtype(self, tiny_params):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = Transformer.apply(tiny_params, tokens, TINY)
        assert logits.shape == (2, 16, TINY.vocab_size)
        assert logits.dtype == jnp.float32  # f32 accumulation at the head

    def test_param_count_matches_config(self, tiny_params):
        n = sum(x.size for x in jax.tree.leaves(tiny_params))
        assert n == TINY.num_params

    def test_causality(self, tiny_params):
        """Changing a future token must not change past logits."""
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (1, 16), 0, TINY.vocab_size)
        logits_a = Transformer.apply(tiny_params, tokens, TINY)
        tokens_b = tokens.at[0, 10].set((tokens[0, 10] + 1) % TINY.vocab_size)
        logits_b = Transformer.apply(tiny_params, tokens_b, TINY)
        np.testing.assert_allclose(
            np.asarray(logits_a[0, :10]), np.asarray(logits_b[0, :10]),
            atol=1e-5)
        assert not np.allclose(np.asarray(logits_a[0, 10:]),
                               np.asarray(logits_b[0, 10:]))

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_sequence_parallel_matches_dense(self, tiny_params, impl):
        """Ring/Ulysses attention over a seq=4 mesh == dense, bitwise-ish."""
        cfg32 = TINY.replace(dtype="float32", attention_impl="dense")
        cfg_sp = cfg32.replace(attention_impl=impl)
        mesh = make_mesh(MeshConfig(data=2, seq=4))
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (2, 32), 0, TINY.vocab_size)
        dense = Transformer.apply(tiny_params, tokens, cfg32)
        sp = jax.jit(lambda p, t: Transformer.apply(
            p, t, cfg_sp, mesh=mesh))(tiny_params, tokens)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(sp),
                                   atol=2e-4, rtol=2e-4)


class TestTrainStep:
    def test_loss_decreases_sharded(self, tiny_params):
        """3D-sharded (dp×fsdp×tp) train step memorizes a tiny batch."""
        import optax
        cfg = TINY.replace(dtype="float32")
        mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        tokens = jax.random.randint(
            jax.random.PRNGKey(3), (4, 33), 0, cfg.vocab_size)
        batch = {"tokens": tokens}

        init_state, train_step = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
            Transformer.param_specs(cfg), mesh,
            optimizer=optax.adam(1e-2))
        state = init_state(tiny_params)

        losses = []
        for _ in range(10):
            state, metrics = train_step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses
        assert int(jax.device_get(state["step"])) == 10

    @pytest.mark.parametrize("on_mesh", [False, True],
                             ids=["no_mesh", "mesh_3d"])
    @pytest.mark.parametrize("policy", ["dots", "attention"])
    def test_remat_policy_matches_full(self, tiny_params, policy, on_mesh):
        """A policy saves what "full" recomputes: the same loss and every
        gradient, here and on the 3D mesh."""
        cfg = TINY.replace(dtype="float32", remat=True)
        assert TINY.remat_policy == "attention"   # what remat=True means
        mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(3), (4, 33), 0, cfg.vocab_size)}

        def run(policy, mesh):
            c = cfg.replace(remat_policy=policy)
            return jax.jit(jax.value_and_grad(
                lambda p: Transformer.loss(p, batch, c, mesh=mesh)))(
                    tiny_params)

        ref_loss, ref_grads = run("full", None)
        loss, grads = run(policy, mesh if on_mesh else None)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
        errs = jax.tree.map(_rel_l2, grads, ref_grads)
        assert all(e < 1e-5 for e in jax.tree.leaves(errs)), errs

    def test_default_remat_without_the_kernel_is_fulls_program(
            self, tiny_params):
        """Only the flash kernel names what the default policy saves: with
        dense attention nothing is named, nothing is saved, and the
        compiled program is "full"'s."""
        from tests.test_model_scopes import stripped

        cfg = TINY.replace(remat=True, attention_impl="dense")
        batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}

        def compiled_text(policy):
            c = cfg.replace(remat_policy=policy)
            # less the source locations, which are no part of the program
            return stripped(jax.jit(jax.value_and_grad(
                lambda p: Transformer.loss(p, batch, c))).lower(
                    tiny_params).compile().as_text())

        assert compiled_text("attention") == compiled_text("full")
        assert compiled_text("dots") != compiled_text("full")

    @pytest.mark.parametrize("remat", [True, False])
    def test_unknown_remat_policy_raises(self, tiny_params, remat):
        cfg = TINY.replace(remat=remat, remat_policy="dot")
        batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
        with pytest.raises(ValueError, match="unknown remat_policy 'dot'"):
            Transformer.loss(tiny_params, batch, cfg)

    def test_param_shardings_applied(self, tiny_params):
        import optax
        cfg = TINY.replace(dtype="float32")
        mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        init_state, _ = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
            Transformer.param_specs(cfg), mesh, optimizer=optax.adam(1e-2))
        state = init_state(tiny_params)
        wg = state["params"]["layers"]["w_gateup"]  # (L, d, 2, ff):
        spec = wg.sharding.spec                     # embed->fsdp, mlp->tensor
        assert "fsdp" in str(spec) and "tensor" in str(spec)
        # adam momenta shard identically to their params (ZeRO-for-free)
        mu = state["opt_state"][0].mu["layers"]["w_gateup"]
        assert mu.sharding == wg.sharding

    def test_opt_sharding_with_shape_collision(self):
        """d_ff == d_model: shapes can collide across params; momenta must
        still shard by tree path, not by shape."""
        import optax
        cfg = TINY.replace(dtype="float32", d_ff=TINY.d_model)
        mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        init_state, _ = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
            Transformer.param_specs(cfg), mesh, optimizer=optax.adam(1e-2))
        state = init_state(params)
        for name in ("w_gateup", "w_down", "wq", "embed"):
            tree = state["params"] if name == "embed" \
                else state["params"]["layers"]
            mtree = state["opt_state"][0].mu if name == "embed" \
                else state["opt_state"][0].mu["layers"]
            assert mtree[name].sharding == tree[name].sharding, name


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


class TestChunkedHeadPerChip:
    """Transformer.loss's chunked head under a mesh that splits only the
    batch runs per chip under shard_map; every other mesh keeps the GSPMD
    path. Both must give mesh=None's loss and gradients."""

    CASES = {
        # id: (mesh axes over 4 devices, cfg overrides, masked, mapped)
        "fsdp4": (dict(data=1, fsdp=4), {}, False, True),
        "data2_fsdp2": (dict(data=2, fsdp=2), {}, False, True),
        "mask": (dict(data=1, fsdp=4), {}, True, True),
        "tied": (dict(data=2, fsdp=2), {"tie_embeddings": True}, False,
                 True),
        "bf16": (dict(data=1, fsdp=4), {"dtype": "bfloat16"}, False, True),
        "tensor2": (dict(data=1, fsdp=2, tensor=2), {}, False, False),
        "seq2": (dict(data=2, seq=2), {}, True, False),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_matches_unsharded(self, case):
        axes, overrides, masked, mapped = self.CASES[case]
        cfg = TINY.replace(**{"dtype": "float32", "attention_impl": "dense",
                              "remat": True, "loss_chunk": 16, **overrides})
        mesh = make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (8, 65), 0, cfg.vocab_size)
        batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
        if masked:
            batch["mask"] = (jax.random.uniform(
                jax.random.PRNGKey(5), (8, 64)) < 0.7).astype(jnp.float32)

        def sharded(p):
            return Transformer.loss(p, batch, cfg, mesh=mesh)

        # which path ran is read from the traced program, as the code reads
        # it from the mesh: dense attention leaves no other shard_map
        assert ("shard_map" in str(jax.make_jaxpr(sharded)(params))) \
            == mapped
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: Transformer.loss(p, batch, cfg)))(params)
        loss, grads = jax.jit(jax.value_and_grad(sharded))(params)
        if cfg.dtype == "bfloat16":
            # same bf16 head, activations and dW carry as one chip: the
            # difference is the order of bf16 roundings, not a precision
            assert abs(float(loss) - float(ref_loss)) < 2e-2
            assert _rel_l2(grads["lm_head"], ref_grads["lm_head"]) < 1e-2
            return
        assert abs(float(loss) - float(ref_loss)) < 1e-6 * abs(
            float(ref_loss)) + 1e-6
        errs = jax.tree.map(_rel_l2, grads, ref_grads)
        assert all(e < 1e-5 for e in jax.tree.leaves(errs)), errs


class TestChunkedHeadGradInForward:
    """The chunked head takes each chunk's gradient in the forward scan (a
    custom_vjp); loss_chunk=0 is plain autodiff through whole-sequence
    logits. On mesh=None both must give the same loss and gradients."""

    CASES = {
        # id: (cfg overrides, masked)
        "dense": ({}, False),
        "tied": ({"tie_embeddings": True}, False),
        "mask": ({}, True),
        "tied_mask": ({"tie_embeddings": True}, True),
        "moe_aux": ({"moe_experts": 4, "moe_aux_coeff": 0.01}, False),
        "unrolled": ({"scan_unroll": 2}, False),
        "bf16": ({"dtype": "bfloat16"}, False),
        "bf16_mask": ({"dtype": "bfloat16"}, True),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_matches_unchunked_autodiff(self, case):
        overrides, masked = self.CASES[case]
        cfg = TINY.replace(**{"dtype": "float32", "attention_impl": "dense",
                              "remat": True, "loss_chunk": 16, **overrides})
        params = Transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (4, 65), 0, cfg.vocab_size)
        batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
        if masked:
            batch["mask"] = (jax.random.uniform(
                jax.random.PRNGKey(5), (4, 64)) < 0.7).astype(jnp.float32)

        def run(c):
            return jax.jit(jax.value_and_grad(
                lambda p: Transformer.loss(p, batch, c)))(params)

        ref_loss, ref_grads = run(cfg.replace(loss_chunk=0))
        loss, grads = run(cfg)
        # an evaluation (the custom_vjp's primal) is the same number
        assert float(jax.jit(lambda p: Transformer.loss(p, batch, cfg))(
            params)) == pytest.approx(float(loss), rel=1e-6)
        if cfg.dtype == "bfloat16":
            assert abs(float(loss) - float(ref_loss)) < 2e-2
            head = "embed" if cfg.tie_embeddings else "lm_head"
            assert _rel_l2(grads[head], ref_grads[head]) < 1e-2
            return
        assert abs(float(loss) - float(ref_loss)) < 1e-5 * abs(
            float(ref_loss))
        errs = jax.tree.map(_rel_l2, grads, ref_grads)
        assert all(e < 1e-5 for e in jax.tree.leaves(errs)), errs
