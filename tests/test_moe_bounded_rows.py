"""A held share's path past the sort over a bounded run of rows
(`ops/moe.row_bound`, `_sorted_ffn`): the branch over the run and the
branch over every row are the same function of the same inputs, the
device picks by the rows the held experts received this step, nothing is
dropped on either, and no `cond` is traced where the bound cannot engage.
On the CPU (`ragged_dot`), float32, sizes at which the bound engages."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import moe

N, K, E, D, F = 2048, 4, 64, 16, 32


def layer(held, seed=0, offset=0):
    """All E experts' router, the `held` experts' weights from `offset`."""
    params = moe.init_moe_params(jax.random.key(seed), D, F, E)
    share = slice(offset, offset + held)
    return dict(params, w_gateup=params["w_gateup"][share],
                w_down=params["w_down"][share])


def tokens(seed=1, n=N):
    return jax.random.normal(jax.random.key(seed), (n, D), jnp.float32)


def every_row(monkeypatch):
    """The parent's program: no bound, no `cond`."""
    monkeypatch.setattr(moe, "row_bound", lambda *a: None)


def loss_and_record(params, x, **kw):
    y, routing = moe.moe_ffn(params, x, num_selected=K, **kw)
    return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), (
        y, routing)


@pytest.mark.parametrize("n,k,held,experts,rows,want", [
    (8192, 22, 8, 512, 65536, 5632),      # train_nemotron3super_ep64_d11
    (16384, 4, 8, 64, 65536, 16384),      # train_glm47flash_ep8_d5
    (16384, 8, 64, 64, 131072, None),     # train_olmoe_d1: every expert
    (N, K, 8, E, N * K, 2048), (N, K, 2, E, N * 2, 512),
    (N, K, 17, E, N * K, None),           # over half the rows
    (64, 4, 4, 16, 256, None),            # the reference tests' sizes
    (1000, 3, 5, 48, 3000, 1024)])        # rounded up to the row tile
def test_the_bound_follows_from_the_shapes(n, k, held, experts, rows, want):
    assert moe.row_bound(n, k, held, experts, rows) == want
    assert want is None or want % moe.GMM_ROWS == 0


@pytest.mark.parametrize("held,offset,scoring", [
    (8, 0, "softmax"), (8, 24, "sigmoid"), (2, 0, "softmax"),
    (2, 62, "sigmoid")])
def test_both_branches_are_one_function(monkeypatch, held, offset, scoring):
    """y, the routing record and the gradients of x, the router and the
    held experts' weights: the run of `row_bound` rows against every row
    (held 2 < k 4 runs over N x 2 rows past the sort)."""
    params, x = layer(held, seed=3, offset=offset), tokens()

    def run():   # traced anew: the bound is read at trace time
        return jax.jit(jax.value_and_grad(
            functools.partial(loss_and_record, expert_offset=offset,
                              scoring=scoring),
            argnums=(0, 1), has_aux=True))(params, x)

    (loss, (y, routing)), grads = run()
    assert int(routing["rows_bounded"]) == 1
    received = int(routing["tokens_per_expert"].sum())
    assert 0 < received <= moe.row_bound(N, K, held, E, N * min(K, held))
    assert received + int(routing["slots_elsewhere"]) == N * K
    every_row(monkeypatch)
    (want_loss, (want_y, want_routing)), want_grads = run()
    assert int(want_routing.pop("rows_bounded")) == 0
    routing.pop("rows_bounded")
    np.testing.assert_array_equal(y, want_y)
    assert float(loss) == float(want_loss)
    jax.tree.map(np.testing.assert_array_equal, routing, want_routing)
    jax.tree.map(np.testing.assert_array_equal, grads, want_grads)
    assert float(jnp.abs(grads[0]["w_router"]).max()) > 0
    assert float(jnp.abs(grads[0]["w_down"]).max()) > 0


def picks(held_slots, held=8):
    """top_e `[N, K]`, each token's experts distinct, with exactly
    `held_slots` slots on the held experts 0..held-1."""
    top_e = held + (np.arange(N)[:, None] * 5 + np.arange(K)[None]) % (
        E - held)
    one, two = min(held_slots, N), max(held_slots - N, 0)
    top_e[:one, 0] = np.arange(one) % held
    top_e[:two, 1] = (np.arange(two) + 1) % held
    assert (np.sort(top_e, 1)[:, 1:] != np.sort(top_e, 1)[:, :-1]).all()
    assert int((top_e < held).sum()) == held_slots
    return jnp.asarray(top_e, jnp.int32)


@pytest.mark.parametrize("held_slots,bounded", [
    (1, 1), (2047, 1), (2048, 1), (2049, 0), (4096, 0)])
def test_the_device_picks_the_branch_by_the_rows_received(
        monkeypatch, held_slots, bounded):
    """R == B runs over the bounded run, R == B + 1 over every row, and
    both give what the path over every row gives."""
    params, x = layer(8), tokens()
    assert moe.row_bound(N, K, 8, E, N * K) == 2048
    top_e = picks(held_slots)
    top_w = jax.random.uniform(jax.random.key(2), (N, K), jnp.float32)

    def run(params, x, top_w):
        y, counts, dropped, took = moe._sorted_ffn(params, x, top_w, top_e,
                                                   None)
        return jnp.sum(y * y), (y, counts, dropped, took)

    def grad():
        return jax.jit(jax.value_and_grad(
            run, argnums=(0, 1, 2), has_aux=True))(params, x, top_w)

    (_, (y, counts, dropped, took)), grads = grad()
    assert int(took) == bounded
    assert int(counts.sum()) == held_slots and int(dropped) == 0
    every_row(monkeypatch)
    (_, (want, want_counts, _, took)), want_grads = grad()
    assert int(took) == 0
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(y, want)
    jax.tree.map(np.testing.assert_array_equal, grads, want_grads)


def test_every_slot_on_held_experts_runs_the_fallback_and_drops_nothing():
    """A choice bias that sends all N x k slots to the 8 held experts: four
    times the bound. The fallback runs, no slot is dropped, and the result
    is the dense layer of those 8 experts alone."""
    held = 8
    params, x = layer(held), tokens()
    # a softmax score is under 1/4 here; a larger bias would round the
    # scores' differences away and move near-ties
    bias = jnp.where(jnp.arange(E) < held, 0.25, 0.0)
    y, routing = jax.jit(lambda p, x: moe.moe_ffn(
        p, x, num_selected=K, norm_topk=True))(
            dict(params, router_bias=bias), x)
    assert int(routing["rows_bounded"]) == 0
    assert int(routing["dropped"]) == 0
    assert int(routing["slots_elsewhere"]) == 0
    assert int(routing["tokens_per_expert"].sum()) == N * K
    # renormalised, the chosen four's weights are a softmax over them
    # alone: the same with the other 56 experts' logits left out
    alone = dict(params, w_router=params["w_router"][:, :held])
    want = moe.moe_ffn_dense_reference(alone, x, num_selected=K,
                                       norm_topk=True)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(y, want, atol=1e-5 * scale, rtol=0)


@pytest.mark.parametrize("held,conds", [(E, 0), (8, 1), (17, 0)])
def test_a_cond_is_traced_only_where_the_bound_engages(held, conds):
    """Every expert held, or a run over half the rows: the parent's
    program, with no `cond` in it."""
    params, x = layer(held), tokens()
    text = str(jax.make_jaxpr(
        lambda p, x: moe.moe_ffn(p, x, num_selected=K))(params, x))
    assert text.count(" cond[") == conds


def test_the_fallback_keeps_only_its_inputs_for_the_backward_pass():
    """What the forward `cond` hands the backward: nothing of the branch
    over every row that is N x k rows long, but the one gather back from
    the token side (`combine`'s per-slot rows, the bounded run's own)."""
    params, x = layer(8), tokens()
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: loss_and_record(p, x)[0], argnums=(0, 1)))(params, x))
    forward = text[:text.index(" cond[")].splitlines()[-3:]
    outputs = " ".join(forward)
    assert f"f32[{N * K},{2 * F}]" not in outputs      # gate and up
    assert f"f32[{N * K},{F}]" not in outputs          # the activation
    assert f"f32[{N * K},{D}]" not in outputs          # the gathered rows
    assert outputs.count(f"f32[{N},{K},{D}]") == 1


CFG = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=3, n_heads=2, d_ff=32,
    max_seq_len=256, dtype="float32", loss_chunk=0, moe_experts=E,
    moe_top_k=K, moe_scoring="sigmoid", moe_experts_held=8,
    moe_aux_coeff=0.0, remat=True)


def test_the_step_counts_the_layers_that_ran_bounded(monkeypatch):
    """`Transformer.loss(with_metrics=True)` under the layers' scan and
    remat: `moe_rows_bounded` `[expert layers]` beside
    `moe_slots_elsewhere`, and the gradients of the path over every row."""
    params = Transformer.init(jax.random.key(3), CFG)
    batch = {"tokens": jax.random.randint(
        jax.random.key(4), (2, 257), 0, CFG.vocab_size)}
    assert moe.row_bound(512, K, 8, E, 512 * K) == 512

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: Transformer.loss(p, batch, CFG, with_metrics=True),
            has_aux=True))(params)

    (loss, metrics), grads = step()
    assert metrics["moe_rows_bounded"].shape == (CFG.n_layers,)
    assert metrics["moe_rows_bounded"].dtype == jnp.int32
    np.testing.assert_array_equal(metrics["moe_rows_bounded"], 1)
    assert metrics["moe_slots_elsewhere"].shape == (CFG.n_layers,)
    assert int(metrics["moe_dropped"]) == 0
    every_row(monkeypatch)
    (want_loss, want_metrics), want_grads = step()
    np.testing.assert_array_equal(want_metrics.pop("moe_rows_bounded"), 0)
    metrics.pop("moe_rows_bounded")
    assert float(loss) == float(want_loss)
    jax.tree.map(np.testing.assert_array_equal, metrics, want_metrics)
    jax.tree.map(np.testing.assert_array_equal, grads, want_grads)


def test_a_config_with_every_expert_held_has_no_such_counter():
    cfg = CFG.replace(moe_experts_held=0, moe_experts=8, n_layers=1)
    params = Transformer.init(jax.random.key(3), cfg)
    batch = {"tokens": jnp.zeros((1, 65), jnp.int32)}
    _, metrics = Transformer.loss(params, batch, cfg, with_metrics=True)
    assert "moe_rows_bounded" not in metrics
    assert "moe_slots_elsewhere" not in metrics


@pytest.mark.parametrize("flood,bounded", [(0.0, 1), (1.0, 0)],
                         ids=["bounded_run", "fallback"])
def test_the_kept_routing_serves_both_branches(flood, bounded):
    """Under the default remat policy a layer keeps its routing
    (`moe.ROUTING_RESIDUALS`: the sort's `order`, `inverse` and `counts`
    are made before the `cond` and are both branches' operands). A step
    whose held rows fit the run, and one whose choice bias sends every
    slot to the held experts and overruns it: the loss and the routing
    record of `remat_policy="full"` to the bit, its gradients to rounding
    (XLA:CPU fuses the forward it runs again in its own way)."""
    params = Transformer.init(jax.random.key(3), CFG)
    params["embed"] = jax.random.normal(jax.random.key(5),
                                        params["embed"].shape)
    params["layers"]["router_bias"] = jnp.broadcast_to(
        jnp.where(jnp.arange(E) < 8, flood, 0.0), (CFG.n_layers, E))
    batch = {"tokens": jax.random.randint(
        jax.random.key(4), (2, 257), 0, CFG.vocab_size)}

    def step(policy):
        cfg = CFG.replace(remat_policy=policy)
        return jax.jit(jax.value_and_grad(
            lambda p: Transformer.loss(p, batch, cfg, with_metrics=True),
            has_aux=True))(params)

    (loss, metrics), grads = step("attention")
    np.testing.assert_array_equal(metrics["moe_rows_bounded"], bounded)
    assert int(metrics["moe_dropped"]) == 0
    if not bounded:      # every slot on the held experts: four runs' worth
        np.testing.assert_array_equal(metrics["moe_slots_elsewhere"], 0)
    (want_loss, want_metrics), want_grads = step("full")
    assert float(loss) == float(want_loss)
    jax.tree.map(np.testing.assert_array_equal, metrics, want_metrics)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, atol=2e-6 * scale, rtol=0)
    assert float(jnp.abs(grads["layers"]["w_router"]).max()) > 0
