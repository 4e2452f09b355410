"""A held share's path past the sort over a bounded run of rows
(`ops/moe.row_bound`, `_sorted_ffn`): the branch over the run and the
branch over every row are the same function of the same inputs, the
device picks by the rows the held experts received this step, nothing is
dropped on either, and no `cond` is traced where the bound cannot engage.
Since PR 60 the run's branch forms a token's sum from the run's rows
(`_permutes`' `token_sums` over `_by_token`) where the branch over every
row gathers a row a slot: the same terms, float32 sums of them in another
association (a weight's cotangent is a row dot on the sorted side). What
that leaves equal to the bit is held to the bit: the routing record; a
sum of one term; the run read by token against EVERY row read by token,
the rows past the run zero (the same association, and a zero row adds
nothing). The rest is held to rounding, each limit four times the
largest difference read over seeds (`same_to_rounding`).
On the CPU (`ragged_dot`), float32, sizes at which the bound engages."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import moe
from test_moe_routing_residuals import equations

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


def same_to_rounding(got, want, limit):
    """float32 sums of the same terms in another association: apart by at
    most `limit` of the largest entry. Each caller's limit is four times
    the largest difference read over 6 to 8 seeds of its comparison (PR
    60, XLA:CPU, `jax.random.key(s + 1000 i)` for every key the test
    draws); the reading is beside the limit."""
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=limit * scale, rtol=0)


# a layer's y and the cotangents of x and of the experts' weights: sums of
# at most k terms (read: 1.1e-7, 0.75e-7 and 0.7e-7 in a layer; 1.1e-7 and
# 1.4e-7 for the two sums alone)
A_TOKENS_SUM = 6e-7
# the chosen weights' cotangent, a row dot over d where the path over every
# row contracts `[N, k, d]` (read: 1.9e-7 alone, 1.2e-7 in a layer)
A_ROW_DOT = 8e-7
# the router's weights' cotangent: those, summed over the N tokens (read:
# 3.5e-7)
THROUGH_THE_ROUTER = 1.5e-6
# every leaf of a three-layer step under remat (read: 7.4e-7, the experts'
# first matmul; `test_the_kept_routing_serves_both_branches`' limit)
A_WHOLE_STEP = 2e-6


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
    same_to_rounding(y, want_y, A_TOKENS_SUM)
    # a sum of y's N x d entries under a cosine, which cancels (read: 4.7e-7)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    jax.tree.map(np.testing.assert_array_equal, routing, want_routing)
    assert set(grads[0]) == {"w_router", "w_gateup", "w_down"}
    for name, got in grads[0].items():
        same_to_rounding(got, want_grads[0][name], THROUGH_THE_ROUTER
                         if name == "w_router" else A_TOKENS_SUM)
    same_to_rounding(grads[1], want_grads[1], A_TOKENS_SUM)
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
    both give what the path over every row gives, to the bit: where the
    run is taken a token has at most one slot on the held experts
    (`picks`), so its sum is one term. The one exception is the cotangent
    of the chosen weights there, a row dot beside the other path's
    contraction of `[N, k, d]`."""
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
    jax.tree.map(np.testing.assert_array_equal, grads[:2], want_grads[:2])
    if bounded:
        same_to_rounding(grads[2], want_grads[2], A_ROW_DOT)
    else:
        np.testing.assert_array_equal(grads[2], want_grads[2])


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
    over every row that is N x k rows long, and since PR 60 no per-slot
    rows `[N, k, d]` of the bounded run's either (`combine` keeps the
    run's rows as they are)."""
    params, x = layer(8), tokens()
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: loss_and_record(p, x)[0], argnums=(0, 1)))(params, x))
    forward = text[:text.index(" cond[")].splitlines()[-3:]
    outputs = " ".join(forward)
    assert f"f32[{N * K},{2 * F}]" not in outputs      # gate and up
    assert f"f32[{N * K},{F}]" not in outputs          # the activation
    assert f"f32[{N * K},{D}]" not in outputs          # the gathered rows
    assert f"f32[{N},{K},{D}]" not in outputs


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
    # read: equal on every seed; held to float32's last place or two
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-7)
    jax.tree.map(np.testing.assert_array_equal, metrics, want_metrics)
    jax.tree.map(functools.partial(same_to_rounding, limit=A_WHOLE_STEP),
                 grads, want_grads)


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


# ---- the sums that come back from the run (PR 60) -------------------------
# `combine` and `slots_of`'s backward over a run of the sorted order against
# the gather of a row a slot that the path over every row still is


def slots_sorted(top_e, held, offset, experts=E):
    """`_sorted_ffn`'s sort: the held experts' groups first."""
    slot_expert = (jnp.asarray(top_e, jnp.int32).reshape(-1) - offset) \
        % experts
    _, order = jax.lax.sort(
        (slot_expert, jnp.arange(slot_expert.size, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    return order, jnp.argsort(order).astype(jnp.int32), int(
        (slot_expert < held).sum())


def a_row_a_slot(ys, top_w, inverse):
    """The parent's `combine` over a run: row `inverse[i]` for every slot
    i, zero past the run, the weighted sum over a token's k slots."""
    n, k = top_w.shape
    per_slot = jnp.take(ys, inverse, axis=0, fill_value=0).reshape(n, k, -1)
    return jnp.einsum("nkd,nk->nd", per_slot, top_w)


def spread(n, k, held, held_slots_of_token, experts=E, offset=0):
    """top_e `[n, k]`, distinct experts a token, token t with
    `held_slots_of_token(t)` slots on the held experts (from `offset`), at
    slots that move with t."""
    top_e = np.empty((n, k), np.int64)
    for t in range(n):
        c = held_slots_of_token(t)
        mine = (np.arange(c) + t) % held
        others = held + (np.arange(k - c) + 3 * t) % (experts - held)
        row = np.concatenate([mine, others])
        top_e[t] = (np.roll(row, t % k) + offset) % experts
    assert all(len(set(row)) == k for row in top_e)
    return top_e


ARRANGEMENTS = {
    # tokens with 0, 1 and k held slots, side by side
    "none_one_all": dict(held=8, count=lambda t: (0, 1, K)[t % 3], m=512),
    # every token has one: the run's tail is a third of it, rows of no
    # held expert, and what they hold is read as the path over every row
    # reads it
    "tail_of_no_expert": dict(held=8, count=lambda t: 1, m=384),
    # the first and the last tokens have none: a window past the run's end
    "empty_at_both_ends": dict(held=8, count=lambda t: K * (64 < t < 192),
                               m=640),
    # the run is exactly the held rows
    "no_tail": dict(held=8, count=lambda t: t % 2, m=128),
    # a share that does not start at expert 0
    "offset": dict(held=4, count=lambda t: t % 5 % 4, m=512, offset=37),
    "two_slots_a_token": dict(held=8, count=lambda t: t % 3, m=320, k=2),
}


@pytest.mark.parametrize("name", list(ARRANGEMENTS))
def test_the_sums_from_the_run_are_the_gathers_by_the_inverse(name):
    """`combine`'s value and its gradients in ys and top_w, and
    `slots_of`'s backward, over a run read by token: to rounding against
    a row a slot (autodiff's scatter-add for the transposes), and (the
    first arrangement) to the bit against ALL N x k rows read by token,
    zero past the run: the same association, and a zero row adds
    nothing."""
    case = dict(ARRANGEMENTS[name])
    n, d, k = 256, 8, case.pop("k", K)
    held, offset, m = case["held"], case.get("offset", 0), case["m"]
    top_e = spread(n, k, held, case["count"], offset=offset)
    order, inverse, received = slots_sorted(top_e, held, offset)
    assert received <= m < n * k
    keys = jax.random.split(jax.random.key(7), 5)
    ys = jax.random.normal(keys[0], (m, d), jnp.float32)   # the tail too
    top_w = jax.random.uniform(keys[1], (n, k), jnp.float32)
    x = jax.random.normal(keys[2], (n, d), jnp.float32)
    g_tokens = jax.random.normal(keys[3], (n, d), jnp.float32)
    g_rows = jax.random.normal(keys[4], (m, d), jnp.float32)
    slots_of, combine = moe._permutes()

    def from_the_run(ys, top_w, x):
        by_token = moe._by_token(order, inverse, m, k)
        y, back = jax.vjp(
            lambda ys, w: combine(ys, w, order[:m], inverse, by_token),
            ys, top_w)
        xs, back_x = jax.vjp(
            lambda x: slots_of(x, order[:m], inverse, k, by_token), x)
        return y, back(g_tokens), xs, back_x(g_rows)[0]

    @jax.jit
    def a_slot_at_a_time(ys, top_w, x):
        y, back = jax.vjp(
            lambda ys, w: a_row_a_slot(ys, w, inverse), ys, top_w)
        xs, back_x = jax.vjp(
            lambda x: jnp.take(x, order[:m] // k, axis=0), x)
        return y, back(g_tokens), xs, back_x(g_rows)[0]

    def from_every_row(ys, top_w, x):
        by_token = moe._by_token(order, inverse, n * k, k)
        rows = jnp.zeros((n * k, d), ys.dtype)
        y, back = jax.vjp(
            lambda ys, w: combine(rows.at[:m].set(ys), w, order, inverse,
                                  by_token), ys, top_w)
        xs, back_x = jax.vjp(
            lambda x: slots_of(x, order, inverse, k, by_token), x)
        return y, back(g_tokens), xs, back_x(rows.at[:m].set(g_rows))[0]

    y, (dys, dw), xs, dx = jax.jit(from_the_run)(ys, top_w, x)
    want_y, (want_dys, want_dw), want_xs, want_dx = a_slot_at_a_time(
        ys, top_w, x)
    same_to_rounding(y, want_y, A_TOKENS_SUM)          # read: 1.1e-7
    np.testing.assert_array_equal(dys, want_dys)       # a product a row
    same_to_rounding(dw, want_dw, A_ROW_DOT)           # read: 1.9e-7
    np.testing.assert_array_equal(xs, want_xs)
    same_to_rounding(dx, want_dx, A_TOKENS_SUM)        # read: 1.4e-7
    if name != "none_one_all":
        return
    # to the bit, op by op (what XLA:CPU fuses it rounds in its own way,
    # shape by shape) and so for one arrangement: seconds a shape
    with jax.disable_jit():
        y, (dys, dw), xs, dx = from_the_run(ys, top_w, x)
        all_y, (all_dys, all_dw), all_xs, all_dx = from_every_row(
            ys, top_w, x)
    jax.tree.map(np.testing.assert_array_equal,
                 (y, dys, dw, xs, dx),
                 (all_y, all_dys[:m], all_dw, all_xs[:m], all_dx))


def test_the_run_read_by_token():
    """`_by_token` on a run one can read: 4 tokens x 2 slots, the run the
    5 leading rows of the sorted order."""
    order = jnp.asarray([5, 0, 7, 4, 1, 2, 3, 6], jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    perm, slots, start = moe._by_token(order, inverse, 5, 2)
    np.testing.assert_array_equal(slots, [0, 1, 4, 5, 7])
    np.testing.assert_array_equal(perm[:5], [1, 4, 3, 0, 2])
    assert perm.shape == (5 + 2 - 1,) and int(perm[5]) < 5
    # token 0 has rows 0-1 of that order, token 1 none, 2 and 3 the rest
    np.testing.assert_array_equal(start, [0, 5, 2, 4])


def test_a_traced_offset_and_fewer_held_than_picked(monkeypatch):
    """A shard's offset is traced (`_exchange_ffn.held_share`), and with 2
    held of a token's 4 picks `keep` hands the path 2 slots a token: the
    run's branch against the path over every row."""
    held, offset = 2, 62
    params, x = layer(held, seed=3, offset=offset), tokens()
    _, top_w, top_e = moe.route(params["w_router"], x, K, True)

    def run(params, x, top_w, offset):
        y, counts, _, took = moe._sorted_ffn(params, x, top_w, top_e, None,
                                             offset)
        return jnp.sum(y * jnp.sin(jnp.arange(y.size).reshape(y.shape))), (
            y, counts, took)

    def grad():
        return jax.jit(jax.value_and_grad(
            run, argnums=(0, 1, 2), has_aux=True))(
                params, x, top_w, jnp.asarray(offset, jnp.int32))

    (_, (y, counts, took)), grads = grad()
    assert int(took) == 1 and int(counts.sum()) > 0
    every_row(monkeypatch)
    (_, (want, want_counts, took)), want_grads = grad()
    assert int(took) == 0
    np.testing.assert_array_equal(counts, want_counts)
    same_to_rounding(y, want, A_TOKENS_SUM)                # read: 0.7e-7
    jax.tree.map(functools.partial(same_to_rounding, limit=A_TOKENS_SUM),
                 grads[:2], want_grads[:2])                # read: equal
    same_to_rounding(grads[2], want_grads[2], A_ROW_DOT)   # read: 1.2e-7


def row_gathers(jaxpr, width):
    """The index counts of the gathers of `width`-wide rows."""
    return sorted(
        eqn.outvars[0].aval.shape[0] for eqn in equations(jaxpr)
        if eqn.primitive.name == "gather"
        and eqn.outvars[0].aval.shape[1:] == (width,))


def gradient_program(params, x):
    return jax.make_jaxpr(jax.grad(
        lambda p, x: loss_and_record(p, x)[0], argnums=(0, 1)))(params, x)


def test_the_runs_branch_gathers_no_row_a_slot():
    """Forward and backward of the bounded branch: every gather of d-wide
    rows has the run's m (or m + k - 1) indices or N, none N x k; the
    fallback beside it keeps its four of N x k (x into expert order, the
    rows back, and their two transposes)."""
    params, x = layer(8), tokens()
    m = moe.row_bound(N, K, 8, E, N * K)
    conds = [eqn for eqn in equations(gradient_program(params, x).jaxpr)
             if eqn.primitive.name == "cond"]
    assert conds
    for cond in conds:
        fallback, run = cond.params["branches"]
        assert set(row_gathers(run.jaxpr, D)) <= {m, m + K - 1, N}
    forward, backward = conds[0], conds[-1]
    assert row_gathers(forward.params["branches"][1].jaxpr, D) == [
        N, m, m + K - 1]
    assert N * K in row_gathers(forward.params["branches"][0].jaxpr, D)
    assert row_gathers(backward.params["branches"][0].jaxpr, D).count(
        N * K) == 4


def test_no_bound_no_reading_by_token(monkeypatch):
    """Every expert held (`row_bound` None): `_by_token` is never called,
    the program has its two sorts and its gathers of N x k rows, and the
    fallback of a bounded layer reads its rows as that program does: the
    same equations past the sort, whatever it is handed beside them."""
    params, x = layer(E), tokens()

    def never(*a):
        raise AssertionError("no run to read by token")

    with monkeypatch.context() as patched:
        patched.setattr(moe, "_by_token", never)
        program = gradient_program(params, x).jaxpr
    names = [eqn.primitive.name for eqn in equations(program)]
    assert names.count("sort") == 2 and "cond" not in names
    assert row_gathers(program, D) == [N * K] * 4

    held = layer(8)
    _, top_w, top_e = moe.route(held["w_router"], x, K, True)
    order, inverse, _ = slots_sorted(top_e, 8, 0)
    counts = jnp.zeros((E,), jnp.int32)
    past_the_sort = (x, top_w, held["w_gateup"], held["w_down"], order,
                     inverse, counts)
    over = functools.partial(moe._rows_ffn, N * K, "ragged_dot", k=K,
                             act="silu")

    def shapes(*extra):
        program = jax.make_jaxpr(jax.grad(
            lambda *a: over(*a).sum(), argnums=(0, 1, 2, 3)))(
                *past_the_sort, *extra)
        return [(eqn.primitive.name, [v.aval.shape for v in eqn.outvars])
                for eqn in equations(program.jaxpr)]

    assert shapes() == shapes(moe._by_token(order, inverse, 2048, K))
