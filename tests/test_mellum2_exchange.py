"""Mellum 2's expert exchange (`ops/moe._exchange_ffn`) under loads that
take the one bounded round and loads that take the rounds for any load,
the ragged round with every row that nothing writes poisoned (PR 64), the
held shares adding up to it and to the uncut layer, the new scopes as
metadata only, and each fault of `benchmark/reference/mellum2_faults.py`
at the small size. The model against its reference is
`tests/test_mellum2_reference.py`'s; both read `tests/_mellum2.py`."""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules

from tests._mellum2 import (BENCH_DIR, E, RULES, assert_close, config, faults,
                            load_json, mesh_of, ref)


# ---- the exchange -------------------------------------------------------------

N, D, F, K = 256, 16, 32, 2


def layer_and_rows(seed=0, n=N, d=D, f=F):
    params = moe.init_moe_params(jax.random.key(seed), d, f, E)
    x = jax.random.normal(jax.random.key(seed + 1), (n, d))
    top_w = jax.nn.softmax(jax.random.normal(jax.random.key(seed + 2),
                                             (n, K)))
    return params, x, top_w


def spread_choice(n=N, seed=3):
    """Two distinct experts a token, near uniform."""
    first = jax.random.randint(jax.random.key(seed), (n, 1), 0, E)
    step = 1 + jax.random.randint(jax.random.key(seed + 1), (n, 1), 0, E - 1)
    return jnp.concatenate([first, (first + step) % E], axis=1)


def loop_reference(params, x, top_w, top_e):
    """The uncut layer as the reference computes it, given the choice."""
    y = jnp.zeros_like(x)
    for e in range(E):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + weight[:, None] * ref.expert_mlp(
            x, params["w_gateup"][e][:, 0].T, params["w_gateup"][e][:, 1].T,
            params["w_down"][e].T)
    return y


def weigh(y):
    return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()


def both_paths(top_e, mesh):
    """(one_device, exchanged): the layer past the router as a weighted
    sum of its output, through the one-device sorted path and through the
    exchange over `mesh`; each also returns y (and the routing record)."""
    def one_device(p, x, w):
        y = moe._sorted_ffn(p, x, w, top_e, None)[0]
        return weigh(y), y

    def exchanged(p, x, w):
        y, record = moe._exchange_ffn(p, x, w, top_e, mesh, RULES)
        return weigh(y), (y, record)

    return one_device, exchanged


def value_and_grads(fn):
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))


LOADS = {     # by the tokens of all four chips, N where not said
    # every token of every chip to chip 0's experts: the worst load
    "all_to_one_chip": lambda n=N: jnp.tile(jnp.array([[0, 1]]), (n, 1)),
    # chip 0's tokens (the first n / 4) all to chip 3: one chip overflows
    "one_chip_overflows": lambda n=N: spread_choice(n).at[:n // 4].set(
        jnp.array([6, 7])),
    "spread": spread_choice,
    # chip 0 sends chip 1 exactly the bucket's n / 4 rows: just under
    "at_the_bound": lambda n=N: spread_choice(n).at[:n // 4].set(
        jnp.array([0, 4])).at[:n // 8].set(jnp.array([2, 3])),
}
BOUNDED = {"all_to_one_chip": 0, "one_chip_overflows": 0, "spread": 1,
           "at_the_bound": 1}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_dropless_at_any_load_and_the_branch_is_agreed(load):
    """The exchange against the one-device sorted path and the reference's
    loop, output and gradients, under loads that take the fast branch and
    loads that take the slow one; a step in which ONE chip overflows takes
    the slow branch on all four (it returns: nothing hangs)."""
    with jax.default_matmul_precision("highest"):
        params, x, top_w = layer_and_rows()
        top_e = LOADS[load]()
        mesh = mesh_of(4)
        assert moe.exchange_bound(N // 4 * K, 4) == 64
        one_device, exchanged = both_paths(top_e, mesh)

        (_, want), want_grads = value_and_grads(one_device)(
            params, x, top_w)
        (_, (got, record)), grads = value_and_grads(exchanged)(
            params, x, top_w)
        assert_close(got, want, "y")
        assert_close(got, loop_reference(params, x, top_w, top_e),
                     "y against the loop")
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(want_grads)):
            if np.abs(np.asarray(b)).max() > 0:
                assert_close(a, b, "gradient")
    assert np.asarray(record["exchange_bounded"]).tolist() == \
        [BOUNDED[load]] * 4
    counts = np.asarray(record["tokens_per_expert"])
    assert counts.sum() == N * K
    np.testing.assert_array_equal(
        record["rows_received"], counts.reshape(4, -1).sum(-1))
    rounds = 1 if BOUNDED[load] else 2
    assert np.asarray(record["exchange_rows_sent"]).tolist() == \
        [rounds * 3 * 64] * 4
    if load == "all_to_one_chip":
        assert np.asarray(record["rows_received"]).tolist() == \
            [N * K, 0, 0, 0]
        assert np.asarray(record["exchange_rows_needed"]).tolist() == \
            [0, 128, 128, 128]
        assert np.asarray(record["exchange_pairs"]).tolist() == \
            [0, 64, 64, 64]


def ragged_all_to_all_from_gathers(operand, output, input_offsets,
                                   send_sizes, output_offsets, recv_sizes,
                                   *, axis_name, axis_index_groups=None):
    """`jax.lax.ragged_all_to_all` as its documentation defines it, for
    XLA:CPU, which has none: every shard gathers every shard's operand and
    offsets, and row q of its output is the row of the source whose run
    covers q (`recv_sizes`: the receiver's own word for how long each run
    is), or `output`'s where nothing lands."""
    me = jax.lax.axis_index(axis_name)
    runs = recv_sizes.size // jax.lax.axis_size(axis_name)   # a pair

    def for_me(told):       # [P, P * runs] -> the senders' runs for me
        return jax.lax.dynamic_slice_in_dim(
            jax.lax.all_gather(told, axis_name), me * runs, runs,
            axis=1).reshape(-1)

    operands = jax.lax.all_gather(operand, axis_name)        # [P, rows, d]
    starts, lands = for_me(input_offsets), for_me(output_offsets)
    q = jnp.arange(output.shape[0])
    covers = (q >= lands[:, None]) & (q < (lands + recv_sizes)[:, None])
    run = covers.argmax(0)
    row = jnp.clip(starts[run] + q - lands[run], 0, operand.shape[0] - 1)
    return jnp.where(covers.any(0)[:, None], operands[run // runs, row],
                     output)


RAGGED_BOUNDED = {"all_to_one_chip": 0, "one_chip_overflows": 1, "spread": 1,
                  "at_the_bound": 1}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_ragged_exchange_sends_the_rows_it_has(load, monkeypatch):
    """The TPU's lowering of the one bounded round (`moe.exchange_impl`:
    each shard's sorted rows through `ragged_all_to_all`) forced onto the
    CPU's mesh with the collective emulated: output and gradients against
    the one-device sorted path and the reference's loop, the rows sent
    are the rows needed, and the branch by what a chip RECEIVES (chip 3's
    1.75 of the mean fits the receive buffer of twice the mean; every row
    to one chip does not)."""
    monkeypatch.setattr(moe, "exchange_impl", lambda mesh: "ragged")
    monkeypatch.setattr(jax.lax, "ragged_all_to_all",
                        ragged_all_to_all_from_gathers)
    with jax.default_matmul_precision("highest"):
        params, x, top_w = layer_and_rows()
        top_e = LOADS[load]()
        mesh = mesh_of(4)
        one_device, exchanged = both_paths(top_e, mesh)

        (_, want), want_grads = value_and_grads(one_device)(
            params, x, top_w)
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda *a: exchanged(*a)[0], argnums=(0, 1, 2)))(
                params, x, top_w))
        (_, (got, record)), grads = value_and_grads(exchanged)(
            params, x, top_w)
        assert_close(got, want, "y")
        assert_close(got, loop_reference(params, x, top_w, top_e),
                     "y against the loop")
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(want_grads)):
            if np.abs(np.asarray(b)).max() > 0:
                assert_close(a, b, "gradient")
    # every permutation a gather in the backward pass too
    assert "scatter" not in jaxpr
    bounded = RAGGED_BOUNDED[load]
    assert np.asarray(record["exchange_bounded"]).tolist() == [bounded] * 4
    counts = np.asarray(record["tokens_per_expert"])
    assert counts.sum() == N * K
    np.testing.assert_array_equal(
        record["rows_received"], counts.reshape(4, -1).sum(-1))
    needed = np.asarray(record["exchange_rows_needed"])
    # chip c's slots for its own experts stay: its tokens are rows c*64..
    own = [int(((np.asarray(top_e)[c * 64:(c + 1) * 64] // 2) == c).sum())
           for c in range(4)]
    assert needed.tolist() == [N // 4 * K - o for o in own]
    sent = np.asarray(record["exchange_rows_sent"])
    if bounded:
        np.testing.assert_array_equal(sent, needed)
    else:       # the dense rounds that take any load, as on the CPU
        assert sent.tolist() == [2 * 3 * 64] * 4
    if load == "one_chip_overflows":
        # chip 0's 128 slots on top of its near-uniform share of the rest
        assert 192 < np.asarray(record["rows_received"])[3] <= 256


# The smallest layer whose receive buffer `megablox` takes: 4 x 128 rows
# (`exchange_bound(256, 4)`, one row tile of 512 a chip) of widths that are
# one lane tile, 128 tokens a chip.
POISONED_N, POISONED_D = 512, 128
RESULTS = ("y", "d_x", "d_top_w", "d_w_first", "d_w_down")


@functools.lru_cache(maxsize=None)
def behind_a_poisoned_exchange(load):
    """The ragged round as the chip runs it, on the CPU's mesh, with every
    row that nothing writes holding nan: the collective emulated
    (`ragged_all_to_all_from_gathers` leaves `output` where no run lands),
    `jax.lax.empty` a nan fill (the receive buffer's rows past the
    received, forward, and the cotangent buffer's, backward) and
    `megablox` the grouped matmul, its kernels interpreted (the
    interpreter starts a kernel's output as nan, so the rows no grid step
    visits stay so, as the chip leaves them whatever the memory held).
    -> (`RESULTS` of the exchange, of the one-device sorted path, y of the
    reference's loop, the routing record)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops

    n, d = POISONED_N, POISONED_D
    assert moe.exchange_bound(n // 4 * K, 4) * 4 == moe.GMM_ROWS
    assert moe.gmm_tiles(moe.GMM_ROWS, d, 2 * d) and moe.gmm_tiles(
        moe.GMM_ROWS, d, d)
    params, x, top_w = layer_and_rows(n=n, d=d, f=d)
    top_e = LOADS[load](n)
    mesh = mesh_of(4)
    one_device, exchanged = both_paths(top_e, mesh)

    def results(y, grads):
        p, dx, dw = grads
        return y, dx, dw, p["w_gateup"], p["w_down"]

    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = value_and_grads(one_device)(
            params, x, top_w)
        looped = loop_reference(params, x, top_w, top_e)
        gmm = ops.gmm
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe, "exchange_impl", lambda mesh: "ragged")
            patch.setattr(jax.lax, "ragged_all_to_all",
                          ragged_all_to_all_from_gathers)
            patch.setattr(moe, "grouped_matmul_impl",
                          lambda *a, **kw: "megablox")
            patch.setattr(ops, "gmm",
                          lambda *args: gmm(*args, interpret=True))
            patch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
                shape, jnp.nan, dtype))
            moe._ragged_exchange.cache_clear()   # it keeps the fill it saw
            try:
                (_, (got, record)), grads = value_and_grads(exchanged)(
                    params, x, top_w)
            finally:
                moe._ragged_exchange.cache_clear()
    return (results(got, grads), results(want, want_grads), looped,
            jax.tree.map(np.asarray, record))


@pytest.mark.parametrize("at", range(len(RESULTS)), ids=RESULTS)
@pytest.mark.parametrize("load", ["spread", "at_the_bound"])
def test_the_rows_past_the_received_are_never_read(load, at):
    """The grouped matmuls behind the exchange are handed the held
    experts' groups and nothing else, so under `megablox` nothing zeroes
    the receive buffer's rows past the received, nor those rows of any
    buffer behind it, forward or backward. With all of them poisoned the
    layer's output and every gradient is finite and is the one-device
    sorted path's and the reference's: no reader reaches them."""
    got, want, looped, record = behind_a_poisoned_exchange(load)
    # the ragged round ran, and left rows that nothing wrote on every chip
    assert record["exchange_bounded"].tolist() == [1] * 4
    assert record["rows_received"].sum() == POISONED_N * K
    assert 0 < record["rows_received"].min()
    assert record["rows_received"].max() < moe.GMM_ROWS
    assert np.isfinite(np.asarray(got[at])).all(), RESULTS[at]
    assert_close(got[at], want[at], RESULTS[at])
    if RESULTS[at] == "y":
        assert_close(got[at], looped, "y against the loop")


def test_exchange_impl_by_what_the_mesh_says():
    assert moe.exchange_impl(mesh_of(4)) == "buckets"


def test_the_held_shares_add_up_to_the_exchange_and_the_uncut_layer():
    """`moe_ffn` as a held share at each of the four offsets (the one-chip
    path of the share cells) sums to what the exchange gives and to the
    reference's uncut layer."""
    with jax.default_matmul_precision("highest"):
        params, x, _ = layer_and_rows(4)
        params["w_router"] = params["w_router"] * 40.0
        def ffn(p, x, **kw):
            return jax.jit(lambda p, x: moe.moe_ffn(p, x, **kw))(p, x)

        whole, routing = ffn(params, x, num_selected=K)
        held = E // 4
        shares = []
        for c in range(4):
            share = dict(params,
                         w_gateup=params["w_gateup"][c * held:(c + 1) * held],
                         w_down=params["w_down"][c * held:(c + 1) * held])
            y, r = ffn(share, x, num_selected=K, expert_offset=c * held)
            shares.append(y)
            np.testing.assert_array_equal(
                r["tokens_per_expert"],
                routing["tokens_per_expert"][c * held:(c + 1) * held])
        exchanged, record = ffn(params, x, num_selected=K, mesh=mesh_of(4),
                                rules=RULES)
        assert_close(sum(shares), exchanged, "shares against the exchange")
        assert_close(exchanged, whole, "exchange against one device")
        _, top_w, top_e = moe.route(params["w_router"], x, K, True)
        assert_close(exchanged, loop_reference(params, x, top_w, top_e),
                     "exchange against the uncut layer")
    assert int(record["dropped"]) == 0
    np.testing.assert_array_equal(record["tokens_per_expert"],
                                  routing["tokens_per_expert"])


def test_exchange_bound_is_twice_the_uniform_share_in_tiles():
    assert moe.exchange_bound(8192 * 8, 4) == 32768          # the cell's
    assert 4 * moe.exchange_bound(8192 * 8, 4) % moe.GMM_ROWS == 0
    assert moe.exchange_bound(256, 4) == 128
    assert moe.exchange_bound(128, 4) == 64
    assert moe.exchange_bound(128, 2) is None     # would hold every slot
    assert moe.exchange_bound(100, 4) == 56       # whole sublane tiles


def test_an_expert_mesh_refuses_what_it_cannot_lay_out():
    params, x, _ = layer_and_rows()
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="partly"):
        moe.moe_ffn(params, x, num_selected=K, mesh=mesh,
                    rules=ShardingRules().replace(
                        expert=("data", "fsdp"), batch="fsdp",
                        expert_embed=None))
    share = dict(params, w_gateup=params["w_gateup"][:2],
                 w_down=params["w_down"][:2])
    with pytest.raises(ValueError, match="held share"):
        moe.moe_ffn(share, x, num_selected=K, mesh=mesh_of(4), rules=RULES)


# ---- scopes ---------------------------------------------------------------------


def stripped(hlo_text):
    """`tests/test_model_scopes.stripped`, and every instruction's name by
    its first place in the text: inside a `shard_map` an instruction is
    named after its op_name (`%jvp_jit_take_along_axis__` with the scopes,
    `%jit_take_along_axis_` without), so the names themselves differ where
    the programs do not."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"(?ms)^FileNames$.*?^StackFrames$.*?\n\n", "", text)
    places = {}
    return re.sub(r"%[\w\-.]+", lambda m: places.setdefault(
        m.group(0), f"%{len(places)}"), text)


def test_the_new_scopes_are_metadata_only(monkeypatch):
    cfg = config(remat=True, loss_chunk=16, layer_pattern="WL", n_layers=2)
    mesh = mesh_of(4)

    def lowered():
        params = jax.eval_shape(
            lambda: Transformer.init(jax.random.key(0), cfg))
        return jax.jit(jax.grad(lambda p, b: Transformer.loss(
            p, b, cfg, mesh=mesh, rules=RULES))).lower(
                params, {"tokens": jax.ShapeDtypeStruct((4, 65), jnp.int32)})

    def compiled():
        return lowered().compile().as_text()

    # the tables of constant positions are folded by XLA:CPU: their
    # scopes are read off the lowered module
    names = lowered().as_text(debug_info=True)
    for scope in ("rope/plain", "rope/yarn"):
        assert re.search(rf"[/(]{scope}[/)]", names), scope
    with_scopes = compiled()
    for scope in ("moe/exchange", "moe/dispatch", "moe/experts",
                  "moe/combine", "attention/window", "attention/full"):
        assert f"/{scope}/" in with_scopes, scope
    exchanges = [line for line in with_scopes.splitlines()
                 if re.search(r" all-to-all(-start)?\(", line)]
    assert exchanges and all("moe/exchange" in line for line in exchanges)

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = compiled()
    assert "moe/exchange" not in without
    assert stripped(with_scopes) == stripped(without)


# ---- the faults -------------------------------------------------------------------

TINY = load_json(os.path.join(BENCH_DIR, "rehearsal", "configs",
                              "tiny-mellum2.json"))
MIX = load_json(os.path.join(BENCH_DIR, "traffic", "rehearsal_tiny.json"))


@pytest.fixture(scope="module")
def fault_rows():
    return {row["variant"]: row for row in faults.read(TINY, MIX, 3)}


@pytest.mark.parametrize("name", faults.FAULTS + faults.PRECISIONS)
def test_every_fault_is_caught_at_the_small_size(fault_rows, name):
    """Each fault moves logits or loss by more than the limits (the
    rehearsal's are looser than the cell's: what passes them passes the
    cell's); bf16 operands pass."""
    row = fault_rows[name]
    assert row["correct"] == (name == "bfloat16"), row
    if name != "bfloat16":
        assert row["rel_l2"] > 0.05 or row["loss_diff"] > 0.05, row
