"""Ling-3.0-flash's layers through the normal path (`Transformer.loss`:
Kimi Delta Attention, latent attention without a query latent and with
the QK-norm, a dense MLP or experts under a group-limited sigmoid router,
a held share of the heads and of the experts) against the plain float32
reference `benchmark/reference/ling3_f32.py`, which shares no code with
`ray_tpu`: seeded random weights, small sizes, on the CPU, float32 against
float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the delta rule in chunks with a triangular inverse against
the recurrence step by step, a grouped matmul over sorted rows against a
masked loop over the resident experts, attention whole against attention
by blocks of queries): 1e-4 relative to the largest entry of each compared
array allows that and nothing else. Every published term has a case below
that fails without it.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer

from tests._ling3 import (BENCH_DIR, E, GROUPS, K, KEPT, PATTERN, RTOL,
                          assert_close, batch, config, expert_share, faults,
                          job, published, ref, reference, rel_diff, weights)
from tests._programs import programs

from benchlib.spec import load_json  # noqa: E402 (the path is _ling3's)


# ---- the model against the reference --------------------------------------

SHARES = {"all_held": (0, 0), "share_4_of_16": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("seed", [0, 3])
def test_logits_and_loss_match_the_reference(share, seed):
    held, offset = SHARES[share]
    cfg = config(held, offset)
    params = weights(config(), seed)
    if held:
        params = expert_share(params, held, offset)
    tokens = batch(cfg, seed)
    w = job.to_reference_layout(params, cfg)
    logits = programs(cfg).logits(params, tokens[:, :-1])
    want, chosen = reference(cfg).forward(w, tokens[:, :-1])
    assert_close(logits, want, "logits")
    loss, metrics = programs(cfg).loss(params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.next_token_loss(
        want, tokens[:, 1:]))) <= RTOL
    # the counters: the held experts' columns of the reference's counts,
    # nothing dropped, held + elsewhere = tokens x k, and every token
    # inside exactly KEPT groups
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    n = tokens[:, :-1].size
    first = offset
    np.testing.assert_array_equal(
        np.asarray(metrics["moe_tokens_per_expert"]),
        counts[:, first:first + (held or E)])
    assert int(metrics["moe_dropped"]) == 0
    groups = np.asarray(metrics["moe_groups_chosen"])
    assert groups.shape == (PATTERN.count("K") + PATTERN.count("L"), GROUPS)
    assert (groups.sum(-1) == KEPT * n).all() and (groups <= n).all()
    for top_e in chosen:    # the reference's choice: KEPT groups a token
        in_groups = np.asarray(top_e) // (E // GROUPS)
        assert max(len(set(row)) for row in in_groups.tolist()) <= KEPT
    if held:
        np.testing.assert_array_equal(
            np.asarray(metrics["moe_slots_elsewhere"]),
            n * K - counts[:, first:first + held].sum(-1))


GRAD_SEED = 5


@functools.lru_cache(maxsize=None)
def model_grads():
    """(weights, tokens, loss, gradients) of the uncut model at GRAD_SEED:
    what the reference's gradients and the other programs below are both
    held to, computed once."""
    cfg = config()
    params = weights(cfg, GRAD_SEED)
    tokens = batch(cfg, GRAD_SEED)
    return (params, tokens) + programs(cfg).grads(params, {"tokens": tokens})


def test_gradients_match_the_reference():
    cfg = config()
    params, tokens, loss, grads = model_grads()
    want_loss, want = reference(cfg).loss_and_grads(
        job.to_reference_layout(params, cfg), tokens)
    assert abs(float(loss) - float(want_loss)) <= RTOL
    got = job.to_reference_layout(grads, cfg)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want)
    scale = max(float(jnp.abs(g).max()) for g in flat_want.values())
    for path, leaf in flat_got:
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:   # a buffer: no gradient
            assert not np.asarray(flat_want[path]).any()
            continue
        # against the largest gradient of the model: a leaf whose own
        # gradient is tiny (a gain behind a saturated gate) has rounding's
        # noise at the model's scale
        err = float(jnp.abs(leaf - flat_want[path]).max())
        assert err <= RTOL * max(scale * 1e-2, float(
            jnp.abs(flat_want[path]).max())), (name, err, scale)


def test_remat_and_the_chunked_head_change_nothing():
    params, tokens, base, base_g = model_grads()
    for over in (dict(remat=True), dict(remat=True, remat_policy="full"),
                 dict(loss_chunk=16)):
        loss, g = programs(config(**over)).grads(params, {"tokens": tokens})
        assert abs(float(loss) - float(base)) <= 1e-6, over
        # two programs that sum in other orders, through the chunks'
        # inverses: float32's rounding, not the tolerance of a term
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(base_g)):
            assert_close(a, b, over, rtol=5e-4)


def test_the_group_limit_changes_the_choice():
    cfg = config()
    params = weights(cfg, 0)
    tokens = batch(cfg, 0)
    with_limit = programs(cfg).logits(params, tokens[:, :-1])
    without = programs(config(moe_groups=1, moe_topk_groups=1)).logits(
        params, tokens[:, :-1])
    assert rel_diff(without, with_limit) > 1e-2


# ---- every term is held -------------------------------------------------------


@pytest.fixture(scope="module")
def faulty():
    cfg = config(4, 4)
    params = expert_share(weights(config(), 4), 4, 4)
    tokens = batch(cfg, 4)
    w = job.to_reference_layout(params, cfg)
    model = published(cfg)
    # op by op, as the unchanged copy below runs: it is held to the bit
    return cfg, params, tokens, w, model, ref.forward(
        w, tokens[:, :-1], model)


@pytest.mark.parametrize("name", faults.FAULTS)
def test_every_fault_moves_the_logits(faulty, name):
    """Each term left out of the reference moves the logits by far more
    than the 1e-4 the model is held to, and by more than the cell's limit
    on the relative L2 (benchmark/configs/ling-3.0-flash-ep64-tp4-d7.json;
    on the chip at the published widths too: its `tolerance.why`)."""
    cfg, params, tokens, w, model, base = faulty
    broken, model_, w_ = faults.variant(name, model, w)
    logits = broken.forward(w_, tokens[:, :-1], model_)
    assert rel_diff(logits, base) > 50 * RTOL, name
    limit = load_json(os.path.join(
        BENCH_DIR, "configs", "ling-3.0-flash-ep64-tp4-d7.json"))[
            "tolerance"]["logits_rel_l2"]
    diff = np.asarray(logits - base, np.float64)
    assert np.sqrt((diff ** 2).sum() / (np.asarray(
        base, np.float64) ** 2).sum()) > limit, name
    # and the unchanged copy is the reference
    plain, model_, w_ = faults.variant(None, model, w)
    assert rel_diff(plain.forward(w_, tokens[:, :-1], model_), base) == 0.0


def test_the_system_fails_with_a_term_left_out():
    """The same from the system's side: what it computes without a term
    is off the reference by far more than the tolerance."""
    cfg = config()
    params = weights(cfg, 6)
    tokens = batch(cfg, 6)
    w = job.to_reference_layout(params, cfg)
    want = reference(cfg).forward(w, tokens[:, :-1])[0]
    for over in (dict(qk_norm=False), dict(moe_routed_scale=1.0),
                 dict(kda_gate_lower=-1.0), dict(moe_topk_groups=GROUPS)):
        other = config(**over)
        mine = params
        if "qk_norm" in over:
            mine = dict(params, runs=[[
                {k: v for k, v in sub.items()
                 if k not in ("q_norm", "k_norm")} for sub in run]
                for run in params["runs"]])
        got = programs(other).logits(mine, tokens[:, :-1])
        assert rel_diff(got, want) > 50 * RTOL, over


# ---- what the configuration refuses, by name ----------------------------------


@pytest.mark.parametrize("over,why", [
    (dict(layer_pattern="kKK*K"), "latent attention in the kinds"),
    (dict(kda_heads=0), "kda_heads"),
    (dict(moe_groups=3), "group-limited"),
    (dict(moe_groups=4, moe_topk_groups=5), "group-limited"),
    (dict(moe_groups=16), "group-limited"),
    (dict(moe_scoring="softmax", moe_aux_coeff=0.01), "group-limited"),
    (dict(moe_dense_layers=1), "leading dense run"),
    (dict(layer_pattern="kkklk", moe_experts=E), "expert layers where"),
    (dict(n_kv_heads=2), "one key/value head"),
    (dict(qk_rope_head_dim=3), "even"),
])
def test_what_is_refused_is_refused_by_name(over, why):
    with pytest.raises(ValueError, match=why):
        config(**over)


def test_param_count_and_runs():
    cfg = config(4, 4)
    params = expert_share(weights(config(), 0), 4, 4)
    frozen = Transformer.frozen(cfg)
    n = sum(int(x.size) for x, keep in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)) if not keep)
    assert n == cfg.num_params
    assert cfg.pattern_runs == [("k", 1), ("K", 2), ("LK", 1)]
    from ray_tpu.models.configs import pattern_runs
    assert pattern_runs("kKKKLKK") == [("k", 1), ("K", 3), ("L", 1),
                                       ("K", 2)]
    assert sum(jax.tree.leaves(frozen)) == 3      # a choice bias a run's sublayer
    specs = Transformer.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
