"""Mixture-of-Experts FFN: a top-k router over expert MLPs.

The reference has no in-tree MoE/expert parallelism (SURVEY.md §2.4 "EP:
Absent"); this is the TPU-native capability filling that row. An expert
is what its weights say: gated, `act(x W_gate) * (x W_up)` then `W_down`
(`w_gateup [.., d, 2, f]`, the dense MLP's), or plain, `act(x W_1)` then
`W_2` (`w_up [.., d, f]`); `act` is `silu` or `relu2` (`relu(x)^2`).

Two routers (`route`, float32 whatever the compute dtype): **softmax**
probabilities, top-k of them, optionally renormalised, with the
load-balancing loss (`load_balancing_loss`); **sigmoid** scores, the
choice by score + a per-expert bias that is a buffer (no gradient reaches
it: the weights are the chosen scores without it), renormalised,
times a scaling factor, no aux loss. The sigmoid router's choice may be
**group-limited** (`n_group`, `topk_group`): the E experts in `n_group`
equal groups, a group ranked by the sum of its two largest scores (with
the bias), a token's top-k taken among the experts of its `topk_group`
best groups only; `groups_chosen` in the routing record counts the
tokens that kept each group.

**A share of the experts.** `moe_ffn` is told which experts it holds by
what it is given: `w_gateup` / `w_down` carry the held experts only (a
contiguous run that starts at `expert_offset`), the router keeps all E
outputs and its k a token. It computes the held experts' part of the
result for the token-slots routed to them; slots routed to an expert held
elsewhere run through nothing here, add nothing, and are counted
(`slots_elsewhere`), not dropped: they are the work of the chips that
hold those experts, and no code here stands in for them or their traffic.
With every expert held this is the whole layer. A **shared expert**
(`w_shared_gateup`, `w_shared_down`) is one more gated FFN that every
token passes, added to the routed sum. **Experts in a latent**
(`w_latent_down [d, r]`, `w_latent_up [r, d]`): the stream is projected
down once a token before the dispatch and the combined sum up once after
it, so the rows that are sorted, gathered and multiplied are r wide; the
router and the shared expert read the stream itself.

Two lowerings of the dispatch, chosen by what the code sees at trace
time (no option):

- **sorted, dropless** (no mesh, or an `expert` mesh axis of 1): the N·k
  token-slots are sorted by expert id, the held experts first (`sort`),
  counted (a binary search per expert), gathered into expert order, run
  through two grouped matmuls over the ragged groups
  (`grouped_matmul_impl`: pallas `megablox.gmm` on a TPU where its tiles
  fit the shapes, else `jax.lax.ragged_dot`), and gathered back by the
  inverse permutation into a weighted sum over each token's k slots. No
  token is ever dropped, and nothing is larger than `[N·k, max(d, 2f)]`:
  the only `[N, E]` tensors are the router's logits and scores. The
  grouped matmuls' work follows the slots received: `megablox.gmm` takes
  the group sizes with the held experts' weights and visits only the row
  tiles of the groups it holds (rows of other groups come back zero);
  `ragged_dot` gets the leading groups' rows and the rest are masked.
  A token picks an expert at most once, so at most min(k, H) of its k
  slots can be held: where H < k the path past the router keeps, per
  token, the min(k, H) slots that sort first (the held ones among them:
  none is ever dropped) and sorts N·min(k, H) slots, not N·k.
  **Past the sort a held share runs over the rows it received.** The held
  experts' groups sort first, so their rows are the leading
  `R = counts[:H].sum()` of the sorted order and the device knows R
  before the gather. Shapes are static, so the run is a bound from the
  shapes (`row_bound`: B, twice what H of E experts receive under uniform
  routing, in whole row tiles) and one `lax.cond` on `R <= B` a layer and
  step: the gather, the grouped matmuls (group sizes `[counts[:H]…,
  B − R]`), the activation and `combine`'s backward run over B rows, and
  the two sums that come back to the tokens (`combine`'s forward,
  `slots_of`'s backward) are formed from those B rows (`_by_token`,
  `_permutes`' `token_sums`: the run gathered once into token order, a
  token's adjacent rows added, one row a token gathered from that; B + N
  indices, where the inverse permutation's N·min(k, H) would mostly read
  rows past B, which are zeros). A step whose held rows exceed B runs
  over all N·min(k, H) rows, the path of a layer with every expert held
  with `ragged_dot` for its grouped matmuls: nothing is ever dropped, and
  `rows_bounded` in the routing record says which ran. Where every
  expert is held or B would pass half the rows no `cond` is traced.
  The kernel's tiles follow from each call's shapes (`gmm_tiles`: for
  the contraction and for the columns the largest multiple of 128 that
  divides the width, up to what the chip's VMEM takes, `GMM_WIDEST`). Both
  permutations are gathers in the backward pass too (`_permutes`: a
  permutation's transpose is its inverse), so no row is scattered.
- **sorted, dropless, exchanged** (an `expert` mesh axis above 1): the
  same path under `shard_map`, one shard a device (`_exchange_ffn`). The
  router runs outside it, row by row under GSPMD, as it does on one
  device. Where the tokens are divided over the experts' axis (the
  `batch` or `seq` rule names it) each shard sorts ITS n·k slots by
  expert id, which is by destination too (a shard's E/P experts are a
  contiguous run of ids), tells every shard how many rows it sends for
  each of its experts (an all-to-all of `[P, E/P]` int32), sends each
  shard the rows of its experts, which then lie there by expert (an
  expert's rows from source 0, 1, ...), runs the grouped matmuls over
  them with its own experts (`megablox` per device, as on one device),
  and sends the results back the way they came; `combine` reads them by
  the inverse permutation. How the rows travel is what the platform
  offers (`exchange_impl`, at trace time). **Ragged**, on a TPU: the
  shard's n·k sorted rows are the send buffer as they are, E runs one
  behind the other, and `jax.lax.ragged_all_to_all` puts the run of
  expert e straight where that expert's shard keeps this source's rows
  for e, in a receive buffer of `P · exchange_bound` rows (twice a
  shard's mean total) that is in the grouped matmul's order as it
  arrives; each shard has told the others where (one more int32
  all-to-all, `[P, 2, E/P]`: the places on its two sides). The rows that
  leave a shard are the rows routed elsewhere, nothing is gathered on the
  receiving side, and what the bound bounds is a shard's RECEIVED total.
  The buffer's rows past the received belong to no group: the grouped
  matmuls are handed the held experts' groups and nothing else, so under
  `megablox` no kernel visits those rows and no pass zeroes them (the
  buffer is `lax.empty`, and so in effect is every buffer behind it, the
  cotangents' too: on the v5e the zeroing was two selects over the whole
  static buffer a `gmm`, 33.6 ms a step, PERF.md section 6, PR 64); the
  exchange back moves the received runs alone.
  **Buckets**, elsewhere (XLA:CPU has no ragged all-to-all): one bucket
  of `exchange_bound` rows a (from, to) pair (twice the uniform share
  n·k/P, in whole tiles) through a fixed-shape `all_to_all`, regrouped by
  expert by a gather from 64-entry tables (the sources' runs are already
  in expert order) and ungrouped by another on the way back: the send
  and the receive buffers are `[P · bound, d]`, so at the uniform load
  half of what travels and of what is gathered is padding, and the bound
  bounds every bucket. No `[N, E, C]` tensor and no token dropped: a step
  in which a receive buffer (a bucket) of some shard would overflow
  takes, on EVERY shard (a `pmax` of the overflow decides the one
  `lax.cond` a layer: a collective in a branch that some shards skip
  hangs the gang), the exchange in `ceil(n·k / bound)` rounds of dense
  buckets, which takes any load in the memory of one round.
  `exchange_bounded` says which ran. Gradients pass through both
  exchanges (an all-to-all's transpose is the all-to-all back, ragged or
  dense) and every permutation stays a gather in the backward pass. Where
  the tokens are NOT divided over the experts' axis (its shards hold the
  same tokens) nothing is exchanged: each shard is the held share above at
  its own offset and the shares are summed (`psum`).

Scopes inside the caller's `moe` (PERF.md section 3): `moe/router`
(logits, scores, top-k), `moe/dispatch` (sort, counts, the `cond` on
the rows received, gather),
`moe/experts` (the grouped matmuls and silu-mul), `moe/combine` (the
gather back and the weighted sum; the caller adds the residual there),
`moe/shared` (the shared expert), `moe/latent` (the two projections
around experts that live in a latent), `moe/exchange` (on an expert mesh:
the counts' and the offsets' all-to-alls, the overflow's `pmax` and both
row exchanges, forward and backward).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

from ray_tpu.parallel.sharding import (ShardingRules, spec_entry_size,
                                       with_logical_constraint)

# The name (`jax.ad_checkpoint.checkpoint_name`) of what a checkpointed
# expert layer keeps of its routing, as `ops.attention.FLASH_RESIDUALS` is
# of the attention kernel's: the router's logits `[N, E]` f32, the chosen
# experts `[N, k]` int32 and their scores `[N, k]` f32 as read off them
# (`route`); `keep` `[N, held]` where fewer experts are held than a token
# picks, and the sort's `order`, `inverse` `[N·min(k, held)]` and `counts`
# `[E]` (`_sorted_ffn`), all int32. `Transformer._remat`'s policy saves
# them, so the backward pass runs no router product, no top-k, no read
# of the chosen scores and no sort again: the scores, the weights'
# normalisation and the one-hot through `keep` are remade from these by
# elementwise work. The LOGITS and not the scores: a sigmoid's and a
# softmax's derivative is written in its own output, so the backward asks
# for the un-named value that went into the naming, and remat would make
# it from the product. Outside a `jax.checkpoint` a name is the identity.
ROUTING_RESIDUALS = "routing_residuals"

# Logical specs for shard_pytree / make_train_step param placement.
MOE_PARAM_SPECS = {
    "w_router": ("embed", None),
    "w_gateup": ("expert", "expert_embed", None, "mlp"),
    "w_down": ("expert", "mlp", "expert_embed"),
}


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    gated: bool = True) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = (2.0 / d_model) ** 0.5
    scale_out = (2.0 / d_ff) ** 0.5
    # gate and up fused along an unsharded group axis, as the dense MLP's
    # w_gateup; a plain expert has the one matrix
    first = {"w_gateup": (n_experts, d_model, 2, d_ff)} if gated \
        else {"w_up": (n_experts, d_model, d_ff)}
    return {
        "w_router": jax.random.normal(
            k1, (d_model, n_experts), jnp.float32) * 0.02,
        **{name: jax.random.normal(k2, shape, jnp.float32) * scale_in
           for name, shape in first.items()},
        "w_down": jax.random.normal(
            k3, (n_experts, d_ff, d_model), jnp.float32) * scale_out,
    }


def activation(act: str):
    import jax

    if act == "silu":
        return jax.nn.silu
    if act == "relu2":
        return lambda x: jax.numpy.square(jax.nn.relu(x))
    raise ValueError(f"unknown expert activation {act!r}")


def _first_matmul(params):
    """An expert's first weight: gate and up fused, or the plain one."""
    return params["w_gateup"] if "w_gateup" in params else params["w_up"]


def limit_to_groups(choice, n_group: int, topk_group: int):
    """Group-limited choice: `choice` `[N, E]` with the experts outside
    each token's `topk_group` best of `n_group` groups at -inf, and the
    groups kept `[N, n_group]` bool. A group's rank is the sum of its two
    largest entries; ties go to the lower index, as `top_k`'s do. No
    gradient passes: the choice is read for its ids only."""
    import jax
    import jax.numpy as jnp

    n, e = choice.shape
    grouped = jax.lax.stop_gradient(choice).reshape(n, n_group, e // n_group)
    rank = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [N, n_group]
    _, best = jax.lax.top_k(rank, topk_group)
    kept = jax.nn.one_hot(best, n_group, dtype=jnp.bool_).any(axis=1)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(n, e), kept


def route(w_router, x, num_selected: int, norm_topk: bool, *,
          scoring: str = "softmax", bias=None, routed_scale: float = 1.0,
          n_group: int = 1, topk_group: int = 1):
    """Router in float32 whatever the compute dtype (a rounded logit
    changes WHICH experts a token gets, not only by how much): the scores
    `[N, E]` (softmax probabilities, or independent sigmoids), the top-k
    weights and expert ids `[N, k]`. With a `bias` `[E]` the choice is the
    top-k of score + bias and the weights are the chosen scores without
    it, read off the selection (`_chosen_scores`); the bias is a buffer,
    no gradient reaches it. With `n_group` > 1 the choice is group-limited
    (`limit_to_groups`) and a fourth value comes back: the tokens that
    kept each group, int32 `[n_group]`."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    logits = checkpoint_name(
        jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                   w_router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST), ROUTING_RESIDUALS)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    probs = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    choice = probs if bias is None else probs + jax.lax.stop_gradient(
        bias.astype(jnp.float32))
    if n_group > 1:
        choice, kept = limit_to_groups(choice, n_group, topk_group)
    values, top_e = jax.lax.top_k(choice, num_selected)
    top_e = checkpoint_name(top_e, ROUTING_RESIDUALS)
    # the chosen scores: `top_k`'s own values where nothing was added to
    # them, else read off the selection by compares (`_chosen_scores`: a
    # gather of N·k scalars from [N, E] is 1.8 ms a layer on the v5e at
    # 8,192 x 22 of 512). Kept either way
    top_w = checkpoint_name(
        _scores_at()(probs, jax.lax.stop_gradient(values), top_e)
        if bias is None and n_group == 1
        else _chosen_scores(probs, top_e),
        ROUTING_RESIDUALS)
    if norm_topk:
        top_w = top_w / jnp.maximum(
            top_w.sum(axis=-1, keepdims=True), 1e-9)
    if routed_scale != 1.0:
        top_w = top_w * routed_scale
    if n_group > 1:
        return probs, top_w, top_e, kept.sum(0, dtype=jnp.int32)
    return probs, top_w, top_e


def _chosen_scores(probs, top_e):
    """`take_along_axis(probs, top_e)` `[N, k]` without the gather: column
    e of a row goes to the slot whose id is e, every other term of the sum
    over E is zero, so the sum is exact and the bits are the gather's. Its
    derivative is the same compares (a slot's cotangent goes to column e;
    `top_k` picks no column twice, so that sum is exact too), no
    scatter-add, and reads the ids it is handed, the NAMED ones. XLA makes
    compare, select and sum one pass over `probs`, so nothing `[N, k, E]`
    reaches memory (`tests/test_chip_compile.py` reads the v5e's steps
    for it). Behind a barrier, as the gather was a pass of its own: a
    compiler that fuses the sum into what reads it rounds the weights'
    normalisation one way where remat keeps the scores and another where
    it makes them again (XLA:CPU, a last bit of the loss)."""
    import jax
    import jax.numpy as jnp

    columns = jax.lax.broadcasted_iota(jnp.int32, (1, 1, probs.shape[-1]), 2)
    return jax.lax.optimization_barrier(
        jnp.where(top_e[..., None] == columns, probs[:, None, :], 0).sum(-1))


@functools.lru_cache(maxsize=None)
def _scores_at():
    """`scores_at(probs, values, top_e)`: `top_k`'s values as what they
    are, `take_along_axis(probs, top_e)`. Forward the values `top_k`
    made, no gather (N·k scalars from `[N, E]` are 1.4 ms a layer on the
    v5e at 16,384 x 8 of 64); backward that gather's transpose at the ids
    it is handed, the NAMED ones: `top_k`'s own derivative reads the ids
    it made itself, and remat would run it again for them. For a router
    without a choice bias and group limit only: where something was added
    to the scores `top_k`'s values are not the weights, and
    `_chosen_scores` reads them. Built on first use, as `_permutes`."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def scores_at(probs, values, top_e):
        return values

    def fwd(probs, values, top_e):
        return values, (top_e, jax.ShapeDtypeStruct(probs.shape, probs.dtype))

    def bwd(res, g):
        top_e, like = res
        dprobs, = jax.linear_transpose(
            lambda probs: jnp.take_along_axis(probs, top_e, axis=-1),
            like)(g)
        return dprobs, jnp.zeros_like(g), None   # the ids are integers

    scores_at.defvjp(fwd, bwd)
    return scores_at


def load_balancing_loss(tokens_per_expert, router_prob, top_k: int):
    """`E * sum_e f_e * P_e` as `transformers`' `load_balancing_loss_func`
    computes it: over the tokens of ALL layers together (`[layers, E]`
    inputs, or `[E]` for one layer), P_e the mean router probability of
    expert e, f_e the share of tokens that chose e in each of their k
    slots, summed over the slots. So f sums to k, and the loss is k (not
    1) under uniform routing."""
    import jax.numpy as jnp

    counts = jnp.atleast_2d(tokens_per_expert).astype(jnp.float32)
    n_experts = counts.shape[-1]
    frac = counts.sum(0) * (top_k / jnp.maximum(counts.sum(), 1.0))
    return n_experts * jnp.sum(frac * jnp.atleast_2d(router_prob).mean(0))


def _past_the_end(padded: str):
    """`jnp.take`'s arguments for what an index past the end reads: the
    last row ("clip"), zero ("fill"), or `take`'s own rule ("")."""
    return {"clip": {"mode": "clip"}, "fill": {"fill_value": 0}, "": {}}[
        padded]


@functools.lru_cache(maxsize=None)
def _permutes(padded: str = ""):
    """(slots_of, combine): the two permutations of the sorted path as
    custom_vjp functions, built on first use (jax is imported lazily in
    this package). A permutation's transpose is the inverse permutation,
    so both directions are row gathers and autodiff's scatter-adds never
    appear; a gather from the `[N, d]` side costs half of one from the
    `[N·k, d]` side on the chip (PERF.md section 6, PR 27), which is why
    combine's backward gathers the token's cotangent, not the slots'.
    A step moves rows six times a layer (each direction in the forward,
    in remat's forward and in the backward), and a gather costs by its
    indices, not by the rows that exist (PERF.md section 6, PR 60). Into
    expert order (`slots_of`, twice, and `combine`'s backward): M indices
    into the tokens' `[N, d]`, M the rows the path runs over. Back to the
    tokens (`combine`, twice, and `slots_of`'s backward): with every row
    (M = N·k) N·k indices into `[N·k, d]` by `inverse`; over a bounded run
    (`by_token`, M = `row_bound`'s B) from the run's side, B + N indices
    (`token_sums`), and `combine`'s backward makes a weight's cotangent as
    a row dot on the sorted side where the other keeps `[N, k, d]`.
    `padded`: the exchange's buckets (`_exchange_ffn`), where `order` has
    rows of no slot, written as an index past the end. "clip": one round
    carries every slot, and what a row of no slot holds is never read (an
    index past the end reads the last row: no pass to mask it; on the v5e
    the mask was a pass of its own over the 604 MB buffer, 1.85 ms, twelve
    times a layer). "fill": one of several rounds, where `inverse` too has
    slots of no row (another round's); both read zero."""
    import jax
    import jax.numpy as jnp

    past_the_end = _past_the_end(padded)
    take = functools.partial(jnp.take, axis=0, **past_the_end)

    def token_sums(rows, weights, by_token, k):
        """rows `[m, d]`, a leading run of the sorted order (`row_bound`),
        weights `[m]` in `by_token`'s order -> `[N, d]`: each token's rows
        of the run, weighted and summed in float32. Read from the run's
        side: its m rows gathered once into token order, where a token's
        at most k rows are adjacent; to each the k - 1 rows behind it
        added where they are the same token's (dense work over `[m, d]`,
        one fusion); and one row a token gathered from that: m + N
        indices, where a gather by `inverse` reads N·k that are mostly the
        fill. Another token's row is kept out of a sum by a weight of 0,
        not selected away (the compare is then made on m scalars, not on
        `[m, d]`), and 0 x inf is nan: a row that is not finite reaches
        the sums of the up to k - 1 tokens before it in the run as well
        as its own, where a gather by `inverse` hands it to its own token
        alone. The step's loss is nan either way; which tokens are is
        not the same set."""
        perm, slots, start = by_token
        m = slots.size
        tok = jnp.pad(slots // k, (0, k - 1), constant_values=-1)
        weights = jnp.pad(weights, (0, k - 1))
        by_tok = jnp.take(rows, perm, axis=0, mode="clip")  # [m + k - 1, d]
        acc = by_tok[:m].astype(jnp.float32) * weights[:m, None]
        for i in range(1, k):
            # another token's row enters at weight 0
            same = jnp.where(tok[i:m + i] == tok[:m], weights[i:m + i], 0)
            acc = acc + by_tok[i:m + i].astype(jnp.float32) * same[:, None]
        # a token with no row in the run starts past its end: zero
        return jnp.take(acc.astype(rows.dtype), start, axis=0, fill_value=0)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def slots_of(x, order, inverse, k, by_token=None):
        """x `[N, d]` -> `[M, d]`: row s is the token of sorted slot s
        (slot i of the unsorted order belongs to token i // k). `order` is
        the sorted order `[N·k]` or, with `by_token` (`_by_token`), the
        leading run of it that `by_token` describes; `inverse` always the
        whole inverse permutation."""
        return take(x, order // k)

    def slots_fwd(x, order, inverse, k, by_token=None):
        return slots_of(x, order, inverse, k), (inverse, by_token)

    def slots_bwd(k, res, g):
        inverse, by_token = res
        if by_token is None:
            per_slot = take(g, inverse).reshape(-1, k, g.shape[-1])
            dx = per_slot.astype(jnp.float32).sum(1).astype(g.dtype)
        else:
            dx = token_sums(g, jnp.ones(g.shape[:1], jnp.float32), by_token,
                            k)
        return dx, None, None, None   # the permutations are integers

    slots_of.defvjp(slots_fwd, slots_bwd)

    @jax.custom_vjp
    def combine(ys, top_w, order, inverse, by_token=None):
        """ys `[M, d]` in expert order (all N·k slots, or with `by_token`
        the leading run `order` names), top_w `[N, k]` -> `[N, d]`: each
        token's k expert outputs, weighted and summed."""
        return combine_fwd(ys, top_w, order, inverse, by_token)[0]

    def combine_fwd(ys, top_w, order, inverse, by_token=None):
        n, k = top_w.shape
        if by_token is not None:
            weights = jnp.take(top_w.reshape(-1), by_token[1])
            return token_sums(ys, weights, by_token, k), (
                ys, top_w, order, by_token)
        per_slot = take(ys, inverse).reshape(n, k, ys.shape[-1])
        y = jnp.einsum("nkd,nk->nd", per_slot.astype(jnp.float32), top_w)
        return y.astype(ys.dtype), (per_slot, top_w, order, None)

    def combine_bwd(res, g):
        rows, top_w, order, by_token = res
        k = top_w.shape[1]
        w_sorted = jnp.take(top_w.reshape(-1), order, **past_the_end)
        by_slot = take(g, order // k).astype(jnp.float32)
        dys = (by_slot * w_sorted[:, None]).astype(rows.dtype)
        if by_token is None:     # every slot's row, `[N, k, d]`
            dw = jnp.einsum("nd,nkd->nk", g.astype(jnp.float32),
                            rows.astype(jnp.float32))
        else:
            # the run's rows `[m, d]`: a weight's cotangent is its row's
            # dot with its token's, made on the sorted side in the pass
            # that makes dys and put at its slot: m scalars scattered
            # (0.2 ms on the v5e where a gather of all N·k by `inverse`
            # is 1.0); a slot past the run keeps zero
            dots = jnp.einsum("md,md->m", by_slot, rows.astype(jnp.float32))
            dw = jnp.zeros((top_w.size,), dots.dtype).at[order].set(
                dots, unique_indices=True).reshape(top_w.shape)
        return dys, dw, None, None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return slots_of, combine


def _by_token(order, inverse, m: int, k: int):
    """The leading run of m rows of the sorted order as `_permutes`'
    `token_sums` reads it, by token, all int32: `slots` `[m]`, the run's
    slots in ascending order, so that a token's are adjacent (slot i is
    token i // k: one sort of m keys); `perm` `[m + k - 1]`, the row of
    the run that holds each, then k - 1 entries more for the rows read
    behind the last; `start` `[N]`, where each token's rows begin in that
    order, m for a token with none."""
    import jax
    import jax.numpy as jnp

    slots, perm = jax.lax.sort(
        (order[:m], jnp.arange(m, dtype=jnp.int32)), num_keys=1)
    rows = (inverse < m).reshape(-1, k).sum(1, dtype=jnp.int32)
    start = jnp.where(rows > 0, jnp.cumsum(rows) - rows, m)
    return (jnp.concatenate([perm, jnp.full((k - 1,), m - 1, jnp.int32)]),
            slots, start)


# megablox tiles (rows, contraction, columns) of the grouped matmul. The
# row tile is the one `row_bound` and `exchange_bound` round to. The other
# two are, each for its own width, the LARGEST multiple of 128 up to
# `GMM_WIDEST` that divides it, whatever the width: 1,024 for 2,048 and
# 3,072, 768 for 1,536, 1,152 for 2,304, 896 for an expert width of
# 7 x 128 (896, 1,792, 2,688), 640 for 2,560: on the v5e a wider tile in
# the place of a narrower one was the faster in every call timed (PERF.md
# section 6, PRs 27 and 62). `GMM_WIDEST` is what that chip's VMEM takes:
# megablox hands one look-up to `gmm`, its transpose and `tgmm` alike, the
# compiler gives a kernel's scope 16 MiB, and `tgmm` needs most (its output
# and accumulator are `[tk, tn]`): in bf16, two buffers of each operand and
# of the output and one f32 accumulator are 4,096 t + 8 t^2 bytes at
# (512, t, t), 14.6 MiB at 1,152, which the compiler takes in all three
# kernels with their temporaries, and 17.5 MiB at 1,280, which it refuses.
GMM_ROWS = 512
GMM_WIDEST = 1152


def gmm_tiles(m: int, k: int, n: int):
    """The tiles of one `megablox` call from its own shapes (the kernel's
    forward and its two transposes each look theirs up), None where no
    tile divides."""
    def tile(width: int):
        return max((t for t in range(128, min(width, GMM_WIDEST) + 1, 128)
                    if width % t == 0), default=None)

    tk, tn = tile(k), tile(n)
    return (GMM_ROWS, tk, tn) if m % GMM_ROWS == 0 and tk and tn else None


def grouped_matmul_impl(mesh, rows: int, d_model: int, d_ff: int,
                        gated: bool = True, per_shard: bool = False) -> str:
    """`megablox` (the pallas grouped matmul that ships with JAX) where
    the call runs on one TPU device (the whole program, or `per_shard`:
    one shard of a `shard_map`) and the kernel's tiles fit the
    expert FFN's shapes (`gmm_tiles`), else `ragged_dot`
    (`jax.lax.ragged_dot`: any platform, any shape, and GSPMD can
    partition it, which it cannot a pallas call). Decided at trace time,
    like `Transformer.resolve_attention_impl`."""
    import jax

    tiles = gmm_tiles(rows, d_model, (1 + gated) * d_ff) and gmm_tiles(
        rows, d_ff, d_model)
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    one_device = mesh is None or mesh.size == 1 or per_shard
    return "megablox" if device.platform == "tpu" and one_device and tiles \
        else "ragged_dot"


def exchange_impl(mesh) -> str:
    """How the rows of the one bounded round of the exchange travel
    (`_exchange_ffn`): `ragged`, each shard's sorted rows as they are
    through `jax.lax.ragged_all_to_all`, where the mesh's devices are TPUs;
    `buckets`, a fixed-shape `all_to_all` of buckets of `exchange_bound`
    rows, everywhere else (XLA:CPU has no ragged all-to-all). Decided at
    trace time from what the mesh says, as `grouped_matmul_impl`."""
    return "ragged" if mesh.devices.flat[0].platform == "tpu" else "buckets"


def experts_ffn(xs, w_gateup, w_down, group_sizes, impl: str = "ragged_dot",
                act: str = "silu"):
    """The expert FFN over rows already in expert order: xs `[M, d]`,
    group_sizes `[G]` summing to M at most; w_gateup `[H, d, 2, f]`
    (gated) or `[H, d, f]` (plain), w_down `[H, f, d]` for the H <= G
    experts whose groups come first. What the rows past the held groups
    come back as is the caller's choice. A caller that names them, a group
    more than it has weights (G > H, sizes summing to M), gets zeros:
    `ragged_dot` masks them, `megablox` selects them away, one pass over
    the whole output after each `gmm`, forward and transposed. A caller
    whose sizes stop at the held groups (G = H, summing to less than M)
    gets zeros from `ragged_dot` and from `megablox` whatever the memory
    held: its kernels visit the row tiles of the groups alone and no pass
    follows them, so such a caller reads none of those rows, nor their
    cotangents (`_exchange_ffn`'s ragged round)."""
    import jax

    held, d, f = w_gateup.shape[0], w_gateup.shape[1], w_gateup.shape[-1]
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops

        # fewer weights than groups: the kernel visits the row tiles of
        # the groups it has weights for and zeroes the other rows
        def grouped(lhs, rhs):
            return ops.gmm(lhs, rhs, group_sizes, lhs.dtype, gmm_tiles)
    else:
        if held < group_sizes.shape[0]:
            group_sizes = group_sizes[:held]   # rows past them: zero

        def grouped(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs, group_sizes)

    gu = grouped(xs, w_gateup.reshape(held, d, -1))
    h = activation(act)(gu[:, :f])
    if w_gateup.ndim == 4:
        h = h * gu[:, f:]
    return grouped(h, w_down)


def row_bound(n_tokens: int, k: int, held: int, n_experts: int,
              rows: int) -> Optional[int]:
    """The rows the path past the sort runs over where a share of the
    experts is held: twice what `held` of `n_experts` receive of the
    `n_tokens * k` slots under uniform routing, up to a multiple of
    `GMM_ROWS` (the kernel's row tile). None where every expert is held or
    the run would be over half of the `rows` the sort hands on: the path
    then runs over all of them and no `cond` is traced."""
    if held >= n_experts:
        return None
    tile = n_experts * GMM_ROWS
    bound = -(-2 * n_tokens * k * held // tile) * GMM_ROWS
    return bound if bound <= rows // 2 else None


def _rows_ffn(m: int, impl: str, x, top_w, w_first, w_down, order, inverse,
              counts, by_token=None, *, k: int, act: str):
    """The path past the sort over the m leading rows of the sorted order
    (all of them, or `row_bound`'s run, which then holds every held
    expert's rows and is read back through `by_token`, `_by_token`):
    gather, the expert FFN, the weighted sum per token."""
    import jax
    import jax.numpy as jnp

    slots_of, combine = _permutes()
    held = w_first.shape[0]
    with jax.named_scope("moe/dispatch"):
        if m < order.size:
            order = order[:m]
            # one more group, of no expert: the run's rows past the held
            received = counts[:held]
            counts = jnp.concatenate([received, m - received.sum(
                keepdims=True)])
        else:
            by_token = None       # every row: read back by `inverse`
        xs = slots_of(x, order, inverse, k, by_token)    # [m, d]
    with jax.named_scope("moe/experts"):
        ys = experts_ffn(xs, w_first, w_down, counts, impl, act)
    with jax.named_scope("moe/combine"):
        return combine(ys, top_w, order, inverse, by_token)


def _sorted_ffn(params, x, top_w, top_e, mesh, expert_offset=0,
                act: str = "silu", per_shard: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    n_experts = params["w_router"].shape[1]
    w_first = _first_matmul(params)
    held = w_first.shape[0]
    picked = top_e.shape[1]
    if held < picked:
        # fewer experts held than a token picks, each at most once: of a
        # token's k slots the `held` that sort first hold every held one
        with jax.named_scope("moe/dispatch"):
            _, keep = jax.lax.top_k(
                -((top_e - expert_offset) % n_experts), held)
            keep = checkpoint_name(keep, ROUTING_RESIDUALS)
            top_e = jnp.take_along_axis(top_e, keep, axis=1)
            # the weights through a one-hot: its transpose is no scatter
            top_w = jnp.einsum("nk,nck->nc", top_w, jax.nn.one_hot(
                keep, top_w.shape[1], dtype=top_w.dtype))
    k = top_e.shape[1]
    with jax.named_scope("moe/dispatch"):
        slot_expert = top_e.reshape(-1)                  # [N·k]
        # the held experts' groups first (a shard's offset is traced)
        if not isinstance(expert_offset, int) or expert_offset:
            slot_expert = (slot_expert - expert_offset) % n_experts
        sorted_expert, order = jax.lax.sort(
            (slot_expert, jnp.arange(slot_expert.size, dtype=jnp.int32)),
            num_keys=1, is_stable=True)
        inverse = jnp.argsort(order)
        # group sizes from the sorted ids' boundaries (a binary search
        # per expert; no [N·k, E] one-hot, no scatter)
        counts = jnp.diff(jnp.searchsorted(
            sorted_expert, jnp.arange(n_experts + 1, dtype=jnp.int32))
        ).astype(jnp.int32)
        order, inverse, counts = checkpoint_name(
            (order, inverse, counts), ROUTING_RESIDUALS)
    over = functools.partial(_rows_ffn, k=k, act=act)
    past_the_sort = (x, top_w, w_first, params["w_down"], order, inverse,
                     counts)
    bound = row_bound(x.shape[0], picked, held, n_experts, order.size)
    impl = grouped_matmul_impl(mesh, bound or order.size, w_first.shape[1],
                               w_first.shape[-1], gated=w_first.ndim == 4,
                               per_shard=per_shard)
    if bound is None:
        y = over(order.size, impl, *past_the_sort)
        bounded = jnp.zeros((), jnp.int32)
    else:
        # the held experts' groups sort first, so their rows are the
        # leading counts[:held].sum() and the device knows the number
        # before the gather. The branch over every row is the dropless
        # fallback. It keeps nothing for the backward pass but what it is
        # given (a `cond`'s branches each return the residuals of both,
        # the one not taken as zeros, and its own are N·k rows long), and
        # its grouped matmuls are `ragged_dot`: every program with a
        # second set of pallas kernels traces and lowers each of them
        # anew, 8-11% of a job's warm set-up (PERF.md section 6, PR 36)
        with jax.named_scope("moe/dispatch"):
            fits = counts[:held].sum() <= bound
            # the run by token, for the two sums that come back from it:
            # made here and kept with the routing, not in the branch,
            # which the backward pass runs again
            by_token = checkpoint_name(_by_token(order, inverse, bound, k),
                                       ROUTING_RESIDUALS)
            y = jax.lax.cond(
                fits, functools.partial(over, bound, impl),
                jax.checkpoint(functools.partial(over, order.size,
                                                 "ragged_dot")),
                *past_the_sort, by_token)
            bounded = fits.astype(jnp.int32)
    if held < n_experts:
        counts = counts[:held]
    return y, counts, jnp.zeros((), jnp.int32), bounded


# The bucket one shard sends another, in multiples of the uniform share
# n·k / P of a shard's slots (`exchange_bound`).
EXCHANGE_BOUND = 2


def exchange_bound(slots: int, shards: int) -> Optional[int]:
    """The rows of one bucket of the exchange (`_exchange_ffn`): of a
    shard's `slots` (its n·k) what goes to ONE of the `shards`,
    `EXCHANGE_BOUND` times the uniform share, in whole tiles: a `shards`-th
    of `GMM_ROWS`, so that the `shards` buckets a shard receives are whole
    row tiles of the kernel, or sublane tiles of 8 where the share is
    smaller than that. None where the bucket would hold every slot: it
    cannot overflow then, and no `cond` is traced. `shards` of them are
    the receive buffer and the rows of the grouped matmuls on both paths
    (`exchange_impl`). What it bounds in the one round that carries every
    slot, and what it costs: under **buckets** every (from, to) bucket;
    the send buffer too is `shards * bound` rows, and at the uniform load
    `(shards - 1) * bound` rows leave a shard where
    `slots * (shards - 1) / shards` are needed, `EXCHANGE_BOUND` times as
    many. Under **ragged** a shard's RECEIVED total, `shards * bound` rows
    (`EXCHANGE_BOUND` times the mean: no (from, to) run has a bound of its
    own); the rows sent are the rows needed, and the bound's rows are the
    static shape of the receive buffer and of the grouped matmuls, whose
    kernels visit the rows received."""
    share = -(-EXCHANGE_BOUND * slots // shards)
    tile = max(GMM_ROWS // shards, 8)
    if share < tile:
        tile = 8
    bound = -(-share // tile) * tile
    return bound if bound < slots else None


def _lookup(table, index):
    """`table[index]` for a small 1-D table as compares and a sum, no
    gather (`_chosen_scores`' reason); an index past the table reads 0."""
    import jax
    import jax.numpy as jnp

    columns = jax.lax.broadcasted_iota(jnp.int32, (1, table.size), 1)
    return jnp.where(index[:, None] == columns, table[None, :], 0).sum(-1)


def _round_tables(r, bucket: int, k: int, slot_expert, order, inverse,
                  counts, recv_counts):
    """The index tables of round `r` of the exchange on one shard, all
    int32. Sending: bucket p of the round holds the rows
    `[r * bucket, (r + 1) * bucket)` of the run of the sorted order that
    goes to shard p. `order_send [P * bucket]`: the slot each row of the
    send buffer carries (n·k: none); `pos_send [n·k]`: the row of the
    buffer (sent, and returned) that carries each slot (`P * bucket`: not
    this round). Receiving: source s's bucket holds its rows for this
    shard's experts in expert order (`recv_counts [P, held]` over all
    rounds); `regroup [P * bucket]`: the row of the receive buffer that
    comes to each place of the expert-major order the grouped matmul
    takes, `ungroup [P * bucket]` the inverse (`P * bucket`: no row), and
    `groups [held + 1]`: each held expert's rows this round, then the
    rows of no expert."""
    import jax.numpy as jnp

    shards, held = recv_counts.shape
    size = shards * bucket
    first = r * bucket
    send_rows = counts.reshape(shards, held).sum(1)          # [P]
    starts = jnp.cumsum(send_rows) - send_rows
    j = jnp.arange(bucket, dtype=jnp.int32)
    run = first + j[None, :]                                 # [1, bucket]
    sorted_at = jnp.minimum(starts[:, None] + run, order.size - 1)
    order_send = jnp.where(run < send_rows[:, None],
                           jnp.take(order, sorted_at), order.size)
    shard_of = slot_expert // held                           # [n·k]
    within = inverse - _lookup(starts, shard_of) - first
    pos_send = jnp.where((within >= 0) & (within < bucket),
                         shard_of * bucket + within, size)
    # a source's run for expert e lies at [before, before + count) of its
    # rows for this shard; this round's bucket holds [lo, hi) of it
    before = jnp.cumsum(recv_counts, axis=1) - recv_counts   # [P, held]
    lo = jnp.clip(before - first, 0, bucket)
    hi = jnp.clip(before + recv_counts - first, 0, bucket)
    rows = hi - lo                                           # [P, held]
    groups = rows.sum(0)                                     # [held]
    # expert-major: expert e's rows from source 0, 1, ...
    target = (jnp.cumsum(groups) - groups)[None, :] \
        + jnp.cumsum(rows, axis=0) - rows                    # [P, held]
    source = jnp.arange(shards, dtype=jnp.int32)[:, None] * bucket + lo
    t = jnp.arange(size, dtype=jnp.int32)
    # the 64 blocks in the target's order (e-major): the block of place t
    # is the number of blocks that end at or before it
    ends = (target + rows).T.reshape(-1)
    block = (t[:, None] >= ends[None, :]).sum(-1, dtype=jnp.int32)
    regroup = jnp.where(t < groups.sum(),
                        t + _lookup((source - target).T.reshape(-1), block),
                        size)
    expert = (j[None, :, None] >= hi[:, None, :]).sum(-1, dtype=jnp.int32)
    shift = jnp.where(
        expert[..., None] == jnp.arange(held, dtype=jnp.int32),
        (target - lo)[:, None, :], 0).sum(-1)                # [P, bucket]
    ungroup = jnp.where(expert < held, j[None, :] + shift, size)
    groups = jnp.concatenate([groups, size - groups.sum(keepdims=True)])
    return (order_send.reshape(-1), pos_send, regroup, ungroup.reshape(-1),
            groups.astype(jnp.int32))


@functools.lru_cache(maxsize=None)
def _ragged_exchange(axis, rows_in: int, rows_out: int, filled: bool):
    """`moved(rows, here, there)`: runs of rows between the shards of
    `axis` through `jax.lax.ragged_all_to_all`, `rows [rows_in, d]` into a
    buffer `[rows_out, d]`. `here` and `there` describe the two sides of
    the one exchange, each three int32 `[P · r]`, r runs a pair of
    shards, entry `p · r + j` for run j to (from) shard p: where the run
    starts in this shard's buffer, how many rows it has, and where it
    starts in p's buffer on the other side. Where nothing lands the
    buffer holds zeros if `filled`, else whatever the memory held: for
    readers that never use those rows. The transpose is the same exchange with
    the sides swapped (every row that travelled has one place on each
    side), so the backward pass is that call: no cotangent for the receive
    buffer, and none of the masks and offset all-to-alls jax's own
    transpose rule puts around it."""
    import jax
    import jax.numpy as jnp

    # on a TPU `lax.empty` allocates and writes nothing (a fill of the
    # 604 MB buffer read 1.85 ms on the v5e, PERF.md section 6, PR 58)
    buffer = jnp.zeros if filled else jax.lax.empty

    @jax.custom_vjp
    def moved(rows, here, there):
        start, size, lands = here
        return jax.lax.ragged_all_to_all(
            rows, buffer((rows_out,) + rows.shape[1:], rows.dtype),
            start, size, lands, there[1], axis_name=axis)

    def fwd(rows, here, there):
        return moved(rows, here, there), (here, there)

    def bwd(res, g):
        here, there = res
        back = _ragged_exchange(axis, rows_out, rows_in, filled)
        return back(g, there, here), None, None

    moved.defvjp(fwd, bwd)
    return moved


@functools.lru_cache(maxsize=None)
def _regrouped(padded: str = "fill"):
    """`regrouped(rows, index, back)`: `rows[index]`, an index past the end
    reading zero ("fill") or the last row ("clip": what a row of no slot
    holds is never read, `_permutes`); its transpose is the gather by
    `back`, the inverse permutation (`_permutes`' reason)."""
    import jax
    import jax.numpy as jnp

    take = functools.partial(jnp.take, axis=0, **_past_the_end(padded))

    @jax.custom_vjp
    def regrouped(rows, index, back):
        return take(rows, index)

    def fwd(rows, index, back):
        return regrouped(rows, index, back), (index, back)

    def bwd(res, g):
        index, back = res
        return take(g, back), None, None

    regrouped.defvjp(fwd, bwd)
    return regrouped


def _axes_above_one(rules: ShardingRules, logical: str, mesh):
    entry = rules.mesh_axes(logical)
    axes = entry if isinstance(entry, tuple) else (entry,)
    return tuple(a for a in axes if a and mesh.shape.get(a, 1) > 1)


def _exchange_ffn(params, x, top_w, top_e, mesh, rules: ShardingRules,
                  act: str = "silu"):
    """The sorted path on a mesh whose experts' axis is above 1 (module
    docstring): x `[N, d]`, top_w and top_e `[N, k]` as `route` made them,
    the experts' leaves `[E, ...]` sharded by expert. -> (y `[N, d]`,
    record): `tokens_per_expert [E]` over all shards, and a shard each
    (`[shards]`, the mesh's token and expert shards in order)
    `rows_received` (rows its experts ran, its own among them),
    `exchange_rows_sent` (rows that left it, the buckets' padding
    included: under the ragged exchange the rows needed),
    `exchange_rows_needed` (its slots routed to other shards),
    `exchange_pairs` (its distinct (token, other shard) pairs: the least
    any exchange must move) and `exchange_bounded` (1: the one bounded
    round; 0: the rounds that take any load)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from jax.sharding import PartitionSpec as P

    w_first, w_down = _first_matmul(params), params["w_down"]
    n_experts = params["w_router"].shape[1]
    ep = _axes_above_one(rules, "expert", mesh)
    over = tuple(dict.fromkeys(_axes_above_one(rules, "batch", mesh)
                               + _axes_above_one(rules, "seq", mesh)))
    shards = spec_entry_size(ep, mesh)
    divided = set(ep) <= set(over)
    if not divided and set(ep) & set(over):
        raise ValueError(
            f"the experts' mesh axes {ep} lie partly over the tokens' "
            f"{over}: all of them (the tokens travel) or none (the shares "
            f"are summed)")
    if n_experts % shards or x.shape[0] % spec_entry_size(over, mesh):
        raise ValueError(
            f"{n_experts} experts over {shards} shards, {x.shape[0]} tokens "
            f"over {spec_entry_size(over, mesh)}: neither divides")
    held = n_experts // shards
    k = top_e.shape[1]
    gated = w_first.ndim == 4
    every = tuple(dict.fromkeys(over + ep))    # the axes a shard is one of

    def summed(counts):     # over the shards that hold other tokens
        return jax.lax.psum(counts, over) if over else counts

    def held_share(x, top_w, top_e, w_router, w_first, w_down):
        """The shards of the experts' axis hold the same tokens: each is
        the held share at its own offset, and the shares are summed."""
        offset = jax.lax.axis_index(ep) * held
        y, mine, _, _ = _sorted_ffn(
            {"w_router": w_router, "w_down": w_down,
             "w_gateup" if gated else "w_up": w_first},
            x, top_w, top_e, mesh, offset, act, per_shard=True)
        with jax.named_scope("moe/combine"):
            y = jax.lax.psum(y, ep)
        with jax.named_scope("moe/dispatch"):
            counts = summed(jax.lax.all_gather(mine, ep, tiled=True))
        zero = jnp.zeros((1,), jnp.int32)
        return y, counts, mine.sum(keepdims=True), zero, zero, zero, zero

    def exchanged(x, top_w, top_e, w_first, w_down):
        n, d = x.shape
        m = n * k
        with jax.named_scope("moe/dispatch"):
            slot_expert = top_e.reshape(-1)
            sorted_expert, order = jax.lax.sort(
                (slot_expert, jnp.arange(m, dtype=jnp.int32)),
                num_keys=1, is_stable=True)
            inverse = jnp.argsort(order)
            counts = jnp.diff(jnp.searchsorted(
                sorted_expert, jnp.arange(n_experts + 1, dtype=jnp.int32))
            ).astype(jnp.int32)
        with jax.named_scope("moe/exchange"):
            # what each shard sends this one, before any row moves
            recv_counts = jax.lax.all_to_all(
                counts.reshape(shards, held), ep, 0, 0)
        order, inverse, counts, recv_counts = checkpoint_name(
            (order, inverse, counts, recv_counts), ROUTING_RESIDUALS)
        bound = exchange_bound(m, shards)
        bucket = bound or m
        size = shards * bucket
        impl = grouped_matmul_impl(mesh, size, d, w_first.shape[-1],
                                   gated=gated, per_shard=True)
        ragged = exchange_impl(mesh) == "ragged"

        def fast_tables():
            """What the one round that carries every slot reads, kept
            with the routing."""
            if not ragged:
                return _round_tables(0, bucket, k, slot_expert, order,
                                     inverse, counts, recv_counts)
            # the two sides of the ragged exchange (`_ragged_exchange`), a
            # run a (shard, expert): this shard's sorted slots, E runs one
            # behind the other, and its receive buffer in the order the
            # grouped matmul takes, expert-major, an expert's rows from
            # source 0, 1, ...: the rows land regrouped. Each shard tells
            # the others where their runs lie on its two sides
            groups = recv_counts.sum(0)                          # [held]
            target = (jnp.cumsum(groups) - groups)[None, :] \
                + jnp.cumsum(recv_counts, axis=0) - recv_counts  # [P, held]
            starts = (jnp.cumsum(counts) - counts).reshape(shards, held)
            with jax.named_scope("moe/exchange"):
                lands = jax.lax.all_to_all(
                    jnp.stack([target, starts], 1), ep, 0, 0)    # [P,2,held]
            return (groups,
                    (starts.reshape(-1), counts, lands[:, 0].reshape(-1)),
                    (target.reshape(-1), recv_counts.reshape(-1),
                     lands[:, 1].reshape(-1)))

        def ragged_round(x, top_w, w_first, w_down, groups, *sides):
            """The one round that carries every slot, each shard's sorted
            rows sent as they are: the one-device path's two permutations
            around the exchange, no row of no slot on the sending side and
            no regrouping on the receiving one."""
            slots_of, combine = _permutes("clip")
            sorted_side, received_side = sides[:3], sides[3:]
            # the receive buffer's rows past the received are no group's
            # (`groups` stops at the held experts): `megablox` visits no
            # row tile past them, forward and transposes, `tgmm` keeps a
            # boundary tile's foreign rows out by a select in the kernel,
            # and both exchanges move the received runs alone, so what
            # those rows hold, here and in every buffer down to the
            # cotangents, is never read and nothing writes it. Under
            # `ragged_dot`, whose lowering may multiply a row it masks,
            # the buffer starts as zeros
            filled = impl != "megablox"
            with jax.named_scope("moe/dispatch"):
                sent = slots_of(x, order, inverse, k)            # [m, d]
            with jax.named_scope("moe/exchange"):
                xs = _ragged_exchange(ep, m, size, filled)(
                    sent, sorted_side, received_side)
            with jax.named_scope("moe/experts"):
                ys = experts_ffn(xs, w_first, w_down, groups, impl, act)
            with jax.named_scope("moe/exchange"):
                back = _ragged_exchange(ep, size, m, filled)(
                    ys, received_side, sorted_side)
            with jax.named_scope("moe/combine"):
                return combine(back, top_w, order, inverse)

        def one_round(r, x, top_w, w_first, w_down, tables=None,
                      impl="ragged_dot"):
            """Round r of the exchange -> this round's part of y; with
            `tables` the one round that carries every slot."""
            padded = "fill" if tables is None else "clip"
            slots_of, combine = _permutes(padded)
            regrouped = _regrouped(padded)
            with jax.named_scope("moe/dispatch"):
                if tables is None:
                    tables = _round_tables(r, bucket, k, slot_expert, order,
                                           inverse, counts, recv_counts)
                order_send, pos_send, regroup, ungroup, groups = tables
                sent = slots_of(x, order_send, pos_send, k)
            with jax.named_scope("moe/exchange"):
                got = jax.lax.all_to_all(
                    sent.reshape(shards, bucket, d), ep, 0, 0)
            with jax.named_scope("moe/dispatch"):
                xs = regrouped(got.reshape(-1, d), regroup, ungroup)
            with jax.named_scope("moe/experts"):
                ys = experts_ffn(xs, w_first, w_down, groups, impl, act)
            with jax.named_scope("moe/combine"):
                back = regrouped(ys, ungroup, regroup)
            with jax.named_scope("moe/exchange"):
                back = jax.lax.all_to_all(
                    back.reshape(shards, bucket, d), ep, 0, 0)
            with jax.named_scope("moe/combine"):
                return combine(back.reshape(-1, d), top_w, order_send,
                               pos_send)

        def fast_round(x, top_w, w_first, w_down, *tables):
            if ragged:
                return ragged_round(x, top_w, w_first, w_down, *tables)
            return one_round(0, x, top_w, w_first, w_down, tables, impl)

        operands = (x, top_w, w_first, w_down)
        if bound is None:
            with jax.named_scope("moe/dispatch"):
                tables = jax.tree.leaves(fast_tables())
            y = fast_round(*operands, *tables)
            fits = jnp.ones((), jnp.bool_)
            rounds = 1
        else:
            rounds = -(-m // bucket)
            with jax.named_scope("moe/dispatch"):
                tables = jax.tree.leaves(checkpoint_name(
                    fast_tables(), ROUTING_RESIDUALS))
                # one round carries every slot iff nothing overflows: a
                # shard's receive buffer where its rows lie packed, else
                # some (from, to) bucket
                fullest, room = (recv_counts.sum(), size) if ragged else (
                    counts.reshape(shards, held).sum(1).max(), bucket)
            with jax.named_scope("moe/exchange"):
                # on a value every shard agrees on: a collective in a
                # branch that some shards skip hangs the gang
                fits = checkpoint_name(
                    jax.lax.pmax(fullest, ep) <= room, ROUTING_RESIDUALS)

            def any_load(x, top_w, w_first, w_down, *tables):
                # its grouped matmuls are `ragged_dot`, as the held share's
                # fallback's and for its reason (`_sorted_ffn`: a second
                # set of pallas kernels in every program); each round is
                # checkpointed, so the backward pass holds one round's
                # rows at a time, as the forward does
                @jax.checkpoint
                def step(y, r):
                    part = one_round(r, x, top_w, w_first, w_down)
                    return y + part.astype(jnp.float32), None
                y, _ = jax.lax.scan(
                    step, jnp.zeros((n, d), jnp.float32),
                    jnp.arange(rounds, dtype=jnp.int32))
                return y.astype(x.dtype)

            with jax.named_scope("moe/dispatch"):
                y = jax.lax.cond(fits, fast_round, jax.checkpoint(any_load),
                                 *operands, *tables)
        with jax.named_scope("moe/dispatch"):
            mine = jax.lax.axis_index(ep)
            own = jax.lax.dynamic_slice_in_dim(counts, mine * held,
                                               held).sum()
            received = recv_counts.sum(keepdims=True).reshape(1)
            needed = (m - own).reshape(1)
            sent_rows = jnp.where(fits, 1, rounds).astype(jnp.int32) \
                * ((shards - 1) * bucket)
            if ragged:      # the one round sent the rows needed, no more
                sent_rows = jnp.where(fits, needed[0], sent_rows)
            # the distinct (token, other shard) pairs
            to = jax.lax.broadcasted_iota(jnp.int32, (1, 1, shards), 2)
            reached = ((top_e // held)[..., None] == to).any(1)    # [n, P]
            pairs = (reached & (to[0] != mine)).sum(dtype=jnp.int32)
            counts = summed(counts)
        return (y, counts, received, sent_rows.reshape(1), needed,
                pairs.reshape(1), fits.astype(jnp.int32).reshape(1))

    tokens = P(over or None, None)
    experts_in = (P(ep, *[None] * (w_first.ndim - 1)), P(ep, None, None))
    a_shard = P(every)
    outs = (tokens, P(), a_shard, a_shard, a_shard, a_shard, a_shard)
    if divided:
        fn = jax.shard_map(exchanged, mesh=mesh,
                           in_specs=(tokens, tokens, tokens) + experts_in,
                           out_specs=outs, check_vma=False)
        y, *record = fn(x, top_w, top_e, w_first, w_down)
    else:
        fn = jax.shard_map(held_share, mesh=mesh,
                           in_specs=(tokens, tokens, tokens, P())
                           + experts_in,
                           out_specs=outs, check_vma=False)
        y, *record = fn(x, top_w, top_e, params["w_router"], w_first,
                        w_down)
    names = ("tokens_per_expert", "rows_received", "exchange_rows_sent",
             "exchange_rows_needed", "exchange_pairs", "exchange_bounded")
    return y, dict(zip(names, record))


def shared_ffn(w_gateup, w_down, x, act: str = "silu"):
    """The shared expert: one FFN on every row of x `[N, d]`; w_gateup
    `[d, 2, f]` (gated) or `[d, f]` (plain), w_down `[f, d]` in the
    compute dtype."""
    import jax.numpy as jnp

    if w_gateup.ndim == 2:
        h = activation(act)(jnp.einsum("nd,df->nf", x, w_gateup))
    else:
        gu = jnp.einsum("nd,dgf->ngf", x, w_gateup)
        h = activation(act)(gu[:, 0]) * gu[:, 1]
    return jnp.einsum("nf,fd->nd", h, w_down)


def moe_ffn(params: Dict[str, Any], x, *, num_selected: int = 2,
            norm_topk: bool = True, scoring: str = "softmax",
            routed_scale: float = 1.0, expert_offset: int = 0,
            act: str = "silu", n_group: int = 1, topk_group: int = 1,
            mesh=None, rules: Optional[ShardingRules] = None
            ) -> Tuple[Any, Dict[str, Any]]:
    """Top-k routed gated-expert FFN.

    x: `[tokens, d_model]` (flatten `[B, T, D]` before calling); params:
    `w_router [d, E]` (used in float32) and, where the router has one,
    `router_bias [E]`; `w_gateup [H, d, 2, f]`, `w_down [H, f, d]` in the
    compute dtype, the H <= E experts held here, experts `expert_offset`
    to `expert_offset + H` (plain experts: `w_up [H, d, f]` in place of
    `w_gateup`); optionally the shared expert's `w_shared_gateup
    [d, 2, fs]` (plain: `w_shared_up [d, fs]`), `w_shared_down [fs, d]`,
    and `w_latent_down [d, r]`, `w_latent_up [r, d]` around experts that
    take r-wide rows; `act` is every expert's activation. Returns
    `(y, routing)`: y `[tokens, d_model]` in x's dtype, the held experts'
    weighted outputs for the slots routed to them plus the shared expert,
    and the layer's routing record

        tokens_per_expert  int32 [H]  slots routed to each held expert
        slots_elsewhere    int32 []   slots routed to experts not held
                                      (with the above: tokens x k)
        router_prob        f32 [E]    mean router score
        dropped            int32 []   slots of held experts that ran
                                      through none
        rows_bounded       int32 []   1 where the path past the sort ran
                                      over `row_bound`'s run of rows, 0
                                      where over every row
        groups_chosen      int32 [n_group]  tokens that kept each group
                                      (group-limited routing only)

    from which `load_balancing_loss` makes the softmax router's aux loss.
    The top-k weights are renormalised to sum to 1 only if `norm_topk`;
    `scoring`, the bias, `routed_scale`, `n_group` and `topk_group` are
    `route`'s.

    Where `mesh` has an `expert` axis above 1 (module docstring) the
    experts' leaves are all E, sharded by expert, `dropped` is 0 as
    everywhere, and the record has besides, a shard each (`[shards]`),
    `rows_received`, `exchange_rows_sent`, `exchange_rows_needed`,
    `exchange_pairs` and `exchange_bounded` (`_exchange_ffn`).
    """
    import jax
    import jax.numpy as jnp

    rules = rules or ShardingRules()
    n_experts = params["w_router"].shape[1]
    held = _first_matmul(params).shape[0]
    k = min(num_selected, n_experts)
    with jax.named_scope("moe/router"):
        probs, top_w, top_e, *groups = route(
            params["w_router"], x, k, norm_topk, scoring=scoring,
            bias=params.get("router_bias"), routed_scale=routed_scale,
            n_group=n_group, topk_group=topk_group)
        router_prob = probs.mean(axis=0)
    expert_parallel = mesh is not None and spec_entry_size(
        rules.mesh_axes("expert"), mesh) > 1
    if expert_parallel and held != n_experts:
        raise ValueError("an expert mesh axis shards all of a layer's "
                         "experts: a held share (moe_experts_held) runs "
                         "without it")
    rows = x
    if "w_latent_down" in params:
        with jax.named_scope("moe/latent"):
            rows = jnp.einsum("nd,dr->nr", x, params["w_latent_down"])
    exchange = {}
    if expert_parallel:
        y, exchange = _exchange_ffn(params, rows, top_w, top_e, mesh, rules,
                                    act)
        counts = exchange.pop("tokens_per_expert")
        dropped = bounded = jnp.zeros((), jnp.int32)
    else:
        y, counts, dropped, bounded = _sorted_ffn(
            params, rows, top_w, top_e, mesh, expert_offset, act)
    if "w_latent_up" in params:
        with jax.named_scope("moe/latent"):
            y = jnp.einsum("nr,rd->nd", y, params["w_latent_up"])
    shared = params.get("w_shared_gateup", params.get("w_shared_up"))
    if shared is not None:
        with jax.named_scope("moe/shared"):
            y = y + shared_ffn(shared, params["w_shared_down"], x, act)
    if held == n_experts:
        elsewhere = jnp.zeros((), jnp.int32)
    else:
        with jax.named_scope("moe/router"):
            elsewhere = x.shape[0] * k - counts.sum()
    routing = {"tokens_per_expert": counts, "slots_elsewhere": elsewhere,
               "router_prob": router_prob, "dropped": dropped,
               "rows_bounded": bounded, **exchange}
    if groups:
        routing["groups_chosen"], = groups
    return y, routing


def moe_ffn_dense_reference(params: Dict[str, Any], x, *,
                            num_selected: int = 2, norm_topk: bool = True):
    """Un-capacitated dense check: every token runs every expert, and the
    unselected ones get weight 0 (no drops). Used by tests to validate
    the dispatch math."""
    import jax
    import jax.numpy as jnp

    k = min(num_selected, params["w_router"].shape[1])
    probs, top_w, top_e = route(params["w_router"], x, k, norm_topk)
    gu = jnp.einsum("nd,edgf->negf", x, params["w_gateup"])
    h = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
    all_out = jnp.einsum("nef,efd->ned", h, params["w_down"])
    gates = jnp.zeros(probs.shape).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_w)
    return jnp.einsum("ne,ned->nd", gates, all_out)
