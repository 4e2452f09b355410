"""What a rematerialised expert layer keeps of its routing
(`ops/moe.ROUTING_RESIDUALS`, saved by `Transformer._remat`'s one policy):
the router's logits, the chosen experts and their scores, `keep`, the
sort's permutations and counts and, where a held share's bound engages, the
bounded run by token (`_by_token`), so the gradient's program holds the f32
router product, each `top_k` and each `sort` once an expert layer where
`remat_policy="full"` holds each twice. The chosen scores are read with no
gather from the `[N, E]` scores (PR 52: `top_k`'s own values, or compares
against the chosen ids where a bias or a group limit made the choice), and
only the router without either still scatters their cotangents.
Read off the jaxpr of `jax.grad(Transformer.loss)` on the CPU, one tiny
configuration of each kind of the benchmark's expert cells; a dense
configuration names nothing and lowers to the same text with and without
the name in the policy."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from ray_tpu.models import TINY, Transformer, TransformerConfig
from ray_tpu.ops import kda, moe

SEQ = 256
BASE = dict(vocab_size=128, d_model=32, n_heads=2, d_ff=16, max_seq_len=SEQ,
            dtype="float32", loss_chunk=0, remat=True)
# one tiny configuration a kind of expert cell (PERF.md section 4); N = 512
# tokens a step, d_model 32: the router's forward product is the only one
# that makes an [N, E] from an [N, d]
KINDS = {
    # train_olmoe_d1: every expert held, softmax, the aux loss, no `cond`
    "every_expert_softmax": TransformerConfig(
        **BASE, n_layers=2, moe_experts=8, moe_top_k=2, moe_norm_topk=False,
        moe_aux_coeff=0.01),
    # train_glm47flash_ep8_d5: a held share behind the sigmoid router with
    # its choice bias, a shared expert, a dense layer first, the `cond`
    "held_share_sigmoid": TransformerConfig(
        **BASE, n_layers=3, moe_experts=64, moe_top_k=4,
        moe_scoring="sigmoid", moe_routed_scale=1.8, moe_shared_experts=1,
        moe_dense_layers=1, moe_dense_ff=48, moe_experts_held=8,
        moe_expert_offset=8, moe_aux_coeff=0.0),
    # train_nemotron3super_ep64_d11: fewer experts held than a token picks
    # (`keep`), experts in a latent, blocks of unlike sublayers
    "held_below_picked_latent": TransformerConfig(
        **{**BASE, "n_heads": 4}, n_layers=5, layer_pattern="MEM*E",
        n_kv_heads=2, attn_head_dim=8, rope=False, ssm_heads=8,
        ssm_head_dim=4, ssm_groups=4, ssm_state=8, ssm_chunk=16,
        moe_experts=64, moe_top_k=6, moe_scoring="sigmoid",
        moe_routed_scale=5.0, moe_shared_experts=1, moe_shared_ff=24,
        moe_latent=24, moe_act="relu2", moe_gated=False, moe_experts_held=2,
        moe_aux_coeff=0.0),
    # train_ling3flash_ep64_d7: a group-limited choice (two more `top_k`
    # a layer: a group's two largest, the groups) behind Kimi Delta
    # Attention and latent attention; the run `LK` holds two expert layers
    "group_limited_kda": TransformerConfig(
        **BASE, n_layers=5, layer_pattern="kKKLK", kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, qk_norm=True,
        moe_dense_ff=48, kda_heads=2, kda_head_dim=8, kda_chunk=32,
        moe_experts=64, moe_top_k=4, moe_scoring="sigmoid",
        moe_routed_scale=2.5, moe_groups=8, moe_topk_groups=4,
        moe_shared_experts=1, moe_experts_held=8, moe_aux_coeff=0.0),
}
BATCH = {"tokens": jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0,
                                      BASE["vocab_size"])}


@functools.lru_cache(maxsize=None)
def weights(cfg, seed=0):
    """`Transformer.init` with a choice bias that is not zero and an
    embedding of order 1, so the router's logits differ by token. Made
    once a (configuration, seed): nothing below writes into them."""
    params = Transformer.init(jax.random.key(seed), cfg)
    params["embed"] = jax.random.normal(
        jax.random.key(seed + 1), params["embed"].shape, jnp.float32)

    def bias(path, leaf):
        if path[-1].key != "router_bias":
            return leaf
        return 0.05 * jax.random.normal(jax.random.key(seed + 2), leaf.shape)

    return jax.tree_util.tree_map_with_path(bias, params)


def subjaxprs(value):
    """The jaxprs among an equation's parameters: `remat`'s and
    `custom_vjp`'s bodies, a `scan`'s, a `cond`'s branches."""
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from subjaxprs(item)


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in subjaxprs(value):
                yield from equations(sub)


def is_router_product(eqn, n_tokens, cfg):
    """`route`'s einsum: [N, d] x [d, E] -> [N, E] f32 at the highest
    precision. The backward's dW is [d, E] and its dx [N, d]."""
    if eqn.primitive.name != "dot_general":
        return False
    precision = eqn.params["precision"]
    highest = precision is not None and all(
        p == jax.lax.Precision.HIGHEST for p in precision)
    return highest and eqn.outvars[0].aval.shape == (n_tokens,
                                                     cfg.moe_experts)


def readings(jaxpr, cfg):
    """How often the program holds each piece of the routing."""
    n_tokens = BATCH["tokens"].shape[0] * SEQ
    counts = {"sort": 0, "top_k": 0, "router_product": 0, "score_gather": 0,
              "score_scatter": 0, "named": 0}
    scores = (n_tokens, cfg.moe_experts)
    for eqn in equations(jaxpr):
        name = eqn.primitive.name
        if name in ("sort", "top_k"):
            counts[name] += 1
        elif name == "gather" and eqn.invars[0].aval.shape == scores:
            counts["score_gather"] += 1     # the chosen scores, [N, k]
        elif name.startswith("scatter") and \
                eqn.outvars[0].aval.shape == scores:
            counts["score_scatter"] += 1    # their cotangents, into [N, E]
        elif is_router_product(eqn, n_tokens, cfg):
            counts["router_product"] += 1
        elif name == "name" and eqn.params["name"] == moe.ROUTING_RESIDUALS:
            counts["named"] += 1
    return counts


def gradient_jaxpr(cfg, params):
    return jax.make_jaxpr(jax.grad(
        lambda p: Transformer.loss(p, BATCH, cfg)))(params).jaxpr


@pytest.fixture(scope="module")
def programs():
    """Per kind: what the forward alone holds, and the gradient under the
    default policy and under "full"."""
    cache = {}

    def of(kind):
        if kind not in cache:
            cfg = KINDS[kind]
            # shapes are all a trace reads: no weights are drawn
            params = jax.eval_shape(lambda: weights.__wrapped__(cfg))
            forward = jax.make_jaxpr(
                lambda p: Transformer.loss(p, BATCH, cfg))(params).jaxpr
            cache[kind] = {
                "forward": readings(forward, cfg),
                "default": readings(gradient_jaxpr(cfg, params), cfg),
                "full": readings(gradient_jaxpr(
                    cfg.replace(remat_policy="full"), params), cfg)}
        return cache[kind]

    return of


# a scan's body is one expert layer of its run: pieces a layer in the
# forward's program, and how many scans hold an expert layer
PIECES = {
    # without a choice bias the chosen scores are `top_k`'s own values,
    # and `_scores_at`'s backward the scatter-add of their cotangents
    "every_expert_softmax": {"sort": 2, "top_k": 1, "router_product": 1,
                             "score_gather": 0, "score_scatter": 1,
                             "named": 6, "scans": 1},
    # with one, compares against the chosen ids both ways. A held share
    # whose bound engages (all three here) sorts a third time, the run's
    # slots into token order, and keeps that too (`moe._by_token`, PR 60:
    # three more named values)
    "held_share_sigmoid": {"sort": 3, "top_k": 1, "router_product": 1,
                           "score_gather": 0, "score_scatter": 0,
                           "named": 9, "scans": 1},
    "held_below_picked_latent": {"sort": 3, "top_k": 2, "router_product": 1,
                                 "score_gather": 0, "score_scatter": 0,
                                 "named": 10, "scans": 2},
    # the group limit's two `top_k` are the forward's alone: the kept
    # `top_e` is all the backward reads of the choice
    "group_limited_kda": {"sort": 3, "top_k": 3, "router_product": 1,
                          "score_gather": 0, "score_scatter": 0,
                          "named": 9, "scans": 3},
}


@pytest.mark.parametrize("reading", ["sort", "top_k", "router_product",
                                     "score_gather"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_gradient_runs_the_routing_once_a_layer(programs, kind, reading):
    """The sort, the `argsort` and a bounded run's sort into token order,
    `route`'s `top_k` and `keep`'s, the f32 product: once an expert layer under the default policy, as in the
    forward alone; twice under "full", whose backward makes them again.
    A gather of the chosen scores from `[N, E]`: in none."""
    assert KINDS[kind].remat_policy == "attention"     # the default
    seen = programs(kind)
    want = PIECES[kind][reading] * PIECES[kind]["scans"]
    assert seen["forward"][reading] == want
    assert seen["default"][reading] == want
    assert seen["full"][reading] == 2 * want


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_chosen_cotangents_scatter_only_without_a_bias(programs, kind):
    """A scatter-add into `[N, E]` is the backward's alone, once a layer
    whatever the policy, and only where `_scores_at` handed on `top_k`'s
    values: a biased or group-limited router's derivative is compares."""
    seen = programs(kind)
    want = PIECES[kind]["score_scatter"] * PIECES[kind]["scans"]
    assert seen["forward"]["score_scatter"] == 0
    assert seen["default"]["score_scatter"] == want
    assert seen["full"]["score_scatter"] == want


@pytest.mark.parametrize("kind", list(KINDS))
def test_what_is_named_is_what_the_layer_has(programs, kind):
    """The logits, `top_e` and the scores read at them, the two
    permutations and the counts; a bounded run by token (three) where a
    share is held; `keep` only where fewer experts are held than a token
    picks."""
    want = PIECES[kind]["named"] * PIECES[kind]["scans"]
    assert programs(kind)["forward"]["named"] == want
    cfg = KINDS[kind]
    assert (want == 10 * PIECES[kind]["scans"]) == (
        0 < cfg.moe_experts_held < cfg.moe_top_k)


@pytest.mark.parametrize("name", [moe.ROUTING_RESIDUALS,
                                  kda.DELTA_RESIDUALS])
def test_a_dense_layer_names_nothing_and_its_program_stays(monkeypatch,
                                                           name):
    """No router and no delta rule, nothing named: the default policy
    with the routing's name in it, or the delta rule's (PR 65), lowers to
    the text of the policy without, and of "full"."""
    cfg = TINY.replace(remat=True, attention_impl="dense")
    params = Transformer.init(jax.random.key(0), cfg)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}

    def lowered(cfg):
        return jax.jit(jax.grad(
            lambda p: Transformer.loss(p, batch, cfg))).lower(
                params).as_text()

    with_name = lowered(cfg)
    assert readings(jax.make_jaxpr(jax.grad(
        lambda p: Transformer.loss(p, batch, cfg)))(params).jaxpr,
        cfg)["named"] == 0
    assert with_name == lowered(cfg.replace(remat_policy="full"))
    saved = []
    names = jax.checkpoint_policies.save_only_these_names

    def without_the_name(*given):
        saved.append(given)
        return names(*(n for n in given if n != name))

    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        without_the_name)
    assert lowered(cfg) == with_name
    assert name in saved[-1]                       # the policy did ask
