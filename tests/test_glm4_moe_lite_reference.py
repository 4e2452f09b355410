"""GLM-4.7-Flash's block through the normal path (`Transformer.loss`:
latent attention, a leading dense layer, a shared expert, the sigmoid
router with its choice bias and scaling factor, a held share of the
experts) against the plain float32 reference
`benchmark/reference/glm4_moe_lite_f32.py`, which shares no code with
`ray_tpu`: seeded random weights, small sizes, on the CPU, float32 against
float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (fused gate/up matmuls, a grouped matmul over sorted rows
against a masked loop over the resident experts, attention whole against
attention by blocks of queries): 1e-4 relative to the largest entry of
each compared array allows that and nothing else. Every published term
has a case below that fails without it.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.train_step import make_train_step

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402
from tests._programs import programs  # noqa: E402

ref = load_module("reference", "glm4_moe_lite_f32")
job = load_module("jobs", "train_lm_mla_moe")

RTOL = 1e-4
SEQ = 64
E, K = 16, 4


def config(held=0, offset=0, **kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=32,
        max_seq_len=SEQ, dtype="float32", rope_theta=1e6, loss_chunk=0,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=20, moe_experts=E, moe_top_k=K,
        moe_norm_topk=True, moe_scoring="sigmoid", moe_routed_scale=1.8,
        moe_shared_experts=1, moe_dense_layers=1, moe_dense_ff=96,
        moe_experts_held=held, moe_expert_offset=offset, moe_aux_coeff=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"hidden_act": "silu", "rope_scaling": None,
           "attention_bias": False, "hidden_size": cfg.d_model,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_heads,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
           "q_lora_rank": cfg.q_lora_rank,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk, "n_group": 1,
           "topk_group": 1, "topk_method": "noaux_tc",
           "routed_scaling_factor": cfg.moe_routed_scale,
           "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}
    out.update(over)
    return out


def weights(cfg, seed):
    """Random weights with every gain off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place or left out), router logits of order
    1 as at the published width, and a choice bias that is not zero."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 16))
    for run in ("dense_layers", "layers"):
        lay = params[run]
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm"):
            lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                      lay[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    lay = params["layers"]
    lay["w_router"] = lay["w_router"] * 6.0
    lay["router_bias"] = 0.2 * jax.random.normal(
        next(keys), lay["router_bias"].shape)
    return params


def share_of(params, cfg, held, offset):
    """The leaves a chip holding experts offset..offset+held keeps."""
    lay = dict(params["layers"])
    for name in ("w_moe_gateup", "w_moe_down"):
        lay[name] = lay[name][:, offset:offset + held]
    return dict(params, layers=lay)


def batch(cfg, seed, rows=2):
    return jax.random.randint(jax.random.key(100 + seed),
                              (rows, SEQ + 1), 0, cfg.vocab_size)


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


def rel_diff(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


SHARES = {"all_held": (0, 0), "share_4_of_16": (4, 8)}


def reference(cfg):
    """The reference at `cfg`'s published keys under `jax.jit`
    (`tests/_programs.reference`): `.forward(w, tokens)` -> (logits,
    chosen)."""
    return _programs.reference(ref, published, cfg, with_routing=True)


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("seed", [0, 3])
def test_logits_and_loss_match_the_reference(share, seed):
    held, offset = SHARES[share]
    full = config()
    cfg = config(held, offset)
    params = weights(full, seed)
    if held:
        params = share_of(params, full, held, offset)
    tokens = batch(cfg, seed)
    logits = programs(cfg).logits(params, tokens[:, :-1])
    loss, metrics = programs(cfg).loss(params, {"tokens": tokens})
    w = job.to_reference_layout(params, cfg)
    assert sorted(w["layers"][1]["experts"]) == list(
        range(offset, offset + cfg.held_experts))
    ref_logits, chosen = reference(cfg).forward(w, tokens[:, :-1])
    assert_close(logits, ref_logits, "logits")
    assert_close(loss, ref.next_token_loss(ref_logits, tokens[:, 1:]),
                 "loss")
    # the counters: the held experts' columns of the reference's counts,
    # and every other slot counted as routed elsewhere
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    mine = counts[:, offset:offset + cfg.held_experts]
    np.testing.assert_array_equal(metrics["moe_tokens_per_expert"], mine)
    slots = tokens[:, :-1].size * K
    if held:
        np.testing.assert_array_equal(metrics["moe_slots_elsewhere"],
                                      slots - mine.sum(-1))
        assert (metrics["moe_slots_elsewhere"] > 0).all()
    else:
        assert "moe_slots_elsewhere" not in metrics
        assert (mine.sum(-1) == slots).all()
    assert int(metrics["moe_dropped"]) == 0
    assert float(metrics["moe_aux_loss"]) == 0.0


def from_reference_layout(grads, cfg):
    """The reference's gradients back in the program's fused layout."""
    d, nh = cfg.d_model, cfg.n_heads
    nd = cfg.moe_dense_layers

    def stack(gs, f):
        return jnp.stack([f(g) for g in gs])

    def gated(pick):
        return (lambda g: jnp.stack([pick(g)["gate_proj"].T,
                                     pick(g)["up_proj"].T], 1),
                lambda g: pick(g)["down_proj"].T)

    def attention(gs):
        return {
            "attn_norm": stack(gs, lambda g: g["input_layernorm"]),
            "mlp_norm": stack(gs, lambda g: g["post_attention_layernorm"]),
            "wq_a": stack(gs, lambda g: g["q_a_proj"].T),
            "q_a_norm": stack(gs, lambda g: g["q_a_layernorm"]),
            "wq_b": stack(gs, lambda g: g["q_b_proj"].T.reshape(
                cfg.q_lora_rank, nh, -1)),
            "wkv_a": stack(gs, lambda g: g["kv_a_proj_with_mqa"].T),
            "kv_a_norm": stack(gs, lambda g: g["kv_a_layernorm"]),
            "wkv_b": stack(gs, lambda g: g["kv_b_proj"].T.reshape(
                cfg.kv_lora_rank, nh, -1)),
            "wo": stack(gs, lambda g: g["o_proj"].T.reshape(nh, -1, d)),
        }

    dense, sparse = grads["layers"][:nd], grads["layers"][nd:]
    gu, down = gated(lambda g: g["mlp"])
    out_dense = dict(attention(dense), w_gateup=stack(dense, gu),
                     w_down=stack(dense, down))
    sgu, sdown = gated(lambda g: g["shared_experts"])
    out = dict(
        attention(sparse),
        w_router=stack(sparse, lambda g: g["mlp.gate"].T),
        w_shared_gateup=stack(sparse, sgu),
        w_shared_down=stack(sparse, sdown),
        w_moe_gateup=stack(sparse, lambda g: jnp.stack([jnp.stack(
            [e["gate_proj"].T, e["up_proj"].T], 1)
            for _, e in sorted(g["experts"].items())])),
        w_moe_down=stack(sparse, lambda g: jnp.stack(
            [e["down_proj"].T for _, e in sorted(g["experts"].items())])))
    return {"embed": grads["embed_tokens"], "final_norm": grads["norm"],
            "lm_head": grads["lm_head"].T, "dense_layers": out_dense,
            "layers": out}


def test_gradients_match_jax_grad_of_the_reference():
    cfg = config()
    params = weights(cfg, 1)
    tokens = batch(cfg, 1)
    _, grads = programs(cfg).grads(params, {"tokens": tokens})
    w = job.to_reference_layout(params, cfg)
    _, ref_grads = reference(cfg).loss_and_grads(w, tokens)
    # the bias enters the choice only: no gradient on either side
    assert not np.asarray(grads["layers"].pop("router_bias")).any()
    for g in ref_grads["layers"][cfg.moe_dense_layers:]:
        assert not np.asarray(g["e_score_correction_bias"]).any()
    want = from_reference_layout(ref_grads, cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), exp in zip(flat, jax.tree.leaves(want)):
        assert np.abs(np.asarray(exp)).max() > 0, path
        assert_close(got, exp, jax.tree_util.keystr(path), rtol=2e-4)


# ---- the shares add up ---------------------------------------------------


def one_expert_layer(cfg, params, i=0):
    """The program's leaves of expert layer i, as moe_ffn takes them."""
    lay = params["layers"]
    return {"w_router": lay["w_router"][i],
            "router_bias": lay["router_bias"][i],
            "w_gateup": lay["w_moe_gateup"][i],
            "w_down": lay["w_moe_down"][i],
            "w_shared_gateup": lay["w_shared_gateup"][i],
            "w_shared_down": lay["w_shared_down"][i]}


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 16 over 4 shares of 4: the routed parts the four shares give
    for one expert layer, plus the shared expert and the residual counted
    once, are the uncut reference's layer output; the held slots of the
    shares sum to N x k and no slot is in two shares."""
    cfg = config()
    params = weights(cfg, 5)
    x = jax.random.normal(jax.random.key(9), (96, cfg.d_model))
    lw = job.to_reference_layout(params, cfg)["layers"][1]
    pub = published(cfg)
    routed, top_e = ref.routed_experts(x, lw, pub)
    with jax.default_matmul_precision("highest"):
        whole = x + ref.shared_experts(x, lw) + routed
    layer = one_expert_layer(cfg, params)
    kw = dict(num_selected=K, norm_topk=True, scoring="sigmoid",
              routed_scale=cfg.moe_routed_scale)
    total = x
    held_counts, elsewhere = [], []
    for offset in range(0, E, 4):
        share = dict(layer, w_gateup=layer["w_gateup"][offset:offset + 4],
                     w_down=layer["w_down"][offset:offset + 4])
        routed_only = {k: v for k, v in share.items()
                       if not k.startswith("w_shared")}
        y, routing = moe.moe_ffn(routed_only, x, expert_offset=offset, **kw)
        with_shared, _ = moe.moe_ffn(share, x, expert_offset=offset, **kw)
        # what every chip computes alike, counted once
        shared = with_shared - y
        assert_close(shared, moe.shared_ffn(
            layer["w_shared_gateup"], layer["w_shared_down"], x), "shared")
        total = total + y + (shared if offset == 0 else 0.0)
        held_counts.append(np.asarray(routing["tokens_per_expert"]))
        elsewhere.append(int(routing["slots_elsewhere"]))
        assert int(routing["dropped"]) == 0
        # this share's part alone: the reference given the same share
        part, _ = ref.routed_experts(x, dict(lw, experts={
            e: w for e, w in lw["experts"].items()
            if offset <= e < offset + 4}), pub)
        assert_close(y, part, f"share at {offset}")
    assert_close(total, whole, "the shares' sum")
    counts = np.concatenate(held_counts)
    assert counts.sum() == x.shape[0] * K
    # no slot in two shares: each share's counts are the reference's
    # choices of its own experts, and elsewhere is the rest
    want = np.bincount(np.asarray(top_e).reshape(-1), minlength=E)
    np.testing.assert_array_equal(counts, want)
    for share_counts, rest in zip(held_counts, elsewhere):
        assert share_counts.sum() + rest == x.shape[0] * K


# ---- each published term has a case that fails without it ---------------


def _reference_logits(cfg, params, tokens, **over):
    w = job.to_reference_layout(params, cfg)
    return ref.forward(w, tokens[:, :-1], published(cfg, **over))


TERMS = {
    "routed_scaling_factor": dict(cfg=dict(moe_routed_scale=1.0)),
    "norm_topk_prob": dict(cfg=dict(moe_norm_topk=False)),
    "sigmoid_not_softmax": dict(cfg=dict(moe_scoring="softmax")),
    "bias_in_the_choice": dict(zero=("layers", "router_bias")),
    "dense_first_layer_width": dict(cfg=dict(moe_dense_ff=32)),
    "shared_expert": dict(zero=("layers", "w_shared_down")),
    "rope_theta": dict(cfg=dict(rope_theta=1e4)),
}


@functools.lru_cache(maxsize=None)
def intact(seed):
    """(weights, tokens, the program's logits, the reference's) of the
    uncut model at a seed: what every changed term and every fault below
    is read against."""
    cfg = config()
    params = weights(cfg, seed)
    tokens = batch(cfg, seed)
    w = job.to_reference_layout(params, cfg)
    return (params, tokens, programs(cfg).logits(params, tokens[:, :-1]),
            reference(cfg).forward(w, tokens[:, :-1])[0])


@pytest.mark.parametrize("term", TERMS)
def test_a_term_left_out_of_the_program_fails(term):
    """The program with one published term changed no longer matches the
    reference; with it, it does (the tests above)."""
    params, tokens, _, want = intact(2)
    change = TERMS[term]
    broken = config(**change.get("cfg", {}))
    if "moe_dense_ff" in change.get("cfg", {}):
        lay = dict(params["dense_layers"])
        lay["w_gateup"] = lay["w_gateup"][..., :32]
        lay["w_down"] = lay["w_down"][:, :32]
        params = dict(params, dense_layers=lay)
    if "zero" in change:
        run, name = change["zero"]
        params = dict(params, **{run: dict(
            params[run], **{name: jnp.zeros_like(params[run][name])})})
    got = programs(broken).logits(params, tokens[:, :-1])
    assert rel_diff(got, want) > 30 * RTOL, term


def test_the_bias_is_in_the_choice_and_not_in_the_weight():
    cfg = config()
    params = weights(cfg, 4)
    layer = one_expert_layer(cfg, params)
    x = jax.random.normal(jax.random.key(2), (64, cfg.d_model))
    kw = dict(scoring="sigmoid", routed_scale=1.8)
    scores, w, e = moe.route(layer["w_router"], x, K, True,
                             bias=layer["router_bias"], **kw)
    _, w0, e0 = moe.route(layer["w_router"], x, K, True, **kw)
    assert (np.asarray(e) != np.asarray(e0)).any()      # it moves choices
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(e), -1)
    want = 1.8 * chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want, rtol=1e-6)      # without the bias
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.8, rtol=1e-5)
    # a far larger bias on one expert puts it in every token's choice
    # and leaves its weight the plain score's
    big = layer["router_bias"].at[3].set(10.0)
    _, wb, eb = moe.route(layer["w_router"], x, K, True, bias=big, **kw)
    assert (np.asarray(eb) == 3).any(axis=-1).all()
    assert float(np.asarray(wb).max()) <= 1.8


ATTENTION_FAULTS = ["kv_a_norm", "q_a_norm", "shared_rotary_key_head",
                    "rope_on_rotary_columns_only", "rope_on_the_key_head"]


@pytest.mark.parametrize("fault", ATTENTION_FAULTS)
def test_a_fault_in_latent_attention_fails(fault, monkeypatch):
    """The reference with one piece of latent attention changed no longer
    matches the program: each latent norm, the rotary key head shared by
    all heads (a head of its own per head instead), RoPE on the 64 rotary
    columns only (over the whole head instead), RoPE on the key head."""
    cfg = config()
    params, tokens, got, want = intact(6)
    assert_close(got, want, "intact")
    plain_norm, plain_rope = ref.rms_norm, ref.apply_rope
    nh, rope = cfg.n_heads, cfg.qk_rope_head_dim
    if fault in ("kv_a_norm", "q_a_norm"):
        width = cfg.kv_lora_rank if fault == "kv_a_norm" \
            else cfg.q_lora_rank
        monkeypatch.setattr(ref, "rms_norm", lambda x, g, eps: x
                            if x.shape[-1] == width
                            else plain_norm(x, g, eps))
    elif fault == "rope_on_the_key_head":
        monkeypatch.setattr(ref, "apply_rope", lambda x, cos, sin: x
                            if x.shape[1] == 1 else plain_rope(x, cos, sin))
    elif fault == "shared_rotary_key_head":
        # every head rotates a different key: head h's is rolled by h
        def per_head(x, cos, sin):
            out = plain_rope(x, cos, sin)
            if x.shape[1] != 1:
                return out
            return jnp.concatenate([jnp.roll(out, h, axis=-1)
                                    for h in range(nh)], axis=1)
        monkeypatch.setattr(ref, "apply_rope", per_head)
    else:   # RoPE over the whole head: the nope columns rotate too
        plain_concat = jnp.concatenate

        def rotate_all(parts, axis=-1):
            out = plain_concat(parts, axis=axis)
            if axis == -1 and len(parts) == 2 and \
                    parts[1].shape[-1] == rope and \
                    parts[0].shape[-1] == cfg.qk_nope_head_dim:
                width = out.shape[-1]
                cos, sin = ref.rope_tables(out.shape[2], width,
                                           cfg.rope_theta)
                return plain_rope(out, cos, sin)
            return out
        monkeypatch.setattr(jnp, "concatenate", rotate_all)
    broken = _reference_logits(cfg, params, tokens)
    monkeypatch.undo()
    assert rel_diff(got, broken) > 30 * RTOL, fault


# ---- the buffer ------------------------------------------------------------


def test_the_choice_bias_is_bit_identical_after_a_step_with_weight_decay():
    import optax
    cfg = config(4, 4)
    params = share_of(weights(config(), 7), cfg, 4, 4)
    mesh = make_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    frozen = Transformer.frozen(cfg)
    assert frozen["layers"]["router_bias"] is True
    assert sum(jax.tree.leaves(frozen)) == 1
    before = np.asarray(params["layers"]["router_bias"]).copy()
    assert before.any()

    def run(**kw):
        init_state, step = make_train_step(
            lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh,
                                          with_metrics=True),
            Transformer.param_specs(cfg), mesh,
            optimizer=optax.adamw(1e-2, weight_decay=0.5), donate=False,
            **kw)
        state = init_state(params)
        for i in range(2):
            state, metrics = step(state, {"tokens": batch(cfg, i)})
        return state, metrics

    state, metrics = run(frozen=frozen)
    after = np.asarray(state["params"]["layers"]["router_bias"])
    assert after.tobytes() == before.tobytes()
    assert metrics["moe_slots_elsewhere"].shape == (2,)
    # what is trained moved; and without `frozen` the decay reaches it
    assert (np.asarray(state["params"]["layers"]["w_router"])
            != np.asarray(params["layers"]["w_router"])).any()
    decayed, _ = run()
    assert (np.asarray(decayed["params"]["layers"]["router_bias"])
            != before).any()


# ---- today's path is today's ------------------------------------------------


def test_every_expert_held_and_softmax_is_the_path_it_was():
    """With every expert held and the softmax router `moe_ffn` returns
    what it returned before it knew shares: the dense all-experts check,
    the counts of all E, nothing elsewhere."""
    key = jax.random.key(0)
    params = moe.init_moe_params(key, d_model=16, d_ff=32, n_experts=8)
    x = jax.random.normal(jax.random.key(1), (40, 16))
    y, routing = moe.moe_ffn(params, x, num_selected=2, norm_topk=False)
    want = moe.moe_ffn_dense_reference(params, x, num_selected=2,
                                       norm_topk=False)
    assert_close(y, want, "moe_ffn")
    assert routing["tokens_per_expert"].shape == (8,)
    assert int(routing["tokens_per_expert"].sum()) == 80
    assert int(routing["slots_elsewhere"]) == 0
    # and the compiled program holds no trace of a share: the same text
    # with and without the new arguments at their defaults
    a = jax.jit(lambda p, x: moe.moe_ffn(p, x, num_selected=2)[0])
    b = jax.jit(lambda p, x: moe.moe_ffn(
        p, x, num_selected=2, scoring="softmax", routed_scale=1.0,
        expert_offset=0)[0])
    assert a.lower(params, x).as_text() == b.lower(params, x).as_text()


@pytest.mark.parametrize("shape,want", [
    ((65536, 2048, 1024), "megablox"),      # OLMoE's rows of 512
    ((65536, 2048, 1536), "megablox"),      # GLM-4.7-Flash's expert width
    ((65536 + 8, 2048, 1536), "ragged_dot"),
    ((65536, 2048, 1000), "ragged_dot"),
])
def test_grouped_matmul_impl_by_shape(shape, want):
    class Tpu:
        platform = "tpu"

    class OneTpu:
        devices = np.asarray([Tpu()], dtype=object)
        size = 1

    assert moe.grouped_matmul_impl(OneTpu(), *shape) == want
    assert moe.grouped_matmul_impl(None, *shape) == "ragged_dot"  # the CPU


# the two matmuls of the six expert configurations, their widths read from
# the benchmark's files: (the rows' width, the expert width, gated)
EXPERT_WIDTHS = {
    "olmoe-1b-7b-0125-d1": ("hidden_size", "intermediate_size", True),
    "glm-4.7-flash-ep8-d5": ("hidden_size", "moe_intermediate_size", True),
    "nemotron-3-super-ep64-tp4-d11": (
        "moe_latent_size", "moe_intermediate_size", False),
    "ling-3.0-flash-ep64-tp4-d7": (
        "hidden_size", "moe_intermediate_size", True),
    "sdar-30b-a3b-chat-ep8-d4": ("hidden_size", "moe_intermediate_size", True),
    "mellum2-12b-a2.5b-ep4-d4": ("hidden_size", "moe_intermediate_size", True),
}


def _expert_calls():
    for name, (rows, expert, gated) in EXPERT_WIDTHS.items():
        with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
            published = json.load(f)
        d, width = published[rows], published[expert]
        yield pytest.param(65536, d, (1 + gated) * width, ..., id=name + "-first")
        yield pytest.param(65536, width, d, ..., id=name + "-down")


@pytest.mark.parametrize("m, k, n, want", [
    # OLMoE keeps the tiles it had, in every call
    (131072, 2048, 2048, (512, 1024, 1024)),
    (131072, 1024, 2048, (512, 1024, 1024)),
    (131072, 2048, 1024, (512, 1024, 1024)),
    # width 1536: the largest multiple of 128 that divides
    (65536, 2048, 3072, (512, 1024, 1024)),
    (65536, 1536, 2048, (512, 768, 1024)),
    (65536, 2048, 1536, (512, 1024, 768)),
    (65536 + 8, 2048, 1536, None),
    (65536, 2048, 1000, None),
    # PR 62, widths of 7 x 128, 9 x 128 and 5 x 128: Mellum2's four
    # look-ups, Nemotron's latent experts, Ling's stream
    (131072, 2304, 1792, (512, 1152, 896)),
    (131072, 896, 2304, (512, 896, 1152)),
    (131072, 1792, 2304, (512, 896, 1152)),
    (131072, 2304, 896, (512, 1152, 896)),
    (5632, 1024, 2688, (512, 1024, 896)),
    (4096, 2560, 1536, (512, 640, 768)),
    *_expert_calls(),
])
def test_gmm_tiles_follow_each_calls_shapes(m, k, n, want):
    """A call's contraction and column tiles are the largest multiple of
    128 up to `GMM_WIDEST` that divides its width, whatever the width; a
    pinned case holds the tiles themselves, `...` the rule alone."""
    got = moe.gmm_tiles(m, k, n)
    if want is not ...:
        assert got == want
    if got is None:
        return
    assert got[0] == moe.GMM_ROWS and m % got[0] == 0
    for tile, width in zip(got[1:], (k, n)):
        assert tile % 128 == 0 and width % tile == 0
        assert tile <= moe.GMM_WIDEST
        assert not any(width % t == 0 for t in range(
            tile + 128, min(width, moe.GMM_WIDEST) + 1, 128))
    assert moe.gmm_tiles(m + 8, k, n) is None
    assert moe.gmm_tiles(m, k + 8, n) is None     # no multiple of 128 divides
    assert moe.gmm_tiles(m, k, n + 8) is None


# ---- config ----------------------------------------------------------------


def test_num_params_at_the_published_widths():
    cfg = TransformerConfig(
        vocab_size=19360, d_model=2048, n_layers=5, n_heads=20, d_ff=1536,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, moe_experts=64, moe_top_k=4,
        moe_scoring="sigmoid", moe_shared_experts=1, moe_dense_layers=1,
        moe_dense_ff=10240, moe_experts_held=8)
    assert cfg.num_params == 591_294_720
    assert cfg.head_dim == 256 == cfg.v_dim and cfg.rope_dim == 64
    shapes = jax.eval_shape(lambda: Transformer.init(jax.random.key(0), cfg))
    frozen = Transformer.frozen(cfg)
    n = sum(int(np.prod(s.shape)) for s, keep in zip(
        jax.tree.leaves(shapes), jax.tree.leaves(frozen)) if not keep)
    assert n == cfg.num_params
    assert shapes["layers"]["router_bias"].shape == (4, 64)
    whole = cfg.replace(n_layers=47, moe_experts_held=0, vocab_size=154880)
    assert round(whole.num_params / 1e9, 2) == 29.94, whole.num_params


@pytest.mark.parametrize("kw,why", [
    (dict(moe_experts_held=4, moe_expert_offset=14), "experts"),
    (dict(moe_dense_layers=3), "moe_dense_layers"),
    (dict(moe_scoring="tanh"), "moe_scoring"),
    # refused for the aux loss alone: with the coefficient 0 it runs
    (dict(moe_experts_held=4, moe_scoring="softmax", moe_aux_coeff=0.01),
     "aux loss"),
    # a query latent is no longer needed (PR 50): the value width is
    (dict(v_head_dim=0), "latent attention"),
    (dict(n_kv_heads=2), "latent attention"),
    (dict(moe_experts=0, moe_dense_layers=0, moe_shared_experts=0,
          kv_lora_rank=0, n_heads=3), "n_heads"),
])
def test_config_refuses(kw, why):
    with pytest.raises(ValueError, match=why):
        config(**kw)


def test_param_specs_cover_every_leaf_and_shard_on_a_mesh():
    """Logical specs for every new leaf, and a step under fsdp x tensor
    on the virtual CPU mesh gives the one-device loss."""
    cfg = config()
    params = weights(cfg, 8)
    specs = Transformer.param_specs(cfg)
    flat = jax.tree.map(lambda s, p: len(s) == p.ndim, specs, params,
                        is_leaf=lambda x: isinstance(x, tuple))
    assert all(jax.tree.leaves(flat)), flat
    tokens = batch(cfg, 8, rows=4)
    want, _ = programs(cfg).loss(params, {"tokens": tokens})
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                     devices=jax.devices()[:4])
    init_state, step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh), specs, mesh,
        frozen=Transformer.frozen(cfg), donate=False)
    state = init_state(params)
    wq_b = state["params"]["layers"]["wq_b"]
    assert len({s.device for s in wq_b.addressable_shards}) == 4
    _, metrics = step(state, {"tokens": tokens})
    assert_close(metrics["loss"], want, "sharded loss", rtol=1e-4)
