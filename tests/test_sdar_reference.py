"""SDAR's block through the normal path (`Transformer.loss` with
`block_length`: the noise process, the doubled stream under the
block-diffusion mask, the masked-token loss weighted by 1/t, plain GQA
with a per-head QK-norm, the softmax router over a held share of the
experts) against the plain float32 reference
`benchmark/reference/sdar_f32.py`, which shares no code with `ray_tpu`:
seeded random weights, small sizes, on the CPU, float32 against float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (fused k/v and gate/up matmuls, a grouped matmul over sorted
rows against a masked loop over the resident experts, attention whole
against attention by blocks of queries): 1e-4 relative to the largest
entry of each compared array allows that and nothing else. Every fault of
`benchmark/reference/sdar_faults.py` has a case below that moves logits or
loss by far more.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig, diffusion, head
from ray_tpu.models.transformer import _qk_norm
from ray_tpu.ops import attention, moe

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402
from tests._programs import programs  # noqa: E402

ref = load_module("reference", "sdar_f32")
faults = load_module("reference", "sdar_faults")
job = load_module("jobs", "train_lm_blockdiff_moe")

RTOL = 1e-4
E, K = 16, 4
VOCAB = 128


def config(held=0, offset=0, block=4, **kw):
    base = dict(
        vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        attn_head_dim=16, d_ff=32, max_seq_len=1024, dtype="float32",
        rope_theta=1e6, norm_eps=1e-6, loss_chunk=0, qk_norm=True,
        qk_norm_per_head=True, moe_experts=E, moe_top_k=K,
        moe_norm_topk=True, moe_scoring="softmax", moe_aux_coeff=0.0,
        moe_experts_held=held, moe_expert_offset=offset,
        block_length=block, mask_token_id=VOCAB - 1)
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"hidden_act": "silu", "attention_bias": False,
           "use_sliding_window": False, "mlp_only_layers": [],
           "decoder_sparse_step": 1, "hidden_size": cfg.d_model,
           "head_dim": cfg.head_dim, "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.kv_heads,
           "num_experts_per_tok": cfg.moe_top_k,
           "norm_topk_prob": cfg.moe_norm_topk,
           "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
           "block_length": cfg.block_length}
    out.update(over)
    return out


def weights(cfg, seed):
    """Random weights with every gain off 1 (a gain of exactly 1 hides a
    norm applied in the wrong place or left out), heads of unlike scale (a
    QK-norm over the whole projection then differs from one a head) and
    router logits of order 1 as at the published width."""
    params = Transformer.init(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 16))
    lay = params["layers"]
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                  lay[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    lay["wq"] = lay["wq"] * jnp.exp(0.5 * jax.random.normal(
        next(keys), (cfg.n_layers, 1, cfg.n_heads, 1)))
    lay["wkv"] = lay["wkv"] * jnp.exp(0.5 * jax.random.normal(
        next(keys), (cfg.n_layers, 1, 1, cfg.kv_heads, 1)))
    lay["w_router"] = lay["w_router"] * 6.0
    return params


def share_of(params, held, offset):
    """The leaves a chip holding experts offset..offset+held keeps."""
    lay = dict(params["layers"])
    for name in ("w_moe_gateup", "w_moe_down"):
        lay[name] = lay[name][:, offset:offset + held]
    return dict(params, layers=lay)


def noisy_batch(cfg, seed, length, rows=2):
    """`diffusion.noised`'s batch of seeded tokens (the mask id nowhere
    among them), and the keys it was noised under."""
    tokens = jax.random.randint(jax.random.key(100 + seed), (rows, length),
                                0, VOCAB - 1)
    keys = job.noise_keys(seed, 2, 0, rows)
    return diffusion.noised({"tokens": tokens,
                             "noise_key": jnp.asarray(keys)}, cfg), keys


@functools.lru_cache(maxsize=None)
def _system_logits(cfg):
    return jax.jit(lambda params, batch: head.logits(
        params, Transformer.block_diffusion_hidden(params, batch, cfg)[0],
        cfg))


def system_logits(params, batch, cfg):
    """The logits at the L noised positions of the 2L stream: one
    `jax.jit` a configuration, as `programs`' are."""
    return _system_logits(cfg)(params, batch)


def reference(cfg):
    """The reference at `cfg`'s published keys under `jax.jit`
    (`tests/_programs.reference`): `.forward(w, noised, clean)` -> (logits,
    chosen), attention in query blocks of 64."""
    return _programs.reference(ref, published, cfg, with_routing=True,
                               query_block=64)


@functools.lru_cache(maxsize=None)
def reference_plain(cfg):
    """`ref.forward_plain(w, tokens)` at `cfg`, one program a length."""
    pub = published(cfg)
    return jax.jit(lambda w, tokens: ref.forward_plain(w, tokens, pub))


def assert_close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        what, float(np.abs(got - want).max()), float(scale))


SHARES = {"all_held": (0, 0), "share_4_of_16": (4, 8)}
# (sequence length, block length): one block of the flash kernel's 128
# rows and several, the published block length and a longer one
SHAPES = {"64x4": (64, 4), "256x4": (256, 4), "256x32": (256, 32)}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("share", SHARES)
def test_logits_and_loss_match_the_reference(share, shape):
    (held, offset), (length, block) = SHARES[share], SHAPES[shape]
    cfg = config(held, offset, block)
    params = weights(config(block=block), 3)
    if held:
        params = share_of(params, held, offset)
    batch, _ = noisy_batch(cfg, 3, length)
    logits = system_logits(params, batch, cfg)
    loss, metrics = programs(cfg).loss(params, batch)
    w = job.to_reference_layout(params, cfg)
    assert sorted(w["layers"][1]["experts"]) == list(
        range(offset, offset + cfg.held_experts))
    ref_logits, chosen = reference(cfg).forward(
        w, batch["tokens"], batch["targets"])
    assert logits.shape == (2, length, VOCAB)
    assert_close(logits, ref_logits, "logits")
    assert_close(loss, ref.masked_diffusion_loss(
        ref_logits, batch["targets"], batch["mask"]), "loss")
    # the counters: the held experts' columns of the reference's counts
    # over the 2L positions, and every other slot counted as elsewhere
    counts = np.asarray(ref.tokens_per_expert(chosen, E))
    mine = counts[:, offset:offset + cfg.held_experts]
    np.testing.assert_array_equal(metrics["moe_tokens_per_expert"], mine)
    slots = 2 * batch["tokens"].size * K
    if held:
        np.testing.assert_array_equal(metrics["moe_slots_elsewhere"],
                                      slots - mine.sum(-1))
    else:
        assert (mine.sum(-1) == slots).all()
    assert int(metrics["moe_dropped"]) == 0
    # no aux loss over a share: it needs the other chips' counts
    assert (float(metrics["moe_aux_loss"]) == 0.0) == bool(held)
    assert int(metrics["diffusion_masked_tokens"]) == int(
        (np.asarray(batch["mask"]) > 0).sum()) > 0
    assert_close(metrics["diffusion_weight_sum"],
                 np.asarray(batch["mask"], np.float64).sum(), "weights")


def from_reference_layout(grads, cfg):
    """The reference's gradients back in the program's fused layout."""
    d, nh, nkv = cfg.d_model, cfg.n_heads, cfg.kv_heads
    gs = grads["layers"]

    def stack(f):
        return jnp.stack([f(g) for g in gs])

    def experts(g):
        return [e for _, e in sorted(g["experts"].items())]

    layers = {
        "attn_norm": stack(lambda g: g["input_layernorm"]),
        "mlp_norm": stack(lambda g: g["post_attention_layernorm"]),
        "wq": stack(lambda g: g["q_proj"].T.reshape(d, nh, -1)),
        "wkv": stack(lambda g: jnp.stack(
            [g["k_proj"].T.reshape(d, nkv, -1),
             g["v_proj"].T.reshape(d, nkv, -1)], 1)),
        "q_norm": stack(lambda g: g["q_norm"]),
        "k_norm": stack(lambda g: g["k_norm"]),
        "wo": stack(lambda g: g["o_proj"].T.reshape(nh, -1, d)),
        "w_router": stack(lambda g: g["mlp.gate"].T),
        "w_moe_gateup": stack(lambda g: jnp.stack([jnp.stack(
            [e["gate_proj"].T, e["up_proj"].T], 1) for e in experts(g)])),
        "w_moe_down": stack(lambda g: jnp.stack(
            [e["down_proj"].T for e in experts(g)]))}
    return {"embed": grads["embed_tokens"], "final_norm": grads["norm"],
            "lm_head": grads["lm_head"].T, "layers": layers}


@pytest.mark.parametrize("share", SHARES)
def test_gradients_match_jax_grad_of_the_reference(share):
    held, offset = SHARES[share]
    cfg = config(held, offset)
    params = weights(config(), 1)
    if held:
        params = share_of(params, held, offset)
    batch, _ = noisy_batch(cfg, 1, 64)
    _, grads = programs(cfg).grads(params, batch)
    w = job.to_reference_layout(params, cfg)
    _, ref_grads = reference(cfg).loss_and_grads(
        w, batch["tokens"], batch["targets"], batch["mask"])
    want = from_reference_layout(ref_grads, cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), exp in zip(flat, jax.tree.leaves(want)):
        assert np.abs(np.asarray(exp)).max() > 0, path
        assert_close(got, exp, jax.tree_util.keystr(path), rtol=2e-4)


# ---- the doubled stream against the objective's own definition ----------


@pytest.mark.parametrize("block,length,blocks", [
    (4, 32, (0, 1, 5, 7)), (32, 64, (0, 1))])
def test_the_doubled_stream_is_the_objective_block_by_block(block, length,
                                                            blocks):
    """For a block b (the first, the second, one in the middle, the
    last), one plain forward pass over `[x^{<b} ; x^b noised]` (causal by
    block, block b in both directions) gives at block b's positions the
    logits the 2L stream gives there: by the program (`Transformer.apply`,
    a plain stream under the block mask) and by the reference
    (`forward_plain`)."""
    cfg = config(block=block)
    params = weights(cfg, 4)
    batch, _ = noisy_batch(cfg, 4, length, rows=1)
    stream = system_logits(params, batch, cfg)
    w = job.to_reference_layout(params, cfg)
    assert blocks[-1] == length // block - 1
    for b in blocks:
        lo, hi = b * block, (b + 1) * block
        plain = jnp.concatenate([batch["targets"][:, :lo],
                                 batch["tokens"][:, lo:hi]], axis=1)
        assert_close(programs(cfg).logits(params, plain)[:, lo:],
                     stream[:, lo:hi], f"block {b} by the program")
        assert_close(reference_plain(cfg)(w, plain)[:, lo:],
                     stream[:, lo:hi], f"block {b} by the reference")


# ---- the noise -------------------------------------------------------------


@pytest.mark.parametrize("block", [4, 32])
def test_the_noise_is_a_numpy_loop_over_blocks_of_the_same_draws(block):
    cfg = config(block=block)
    length, rows = 128, 3
    batch, keys = noisy_batch(cfg, 7, length, rows)
    clean = np.asarray(batch["targets"])
    assert (clean != cfg.mask_token).all()       # nowhere in the clean copy
    noised, weight = np.asarray(batch["tokens"]), np.asarray(batch["mask"])
    typed = jax.random.wrap_key_data(jnp.asarray(keys),
                                     impl="threefry2x32")
    for row in range(rows):
        t, u = map(np.asarray, diffusion.block_times(typed[row], length,
                                                     cfg))
        assert t.shape == (length // block,) and u.shape == (length,)
        assert (t >= cfg.diffusion_t_min).all() and (t < 1).all()
        for b in range(length // block):
            for i in range(b * block, (b + 1) * block):
                if u[i] < t[b]:
                    assert noised[row, i] == cfg.mask_token
                    assert weight[row, i] == np.float32(1.0) / t[b]
                else:
                    assert noised[row, i] == clean[row, i]
                    assert weight[row, i] == 0.0
    # a sequence's noise is its own key's: another row's key, another draw
    assert not np.array_equal(weight[0], weight[1])
    # one time a block: the masked positions of a block weigh alike
    blocks = weight.reshape(rows, -1, block)
    assert ((blocks == 0) | (blocks == blocks.max(-1, keepdims=True))).all()
    # typed keys and their two words are the same keys
    again = diffusion.noised({"tokens": batch["targets"],
                              "noise_key": typed}, cfg)
    np.testing.assert_array_equal(again["mask"], batch["mask"])


def test_what_is_refused_is_refused_by_name():
    cfg = config()
    with pytest.raises(ValueError, match="does not divide"):
        diffusion.noised({"tokens": jnp.zeros((1, 66), jnp.int32),
                          "noise_key": jnp.zeros((1, 2), jnp.uint32)}, cfg)
    with pytest.raises(ValueError, match="diffusion.noised"):
        Transformer.loss(Transformer.init(jax.random.key(0), cfg),
                         {"tokens": jnp.zeros((1, 65), jnp.int32)}, cfg)
    with pytest.raises(ValueError, match="aux loss"):
        config(held=4, moe_aux_coeff=0.01)
    for impl in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="ring or\\s+ulysses"):
            config(attention_impl=impl)
    with pytest.raises(ValueError, match="block-diffusion"):
        Transformer.pipeline_loss({}, {"tokens": jnp.zeros((2, 9),
                                                            jnp.int32)},
                                  config(moe_experts=0), mesh=None,
                                  n_stages=1, n_micro=1)
    with pytest.raises(ValueError, match="doubled stream"):
        attention.dense_attention(*(jnp.zeros((1, 24, 2, 16)),) * 3,
                                  block_length=4, noised=10)
    with pytest.raises(ValueError, match="no window"):
        attention.dense_attention(*(jnp.zeros((1, 24, 2, 16)),) * 3,
                                  block_length=4, window=8)


# ---- the mask, on both paths ------------------------------------------------


@pytest.mark.parametrize("noised", [0, 256])
@pytest.mark.parametrize("block", [4, 32])
def test_the_flash_mask_is_the_dense_mask(block, noised):
    """`flash_attention`'s splash mask in pallas interpret mode against
    `dense_attention`'s predicate, output and gradients, over a plain
    stream and a doubled one (512 positions: sixteen kernel blocks of
    128... the block table says how many the mask leaves)."""
    t, h, hkv, d = 512, 4, 2, 64
    q, k, v = (jax.random.normal(jax.random.key(i), (1, t, n, d))
               for i, n in enumerate((h, hkv, hkv)))
    kw = dict(block_length=block, noised=noised)

    def flash(q, k, v):
        return attention._splash_attention(
            q, k, v, causal=True, scale=d ** -0.5, interpret=True, **kw)

    def dense(q, k, v):
        return attention.dense_attention(q, k, v, **kw)

    assert_close(flash(q, k, v), dense(q, k, v), "output", rtol=2e-5)
    cot = jax.random.normal(jax.random.key(9), (1, t, h, d))
    got = jax.grad(lambda *a: (flash(*a) * cot).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * cot).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert_close(g, w, "d" + name, rtol=2e-5)


@pytest.mark.parametrize("length,block", [(64, 4), (256, 32), (1024, 4)])
def test_the_mask_is_the_four_lines_and_leaves_l2_plus_lb_pairs(length,
                                                                block):
    ids = np.arange(2 * length)
    mine = attention.block_visible(ids[:, None], ids[None, :], block, length)
    np.testing.assert_array_equal(
        mine, np.asarray(ref.block_diffusion_mask(length, block)))
    assert mine.sum() == length * length + length * block
    np.testing.assert_array_equal(
        attention.block_visible(ids[:, None], ids[None, :], block),
        np.asarray(ref.block_causal_mask(2 * length, block)))
    if 2 * length % 128 == 0:
        table = attention.block_table(2 * length, 128, block, length)
        assert table["pairs_needed"] == mine.sum()
        assert table["non_empty"] * table["block_pairs"] >= mine.sum()
        assert 0 < table["partial"] <= table["non_empty"] <= table["blocks"]


def test_the_block_table_of_the_cells_call():
    """8,192 tokens in blocks of 4, heads of 128: 16 x 16 kernel blocks of
    1,024, of which the mask leaves 80 (the noised diagonal 8, the clean
    triangle 36 twice), 24 of them partial: every block on the three
    diagonals."""
    assert attention.block_table(16384, 128, 4, 8192) == {
        "blocks": 256, "non_empty": 80, "partial": 24,
        "block_pairs": 1024 * 1024, "pairs_needed": 8192 * 8192 + 8192 * 4}


# ---- the per-head QK-norm ----------------------------------------------------


def test_the_per_head_qk_norm_is_a_loop_over_heads():
    x = jax.random.normal(jax.random.key(0), (2, 8, 4, 16)) \
        * jnp.exp(jax.random.normal(jax.random.key(1), (1, 1, 4, 1)))
    gain = 1.0 + 0.3 * jax.random.normal(jax.random.key(2), (16,))
    got = _qk_norm(x, gain, 1e-6)
    for h in range(4):
        assert_close(got[:, :, h], ref.rms_norm(x[:, :, h], gain, 1e-6),
                     f"head {h}")
    # the whole projection's (OLMoE's) is another function of the same x
    whole = _qk_norm(x, jnp.tile(gain, 4), 1e-6)
    assert_close(whole, ref.rms_norm(x.reshape(2, 8, -1), jnp.tile(gain, 4),
                                     1e-6).reshape(x.shape), "whole")
    assert float(jnp.abs(whole - got).max()) > 0.1
    # and the leaves say which: one head's width, or the projection's
    per_head = jax.eval_shape(lambda: Transformer.init(
        jax.random.key(0), config()))["layers"]
    assert per_head["q_norm"].shape == per_head["k_norm"].shape == (2, 16)
    whole_cfg = config(qk_norm_per_head=False)
    leaves = jax.eval_shape(lambda: Transformer.init(
        jax.random.key(0), whole_cfg))["layers"]
    assert leaves["q_norm"].shape == (2, 64)
    assert leaves["k_norm"].shape == (2, 32)
    for cfg in (config(), whole_cfg, config(4, 8)):
        shapes = jax.eval_shape(lambda c=cfg: Transformer.init(
            jax.random.key(0), c))
        assert sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(shapes)) == cfg.num_params


# ---- the shares add up ---------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 16 over 4 shares of 4, every offset: the routed parts the four
    shares give for one layer, plus the residual counted once, are the
    uncut reference's layer output; the held slots of the shares sum to
    N x k and no slot is in two shares."""
    cfg = config()
    params = weights(cfg, 5)
    x = jax.random.normal(jax.random.key(9), (96, cfg.d_model))
    lw = job.to_reference_layout(params, cfg)["layers"][1]
    pub = published(cfg)
    with jax.default_matmul_precision("highest"):
        routed, top_e = ref.sparse_moe(x, lw, pub)
    lay = params["layers"]
    layer = {"w_router": lay["w_router"][1],
             "w_gateup": lay["w_moe_gateup"][1],
             "w_down": lay["w_moe_down"][1]}
    total = x
    held_counts = []
    for offset in range(0, E, 4):
        share = dict(layer, w_gateup=layer["w_gateup"][offset:offset + 4],
                     w_down=layer["w_down"][offset:offset + 4])
        y, routing = moe.moe_ffn(share, x, expert_offset=offset,
                                 num_selected=K, norm_topk=True,
                                 scoring="softmax")
        total = total + y
        held_counts.append(np.asarray(routing["tokens_per_expert"]))
        assert int(routing["dropped"]) == 0
        assert held_counts[-1].sum() + int(routing["slots_elsewhere"]) \
            == x.shape[0] * K
        # this share's part alone: the reference given the same share
        with jax.default_matmul_precision("highest"):
            part, _ = ref.sparse_moe(x, dict(lw, experts={
                e: w for e, w in lw["experts"].items()
                if offset <= e < offset + 4}), pub)
        assert_close(y, part, f"share at {offset}")
    assert_close(total, x + routed, "the shares' sum")
    np.testing.assert_array_equal(
        np.concatenate(held_counts),
        np.bincount(np.asarray(top_e).reshape(-1), minlength=E))


# ---- each fault moves logits or loss -----------------------------------------


@pytest.fixture(scope="module")
def fault_rows():
    """Every variant's reading on one noised sample at the small size, a
    share of 4 of 16 held, on the stand-in weights the cell's job makes,
    and the system's own distance from the reference there."""
    from benchlib.spec import load_json
    init = load_json(os.path.join(BENCH_DIR, "rehearsal", "configs",
                                  "tiny-sdar.json"))["init"]
    # heads of 64: a head's scores spread with its width, and the
    # stand-in heads pick few keys only where they are wide enough
    cfg = config(4, 8, attn_head_dim=64)
    # the cell's own stand-in weights (the job's `init_params`)
    params = share_of(job.init_params(
        jax.random.key(6), config(attn_head_dim=64),
        dict(init, anchor_pairs=8)), 4, 8)
    length = 256
    batch, keys = noisy_batch(cfg, 6, length)
    times = faults.block_times(cfg, keys, length)
    w = job.to_reference_layout(params, cfg)
    rows = {row["variant"]: row for row in faults.readings(
        published(cfg), w, batch, times)}
    base = ref.forward(w, batch["tokens"], batch["targets"], published(cfg))
    diff = np.asarray(system_logits(params, batch, cfg) - base, np.float64)
    own = float(np.sqrt((diff ** 2).sum()
                        / (np.asarray(base, np.float64) ** 2).sum()))
    own_loss = abs(float(programs(cfg).loss(params, batch)[0])
                   - float(ref.masked_diffusion_loss(
                       base, batch["targets"], batch["mask"])))
    return rows, (own, own_loss), job.loss_weight_norm(batch["mask"])


@pytest.mark.parametrize("name", faults.FAULTS + faults.PRECISIONS)
def test_each_fault_moves_logits_or_loss(fault_rows, name):
    """At the small size, in float32, the system sits within 1e-4 of the
    reference and every fault but one moves the logits or the loss by
    more than the cell's limits (`tolerance` of the configuration file);
    bf16 operands move the logits less than the faults do."""
    rows, (own, own_loss), weight_norm = fault_rows
    tol = job_tolerance()
    assert own <= RTOL and own_loss <= 1e-5
    row = rows[name]
    if name == "targets_shifted":
        # stand-in logits know nothing of their targets, so shifted
        # targets move the loss by a random draw, of a width that grows
        # with the logits' spread: 1.7-2.6 weight norms at the published
        # widths (PERF.md section 6, PR 53), a tenth of one here, and a
        # thousand times what the system is off by either way
        assert row["rel_l2"] == 0.0
        assert row["loss_diff"] > max(1000 * own_loss, 0.05 * weight_norm)
        return
    if name == "bfloat16":
        assert row["rel_l2"] < min(rows[f]["rel_l2"] for f in
                                   faults.MASK_FAULTS + faults.LAYER_FAULTS)
        return
    if name in faults.LOSS_FAULTS:
        assert row["rel_l2"] == 0.0
        assert row["loss_diff"] > tol["loss_per_weight_norm"] \
            * weight_norm, (row, weight_norm)
    else:
        assert row["rel_l2"] > tol["logits_rel_l2"], row


def job_tolerance():
    from benchlib.spec import load_json
    return load_json(os.path.join(
        BENCH_DIR, "configs", "sdar-30b-a3b-chat-ep8-d4.json"))["tolerance"]
