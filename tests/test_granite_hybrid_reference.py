"""A Granite 4.0-H hybrid (`layer_pattern` kinds `n`, a Mamba-2 mixer then
an MLP, and `l` without RoPE; four muP scalars; a tied embedding) on packed
documents, held against `benchmark/reference/granite_hybrid_f32.py` in
float32 at 1e-4, term by term: the reference knows no segment and runs
each document alone, the program is handed `segment_ids`. Logits, loss and
every parameter's gradient; each of the four scalars; the mixer and
attention alone; the reference's own cutting; the job's documents. The
faults of `granite_hybrid_faults.py` are read in
`tests/benchmark/test_granite4hmicro_cell.py`. Whole-model programs
compile once a configuration."""

import functools
import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import flops_granite  # noqa: E402
from benchlib.spec import load_json, load_module  # noqa: E402

from ray_tpu.models import Transformer  # noqa: E402
from ray_tpu.models.configs import TransformerConfig  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402


@pytest.fixture(autouse=True)
def highest_precision():
    """float32 products at full precision, for this file's tests alone (a
    `jax.config.update` at import would reach every file a worker
    collects: the chip-compile tests' bf16 kernels do not compile under
    it)."""
    with jax.default_matmul_precision("highest"):
        yield


ref = load_module("reference", "granite_hybrid_f32")
job = load_module("jobs", "train_lm_granite_packed")

T = 64
KINDS = ["mamba", "mamba", "attention", "mamba"]
MODEL = {
    "attention_bias": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 48, "layer_types": KINDS, "logits_scaling": 8,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 8, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
    "max_position_embeddings": 128, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 4,
    "num_experts_per_tok": 0, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 48, "tie_word_embeddings": True,
    "vocab_size": 64}
TRAIN = {"compute_dtype": "float32", "param_dtype": "float32",
         "remat": True, "attention_impl": "dense", "loss_chunk": 16,
         "scan_unroll": 1}
INIT = {"norm_gain_std": 0.3, "conv_bias_std": 0.3, "d_skip_std": 0.3,
        "q_gain": 8.0}
LENGTHS = [[2, 15, 15, 8, 25]]      # over T + 1 tokens
SCALARS = ("embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling")


def freeze(model):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


@functools.lru_cache(maxsize=None)
def programs(frozen):
    """The program at a published config: cfg, params in both layouts,
    and one jit each of logits, loss and gradients."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    cfg = job.transformer_config(model, TRAIN, T)
    params = job.init_params(jax.random.key(5), cfg, INIT)
    weights = job.to_reference_layout(params, cfg)
    logits = jax.jit(lambda p, t, ids: Transformer.apply(
        p, t, cfg, segment_ids=ids))
    grads = jax.jit(jax.value_and_grad(lambda p, t, ids: Transformer.loss(
        p, {"tokens": t, "segment_ids": ids}, cfg)))
    return model, cfg, params, weights, logits, grads


def batch():
    tokens = jax.random.randint(jax.random.key(6), (1, T + 1), 0, 64)
    ids = jnp.asarray(job.PackedBatches.segment_ids(LENGTHS))
    return tokens, ids


def close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def test_the_config_is_the_published_kinds():
    model, cfg, params, *_ = programs(freeze(MODEL))
    assert cfg.layer_pattern == "nnln" and cfg.tie_embeddings
    assert not cfg.rope and cfg.softmax_scale == 0.0625
    assert "lm_head" not in params
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params == flops_granite.total_params(model)
    assert cfg.pattern_runs == [("n", 2), ("ln", 1)]


def test_logits_loss_and_gradients_against_the_reference():
    model, cfg, params, weights, logits, grads = programs(freeze(MODEL))
    tokens, ids = batch()
    want = ref.forward(weights, tokens[:, :-1], model,
                       ref.input_lengths(LENGTHS, T + 1))
    close(logits(params, tokens[:, :-1], ids[:, :-1]), want)
    loss, g = grads(params, tokens, ids)
    want_loss, want_g = ref.loss_and_grads(weights, tokens, model, LENGTHS)
    close(loss, want_loss)
    close(want_loss, ref.next_token_loss(want, tokens, LENGTHS))
    # the program's gradients in the reference's layout: a re-layout is
    # linear, so the gradients go through it as the weights did
    got_g = job.to_reference_layout(g, cfg)
    flat_got, tree = jax.tree.flatten(got_g)
    flat_want, tree_want = jax.tree.flatten(want_g)
    assert tree == tree_want
    for got, w in zip(flat_got, flat_want):
        close(got, w, tol=2e-4)


@pytest.mark.parametrize("scalar", SCALARS)
def test_each_muP_scalar(scalar):
    """Another value of one scalar: both sides move, and agree."""
    changed = dict(MODEL, **{scalar: MODEL[scalar] * 1.7})
    model, cfg, params, weights, logits, _ = programs(freeze(changed))
    _, _, _, _, base_logits, _ = programs(freeze(MODEL))
    tokens, ids = batch()
    got = logits(params, tokens[:, :-1], ids[:, :-1])
    close(got, ref.forward(weights, tokens[:, :-1], model,
                           ref.input_lengths(LENGTHS, T + 1)))
    base = base_logits(params, tokens[:, :-1], ids[:, :-1])
    assert np.abs(np.asarray(got - base)).max() > 1e-3 * np.abs(
        np.asarray(base)).max()


def test_the_mixer_alone_against_the_reference():
    model, cfg, params, weights, *_ = programs(freeze(MODEL))
    sub = {name: leaf[0] for name, leaf in params["runs"][0][0].items()}
    n = jax.random.normal(jax.random.key(7), (1, 32, 32))
    got = ssm.mamba2_mixer(n, sub, head_dim=8, state=16, chunk=16, eps=1e-5)
    close(got, ref.mamba2_mixer(n, weights["layers"][0], model))
    # one group: the gated norm runs over all 64 inner channels
    y = jax.random.normal(jax.random.key(8), (1, 4, 64))
    z = jax.random.normal(jax.random.key(9), (1, 4, 64))
    gain = 1.0 + 0.3 * jax.random.normal(jax.random.key(10), (64,))
    close(ssm.gated_norm(y, z, gain, 1, 1e-5),
          ref.gated_rms_norm(y, z, gain, 1e-5))


def test_attention_alone_against_the_reference():
    """The softmax scale is `attention_multiplier`, there is no rotary
    embedding, GQA 4 / 2."""
    from ray_tpu.ops.attention import dense_attention
    model, cfg, params, weights, *_ = programs(freeze(MODEL))
    lw = weights["layers"][2]
    n = jax.random.normal(jax.random.key(11), (1, 32, 32))
    want = ref.attention(n, lw, model)
    sub = {name: leaf[0] for name, leaf in params["runs"][1][0].items()}
    q = jnp.einsum("btd,dhk->bthk", n, sub["wq"])
    kv = jnp.einsum("btd,dghk->btghk", n, sub["wkv"])
    o = dense_attention(q, kv[:, :, 0], kv[:, :, 1], scale=cfg.softmax_scale)
    close(jnp.einsum("bthk,hkd->btd", o, sub["wo"]), want)
    assert ref.softmax_scale(model) == 0.0625 != 8 ** -0.5


def test_the_reference_cuts_and_knows_no_segment():
    assert ref.documents([3, 5, 100], 10) == [(0, 3), (3, 5), (8, 2)]
    assert ref.documents([10, 4], 10) == [(0, 10)]
    with pytest.raises(ValueError):
        ref.documents([3, 5], 10)
    assert [ref.padded_length(n) for n in (1, 128, 129, 512, 513, 2049,
                                           8192, 9000)] == \
        [128, 128, 512, 512, 2048, 8192, 8192, 9000]
    assert ref.input_lengths([[3, 5, 2]], 10) == [[3, 5, 1]]
    assert ref.input_lengths([[3, 6, 1]], 10) == [[3, 6, 0]]
    keep = ref.trained_positions([[3, 5, 2]], 10)
    assert keep.tolist() == [[True, True, False, True, True, True, True,
                              False, True]]
    # no function of the reference takes an id or a mask of documents
    for name, fn in inspect.getmembers(ref, inspect.isfunction):
        if fn.__module__ == ref.__name__:
            assert not [p for p in inspect.signature(fn).parameters
                        if "segment" in p or "mask" in p], name
    source = inspect.getsource(ref).split('"""', 2)[2]
    assert "segment_ids" not in source and "ray_tpu" not in source


def test_padding_a_document_changes_none_of_its_logits():
    model, cfg, params, weights, *_ = programs(freeze(MODEL))
    tokens, _ = batch()
    doc = tokens[:, :20]
    exact = ref.document_logits(weights, doc, model)
    padded = ref.document_logits(
        weights, jnp.pad(doc, ((0, 0), (0, 12))), model)[:, :20]
    close(padded, exact, tol=1e-5)


def test_the_job_draws_documents_by_its_law():
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "sft_8k_packed.json"))
    law = mix["documents"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 512, 1.0, 16, 8192)
    batches = job.PackedBatches(mix, 12544, 2 ** 31 + 11)   # a large seed
    tokens, ids, lengths = batches.batch(3)
    again = batches.batch(3)
    assert tokens.shape == ids.shape == (1, 8193)
    assert np.array_equal(ids, again[1]) and lengths == again[2]
    assert not np.array_equal(ids, batches.batch(4)[1])
    assert sum(lengths[0]) == 8193 and all(
        16 <= n <= 8192 for n in lengths[0][:-1])
    assert tokens.max() < 12544 and (np.diff(ids[0]) >= 0).all()
    drawn = [n for step in range(40)
             for n in batches.lengths(1, step, 1, 8193)[0][:-1]]
    assert 400 < np.median(drawn) < 640 and 6 < len(drawn) / 40 < 14
    counted = job.counted([[3, 5, 2]])
    assert counted == {"packed_docs": 3, "packed_labels": 7,
                       "packed_attn_pairs_needed": 6 + 15 + 1}
    assert job.counted([[9, 1]])["packed_docs"] == 1
