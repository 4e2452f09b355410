"""Ouro-2.6B's looped stack through the normal path (`Transformer.loss`:
one stack of dense layers under a sandwich norm run `loops` times through
the same weights, the final norm closing every pass, a head and an exit
gate after every pass, the expected next-token loss under the exit
distribution less its entropy's share) against the plain float32
reference `benchmark/reference/ouro_f32.py`, which shares no code with
`ray_tpu`: seeded random weights, small sizes, on the CPU, float32 against
float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (a scan over the passes and over the layers against Python
loops, attention whole against attention by blocks of queries, the head
chunked against whole): 1e-4 relative to the largest entry of each
compared array allows that and nothing else. Every published or assumed
term has a case below that fails without it.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig, head

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests import _programs  # noqa: E402
from tests._programs import programs  # noqa: E402

ref = load_module("reference", "ouro_f32")
faults = load_module("reference", "ouro_faults")
job = load_module("jobs", "train_lm_looped")

RTOL = 1e-4
SEQ, VOCAB, LOOPS, LAYERS = 48, 128, 4, 2
INIT = {"embed_std": 1.0, "norm_gain_std": 0.3, "gate_z_std": 1.0,
        "gate_bias": -0.6}


def config(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_layers=LAYERS, n_heads=4,
        n_kv_heads=4, d_ff=48, max_seq_len=SEQ, dtype="float32",
        loss_chunk=16, norm_eps=1e-6, rope_theta=1e6, loops=LOOPS,
        exit_gate=True, exit_entropy_coeff=0.05, norm_placement="both")
    base.update(kw)
    return TransformerConfig(**base)


def published(cfg, **over):
    """The config.json keys the reference reads."""
    out = {"rms_norm_eps": cfg.norm_eps, "head_dim": cfg.head_dim,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.kv_heads, "hidden_act": "silu",
           "rope_theta": cfg.rope_theta, "total_ut_steps": cfg.loops,
           "exit_entropy_coeff": cfg.exit_entropy_coeff}
    out.update(over)
    return out


def reference(cfg):
    """The reference at `cfg`'s published keys under `jax.jit`
    (`tests/_programs.reference`)."""
    return _programs.reference(ref, published, cfg)


def weights(cfg, seed):
    """The job's stand-in weights: every gain off 1, the gate spread."""
    return job.init_params(jax.random.key(seed), cfg, INIT)


def tokens(seed, batch=2):
    return jax.random.randint(jax.random.key(seed), (batch, SEQ + 1), 0,
                              VOCAB)


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


@functools.lru_cache(maxsize=None)
def passes_program(cfg):
    """(every pass's logits [R, B, T, V], z [R, B, T]) of the system."""
    def both(params, toks):
        hs, _, _, z = Transformer.hidden(params, toks, cfg, with_aux=True)
        return jnp.stack([head.logits(params, h, cfg) for h in hs]), z
    return jax.jit(both)


@functools.lru_cache(maxsize=None)
def reference_passes(cfg):
    model = published(cfg)

    def both(w, toks):
        hs, zs, _ = ref.passes(w, toks, model)
        return jnp.stack([ref.logits_of(w, h) for h in hs]), jnp.stack(zs)
    return jax.jit(both)


@functools.lru_cache(maxsize=None)
def both_sides(seed=0):
    cfg = config()
    params, toks = weights(cfg, seed), tokens(seed + 1)
    with jax.default_matmul_precision("highest"):
        loss, grads = programs(cfg).grads(params, {"tokens": toks})
        ref_loss, ref_grads = reference(cfg).loss_and_grads(
            job.to_reference_layout(params, cfg), toks)
    return cfg, params, toks, (loss, job.to_reference_layout(grads, cfg)), \
        (ref_loss, ref_grads)


# ---- the forward pass ----------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_passes_logits_and_z_match_the_reference(seed):
    cfg = config()
    params, toks = weights(cfg, seed), tokens(seed + 10)
    with jax.default_matmul_precision("highest"):
        logits, z = passes_program(cfg)(params, toks[:, :-1])
        want, want_z = reference_passes(cfg)(
            job.to_reference_layout(params, cfg), toks[:, :-1])
    assert logits.shape == (LOOPS, 2, SEQ, VOCAB) and z.dtype == jnp.float32
    for t in range(LOOPS):
        close(logits[t], want[t])
    close(z, want_z)
    # the passes differ: a pass that ran the same thing twice would not
    assert float(jnp.abs(want[1] - want[0]).max()) > 0.1
    # apply's logits are the last pass's
    close(programs(cfg).logits(params, toks[:, :-1]), want[-1])


def test_exit_distribution_matches_the_reference_and_sums_to_one():
    z = 3.0 * jax.random.normal(jax.random.key(5), (LOOPS, 2, SEQ))
    log_p = Transformer.exit_log_probs(z)
    want = jnp.stack(ref.exit_distribution(list(z)))
    close(log_p, want, 1e-6)
    np.testing.assert_allclose(np.exp(np.asarray(log_p)).sum(0), 1.0,
                               atol=1e-6)
    # from log-sigmoids: a gate far out leaves no NaN and no -inf behind
    hard = Transformer.exit_log_probs(jnp.full((LOOPS, 1), 80.0))
    assert np.isfinite(np.asarray(hard)).all()
    # the last gate's z is read by nothing
    moved = z.at[-1].add(5.0)
    np.testing.assert_array_equal(np.asarray(log_p), np.asarray(
        Transformer.exit_log_probs(moved)))


def test_loss_matches_the_reference():
    _, _, _, (loss, _), (ref_loss, _) = both_sides()
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)


def test_step_metrics_are_the_forward_passes():
    cfg, params, toks, (loss, _), _ = both_sides()
    with jax.default_matmul_precision("highest"):
        got, metrics = programs(cfg).loss(params, {"tokens": toks})
        logits, z = reference_passes(cfg)(
            job.to_reference_layout(params, cfg), toks[:, :-1])
    assert float(got) == pytest.approx(float(loss), rel=1e-6)
    log_p = jnp.stack(ref.exit_distribution(list(z)))
    nll = jnp.stack([ref.token_nll(l, toks[:, 1:]) for l in logits])
    close(metrics["loop_exit_mass"], jnp.exp(log_p).mean((1, 2)))
    close(metrics["loop_pass_nll"], nll.mean((1, 2)))
    close(metrics["loop_exit_entropy"],
          -(jnp.exp(log_p) * log_p).sum(0).mean(), 1e-5)
    assert abs(float(metrics["loop_exit_mass"].sum()) - 1.0) <= 1e-5
    # the stand-in gate leaves no pass under a tenth of the mass
    assert float(metrics["loop_exit_mass"].min()) > 0.1


# ---- the gradients ---------------------------------------------------------

LAYER_LEAVES = ["input_layernorm", "input_layernorm_2",
                "post_attention_layernorm", "post_attention_layernorm_2",
                "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                "up_proj", "down_proj"]


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("leaf", LAYER_LEAVES)
def test_layer_gradients_match_the_reference(layer, leaf):
    _, _, _, (_, grads), (_, ref_grads) = both_sides()
    close(grads["layers"][layer][leaf], ref_grads["layers"][layer][leaf])
    assert float(jnp.abs(ref_grads["layers"][layer][leaf]).max()) > 0


@pytest.mark.parametrize("leaf", ["embed_tokens", "norm", "lm_head"])
def test_other_gradients_match_the_reference(leaf):
    _, _, _, (_, grads), (_, ref_grads) = both_sides()
    close(grads[leaf], ref_grads[leaf])


@pytest.mark.parametrize("leaf", ["weight", "bias"])
def test_the_gates_gradient_matches_the_reference(leaf):
    _, _, _, (_, grads), (_, ref_grads) = both_sides()
    close(grads["early_exit_gate"][leaf], ref_grads["early_exit_gate"][leaf])
    assert float(jnp.abs(ref_grads["early_exit_gate"][leaf]).max()) > 1e-4


def test_the_gates_gradient_from_the_forward_pass_alone():
    """What the job compares on the chip: `gate_gradient` needs no
    backward pass through the layers, and is autodiff's."""
    cfg, params, toks, _, (_, ref_grads) = both_sides()
    w, model = job.to_reference_layout(params, cfg), published(cfg)
    with jax.default_matmul_precision("highest"):
        hs, _, xs = ref.passes(w, toks[:, :-1], model)
        nll = [ref.token_nll(ref.logits_of(w, h), toks[:, 1:]) for h in hs]
        got = ref.gate_gradient(w, hs, xs, nll, model)
    for leaf in ("weight", "bias"):
        close(got[leaf], ref_grads["early_exit_gate"][leaf], 1e-5)


@pytest.mark.parametrize("chunk", [0, 16])
def test_a_masked_batch_is_the_mean_over_its_tokens(chunk):
    """A mask weights the expectation and the entropy alike."""
    cfg = config(loss_chunk=chunk)
    params, toks = weights(cfg, 3), tokens(4)
    mask = (jax.random.uniform(jax.random.key(6), (2, SEQ)) > 0.4).astype(
        jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, metrics = programs(cfg).loss(
            params, {"tokens": toks[:, :-1], "targets": toks[:, 1:],
                     "mask": mask})
        logits, z = reference_passes(config())(
            job.to_reference_layout(params, cfg), toks[:, :-1])
    log_p = jnp.stack(ref.exit_distribution(list(z)))
    p = jnp.exp(log_p)
    nll = jnp.stack([ref.token_nll(l, toks[:, 1:]) for l in logits])
    per_token = (p * nll).sum(0) + 0.05 * (p * log_p).sum(0)
    want = (per_token * mask).sum() / mask.sum()
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    close(metrics["loop_exit_mass"], (p * mask).sum((1, 2)) / mask.sum())


# ---- the passes share their weights ----------------------------------------


def test_weight_gradient_is_the_sum_over_unshared_copies():
    """The looped stack's weight gradient is the sum of the gradients of
    R copies of the stack that share nothing: the same loss written with
    one set of layers a pass (`_stack` called R times on R arguments, the
    final norm and the gate as they are)."""
    from ray_tpu.models.transformer import _norm
    from ray_tpu.parallel.sharding import ShardingRules

    cfg, params, toks, _, _ = both_sides()
    batch = {"tokens": toks}

    def unshared(copies, params):
        tok, targets = toks[:, :-1], toks[:, 1:]
        x = Transformer.embed(params, tok, cfg)
        hs = []
        for layers in copies:
            x = Transformer._stack(layers, x, cfg, mesh=None,
                                   rules=ShardingRules())[0]
            x = _norm(x, params, "final_norm", cfg.norm_eps)
            hs.append(x)
        hs = jnp.stack(hs)
        z = jnp.sum(hs * params["exit_gate"], -1) + params["exit_gate_bias"]
        log_p = Transformer.exit_log_probs(z)
        p = jnp.exp(log_p)
        nll = jnp.stack([ref.token_nll(head.logits(params, h, cfg), targets)
                         for h in hs])
        return jnp.mean((p * nll).sum(0) + 0.05 * (p * log_p).sum(0))

    with jax.default_matmul_precision("highest"):
        shared = programs(cfg).grads(params, batch)[1]["layers"]
        loss, by_copy = jax.jit(jax.value_and_grad(unshared))(
            [params["layers"]] * LOOPS, params)
    assert float(loss) == pytest.approx(
        float(both_sides()[3][0]), rel=1e-5)
    for name, leaf in shared.items():
        close(leaf, sum(copy[name] for copy in by_copy))
        # no single pass gives it
        assert not np.allclose(np.asarray(leaf), np.asarray(
            by_copy[-1][name]), rtol=1e-2, atol=0)


# ---- one pass, no gate: the dense decoder ----------------------------------


def test_one_loop_and_no_gate_is_the_dense_decoder_to_the_bit():
    """`loops` = 1 with no gate takes the code path every other
    configuration takes: same leaves, same jaxpr, same loss bit for bit."""
    dense = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=LAYERS, n_heads=4, d_ff=48,
        max_seq_len=SEQ, dtype="float32", loss_chunk=16)
    one = dense.replace(loops=1, exit_gate=False, norm_placement="pre")
    assert one == dense
    params = Transformer.init(jax.random.key(0), dense)
    assert "exit_gate" not in params and "attn_post_norm" not in \
        params["layers"]
    batch = {"tokens": tokens(0)}
    parent = jax.make_jaxpr(lambda p: Transformer.loss(p, batch, dense))(
        params)
    assert "loops" not in str(parent) and str(parent).count("while") == \
        str(jax.make_jaxpr(lambda p: Transformer.loss(
            p, batch, one))(params)).count("while")
    # a looped stack without a gate trains its last pass alone
    twice = dense.replace(loops=2)
    got = jax.jit(lambda p: Transformer.loss(p, batch, twice))(params)
    hs = Transformer.hidden(params, batch["tokens"][:, :-1], twice)
    assert hs.shape == (2, 2, SEQ, 32)
    want = head.nll_sum(head.weight(params, twice), hs[-1],
                        batch["tokens"][:, 1:], twice) / (2 * SEQ)
    assert float(got) == pytest.approx(float(want), rel=1e-6)


def test_where_the_norms_sit_is_said_by_the_leaves():
    for placement, norms in (
            ("pre", {"attn_norm", "mlp_norm"}),
            ("post", {"attn_post_norm", "mlp_post_norm"}),
            ("both", {"attn_norm", "mlp_norm", "attn_post_norm",
                      "mlp_post_norm"})):
        cfg = config(norm_placement=placement, loops=1, exit_gate=False)
        shapes = jax.eval_shape(lambda: Transformer.init(
            jax.random.key(0), cfg))
        have = {n for n in shapes["layers"] if n.endswith("norm")}
        assert have == norms
        specs = Transformer.param_specs(cfg)
        assert jax.tree.structure(shapes) == jax.tree.structure(
            specs, is_leaf=lambda x: isinstance(x, tuple))
        assert cfg.num_params == sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    looped = config()
    shapes = jax.eval_shape(lambda: Transformer.init(jax.random.key(0),
                                                     looped))
    assert shapes["exit_gate"].shape == (32,)
    assert looped.num_params == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("kw,said", [
    (dict(loops=0), "loops is 1 or above"),
    (dict(loops=1, exit_gate=True), "an exit gate is a looped stack's"),
    (dict(layer_pattern="aa", norm_placement="pre", gdn_heads=0),
     "looped stack"),
    (dict(moe_experts=4), "looped stack"),
    (dict(block_length=4), "looped stack"),
    (dict(attention_impl="ring"), "looped stack"),
    (dict(norm_placement="sideways"), "norm_placement"),
    (dict(loops=1, exit_gate=False, layer_pattern="aa",
          norm_placement="both"), "norm_placement"),
])
def test_what_a_looped_stack_refuses(kw, said):
    with pytest.raises(ValueError, match=said):
        config(**kw)


def test_the_pipeline_refuses_a_looped_stack_by_name():
    cfg = config(exit_gate=False)
    with pytest.raises(ValueError, match="looped"):
        Transformer.pipeline_loss(
            None, {"tokens": jnp.zeros((2, SEQ + 1), jnp.int32)}, cfg,
            mesh=None, n_stages=2, n_micro=2)


# ---- the faults ------------------------------------------------------------

SAID = {**dict.fromkeys(faults.BY_LOGITS, "rel_l2"),
        "gate_before_norm": "z_rel_l2", "last_pass_lambda": "exit_prob_abs",
        **dict.fromkeys(faults.BY_OBJECTIVE, "objective_diff"),
        "stopped_weights": "gate_grad_rel_l2"}


@functools.lru_cache(maxsize=None)
def fault_rows():
    model = dict(published(config()), vocab_size=VOCAB, hidden_size=32,
                 intermediate_size=48, num_hidden_layers=LAYERS,
                 max_position_embeddings=SEQ,
                 layer_types=["full_attention"], job="train_lm_looped",
                 init=INIT, train=dict(
                     compute_dtype="float32", param_dtype="float32",
                     attention_impl="dense", remat=False, loss_chunk=16,
                     scan_unroll=1),
                 tolerance=dict(logits_rel_l2=1e-3, gate_z_rel_l2=1e-3,
                                exit_prob_abs=1e-3, loss_abs=1e-4,
                                objective_abs=1e-4, gate_grad_rel_l2=1e-3))
    mix = {"kind": "token_batches", "sequences_per_step": 2,
           "tokens_per_sequence": SEQ,
           "unigram": {"law": "zipf", "exponent": 1.1},
           "reference_sample": {"sequences": 2, "tokens": SEQ}}
    with jax.default_matmul_precision("highest"):
        return {r["variant"]: r for r in faults.read(model, mix, 7)}


@pytest.mark.parametrize("name", faults.FAULTS)
def test_each_fault_moves_what_it_is_said_to_move(name):
    row = fault_rows()[name]
    assert SAID[name] in [k for k, limit in faults.LIMITS.items()
                          if limit in row["fails"]], row
    assert row["correct"] is False
    if name in faults.BY_OBJECTIVE + faults.BY_GATE_GRADIENT:
        # the forward pass is untouched: only the objective's readings move
        assert row["rel_l2"] == 0 and row["z_rel_l2"] == 0 \
            and row["exit_prob_abs"] == 0
        assert "gate_grad_rel_l2" in row["fails"]
    if name in faults.BY_LOGITS:
        # the objective on the unchanged forward pass is the reference's
        assert row["objective_diff"] == 0 and row["gate_grad_rel_l2"] == 0
    if name == "stopped_weights":   # nothing but a gradient check sees it
        assert row["fails"] == ["gate_grad_rel_l2"] and row["loss_diff"] == 0


def test_positions_that_run_on_are_the_same_model():
    """RoPE reads the difference of two positions and a pass attends
    within itself: the fault ISSUE 63 lists moves nothing but rounding."""
    row = fault_rows()["positions_run_on"]
    assert row["correct"] is True and row["rel_l2"] < 1e-5


def test_narrower_operands_are_ordered():
    rows = fault_rows()
    assert 0 < rows["bfloat16"]["rel_l2"] < rows["float8_e4m3fn"][
        "rel_l2"] < rows["float8_e5m2"]["rel_l2"]
    assert 0 < rows["bfloat16"]["loss_diff"] < rows["float8_e5m2"][
        "loss_diff"]
