"""Packed documents (`segment_ids`) as an input of the model step: a packed
sequence's outputs and gradients at a document's positions equal those of
the document run ALONE, in float32 to 1e-4, for `ops/ssm.causal_conv`,
`ssd_scan`, `ssd_scan_pallas` (pallas interpret mode, at the smallest shape
that takes chunk 256 and one group) and both attention paths, for
boundaries inside a chunk, on a chunk's edge, around a document shorter
than the convolution's 4 taps, and for one document filling the sequence;
`segment_ids=None` hands the kernels no segment operand; every layer kind
that has not been taught boundaries refuses them by name; the loss drops
the labels that cross a boundary and counts what it trained on.

"Alone" is the function under test WITHOUT `segment_ids` on the document
moved to the sequence's start with nothing behind it (every function here
is causal), one compile a function: boundaries are data, not shape."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer
from ray_tpu.models.configs import TransformerConfig
from ray_tpu.ops import attention as attn_ops
from ray_tpu.ops import ssm


@pytest.fixture(autouse=True)
def highest_precision():
    """float32 products at full precision, for this file's tests alone (a
    `jax.config.update` at import would reach every file a worker
    collects: the chip-compile tests' bf16 kernels do not compile under
    it)."""
    with jax.default_matmul_precision("highest"):
        yield


# document lengths over 512 positions; chunk 256 (the scans), 4 taps
BOUNDARIES = {
    "inside_a_chunk": [100, 56, 200, 156],
    "on_the_chunk_edge": [256, 256],
    "shorter_than_the_taps": [3, 2, 1, 250, 255, 1],
    "one_document": [512],
    "edge_and_inside": [40, 216, 3, 253],
}
T = 512


def ids_of(lengths, total=T, batch=1):
    assert sum(lengths) == total
    row = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    return jnp.asarray(np.tile(row, (batch, 1)))


def docs_of(lengths):
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    return list(zip(starts.tolist(), lengths))


def alone(fn, lengths, seq_args, rest=(), axis=1):
    """`fn(*seq_args, *rest)` a document at a time: each document moved to
    the start of an otherwise empty sequence of the same length, its
    outputs moved back. `seq_args` run along `axis`."""
    total = seq_args[0].shape[axis]
    place = jnp.arange(total)
    out = None
    for start, n in docs_of(lengths):
        def moved(a, by, keep):
            shape = [1] * a.ndim
            shape[axis] = total
            return jnp.roll(a, by, axis=axis) * keep.reshape(shape).astype(
                a.dtype)
        inside = place < n
        y = fn(*[moved(a, -start, inside) for a in seq_args], *rest)
        back = moved(y * inside.reshape(
            [total if i == axis else 1 for i in range(y.ndim)]).astype(
                y.dtype), start, (place >= start) & (place < start + n))
        out = back if out is None else out + back
    return out


def close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def both(fn_packed, fn_alone, operands, probe):
    """(outputs, gradients under `probe`) of the two sides."""
    def side(fn):
        out, pull = jax.vjp(fn, *operands)
        return out, pull(probe)
    return side(fn_packed), side(fn_alone)


# ---- the convolution ---------------------------------------------------


@pytest.fixture(scope="module")
def conv_operands():
    k = jax.random.split(jax.random.key(0), 4)
    return (jax.random.normal(k[0], (2, T, 24)),
            jax.random.normal(k[1], (24, 4)) * 0.5,
            jax.random.normal(k[2], (24,)) * 0.3,
            jax.random.normal(k[3], (2, T, 24)))


@pytest.mark.parametrize("name", list(BOUNDARIES))
def test_causal_conv_packed_equals_alone(conv_operands, name):
    x, w, b, probe = conv_operands
    lengths = BOUNDARIES[name]
    ids = ids_of(lengths, batch=2)
    (y, g), (y0, g0) = both(
        lambda x, w, b: ssm.causal_conv(x, w, b, ids),
        lambda x, w, b: alone(ssm.causal_conv, lengths, (x,), (w, b)),
        (x, w, b), probe)
    close(y, y0)
    for got, want in zip(g, g0):
        close(got, want)
    if len(lengths) > 1:   # the boundaries change something
        assert np.abs(np.asarray(y - ssm.causal_conv(x, w, b))).max() > 1e-2


def test_causal_conv_without_ids_is_the_plain_convolution(conv_operands):
    x, w, b, _ = conv_operands
    assert "eq" not in str(jax.make_jaxpr(ssm.causal_conv)(x, w, b))
    close(ssm.causal_conv(x, w, b, ids_of([T], batch=2)),
          ssm.causal_conv(x, w, b))


# ---- the scans -----------------------------------------------------------

H, P, G, N, Q = 8, 64, 1, 128, 256   # the smallest shape that takes chunk
                                     # 256 and one group in the kernel


@pytest.fixture(scope="module")
def scan_operands():
    k = jax.random.split(jax.random.key(1), 6)
    return (jax.random.normal(k[0], (1, T, H * P)),
            jax.nn.softplus(jax.random.normal(k[1], (1, T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.0)),
            jax.random.normal(k[3], (1, T, G * N)) * 0.3,
            jax.random.normal(k[4], (1, T, G * N)) * 0.3,
            jax.random.normal(k[5], (1, T, H * P)))


def xla_scan(x, dt, a, b, c, ids=None):
    return ssm.ssd_scan(x.reshape(1, T, H, P), dt, a,
                        b.reshape(1, T, G, N), c.reshape(1, T, G, N), Q,
                        ids).reshape(1, T, H * P)


def kernel_scan(x, dt, a, b, c, ids=None):
    return ssm.ssd_scan_pallas(x, dt, a, b, c, Q, G, segment_ids=ids,
                               interpret=True)


@functools.lru_cache(maxsize=None)
def scan_programs(which):
    """(packed, one document moved to the start) of a scan, each one
    program whatever the boundaries: ids, start and length are data."""
    fn = {"xla": xla_scan, "pallas": kernel_scan}[which]

    def packed(probe, ids, *operands):
        out, pull = jax.vjp(lambda *o: fn(*o, ids), *operands)
        return out, pull(probe)

    def one(probe, start, n, x, dt, a, b, c):
        place = jnp.arange(T)
        inside = (place < n)[None, :, None]

        def moved(x, dt, b, c):
            return [jnp.roll(v, -start, axis=1) * inside
                    for v in (x, dt, b, c)]

        def f(x, dt, a, b, c):
            x, dt, b, c = moved(x, dt, b, c)
            y = fn(x, dt, a, b, c) * inside
            return jnp.roll(y, start, axis=1)
        out, pull = jax.vjp(f, x, dt, a, b, c)
        return out, pull(probe)

    return jax.jit(packed), jax.jit(one)


SCAN_CASES = [("xla", name) for name in BOUNDARIES] + [
    ("pallas", "edge_and_inside"), ("pallas", "shorter_than_the_taps"),
    ("pallas", "one_document")]


@pytest.mark.parametrize("which,name", SCAN_CASES,
                         ids=[f"{w}-{n}" for w, n in SCAN_CASES])
def test_scan_packed_equals_alone(scan_operands, which, name):
    """Forward and the five gradients, through `ssd_scan` and through the
    kernels in interpret mode."""
    *operands, probe = scan_operands
    lengths = BOUNDARIES[name]
    packed, one = scan_programs(which)
    y, grads = packed(probe, ids_of(lengths), *operands)
    want_y, want = 0.0, [0.0] * 5
    for start, n in docs_of(lengths):
        y1, g1 = one(probe, start, n, *operands)
        want_y = want_y + y1
        want = [acc + g for acc, g in zip(want, g1)]
    close(y, want_y)
    for got, w in zip(grads, want):
        close(got, w)


def test_the_kernels_equal_the_xla_scan_under_documents(scan_operands):
    *operands, probe = scan_operands
    ids = ids_of(BOUNDARIES["edge_and_inside"])
    y, g = scan_programs("pallas")[0](probe, ids, *operands)
    y0, g0 = scan_programs("xla")[0](probe, ids, *operands)
    close(y, y0)
    for got, want in zip(g, g0):
        close(got, want)
    # and the boundaries change something
    assert np.abs(np.asarray(y0 - xla_scan(*operands))).max() > 1e-2


def test_chunk_marks():
    ids = ids_of([100, 56, 200, 156])
    carry, keep = ssm.chunk_marks(ids, 256)
    carry, keep = np.asarray(carry[0]), np.asarray(keep[0])
    assert not carry[:256].any()            # nothing enters the first chunk
    # the second chunk starts inside the third document (156..355)
    assert carry[256:356].all() and not carry[356:].any()
    assert not keep[:156].any() and keep[156:256].all()
    assert not keep[256:356].any() and keep[356:].all()
    # a boundary on the chunk's edge: nothing is carried over it
    carry, keep = ssm.chunk_marks(ids_of([256, 256]), 256)
    assert not np.asarray(carry).any() and np.asarray(keep).all()


def pallas_operands(fn, *args):
    """Operand counts of the pallas calls in `fn(*args)`'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((str(eqn.params.get("name", "")
                                  or eqn.params["name_and_src_info"]),
                              len(eqn.invars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_no_ids_hand_the_scan_kernels_no_operand(scan_operands):
    *operands, probe = scan_operands

    def grad_of(ids):
        return lambda *o: jax.vjp(
            lambda *o: kernel_scan(*o, ids), *o)[1](probe)
    plain = dict(pallas_operands(grad_of(None), *operands))
    packed = dict(pallas_operands(
        grad_of(ids_of(BOUNDARIES["one_document"])), *operands))
    fwd, bwd = sorted(plain), sorted(packed)
    assert len(plain) == len(packed) == 2 and fwd == bwd
    for name in plain:    # x, B, C, dt, a (and the states, dy): one more
        assert packed[name] == plain[name] + 1, (name, plain, packed)
    assert sorted(plain.values()) == [5, 7]


@pytest.mark.parametrize("per_group,p,chunk,want", [
    (16, 64, 128, 16), (64, 64, 128, 16), (64, 64, 256, 8), (8, 128, 256, 8),
    (16, 128, 128, 8), (8, 64, 256, 8), (4, 64, 128, None)])
def test_scan_head_block_by_chunk(per_group, p, chunk, want):
    """Granite's 64 heads of 64 in one group at chunk 256 take 8 heads a
    grid step (sixteen overran the v5e's scoped VMEM in the backward
    kernel); chunk 128 keeps its sixteen."""
    assert ssm.scan_head_block(per_group, p, chunk) == want
    assert ssm.scan_shape_ok(8192, 64, 64, 1, 128, 256)


# ---- attention -------------------------------------------------------------

TA = 256
ATTN_BOUNDARIES = {"inside_a_block": [3, 97, 28, 72, 56],
                   "on_the_block_edge": [128, 128],
                   "one_document": [256]}


@pytest.fixture(scope="module")
def attn_operands():
    k = jax.random.split(jax.random.key(2), 4)
    return (jax.random.normal(k[0], (2, TA, 4, 64)),
            jax.random.normal(k[1], (2, TA, 2, 64)),
            jax.random.normal(k[2], (2, TA, 2, 64)),
            jax.random.normal(k[3], (2, TA, 4, 64)))


def dense(q, k, v, window=0, ids=None):
    return attn_ops.dense_attention(q, k, v, scale=0.05, window=window,
                                    segment_ids=ids)


def splash(q, k, v, window=0, ids=None):
    return attn_ops._splash_attention(
        q, k, v, causal=True, scale=0.05, window=window, segment_ids=ids,
        interpret=True)


@pytest.mark.parametrize("window", [0, 64], ids=["causal", "window64"])
@pytest.mark.parametrize("name", list(ATTN_BOUNDARIES))
@pytest.mark.parametrize("path", [dense, splash], ids=["dense", "splash"])
def test_attention_packed_equals_alone(attn_operands, path, name, window):
    q, k, v, probe = attn_operands
    lengths = ATTN_BOUNDARIES[name]
    ids = ids_of(lengths, TA, batch=2)
    (y, g), (y0, g0) = both(
        lambda q, k, v: path(q, k, v, window, ids),
        lambda q, k, v: alone(
            lambda q, k, v: dense(q, k, v, window), lengths, (q, k, v)),
        (q, k, v), probe)
    close(y, y0)
    for got, want in zip(g, g0):
        close(got, want)


def test_no_ids_hand_the_attention_kernels_no_operand(attn_operands):
    q, k, v, _ = attn_operands
    plain = pallas_operands(lambda q, k, v: splash(q, k, v), q, k, v)
    packed = pallas_operands(lambda q, k, v: splash(
        q, k, v, ids=ids_of([256], TA, batch=2)), q, k, v)
    assert len(plain) == len(packed) == 1
    assert "segmented" not in plain[0][0] and "segmented" in packed[0][0]
    assert packed[0][1] > plain[0][1]


def test_attention_refuses_ids_it_cannot_take(attn_operands):
    q, k, v, _ = attn_operands
    with pytest.raises(ValueError, match="block-diffusion"):
        attn_ops.dense_attention(q, k, v, block_length=4,
                                 segment_ids=ids_of([256], TA, batch=2))
    with pytest.raises(ValueError, match="one id a position"):
        attn_ops.dense_attention(q, k, v,
                                 segment_ids=ids_of([256], TA, batch=1))


def test_causal_block_pairs():
    # 8 blocks of 1,024 at 8,192 tokens: 36 blocks on and under the diagonal
    assert attn_ops.causal_block_pairs(8192, 64) == 36 * 1024 * 1024
    assert attn_ops.causal_block_pairs(256, 64) == 256 * 256


# ---- the model -------------------------------------------------------------

TM = 64
GRANITE = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, layer_pattern="nnln", n_heads=4,
    n_kv_heads=2, rope=False, d_ff=48, max_seq_len=TM, tie_embeddings=True,
    ssm_heads=4, ssm_head_dim=8, ssm_groups=1, ssm_state=16, ssm_chunk=16,
    embed_scale=12.0, residual_scale=0.22, attn_scale=0.0625,
    logit_divisor=8.0, dtype="float32", loss_chunk=16)
PLAIN = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=48,
    max_seq_len=TM, dtype="float32", loss_chunk=16)
HYBRID = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=3, layer_pattern="M*E", n_heads=4,
    n_kv_heads=2, attn_head_dim=8, rope=False, d_ff=24, max_seq_len=TM,
    ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=16,
    moe_experts=4, moe_top_k=2, moe_scoring="sigmoid", moe_aux_coeff=0.0,
    dtype="float32", loss_chunk=16)
MODEL_LENGTHS = [2, 15, 15, 8, 25]     # over TM + 1 tokens: inside chunks,
                                       # a document shorter than the taps


@functools.lru_cache(maxsize=None)
def model_programs(cfg):
    def packed(params, tokens, ids):
        return Transformer.loss(params, {"tokens": tokens,
                                         "segment_ids": ids}, cfg)

    def one(params, tokens, n):
        """The loss's SUM over one document, moved to the start: its
        labels are the document's own but its last position's."""
        mask = (jnp.arange(TM) < n - 1).astype(jnp.float32)[None]
        return Transformer.loss(params, {"tokens": tokens, "mask": mask},
                                cfg) * jnp.maximum(n - 1, 1)

    return (jax.jit(jax.value_and_grad(packed)),
            jax.jit(jax.value_and_grad(one)),
            jax.jit(lambda p, t, ids: Transformer.apply(
                p, t, cfg, segment_ids=ids)),
            jax.jit(lambda p, t: Transformer.apply(p, t, cfg)))


@pytest.mark.parametrize("cfg", [GRANITE, PLAIN, HYBRID],
                         ids=["granite_nnln", "homogeneous", "hybrid_M*E"])
def test_model_packed_equals_alone(cfg):
    """Logits, loss and every parameter's gradient of the packed sequence
    against the documents run alone (a document's labels all but its
    last position's), through `Transformer.loss` with `segment_ids` in
    the batch."""
    params = Transformer.init(jax.random.key(3), cfg)
    tokens = jax.random.randint(jax.random.key(4), (1, TM + 1), 0, 64)
    ids = ids_of(MODEL_LENGTHS, TM + 1)
    packed, one, logits_packed, logits_plain = model_programs(cfg)
    loss, grads = packed(params, tokens, ids)
    total, labels = 0.0, 0
    want = jax.tree.map(jnp.zeros_like, params)
    got_logits = logits_packed(params, tokens[:, :-1], ids[:, :-1])
    for start, n in docs_of(MODEL_LENGTHS):
        moved = jnp.roll(tokens, -start, axis=1)
        inside = jnp.arange(TM + 1) < n
        moved = jnp.where(inside[None], moved, 0)
        s, g = one(params, moved, n)
        total, labels = total + s, labels + n - 1
        want = jax.tree.map(jnp.add, want, g)
        m = min(n, TM - start)
        close(got_logits[:, start:start + m],
              logits_plain(params, moved[:, :-1])[:, :m])
    close(loss, total / labels)
    flat_got = jax.tree.leaves(grads)
    flat_want = jax.tree.leaves(jax.tree.map(lambda g: g / labels, want))
    for got, w in zip(flat_got, flat_want):
        close(got, w, tol=2e-4)


def test_loss_counts_what_it_trains_on():
    params = Transformer.init(jax.random.key(3), GRANITE)
    tokens = jax.random.randint(jax.random.key(4), (1, TM + 1), 0, 64)
    ids = ids_of(MODEL_LENGTHS, TM + 1)
    _, metrics = Transformer.loss(
        params, {"tokens": tokens, "segment_ids": ids}, GRANITE,
        with_metrics=True)
    inputs = [2, 15, 15, 8, 24]
    assert int(metrics["packed_docs"]) == 5
    assert int(metrics["packed_labels"]) == TM - 4
    assert int(metrics["packed_attn_pairs_needed"]) == sum(
        n * (n + 1) // 2 for n in inputs)
    # explicit targets: ids a position, the caller's mask joins
    _, again = Transformer.loss(
        params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
                 "segment_ids": ids[:, :-1]}, GRANITE, with_metrics=True)
    assert int(again["packed_labels"]) == TM - 4
    # without ids nothing is counted and nothing is traced
    _, none = Transformer.loss(params, {"tokens": tokens}, GRANITE,
                               with_metrics=True)
    assert none == {}
    text = str(jax.make_jaxpr(lambda p, t: Transformer.loss(
        p, {"tokens": t}, GRANITE))(params, tokens))
    assert "cummax" not in text


UNTAUGHT = {
    "Mamba-1": dict(layer_pattern="ml", n_layers=2, ssm_d_inner=64,
                    ssm_dt_rank=4, ssm_state=16, ssm_chunk=16),
    "Kimi Delta Attention": dict(layer_pattern="kl", n_layers=2, kda_heads=2,
                                 kda_head_dim=16, kda_chunk=16),
    "Gated DeltaNet": dict(layer_pattern="da", n_layers=2, gdn_heads=2,
                           gdn_key_dim=16, gdn_value_dim=16, gdn_chunk=16),
    "differential attention": dict(
        layer_pattern="wf", n_layers=2, diff_attention=True, attn_window=16,
        n_heads=4, n_kv_heads=2),
    "block-diffusion": dict(block_length=4),
    "looped stack": dict(loops=2),
    "several streams": dict(residual_streams=2),
    "ring attention": dict(attention_impl="ring"),
    "ulysses attention": dict(attention_impl="ulysses"),
}


@pytest.mark.parametrize("what", list(UNTAUGHT))
def test_untaught_kinds_refuse_segment_ids_by_name(what):
    cfg = PLAIN.replace(**UNTAUGHT[what])
    params = jax.eval_shape(lambda k: Transformer.init(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, TM + 1), jnp.int32)
    with pytest.raises(ValueError, match="segment_ids") as err:
        jax.eval_shape(lambda p, t: Transformer.loss(
            p, {"tokens": t, "segment_ids": t}, cfg), params, tokens)
    assert what.split()[0].lower() in str(err.value).lower(), err.value
    with pytest.raises(ValueError, match="segment_ids"):
        jax.eval_shape(lambda p, t: Transformer.apply(
            p, t[:, :-1], cfg, segment_ids=t[:, :-1]), params, tokens)


def test_pipeline_loss_refuses_segment_ids():
    with pytest.raises(ValueError, match="pipeline_loss"):
        Transformer.pipeline_loss(
            {}, {"tokens": None, "segment_ids": None}, PLAIN, mesh=None,
            n_stages=2, n_micro=2)


def test_the_taught_kinds_are_not_refused():
    for cfg in (GRANITE, PLAIN, HYBRID,
                PLAIN.replace(layer_pattern="WL", n_layers=2, attn_window=16,
                              moe_experts=4, moe_top_k=2)):
        assert Transformer.untaught_by_packing(cfg, "dense") == []
        assert Transformer.untaught_by_packing(cfg, "flash") == []
