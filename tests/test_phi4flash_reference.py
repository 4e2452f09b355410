"""Phi-4-mini-flash-reasoning's layers through the normal path
(`Transformer.loss`: Mamba-1 mixers, differential attention under a
window, a full mask and as cross-attention on another layer's keys and
values, gated memory units on another layer's scan output, each followed
by a dense MLP, LayerNorm with a bias, a tied head) against the plain
float32 reference `benchmark/reference/phi4flash_f32.py`, which shares no
code with `ray_tpu`: seeded random weights, small sizes, on the CPU,
float32 against float32.

Tolerance. Both sides compute in float32 and differ only in the order of
their sums (the scan in chunks against the recurrence step by step,
attention whole against attention by blocks of queries and key pairs):
1e-4 relative to the largest entry of each compared array allows that and
nothing else.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.models.configs import pattern_runs
from ray_tpu.ops import attention as attn_ops
from ray_tpu.ops import ssm
from ray_tpu.parallel.sharding import ShardingRules

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module  # noqa: E402

from tests._programs import programs, value_and_grads  # noqa: E402

ref = load_module("reference", "phi4flash_f32")
job = load_module("jobs", "train_lm_sambay")

RTOL = 1e-4
SEQ = 96
INIT = {"q_gain": 3.0, "norm_gain_std": 0.3, "bias_std": 0.3}


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= rtol * np.abs(b).max(), \
        (np.abs(a - b).max(), np.abs(b).max())


def model(kinds, first, **kw):
    """A small configuration file's keys."""
    base = {
        "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
        "num_key_value_heads": 4, "sliding_window": 16, "vocab_size": 128,
        "num_hidden_layers": len(kinds), "layer_kinds": kinds,
        "first_layer_index": first, "layer_norm_eps": 1e-5,
        "max_position_embeddings": 512, "tie_word_embeddings": True,
        "mamba": {"d_inner": 48, "d_state": 4, "d_conv": 4, "dt_rank": 4}}
    base.update(kw)
    return base


TRAIN = {"compute_dtype": "float32", "param_dtype": "float32",
         "attention_impl": "dense", "remat": False, "loss_chunk": 0,
         "scan_unroll": 1, "scan_chunk": 32}


def config(m, seq=SEQ, **train):
    return job.transformer_config(m, dict(TRAIN, **train), seq)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---- the Mamba-1 scan and mixer ---------------------------------------------


def scan_inputs(key, b=2, t=SEQ, c=24, n=4):
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (b, t, c)),
            3 * jax.nn.softplus(jax.random.normal(ks[1], (b, t, c))),
            -jnp.exp(jax.random.normal(ks[2], (c, n))),
            jax.random.normal(ks[3], (b, t, n)),
            jax.random.normal(ks[4], (b, t, n)),
            jax.random.normal(ks[5], (b, t, c)))


@jax.jit
def recurrence(x, dt, a, b, c):
    return jnp.stack([ref.recurrence(x[i], dt[i], a, b[i], c[i])
                      for i in range(x.shape[0])])


@functools.lru_cache(maxsize=None)
def chunked(chunk):
    """`selective_scan` under one `jax.jit` a chunk length."""
    return jax.jit(lambda *a: ssm.selective_scan(*a, chunk))


@pytest.mark.parametrize("chunk", [96, 32, 24, 16])
def test_selective_scan_is_the_recurrence(chunk):
    """T of one to six chunks, sub-chunks of 32, 8 and 16 steps; steps as
    large as 9 (decays down to e^-100): nothing overflows."""
    *args, _ = scan_inputs(jax.random.key(0))
    close(chunked(chunk)(*args), recurrence(*args))


def test_a_bfloat16_state_fails_this_comparison():
    """The state is float32 in the program: the recurrence with its state
    kept in bfloat16 is over ten times further from `selective_scan` than
    RTOL allows (on the chip the logits' limit cannot tell it from bf16
    operands' own rounding: PERF.md section 7)."""
    *args, _ = scan_inputs(jax.random.key(0))
    x, dt, a, b, c = args
    narrow = jnp.stack([ref.recurrence(x[i], dt[i], a, b[i], c[i],
                                       dtype="bfloat16") for i in range(2)])
    got = chunked(32)(*args)
    assert float(jnp.abs(got - narrow).max()) > 10 * RTOL * float(
        jnp.abs(got).max())


def test_selective_scan_refuses_a_ragged_chunk():
    *args, _ = scan_inputs(jax.random.key(0))
    with pytest.raises(ValueError, match="whole chunks"):
        ssm.selective_scan(*args, 36)


@pytest.mark.parametrize("chunk", [32, 48])
def test_selective_scan_gradients_are_the_recurrences(chunk):
    *args, w = scan_inputs(jax.random.key(1))
    _, got = value_and_grads(chunked(chunk))(w, *args)
    _, want = value_and_grads(recurrence)(w, *args)
    for g, r in zip(got, want):
        close(g, r)


def test_mamba1_mixer_and_its_gradients_against_the_reference():
    m = model("ms", 0)
    cfg = config(m)
    params = job.init_params(jax.random.key(2), cfg, INIT)
    lp = {k: v[0] for k, v in params["runs"][0][1].items()}
    lw = job.to_reference_layout(params, cfg)["layers"][1]
    h = jax.random.normal(jax.random.key(3), (2, SEQ, 64))

    def mine(h, lp):
        return ssm.mamba1_mixer(h, lp, chunk=32)

    def theirs(h, lw):
        f, y, _ = ref.mamba(h, lw, m)
        return f, y
    w = jax.random.normal(jax.random.key(4), (2, SEQ, 64))

    def with_grads(mixer):
        """((_, both outputs), the gradients of the first under w): one
        program."""
        def loss(h, leaves):
            out = mixer(h, leaves)
            return jnp.sum(out[0] * w), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    (_, out_mine), g_mine = with_grads(mine)(h, lp)
    (_, out_ref), g_ref = with_grads(theirs)(h, lw)
    for a, b in zip(out_mine, out_ref):
        close(a, b)
    close(g_mine[0], g_ref[0])
    for mine_name, ref_name, turned in (
            ("w_in", "in_proj", True), ("w_x", "x_proj", True),
            ("w_dt", "dt_proj", True), ("dt_bias", "dt_proj_bias", False),
            ("A_log", "A_log", False), ("D", "D", False),
            ("conv_w", "conv1d", False), ("conv_b", "conv1d_bias", False),
            ("w_out", "out_proj", True)):
        r = g_ref[1][ref_name]
        close(g_mine[1][mine_name], r.T if turned else r)


# ---- differential attention ---------------------------------------------------


def dense_differential(q, k, v, lam, window):
    """Published layout, float32, whole: q `[B, T, P, 2, hd]`, k
    `[B, T, G, 2, hd]`, v `[B, T, G, 2hd]` -> `A1 V - lam A2 V`
    `[B, T, P, 2hd]`."""
    t, per = q.shape[1], q.shape[2] // k.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    kk = jnp.repeat(k, per, axis=2)
    s = jnp.einsum("bqpjd,bkpjd->bpjqk", q, kk) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bpjqk,bkpd->bqpjd", p, jnp.repeat(v, per, axis=2))
    return o[:, :, :, 0] - lam * o[:, :, :, 1]


def program_order(q):
    """Published `[B, T, (g, r), j, hd]` -> the program's `[B, T, (g, j,
    r), hd]`."""
    b, t, p, _, hd = q.shape
    return jnp.swapaxes(q.reshape(b, t, p // 2, 2, 2, hd), 3, 4).reshape(
        b, t, 2 * p, hd)


@pytest.mark.parametrize("window", [0, 128])
def test_splash_maps_in_interpret_mode_against_dense(window):
    """The kernel call differential attention makes: 8 query heads of 64
    over 4 key heads, value heads of 128 repeated for their two maps, under
    a window or the causal mask; the pallas kernels in interpret mode
    against the dense masked path, forward and gradients."""
    t = 256
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (1, t, 8, 64))
    k = jax.random.normal(ks[1], (1, t, 4, 64))
    v = jnp.repeat(jax.random.normal(ks[2], (1, t, 2, 128)), 2, axis=2)
    w = jax.random.normal(ks[3], (1, t, 8, 128))
    flash = functools.partial(attn_ops._splash_attention, causal=True,
                              scale=0.125, window=window, interpret=True)
    dense = functools.partial(attn_ops.dense_attention, causal=True,
                              scale=0.125, window=window)
    close(flash(q, k, v), dense(q, k, v), 2e-3)
    g_f = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    g_d = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g_f, g_d):
        close(a, b, 2e-3)
    # the window is seen: the same call under the other mask differs
    other = functools.partial(attn_ops.dense_attention, causal=True,
                              scale=0.125, window=0 if window else 128)
    assert float(jnp.abs(other(q, k, v) - dense(q, k, v)).max()) > 0.1


@pytest.mark.parametrize("kind,impl", [
    ("w", "dense"), ("f", "dense"), ("c", "dense"),
    ("w", "flash"), ("f", "flash"), ("c", "flash")])
def test_differential_attention_layer_against_dense_masked(
        kind, impl, monkeypatch):
    """One attention layer of each kind through `_make_layer_fn` against
    the formula in the published layout, whole and dense; `flash` is the
    splash path in interpret mode."""
    t, d, window = (256, 256, 128) if impl == "flash" else (SEQ, 64, 16)
    heads = d // 64 if impl == "flash" else 8
    if impl == "flash":
        monkeypatch.setattr(attn_ops, "flash_attention", functools.partial(
            attn_ops._splash_attention, interpret=True))
    m = model("sf" + kind if kind == "c" else "s" + kind, 5,
              hidden_size=d, num_attention_heads=heads,
              num_key_value_heads=heads // 2, sliding_window=window)
    cfg = config(m, t, attention_impl=impl)
    params = job.init_params(jax.random.key(6), cfg, INIT)
    place = len(m["layer_kinds"]) - 1
    lp = {k_: v_[0] for k_, v_ in params["runs"][0][place].items()}
    lw = job.to_reference_layout(params, cfg)["layers"][place]
    layer = Transformer._make_layer_fn(cfg, None, ShardingRules(), None,
                                       None, seq_len=t)
    x = jax.random.normal(jax.random.key(7), (1, t, d))
    hd = d // heads
    kf = jax.random.normal(jax.random.key(8), (1, t, heads // 4, 2, hd))
    vf = jax.random.normal(jax.random.key(9), (1, t, heads // 4, 2 * hd))
    shared = {"k": kf.reshape(1, t, heads // 2, hd), "v": vf}
    # the attention sublayer alone: without the MLP's leaves
    sub = {n: leaf for n, leaf in lp.items()
           if n not in ("mlp_norm", "mlp_norm_bias", "w_gateup", "w_down")}
    out, _, made = jax.jit(lambda x, sub, shared: layer(
        x, sub, shared, kind))(x, sub, shared)

    n = ref.layer_norm(x, lw["input_layernorm"], 1e-5)
    if kind == "c":
        q = ref.linear(n, lw["Wq"], lw["Wq_bias"])
        k, v = kf, vf
    else:
        qkv = ref.linear(n, lw["Wqkv"], lw["Wqkv_bias"])
        q = qkv[..., :d]
        k = qkv[..., d:d + d // 2].reshape(1, t, heads // 4, 2, hd)
        v = qkv[..., d + d // 2:].reshape(1, t, heads // 4, 2 * hd)
    lam_init = 0.8 - 0.6 * np.exp(-0.3 * (5 + place))
    lam = jnp.exp(jnp.sum(lw["lambda_q1"] * lw["lambda_k1"])) \
        - jnp.exp(jnp.sum(lw["lambda_q2"] * lw["lambda_k2"])) + lam_init
    o = dense_differential(q.reshape(1, t, heads // 2, 2, hd), k, v, lam,
                           window if kind == "w" else 0)
    o = ref.rms_norm(o, lw["subln"], 1e-5) * (1 - lam_init)
    want = x + ref.linear(o.reshape(1, t, d), lw["out_proj"],
                          lw["out_proj_bias"])
    close(out, want, 2e-3 if impl == "flash" else RTOL)
    if kind == "f":     # what the layer hands on: its K and V as they are
        close(made["k"].reshape(k.shape), k)
        close(made["v"], v)
    else:
        assert made == {}


# ---- tensors that cross layers ---------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_shared_tensors_gradient_is_the_sum_over_their_readers(remat):
    """m and K, V are read by two GMU and two cross layers (one scanned
    run `gc` x 2): their gradient through `_stack` is the sum of the
    gradients each reading layer gives alone, with and without remat."""
    m = model("mwsfgcgc", 12)
    cfg = config(m, remat=remat)
    assert cfg.pattern_runs == [("mwsf", 1), ("gc", 2)]
    params = job.init_params(jax.random.key(10), cfg, INIT)
    run = params["runs"][1]
    ks = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(ks[0], (2, SEQ, 64))
    shared = {"memory": jax.random.normal(ks[1], (2, SEQ, 48)),
              "k": jax.random.normal(ks[2], (2, SEQ, 4, 8)),
              "v": jax.random.normal(ks[3], (2, SEQ, 2, 16))}
    w = jax.random.normal(ks[4], (2, SEQ, 64))
    kw = dict(mesh=None, rules=ShardingRules())

    def through_stack(shared):
        out, _, after = Transformer._stack(run, x, cfg, kinds="gc",
                                           shared=shared, **kw)
        assert set(after) == set(shared)
        return jnp.sum(out * w)

    layer = Transformer._make_layer_fn(cfg, None, ShardingRules(), None,
                                       None, seq_len=SEQ)

    def one_by_one(copies):
        """Every reading layer its own copy of the tensors."""
        h = x
        for i in range(2):
            for kind, sub, copy in zip("gc", run, copies[2 * i:2 * i + 2]):
                h = layer(h, {n: leaf[i] for n, leaf in sub.items()},
                          copy, kind)[0]
        return jnp.sum(h * w)

    whole, total = jax.jit(jax.value_and_grad(through_stack))(shared)
    apart, each = jax.jit(jax.value_and_grad(one_by_one))([shared] * 4)
    close(whole, apart)
    for name in shared:
        close(total[name], sum(g[name] for g in each))
    # a GMU reads the memory alone, a cross layer K and V alone
    assert not np.asarray(each[0]["k"]).any()
    assert not np.asarray(each[1]["memory"]).any()
    assert np.asarray(each[1]["k"]).any() and np.asarray(
        each[2]["memory"]).any()


def test_remat_does_not_recompute_the_shared_tensors():
    """Under `remat=True` the makers' products are inputs of the layers
    that read them: the gradient's jaxpr runs the selective scan's chunk
    loop for the two mixers only (forward, remat's forward and the
    backward each), not once more per reading layer."""
    m = model("mwsfgcgc", 12)
    toks = jax.random.randint(jax.random.key(12), (1, SEQ + 1), 0, 128)

    def scans(kinds):
        mm = dict(m, layer_kinds=kinds, num_hidden_layers=len(kinds))
        cfg = config(mm, remat=True)
        params = job.init_params(jax.random.key(13), cfg, INIT)
        text = str(jax.make_jaxpr(jax.grad(lambda p: Transformer.loss(
            p, {"tokens": toks}, cfg)))(params))
        return text.count("cumsum")
    assert scans("mwsfgcgc") == scans("mwsfgc") > 0


# ---- the model -----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def reference_case(kinds, first):
    """(weights, tokens, the reference's logits, loss and gradients) at a
    pattern: remat changes the program, not what it is held to. The
    reference under one `jax.jit`, its attention in three blocks of
    queries, one ragged."""
    m = model(kinds, first)
    cfg = config(m)
    params = job.init_params(jax.random.key(14), cfg, INIT)
    toks = jax.random.randint(jax.random.key(15), (2, SEQ + 1), 0, 128)
    weights = job.to_reference_layout(params, cfg)

    def loss(w):
        logits = ref.forward(w, toks[:, :-1], m)
        return ref.next_token_loss(logits, toks[:, 1:]), logits

    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        patch.setattr(ref, "QUERY_BLOCK", 40)
        (ref_loss, want), ref_grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(weights)
    return params, toks, want, ref_loss, ref_grads


@pytest.mark.parametrize("kinds,first,runs", [
    ("mwsfgc", 14, [("mwsfgc", 1)]),
    ("mwmwsfgcgc", 12, [("mw", 2), ("sf", 1), ("gc", 2)]),
])
@pytest.mark.parametrize("remat", [False, True])
def test_model_against_the_reference(kinds, first, runs, remat):
    cfg = config(model(kinds, first), remat=remat)
    assert cfg.pattern_runs == runs
    params, toks, want, ref_loss, ref_grads = reference_case(kinds, first)
    close(programs(cfg).logits(params, toks[:, :-1]), want)
    loss, grads = programs(cfg).grads(params, {"tokens": toks})
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    # the tied embedding's gradient: the lookup's and the head's together
    close(grads["embed"], ref_grads["embed_tokens"])
    close(grads["final_norm_bias"], ref_grads["final_layernorm"]["bias"])
    subs = [sub for _, sub in job.sublayers(grads["runs"], cfg)]
    for sub, lw in zip(subs, ref_grads["layers"]):
        close(sub["w_down"], lw["down_proj"].T)
        if "w_gmu_in" in sub:
            close(sub["w_gmu_in"], lw["in_proj"].T)
        if "A_log" in sub:
            close(sub["A_log"], lw["A_log"])
            close(sub["w_dt"], lw["dt_proj"].T)
        if "subln" in sub:
            close(sub["subln"], lw["subln"])
            close(sub["lambda_q1"], lw["lambda_q1"])
            close(sub["bo"], lw["out_proj_bias"])


def test_lambda_init_reads_the_published_index():
    m = model("mwsfgc", 14)
    cfg = config(m)
    params = Transformer.init(jax.random.key(0), cfg)
    got = [float(sub["lambda_init"][0]) for sub in params["runs"][0]
           if "lambda_init" in sub]
    want = [0.8 - 0.6 * np.exp(-0.3 * l) for l in (15, 17, 19)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    frozen = Transformer.frozen(cfg)
    assert [sub.get("lambda_init") for sub in frozen["runs"][0]] == \
        [None, True, None, True, None, True]
    assert sum(jax.tree.leaves(frozen)) == 3


def test_published_pattern_is_three_runs():
    assert pattern_runs("mw" * 8 + "sf" + "gc" * 7) == \
        [("mw", 8), ("sf", 1), ("gc", 7)]


@pytest.mark.parametrize("pattern,names", [
    ("mwgfsc", "gated memory unit (g)"),
    ("mwsgcf", r"cross-attention \(c\)"),
    ("mwgc", "gated memory unit (g)"),
    ("sfc" + "sg", "one layer makes it"),
    ("mwsffc", "one layer makes it"),
])
def test_config_refuses_a_reader_without_its_maker(pattern, names):
    m = model("mwsfgc", 14)
    cfg = config(m)
    with pytest.raises(ValueError, match=names.replace("(", r"\(").replace(
            ")", r"\)") if "\\" not in names else names):
        cfg.replace(layer_pattern=pattern, n_layers=len(pattern))


@pytest.mark.parametrize("change,names", [
    (dict(ssm_dt_rank=0), "Mamba-1 mixer"),
    (dict(attn_window=0), "window attention"),
    (dict(n_kv_heads=8), "differential attention pairs"),
    (dict(norm="layer"), "unknown norm"),
])
def test_config_refuses_the_new_kinds_without_their_sizes(change, names):
    cfg = config(model("mwsfgc", 14))
    with pytest.raises(ValueError, match=names):
        cfg.replace(**change)


def test_ring_attention_takes_no_window():
    cfg = config(model("mwsfgc", 14), attention_impl="ring")
    mesh = jax.make_mesh((1, 2), ("data", "seq"),
                         devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="no window"):
        Transformer._make_attention(cfg, mesh, ShardingRules(), SEQ,
                                    window=16)
