"""What `granite_hybrid_f32` reads with one published term left out, one
document boundary ignored, or computed in a narrower precision: the second
of the two readings a configuration's `tolerance` is set from (the first
is the system's own error, in every run's `reference_logits` and
`reference_loss` checks). Each fault, and the precision below the one the
configuration states, has to come out as not correct; bf16 operands pass.

The reference stays plain and knows no segment: each variant is made
here, outside it, on a fresh copy of the module and on the job's own
weights (`init_params`) and packed reference sample:

    state_carried       the mixers' state carried over every boundary:
                        the documents' recurrences run as one (the
                        convolution and attention still cut)
    conv_taps_cross     the convolution's taps reach over every boundary
                        (the state and attention still cut)
    attention_crosses   attention sees the whole sequence's keys up to
                        the query (the mixers still cut)
    crossing_labels     the labels that cross a boundary left in the loss
                        (the logits are the reference's own: rel_l2 0, and
                        on stand-in weights a crossing label costs what
                        any other does, so the loss hardly moves: what
                        shows is the COUNT of labels trained on, which the
                        job holds equal to its own count of the batch, the
                        check `reference_sample_counters`)
    attn_scale_sqrt     attention_multiplier read as 1/sqrt(head_dim)
    no_residual_mult    residual_multiplier left out (1.0)
    no_embedding_mult   embedding_multiplier left out (1.0)
    no_logits_scaling   logits_scaling left out (1.0)
    rope_applied        rotary embedding (rope_theta, rotate-half) applied
                        to attention's queries and keys
    norm_per_head       the gated norm taken over each head's 64 channels
                        instead of over all 4,096
    no_D_skip           `D x` left out of the scan's output
    bfloat16, float8_e4m3fn, float8_e5m2
                        both operands of every weight matmul rounded
                        (projections, MLPs, head; the scan, attention's
                        two products and everything else stay float32: a
                        floor of what the precision costs)

The three boundary faults are made by running the sequence's documents
TOGETHER through the function that a fault says crosses (one call over
the joined documents) and alone through the rest: still no mask and no
id, only another cutting.

    python3 benchmark/reference/granite_hybrid_faults.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and variant: `rel_l2` of the logits against
the unchanged reference, `loss_diff`, `labels` (the positions the loss
counts) and `correct`: the configuration's two limits applied to the
first two, and the labels' count equal to the reference's.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
BOUNDARY = ("state_carried", "conv_taps_cross", "attention_crosses")
FAULTS = BOUNDARY + (
    "crossing_labels", "attn_scale_sqrt", "no_residual_mult",
    "no_embedding_mult", "no_logits_scaling", "rope_applied",
    "norm_per_head", "no_D_skip")
ONES = {"no_residual_mult": "residual_multiplier",
        "no_embedding_mult": "embedding_multiplier",
        "no_logits_scaling": "logits_scaling"}


def rotary(x, theta: float):
    """Rotate-half rotary embedding of x [B, H, T, D]."""
    import jax.numpy as jnp
    t, d = x.shape[2], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([angles, angles], axis=-1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def fresh_reference(name):
    spec = importlib.util.spec_from_file_location(
        f"_granite_hybrid_f32_{name}", os.path.join(
            BENCH_DIR, "reference", "granite_hybrid_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def joined(ref, crossing: str):
    """A `forward` for `ref` that runs one of three functions over the
    sequence's documents JOINED (what `crossing` names) and everything
    else over each document alone: the whole sequence goes through the
    model once, and every function that does not cross is applied to each
    document's rows by itself (rows are independent in the norms, the
    projections and the MLP; the convolution, the scan and attention cut
    at `starts` unless they are the one that crosses)."""
    import jax
    import jax.numpy as jnp

    plain = {"conv_taps_cross": ref.causal_conv1d,
             "state_carried": ref._compiled_scan(),
             "attention_crosses": ref.causal_attention}
    cuts: list = []     # (start, length) of the sequence being run

    def alone(fn, axis):
        """`fn` applied to each document's slice along `axis` of its
        array arguments (all of full length there), the results joined."""
        def cut(*args, **kw):
            parts, total = [], sum(n for _, n in cuts)
            for start, n in cuts:
                size = ref.padded_length(n)
                sliced = []
                for a in args:   # what runs along the sequence
                    if getattr(a, "ndim", 0) > axis \
                            and a.shape[axis] == total:
                        a = jax.lax.slice_in_dim(a, start, start + n,
                                                 axis=axis)
                        pad = [(0, 0)] * a.ndim
                        pad[axis] = (0, size - n)
                        a = jnp.pad(a, pad)
                    sliced.append(a)
                parts.append(jax.lax.slice_in_dim(
                    fn(*sliced, **kw), 0, n, axis=axis))
            return jnp.concatenate(parts, axis=axis)
        return cut

    if crossing != "conv_taps_cross":
        ref.causal_conv1d = alone(plain["conv_taps_cross"], 1)
    if crossing != "state_carried":
        scan = alone(plain["state_carried"], 1)
        ref._compiled_scan = lambda: scan
    if crossing != "attention_crosses":
        ref.causal_attention = alone(plain["attention_crosses"], 2)

    def forward(weights, tokens, cfg, lengths):
        ref.check(cfg, weights)
        rows = []
        with jax.default_matmul_precision("highest"):
            for row, row_lengths in zip(tokens, lengths):
                cuts[:] = ref.documents(row_lengths, tokens.shape[1])
                rows.append(ref.document_logits(weights, row[None], cfg)[0])
        return jnp.stack(rows)

    return forward


def variant(name, model: Dict[str, Any], weights: Dict[str, Any]):
    """(module, config, weights, forward) of the reference with `name`
    applied (None: the reference as it is)."""
    import jax
    import jax.numpy as jnp

    ref = fresh_reference(name)
    forward = ref.forward
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name in BOUNDARY:
        forward = joined(ref, name)
    elif name == "attn_scale_sqrt":
        ref.softmax_scale = lambda cfg: float(
            cfg["hidden_size"] // cfg["num_attention_heads"]) ** -0.5
    elif name in ONES:
        model = dict(model, **{ONES[name]: 1.0})
    elif name == "rope_applied":
        plain_attn, theta = ref.causal_attention, float(model["rope_theta"])
        ref.causal_attention = lambda q, k, v, scale: plain_attn(
            rotary(q, theta), rotary(k, theta), v, scale)
    elif name == "norm_per_head":
        p = model["mamba_d_head"]

        def per_head(y, z, gain, eps):
            v = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (-1, p))
            return ref.rms_norm(v, gain.reshape(-1, p), eps).reshape(y.shape)
        ref.gated_rms_norm = per_head
    elif name == "no_D_skip":
        ref.skip = lambda y, x, d_skip: y
    elif name not in (None, "crossing_labels"):
        raise KeyError(name)
    return ref, model, weights, forward


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + PRECISIONS) -> Iterator[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from benchlib.spec import load_module

    job = load_module("jobs", model["job"])
    batches = job.PackedBatches(mix, model["vocab_size"], seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = jax.jit(lambda k: job.init_params(k, cfg, model["init"]))(
        jax.random.key(seed))
    weights = jax.jit(lambda p: job.to_reference_layout(p, cfg))(params)
    del params
    sample, _, lengths = batches.reference_sample(**mix["reference_sample"])
    sample = jnp.asarray(sample)

    def side(name):
        ref, cfg_, weights_, forward = variant(name, model, weights)
        logits = forward(weights_, sample[:, :-1], cfg_,
                         ref.input_lengths(lengths, sample.shape[1]))
        # one document over everything: no position is left out but the
        # sequence's own last
        counted = [[sample.shape[1]]] * len(lengths) \
            if name == "crossing_labels" else lengths
        labels = int(ref.trained_positions(counted, sample.shape[1]).sum())
        return logits, float(ref.next_token_loss(logits, sample,
                                                 counted)), labels

    base, base_loss, base_labels = side(None)
    tol = model["tolerance"]
    for name in names:
        logits, loss, labels = side(name)
        diff = logits - base
        rel_l2 = float(jnp.sqrt(jnp.sum(diff * diff)
                                / jnp.sum(base * base)))
        loss_diff = abs(loss - base_loss)
        yield {"seed": seed, "variant": name, "rel_l2": rel_l2,
               "loss_diff": loss_diff, "labels": labels,
               "correct": rel_l2 <= tol["logits_rel_l2"]
               and loss_diff <= tol["loss_abs"]
               and labels == base_labels}


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[3:]:
        for row in read(model, mix, int(seed)):
            print(json.dumps(row), flush=True)
