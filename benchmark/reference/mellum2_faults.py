"""What `mellum2_f32` reads with one term of a layer misread or left out,
the exchange done wrongly, or computed in a narrower precision: the second
of the two readings a configuration's `tolerance` is set from (the first
is the system's own error, in every run's `reference_logits` and
`reference_loss` checks). Each fault, and the precision below the one the
configuration states, has to come out as not correct by one of the two
limits; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`) and
sample:

  the window
    window_one_short        a sliding layer sees window - 1 keys
    window_one_long         a sliding layer sees window + 1 keys
    no_window_on_sliding    a sliding layer is causal
    window_on_full          the full layer under the window
  the rotary embedding
    no_yarn_on_full         the full layer's table plain
    yarn_on_sliding         YaRN's table on the sliding layers too
    no_attention_factor     YaRN's cos and sin not scaled
    attention_factor_on_q   scaled on q alone: a score times the factor,
                            not its square
    ramp_reversed           the ramp the wrong way round: the fast pairs
                            divided by the factor, the slow ones kept
    pairs_interleaved       pairs (2p, 2p + 1) where the family takes
                            (p, p + d/2)
  the layer
    no_qk_norm              the QK-norm left out
    qk_norm_whole           the QK-norm over the whole projection, not a
                            head at a time (the gain tiled over the heads)
    topk_not_normalised     the chosen weights not normalised
  the exchange (the reference knows none: these are what a wrong one
  would compute, in the reference's own terms: sequence c is chip c's,
  expert e lives on chip e div (E / chips))
    bucket_never_sent       the slots of chip 1's tokens routed to chip
                            2's experts add nothing
    expert_offset_lost      every chip runs the rows it received through
                            chip 0's experts: expert e mod (E / chips)
    returned_in_wrong_order the results of the rows chip 1 sent chip 2
                            come back in the reverse of the order sent
  bfloat16, float8_e4m3fn, float8_e5m2
                            both operands of every weight matmul rounded
                            (projections, router, experts, head;
                            attention's two products and everything else
                            stay float32: a floor of what the precision
                            costs)

    python3 benchmark/reference/mellum2_faults.py <config.json> \\
        <traffic.json> <tokens a sequence, 0: the traffic's> <seed> ...

prints one JSON line per seed and variant: `rel_l2` of the logits against
the unchanged reference, `loss_diff`, and `correct`, the configuration's
two limits applied to them. On as many devices as the configuration's
mesh takes the weights lie as the cell's do; on fewer, on one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
WINDOW_FAULTS = ("window_one_short", "window_one_long",
                 "no_window_on_sliding", "window_on_full")
ROPE_FAULTS = ("no_yarn_on_full", "yarn_on_sliding", "no_attention_factor",
               "attention_factor_on_q", "ramp_reversed", "pairs_interleaved")
LAYER_FAULTS = ("no_qk_norm", "qk_norm_whole", "topk_not_normalised")
EXCHANGE_FAULTS = ("bucket_never_sent", "expert_offset_lost",
                   "returned_in_wrong_order")
FAULTS = WINDOW_FAULTS + ROPE_FAULTS + LAYER_FAULTS + EXCHANGE_FAULTS
BUCKET = (1, 2)    # (from chip, to chip) of the faults that take one
COMPARE_CHUNK = 1024


def faulty_moe(name: str, ref, chips: int):
    """`mellum2_f32.sparse_moe` as a wrongly done exchange would compute
    it (module docstring). m `[N, hidden]` is `chips` equal runs of
    tokens, chip by chip."""
    import jax.numpy as jnp
    import numpy as np

    def sparse_moe(m, lw, cfg):
        top_w, top_e = ref.route(m, lw, cfg)
        n, k = top_e.shape
        experts = cfg["num_experts"]
        held = experts // chips
        chip_of_token = np.arange(n) // (n // chips)
        frm, to = BUCKET
        # [N, k]: the slots of the one bucket
        in_bucket = (chip_of_token[:, None] == frm) & (
            np.asarray(top_e) // held == to)
        y = jnp.zeros_like(m)
        for e, ew in sorted(lw["experts"].items()):
            if name == "expert_offset_lost":
                picked = top_e % held == e   # chip 0's, whoever was meant
                if e >= held:
                    continue
            else:
                picked = top_e == e
                if name in ("bucket_never_sent", "returned_in_wrong_order"):
                    picked = picked & ~in_bucket
            weight = jnp.sum(jnp.where(picked, top_w, 0.0), axis=-1)
            y = y + weight[:, None] * ref._compiled_expert_mlp()(
                m, ew["gate_proj"], ew["up_proj"], ew["down_proj"])
        if name == "returned_in_wrong_order":
            # the bucket's slots in the order sent: by expert, then by
            # slot; each gets the result of the slot at the mirrored place
            flat = np.flatnonzero(in_bucket.reshape(-1))
            ids = np.asarray(top_e).reshape(-1)[flat]
            sent = flat[np.argsort(ids, kind="stable")]
            out = jnp.zeros((sent.size, m.shape[1]), m.dtype)
            for e in np.unique(ids):
                at = np.flatnonzero(np.asarray(top_e).reshape(-1)[sent] == e)
                ew = lw["experts"][int(e)]
                out = out.at[at].set(ref._compiled_expert_mlp()(
                    m[sent[at] // k], ew["gate_proj"], ew["up_proj"],
                    ew["down_proj"]))
            weights = top_w.reshape(-1)[sent]
            y = y.at[sent // k].add(weights[:, None] * out[::-1])
        return y, top_e

    return sparse_moe


def variant(name, model: Dict[str, Any], chips: int = 4):
    """(module, config) of the reference with the fault or the precision
    `name` applied (None: as it is)."""
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_mellum2_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                             "mellum2_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    rope = model["rope_parameters"]
    window = model["sliding_window"]
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name in ("window_one_short", "window_one_long"):
        plain_mask = ref.sliding_mask
        off = -1 if name == "window_one_short" else 1
        ref.sliding_mask = lambda t, w: plain_mask(t, w + off)
    elif name == "no_window_on_sliding":
        ref.sliding_mask = lambda t, w: ref.causal_mask(t)
    elif name == "window_on_full":
        plain_mask = ref.sliding_mask
        ref.causal_mask = lambda t: plain_mask(t, window)
    elif name == "no_yarn_on_full":
        model = dict(model, rope_parameters=dict(
            rope, full_attention=rope["sliding_attention"]))
    elif name == "yarn_on_sliding":
        model = dict(model, rope_parameters=dict(
            rope, sliding_attention=rope["full_attention"]))
    elif name == "no_attention_factor":
        ref.attention_factor = lambda rope: 1.0
    elif name == "attention_factor_on_q":
        # the same scores as the factor on q alone: its root on both
        plain_factor = ref.attention_factor
        ref.attention_factor = lambda rope: plain_factor(rope) ** 0.5
    elif name == "ramp_reversed":
        def reversed_ramp(head_dim, rope):
            low, high = ref.yarn_bounds(head_dim, rope)
            ramp = 1.0 - jnp.clip(
                (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                / max(high - low, 0.001), 0.0, 1.0)
            plain = ref.plain_inv_freq(head_dim, float(rope["rope_theta"]))
            return plain * (1.0 - ramp) + plain / rope["factor"] * ramp
        ref.yarn_inv_freq = reversed_ramp
    elif name == "pairs_interleaved":
        def rotate_pairs(x):
            pairs = x.reshape(x.shape[:-1] + (-1, 2))
            return jnp.stack([-pairs[..., 1], pairs[..., 0]],
                             axis=-1).reshape(x.shape)

        plain_tables = ref.rope_tables

        def interleaved_tables(positions, head_dim, rope):
            cos, sin = plain_tables(positions, head_dim, rope)
            half = head_dim // 2
            return (jnp.repeat(cos[:, :half], 2, axis=-1),
                    jnp.repeat(sin[:, :half], 2, axis=-1))
        ref.rotate_half, ref.rope_tables = rotate_pairs, interleaved_tables
    elif name == "no_qk_norm":
        ref.qk_norm = lambda x, gain, eps: x
    elif name == "qk_norm_whole":
        plain_norm = ref.rms_norm
        ref.qk_norm = lambda x, gain, eps: plain_norm(
            x.reshape(x.shape[:2] + (-1,)), jnp.tile(gain, x.shape[2]),
            eps).reshape(x.shape)
    elif name == "topk_not_normalised":
        model = dict(model, norm_topk_prob=False)
    elif name in EXCHANGE_FAULTS:
        ref.sparse_moe = faulty_moe(name, ref, chips)
    elif name is not None:
        raise KeyError(name)
    return ref, model


def readings(model: Dict[str, Any], weights: Dict[str, Any], tokens,
             targets, names=FAULTS + PRECISIONS, chips: int = 4
             ) -> Iterator[Dict[str, Any]]:
    """One row a variant: the logits' `rel_l2` and the loss's difference
    against the unchanged reference on the sample, compared a run of
    positions at a time (the logits are never whole)."""
    import jax
    import numpy as np

    block = model.get("reference_query_block")

    def hidden_of(name):
        ref, cfg = variant(name, model, chips)
        with jax.default_matmul_precision("highest"):
            return ref, cfg, ref.hidden(weights, tokens, cfg,
                                        query_block=block)

    plain, _, base = hidden_of(None)

    def compared(ref, cfg):
        @jax.jit
        def compare(w, h, base_h, tgt):
            with jax.default_matmul_precision("highest"):
                logits = ref.head(w, h, cfg)
                base_logits = plain.head(w, base_h, model)
            diff = logits - base_logits
            return (jax.numpy.sum(diff * diff),
                    jax.numpy.sum(base_logits * base_logits),
                    jax.numpy.sum(plain.next_token_nll(logits, tgt)),
                    jax.numpy.sum(plain.next_token_nll(base_logits, tgt)))
        return compare

    head_w = {"norm": weights["norm"], "lm_head": weights["lm_head"]}
    for name in names:
        ref, cfg, h = hidden_of(name)
        compare = compared(ref, cfg)
        sums = np.zeros(4)
        for s in range(0, tokens.shape[1], COMPARE_CHUNK):
            e = s + COMPARE_CHUNK
            sums += np.asarray(jax.device_get(compare(
                head_w, h[:, s:e], base[:, s:e], targets[:, s:e])),
                np.float64)
        del h
        yield {"variant": name,
               "rel_l2": float(np.sqrt(sums[0] / sums[1])),
               "loss_diff": float(abs(sums[2] - sums[3]) / targets.size)}


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + PRECISIONS, tokens: int = 0
         ) -> Iterator[Dict[str, Any]]:
    import math

    import jax

    from benchlib.spec import load_module
    from benchlib.traffic import TokenBatches
    from ray_tpu.parallel import MeshConfig, make_mesh

    job = load_module("jobs", model["job"])
    batches = TokenBatches(mix, model["vocab_size"], seed)
    sample_cfg = mix["reference_sample"]
    length = tokens or sample_cfg["tokens"]
    cfg = job.transformer_config(model, model["train"], length)
    layout = model["layout"]
    wanted = math.prod(abs(v) for v in layout["mesh"].values())
    devices = jax.devices()
    mesh = make_mesh(MeshConfig(**layout["mesh"])) \
        if len(devices) == wanted else make_mesh(
            MeshConfig(data=1), devices=devices[:1])
    params = job.sharded_init(jax.random.key(seed), cfg, model["init"], mesh,
                              job.sharding_rules(layout))
    weights = job.reference_weights(params, cfg, mesh)
    del params
    sample = batches.reference_sample(sample_cfg["sequences"], length)
    put = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        ("data", "fsdp"), None))
    tol = model["tolerance"]
    for row in readings(model, weights,
                        jax.device_put(sample[:, :-1], put),
                        jax.device_put(sample[:, 1:], put), names,
                        chips=wanted):
        yield dict(row, seed=seed, tokens=length,
                   correct=row["rel_l2"] <= tol["logits_rel_l2"]
                   and row["loss_diff"] <= tol["loss_abs"])


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[4:]:
        for row in read(model, mix, int(seed), tokens=int(sys.argv[3])):
            print(json.dumps(row), flush=True)
