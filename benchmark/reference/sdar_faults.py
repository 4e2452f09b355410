"""What `sdar_f32` reads with one term of the objective or of the layer
misread or left out, or computed in a narrower precision: the second of
the two readings a configuration's `tolerance` is set from (the first is
the system's own error, in every run's `reference_logits` and
`reference_loss` checks). Each fault, and the precision below the one the
configuration states, has to come out as not correct by one of the two
limits; bf16 operands pass.

The reference stays plain: each variant is made here, outside it, on a
fresh copy of the module and on the job's own weights (`init_params`,
`balance_held_share`), noised sample and share:

  the mask
    own_clean_copy_visible  a noised block sees its own clean copy (`<=`
                            for `<`: the leak that makes a trained
                            model's loss trivially small)
    noised_block_causal     a noised block read causally inside
    clean_sees_noised       a clean position sees its block's noised copy
    positions_run_on        the clean half's position ids run on from L
  the loss (the logits are the reference's own: the loss limit judges)
    no_inverse_t            the 1/t left out (weight 1 on masked positions)
    every_position          over every position, not the masked ones
    targets_shifted         targets shifted by one (next-token training)
  the layer
    no_qk_norm              the QK-norm left out
    qk_norm_whole           the QK-norm over the whole projection, not a
                            head at a time (the gain tiled over the heads)
    topk_not_normalised     the chosen weights not normalised
  bfloat16, float8_e4m3fn, float8_e5m2
                            both operands of every weight matmul rounded
                            (projections, router, experts, head;
                            attention's two products and everything else
                            stay float32: a floor of what the precision
                            costs)

    python3 benchmark/reference/sdar_faults.py <config.json> \\
        <traffic.json> <seed> [<seed> ...]

prints one JSON line per seed and variant: `rel_l2` of the logits against
the unchanged reference, `loss_diff` and what it is in units of the
sample's weights' norm (`jobs/train_lm_blockdiff_moe.loss_weight_norm`),
and `correct`, the configuration's two limits applied to them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Iterator

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
MASK_FAULTS = ("own_clean_copy_visible", "noised_block_causal",
               "clean_sees_noised", "positions_run_on")
LOSS_FAULTS = ("no_inverse_t", "every_position", "targets_shifted")
LAYER_FAULTS = ("no_qk_norm", "qk_norm_whole", "topk_not_normalised")
FAULTS = MASK_FAULTS + LOSS_FAULTS + LAYER_FAULTS


def faulty_mask(name: str, length: int, block: int):
    """`sdar_f32.block_diffusion_mask` with one line misread."""
    import jax.numpy as jnp

    i = jnp.arange(2 * length)[:, None]
    j = jnp.arange(2 * length)[None, :]
    blk_i, blk_j = (i % length) // block, (j % length) // block
    noised_i, noised_j = i < length, j < length
    inside = blk_i == blk_j
    if name == "noised_block_causal":
        inside = inside & (j <= i)
    before = blk_j <= blk_i if name == "own_clean_copy_visible" \
        else blk_j < blk_i
    mask = (noised_i & noised_j & inside) | (noised_i & ~noised_j & before) \
        | (~noised_i & ~noised_j & (blk_j <= blk_i))
    if name == "clean_sees_noised":
        mask = mask | (~noised_i & noised_j & (blk_i == blk_j))
    return mask


def variant(name, model: Dict[str, Any]):
    """(module, config) of the reference with the mask, layer or
    precision fault `name` applied (None, or a loss fault: as it is)."""
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        f"_sdar_f32_{name}", os.path.join(BENCH_DIR, "reference",
                                          "sdar_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    if name in PRECISIONS:
        plain, dtype = ref.linear, jnp.dtype(name)
        ref.linear = lambda x, w: plain(x.astype(dtype), w.astype(dtype))
    elif name == "positions_run_on":
        ref.position_ids = lambda length: jnp.arange(2 * length)
    elif name in MASK_FAULTS:
        ref.block_diffusion_mask = lambda length, block: faulty_mask(
            name, length, block)
    elif name == "no_qk_norm":
        ref.qk_norm = lambda x, gain, eps: x
    elif name == "qk_norm_whole":
        plain_norm = ref.rms_norm
        ref.qk_norm = lambda x, gain, eps: plain_norm(
            x.reshape(x.shape[:2] + (-1,)), jnp.tile(gain, x.shape[2]),
            eps).reshape(x.shape)
    elif name == "topk_not_normalised":
        model = dict(model, norm_topk_prob=False)
    elif name is not None and name not in LOSS_FAULTS:
        raise KeyError(name)
    return ref, model


def loss_inputs(name, clean, weights, times):
    """(targets, weights) of the loss with the loss fault `name` applied;
    `times` `[B, L]`: the time t of each position's block."""
    import jax.numpy as jnp

    if name == "no_inverse_t":
        return clean, (weights > 0).astype(weights.dtype)
    if name == "every_position":
        return clean, 1.0 / times
    if name == "targets_shifted":
        return jnp.roll(clean, -1, axis=1), weights
    return clean, weights


def readings(model: Dict[str, Any], weights: Dict[str, Any],
             noisy: Dict[str, Any], times, names=FAULTS + PRECISIONS
             ) -> Iterator[Dict[str, Any]]:
    """One row a variant: the logits' `rel_l2` and the loss's difference
    against the unchanged reference on the noised sample `noisy`
    (`diffusion.noised`'s batch)."""
    import jax.numpy as jnp

    block = model.get("reference_query_block")

    def logits_of(name):
        ref, cfg = variant(name, model)
        return ref, ref.forward(weights, noisy["tokens"], noisy["targets"],
                                cfg, query_block=block)

    plain, base = logits_of(None)

    def loss_of(name, logits) -> float:
        return float(plain.masked_diffusion_loss(logits, *loss_inputs(
            name, noisy["targets"], noisy["mask"], times)))

    base_loss = loss_of(None, base)
    for name in names:
        logits = base if name in LOSS_FAULTS else logits_of(name)[1]
        diff = logits - base
        yield {"variant": name,
               "rel_l2": float(jnp.sqrt(jnp.sum(diff * diff)
                                        / jnp.sum(base * base))),
               "loss_diff": abs(loss_of(name, logits) - base_loss)}


def block_times(cfg, keys, length: int):
    """The time t of each position's block `[B, length]`, from the
    program's own draws under `keys` `[B, 2]`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import diffusion

    t, _ = jax.vmap(lambda key: diffusion.block_times(key, length, cfg))(
        jax.random.wrap_key_data(jnp.asarray(keys), impl="threefry2x32"))
    return jnp.repeat(t, cfg.block_length, axis=1)


def read(model: Dict[str, Any], mix: Dict[str, Any], seed: int,
         names=FAULTS + PRECISIONS) -> Iterator[Dict[str, Any]]:
    import jax

    from benchlib.spec import load_module
    from benchlib.traffic import TokenBatches

    job = load_module("jobs", model["job"])
    batches = TokenBatches(mix, model["vocab_size"] - 1, seed)
    cfg = job.transformer_config(model, model["train"], batches.tokens)
    params = jax.jit(lambda k: job.init_params(k, cfg, model["init"]))(
        jax.random.key(seed))
    params, _ = job.balance_held_share(params, cfg, None, batches,
                                       model["init"])
    weights = jax.jit(lambda p: job.to_reference_layout(p, cfg))(params)
    del params
    sample = batches.reference_sample(**mix["reference_sample"])[:, :-1]
    keys = job.noise_keys(seed, 2, 0, sample.shape[0])
    noisy = job.noised_sample(cfg, sample, keys)
    times = block_times(cfg, keys, sample.shape[1])
    tol = model["tolerance"]
    weight_norm = job.loss_weight_norm(noisy["mask"])
    for row in readings(model, weights, noisy, times, names):
        ratio = row["loss_diff"] / weight_norm
        yield dict(row, seed=seed, loss_diff_over_weight_norm=ratio,
                   correct=row["rel_l2"] <= tol["logits_rel_l2"]
                   and ratio <= tol["loss_per_weight_norm"])


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
    from benchlib.spec import load_json

    model, mix = load_json(sys.argv[1]), load_json(sys.argv[2])
    for seed in sys.argv[3:]:
        for row in read(model, mix, int(seed)):
            print(json.dumps(row), flush=True)
