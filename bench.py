"""Headline benchmark: GPT-2-125M-scale train-step throughput (tokens/sec).

Matches BASELINE.json north-star config #4 ("Ray Train JaxTrainer: GPT-2
125M data-parallel"): a full forward/backward/adamw train step of the
flagship decoder on the available TPU chip(s), bf16 compute / f32 params,
pallas flash attention, fused QKV / gate-up projections, unchunked
cross-entropy, no rematerialization (activations fit 125M@seq1024/batch16:
10.9 GB by the compiler's memory analysis for a v5e). A run that does not
fit fails with the runtime's out-of-memory error; nothing is retried under
other settings. Without a TPU it exits non-zero and prints no metric.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec", "vs_baseline": N, ...}

vs_baseline anchor: 100k tokens/sec/chip ~= GPU-parity for 125M-class
models (A100-80G class at ~40% MFU), set in round 1 assuming nominal v5e
peak (197 bf16 TFLOP/s). This run also MEASURES the chip's achievable
matmul ceiling with a dependent 8192^3 bf16 matmul chain (ROADMAP S1
replaces it with a table of published peaks keyed by device_kind).
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_TOKENS_PER_SEC = 100_000.0
BATCH = 16     # per-device
WARMUP = 3
STEPS = 15

# effective model FLOPs per token for GPT-2 125M @ seq 1024 (fwd+bwd
# matmuls incl. attention + lm head; excludes remat recompute)
MODEL_FLOPS_PER_TOKEN = 968e6


def _measure_matmul_ceiling_tflops() -> float:
    """Achievable bf16 matmul throughput on one chip (dependent chain so
    each matmul waits for the previous — same regime as a train step).

    Timed as t(3N iters) - t(N iters) over 2N iters: the difference
    cancels the constant dispatch and host-read cost of one call."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    m, k, n = 8192, 8192, 8192
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n), jnp.bfloat16)
    wb = jax.random.normal(jax.random.PRNGKey(4), (n, k), jnp.bfloat16)
    base = 8

    @functools.partial(jax.jit, static_argnums=3)
    def chain(x, w, wb, iters):
        return lax.fori_loop(0, iters, lambda i, x: (x @ w) @ wb, x)

    def timed(iters):
        t0 = time.perf_counter()
        jax.device_get(chain(x, w, wb, iters)[0, 0])
        return time.perf_counter() - t0

    for it in (base, 3 * base):  # compile + warm both variants
        jax.device_get(chain(x, w, wb, it)[0, 0])
    short = min(timed(base) for _ in range(2))
    long = min(timed(3 * base) for _ in range(2))
    dt = max(long - short, 1e-9) / (2 * base)
    return 2 * m * k * n * 2 / dt / 1e12


def main() -> int:
    import jax

    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench.py measures the chip and found none: {devices}",
              file=sys.stderr)
        return 1

    import optax

    from ray_tpu.models import GPT2_125M, Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    mesh = make_mesh(MeshConfig(data=-1), devices=devices)
    # No remat, UNCHUNKED loss — the [B,T,vocab] f32 logits fit at batch
    # 16 and the chunked-CE path's per-chunk jax.checkpoint recompute of
    # the lm-head matmul costs ~7% (round-4 sweep: 106.1k tok/s unchunked
    # vs 99.0k chunk=512 vs 74.7k chunk=256@12heads).
    cfg = GPT2_125M.replace(attention_impl="auto", scan_unroll=12,
                            loss_chunk=0)
    params = Transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH * len(devices), cfg.max_seq_len + 1),
        0, cfg.vocab_size)
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh,
        optimizer=optax.adamw(1e-4, weight_decay=0.01))
    state = init_state(params)
    batch = {"tokens": tokens}
    seq = cfg.max_seq_len

    for _ in range(WARMUP):
        state, metrics = train_step(state, batch)
    # block_until_ready is fence enough on this runtime (one v5e chip,
    # PR 22: 5 steps took 0.7406 s to block_until_ready and 0.7411 s to
    # a host read of the loss; enqueueing them took 3.5 ms)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, metrics = train_step(state, batch)
    # the timed window ends in a host read only because the loss is
    # printed; it waits for the last step like block_until_ready does
    final_loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0

    tokens_per_step = BATCH * len(devices) * seq
    value = tokens_per_step * STEPS / dt
    per_chip = value / len(devices)

    del state  # free HBM before the ceiling probe
    ceiling = _measure_matmul_ceiling_tflops()
    model_tflops = per_chip * MODEL_FLOPS_PER_TOKEN / 1e12
    print(json.dumps({
        "metric": "gpt2_125m_train_tokens_per_sec",
        "value": round(value, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(per_chip / BASELINE_TOKENS_PER_SEC, 4),
        "n_devices": len(devices),
        "platform": devices[0].platform,
        "loss": round(final_loss, 4),
        "model_tflops_per_sec": round(model_tflops, 1),
        "measured_matmul_ceiling_tflops": round(ceiling, 1),
        "mfu_vs_measured_ceiling": round(model_tflops / ceiling, 4),
        "remat": cfg.remat,
        "step_ms": round(dt / STEPS * 1e3, 1),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
