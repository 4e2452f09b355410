"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python3 chip_smoke.py             # one TPU chip: phases train, rl
    python3 chip_smoke.py --chips 4   # one four-chip host: phase fsdp only

Drives the two north-star paths once through their user entry points:

  train  ray_tpu.init() + JaxTrainer(...).fit() with one worker holding
         {"TPU": 1}: GPT2_125M at full width and depth (batch 16 x 1,025
         tokens, bf16 compute / f32 params, attention_impl="auto",
         adamw), 3 warm-up + 5 timed steps on seeded random weights.
  rl     ImpalaConfig on MiniPong-v0 as examples/rllib_impala_minipong.py
         (2 CPU EnvRunner actors x 4 envs, fragment 32, batch 256,
         Nature-CNN on [84,84,4] uint8), local learner on the chip,
         until 3 learner updates have finished.
  fsdp   (--chips 4 only) JaxTrainer, one worker holding {"TPU": 4},
         MeshConfig(fsdp=4), same model, batch and seed; then the same
         steps on a one-device mesh in the same worker, compared.

One process for each chip: this parent never imports JAX. It runs each
phase as a child process in turn and waits for it to exit, so the chip
is free for the next. In `train` and `fsdp` the driver stays off JAX and
the train worker holds the chip; in `rl` the driver holds it (local
learner) and the EnvRunner actors are pinned to the CPU.

Every phase prints one JSON line; numbers in them are from a smoke run,
not a benchmark. The LAST line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as reported by the processes that held the chip. Any
failure — no accelerator, a phase that failed, a directory without the
repo — exits non-zero and never prints "ok": true.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

SEED = 0
BATCH = 16
WARMUP_STEPS = 3
TIMED_STEPS = 5
# sharded vs one-device loss. Step 0 runs the same weights on the same
# batch, so only reduction order differs; after that two bf16 training
# trajectories drift apart (0.018 by step 6 on four v5e chips, on a loss
# of 9) — a wrong batch split or a missed gradient reduction shows as
# whole units.
FSDP_LOSS_ATOL_STEP0 = 2e-3
FSDP_LOSS_ATOL = 5e-2
RL_MIN_UPDATES = 3
RL_DEADLINE_S = 90.0
PHASE_TIMEOUT_S = {"train": 600, "rl": 300, "fsdp": 900}


def render_last_line(ok: bool, device: Dict[str, Any]) -> str:
    """The one line the driver reads: exactly `ok` and `device`, and
    `device` exactly `platform`, `kind`, `count`."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": device.get("platform"),
        "kind": device.get("kind"),
        "count": device.get("count")}})


def _device_dict() -> Dict[str, Any]:
    """The device as this process's JAX reports it."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _cache_counter() -> Dict[str, int]:
    """Counts this process's compiles that asked the persistent cache,
    and how many of them it answered."""
    import jax.monitoring
    counts = {"requests": 0, "hits": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


# ---------------------------------------------------------------------
# train / fsdp: the loop a JaxTrainer worker runs
# ---------------------------------------------------------------------


def _run_gpt2(cfg, mesh, lr: float) -> Dict[str, Any]:
    """make_train_step over `mesh`, WARMUP_STEPS + TIMED_STEPS steps on
    one seeded batch; returns losses, timings and the final state."""
    import jax
    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.parallel.train_step import make_train_step

    params = Transformer.init(jax.random.PRNGKey(SEED), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (BATCH, cfg.max_seq_len + 1), 0,
        cfg.vocab_size)
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh,
        optimizer=optax.adamw(lr, weight_decay=0.01))
    state = init_state(params)
    del params
    batch = {"tokens": tokens}

    t0 = time.perf_counter()
    compiled = train_step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    kernel_calls = compiled.as_text().count("tpu_custom_call")
    del compiled

    losses = []
    t0 = time.perf_counter()
    for _ in range(WARMUP_STEPS):
        state, metrics = train_step(state, batch)
        losses.append(metrics["loss"])
    jax.block_until_ready(losses[-1])
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, metrics = train_step(state, batch)
        losses.append(metrics["loss"])
    losses = [float(x) for x in jax.device_get(losses)]  # the host read
    timed_s = time.perf_counter() - t0
    return {
        "state": state,
        "losses": [round(x, 4) for x in losses],
        "attention_impl": Transformer.resolve_attention_impl(cfg, mesh),
        "pallas_kernel_calls": kernel_calls,
        "compile_s": round(compile_s, 2),
        "warmup_s": round(warmup_s, 2),
        "step_ms": round(timed_s / TIMED_STEPS * 1e3, 2),
        "tokens_per_s": round(
            BATCH * cfg.max_seq_len * TIMED_STEPS / timed_s, 1),
    }


def _check_run(tag: str, run: Dict[str, Any], want_kernel: bool) -> None:
    import math
    losses = run["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{tag}: loss did not fall: {losses}")
    if want_kernel and (run["attention_impl"] != "flash"
                        or not run["pallas_kernel_calls"]):
        raise AssertionError(
            f"{tag}: step compiled with attention "
            f"{run['attention_impl']!r} and {run['pallas_kernel_calls']} "
            f"tpu_custom_call(s); expected the pallas flash kernel")


def _gpt2_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: examples/train_gpt2_jax.py's loop (make_mesh
    -> make_train_step -> train.report) at the 125M size."""
    import jax

    import ray_tpu.train as train
    from ray_tpu import models
    from ray_tpu._private.compile_cache import compile_cache_dir
    from ray_tpu.parallel import MeshConfig, make_mesh

    cache = _cache_counter()
    device = _device_dict()
    on_tpu = device["platform"] == "tpu"
    # unrolled, whole-sequence logits: the rolled layer
    # scan keeps 13.8 GB of residuals live at batch 16 (compile-time
    # memory analysis), the unrolled one 9.1 GB
    cfg = getattr(models, config["model"]).replace(
        attention_impl="auto", scan_unroll=True, loss_chunk=0)
    report: Dict[str, Any] = {
        "device": device, "model": config["model"],
        "params": cfg.num_params, "batch": BATCH,
        "seq": cfg.max_seq_len, "steps": WARMUP_STEPS + TIMED_STEPS,
        "cache_dir": compile_cache_dir(),
    }

    mesh = make_mesh(MeshConfig(**config["mesh"]))
    run = _run_gpt2(cfg, mesh, config["lr"])
    state = run.pop("state")
    _check_run("mesh", run, want_kernel=on_tpu)
    report.update(run)
    report["mesh"] = {k: v for k, v in mesh.shape.items() if v > 1}

    if config["compare_one_device"]:
        # every device must hold its share: code that has only seen one
        # chip may put everything on the first
        n = len(jax.devices())
        leaf = state["params"]["layers"]["w_down"]
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n or any(
                s.data.size * n != leaf.size for s in shards):
            raise AssertionError(
                f"fsdp leaf {leaf.shape} is not {n} pieces of 1/{n} on "
                f"{n} devices: "
                f"{[(s.device.id, s.data.shape) for s in shards]}")
        in_use = [d.memory_stats()["bytes_in_use"]
                  for d in jax.devices()] if on_tpu else []
        if not all(in_use):
            raise AssertionError(f"a device holds nothing: {in_use}")
        report["shard_shape"] = list(shards[0].data.shape)
        report["leaf_shape"] = list(leaf.shape)
        report["bytes_in_use"] = in_use
        del state, leaf, shards
        one = _run_gpt2(cfg, make_mesh(MeshConfig(data=-1),
                                       devices=jax.devices()[:1]),
                        config["lr"])
        del one["state"]
        _check_run("one-device", one, want_kernel=on_tpu)
        diffs = [abs(a - b)
                 for a, b in zip(run["losses"], one["losses"])]
        if diffs[0] > FSDP_LOSS_ATOL_STEP0 or max(diffs) > FSDP_LOSS_ATOL:
            raise AssertionError(
                f"sharded and one-device losses differ by {diffs} "
                f"(allowed {FSDP_LOSS_ATOL_STEP0} at step 0, "
                f"{FSDP_LOSS_ATOL} after): "
                f"{run['losses']} vs {one['losses']}")
        report["one_device"] = one
        report["loss_abs_diff_step0"] = round(diffs[0], 5)
        report["loss_abs_diff_max"] = round(max(diffs), 5)
        report["loss_atol"] = [FSDP_LOSS_ATOL_STEP0, FSDP_LOSS_ATOL]
    if on_tpu:
        report["peak_bytes_in_use"] = max(
            d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())
    report["cache_requests"] = cache["requests"]
    report["cache_hits"] = cache["hits"]
    train.report(report)


def _node_facts(need_tpus: int) -> Dict[str, Any]:
    """What the node advertises and which object-store layout it runs,
    checked before any work is scheduled on it."""
    import ray_tpu
    from ray_tpu.util.state import object_store_stats

    tpus = ray_tpu.cluster_resources().get("TPU", 0)
    stores = object_store_stats()["stats"]
    arena = bool(stores) and all(s["native_arena"] for s in stores)
    facts = {"TPU_resource": tpus,
             "store": "native arena" if arena else "file-per-object"}
    if tpus < need_tpus:
        raise RuntimeError(
            f"the node advertises TPU: {tpus}, this phase needs "
            f"{need_tpus}: no accelerator here (chip discovery counts "
            f"/dev/accel* and /dev/vfio/*)")
    if not arena:
        raise RuntimeError(
            "the object store fell back to the file-per-object layout: "
            "ray_tpu/native/store_arena.cpp did not build or load")
    return facts


def _train_phase(name: str, chips: int, mesh: Dict[str, int],
                 compare_one_device: bool, *, model: str = "GPT2_125M",
                 tpus: Optional[int] = None) -> Dict[str, Any]:
    import tempfile

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    tpus = chips if tpus is None else tpus
    ray_tpu.init()
    try:
        facts = _node_facts(tpus)
        resources: Dict[str, float] = {"CPU": 1}
        if tpus:
            resources["TPU"] = tpus
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
            result = JaxTrainer(
                _gpt2_loop,
                train_loop_config={
                    "model": model, "mesh": mesh, "lr": 6e-4,
                    "compare_one_device": compare_one_device},
                scaling_config=ScalingConfig(
                    num_workers=1, resources_per_worker=resources),
                run_config=RunConfig(name=f"chip_smoke_{name}",
                                     storage_path=storage)).fit()
        if result.error is not None:
            raise result.error
    finally:
        ray_tpu.shutdown()
    out = {"phase": name, "ok": True, **facts, **result.metrics,
           # the worker held the chip, so this process must not have
           "driver_imported_jax": "jax" in sys.modules}
    if out["driver_imported_jax"]:
        raise AssertionError("the train driver imported JAX; the chip "
                             "belongs to the train worker")
    return out


def phase_train() -> Dict[str, Any]:
    return _train_phase("train", 1, {"data": -1}, False)


def phase_fsdp() -> Dict[str, Any]:
    return _train_phase("fsdp", 4, {"data": 1, "fsdp": 4}, True)


# ---------------------------------------------------------------------
# rl: IMPALA on MiniPong, local learner on the chip
# ---------------------------------------------------------------------


def phase_rl(require_platform: str = "tpu") -> Dict[str, Any]:
    cache = _cache_counter()
    device = _device_dict()  # this process holds the chip from here on
    if device["platform"] != require_platform:
        raise RuntimeError(f"JAX found no accelerator: {device}")
    import jax

    import ray_tpu
    from ray_tpu._private.compile_cache import compile_cache_dir
    from ray_tpu.rllib.algorithms.impala import ImpalaConfig

    ray_tpu.init()
    algo = None
    try:
        facts = _node_facts(0)
        config = (ImpalaConfig()
                  .environment("MiniPong-v0",
                               env_config={"paddle_w": 5, "max_returns": 3,
                                           "speeds": (-0.5, 0.5)})
                  .env_runners(num_env_runners=2,
                               num_envs_per_env_runner=4,
                               rollout_fragment_length=32)
                  .training(train_batch_size=256, lr=6e-4,
                            entropy_coeff=0.02, vf_loss_coeff=0.5)
                  .debugging(seed=SEED))
        algo = config.build()
        t0 = time.perf_counter()
        result: Dict[str, Any] = {}
        while result.get("num_updates_total", 0) < RL_MIN_UPDATES:
            if time.perf_counter() - t0 > RL_DEADLINE_S:
                raise TimeoutError(
                    f"{result.get('num_updates_total', 0)} learner "
                    f"updates in {RL_DEADLINE_S:.0f}s, need "
                    f"{RL_MIN_UPDATES}; learner error: "
                    f"{algo._learner_error!r}")
            result = algo.train()
        elapsed = time.perf_counter() - t0
        if algo._learner_error is not None:
            raise algo._learner_error
        runner_backends = ray_tpu.get(
            [a.backend.remote() for a in algo.env_runners.actors],
            timeout=60)
        if runner_backends != ["cpu"] * 2:
            raise AssertionError(
                f"EnvRunner actors must act on the CPU: {runner_backends}")
        learner = algo.learner_group._local
        with learner._state_lock:
            param_platforms = sorted({
                d.platform for leaf in jax.tree.leaves(learner._params)
                for d in leaf.devices()})
        if param_platforms != [require_platform]:
            raise AssertionError(
                f"learner params live on {param_platforms}")
        stats = result["learner"]
        if not all(v == v and abs(v) != float("inf")
                   for v in stats.values()):
            raise AssertionError(f"non-finite learner stats: {stats}")
        feed = result["device_feed"]
    finally:
        if algo is not None:
            algo.stop()
        ray_tpu.shutdown()
    return {
        "phase": "rl", "ok": True, "device": device, **facts,
        "learner_updates": result["num_updates_total"],
        "env_steps_sampled": result["num_env_steps_sampled_lifetime"],
        "env_steps_trained": result["num_env_steps_trained_total"],
        "elapsed_s": round(elapsed, 2),
        "env_steps_per_s": round(
            result["num_env_steps_sampled_lifetime"] / elapsed, 1),
        "feed_stall_pct": round(feed["feed_stall_pct"], 1),
        "learner_busy_s": round(feed["learner_busy_s"], 3),
        "learner_stats": {k: round(v, 5) for k, v in stats.items()},
        "learner_param_platforms": param_platforms,
        "env_runner_backends": runner_backends,
        "cache_dir": compile_cache_dir(),
        "cache_requests": cache["requests"], "cache_hits": cache["hits"],
    }


PHASES: Dict[str, Callable[[], Dict[str, Any]]] = {
    "train": phase_train, "rl": phase_rl, "fsdp": phase_fsdp}


# ---------------------------------------------------------------------
# parent: one child process per phase, then the last line
# ---------------------------------------------------------------------


def _run_phase(name: str) -> int:
    """Child entry: run one phase, print its JSON line, exit."""
    import logging
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)
    try:
        result = PHASES[name]()
    except Exception as e:  # noqa: BLE001 - reported, then exit 1
        traceback.print_exc(file=sys.stderr)
        result = {"phase": name, "ok": False,
                  "error": f"{type(e).__name__}: {e}"[:2000]}
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


def _spawn_phase(name: str) -> Optional[Dict[str, Any]]:
    """Run `chip_smoke.py --phase name` in its own session, echo its
    stdout, and return its JSON line (None if it printed none). The
    whole session is killed afterwards: nothing a phase started may
    outlive it or keep the chip."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, text=True, start_new_session=True)

    def kill_session() -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S[name])
    except subprocess.TimeoutExpired:
        print(f"chip_smoke: phase {name} timed out after "
              f"{PHASE_TIMEOUT_S[name]}s", file=sys.stderr)
        kill_session()
        out, _ = proc.communicate()
    finally:
        kill_session()
    result = None
    for line in out.splitlines():
        print(line)
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict) and parsed.get("phase") == name:
            result = parsed
    if proc.returncode != 0 and result is not None:
        result["ok"] = False
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--phase", choices=sorted(PHASES),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.phase:
        return _run_phase(args.phase)

    device: Dict[str, Any] = {}
    ok = True
    for name in (("fsdp",) if args.chips == 4 else ("train", "rl")):
        result = _spawn_phase(name)
        if result is None or not result.get("ok"):
            ok = False
            break
        if device and result["device"] != device:
            print(f"chip_smoke: phase {name} saw {result['device']}, an "
                  f"earlier phase {device}", file=sys.stderr)
            ok = False
            break
        device = result["device"]
    ok = ok and device.get("platform") == "tpu" \
        and device.get("count") == args.chips
    sys.stderr.flush()
    print(render_last_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
