"""Job kind `rl_algo`: an RLlib algorithm driven through `Algorithm.train()`.

The process that runs the cell is the driver and holds the chip: the
learner is local (a thread of this process, on the device) and the
EnvRunner actors pin themselves to the CPU, as in `chip_smoke.py`'s `rl`
phase. The configuration file names the algorithm's config class by import
path and holds the environment, the runner fleet, the training options and
the guarantees; the traffic mix overrides training options (the replay
proportion) and says how long to warm up and trace.

The window arithmetic is `tools/bench_rl.py`'s: warm until the learner has
made its updates at every batch shape, then deltas of lifetime counters
over `--seconds` of `train()` calls.
"""

from __future__ import annotations

import importlib
import math
import os
import time
from typing import Any, Dict, List

HOST_ANNOTATIONS = ("algo.train",)
FEED_KEYS = ("feed_wait_s", "feed_xfer_s", "learner_busy_s", "batches_fed",
             "feed_bytes")


def build_algorithm(model: Dict[str, Any], mix: Dict[str, Any], seed: int):
    module, _, cls = model["algorithm_config"].partition(":")
    config = getattr(importlib.import_module(module), cls)()
    env_config = {k: tuple(v) if isinstance(v, list) else v
                  for k, v in model.get("env_config", {}).items()}
    training = dict(model["training"])
    training.update({k: v for k, v in model["guarantees"].items()
                     if hasattr(config, k)})
    training.update(mix.get("training_overrides", {}))
    config = (config.environment(model["env"], env_config=env_config)
              .env_runners(**model["env_runners"])
              .training(**training)
              .debugging(seed=seed))
    return config, config.build()


def seeded_batch(algo, config, seed: int) -> Dict[str, Any]:
    """One time-major batch of the learner's contract from the seed:
    random pixels and actions, sparse rewards and terminals, a behaviour
    policy some way off the target's."""
    import numpy as np

    rng = np.random.default_rng([seed, 0x7EF])
    t_len = config.rollout_fragment_length
    b = 2 * config.num_envs_per_env_runner
    n_actions = int(algo.action_space.n)
    return {
        "obs": rng.integers(0, 256, (t_len, b) + tuple(
            algo.observation_space.shape), dtype=np.uint8),
        "actions": rng.integers(0, n_actions, (t_len, b)),
        "rewards": (rng.random((t_len, b)) < 0.05).astype(np.float32)
        * rng.choice([-1.0, 1.0], (t_len, b)).astype(np.float32),
        "dones": rng.random((t_len, b)) < 0.02,
        "behaviour_logp": np.log(rng.uniform(
            0.15, 0.6, (t_len, b))).astype(np.float32),
        "bootstrap_value": rng.normal(0, 0.5, (b,)).astype(np.float32),
    }


def reference_check(algo, config, model, reference, seed: int):
    """The learner's loss on one seeded batch against the plain numpy
    reference fed the system's own logits and values."""
    import jax

    learner = algo.learner_group._local
    batch = seeded_batch(algo, config, seed)
    t_len, b = batch["actions"].shape

    def system(params, batch, extra):
        loss, stats = learner.compute_loss(params, batch, extra)
        obs = batch["obs"].reshape((t_len * b,) + batch["obs"].shape[2:])
        out = learner.module.forward_train(params, {"obs": obs})
        return loss, stats, out["action_dist_inputs"], out["vf_preds"]

    with learner._state_lock:
        loss, stats, logits, values = jax.device_get(jax.jit(system)(
            learner._params, batch, learner.extra_inputs()))
    want = reference.impala_loss(
        logits.reshape(t_len, b, -1), values.reshape(t_len, b), batch,
        gamma=config.gamma, vf_loss_coeff=config.vf_loss_coeff,
        entropy_coeff=config.entropy_coeff,
        clip_rho_threshold=config.clip_rho_threshold,
        clip_pg_rho_threshold=config.clip_pg_rho_threshold)
    got = {"total_loss": float(loss),
           **{k: float(stats[k]) for k in want if k in stats}}
    worst = max(abs(got[k] - want[k]) for k in got)
    return worst <= model["tolerance"]["loss_abs"], {
        "system": got, "reference": want, "worst_abs": worst,
        "allowed": model["tolerance"]["loss_abs"]}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from benchlib import device as bdev
    from benchlib.checks import Checks
    from benchlib.spec import load_module

    model, mix, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    rehearsal = bool(model.get("rehearsal"))
    compiles = bdev.count_compiles()
    device = bdev.require_device(cell["chips"], rehearsal)

    import jax

    import ray_tpu
    from ray_tpu._private import goodput

    checks = Checks()
    check = checks.add
    phases: Dict[str, float] = {}
    clock = time.perf_counter
    t = clock()
    ray_tpu.init()
    algo = None
    try:
        config, algo = build_algorithm(model, mix, ctx["seed"])
        n_runners = config.num_env_runners
        phases["build_s"] = clock() - t
        t = clock()
        ok, detail = reference_check(
            algo, config, model,
            load_module("reference", model["reference"]), ctx["seed"])
        check("reference_loss", ok, detail)
        phases["reference_check_s"] = clock() - t
        t = clock()

        # ---- warm-up: until the learner has updated at every shape ---
        result: Dict[str, Any] = {}
        deadline = clock() + float(mix["warmup_max_seconds"])
        while result.get("num_updates_total", 0) < mix["warmup_min_updates"]:
            if clock() > deadline:
                raise TimeoutError(
                    f"{result.get('num_updates_total', 0)} learner updates "
                    f"in {mix['warmup_max_seconds']} s of warm-up; learner "
                    f"error: {algo._learner_error!r}")
            result = algo.train()
        phases["warmup_s"] = clock() - t
        compiles_before = len(compiles)

        def snapshot(result: Dict[str, Any]) -> Dict[str, Any]:
            feed = result.get("device_feed", {})
            ledger = (goodput.summary().get(model["goodput_job"]) or {}
                      ).get("buckets", {})
            return {
                "sampled": result["num_env_steps_sampled_lifetime"],
                "trained": result["num_env_steps_trained_total"],
                "updates": result["num_updates_total"],
                "feed": {k: feed.get(k, 0.0) for k in FEED_KEYS},
                "goodput": dict(ledger),
            }

        # ---- the measured window ------------------------------------
        base = snapshot(result)
        queue_depth: List[int] = []
        train_calls = 0
        window_started_at = time.time()
        t0 = clock()
        while clock() - t0 < ctx["seconds"]:
            result = algo.train()
            queue_depth.append(result.get("learner_queue_depth", 0))
            train_calls += 1
        window_s = clock() - t0
        end = snapshot(result)
        window_compiles = len(compiles) - compiles_before
        if algo._learner_error is not None:
            raise algo._learner_error

        def delta(key: str) -> float:
            return end[key] - base[key]

        feed = {k: end["feed"][k] - base["feed"][k] for k in FEED_KEYS}
        ledger = {k: end["goodput"].get(k, 0.0) - base["goodput"].get(k, 0.0)
                  for k in end["goodput"]}

        # ---- a few traced seconds, after the window -----------------
        reduced = None
        spans = None
        if ctx["trace"]:
            from benchlib import span_buckets
            spans = span_buckets.attribute(
                ray_tpu.timeline(spans=True),
                since_us=window_started_at * 1e6)

            def traced() -> None:
                nonlocal result
                t1 = clock()
                while clock() - t1 < float(mix["trace_seconds"]):
                    with jax.profiler.TraceAnnotation("algo.train"):
                        result = algo.train()
            reduced = bdev.trace_window(
                os.path.join(ctx["scratch_dir"], "trace"), traced,
                HOST_ANNOTATIONS)

        # ---- checks on the run --------------------------------------
        backends = ray_tpu.get(
            [a.backend.remote() for a in algo.env_runners.actors],
            timeout=60)
        check("runners_on_cpu", backends == ["cpu"] * n_runners, backends)
        healthy = result.get("num_healthy_env_runners")
        check("runners_healthy", healthy == n_runners,
              {"healthy": healthy, "configured": n_runners})
        learner = algo.learner_group._local
        with learner._state_lock:
            platforms = sorted({d.platform
                                for leaf in jax.tree.leaves(learner._params)
                                for d in leaf.devices()})
        check("learner_on_device", platforms == [device["platform"]],
              platforms)
        stats = result.get("learner", {})
        check("learner_stats_finite",
              bool(stats) and all(math.isfinite(v) for v in stats.values()),
              stats)
        ratio = 1.0 + float(config.replay_proportion)
        slack = (config.learner_queue_size + 2) * ratio \
            * config.train_batch_size
        check("trained_within_replay_ratio",
              delta("trained") <= ratio * delta("sampled") + slack,
              {"trained": delta("trained"), "sampled": delta("sampled"),
               "ratio": ratio, "slack_steps": slack})
        check("no_compile_in_window", window_compiles == 0,
              {"compiles_in_window": window_compiles,
               "compiles_in_setup": compiles_before})
        check("updates_in_window", delta("updates") >= 3, delta("updates"))
    finally:
        if algo is not None:
            algo.stop()
        ray_tpu.shutdown()

    per_request = config.rollout_fragment_length \
        * config.num_envs_per_env_runner
    bdev.finish_device(device, reduced)
    return {
        "device": device,
        "correct": checks.all_ok,
        "checks": dict(checks),
        "attempted": int(delta("sampled") // per_request + delta("updates")),
        "failed": int(n_runners - (healthy or 0)),
        "window_started_at": window_started_at,
        "end_to_end": {
            "trained_env_steps_per_s": delta("trained") / window_s},
        "clock": {"setup_phases_s": phases, "window_s": window_s,
                  "train_calls": train_calls},
        "static": {"chips": cell["chips"], "runners": n_runners,
                   "replay_proportion": config.replay_proportion,
                   "train_batch_size": config.train_batch_size},
        "counters": {
            "sampled_env_steps": delta("sampled"),
            "trained_env_steps": delta("trained"),
            "updates": delta("updates"),
            "device_feed": feed,
            "goodput_s": ledger,
            "learner_queue_depth": {
                "min": min(queue_depth), "max": max(queue_depth),
                "median": sorted(queue_depth)[len(queue_depth) // 2],
                "size": config.learner_queue_size},
            "reference": checks["reference_loss"]["detail"],
        },
        "spans": spans,
        "trace": reduced,
    }
