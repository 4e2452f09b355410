"""Learner: the gradient engine, one jitted update.

reference parity: rllib/core/learner/learner.py:231 (Learner ABC:
compute_loss / compute_gradients / postprocess_gradients /
apply_gradients / additional_update at :557,679,988,1042) and
TorchLearner (torch_learner.py:53). The torch stack splits those into
five framework methods because autograd is stateful; in jax the whole
minibatch update — loss, grad, clip, apply — is ONE pure jitted function,
so the TPU Learner exposes compute_loss (override per algorithm) and the
engine jits everything around it. Gradient clipping ≙ postprocess_
gradients; additional_update handles KL-coeff style schedules.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ray_tpu.rllib.core.rl_module import RLModule


class Learner:
    def __init__(self, module: RLModule, config):
        self.module = module
        self.config = config
        self._params = None
        self._opt_state = None
        self._optimizer = None
        self._update_fn = None
        # Serializes updates against weight reads: the jitted update
        # DONATES the params buffer, so a concurrent device_get (e.g. an
        # async IMPALA driver syncing weights while the learner thread
        # trains) would read a deleted array.
        self._state_lock = threading.Lock()
        # mutable non-jitted state for additional_update (e.g. kl coeff)
        self.curr_kl_coeff = getattr(config, "kl_coeff", 0.0)

    # ---- build ------------------------------------------------------
    def build(self, seed: int = 0) -> None:
        import jax
        import optax

        from ray_tpu._private.compile_cache import enable_compile_cache
        enable_compile_cache()

        # params/opt_state are lock-guarded everywhere else (a weight
        # sync racing an update must not tear the pytree); build() is
        # nominally pre-concurrency but is a public entry point, so it
        # takes the same lock rather than asserting callers sequence it
        with self._state_lock:
            self._params = self.module.init_params(
                jax.random.PRNGKey(seed))
        clip = getattr(self.config, "grad_clip", None)
        chain = []
        if clip:
            chain.append(optax.clip_by_global_norm(clip))
        chain.append(optax.adam(self.config.lr))
        self._optimizer = optax.chain(*chain)
        with self._state_lock:
            self._opt_state = self._optimizer.init(self._params)

        def update(params, opt_state, batch, extra):
            def loss_wrap(p):
                loss, stats = self.compute_loss(p, batch, extra)
                return loss, stats

            (loss, stats), grads = jax.value_and_grad(
                loss_wrap, has_aux=True)(params)
            updates, opt_state = self._optimizer.update(
                grads, opt_state, params)
            updates = self.postprocess_updates(updates, extra)
            params = optax.apply_updates(params, updates)
            stats = dict(stats)
            stats["total_loss"] = loss
            stats["grad_norm"] = optax.global_norm(grads)
            return params, opt_state, stats

        def update_idx(params, opt_state, batch, idx, extra):
            # one minibatch with its gather fused into the program (only
            # the small idx crosses the host boundary per step); idx may
            # be a per-module dict for multi-agent batches
            if isinstance(idx, dict):
                mb = {mid: jax.tree.map(lambda v: v[idx[mid]],
                                        batch[mid])
                      for mid in idx}
            else:
                mb = jax.tree.map(lambda v: v[idx], batch)
            return update(params, opt_state, mb, extra)

        def sweep(params, opt_state, batch, idx_mat, extra):
            # The WHOLE minibatch-SGD sweep (num_epochs x minibatches) as
            # one lax.scan program: one XLA dispatch per Learner.update
            # instead of one per minibatch — dispatch latency would
            # otherwise dominate small updates.
            # idx_mat: [steps, minibatch] row indices into batch.
            def body(carry, idx):
                p, o = carry
                p, o, st = update_idx(p, o, batch, idx, extra)
                return (p, o), st

            (params, opt_state), stats_seq = jax.lax.scan(
                body, (params, opt_state), idx_mat)
            return params, opt_state, stats_seq

        self._update_fn = jax.jit(update, donate_argnums=(0, 1))
        self._sweep_fn = jax.jit(sweep, donate_argnums=(0, 1))
        self._update_idx_fn = jax.jit(update_idx, donate_argnums=(0, 1))

    @staticmethod
    def _use_scan_sweep() -> bool:
        """Whether the minibatch-SGD sweep runs as ONE lax.scan program
        or as a python loop of per-minibatch jit calls. Off the CPU it
        scans: each dispatch costs the host about 1.5 ms, more than a
        small update takes on the device (one v5e chip, PR 22: a
        40-minibatch PPO sweep took 4.8 ms scanned against 63 ms looped
        on the CartPole MLP, 17 ms against 68 ms on the Nature-CNN). On
        the CPU it loops: XLA:CPU emits convolutions inside while-loop
        bodies through a slow generic path (~50x slower than the same
        update outside the loop)."""
        import jax
        return jax.default_backend() != "cpu"

    # ---- distributed (mesh gang) build ------------------------------
    def data_axis_for(self, key: str) -> int:
        """Which axis of a batch column is the data-parallel axis (row
        batches → 0; time-major IMPALA sequences override to 1)."""
        return 0

    def build_distributed(self, seed: int = 0) -> None:
        """Build after jax.distributed.initialize: params/opt replicated
        over a 'data' mesh spanning every process, batches sharded along
        the data axis. Gradients all-reduce over ICI because the jitted
        global-mean loss contracts over the sharded batch axis with
        replicated params — the DDP-equivalent the reference gets from
        torch DDP (torch_learner.py:378-390), with XLA inserting the
        psum instead of a wrapper module."""
        import jax
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devices = np.array(jax.devices())
        self._mesh = Mesh(devices, ("data",))
        self._rep = NamedSharding(self._mesh, P())

        host_params = self.module.init_params(jax.random.PRNGKey(seed))

        def _replicate(x):
            return jax.make_array_from_callback(
                np.shape(x), self._rep, lambda idx: np.asarray(x)[idx])

        self._replicate_host = _replicate
        # same locking rationale as build(): public entry, shared state
        with self._state_lock:
            self._params = jax.tree.map(_replicate, host_params)
        clip = getattr(self.config, "grad_clip", None)
        chain = []
        if clip:
            chain.append(optax.clip_by_global_norm(clip))
        chain.append(optax.adam(self.config.lr))
        self._optimizer = optax.chain(*chain)
        with self._state_lock:
            self._opt_state = jax.tree.map(
                _replicate, self._optimizer.init(host_params))

        def update(params, opt_state, batch, extra):
            def loss_wrap(p):
                return self.compute_loss(p, batch, extra)

            (loss, stats), grads = jax.value_and_grad(
                loss_wrap, has_aux=True)(params)
            updates, opt_state = self._optimizer.update(
                grads, opt_state, params)
            updates = self.postprocess_updates(updates, extra)
            params = optax.apply_updates(params, updates)
            stats = dict(stats)
            stats["total_loss"] = loss
            stats["grad_norm"] = optax.global_norm(grads)
            return params, opt_state, stats

        self._update_fn = jax.jit(
            update, donate_argnums=(0, 1),
            out_shardings=(self._rep, self._rep, self._rep))
        self._distributed = True

    def _make_global_batch(self, local: Dict[str, np.ndarray]
                           ) -> Dict[str, Any]:
        """Process-local shard → global jax.Arrays sharded on 'data'."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = {}
        for k, v in local.items():
            axis = self.data_axis_for(k)
            spec = P(*([None] * axis), "data")
            out[k] = jax.make_array_from_process_local_data(
                NamedSharding(self._mesh, spec), np.asarray(v))
        return out

    def update_distributed(self, local_batch: Dict[str, np.ndarray],
                           minibatch_size: Optional[int] = None,
                           num_iters: int = 1,
                           seed: int = 0) -> Dict[str, float]:
        """DDP-style minibatch SGD: every process runs the SAME number of
        jitted steps (collectives wedge otherwise); each step's global
        minibatch is the union of per-process local samples."""
        import jax

        first = next(iter(local_batch))
        axis = self.data_axis_for(first)
        n = local_batch[first].shape[axis]
        nprocs = max(1, jax.process_count())
        local_mb = max(1, (minibatch_size or n * nprocs) // nprocs)
        rng = np.random.default_rng(seed)
        stats: Dict[str, Any] = {}
        count = 0
        for _ in range(num_iters):
            perm = rng.permutation(n)
            for start in range(0, n - local_mb + 1, local_mb):
                idx = perm[start:start + local_mb]
                mb = {k: np.take(v, idx, axis=self.data_axis_for(k))
                      for k, v in local_batch.items()}
                gb = self._make_global_batch(mb)
                with self._state_lock:
                    self._params, self._opt_state, st = self._update_fn(
                        self._params, self._opt_state, gb,
                        self.extra_inputs())
                count += 1
                self._accumulate(stats, st)
        if count == 0:  # batch smaller than one minibatch: single step
            gb = self._make_global_batch(local_batch)
            with self._state_lock:
                self._params, self._opt_state, st = self._update_fn(
                    self._params, self._opt_state, gb, self.extra_inputs())
            count = 1
            stats = {}
            self._accumulate(stats, st)
        return self._finalize(stats, count)

    # ---- algorithm contract ----------------------------------------
    def compute_loss(self, params, batch: Dict[str, Any],
                     extra: Dict[str, Any]) -> Tuple[Any, Dict[str, Any]]:
        raise NotImplementedError

    def additional_update(self, **kwargs) -> Dict[str, Any]:
        return {}

    def postprocess_updates(self, updates, extra):
        """Inside-jit hook between optimizer.update and apply_updates
        (e.g. TD3 masks the actor subtree on non-delayed steps —
        zeroing the LOSS alone wouldn't stop Adam momentum from moving
        the params). Default: identity."""
        return updates

    def extra_inputs(self) -> Dict[str, Any]:
        """Scalars threaded into the jitted loss (kl coeff etc.)."""
        return {}

    def _stage_weights_async(self) -> None:
        """Start async device→host copies of the params so a later
        get_weights (weight broadcast to samplers) finds the data already
        landed instead of paying one blocking round trip per leaf."""
        import jax
        for leaf in jax.tree.leaves(self._params):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()

    # ---- stats ------------------------------------------------------
    @staticmethod
    def _accumulate(stats: Dict[str, Any], st: Dict[str, Any]) -> None:
        """Scalar stats average over minibatches; array-valued stats
        (e.g. per-sample TD errors for prioritized replay) keep the last
        minibatch's values."""
        for k, v in st.items():
            if getattr(v, "ndim", 0):
                stats[k] = np.asarray(v)
            else:
                stats[k] = stats.get(k, 0.0) + float(v)

    @staticmethod
    def _finalize(stats: Dict[str, Any], count: int) -> Dict[str, Any]:
        return {k: (v if isinstance(v, np.ndarray) else v / count)
                for k, v in stats.items()}

    # ---- update loop ------------------------------------------------
    def update(self, batch: Dict[str, np.ndarray],
               minibatch_size: Optional[int] = None,
               num_iters: int = 1,
               seed: int = 0) -> Dict[str, float]:
        """Minibatch SGD over the batch (reference Learner.update /
        TorchLearner._update loop)."""
        import jax

        from ray_tpu._private import spans as _spans
        from ray_tpu.util import jax_sentinel
        with _spans.traced("learner.update", num_iters=num_iters), \
                jax_sentinel.step_region("learner.update"):
            return self._update_impl(batch, minibatch_size, num_iters,
                                     seed, jax)

    def _update_impl(self, batch, minibatch_size, num_iters, seed, jax
                     ) -> Dict[str, float]:
        assert self._update_fn is not None, "call build() first"
        n = len(batch["obs"])
        minibatch_size = minibatch_size or n
        rng = np.random.default_rng(seed)
        # Row-index matrix for the scanned sweep: num_iters epochs of
        # shuffled minibatches, ragged tails dropped (stable jit shapes).
        rows = []
        for _ in range(num_iters):
            perm = rng.permutation(n)
            for start in range(0, n - minibatch_size + 1, minibatch_size):
                rows.append(perm[start:start + minibatch_size])
        if not rows:  # batch smaller than one minibatch: single step
            rows = [rng.permutation(n)]
        idx_mat = np.stack(rows).astype(np.int32)
        # One explicit host→device transfer of the whole batch up front
        # (dispatching jit calls with raw numpy batches can re-transfer
        # per-array, synchronously, on some backends).
        dev_batch = jax.device_put(batch)
        if self._use_scan_sweep():
            # ONE jitted lax.scan dispatch for the whole sweep
            with self._state_lock:
                self._params, self._opt_state, stats_seq = \
                    self._sweep_fn(self._params, self._opt_state,
                                   dev_batch, idx_mat,
                                   self.extra_inputs())
            return self._sweep_stats(jax.device_get(stats_seq))
        return self._loop_sweep(dev_batch, list(idx_mat))

    def _loop_sweep(self, dev_batch, step_indices) -> Dict[str, Any]:
        """Loop-sweep shared by single- and multi-agent update paths:
        one dispatch per minibatch, stats forced once at the end so the
        steps still pipeline."""
        import jax

        pending = []
        extra = self.extra_inputs()
        with self._state_lock:
            for idx in step_indices:
                self._params, self._opt_state, st = self._update_idx_fn(
                    self._params, self._opt_state, dev_batch, idx, extra)
                pending.append(st)
        host = jax.device_get(pending)  # single forcing point
        stacked = {k: np.stack([np.asarray(s[k]) for s in host])
                   for k in host[0]} if host else {}
        return self._sweep_stats(stacked)

    @staticmethod
    def _sweep_stats(stats_seq: Dict[str, Any]) -> Dict[str, Any]:
        """Stacked scan stats -> reported stats: scalars average over
        minibatches; array-valued stats (e.g. per-sample TD errors) keep
        the last minibatch's values — the _accumulate/_finalize
        contract."""
        out: Dict[str, Any] = {}
        for k, v in stats_seq.items():
            arr = np.asarray(v)
            if arr.ndim <= 1:
                out[k] = float(np.mean(arr))
            else:
                out[k] = arr[-1]
        return out

    # ---- weights ----------------------------------------------------
    def get_weights(self):
        import jax
        with self._state_lock:
            return jax.device_get(self._params)

    def set_weights(self, weights) -> None:
        with self._state_lock:
            if getattr(self, "_distributed", False):
                # Host pytrees must be re-laid-out as replicated global
                # arrays or the jitted update would see mixed shardings.
                import jax
                self._params = jax.tree.map(self._replicate_host, weights)
            else:
                self._params = weights

    def get_state(self) -> Dict[str, Any]:
        import jax
        with self._state_lock:
            return {"params": jax.device_get(self._params),
                    "opt_state": jax.device_get(self._opt_state),
                    "kl_coeff": self.curr_kl_coeff}

    def set_state(self, state: Dict[str, Any]) -> None:
        with self._state_lock:
            if getattr(self, "_distributed", False):
                import jax
                self._params = jax.tree.map(self._replicate_host,
                                            state["params"])
                self._opt_state = jax.tree.map(self._replicate_host,
                                               state["opt_state"])
            else:
                self._params = state["params"]
                self._opt_state = state["opt_state"]
            self.curr_kl_coeff = state.get("kl_coeff", self.curr_kl_coeff)


class MultiAgentLearnerMixin:
    """update() over a MultiAgentBatch {module_id: columns}.

    reference parity: Learner.update on a MultiAgentBatch
    (rllib/policy/sample_batch.py MultiAgentBatch; per-module losses in
    core/learner/learner.py compute_loss_for_module). Here one jitted
    lax.scan sweep steps every module together: per-module minibatch
    index vectors gather from per-module sub-batches (static shapes,
    since lane→module routing is fixed), the summed loss yields
    independent per-module gradients, and one optimizer updates the
    union params pytree."""

    def update_distributed(self, local_batch, minibatch_size=None,
                           num_iters=1, seed=0):
        """DDP-style minibatch SGD over a nested {module_id: columns}
        batch. Each rank holds its own per-module shard (equal sizes
        across ranks — _MeshLearnerActor._local_shard truncates), the
        shared seed makes every rank pick identical per-module index
        sets and step counts (collectives wedge otherwise), and each
        step's global minibatch is the per-module union of the local
        samples — per-agent modules shard across learner ranks with
        static per-rank shapes."""
        import jax

        n_m = {mid: len(next(iter(b.values())))
               for mid, b in local_batch.items()}
        empty = [mid for mid, n in n_m.items() if n == 0]
        if empty:
            raise ValueError(
                f"modules {empty} have no rows on this learner rank: "
                f"every rank needs >=1 row per module (grow "
                f"train_batch_size / rollout length or reduce "
                f"num_learners)")
        nprocs = max(1, jax.process_count())
        total = sum(n_m.values())
        local_target = max(1, (minibatch_size or total * nprocs)
                           // nprocs)
        mb_m = {mid: max(1, min(n, round(local_target * n / total)))
                for mid, n in n_m.items()}
        steps_per_epoch = max(1, min(n // mb_m[mid]
                                     for mid, n in n_m.items()))
        rng = np.random.default_rng(seed)
        stats: Dict[str, Any] = {}
        count = 0
        for _ in range(num_iters):
            perms = {mid: rng.permutation(n) for mid, n in n_m.items()}
            for s in range(steps_per_epoch):
                gb = {}
                for mid, b in local_batch.items():
                    idx = perms[mid][s * mb_m[mid]:(s + 1) * mb_m[mid]]
                    gb[mid] = self._make_global_batch(
                        {k: np.take(v, idx,
                                    axis=self.data_axis_for(k))
                         for k, v in b.items()})
                with self._state_lock:
                    self._params, self._opt_state, st = \
                        self._update_fn(self._params, self._opt_state,
                                        gb, self.extra_inputs())
                count += 1
                self._accumulate(stats, st)
        return self._finalize(stats, count)

    def update(self, batch, minibatch_size=None, num_iters=1, seed=0):
        import jax

        assert self._sweep_fn is not None, "call build() first"
        rng = np.random.default_rng(seed)
        n_m = {mid: len(b["obs"]) for mid, b in batch.items()}
        total = sum(n_m.values())
        minibatch_size = minibatch_size or total
        # Per-module minibatch sizes proportional to module rows; every
        # module steps the same number of scan iterations.
        mb_m = {mid: max(1, min(n, round(minibatch_size * n / total)))
                for mid, n in n_m.items()}
        steps_per_epoch = max(1, min(n // mb_m[mid]
                                     for mid, n in n_m.items()))
        rows: Dict[str, list] = {mid: [] for mid in n_m}
        for _ in range(num_iters):
            perms = {mid: rng.permutation(n) for mid, n in n_m.items()}
            for s in range(steps_per_epoch):
                for mid in n_m:
                    start = s * mb_m[mid]
                    rows[mid].append(
                        perms[mid][start:start + mb_m[mid]])
        idx_mat = {mid: np.stack(r).astype(np.int32)
                   for mid, r in rows.items()}
        dev_batch = jax.device_put(batch)
        if self._use_scan_sweep():
            with self._state_lock:
                self._params, self._opt_state, stats_seq = \
                    self._sweep_fn(self._params, self._opt_state,
                                   dev_batch, idx_mat,
                                   self.extra_inputs())
            return self._sweep_stats(jax.device_get(stats_seq))
        # loop sweep (Learner._loop_sweep): per-step dict idx
        n_steps = len(next(iter(idx_mat.values())))
        return self._loop_sweep(
            dev_batch,
            [{mid: m[s] for mid, m in idx_mat.items()}
             for s in range(n_steps)])
