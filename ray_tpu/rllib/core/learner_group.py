"""LearnerGroup: one local learner or a mesh-coupled gang of learner actors.

reference parity: rllib/core/learner/learner_group.py:63 — local mode
(num_learners=0, learner in-process: the CartPole north-star config) or
remote mode where learner actors form a jax.distributed process group
exactly as the reference LearnerGroup reuses Train's BackendExecutor to
build a torch process group (learner_group.py:103-115). Gradients sync
through XLA collectives over the shared 'data' mesh (the DDP-allreduce
equivalent of torch_learner.py:378-390) — every learner holds identical
replicated params after every step, so there is no unsound weight
averaging and Adam semantics match single-learner training exactly.
On TPU pods each learner process contributes its chips and the psum
rides ICI; in chip-free CI the same code runs over multi-process CPU.

Elastic mode (elastic_min_learners set): the gang survives member
death and explicit resizes. The driver keeps a host-side state cache
(params/opt state, refreshed every `state_refresh_every` successful
updates, default 1 — the gang's durable checkpoint); when an update
loses an actor or
reconfigure() is called, the gang is drained, re-spawned at the new
world size (bounded by elastic_reform_timeout_s, stepping down toward
elastic_min_learners when capacity is short), the cached state is
re-replicated over the new mesh (reshard: each rank re-slices its data
shard by the new world), and the update is retried — with the same
elastic.* span sequence + reconfiguration metrics as the train plane
(train/elastic.py).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional

import numpy as np

logger = logging.getLogger(__name__)


class _MeshLearnerActor:
    """One rank of the learner gang; must run in a fresh worker process
    (jax.distributed can only initialize before any other jax use, which
    the gang's unique runtime-env pool key guarantees)."""

    def __init__(self, factory: Callable[[], Any], coordinator: str,
                 world: int, rank: int, seed: int, gang_id: str = ""):
        import os

        import jax
        # Heartbeat sidecar BEFORE jax.distributed.initialize: the
        # rendezvous itself is a collective that can wedge (a peer
        # SIGSTOPped mid-join), and the supervisor can only see that
        # through beats that started first.
        self._heartbeat = None
        if gang_id:
            from ray_tpu.train.heartbeat import HeartbeatSender
            hb = HeartbeatSender(gang_id, rank)
            if hb.start():
                self._heartbeat = hb
                hb.set_phase("rendezvous")
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            # XLA's CPU backend refuses cross-process computations
            # ("Multiprocess computations aren't implemented on the CPU
            # backend") unless collectives go through gloo — required
            # for the chip-free ladder to exercise real gang updates.
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=world, process_id=rank)
        self.rank = rank
        self.world = world
        self.learner = factory()
        self.learner.build_distributed(seed=seed)
        if self._heartbeat is not None:
            self._heartbeat.set_phase("ready")

    def ping(self) -> str:
        return "pong"

    def _local_shard(self, batch: Dict[str, np.ndarray]
                     ) -> Dict[str, np.ndarray]:
        """Equal per-rank slices along each column's data axis (truncating
        the remainder so every rank runs identical jit step counts).
        Multi-agent batches are nested {module_id: {col: array}}; each
        module's rows shard independently so every rank holds a static
        per-module shape (the lane→module split is deterministic, so all
        ranks agree on each module's row count)."""
        if batch and all(isinstance(v, dict) for v in batch.values()):
            return {mid: self._local_shard(sub)
                    for mid, sub in batch.items()}
        first = next(iter(batch))
        axis = self.learner.data_axis_for(first)
        n = batch[first].shape[axis]
        per = n // self.world
        out = {}
        for k, v in batch.items():
            a = self.learner.data_axis_for(k)
            sl = [slice(None)] * v.ndim
            sl[a] = slice(self.rank * per, (self.rank + 1) * per)
            out[k] = v[tuple(sl)]
        return out

    def update(self, batch, minibatch_size, num_iters, seed):
        if self._heartbeat is not None:
            # the update round is the supervisor's step unit
            self._heartbeat.note_step()
            self._heartbeat.set_phase("update")
        return self.learner.update_distributed(
            self._local_shard(batch), minibatch_size, num_iters, seed)

    def additional_update(self, **kw):
        return self.learner.additional_update(**kw)

    def get_weights(self):
        return self.learner.get_weights()

    def set_weights(self, w):
        self.learner.set_weights(w)

    def get_state(self):
        return self.learner.get_state()

    def set_state(self, s):
        self.learner.set_state(s)


from ray_tpu.train.elastic import free_port as _free_port


class LearnerGroup:
    # wedge supervisor cadence (mirrors train/backend_executor.py)
    WEDGE_POLL_S = 1.0
    WEDGE_HB_REFRESH_S = 2.0

    def __init__(self, learner_factory: Callable[[], Any],
                 num_learners: int = 0, seed: int = 0, *,
                 elastic_min_learners: Optional[int] = None,
                 elastic_reform_timeout_s: float = 60.0,
                 state_refresh_every: int = 1,
                 step_deadline_s: Optional[float] = None):
        if step_deadline_s is not None and step_deadline_s <= 0:
            raise ValueError(
                f"step_deadline_s must be > 0, got {step_deadline_s}")
        self._num_learners = num_learners   # achieved world size
        self._target_learners = num_learners  # what re-forms aim for
        self._factory = learner_factory
        self._seed = seed
        self._elastic_min = elastic_min_learners
        self._reform_timeout_s = elastic_reform_timeout_s
        # gang heartbeat channel id; fresh per formation (_spawn_gang)
        self._gang_uid: Optional[str] = None
        # per-step wedge deadline — enforced only for elastic gangs
        # (explicit step_deadline_s, else auto-calibrated from trailing
        # update times; runtime-tunable via metrics_configure)
        self._step_deadline = None
        if elastic_min_learners is not None:
            from ray_tpu.train.heartbeat import StepDeadline
            self._step_deadline = StepDeadline(step_deadline_s)
        # How many updates between durable-cache refreshes. The cache
        # fetch pulls the FULL params+opt state from rank 0 to the
        # driver, so for large models every-update (the default, exact
        # continuity) can dominate step time; N>1 trades that cost for
        # losing up to N-1 updates when a reconfiguration falls back to
        # an older cache (the caller retries only the failed update).
        if state_refresh_every < 1:
            raise ValueError("state_refresh_every must be >= 1")
        self._state_refresh_every = state_refresh_every
        self._updates_since_refresh = 0
        self._ckpt_state: Optional[Dict[str, Any]] = None
        self._tracker = None
        if elastic_min_learners is not None:
            if num_learners == 0:
                raise ValueError(
                    "elastic_min_learners requires a remote gang "
                    "(num_learners >= 1)")
            if not (1 <= elastic_min_learners <= num_learners):
                raise ValueError(
                    f"elastic_min_learners={elastic_min_learners} not in "
                    f"[1, num_learners={num_learners}]")
            from ray_tpu.train.elastic import ReconfigTracker
            self._tracker = ReconfigTracker("learner")
        if num_learners == 0:
            self._local = learner_factory()
            self._local.build(seed=seed)
            self._actors: List[Any] = []
            return
        self._local = None
        self._actors = self._spawn_gang(num_learners)
        if self._tracker is not None:
            # the gang's durable fallback until the first update lands
            self._ckpt_state = self.get_state()

    @property
    def elastic(self) -> bool:
        return self._tracker is not None

    def _spawn_gang(self, world: int) -> List[Any]:
        """Spawn + rendezvous one gang generation of `world` fresh
        processes. Each formation gets its OWN runtime-env pool key
        (train.elastic.gang_runtime_env): jax.distributed must
        initialize before any other jax use, so a re-form can never
        reuse a previous generation's processes."""
        import uuid

        import ray_tpu
        from ray_tpu.train.elastic import gang_runtime_env
        gang_env = gang_runtime_env("RAY_TPU_LEARNER_GANG")
        coordinator = f"127.0.0.1:{_free_port()}"
        # fresh heartbeat channel per generation: stale rows from a
        # torn-down gang never shadow the new one
        self._gang_uid = f"learner:{uuid.uuid4().hex[:8]}"
        actor_cls = ray_tpu.remote(_MeshLearnerActor)
        actors = [
            actor_cls.options(num_cpus=1, runtime_env=gang_env).remote(
                self._factory, coordinator, world, rank, self._seed,
                self._gang_uid)
            for rank in range(world)
        ]
        # Barrier on gang readiness (rank 0 hosts the coordinator; all
        # ranks block in jax.distributed.initialize until every peer is
        # up — mirror of the reference's process-group rendezvous). On
        # failure the attempt's actors must die HERE: the caller's
        # _kill_gang only sees self._actors, and a leaked attempt would
        # sit blocked in jax.distributed holding its CPUs — making every
        # smaller world size infeasible too.
        try:
            ray_tpu.get([a.ping.remote() for a in actors],
                        timeout=self._reform_timeout_s
                        if self.elastic else 300)
        except BaseException:
            for a in actors:
                try:
                    ray_tpu.kill(a)
                except Exception:  # noqa: BLE001 - actor already dead
                    pass
            raise
        return actors

    def __len__(self) -> int:
        return max(1, self._num_learners)

    # ---- elastic reconfiguration ------------------------------------
    def reconfigure(self, num_learners: Optional[int] = None,
                    reason: str = "manual") -> int:
        """Re-form the gang at `num_learners` (default: the target
        world size) from the cached state; returns the achieved world
        size. An explicit `num_learners` also becomes the new target.
        Elastic gangs only."""
        if not self.elastic:
            raise RuntimeError("reconfigure() requires elastic mode "
                               "(elastic_min_learners)")
        if num_learners is not None:
            # validate BEFORE persisting: a rejected target must not
            # poison later worker_death recoveries
            if num_learners < self._elastic_min:
                raise ValueError(
                    f"target {num_learners} below elastic_min_learners="
                    f"{self._elastic_min}")
            self._target_learners = num_learners
        return self._elastic_reconfigure(
            reason, target=num_learners or self._target_learners)

    def _elastic_reconfigure(self, reason: str, target: int) -> int:
        import ray_tpu
        if not (self._elastic_min <= target):
            raise ValueError(
                f"target {target} below elastic_min_learners="
                f"{self._elastic_min}")
        rec = self._tracker.start(reason,
                                  world_size=len(self._actors))
        try:
            with rec.phase("drain"):
                self._kill_gang()
            with rec.phase("checkpoint") as attrs:
                attrs["cached"] = self._ckpt_state is not None
            achieved: Optional[int] = None
            with rec.phase("reform"):
                # step down toward the min when capacity is short; each
                # attempt is bounded by elastic_reform_timeout_s
                last_err: Optional[BaseException] = None
                for world in range(target, self._elastic_min - 1, -1):
                    try:
                        self._actors = self._spawn_gang(world)
                        achieved = world
                        break
                    except Exception as e:  # noqa: BLE001 - rendezvous
                        last_err = e        # timeout / spawn failure
                        self._kill_gang()
                if achieved is None:
                    raise RuntimeError(
                        f"elastic learner re-form infeasible: no world "
                        f"size in [{self._elastic_min}, {target}] "
                        f"became ready within "
                        f"{self._reform_timeout_s:.0f}s per attempt "
                        f"({last_err!r})")
            self._num_learners = achieved
            with rec.phase("reshard", world_size=achieved):
                if self._ckpt_state is not None:
                    ray_tpu.get(
                        [a.set_state.remote(self._ckpt_state)
                         for a in self._actors], timeout=600)
            with rec.phase("resume"):
                pass  # the caller's retried update is the resume
            rec.finish(achieved)
            return achieved
        except BaseException as e:
            rec.abort(e)
            raise

    def _kill_gang(self) -> None:
        import ray_tpu
        for a in self._actors:
            try:
                ray_tpu.kill(a)
            except Exception:  # noqa: BLE001 - actor already dead
                pass
        self._actors = []
        if self._gang_uid is not None:
            from ray_tpu.train import heartbeat as hb
            from ray_tpu.train.elastic import _core_worker_or_none
            cw = _core_worker_or_none()
            if cw is not None:
                hb.clear_gang(cw._gcs.call, self._gang_uid)
            self._gang_uid = None

    # ---- updates ----------------------------------------------------
    def update(self, batch: Dict[str, np.ndarray],
               minibatch_size: Optional[int] = None,
               num_iters: int = 1, seed: int = 0) -> Dict[str, float]:
        from ray_tpu._private import goodput
        if self._local is not None:
            # the local learner computes in-process: sentinel compile
            # events on this thread re-attribute warmup out of the
            # productive window
            with goodput.bucket(goodput.PRODUCTIVE):
                return self._local.update(batch, minibatch_size,
                                          num_iters, seed)
        try:
            with goodput.bucket(goodput.PRODUCTIVE):
                return self._update_remote(batch, minibatch_size,
                                           num_iters, seed)
        except Exception as e:  # noqa: BLE001 - actor death mid-update
            from ray_tpu.exceptions import RayTaskError
            from ray_tpu.train.backend_executor import GangWedgedError
            if not self.elastic or isinstance(e, RayTaskError):
                # a RayTaskError means the update RAN and raised — a
                # deterministic application error that a gang re-form
                # would only replay (and miscount as a worker_death
                # reconfiguration); only infrastructure failures
                # (actor death, lost worker, timeout, wedge) reconfigure
                raise
            logger.warning(
                "elastic learner gang update failed (%r); "
                "reconfiguring and retrying", e)
            # aim back at the TARGET, not the achieved size: a gang
            # that degraded to 3/4 must try for 4 again when capacity
            # returns, not ratchet down toward the minimum
            self._elastic_reconfigure(
                "wedge" if isinstance(e, GangWedgedError)
                else "worker_death",
                target=self._target_learners)
            with goodput.bucket(goodput.PRODUCTIVE):
                return self._update_remote(batch, minibatch_size,
                                           num_iters, seed)

    def _update_remote(self, batch, minibatch_size, num_iters, seed):
        import ray_tpu
        # Same full batch + same seed to every rank: each slices its own
        # equal shard and all ranks enter the jitted collective step the
        # same number of times.
        refs = [a.update.remote(batch, minibatch_size, num_iters, seed)
                for a in self._actors]
        if self.elastic:
            # wedge-aware wait: a rank SIGSTOPped inside the psum
            # otherwise blocks every peer for the full 600s get
            stats = self._await_update(refs, timeout=600)
        else:
            stats = ray_tpu.get(refs, timeout=600)
        # Scalars mean-reduce across ranks; array stats (per-sample TD
        # errors + their batch indexes) concatenate in rank order — each
        # rank reported its own shard of the global batch.
        out: Dict[str, Any] = {}
        for k in stats[0]:
            if getattr(stats[0][k], "ndim", 0):
                out[k] = np.concatenate([np.asarray(s[k]) for s in stats])
            else:
                out[k] = float(np.mean([s[k] for s in stats]))
        if self.elastic:
            # refresh the durable fallback: the state every rank holds
            # after this (replicated) step — what a reconfiguration
            # reshards from (paced by state_refresh_every for large
            # models; a failed fetch just leaves the older cache)
            self._updates_since_refresh += 1
            if self._updates_since_refresh >= self._state_refresh_every:
                try:
                    self._ckpt_state = ray_tpu.get(
                        self._actors[0].get_state.remote(), timeout=600)
                    self._updates_since_refresh = 0
                except Exception:  # noqa: BLE001 - the NEXT update's
                    pass           # failure path uses the older cache
        return out

    # ---- collective-wedge supervisor (train/heartbeat.py) -----------
    def _await_update(self, refs: List[Any], timeout: float
                      ) -> List[Any]:
        """Await one update round with the wedge trip armed — the
        learner-plane mirror of BackendExecutor._await_round. Short
        wait slices; between slices the supervisor refreshes the gang
        heartbeat table (which also carries the runtime step-deadline
        override) and, once the deadline expires, checks staleness.
        Two-factor trip: deadline expired AND >= 1 stale heartbeat —
        every-rank-fresh-but-slow keeps waiting. On a trip the wedged
        pids are hard-killed via their node managers and
        GangWedgedError routes into _elastic_reconfigure with
        reason="wedge". Round times feed the deadline calibrator."""
        import time as _time

        import ray_tpu
        from ray_tpu.train import heartbeat as hb
        from ray_tpu.train.backend_executor import GangWedgedError
        t0 = _time.monotonic()
        hb_next = 0.0
        override: Optional[float] = None
        while True:
            ready, pending = ray_tpu.wait(
                refs, num_returns=len(refs), timeout=self.WEDGE_POLL_S)
            if not pending:
                stats = ray_tpu.get(  # graftlint: disable=RT002
                    refs, timeout=60)
                self._step_deadline.observe(_time.monotonic() - t0)
                return stats
            now = _time.monotonic()
            if now - t0 > timeout:
                raise TimeoutError(
                    f"no learner update round within {timeout:.0f}s")
            if now < hb_next:
                continue
            hb_next = now + self.WEDGE_HB_REFRESH_S
            reply = self._query_heartbeats()
            if reply is None:
                continue
            if reply.get("step_deadline_override_s") is not None:
                override = reply["step_deadline_override_s"]
            deadline = self._step_deadline.current(override)
            if deadline is None or now - t0 < deadline:
                continue
            from ray_tpu._private.config import Config
            stale = hb.stale_ranks(reply,
                                   Config.watchdog_gang_heartbeat_s)
            if not stale:
                continue  # slow but every rank alive: keep waiting
            from ray_tpu._private import spans
            cls = hb.classify_wedge(reply, stale)
            spans.instant(
                "elastic.wedge_detect", gang=self._gang_uid,
                classification=cls["kind"],
                ranks=",".join(str(r) for r in cls["ranks"]),
                nodes=",".join(n[:12] for n in cls["nodes"]),
                deadline_s=round(deadline, 3),
                waited_s=round(now - t0, 3))
            logger.error(
                "elastic learner: step deadline %.1fs expired after "
                "%.1fs with stale heartbeat(s) from rank(s) %s (%s); "
                "hard-killing wedged processes and re-forming",
                deadline, now - t0, cls["ranks"], cls["kind"])
            killed = hb.hard_kill_ranks(stale)
            raise GangWedgedError(
                f"learner rank(s) {cls['ranks']} wedged mid-update "
                f"({cls['kind']}): step deadline {deadline:.1f}s "
                f"expired with heartbeats "
                f"{[round(r['age_s'], 1) for r in stale]}s stale; "
                f"hard-killed ranks {killed} via their node managers")

    def _query_heartbeats(self) -> Optional[Dict[str, Any]]:
        if self._gang_uid is None:
            return None
        from ray_tpu.train import heartbeat as hb
        from ray_tpu.train.elastic import _core_worker_or_none
        cw = _core_worker_or_none()
        if cw is None:
            return None
        try:
            return hb.query_gang(cw._gcs.call, self._gang_uid)
        except Exception:  # noqa: BLE001 - GCS hiccup: retry next slice
            return None

    def additional_update(self, **kwargs) -> Dict[str, Any]:
        if self._local is not None:
            return self._local.additional_update(**kwargs)
        import ray_tpu
        outs = ray_tpu.get(
            [a.additional_update.remote(**kwargs) for a in self._actors],
            timeout=120)
        return outs[0]

    # ---- weights ----------------------------------------------------
    def get_weights(self):
        if self._local is not None:
            return self._local.get_weights()
        import ray_tpu
        return ray_tpu.get(self._actors[0].get_weights.remote(),
                           timeout=600)

    def set_weights(self, w) -> None:
        if self._local is not None:
            self._local.set_weights(w)
            return
        import ray_tpu
        ray_tpu.get([a.set_weights.remote(w) for a in self._actors],
                    timeout=600)

    def get_state(self):
        if self._local is not None:
            return self._local.get_state()
        import ray_tpu
        return ray_tpu.get(self._actors[0].get_state.remote(), timeout=600)

    def set_state(self, state) -> None:
        if self._local is not None:
            self._local.set_state(state)
            return
        import ray_tpu
        ray_tpu.get([a.set_state.remote(state) for a in self._actors],
                    timeout=600)
        if self.elastic:
            self._ckpt_state = state

    def shutdown(self) -> None:
        self._kill_gang()
        if self._tracker is not None:
            self._tracker.close()
