"""Double-buffered host-to-HBM batch feed for TPU learners.

reference parity: SURVEY.md §7.3 names "EnvRunner→Learner throughput"
a hard part — trajectories arrive host-side and the device feed must be
pipelined to keep env-steps/sec/chip up. The reference keeps its GPU fed
with torch pinned-memory prefetch inside the learner; the TPU-native
equivalent stages each batch into reusable pinned host buffers (one
contiguous segment per dtype — HostStage), ships the few segments with
fused `jax.device_put` calls on a feeder thread while the chip executes
update k, and carves the per-column leaves back out ON DEVICE with a
jitted, buffer-donating unfuse (the segment's HBM is reused for the
leaves instead of living twice). Residual blocking time is accounted so
benchmarks report an honest feed-stall %, and the copied-bytes counter +
transfer-time histogram (`ray_tpu_transport_*`) make
`feed_xfer_stall_pct` attributable.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu._private import spans as _spans


def _feed_metrics():
    from ray_tpu.util.metrics import Counter, Histogram, get_or_create
    counter = get_or_create(
        Counter, "ray_tpu_transport_feed_bytes_total",
        description="host->device bytes shipped by DeviceFeed")
    hist = get_or_create(
        Histogram, "ray_tpu_transport_feed_xfer_seconds",
        description="host->device transfer time per batch (seconds)",
        boundaries=[0.0005, 0.002, 0.01, 0.05, 0.2, 1.0, 5.0])
    return counter, hist


class StagedBatch:
    """One train batch packed into per-dtype contiguous host segments.

    `segments` maps dtype name -> 1-D numpy buffer holding every column
    of that dtype back to back; `layout` maps column key ->
    (dtype_name, offset_elems, n_elems, shape). The feed ships the
    segments (a handful of transfers regardless of column count) and
    reconstructs the columns on device; host-side consumers (sync path,
    gang learners) use as_dict().
    """

    __slots__ = ("segments", "layout", "_release_cb")

    def __init__(self, segments: Dict[str, np.ndarray],
                 layout: Dict[str, Tuple[str, int, int, Tuple[int, ...]]],
                 release_cb=None):
        self.segments = segments
        self.layout = layout
        self._release_cb = release_cb

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.segments.values())

    def as_dict(self) -> Dict[str, np.ndarray]:
        """Host-side column views into the staging segments (valid until
        release())."""
        return {k: self.segments[dt][off:off + n].reshape(shape)
                for k, (dt, off, n, shape) in self.layout.items()}

    def release(self) -> None:
        """Hand the staging slot back to its HostStage for reuse. Call
        only when the segments' contents are no longer referenced (the
        transfer landed, or the dict was deep-copied)."""
        cb, self._release_cb = self._release_cb, None
        if cb is not None:
            cb(self.segments)


class HostStage:
    """Pool of reusable per-dtype staging buffers.

    assemble() copies a list of same-structure fragments into ONE
    contiguous buffer per dtype — the copy that np.concatenate would do
    anyway, but into preallocated memory that is reused batch after
    batch (steady state: zero allocations on the trajectory hot path).
    Slots cycle through a bounded free list; if consumers fall behind
    the pool grows a fresh slot rather than deadlocking.
    """

    def __init__(self, slots: int = 4):
        self._slots = max(1, slots)
        self._free: "queue.Queue[Dict[str, np.ndarray]]" = queue.Queue()
        for _ in range(self._slots):
            self._free.put({})
        self.bytes_staged = 0

    def _acquire(self) -> Dict[str, np.ndarray]:
        try:
            return self._free.get_nowait()
        except queue.Empty:
            # all slots in flight (consumer stalled): grow immediately
            # rather than blocking the trajectory assembly hot path
            return {}

    def _release(self, segments: Dict[str, np.ndarray]) -> None:
        # drop oversized pools silently (the grown slot replaces a lost one)
        if self._free.qsize() < self._slots:
            self._free.put(segments)

    def assemble(self, frags: Sequence[Dict[str, np.ndarray]],
                 axis_for) -> StagedBatch:
        """Stack same-structure fragments along axis_for(key) into a
        StagedBatch backed by a pooled slot."""
        with _spans.traced("feed.stage", nfrags=len(frags)) as _sp:
            sb = self._assemble_impl(frags, axis_for)
            _sp["bytes"] = sb.nbytes
            return sb

    def _assemble_impl(self, frags: Sequence[Dict[str, np.ndarray]],
                       axis_for) -> StagedBatch:
        keys = list(frags[0].keys())
        plans: List[Tuple[str, str, int, Tuple[int, ...], int]] = []
        totals: Dict[str, int] = {}
        for k in keys:
            axis = axis_for(k)
            parts = [np.asarray(f[k]) for f in frags]
            shape = list(parts[0].shape)
            shape[axis] = sum(p.shape[axis] for p in parts)
            n = int(np.prod(shape))
            dt = parts[0].dtype.name
            plans.append((k, dt, totals.get(dt, 0), tuple(shape), axis))
            totals[dt] = totals.get(dt, 0) + n
        slot = self._acquire()
        try:
            segments: Dict[str, np.ndarray] = {}
            for dt, n in totals.items():
                buf = slot.get(dt)
                if buf is None or buf.size < n:
                    buf = np.empty(max(n, 1), dtype=np.dtype(dt))
                segments[dt] = buf
            layout: Dict[str, Tuple[str, int, int, Tuple[int, ...]]] = {}
            for k, dt, off, shape, axis in plans:
                n = int(np.prod(shape))
                dest = segments[dt][off:off + n].reshape(shape)
                parts = [np.asarray(f[k]) for f in frags]
                if len(parts) == 1:
                    np.copyto(dest, parts[0])
                else:
                    np.concatenate(parts, axis=axis, out=dest)
                layout[k] = (dt, off, n, shape)
                self.bytes_staged += dest.nbytes
        except BaseException:
            # the StagedBatch below takes slot ownership; until then a
            # failed assembly (mismatched frag shape/dtype) must hand
            # the slot back or the stage permanently loses capacity
            self._release(slot)
            raise
        return StagedBatch(segments, layout, release_cb=self._release)


class DeviceFeed:
    """Pulls (batch, meta) items from a host queue, eagerly dispatches
    the host→device transfer, and hands device-resident batches to the
    consumer.

    `depth` bounds how many transfers may be in flight (double buffering
    at the default 2): enough to hide transfer latency behind compute,
    small enough not to pile batches up in HBM.

    StagedBatch items take the fused path: one device_put per dtype
    segment (instead of one per column), an on-device jitted unfuse that
    DONATES the segment buffers into the reconstructed columns, and slot
    recycling back to the HostStage the moment the transfer lands.

    Stall accounting (all in seconds, monotonically increasing):
      - wait_s: total consumer time blocked in get() — includes upstream
        sample starvation, i.e. the true EnvRunner→Learner gap.
      - xfer_s: the part of wait_s spent waiting for an already-dequeued
        transfer to land in HBM (pure host→device feed stall).
      - busy_s: consumer-reported compute time (add via add_busy).
    """

    def __init__(self, host_queue: "queue.Queue",
                 depth: int = 2,
                 stop_event: Optional[threading.Event] = None,
                 stall_bucket: str = "feed_stall"):
        self._host = host_queue
        # goodput bucket the consumer's blocked get() time charges to
        # (replay learners pass "replay_stall")
        self._stall_bucket = stall_bucket
        self._out: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = stop_event or threading.Event()
        self.wait_s = 0.0
        self.xfer_s = 0.0
        self.busy_s = 0.0
        self.batches = 0
        self.fused_batches = 0
        self.bytes_fed = 0
        self._unfuse_cache: Dict[Tuple, Any] = {}
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="device-feed")
        self._thread.start()

    # -- fused transfer ------------------------------------------------

    def _unfuse_fn(self, layout_sig: Tuple):
        """Jitted segments->columns reconstruction for one layout. The
        segment arrays are donated: XLA reuses their HBM for the column
        views instead of keeping batch bytes resident twice."""
        import jax
        fn = self._unfuse_cache.get(layout_sig)
        if fn is None:
            layout = {k: (dt, off, n, shape)
                      for k, dt, off, n, shape in layout_sig}

            def unfuse(segs):
                return {k: jax.lax.dynamic_slice_in_dim(
                            segs[dt], off, n).reshape(shape)
                        for k, (dt, off, n, shape)
                        in sorted(layout.items())}

            donate = () if jax.default_backend() == "cpu" else (0,)
            fn = jax.jit(unfuse, donate_argnums=donate)
            self._unfuse_cache[layout_sig] = fn
        return fn

    def _ship(self, batch: Any) -> Tuple[Any, int]:
        """Host→device for one batch; returns (device batch, bytes)."""
        import jax
        if isinstance(batch, StagedBatch):
            nbytes = batch.nbytes
            try:
                with _spans.traced("feed.ship", bytes=nbytes, fused=True):
                    segs = {dt: jax.device_put(seg)
                            for dt, seg in sorted(batch.segments.items())}
                    # intentional barrier: the transfer must land before
                    # the slot is reused # graftlint: disable=RT021
                    jax.block_until_ready(list(segs.values()))
                sig = tuple((k, dt, off, n, shape)
                            for k, (dt, off, n, shape)
                            in sorted(batch.layout.items()))
                with _spans.traced("feed.unfuse"):
                    dev = self._unfuse_fn(sig)(segs)
            finally:
                # a failed device_put/unfuse must still return the slot
                # to the stage, or the feed wedges once slots run out
                batch.release()
            self.fused_batches += 1
            return dev, nbytes
        with _spans.traced("feed.ship", fused=False) as _sp:
            dev = jax.device_put(batch)
            # intentional barrier: ship measures landed-transfer time,
            # and nbytes reads need materialized leaves
            jax.block_until_ready(dev)  # graftlint: disable=RT021
            nbytes = sum(getattr(v, "nbytes", 0)
                         for v in jax.tree_util.tree_leaves(dev))
            _sp["bytes"] = nbytes
        return dev, nbytes

    def _run(self) -> None:
        counter = hist = None
        while not self._stop.is_set():
            try:
                batch, meta = self._host.get(timeout=0.2)
            except queue.Empty:
                continue
            t0 = time.perf_counter()
            dev, nbytes = self._ship(batch)
            dt = time.perf_counter() - t0
            self.bytes_fed += nbytes
            if counter is None:
                try:
                    counter, hist = _feed_metrics()
                except Exception:  # noqa: BLE001 - metrics best-effort
                    counter, hist = False, False
            if counter:
                counter.inc(nbytes)
                hist.observe(dt)
            while not self._stop.is_set():
                try:
                    self._out.put((dev, meta), timeout=0.2)
                    break
                except queue.Full:
                    continue

    def get(self, timeout: float = 0.2) -> Tuple[Any, Any]:
        """Next device-resident batch; raises queue.Empty on timeout.
        Blocks until the transfer has actually landed so downstream
        compute timing is clean. Starvation (nothing queued — the
        upstream sampler is the bottleneck) and transfer wait both
        accumulate into wait_s; xfer_s isolates the transfer part."""
        import jax
        t0 = time.perf_counter()
        # feed.wait = consumer blocked on the feed (starvation: upstream
        # sampling is the bottleneck); feed.xfer isolates the tail spent
        # waiting for an already-dequeued transfer to land in HBM
        from ray_tpu._private import goodput
        with _spans.traced("feed.wait") as _sp:
            try:
                dev, meta = self._out.get(timeout=timeout)
            except queue.Empty:
                waited = time.perf_counter() - t0
                self.wait_s += waited
                # starvation is badput on the consumer's ledger even
                # when the get comes back empty
                goodput.charge(self._stall_bucket, waited)
                _sp["empty"] = True
                raise
            t1 = time.perf_counter()
            with _spans.traced("feed.xfer"):
                # intentional barrier: xfer_s attributes residual
                # transfer time to the consumer-visible wait
                jax.block_until_ready(dev)  # graftlint: disable=RT021
            t2 = time.perf_counter()
        self.wait_s += t2 - t0
        self.xfer_s += t2 - t1
        goodput.charge(self._stall_bucket, t2 - t0)
        self.batches += 1
        return dev, meta

    def add_busy(self, seconds: float) -> None:
        self.busy_s += seconds

    def stats(self) -> dict:
        total = self.wait_s + self.busy_s
        return {
            "feed_wait_s": self.wait_s,
            "feed_xfer_s": self.xfer_s,
            "learner_busy_s": self.busy_s,
            "feed_stall_pct": (100.0 * self.wait_s / total) if total else 0.0,
            "feed_xfer_stall_pct": (
                100.0 * self.xfer_s / total) if total else 0.0,
            "batches_fed": self.batches,
            "fused_batches": self.fused_batches,
            "feed_bytes": self.bytes_fed,
        }

    def stop(self) -> None:
        self._stop.set()
