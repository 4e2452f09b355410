"""Always-on JAX recompile/transfer sentinel.

Runtime half of the graftlint XLA hot-path pairing (lint/jaxrules.py is
the static half): the lint rules catch the hazards visible in source —
recompile-prone call shapes (RT020), hidden device→host syncs (RT021),
donation misuse (RT022) — and this module catches the ones only the
live process can see, exporting them through the per-process metrics
registry so they ride the cluster harvest onto /metrics and the
watchdog's `jit_recompile_storm` / `unexpected_host_transfer` probes.

Two signals:

  - **compiles** — `jax.monitoring`'s backend-compile duration event
    fires once for every program the process's own jit cache did not
    hold (silent on a cached dispatch). In jax 0.9.0 the event wraps
    `compiler.compile_or_get_cached` (`pxla._cached_compilation`), so a
    program LOADED from the persistent compilation cache fires it too,
    with the retrieval as its duration: the event cannot tell a cache
    load from a compile. Counting it per step-region label still splits
    warmup (`kind="first"`) from the steady-state recompiles that mean
    a shape/static-arg hazard slipped through (`kind="recompile"`: a
    shape that changes is a hazard whether or not the disk had the
    program):
        ray_tpu_jit_compiles_total{fn=<region>, kind=first|recompile}
    Where the outcomes ARE told apart is the flight recorder: each of
    JAX's three phases of such a program is a span (`jax.trace`,
    `jax.lower`, `jax.compile`) carrying `fun` (the jitted function's
    name), `region` (as `host_sync.*`: the open step region,
    `after:<the last one>` or `untracked`) and, on `jax.compile`,
    `cache`, from the persistent cache's own events on the same thread
    since its previous compile event:
        hit    found and loaded (`retrieval_s`: the read alone)
        miss   not found, compiled, and WRITTEN (jax fires
               `cache_misses` where it writes the entry)
        small  asked, not found, compiled, and not kept, so compiled
               again in every run. Since `enable_compile_cache()` puts
               jax's compile-time floor at 0 (PR 56) that is no longer
               the common case but what is left: a program with a host
               callback, a process that is not process 0, an entry
               under a size floor, or a floor the user's environment set
        off    the cache was not asked at all
    An event of under FOLD_BELOW_S records no span of its own (a set-up
    that compiles op by op fires three events an eager op): it is summed
    per thread, span name and `cache` into a count and seconds, and the
    sum is written as ONE record (`folded_n`, `folded_s`; its interval
    is the stretch the folded events lay in, at most FOLD_SPAN_S long)
    when the next such event comes that late, and at every snapshot of
    the ring. So seconds and counts stay exact (a reader adds `dur` of
    the records without `folded_n` to `folded_s` of those with) while
    the ring holds hundreds of records a set-up, not tens of thousands.
    (ISSUE 55 proposed carrying the sums on the next recorded span of the
    name; a record of their own keeps WHEN they ran, which a reader
    that splits set-up from window needs, and leaves a span's `dur` its
    own.) `jax.trace` events nest (`matmul` inside `<lambda>`): readers
    take the union of a thread's intervals, not the sum.
  - **host transfers** — the Python-level forcing points on jax arrays
    (`.item()`, `__array__`/np coercion, `__float__`/`__int__`/
    `__bool__`) and `jax.device_get` are patched to account the bytes
    they pull across, tagged by step region:
        ray_tpu_host_transfer_bytes_total{region=<region>}
    On a thread that has run a step region each forcing point also
    records a flight-recorder span (`host_sync.<via>`) whose duration
    is the actual blocked wall time, so `tools/perf_report.py` can
    attribute step time stalled on syncs: `region=<region>` inside one,
    `region="after:<last region>"` past it — a train loop's
    `float(metrics["loss"])` after `train_step` is
    `host_sync.float{region="after:train.step"}`, the loop's wait for
    the device. The span is `spans.traced`, so a device trace shows it
    on the thread's host line too. As an escalation, RAY_TPU_JAX_SENTINEL_GUARD=log|disallow
    additionally applies jax's device→host transfer guard for the
    region scope — "log" names every transfer source C++-side,
    "disallow" turns hidden syncs into hard errors at the offending
    line. Off by default: the guard logs the *sanctioned* forcing
    points too, and one warning per update is operator spam.

Scoping: training loops wrap their step in `step_region(name)` —
Learner.update, IMPALA's learner loop, and the sharded train_step
factory already do. Transfers outside any region account under
region="untracked" and are never judged by the watchdog; transfers
INSIDE a region are presumed-bad (the lint rules enforce that hot
paths sync at one sanctioned forcing point) and alert once their
per-harvest delta crosses `Config.watchdog_host_transfer_bytes`.

Off switch: RAY_TPU_JAX_SENTINEL=0 makes install() refuse and
step_region() return a shared no-op — nothing is patched, no listener
registered, call sites pay one flag check. Installation is lazy and
idempotent; importing this module never imports jax.
"""

from __future__ import annotations

import os
import threading
from _thread import get_ident as _get_ident
from time import perf_counter
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import spans as _spans

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PHASE_SPANS = {TRACE_EVENT: "jax.trace", LOWER_EVENT: "jax.lower",
               COMPILE_EVENT: "jax.compile"}
# the persistent cache's own events, on the compiling thread, in order:
# asked -> (found | written after the compile | neither)
CACHE_OUTCOMES = {
    "/jax/compilation_cache/compile_requests_use_cache": "small",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss"}
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
FOLD_BELOW_S = 1e-3   # gc.collect's rule: shorter records no span
FOLD_SPAN_S = 1.0     # the longest stretch one folded record stands for

SNAPSHOT_KEY = "jax_sentinel"

_lock = threading.Lock()
_tls = threading.local()

_installed = False
_listener_registered = False

# region label -> lifetime compile count (splits first vs recompile)
_compiles: Dict[str, int] = {}
# (thread, span name, cache outcome) -> [count, seconds, first start,
# last end] of the events under FOLD_BELOW_S not yet written to the ring
_folded: Dict[Tuple[int, str, Optional[str]], List[float]] = {}
# "jax.compile|hit" -> [events, seconds] of the process's life, whatever
# became of their spans: what the ring's records must add up to
_phase_totals: Dict[str, List[float]] = {}

_compile_counter: Any = None
_xfer_counter: Any = None

_orig: Dict[str, Any] = {}


def enabled() -> bool:
    return os.environ.get("RAY_TPU_JAX_SENTINEL", "1").lower() not in (
        "0", "false", "no", "off")


def installed() -> bool:
    return _installed


def current_region() -> Optional[str]:
    stack = getattr(_tls, "regions", None)
    return stack[-1] if stack else None


# ---------------------------------------------------------------------
# Accounting funnel
# ---------------------------------------------------------------------


def _region_label() -> Optional[str]:
    """The open step region's label, `after:<the last one>` on a thread
    that has left its region, None on one that never ran any."""
    region = current_region()
    if region is None:
        last = getattr(_tls, "last_region", None)
        return None if last is None else "after:" + last
    return region


def _sync_span(via: str):
    """The span around one forcing point: its blocked wall time under
    the open step region's label, or `after:<the last one>` on a thread
    that has left its region (the loop's own read of the step's
    result). A thread that never ran a region gets the shared no-op."""
    region = _region_label()
    if region is None:
        return _spans.NOOP
    return _spans.traced(f"host_sync.{via}", region=region)


def _account(nbytes: int, sp: Dict[str, Any]) -> None:
    """One observed device→host transfer: count the bytes against the
    current step region (outside one: "untracked", which the watchdog
    never judges) and put them on the forcing point's span."""
    if not _installed:
        return
    try:
        sp["bytes"] = int(nbytes)
        _xfer_counter.inc(
            float(max(0, nbytes)),
            tags={"region": current_region() or "untracked"})
    except Exception:  # noqa: BLE001 - accounting must never break the
        pass           # transfer it observes


def _in_xfer() -> bool:
    return getattr(_tls, "in_xfer", False)


def _on_event(event: str, **_kw: Any) -> None:
    """jax.monitoring's plain-event listener: what the persistent cache
    says of the program this thread is compiling, kept for the
    `jax.compile` span that the compile event closes."""
    outcome = CACHE_OUTCOMES.get(event)
    if outcome is not None and _installed:
        _tls.cache = outcome


def _write_folded(key: Tuple[int, str, Optional[str]],
                  acc: List[float]) -> None:
    tid, name, cache = key
    attrs: Dict[str, Any] = {"folded_n": acc[0], "folded_s": acc[1]}
    if cache is not None:
        attrs["cache"] = cache
    _spans.ring().record(("X", name, acc[2], acc[3] - acc[2], tid, None,
                          attrs))


def _flush_folded() -> None:
    """Every sum to the ring; the ring's snapshot calls this first."""
    with _lock:
        pending = list(_folded.items())
        _folded.clear()
    for key, acc in pending:
        _write_folded(key, acc)


def _phase_span(name: str, duration: float, fun: Any,
                cache: Optional[str], retrieval_s: Optional[float]) -> None:
    """One of JAX's three phases, ended now: a span of its own from
    FOLD_BELOW_S up, else into its (thread, name, cache) sum."""
    label = name if cache is None else f"{name}|{cache}"
    recording = _spans.enabled()
    now = perf_counter()
    folds = recording and duration < FOLD_BELOW_S
    key = (_get_ident(), name, cache)
    stale = None
    with _lock:
        total = _phase_totals.setdefault(label, [0, 0.0])
        total[0] += 1
        total[1] += duration
        if folds:
            acc = _folded.get(key)
            if acc is not None and now - acc[2] > FOLD_SPAN_S:
                stale, acc = acc, None
            if acc is None:
                _folded[key] = [1, duration, now - duration, now]
            else:
                acc[0] += 1
                acc[1] += duration
                acc[3] = now
    if stale is not None:
        _write_folded(key, stale)
    if folds or not recording:
        return
    attrs: Dict[str, Any] = {"region": _region_label() or "untracked"}
    if fun is not None:   # some of jax's traces name no function
        attrs["fun"] = str(fun)
    if cache is not None:
        attrs["cache"] = cache
    if retrieval_s is not None:
        attrs["retrieval_s"] = retrieval_s
    _spans.complete(name, duration, **attrs)


def _on_event_duration(event: str, duration: float,
                       **kw: Any) -> None:
    """jax.monitoring's duration listener, on the dispatching thread, so
    the thread-local region label attributes it: each of the three
    phases of a program the process's jit cache did not hold becomes a
    span, and the last of them, the backend-compile event (a load from
    the persistent cache fires it too), is counted and charged to the
    goodput ledger's `compile` whatever its outcome. Never runs on a
    cached dispatch. The listener stays registered for the process
    lifetime; _installed gates its body."""
    if not _installed:
        return
    if event == CACHE_RETRIEVAL_EVENT:
        _tls.retrieval_s = float(duration)
        return
    name = PHASE_SPANS.get(event)
    if name is None:
        return
    try:
        cache = retrieval_s = None
        if event == COMPILE_EVENT:
            cache = getattr(_tls, "cache", None) or "off"
            if cache == "hit":
                retrieval_s = getattr(_tls, "retrieval_s", None)
            _tls.cache = _tls.retrieval_s = None
        _phase_span(name, float(duration), kw.get("fun_name"), cache,
                    retrieval_s)
        if cache is None:
            return
        fn = current_region() or "untracked"
        with _lock:
            n = _compiles.get(fn, 0)
            _compiles[fn] = n + 1
        _compile_counter.inc(
            1.0, tags={"fn": fn,
                       "kind": "first" if n == 0 else "recompile"})
        # goodput: the event fires synchronously on the jit-calling
        # thread with the compile's wall duration — re-attribute it out
        # of whatever ledger bucket is open there (typically
        # productive_step) into `compile`
        from ray_tpu._private import goodput
        goodput.charge("compile", float(duration))
    except Exception:  # noqa: BLE001 - telemetry is best-effort
        pass


def _snapshot_extra() -> Dict[str, Any]:
    """Rides every metrics harvest: which regions this process has
    compiled under (the watchdog's storm probe names them; operators
    grep it from `ray_tpu metrics dump`), and its traces, lowerings and
    compiles by cache outcome as [events, seconds] of the process's
    life: whether the persistent cache hits, without a timeline."""
    with _lock:
        return {"installed": _installed, "compiles": dict(_compiles),
                "phases": {k: list(v) for k, v in _phase_totals.items()}}


# ---------------------------------------------------------------------
# Install / uninstall
# ---------------------------------------------------------------------


def install() -> bool:
    """Idempotent lazy install: metrics, compile listener, and the
    ArrayImpl/device_get transfer funnel. Returns False (and patches
    nothing) when RAY_TPU_JAX_SENTINEL=0 or jax is unavailable."""
    global _installed, _listener_registered
    global _compile_counter, _xfer_counter
    if _installed:
        return True
    if not enabled():
        return False
    with _lock:
        if _installed:
            return True
        try:
            import jax
            import jax.monitoring
        except ImportError:  # no jax in this process
            return False
        from jax._src.array import ArrayImpl
        from ray_tpu._private import metrics_plane
        from ray_tpu.util.metrics import Counter, get_or_create
        _compile_counter = get_or_create(
            Counter, "ray_tpu_jit_compiles_total",
            description="programs the jit cache did not hold (compiled "
                        "or loaded from the persistent cache) by "
                        "step-region label; kind=first is warmup, "
                        "kind=recompile means a recompile hazard (see "
                        "graftlint RT020)",
            tag_keys=("fn", "kind"))
        _xfer_counter = get_or_create(
            Counter, "ray_tpu_host_transfer_bytes_total",
            description="device->host bytes forced through jax array "
                        "coercions and jax.device_get, by step region "
                        "(region=untracked outside step_region scopes; "
                        "see graftlint RT021)",
            tag_keys=("region",))
        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            jax.monitoring.register_event_listener(_on_event)
            _spans.before_snapshot(_flush_folded)
            _listener_registered = True
        metrics_plane.register_snapshot_extra(
            SNAPSHOT_KEY, _snapshot_extra)

        # -- transfer funnel: ArrayImpl coercions + jax.device_get.
        # block_until_ready and the buffer protocol live in C++ and
        # can't be wrapped from Python; every *coercing* forcing point
        # goes through one of these.
        _orig["item"] = ArrayImpl.item
        _orig["__array__"] = ArrayImpl.__array__
        _orig["__float__"] = ArrayImpl.__float__
        _orig["__int__"] = ArrayImpl.__int__
        _orig["__bool__"] = ArrayImpl.__bool__
        _orig["device_get"] = jax.device_get

        def _forcing(name: str, via: str):
            orig = _orig[name]

            def forcing(self, *a, **kw):
                if _in_xfer():   # a leaf of an accounted device_get
                    return orig(self, *a, **kw)
                with _sync_span(via) as sp:
                    out = orig(self, *a, **kw)
                    _account(getattr(self, "nbytes", 0), sp)
                return out
            forcing.__name__ = name
            return forcing

        def device_get(x):
            # reentrancy guard: device_get coerces each leaf through
            # __array__ — one accounted transfer, not two
            if _in_xfer():
                return _orig["device_get"](x)
            with _sync_span("device_get") as sp:
                _tls.in_xfer = True
                try:
                    out = _orig["device_get"](x)
                finally:
                    _tls.in_xfer = False
                try:
                    total = sum(getattr(leaf, "nbytes", 0)
                                for leaf in jax.tree_util.tree_leaves(x))
                except Exception:  # noqa: BLE001 - odd pytree
                    total = 0
                _account(total, sp)
            return out

        ArrayImpl.item = _forcing("item", "item")
        ArrayImpl.__array__ = _forcing("__array__", "asarray")
        for name in ("__float__", "__int__", "__bool__"):
            setattr(ArrayImpl, name, _forcing(name, name.strip("_")))
        jax.device_get = device_get
        _installed = True
        return True


def uninstall() -> None:
    """Restore the patched forcing points (tests). The monitoring
    listener stays registered — _installed gates its body — so a later
    install() never double-registers."""
    global _installed
    with _lock:
        if not _installed:
            return
        import jax
        from jax._src.array import ArrayImpl
        from ray_tpu._private import metrics_plane
        ArrayImpl.item = _orig["item"]
        ArrayImpl.__array__ = _orig["__array__"]
        ArrayImpl.__float__ = _orig["__float__"]
        ArrayImpl.__int__ = _orig["__int__"]
        ArrayImpl.__bool__ = _orig["__bool__"]
        jax.device_get = _orig["device_get"]
        metrics_plane.unregister_snapshot_extra(SNAPSHOT_KEY)
        _installed = False


# ---------------------------------------------------------------------
# Step regions
# ---------------------------------------------------------------------


class _NoopRegion:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


NOOP = _NoopRegion()


def _guard_mode() -> Optional[str]:
    mode = os.environ.get("RAY_TPU_JAX_SENTINEL_GUARD", "").lower()
    return mode if mode in ("log", "disallow") else None


class _StepRegion:
    """Labels compiles/transfers on this thread with `name`; with
    RAY_TPU_JAX_SENTINEL_GUARD set, also applies jax's device→host
    transfer guard for the scope. Regions nest; the innermost label
    wins (a learner.update inside an IMPALA learner.step attributes
    to learner.update)."""

    __slots__ = ("name", "_tg")

    def __init__(self, name: str):
        self.name = name
        self._tg = None

    def __enter__(self):
        stack = getattr(_tls, "regions", None)
        if stack is None:
            stack = _tls.regions = []
        stack.append(self.name)
        mode = _guard_mode()
        if mode is not None:
            try:
                import jax
                self._tg = jax.transfer_guard_device_to_host(mode)
                self._tg.__enter__()
            except Exception:  # noqa: BLE001 - the guard is advisory:
                # a jax too old for per-direction guards still gets
                # the Python-side accounting, just not the XLA log
                self._tg = None
        return self

    def __exit__(self, *exc):
        if self._tg is not None:
            try:
                self._tg.__exit__(*exc if exc else (None, None, None))
            except Exception:  # noqa: BLE001 - a failed guard restore
                pass           # must not mask the region body's result
        stack = getattr(_tls, "regions", None)
        if stack:
            stack.pop()
        # what a forcing point past the region is labelled after
        _tls.last_region = self.name
        return None


def step_region(name: str):
    """Context manager marking a hot training-step scope. First use
    installs the sentinel (lazy); with RAY_TPU_JAX_SENTINEL=0 this is
    a shared no-op and nothing is ever patched."""
    if not install():
        return NOOP
    return _StepRegion(name)
