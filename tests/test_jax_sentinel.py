"""jax sentinel (util/jax_sentinel.py): compile counters, transfer
accounting, the watchdog's storm/transfer probes, and the off switch.

The sentinel is the runtime half of the graftlint RT020/RT021 pairing:
what the lint rules can't prove statically (a recompile per step, bytes
forced device→host inside a step region) shows up here as metric deltas
the watchdog judges within two harvest intervals.
"""

import os
import subprocess
import sys

import pytest

from ray_tpu._private import metrics_plane as mp
from ray_tpu._private import spans
from ray_tpu.util import jax_sentinel
from ray_tpu.util import metrics as um


def _series(name):
    """{sorted-tag-tuple: value} for one metric from the process
    registry (counters accumulate across tests — assert deltas)."""
    out = {}
    for m in um.collect_wire():
        if m["name"] != name:
            continue
        for s in m["series"]:
            out[tuple(sorted(s["tags"].items()))] = s["value"]
    return out


def _sentinel_records_since(ring, start):
    """The sentinel's own records written after ring index `start`. The
    ring is the process's: a collection (spans._on_gc) or a thread another
    file left behind may write to it while a test runs, so `ring._i` alone
    does not count what the sentinel wrote."""
    new = ring._i - start
    return [r for r in ring.snapshot_records()[-new:]
            if r[1].startswith("jax.")] if new else []


def _flat_series():
    """collect_wire() flattened to the harvest's `name{k=v,...}` keys
    (same shape Watchdog.evaluate receives from the cluster merge)."""
    out = {}
    for m in um.collect_wire():
        for s in m["series"]:
            if "value" not in s:
                continue  # histogram bucket rows
            tags = ",".join(f"{k}={v}"
                            for k, v in sorted(s["tags"].items()))
            out[f"{m['name']}{{{tags}}}" if tags else m["name"]] = \
                s["value"]
    return out


# ---- watchdog probes (no jax needed) ---------------------------------------


def _make_watchdog(events, **kw):
    kw.setdefault("jit_recompiles", 3)
    kw.setdefault("jit_recompile_warmup_s", 0.0)
    kw.setdefault("host_transfer_bytes", 100.0)
    return mp.Watchdog(
        emit=lambda et, msg, severity="INFO", **f:
            events.append((et, msg, severity, f)),
        cooldown_s=0.0, wait_edge_age_s=600.0,
        store_occupancy_frac=0.95, queue_depth=1000, **kw)


def _alerts(events, probe):
    return [(m, s, f) for _t, m, s, f in events
            if f.get("probe") == probe]


def test_watchdog_recompile_storm_within_two_harvests():
    events = []
    wd = _make_watchdog(events)
    key = "ray_tpu_jit_compiles_total{fn=learner.update,kind=recompile}"
    wd.evaluate([], {key: 5.0}, [], interval_s=0.01)  # baseline round
    assert not _alerts(events, "jit_recompile_storm")
    wd.evaluate([], {key: 9.0}, [], interval_s=0.01)  # delta 4 >= 3
    alerts = _alerts(events, "jit_recompile_storm")
    assert len(alerts) == 1
    msg, severity, fields = alerts[0]
    assert severity == "ERROR"
    assert fields["fn"] == "learner.update"
    assert fields["value"] == 4.0
    assert "RT020" in msg


def test_watchdog_recompile_probe_skips_untracked_first_and_small():
    events = []
    wd = _make_watchdog(events)
    series = {
        # outside any step region: by definition not a hot path
        "ray_tpu_jit_compiles_total{fn=untracked,kind=recompile}": 0.0,
        # warmup compiles are the expected cost of a cold start
        "ray_tpu_jit_compiles_total{fn=learner.update,kind=first}": 0.0,
        # below the per-window threshold
        "ray_tpu_jit_compiles_total{fn=train.step,kind=recompile}": 0.0,
    }
    wd.evaluate([], series, [], interval_s=0.01)
    bumped = {k: v + (10.0 if "untracked" in k or "first" in k else 2.0)
              for k, v in series.items()}
    wd.evaluate([], bumped, [], interval_s=0.01)
    assert not _alerts(events, "jit_recompile_storm")


def test_watchdog_recompile_probe_warmup_grace():
    """A label inside its warmup window never storms: cold starts
    legitimately compile several modules under one region label."""
    events = []
    wd = _make_watchdog(events, jit_recompile_warmup_s=600.0)
    key = "ray_tpu_jit_compiles_total{fn=learner.update,kind=recompile}"
    wd.evaluate([], {key: 0.0}, [], interval_s=0.01)
    wd.evaluate([], {key: 50.0}, [], interval_s=0.01)
    assert not _alerts(events, "jit_recompile_storm")


def test_watchdog_host_transfer_within_two_harvests():
    events = []
    wd = _make_watchdog(events)
    key = "ray_tpu_host_transfer_bytes_total{region=learner.update}"
    unk = "ray_tpu_host_transfer_bytes_total{region=untracked}"
    wd.evaluate([], {key: 0.0, unk: 0.0}, [], interval_s=0.01)
    assert not _alerts(events, "unexpected_host_transfer")
    # untracked bytes never alert however large; in-region bytes alert
    # on the first judged round once the delta crosses the floor
    wd.evaluate([], {key: 500.0, unk: 1e9}, [], interval_s=0.01)
    alerts = _alerts(events, "unexpected_host_transfer")
    assert len(alerts) == 1
    msg, severity, fields = alerts[0]
    assert severity == "ERROR"
    assert fields["region"] == "learner.update"
    assert fields["value"] == 500.0
    assert "RT021" in msg


def test_watchdog_host_transfer_below_floor_quiet():
    events = []
    wd = _make_watchdog(events)
    key = "ray_tpu_host_transfer_bytes_total{region=learner.update}"
    wd.evaluate([], {key: 0.0}, [], interval_s=0.01)
    wd.evaluate([], {key: 99.0}, [], interval_s=0.01)
    assert not _alerts(events, "unexpected_host_transfer")


# ---- live sentinel (jax, CPU) ----------------------------------------------


@pytest.fixture
def sentinel():
    """A fresh install. A neighbour in this xdist worker that ran a step
    region leaves the sentinel installed, and one that then calls
    `util.metrics.clear()` (tests/test_observability.py) empties the
    registry under it: install() used to return at `if _installed` with
    counters no collect() could see, and every series delta here read 0
    (ROADMAP D12, the red of PR 54's run). uninstall() first, so that
    install() fetches its counters from the registry that stands."""
    pytest.importorskip("jax")
    jax_sentinel.uninstall()
    assert jax_sentinel.install()
    try:
        yield jax_sentinel
    finally:
        jax_sentinel.uninstall()


_CACHE_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_config():
    """set(mode, directory): the persistent compilation cache `off`, or
    on at `directory` with jax's two floors at their defaults (`small`:
    nothing compiled in under a second is kept) or at 0 (`keep`). What
    tests/conftest.py left (off) is put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {k: getattr(jax.config, k) for k in _CACHE_KEYS}

    def put(values):
        for k, v in values.items():
            jax.config.update(k, v)
        cc.reset_cache()

    def set_(mode, directory=None):
        put({"jax_enable_compilation_cache": mode != "off",
             "jax_compilation_cache_dir":
                 None if mode == "off" else str(directory),
             "jax_persistent_cache_min_compile_time_secs":
                 0 if mode == "keep" else 1.0,   # jax's default: 1 s
             "jax_persistent_cache_min_entry_size_bytes": 0})

    try:
        yield set_
    finally:
        put(before)


def _heavy(scale):
    """A body that takes the CPU's compiler well over FOLD_BELOW_S, so
    that its `jax.compile` is a span of its own."""
    def body(v):
        import jax.numpy as jnp
        for i in range(24):
            v = jnp.sin(v) * scale + jnp.cos(v + i)
        return v
    return body


def _compiles_of(fun):
    """The ring's `jax.compile` records of one jitted function, oldest
    first: (cache, region, duration)."""
    return [((r[6] or {}).get("cache"), (r[6] or {}).get("region"), r[3])
            for r in spans.ring().snapshot_records()
            if r[1] == "jax.compile" and (r[6] or {}).get("fun") == fun]


def test_compile_counter_first_warm_recompile(sentinel, cache_config,
                                              tmp_path):
    """What this asserts on is this test's own: the `jax.compile` records
    of a function only it jits, and the counter's series of a label only
    it opens (not a process-wide series' delta)."""
    import jax
    import jax.numpy as jnp

    # pre-warm the inputs OUTSIDE any region so their builder compiles
    # don't attribute to the label under test
    x = jnp.ones((4,), dtype=jnp.float32)
    y = jnp.ones((8,), dtype=jnp.float32)
    sentinel_t1_step = _heavy(2.0)
    sentinel_t1_step.__name__ = sentinel_t1_step.__qualname__ = \
        "sentinel_t1_step"
    fun = "jit(sentinel_t1_step)"
    f = jax.jit(sentinel_t1_step)
    name = "ray_tpu_jit_compiles_total"
    first_key = (("fn", "sentinel.t1"), ("kind", "first"))
    rec_key = (("fn", "sentinel.t1"), ("kind", "recompile"))
    cache_config("keep", tmp_path)

    assert _compiles_of(fun) == []
    before = _series(name)
    with jax_sentinel.step_region("sentinel.t1"):
        f(x).block_until_ready()
    cold = _series(name)
    assert cold.get(first_key, 0.0) - before.get(first_key, 0.0) == 1.0
    assert [(c, r) for c, r, _ in _compiles_of(fun)] == [
        ("miss", "sentinel.t1")]   # compiled, and written to tmp_path
    assert any(tmp_path.iterdir())

    with jax_sentinel.step_region("sentinel.t1"):
        f(x).block_until_ready()  # in-memory warm: no event, no record
    warm = _series(name)
    assert warm.get(first_key, 0.0) == cold.get(first_key, 0.0)
    assert warm.get(rec_key, 0.0) == cold.get(rec_key, 0.0)
    assert len(_compiles_of(fun)) == 1

    with jax_sentinel.step_region("sentinel.t1"):
        f(y).block_until_ready()  # new shape: a second program
    hot = _series(name)
    assert hot.get(rec_key, 0.0) - warm.get(rec_key, 0.0) == 1.0
    assert hot.get(first_key, 0.0) == cold.get(first_key, 0.0)
    assert [(c, r) for c, r, _ in _compiles_of(fun)] == [
        ("miss", "sentinel.t1")] * 2

    # the floors at their defaults: asked, compiled, not kept
    cache_config("small", tmp_path / "unkept")
    z = jnp.ones((16,), dtype=jnp.float32)
    with jax_sentinel.step_region("sentinel.t1"):
        f(z).block_until_ready()
    assert [c for c, _, _ in _compiles_of(fun)] == ["miss", "miss", "small"]
    assert _series(name).get(rec_key, 0.0) - warm.get(rec_key, 0.0) == 2.0


@pytest.mark.parametrize("mode, outcome", [
    ("keep", "miss"), ("small", "small"), ("off", "off")])
def test_three_phases_are_spans_with_fun_region_and_cache(
        sentinel, cache_config, tmp_path, mode, outcome):
    """(a) `jax.trace`, `jax.lower`, `jax.compile` of one program, on
    the dispatching thread, in order, the last with the persistent
    cache's outcome. (`hit` needs a second process: the next test.)"""
    import jax
    import jax.numpy as jnp

    body = _heavy(3.0)
    body.__name__ = body.__qualname__ = f"sentinel_phases_{mode}"
    cache_config(mode, tmp_path)
    x = jnp.ones((4,), dtype=jnp.float32)
    t0 = spans.begin()
    with jax_sentinel.step_region("sentinel.phases"):
        jax.jit(body)(x).block_until_ready()
    float(x[0])   # a thread past its region
    jax.jit(lambda v: body(v) + 1.0)(x).block_until_ready()
    mine = [r for r in spans.ring().snapshot_records()
            if r[2] >= t0 and r[1].startswith("jax.")
            and body.__name__ in str((r[6] or {}).get("fun"))]
    assert [r[1] for r in mine] == ["jax.trace", "jax.lower", "jax.compile"]
    trace, lower, compiled = mine
    assert trace[6] == {"fun": body.__name__, "region": "sentinel.phases"}
    assert lower[6] == {"fun": f"jit({body.__name__})",
                        "region": "sentinel.phases"}
    assert compiled[6] == {"fun": f"jit({body.__name__})",
                           "region": "sentinel.phases", "cache": outcome}
    assert trace[2] + trace[3] <= lower[2] + 1e-4
    assert lower[2] + lower[3] <= compiled[2] + 1e-4
    assert len({r[4] for r in mine}) == 1   # one thread: the caller's
    after = [r for r in spans.ring().snapshot_records()
             if r[2] >= t0 and r[1] == "jax.compile"
             and (r[6] or {}).get("fun") == "jit(<lambda>)"]
    assert after and after[-1][6]["region"] == "after:sentinel.phases"


_HIT_SCRIPT = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from ray_tpu._private import spans
from ray_tpu.util import jax_sentinel
assert jax_sentinel.install()
def sentinel_second_process(v):
    for i in range(24):
        v = jnp.sin(v) * 2.0 + jnp.cos(v + i)
    return v
jax.jit(sentinel_second_process)(jnp.ones((4,))).block_until_ready()
for r in spans.snapshot()["spans"]:
    a = r[6] or {}
    if r[1] == "jax.compile" and a.get("fun") == "jit(sentinel_second_process)":
        print("OUTCOME", a["cache"], a.get("retrieval_s", -1.0), r[3])
print("PHASES", sorted(jax_sentinel._snapshot_extra()["phases"]))
"""


def test_a_second_process_on_the_same_directory_reads_hit(tmp_path):
    """(a) the fourth outcome: what one process compiled and wrote, the
    next one loads, and says so, with the read's own seconds inside the
    event's (jax 0.9.0 fires the compile event around the load)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    said = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _HIT_SCRIPT, str(tmp_path)], env=env,
            capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append([ln.split() for ln in out.stdout.splitlines()
                     if ln.startswith(("OUTCOME", "PHASES"))])
    (first, _), (second, phases) = said
    assert first[1] == "miss" and float(first[2]) == -1.0
    assert second[1] == "hit"
    assert 0.0 < float(second[2]) <= float(second[3])
    assert "'jax.compile|hit'," in " ".join(phases) + ","


def test_short_events_fold_into_bounded_records_with_exact_sums(
        sentinel, monkeypatch):
    """(b) 2,000 events under FOLD_BELOW_S (a set-up that compiles op by
    op) leave a handful of records, and what those carry adds up to the
    listener's own totals to the last bit."""
    monkeypatch.setattr(jax_sentinel, "_phase_totals", {})
    monkeypatch.setattr(jax_sentinel, "_folded", {})
    ring = spans.ring()
    start = ring._i
    t0 = spans.begin()
    expect = {}
    for i in range(2000):
        kind = i % 4
        dur = 1e-5 + (i % 7) * 1e-5
        if kind == 0:
            event, label = jax_sentinel.TRACE_EVENT, "jax.trace"
        elif kind == 1:
            event, label = jax_sentinel.LOWER_EVENT, "jax.lower"
        else:
            event = jax_sentinel.COMPILE_EVENT
            label = "jax.compile|small" if kind == 2 else "jax.compile|off"
            if kind == 2:
                jax_sentinel._on_event(
                    "/jax/compilation_cache/compile_requests_use_cache")
        total = expect.setdefault(label, [0, 0.0])
        total[0] += 1
        total[1] += dur
        jax_sentinel._on_event_duration(event, dur, fun_name="op")
    # one long one among them is a span of its own, and carries nothing
    jax_sentinel._on_event_duration(jax_sentinel.LOWER_EVENT, 0.25,
                                    fun_name="jit(big)")
    # nothing else of the sentinel's reached the ring yet
    assert len(_sentinel_records_since(ring, start)) == 1
    snap = spans.snapshot()       # the snapshot writes the sums
    mine = [r for r in snap["spans"]
            if r[2] >= t0 - 1.0 and r[1].startswith("jax.")
            and ("folded_n" in (r[6] or {})
                 or (r[6] or {}).get("fun") == "jit(big)")]
    assert len(mine) == 5         # four sums and the long lowering
    got = {}
    for r in mine:
        a = r[6]
        if "folded_n" not in a:
            assert a["fun"] == "jit(big)" and "cache" not in a
            assert r[3] == pytest.approx(0.25, abs=1e-4)
            continue
        label = r[1] + ("|" + a["cache"] if "cache" in a else "")
        got[label] = [a["folded_n"], a["folded_s"]]
        assert 0.0 <= r[3] < jax_sentinel.FOLD_SPAN_S   # where they lay
    assert got == expect
    totals = jax_sentinel._snapshot_extra()["phases"]
    expect["jax.lower"][0] += 1
    expect["jax.lower"][1] += 0.25
    assert totals == expect
    assert jax_sentinel._folded == {}


def test_a_sum_is_written_once_it_spans_a_second(sentinel, monkeypatch):
    """A fold stands for a stretch of at most FOLD_SPAN_S: the next short
    event after it writes the sum and starts another, so a record says
    WHEN its events ran to within that."""
    monkeypatch.setattr(jax_sentinel, "_folded", {})
    monkeypatch.setattr(jax_sentinel, "FOLD_SPAN_S", 0.05)
    ring = spans.ring()
    start = ring._i
    for _ in range(3):
        jax_sentinel._on_event_duration(jax_sentinel.TRACE_EVENT, 1e-4,
                                        fun_name="op")
    assert _sentinel_records_since(ring, start) == []
    import time
    time.sleep(0.06)
    jax_sentinel._on_event_duration(jax_sentinel.TRACE_EVENT, 2e-4,
                                    fun_name="op")
    (rec,) = _sentinel_records_since(ring, start)
    assert rec[1] == "jax.trace" and rec[6]["folded_n"] == 3
    assert rec[6]["folded_s"] == pytest.approx(3e-4)
    (pending,) = jax_sentinel._folded.values()
    assert pending[0] == 1 and pending[1] == 2e-4


def test_off_recorder_counts_and_records_nothing(sentinel, monkeypatch):
    """RAY_TPU_SPANS=0: the totals still count, no record and no sum."""
    monkeypatch.setattr(jax_sentinel, "_phase_totals", {})
    monkeypatch.setattr(jax_sentinel, "_folded", {})
    spans.configure(enabled=False)
    try:
        start = spans.ring()._i
        jax_sentinel._on_event_duration(jax_sentinel.TRACE_EVENT, 1e-4)
        jax_sentinel._on_event_duration(jax_sentinel.COMPILE_EVENT, 0.5)
        assert spans.ring()._i == start and jax_sentinel._folded == {}
    finally:
        spans.configure(enabled=True)
    assert jax_sentinel._snapshot_extra()["phases"] == {
        "jax.trace": [1, 1e-4], "jax.compile|off": [1, 0.5]}


def test_transfer_accounting_bytes_and_spans(sentinel):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(16, dtype=jnp.float32)  # 64 bytes
    s = jnp.float32(1.0)                   # 4 bytes
    name = "ray_tpu_host_transfer_bytes_total"
    key = (("region", "sentinel.t2"),)
    unk = (("region", "untracked"),)

    before = _series(name)
    with jax_sentinel.step_region("sentinel.t2"):
        assert s.item() == 1.0
        host = jax.device_get(x)
    assert host.shape == (16,)
    after = _series(name)
    # .item() pulls the 4-byte scalar; device_get pulls the 64-byte
    # tree exactly once (the per-leaf __array__ is reentrancy-guarded)
    assert after.get(key, 0.0) - before.get(key, 0.0) == 68.0

    # the same forcing points OUTSIDE a region account as untracked
    assert s.item() == 1.0
    outside = _series(name)
    assert outside.get(key, 0.0) == after.get(key, 0.0)
    assert outside.get(unk, 0.0) - after.get(unk, 0.0) == 4.0

    # in-region syncs also land on the flight recorder as host_sync.*
    # spans carrying bytes + region (perf_report's host_sync bucket)
    recs = [r for r in spans.ring().snapshot_records()
            if r[1].startswith("host_sync.")
            and (r[6] or {}).get("region") == "sentinel.t2"]
    assert {r[1] for r in recs} == {"host_sync.item",
                                    "host_sync.device_get"}


def test_snapshot_extra_rides_process_snapshot(sentinel):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: v + 1.0)
    with jax_sentinel.step_region("sentinel.t3"):
        f(jnp.ones((2,))).block_until_ready()
    snap = mp.snapshot_process()
    extra = snap[jax_sentinel.SNAPSHOT_KEY]
    assert extra["installed"] is True
    assert extra["compiles"].get("sentinel.t3", 0) >= 1


def test_live_breach_alerts_within_two_harvests(sentinel):
    """End-to-end: real in-region transfers crossing the configured
    floor raise unexpected_host_transfer on the second harvest."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(64, dtype=jnp.float32)  # 256 bytes per device_get
    events = []
    wd = _make_watchdog(events, host_transfer_bytes=200.0)
    with jax_sentinel.step_region("sentinel.live"):
        jax.device_get(x)  # breach begins
    wd.evaluate([], _flat_series(), [], interval_s=0.01)  # baselined
    assert not _alerts(events, "unexpected_host_transfer")
    with jax_sentinel.step_region("sentinel.live"):
        jax.device_get(x)  # breach continues into the next window
    wd.evaluate([], _flat_series(), [], interval_s=0.01)  # judged
    alerts = _alerts(events, "unexpected_host_transfer")
    assert [f["region"] for _m, _s, f in alerts] == ["sentinel.live"]


def test_live_recompile_storm_alerts_within_two_harvests(sentinel):
    """End-to-end: real steady-state recompiles (shape-varying calls
    under one region label) raise jit_recompile_storm on the second
    harvest after the storm starts."""
    import jax
    import jax.numpy as jnp

    xs = [jnp.ones((n,), dtype=jnp.float32) for n in range(2, 7)]
    f = jax.jit(lambda v: v * 3.0)
    events = []
    wd = _make_watchdog(events, jit_recompiles=3)
    with jax_sentinel.step_region("sentinel.storm"):
        f(xs[0]).block_until_ready()  # first compile
        f(xs[1]).block_until_ready()  # storm begins
    wd.evaluate([], _flat_series(), [], interval_s=0.01)  # baselined
    assert not _alerts(events, "jit_recompile_storm")
    with jax_sentinel.step_region("sentinel.storm"):
        for x in xs[2:]:
            f(x).block_until_ready()  # 3 recompiles in one window
    wd.evaluate([], _flat_series(), [], interval_s=0.01)  # judged
    alerts = _alerts(events, "jit_recompile_storm")
    assert [f2["fn"] for _m, _s, f2 in alerts] == ["sentinel.storm"]


def test_off_switch_disables_everything():
    """RAY_TPU_JAX_SENTINEL=0: install() refuses, step_region is a
    shared no-op, nothing is patched — checked in a subprocess so the
    env var is read fresh (and jax is never even imported)."""
    code = (
        "from ray_tpu.util import jax_sentinel\n"
        "import sys\n"
        "assert not jax_sentinel.enabled()\n"
        "assert not jax_sentinel.install()\n"
        "assert not jax_sentinel.installed()\n"
        "assert jax_sentinel.step_region('x') is jax_sentinel.NOOP\n"
        "assert 'jax' not in sys.modules\n"
        "print('SENTINEL-OFF-OK')\n")
    env = dict(os.environ, RAY_TPU_JAX_SENTINEL="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "SENTINEL-OFF-OK" in out.stdout


def test_metrics_plane_configure_exposes_sentinel_knobs():
    events = []
    wd = _make_watchdog(events, jit_recompiles=7,
                        jit_recompile_warmup_s=5.0,
                        host_transfer_bytes=42.0)
    assert wd.jit_recompiles == 7
    assert wd.jit_recompile_warmup_s == 5.0
    assert wd.host_transfer_bytes == 42.0
