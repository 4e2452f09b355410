"""Debug plane: attributed logs, queryable log API, crash postmortems.

reference parity: _private/log_monitor.py + `ray logs` + the dashboard
log views; postmortems are this repo's black-box flight dumps (ISSUE 7).
Covers: attribution stamping (encode/parse + stream splitting),
rotation-safe tailing, the GCS fan-out query (server-side filters, one
overall deadline with an unreachable node), follow mode, flood-control
drop accounting, and chaos-kill postmortem bundles.
"""

import os
import re
import time

import pytest

import ray_tpu
from ray_tpu._private import log_plane
from ray_tpu._private.log_monitor import LogMonitor
from ray_tpu.util import state as state_api


def _gcs():
    from ray_tpu._private import worker as worker_mod
    return worker_mod.global_worker().core_worker._gcs


# ---- attribution stamping (unit) ------------------------------------------


def test_stamp_roundtrip_carries_context():
    prev = log_plane._context_provider
    log_plane.set_context_provider(
        lambda: ("a" * 40, "b" * 40, "tr0123456789abcd"))
    try:
        line, rec = log_plane.format_line("hello world", "OUT")
    finally:
        log_plane.set_context_provider(prev)
    assert line.startswith(log_plane.STAMP + " ")
    parsed = log_plane.parse_line(line)
    assert parsed["msg"] == "hello world"
    assert parsed["level"] == "OUT"
    assert parsed["task_id"] == "a" * 12
    assert parsed["actor_id"] == "b" * 12
    assert parsed["trace_id"] == "tr0123456789abcd"
    assert parsed["pid"] == os.getpid()
    assert abs(parsed["ts"] - time.time()) < 5.0


def test_unstamped_lines_parse_as_raw():
    rec = log_plane.parse_line("native library chatter")
    assert rec["level"] == "RAW"
    assert rec["msg"] == "native library chatter"
    assert rec["task_id"] is None and rec["trace_id"] is None


def test_attributed_stream_buffers_partial_lines():
    import io

    class _Sink(io.StringIO):
        pass

    sink = _Sink()
    prev = log_plane._context_provider
    log_plane.set_context_provider(lambda: (None, None, None))
    try:
        s = log_plane.AttributedStream(sink, "OUT")
        s.write("par")
        assert sink.getvalue() == ""  # no newline yet: buffered
        s.write("tial\nsecond line\ntrail")
        out = sink.getvalue().splitlines()
    finally:
        log_plane.set_context_provider(prev)
    assert len(out) == 2
    assert log_plane.parse_line(out[0])["msg"] == "partial"
    assert log_plane.parse_line(out[1])["msg"] == "second line"


def test_filter_records_prefix_ids_and_regex():
    recs = [
        {"ts": 1.0, "actor_id": "b" * 12, "task_id": "a" * 12,
         "trace_id": "t1", "level": "OUT", "msg": "keep me",
         "node_id": "n" * 12, "worker_id": "w" * 12},
        {"ts": 2.0, "actor_id": "c" * 12, "task_id": "d" * 12,
         "trace_id": "t2", "level": "OUT", "msg": "drop me",
         "node_id": "n" * 12, "worker_id": "x" * 12},
    ]
    # full-hex query against the stamp's 12-char prefix must match
    assert len(log_plane.filter_records(recs, {"actor_id": "b" * 40})) == 1
    assert len(log_plane.filter_records(recs, {"match": "keep"})) == 1
    assert len(log_plane.filter_records(recs, {"trace_id": "t2"})) == 1
    assert len(log_plane.filter_records(recs, {"worker_id": "w"})) == 1
    assert len(log_plane.filter_records(recs, None)) == 2


# ---- log monitor: rotation-safe tailing + flood control (unit) -------------


class _FakeGcs:
    def __init__(self):
        self.published = []

    def call(self, method, **kw):
        if method == "publish":
            self.published.append(kw["message"])

    def close(self):
        pass


def _monitor(tmp_path, **kw):
    d = str(tmp_path / "logs")
    os.makedirs(d, exist_ok=True)
    fake = _FakeGcs()
    mon = LogMonitor(d, None, "f" * 24, poll_interval=3600,
                     _client=fake, **kw)
    return mon, fake, d


def test_rotation_safe_offsets(tmp_path):
    mon, fake, d = _monitor(tmp_path)
    try:
        path = os.path.join(d, "worker-aaaaaaaaaaaa.log")
        with open(path, "w") as f:
            f.write("one\ntwo\n")
        mon.scan_now()
        assert [r["msg"] for r in mon.tail_records(
            "worker-aaaaaaaaaaaa", 10)] == ["one", "two"]
        # copytruncate-style rotation: size drops below the offset
        with open(path, "w") as f:
            f.write("three\n")
        mon.scan_now()
        msgs = [r["msg"] for r in mon.tail_records("worker-aaaaaaaaaaaa", 10)]
        assert msgs == ["one", "two", "three"]
        # replace-style rotation: new inode restarts the tail at 0
        tmp = path + ".new"
        with open(tmp, "w") as f:
            f.write("four\n")
        os.replace(tmp, path)
        mon.scan_now()
        msgs = [r["msg"] for r in mon.tail_records("worker-aaaaaaaaaaaa", 10)]
        assert msgs == ["one", "two", "three", "four"]
        # records carry node + worker identity
        rec = mon.tail_records("worker-aaaaaaaaaaaa", 1)[0]
        assert rec["worker_id"] == "aaaaaaaaaaaa"
        assert rec["node_id"] == "f" * 12
    finally:
        mon.stop()


def test_flood_control_sheds_stream_keeps_index(tmp_path):
    mon, fake, d = _monitor(tmp_path, rate_lps=1.0, burst=5)
    try:
        path = os.path.join(d, "worker-bbbbbbbbbbbb.log")
        with open(path, "w") as f:
            for i in range(60):
                f.write(f"line-{i}\n")
        mon.scan_now()
        mon._drain_publish()  # the monitor thread's job, forced here
        assert len(fake.published) == 1
        msg = fake.published[0]
        # the stream shed past the burst budget...
        assert len(msg["records"]) <= 5
        assert msg["dropped"] >= 55
        assert msg["dropped_total"] == msg["dropped"]
        # ...but the tail index kept everything (bounded by maxlen)
        assert len(mon.tail_records("worker-bbbbbbbbbbbb", 100)) == 60
    finally:
        mon.stop()


def test_tail_index_bounded(tmp_path):
    mon, fake, d = _monitor(tmp_path, tail_lines=25)
    try:
        path = os.path.join(d, "worker-cccccccccccc.log")
        with open(path, "w") as f:
            for i in range(100):
                f.write(f"line-{i}\n")
        mon.scan_now()
        recs = mon.tail_records("worker-cccccccccccc", 1000)
        assert len(recs) == 25
        assert recs[-1]["msg"] == "line-99"
    finally:
        mon.stop()


# ---- cluster query plane (live) -------------------------------------------


def test_actor_filtered_query_one_fanout_round(ray_start):
    """Acceptance: `logs --actor <name> --tail N` returns only that
    actor's lines, each carrying node/worker/task ids and trace id."""

    @ray_tpu.remote
    class Talker:
        def speak(self, what):
            print(f"speak {what} LOGPLANE-{what}")
            return what

    a = Talker.options(name="talker-a", num_cpus=0.1).remote()
    b = Talker.options(name="talker-b", num_cpus=0.1).remote()
    from ray_tpu.util import tracing
    with tracing.start_trace("logplane-test") as trace_id:
        assert ray_tpu.get(a.speak.remote("AAA"), timeout=120) == "AAA"
    assert ray_tpu.get(b.speak.remote("BBB"), timeout=120) == "BBB"

    out = {}
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        out = state_api.logs(actor="talker-a", match="LOGPLANE-", tail=50)
        if out["records"]:
            break
        time.sleep(0.2)
    recs = out["records"]
    assert recs, "actor-filtered query returned nothing"
    assert all("LOGPLANE-AAA" in r["msg"] for r in recs), recs
    for r in recs:
        assert r["node_id"] and r["worker_id"] and r["task_id"], r
        assert r["trace_id"] == trace_id
        assert r["actor_id"]
    # the other actor's lines exist but are filtered out server-side
    out_b = state_api.logs(actor="talker-b", match="LOGPLANE-", tail=50)
    assert all("LOGPLANE-BBB" in r["msg"] for r in out_b["records"])


def test_trace_id_filter(ray_start):
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def traced():
        print("inside traced task TRACEMARK")
        return 1

    with tracing.start_trace("logplane-trace") as trace_id:
        assert ray_tpu.get(traced.remote(), timeout=120) == 1
    recs = []
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and not recs:
        recs = state_api.logs(trace_id=trace_id, tail=50)["records"]
        time.sleep(0.2)
    assert recs and all(r["trace_id"] == trace_id for r in recs)
    assert any("TRACEMARK" in r["msg"] for r in recs)


def test_single_deadline_with_unreachable_node(ray_start):
    """An unreachable node must not hang or double the query's worst
    case: both gather phases run under ONE overall deadline, and the
    reply names the node that never answered."""
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.state import NodeInfo
    ghost = NodeInfo(node_id=NodeID.from_random(),
                     address=("127.0.0.1", 1),
                     store_address=("127.0.0.1", 1),
                     resources_total={}, labels={})
    _gcs().call("register_node", info=ghost)
    try:
        t0 = time.monotonic()
        out = state_api.logs(tail=5, timeout=1.5)
        dt = time.monotonic() - t0
        assert ghost.node_id.hex() in out["unreachable"]
        # timeout + grace + slack, NOT timeout * phases
        assert dt < 6.0, f"fan-out took {dt:.1f}s against a 1.5s deadline"
    finally:
        _gcs().call("unregister_node", node_id_hex=ghost.node_id.hex())


def test_driver_records_survive_identity_filters(ray_start):
    """Driver ring records get node/worker identity attached BEFORE
    filtering — a node- or worker-filtered query must not silently drop
    every driver line."""
    import logging
    logging.getLogger("driver-test").warning("driver ring DRIVERMARK")
    snap = log_plane.snapshot(filters={"match": "DRIVERMARK"})
    assert snap["records"], "driver logging capture missed the record"
    rec = snap["records"][-1]
    assert rec["worker_id"] and rec["level"] == "WARNING"
    snap2 = log_plane.snapshot(filters={
        "match": "DRIVERMARK", "worker_id": rec["worker_id"],
        **({"node_id": rec["node_id"]} if rec["node_id"] else {})})
    assert snap2["records"], "identity filter dropped the driver record"


def test_follow_mode_streams_new_records(ray_start):
    import threading
    got = []

    def consume():
        for rec in state_api.follow_logs(match="FOLLOWMARK",
                                         duration=12.0):
            got.append(rec)
            return

    from ray_tpu._private import worker as worker_mod
    cw = worker_mod.global_worker().core_worker
    subs_before = len([k for k in cw._subscriptions
                       if k[0] == "worker_logs"])
    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.8)  # let the generator subscribe

    @ray_tpu.remote
    def chatty():
        print("hello from follow FOLLOWMARK")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=120) == 1
    t.join(timeout=15)
    assert got, "follow mode never yielded the new record"
    assert "FOLLOWMARK" in got[0]["msg"]
    assert got[0]["worker_id"] and got[0]["task_id"]
    # the generator's teardown unsubscribed end to end: repeated
    # follows must not multiply the publish fan-out
    assert len([k for k in cw._subscriptions
                if k[0] == "worker_logs"]) == subs_before


# ---- crash postmortems (live) ---------------------------------------------


def test_kill_worker_postmortem_bundle(ray_start):
    """Acceptance: under a chaos kill_worker rule the raised failure
    names a postmortem id whose bundle holds the dead worker's last log
    lines and span-ring tail."""
    from ray_tpu import chaos

    @ray_tpu.remote
    class Doomed:
        def work(self):
            print("about to die DOOMED-MARK")
            return 1

    a = Doomed.options(num_cpus=0.1).remote()
    assert ray_tpu.get(a.work.remote(), timeout=120) == 1
    rid = chaos.inject("kill_worker", actor_class="Doomed", max_fires=1)
    err = None
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and err is None:
            try:
                ray_tpu.get(a.work.remote(), timeout=30)
                time.sleep(0.1)
            except Exception as e:  # noqa: BLE001 - the death we seeded
                err = e
    finally:
        chaos.clear([rid])
    assert err is not None, "kill_worker rule never fired"
    m = re.search(r"postmortem (pm-[0-9a-f]+)", str(err))
    assert m, f"error does not reference a postmortem: {err}"
    pm_id = m.group(1)
    bundle = None
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and bundle is None:
        bundle = state_api.get_postmortem(pm_id)
        time.sleep(0.2)
    assert bundle is not None, "bundle never reached the GCS ring"
    assert bundle["kind"] == "worker_death"
    assert bundle["is_actor"] and bundle["actor_id"]
    assert any("DOOMED-MARK" in r.get("msg", "")
               for r in bundle["log_tail"]), bundle["log_tail"][-5:]
    # the worker's own black-box flight dump carried its span ring out
    assert bundle["span_tail"], "span-ring tail missing from the bundle"
    assert bundle["gauges"].get("store_capacity_bytes")
    # and the summary listing shows it without the bulky tails
    summaries = state_api.postmortems()
    match = [s for s in summaries if s["postmortem_id"] == pm_id]
    assert match and "log_tail" not in match[0]
    assert match[0]["log_lines"] == len(bundle["log_tail"])


def test_task_error_postmortem(ray_start):
    # a name of its own: tests/test_core_api.py has a failing `boom` too,
    # and under xdist both files can share one worker's cluster
    @ray_tpu.remote
    def boom_postmortem():
        print("pre-failure context BOOM-MARK")
        raise ValueError("intentional")

    with pytest.raises(ValueError):
        ray_tpu.get(boom_postmortem.options(max_retries=0).remote(),
                    timeout=120)
    found = None
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and found is None:
        for s in state_api.postmortems():
            if s.get("kind") == "task_error" \
                    and s.get("task") == "boom_postmortem":
                found = state_api.get_postmortem(s["postmortem_id"])
                break
        time.sleep(0.2)
    assert found is not None, "no task_error postmortem captured"
    assert "intentional" in found["reason"]
    assert "ValueError" in (found.get("traceback") or "")
    assert any("BOOM-MARK" in r.get("msg", "") for r in found["log_tail"])


# ---- CLI surface -----------------------------------------------------------


def test_cli_logs_query_and_postmortem_listing(ray_start, capsys):
    import json as _json

    from ray_tpu.scripts.cli import main as cli_main

    @ray_tpu.remote
    def clitalk():
        print("cli surface CLIMARK")
        return 1

    assert ray_tpu.get(clitalk.remote(), timeout=120) == 1
    addr = ray_tpu.get_gcs_address()
    out = ""
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and "CLIMARK" not in out:
        assert cli_main(["logs", "--address", addr, "--match", "CLIMARK",
                         "--format", "json"]) == 0
        out = capsys.readouterr().out
        time.sleep(0.2)
    payload = _json.loads(out)
    assert any("CLIMARK" in r["msg"] for r in payload["records"])
    # text mode renders id-prefixed lines
    assert cli_main(["logs", "--address", addr, "--match", "CLIMARK"]) == 0
    text = capsys.readouterr().out
    assert "CLIMARK" in text and "w:" in text and "t:" in text
    # postmortem listing renders (content covered by the kill test)
    assert cli_main(["logs", "--address", addr, "--postmortems"]) == 0
