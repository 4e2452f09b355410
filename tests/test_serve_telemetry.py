"""Serve request telemetry: end-to-end tracing, RED metrics, the
slow/error request ring, proxy error semantics, and the SLO watchdog.

reference parity: serve/_private/proxy.py + metrics_utils.py (the
reference's deployment-tagged request instrumentation), rebuilt on this
repo's span/metrics/watchdog planes (see README "Serve request
telemetry")."""

import json
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import state as state_api


@pytest.fixture()
def serve_session(ray_start):
    yield ray_start
    serve.shutdown()


def _gcs():
    return ray_tpu._private.worker.global_worker().core_worker._gcs


def _post(port, dep, body=None, request_id=None, timeout=60):
    headers = {"Content-Type": "application/json"}
    if request_id:
        headers["X-Request-Id"] = request_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{dep}",
        data=json.dumps(body if body is not None else {}).encode(),
        headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def test_trace_id_propagates_proxy_to_nested_replicas(serve_session):
    """One inbound X-Request-Id links ingress → handle → replica →
    NESTED deployment call: the header comes back on the response, the
    request ring names it with a per-stage breakdown, and `ray_tpu
    timeline --trace-id` shows the same request's spans merged across
    the proxy and BOTH replica processes."""

    @serve.deployment(name="tele_embedder")
    def embedder(text):
        return len(text)

    @serve.deployment(name="tele_ranker")
    class Ranker:
        def __init__(self, downstream):
            self.downstream = downstream

        def __call__(self, texts):
            refs = [self.downstream.remote(t) for t in texts]
            return sorted(ray_tpu.get(refs, timeout=60), reverse=True)

    emb = serve.run(embedder)
    serve.run(Ranker.bind(emb))
    proxy = serve.start_http(port=0)
    port = ray_tpu.get(proxy.ready.remote())
    tid = "feedbeefdeadc0de"
    try:
        body, headers = _post(port, "tele_ranker",
                              {"texts": ["aa", "bbbb", "c"]},
                              request_id=tid)
        assert body == {"result": [4, 2, 1]}
        assert headers.get("X-Request-Id") == tid

        # the ring entry carries the SAME id + a per-stage breakdown
        out = state_api.serve_requests(deployment="tele_ranker")
        mine = [e for e in out["requests"] if e["trace_id"] == tid]
        assert mine, out
        stages = mine[0]["stages"]
        for stage in ("parse_s", "route_s", "handle_s", "serialize_s",
                      "write_s"):
            assert stage in stages, stages
        assert mine[0]["code"] == 200 and mine[0]["error"] is None

        # merged timeline: the one trace id spans proxy AND both
        # replica processes (nested call included)
        events = ray_tpu.timeline(spans=True, trace_id=tid)
        by_name = {}
        for e in events:
            if e.get("cat") == "span":
                by_name.setdefault(e["name"], set()).add(e["pid"])
        assert "serve.proxy.request" in by_name
        assert "serve.handle.submit" in by_name
        # execute spans from the ranker replica and the nested
        # embedder replica: two distinct process rows
        assert len(by_name.get("serve.replica.execute", ())) >= 2, \
            by_name
        assert "serve.replica.queue" in by_name
    finally:
        ray_tpu.kill(proxy)


def test_red_metrics_and_queue_gauges_on_merged_endpoint(serve_session):
    """Per-deployment requests_total{code} + request/queue histograms
    and the handle/replica queue-depth gauges all ride the PR-6 harvest
    onto the cluster-merged /metrics exposition."""

    @serve.deployment(name="tele_red")
    def red(x=0):
        return x

    serve.run(red)
    proxy = serve.start_http(port=0)
    port = ray_tpu.get(proxy.ready.remote())
    try:
        for i in range(6):
            _post(port, "tele_red", {"x": i})
        text = state_api.cluster_metrics_text(fresh=True)
        assert 'ray_tpu_serve_requests_total{' in text
        # per-deployment, code-tagged counter series
        line = next(l for l in text.splitlines()
                    if l.startswith("ray_tpu_serve_requests_total")
                    and 'deployment="tele_red"' in l)
        assert 'code="200"' in line
        assert "ray_tpu_serve_request_seconds_bucket" in text
        assert 'ray_tpu_serve_queue_seconds_bucket' in text
        assert "ray_tpu_serve_handle_queue_depth" in text
        assert "ray_tpu_serve_replica_queue_depth" in text
    finally:
        ray_tpu.kill(proxy)


def test_error_semantics_and_request_ring(serve_session):
    """Satellite: unknown deployment → 404, handler exception → 500,
    configured timeout → 504 — each still recording trace + metrics —
    and the ring's --errors/--slowest/--deployment query surface plus
    the `ray_tpu serve requests` CLI."""

    @serve.deployment(name="tele_flaky")
    def flaky(x=0):
        raise ValueError("boom")

    @serve.deployment(name="tele_slow")
    def slow(x=0):
        time.sleep(6.0)
        return x

    serve.run(flaky)
    serve.run(slow)
    # the timeout leaves the handler that raises two seconds to answer
    # with the machine under the whole suite's load (at 0.4 s its first,
    # cold request could time out too: a 504 where the 500 is due), and
    # stays a third of what the slow handler takes
    proxy = serve.start_http(port=0, request_timeout_s=2.0)
    port = ray_tpu.get(proxy.ready.remote())
    try:
        codes = {}
        for dep in ("tele_nope", "tele_flaky", "tele_slow"):
            try:
                _post(port, dep)
                codes[dep] = 200
            except urllib.error.HTTPError as e:
                codes[dep] = e.code
                payload = json.loads(e.read())
                assert payload["error"] and payload["request_id"]
        assert codes == {"tele_nope": 404, "tele_flaky": 500,
                         "tele_slow": 504}, codes

        # the ring of this test's requests: another test's proxy may
        # still be dying beside this one, with its own entries
        mine = ("tele_nope", "tele_flaky", "tele_slow")
        errs = [e for e in state_api.serve_requests(errors=True)["requests"]
                if e["deployment"] in mine]
        ring_codes = {e["deployment"]: e["code"] for e in errs}
        assert ring_codes.get("tele_nope") == 404
        assert ring_codes.get("tele_flaky") == 500
        assert ring_codes.get("tele_slow") == 504
        # every captured request carries a trace id (504 included:
        # "timed-out requests must still record their trace")
        assert all(e.get("trace_id") for e in errs)

        only_flaky = state_api.serve_requests(
            deployment="tele_flaky", errors=True)["requests"]
        assert only_flaky and all(e["deployment"] == "tele_flaky"
                                  for e in only_flaky)
        slowest = [e for e in state_api.serve_requests(
            slowest=16)["requests"] if e["deployment"] in mine]
        assert slowest and slowest[0]["deployment"] == "tele_slow"

        # timed-out requests still count, code-tagged 504
        text = state_api.cluster_metrics_text(fresh=True)
        assert any('deployment="tele_slow"' in l and 'code="504"' in l
                   for l in text.splitlines()
                   if l.startswith("ray_tpu_serve_requests_total"))

        # CLI: text table + json
        from ray_tpu.scripts.cli import main as cli_main
        addr = ray_tpu.get_gcs_address()
        assert cli_main(["serve", "requests", "--address", addr,
                         "--errors", "--format", "json"]) == 0
        assert cli_main(["serve", "requests", "--address", addr,
                         "--slowest", "3"]) == 0
    finally:
        ray_tpu.kill(proxy)


def test_grpc_proxy_trace_metadata_and_not_found(serve_session):
    """The gRPC ingress honors x-request-id metadata (echoed in the
    trailing metadata) and maps unknown deployments to NOT_FOUND."""
    import grpc

    @serve.deployment(name="tele_grpc")
    def g(x=0):
        return x * 2

    serve.run(g)
    proxy = serve.start_grpc(port=0)
    port = ray_tpu.get(proxy.ready.remote())
    try:
        import pickle
        tid = "cafebabe01234567"
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            fn = channel.unary_unary(
                serve.grpc_proxy.SERVICE_PREFIX + "tele_grpc",
                request_serializer=None, response_deserializer=None)
            resp, call = fn.with_call(
                pickle.dumps(((21,), {}), protocol=5), timeout=60,
                metadata=(("x-request-id", tid),))
            assert pickle.loads(resp) == 42
            trailing = dict(call.trailing_metadata() or ())
            assert trailing.get("x-request-id") == tid
        with pytest.raises(grpc.RpcError) as e:
            serve.grpc_call(f"127.0.0.1:{port}", "tele_missing", 1,
                            timeout=30)
        assert e.value.code() == grpc.StatusCode.NOT_FOUND
        # the grpc ring entries share the http proxies' shape
        errs = state_api.serve_requests(errors=True)["requests"]
        assert any(e["deployment"] == "tele_missing"
                   and e["method"] == "grpc" and e["code"] == 404
                   for e in errs)
    finally:
        ray_tpu.get(proxy.stop.remote(), timeout=30)
        ray_tpu.kill(proxy)


def test_slo_watchdog_alerts_under_chaos(serve_session):
    """serve_latency_slo + serve_error_burn HEALTH_ALERTs fire within
    two harvest intervals under a chaos-injected replica delay rule and
    an erroring deployment, live on the running watchdog."""
    import threading

    from ray_tpu import chaos

    @serve.deployment(name="tele_slo")
    def slo(x=0):
        return x

    @serve.deployment(name="tele_burn")
    def burn(x=0):
        raise ValueError("burn")

    serve.run(slo)
    serve.run(burn)
    proxy = serve.start_http(port=0)
    port = ray_tpu.get(proxy.ready.remote())
    # warm both paths before the clock starts (replica startup +
    # listener arming must not eat the alert-latency budget)
    _post(port, "tele_slo")
    try:
        _post(port, "tele_burn")
    except urllib.error.HTTPError:
        pass

    interval = 1.0
    t_start = time.time()
    _gcs().call("metrics_configure", interval_s=interval,
                cooldown_s=0.1, serve_p99_s=0.05, serve_error_rate=0.2)
    rid = chaos.inject("delay", method="w_push_task",
                       actor_class="Replica", delay_ms=150)
    stop = [False]

    def load(dep):
        while not stop[0]:
            try:
                _post(port, dep, timeout=30)
            except urllib.error.HTTPError:
                pass

    threads = [threading.Thread(target=load, args=(d,), daemon=True)
               for d in ["tele_slo"] * 4 + ["tele_burn"] * 3]
    for t in threads:
        t.start()
    found = {}
    try:
        deadline = time.monotonic() + 25
        while time.monotonic() < deadline and len(found) < 2:
            time.sleep(0.2)
            for a in state_api.health_alerts():
                if a.get("ts", 0) >= t_start and a.get("probe") in (
                        "serve_latency_slo", "serve_error_burn"):
                    found.setdefault(a["probe"], a)
        assert "serve_latency_slo" in found, found
        assert "serve_error_burn" in found, found
        assert found["serve_error_burn"]["severity"] == "ERROR"
        # within two harvest intervals (+ scheduling slack on a loaded
        # box; traffic is continuous so the first judged window breaches)
        for a in found.values():
            assert a["ts"] - t_start < 2 * interval + 4.0, a
    finally:
        stop[0] = True
        for t in threads:
            t.join(timeout=5)
        chaos.clear([rid])
        _gcs().call("metrics_configure", interval_s=2.0,
                    cooldown_s=30.0, serve_p99_s=2.0,
                    serve_error_rate=0.1)
        ray_tpu.kill(proxy)


def test_telemetry_overhead_bounded(serve_session):
    """Acceptance: telemetry cost per request (records/request x
    in-situ per-record cost) stays under 2% of the measured request
    latency — the PR-5 methodology, since a direct on/off A-B cannot
    resolve sub-1% effects under this box's scheduling noise."""
    from ray_tpu._private import spans
    from ray_tpu.util.metrics import Histogram, get_or_create

    @serve.deployment(name="tele_overhead")
    def fast(x=0):
        return x

    handle = serve.run(fast)
    # measured request latency on the REAL path (handle → replica)
    lat = []
    for i in range(30):
        t0 = time.perf_counter()
        assert ray_tpu.get(handle.remote(i), timeout=60) == i
        lat.append(time.perf_counter() - t0)
    mean_latency = sum(lat) / len(lat)

    def best_of(fn, batches=5, n=5000):
        fn(500)  # warm
        return min(fn(n) for _ in range(batches))

    def span_batch(n):
        t0 = time.perf_counter()
        for _ in range(n):
            spans.end("tele.cost_probe", spans.begin())
        return (time.perf_counter() - t0) / n

    hist = get_or_create(Histogram, "tele_cost_probe_seconds",
                         boundaries=[0.01, 1.0],
                         tag_keys=("deployment",))

    def metric_batch(n):
        t0 = time.perf_counter()
        for _ in range(n):
            hist.observe(0.001, tags={"deployment": "d"})
        return (time.perf_counter() - t0) / n

    span_cost = best_of(span_batch)
    metric_cost = best_of(metric_batch)
    # handle-path records per request: handle.submit + replica.queue +
    # replica.execute spans; request_seconds + queue_seconds observes
    # (the proxy path adds 2 spans + 1 counter inc on a >=1ms-larger
    # request, so the handle path is the worst case for the ratio)
    per_request = 3 * span_cost + 2 * metric_cost
    overhead = per_request / mean_latency
    assert overhead < 0.02, (
        f"telemetry overhead {100 * overhead:.3f}% "
        f"(span {span_cost * 1e6:.2f}us, metric "
        f"{metric_cost * 1e6:.2f}us, request {mean_latency * 1e3:.2f}ms)")
