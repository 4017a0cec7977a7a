"""Integration tests of the real HTTP server (reference
cmd/integration/server_test.go shape: boot the full wiring, drive
Predicate over the wire, poll for async effects)."""

import json
import time
import urllib.request

import pytest

from k8s_spark_scheduler_tpu.config import Install
from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
from k8s_spark_scheduler_tpu.kube.crd import DEMAND_CRD_NAME, demand_crd_spec
from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
from k8s_spark_scheduler_tpu.server.wiring import init_server_with_clients
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types import serde


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture
def served():
    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    scheduler = init_server_with_clients(
        api, Install(binpack_algo="tightly-pack"), demand_poll_interval=0.02
    )
    scheduler.lazy_demand_informer.wait_ready(5)
    http = ExtenderHTTPServer(scheduler, port=0)
    http.start()
    yield api, scheduler, http
    http.stop()
    scheduler.stop()


def _create_nodes(api, count=2):
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import Resources, ZONE_LABEL

    for i in range(count):
        api.create(
            Node(
                meta=ObjectMeta(
                    name=f"n{i}",
                    labels={ZONE_LABEL: "z1", "resource_channel": "batch-medium-priority"},
                ),
                allocatable=Resources.of("8", "8Gi", "1"),
            )
        )


def _driver_pod_json(app_id="app-http", executors=2):
    pods = Harness.static_allocation_spark_pods(app_id, executors)
    return serde.pod_to_dict(pods[0]), [serde.pod_to_dict(p) for p in pods[1:]]


def test_predicates_end_to_end(served):
    api, scheduler, http = served
    _create_nodes(api)

    driver_json, exec_jsons = _driver_pod_json()
    # the driver pod exists in the cluster before kube-scheduler calls us
    api.create(serde.pod_from_dict(driver_json))

    status, result = _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})
    assert status == 200
    assert result["NodeNames"] and result["NodeNames"][0] in ("n0", "n1")

    # reservation lands in the API server asynchronously
    deadline = time.time() + 5
    while time.time() < deadline and not api.list("ResourceReservation"):
        time.sleep(0.01)
    rrs = api.list("ResourceReservation")
    assert len(rrs) == 1 and rrs[0].name == "app-http"

    # bind the driver, then schedule executors over the wire
    driver = api.get("Pod", "default", serde.pod_from_dict(driver_json).name)
    driver.node_name = result["NodeNames"][0]
    driver.phase = "Running"
    api.update(driver)
    for exec_json in exec_jsons:
        api.create(serde.pod_from_dict(exec_json))
        status, result = _post(
            http.port, "/predicates", {"Pod": exec_json, "NodeNames": ["n0", "n1"]}
        )
        assert status == 200 and result["NodeNames"]


def test_predicates_rejects_bad_payloads(served):
    _, _, http = served
    status, body = _post(http.port, "/predicates", {"Pod": {"metadata": {}}, "NodeNames": []})
    # a pod with no spark role → failure result, not a 500
    assert status == 200
    assert not body.get("NodeNames")

    req = urllib.request.Request(
        f"http://127.0.0.1:{http.port}/predicates", data=b"{not json", method="POST"
    )
    try:
        urllib.request.urlopen(req, timeout=10)
        raised = False
    except urllib.error.HTTPError as e:
        raised = e.code == 400
    assert raised


def test_management_endpoints(served):
    _, _, http = served
    assert _get(http.port, "/status/liveness")[0] == 200
    assert _get(http.port, "/status/readiness")[0] == 200
    status, metrics = _get(http.port, "/metrics")
    assert status == 200 and "counters" in metrics
    assert _get(http.port, "/nope")[0] == 404


def test_conversion_webhook_roundtrip(served):
    _, _, http = served
    from k8s_spark_scheduler_tpu.scheduler.reservations_manager import (
        new_resource_reservation,
    )
    from k8s_spark_scheduler_tpu.types.resources import Resources

    pods = Harness.static_allocation_spark_pods("app-conv", 1, executor_gpu="2")
    rr = new_resource_reservation(
        "n0", ["n1"], pods[0], Resources.of("1", "1Gi", "1"), Resources.of("2", "2Gi", "2")
    )
    v2 = serde.rr_to_dict_v1beta2(rr)

    # v1beta2 → v1beta1
    review = {
        "request": {
            "uid": "u1",
            "desiredAPIVersion": "sparkscheduler.palantir.com/v1beta1",
            "objects": [v2],
        }
    }
    status, body = _post(http.port, "/convert", review)
    assert status == 200
    response = body["response"]
    assert response["result"]["status"] == "Success"
    v1 = response["convertedObjects"][0]
    assert v1["apiVersion"].endswith("v1beta1")
    assert v1["spec"]["reservations"]["driver"]["cpu"] == "1"
    assert serde.RESERVATION_SPEC_ANNOTATION_KEY in v1["metadata"]["annotations"]

    # v1beta1 → v1beta2 recovers the GPU dimension from the annotation
    review = {
        "request": {
            "uid": "u2",
            "desiredAPIVersion": "sparkscheduler.palantir.com/v1beta2",
            "objects": [v1],
        }
    }
    status, body = _post(http.port, "/convert", review)
    back = body["response"]["convertedObjects"][0]
    assert back["spec"]["reservations"]["executor-1"]["resources"]["nvidia.com/gpu"] == "2"
    assert serde.RESERVATION_SPEC_ANNOTATION_KEY not in back["metadata"]["annotations"]
    # full round trip is lossless
    assert back["spec"] == v2["spec"]


def test_standalone_webhook_module():
    http = ExtenderHTTPServer(None, port=0, webhook_only=True)
    http.start()
    try:
        status, body = _post(http.port, "/convert", {"request": {"uid": "x", "objects": []}})
        assert status == 200 and body["response"]["result"]["status"] == "Success"
        # predicates must not be served by the standalone webhook
        status, _ = _post(http.port, "/predicates", {"Pod": {}, "NodeNames": []})
        assert status == 404
    finally:
        http.stop()


def test_cli_version():
    from k8s_spark_scheduler_tpu.server.__main__ import main

    assert main(["--version"]) == 0


def test_static_compaction_integration(served):
    """cmd/integration/server_test.go:41 Test_StaticCompaction: a
    pre-existing reservation whose executor pod is gone plus an
    out-of-band-scheduled replacement; the first Predicate after idle
    reconciles and the ASYNC write-back visibly patches the RR at the
    API server (polled, like waitForCondition common.go:119-136)."""
    api, scheduler, http = served
    from k8s_spark_scheduler_tpu.scheduler.extender import (
        LEADER_ELECTION_INTERVAL_SECONDS,
    )
    from k8s_spark_scheduler_tpu.scheduler.reservations_manager import (
        new_resource_reservation,
    )
    from k8s_spark_scheduler_tpu.types.objects import PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    _create_nodes(api)

    # pre-existing state: driver + one executor reservation, but the
    # executor named in status is long dead and a NEW executor pod was
    # scheduled out of band (by the previous leader)
    pods = Harness.static_allocation_spark_pods("app-compact", 1)
    driver, executor = pods
    driver.node_name = "n0"
    driver.phase = PodPhase.RUNNING
    created_driver = api.create(driver)

    rr = new_resource_reservation(
        "n0", ["n1"], created_driver, Resources.of("1", "1Gi"), Resources.of("1", "1Gi")
    )
    rr.status.pods["executor-1"] = "long-gone-executor"
    api.create(rr)

    executor.node_name = "n1"
    executor.phase = PodPhase.RUNNING
    api.create(executor)

    # force the idle-reconcile path on the next request
    scheduler.extender._last_request = (
        time.time() - LEADER_ELECTION_INTERVAL_SECONDS - 1
    )
    probe = Harness.static_allocation_spark_pods("probe-app", 0)[0]
    api.create(serde.pod_from_dict(serde.pod_to_dict(probe)))
    status, _ = _post(
        http.port, "/predicates", {"Pod": serde.pod_to_dict(probe), "NodeNames": ["n0", "n1"]}
    )
    assert status == 200

    # the reconciler claims the orphan executor onto the stale reservation
    # and the async client patches the API server visibly
    deadline = time.time() + 5
    patched = False
    while time.time() < deadline and not patched:
        server_rr = api.get("ResourceReservation", "default", "app-compact")
        patched = server_rr.status.pods.get("executor-1") == executor.name
        time.sleep(0.01)
    assert patched, server_rr.status.pods


def test_concurrent_predicates_soak(served):
    """Parallel Filter requests from many client threads must neither
    crash nor double-book: every successful gang keeps reservation
    accounting consistent (kube-scheduler serializes per instance; the
    extender enforces the same internally for threaded front ends)."""
    import threading

    api, scheduler, http = served
    _create_nodes(api, count=4)
    nodes = [f"n{i}" for i in range(4)]

    results = {}
    errors = []

    def submit(i):
        try:
            pods = Harness.static_allocation_spark_pods(f"soak-{i}", 2)
            api.create(serde.pod_from_dict(serde.pod_to_dict(pods[0])))
            status, out = _post(
                http.port,
                "/predicates",
                {"Pod": serde.pod_to_dict(pods[0]), "NodeNames": nodes},
            )
            results[i] = (status, tuple(out.get("NodeNames") or []))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submit, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert not errors
    assert all(status == 200 for status, _ in results.values())
    # 4 nodes x 8cpu = 32 cpu; each app needs 3 -> exactly 10 fit
    granted = [i for i, (_, ns) in results.items() if ns]
    assert len(granted) == 10
    # accounting: total reserved cpu across RRs never exceeds capacity
    total = 0
    for rr in scheduler.resource_reservation_cache.list():
        for res in rr.spec.reservations.values():
            total += res.resources_value().cpu.value()
    assert total <= 32, total


def test_request_tracing_header(served):
    _, _, http = served
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{http.port}/convert",
        data=b'{"request": {"uid": "t", "objects": []}}',
        headers={"X-Trace-Id": "my-trace-123"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.headers.get("X-Trace-Id") == "my-trace-123"
    # auto-generated when absent
    req = urllib.request.Request(
        f"http://127.0.0.1:{http.port}/convert",
        data=b'{"request": {"uid": "t", "objects": []}}',
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.headers.get("X-Trace-Id")


def test_uid_less_wire_pod_reservation_still_gcd(served):
    """A pod POSTed without metadata.uid (kube-scheduler always sends
    one; simulators may not) must not produce a reservation whose owner
    reference the GC can never match — that would leak held capacity
    forever.  The extender backfills the UID from its informer."""
    api, scheduler, http = served
    _create_nodes(api)

    driver_json, _ = _driver_pod_json("app-no-uid")
    api.create(serde.pod_from_dict(driver_json))
    assert not driver_json["metadata"].get("uid")  # wire pod is UID-less

    status, result = _post(
        http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]}
    )
    assert status == 200 and result["NodeNames"]

    deadline = time.time() + 5
    while time.time() < deadline and not api.list("ResourceReservation"):
        time.sleep(0.01)
    rr = api.list("ResourceReservation")[0]
    stored = api.get("Pod", "default", "app-no-uid-driver")
    assert rr.meta.owner_references[0].uid == stored.meta.uid

    # owner GC collects the reservation when the driver goes away
    api.delete("Pod", "default", stored.name)
    deadline = time.time() + 5
    while time.time() < deadline and api.list("ResourceReservation"):
        time.sleep(0.01)
    assert not api.list("ResourceReservation")


def test_uid_less_unknown_pod_rejected(served):
    """A UID-less pod the informer has never seen must be rejected
    (FAILURE result), not granted a reservation no GC can ever collect."""
    api, scheduler, http = served
    _create_nodes(api)

    driver_json, _ = _driver_pod_json("app-ghost")
    # deliberately NOT created in the API server
    status, result = _post(
        http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]}
    )
    assert status == 200
    assert not result.get("NodeNames")
    assert result["FailedNodes"]
    time.sleep(0.2)
    assert not api.list("ResourceReservation")


def test_readiness_gates_on_solver_warmup(served):
    """Readiness must report not-ready while the solver warmup is still
    compiling (its compiler threads would otherwise contend with the
    first Filters), and flip ready when it completes (r5)."""
    import threading

    _, scheduler, http = served
    ev = getattr(scheduler, "_warm_done", None)
    assert ev is None or ev.is_set()  # CPU-host warmup finishes fast
    # simulate an in-flight warmup
    scheduler._warm_done = threading.Event()
    try:
        assert not scheduler.warmup_complete()
        assert _get(http.port, "/status/readiness")[0] == 503
        scheduler._warm_done.set()
        assert scheduler.warmup_complete()
        assert _get(http.port, "/status/readiness")[0] == 200
        assert scheduler.wait_ready(timeout=5.0)
    finally:
        scheduler._warm_done.set()


def _get_raw(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.headers, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read()


def test_trace_id_sanitization(served):
    """An unvalidated client header must not flow into response headers
    or log lines: bad charset / oversized ids are replaced."""
    _, _, http = served
    payload = b'{"request": {"uid": "t", "objects": []}}'
    for bad in ("evil\ninjected: header", "x" * 200, 'quo"te', "space id"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/convert", data=payload, method="POST"
        )
        req.add_unredirected_header("X-Trace-Id", bad.replace("\n", ""))
        with urllib.request.urlopen(req, timeout=10) as resp:
            echoed = resp.headers.get("X-Trace-Id")
            assert echoed != bad.replace("\n", "")
            assert echoed and len(echoed) <= 64
    # a well-formed id still round-trips
    req = urllib.request.Request(
        f"http://127.0.0.1:{http.port}/convert", data=payload,
        headers={"X-Trace-Id": "good-id_123"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.headers.get("X-Trace-Id") == "good-id_123"


def test_metrics_prometheus_negotiation(served):
    api, scheduler, http = served
    _create_nodes(api)
    driver_json, _ = _driver_pod_json("app-prom")
    api.create(serde.pod_from_dict(driver_json))
    _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})

    # default stays JSON (existing dashboards/tests read it)
    status, body = _get(http.port, "/metrics")
    assert status == 200 and "counters" in body

    # Accept: text/plain → Prometheus exposition
    status, headers, raw = _get_raw(
        http.port, "/metrics", {"Accept": "text/plain;version=0.0.4"}
    )
    assert status == 200
    assert headers.get("Content-Type").startswith("text/plain")
    text = raw.decode()
    assert "# TYPE foundry_spark_scheduler_requests counter" in text
    assert 'outcome="success"' in text
    # ?format=prometheus works without the header
    status, _, raw2 = _get_raw(http.port, "/metrics?format=prometheus")
    assert status == 200 and b"# TYPE" in raw2


@pytest.fixture
def served_fifo():
    """Full wiring with the FIFO device queue solver (the acceptance
    configuration: every predicate runs FIFO gate + binpack kernel)."""
    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    scheduler = init_server_with_clients(
        api,
        Install(binpack_algo="tpu-batch", fifo=True),
        demand_poll_interval=0.02,
    )
    scheduler.lazy_demand_informer.wait_ready(5)
    # force the XLA lane so the kernel profiler sees jit compile +
    # execute even on hosts where the native C++ lane would serve
    solver = scheduler.extender.binpacker.queue_solver
    if solver is not None:
        solver.backend = "xla"
    http = ExtenderHTTPServer(scheduler, port=0)
    http.start()
    yield api, scheduler, http
    http.stop()
    scheduler.stop()


def test_traces_cover_fifo_binpack_and_writeback(served_fifo):
    """Acceptance: a predicate request produces a retrievable span tree
    covering FIFO gate, binpack kernel (with compile/execute timings),
    and reservation write-back; /metrics serves Prometheus text for the
    same run."""
    api, scheduler, http = served_fifo
    _create_nodes(api, count=3)

    # one earlier pending driver so the FIFO queue pass has real work
    earlier = Harness.static_allocation_spark_pods("app-earlier", 1)[0]
    api.create(earlier)
    import time as _t

    _t.sleep(0.05)  # strictly earlier creation timestamp
    driver_json, _ = _driver_pod_json("app-traced", executors=1)
    api.create(serde.pod_from_dict(driver_json))

    status, result = _post(
        http.port,
        "/predicates",
        {"Pod": driver_json, "NodeNames": ["n0", "n1", "n2"]},
    )
    assert status == 200 and result["NodeNames"]

    status, body = _get(http.port, "/traces")
    assert status == 200
    traces = body["traces"]
    assert traces, "no traces recorded"

    def walk(span):
        yield span
        for c in span.get("children", ()):
            yield from walk(c)

    pod_name = driver_json["metadata"]["name"]
    trace = next(
        t
        for t in traces
        if any(s.get("tags", {}).get("pod") == pod_name for s in walk(t["root"]))
    )
    spans = list(walk(trace["root"]))
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    assert "http.request" in by_name and "predicate" in by_name
    # FIFO gate phase with the earlier driver counted
    (gate,) = by_name["fifo_gate"]
    assert gate["tags"]["earlierApps"] >= 1
    assert gate["tags"]["earlierOk"] is True
    # binpack kernel spans with the compile/execute split
    kernel_spans = [s for s in spans if s["name"].startswith("kernel:")]
    assert kernel_spans, [s["name"] for s in spans]
    assert any("executeMs" in s["tags"] for s in kernel_spans)
    assert any(
        "compileMs" in s["tags"] or s["tags"].get("cacheHit") is True
        for s in kernel_spans
    )
    # reservation write-back phase
    (writeback,) = by_name["reservation.writeback"]
    assert writeback["tags"]["app"] == "app-traced"
    # the predicate span carries the decision tags
    pred = by_name["predicate"][0]
    assert pred["tags"]["outcome"] == "success"
    assert pred["tags"]["node"] in ("n0", "n1", "n2")
    # durations are measured and nested spans are bounded by the root
    assert all(s["durationMs"] >= 0 for s in spans)
    assert trace["durationMs"] >= pred["durationMs"]

    # the same run exposes kernel metrics over valid Prometheus text
    status, headers, raw = _get_raw(
        http.port, "/metrics", {"Accept": "text/plain"}
    )
    assert status == 200
    text = raw.decode()
    assert "foundry_spark_scheduler_tpu_kernel_execute_time" in text
    # a miss, or a hit where an earlier test file of this process compiled the shape
    assert any(
        f"foundry_spark_scheduler_tpu_kernel_cache_{result}_count" in text for result in ("miss", "hit")
    )
    assert "foundry_spark_scheduler_trace_span_time" in text

    # the application_scheduled event carries the same trace id
    evts = scheduler.event_log.by_trace_id(trace["traceId"])
    assert any(e.name.endswith("application_scheduled") for e in evts)


def test_debug_schedule_endpoint(served_fifo):
    api, scheduler, http = served_fifo
    _create_nodes(api)
    driver_json, _ = _driver_pod_json("app-debug", executors=1)
    api.create(serde.pod_from_dict(driver_json))
    _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})

    pod_name = driver_json["metadata"]["name"]
    status, headers, raw = _get_raw(http.port, f"/debug/schedule/{pod_name}")
    assert status == 200
    text = raw.decode()
    assert "predicate" in text and "outcome=success" in text
    assert "reservation.writeback" in text
    # correlated events are appended
    assert "application_scheduled" in text

    status, _, _ = _get_raw(http.port, "/debug/schedule/no-such-pod")
    assert status == 404


def test_explain_endpoint_acceptance(served_fifo):
    """ISSUE 6 acceptance: GET /explain/<pod> returns the tightest-
    dimension shortfall + blocker fields for a refused driver, and the
    enriched /debug/schedule carries the provenance section."""
    api, scheduler, http = served_fifo
    _create_nodes(api)  # 2 nodes × 8 cpu
    # a gang that cannot fit: 8 executors × 4 cpu
    pods = Harness.static_allocation_spark_pods(
        "app-explain", 8, driver_cpu=2, executor_cpu=4,
        driver_mem="1Gi", executor_mem="1Gi",
    )
    driver_json = serde.pod_to_dict(pods[0])
    api.create(serde.pod_from_dict(driver_json))
    status, body = _post(
        http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]}
    )
    assert status == 200 and body.get("FailedNodes")

    pod_name = driver_json["metadata"]["name"]
    status, record = _get(http.port, f"/explain/{pod_name}")
    assert status == 200
    assert record["pod"] == pod_name
    assert record["outcome"] == "failure-fit"
    from k8s_spark_scheduler_tpu.native.fifo import native_explain_available

    if native_explain_available():
        sf = record["shortfall"]
        assert sf["tightestDimension"] == "cpu"
        assert sf["shortfallExecutors"] >= 1
        assert "blockedBy" in sf
        assert "short" in record["summary"]
        # the wire failure message carries the same actionable detail
        assert "short" in next(iter(body["FailedNodes"].values()))

    status, _ = _get(http.port, "/explain/no-such-pod")
    assert status == 404

    # /debug/schedule gains the provenance section
    status, _, raw = _get_raw(http.port, f"/debug/schedule/{pod_name}")
    assert status == 200
    assert "provenance:" in raw.decode()


def test_metrics_openmetrics_negotiation(served_fifo):
    """Satellite: the exemplar-carrying flavour is explicit opt-in
    (?format=openmetrics); EVERY Accept header keeps getting the plain
    0.0.4 text a Prometheus parser accepts — including a strict
    OpenMetrics-only scraper, whose parser would reject our pragmatic
    exemplar placement and fail the whole scrape."""
    api, scheduler, http = served_fifo
    _create_nodes(api)
    driver_json, _ = _driver_pod_json("app-om", executors=1)
    api.create(serde.pod_from_dict(driver_json))
    _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})

    # explicit opt-in: exemplars + # EOF + openmetrics content type
    status, headers, raw = _get_raw(http.port, "/metrics?format=openmetrics")
    assert status == 200
    assert headers.get("Content-Type").startswith("application/openmetrics-text")
    text = raw.decode()
    assert text.rstrip().endswith("# EOF")
    # the predicate's latency histogram carries its trace exemplar
    assert "schedule_time_count" in text
    assert 'trace_id="' in text

    # plain negotiation unchanged: no exemplars, no EOF
    status, headers, raw = _get_raw(
        http.port, "/metrics", {"Accept": "text/plain;version=0.0.4"}
    )
    assert status == 200 and headers.get("Content-Type").startswith("text/plain")
    plain = raw.decode()
    assert "trace_id" not in plain and "# EOF" not in plain

    # Accept headers NEVER negotiate the pragmatic flavour — a stock
    # dual-accept Prometheus and a strict OpenMetrics-only scraper both
    # get the plain 0.0.4 text their parsers accept
    for accept in (
        "application/openmetrics-text;version=1.0.0;q=0.5,"
        "text/plain;version=0.0.4;q=0.4",
        "application/openmetrics-text;version=1.0.0",
    ):
        status, headers, raw = _get_raw(http.port, "/metrics", {"Accept": accept})
        assert status == 200
        assert headers.get("Content-Type").startswith("text/plain")
        assert b"# EOF" not in raw and b"trace_id" not in raw


def test_capacity_endpoint_empty_cluster_and_latest(served):
    """ISSUE 7 satellite: /state/capacity answers 200 with a zeroed
    sample on an empty cluster, and a populated one after nodes exist;
    ?ns= scopes the queued-driver forecasts."""
    api, scheduler, http = served

    status, body = _get(http.port, "/state/capacity")
    assert status == 200
    assert body["nodes"] == 0 and body["readyNodes"] == 0
    assert body["free"] == [0, 0, 0]

    _create_nodes(api)
    time.sleep(0.2)  # informer events land in the mirror
    status, body = _get(http.port, "/state/capacity")
    assert status == 200
    assert body["nodes"] == 2 and body["readyNodes"] == 2
    assert body["free"][0] > 0
    assert len(body["fragIndex"]) == 3
    assert body["groups"], "per-(group, zone) entries missing"
    assert body["headroom"], "headroom-by-shape missing"
    for info in body["headroom"].values():
        assert info["headroom"] >= 0

    # a pending driver that cannot fit shows up in the queue forecast
    big = Harness.static_allocation_spark_pods(
        "app-cap-big", 8, executor_cpu="4", executor_mem="1Gi"
    )[0]
    api.create(big)
    _post(
        http.port, "/predicates",
        {"Pod": serde.pod_to_dict(big), "NodeNames": ["n0", "n1"]},
    )
    status, body = _get(http.port, "/state/capacity")
    assert status == 200
    assert body["queuedGangs"] == 1 and body["pressure"] == 1
    assert body["queue"][0]["pod"] == big.name
    assert body["queue"][0]["state"] == "needs-scaleup"

    # ns scoping filters the forecasts, not the cluster aggregates
    status, scoped = _get(http.port, "/state/capacity?ns=default")
    assert status == 200 and len(scoped["queue"]) == 1
    status, scoped = _get(http.port, "/state/capacity?ns=elsewhere")
    assert status == 200 and scoped["queue"] == []
    assert scoped["nodes"] == 2

    # group/zone scoping filters the per-group entries
    status, scoped = _get(http.port, "/state/capacity?zone=z1")
    assert status == 200 and len(scoped["groups"]) >= 1
    status, scoped = _get(http.port, "/state/capacity?zone=no-such-zone")
    assert status == 200 and scoped["groups"] == {}


def test_capacity_history_bounds_and_diff(served):
    api, scheduler, http = served
    _create_nodes(api)
    time.sleep(0.2)
    status, first = _get(http.port, "/state/capacity")
    assert status == 200

    # a node-structure change between samples
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import Resources, ZONE_LABEL

    api.create(
        Node(
            meta=ObjectMeta(
                name="n-extra",
                labels={ZONE_LABEL: "z2", "resource_channel": "batch-medium-priority"},
            ),
            allocatable=Resources.of("4", "4Gi"),
        )
    )
    time.sleep(0.2)
    status, second = _get(http.port, "/state/capacity")
    assert status == 200 and second["nodes"] == 3

    status, hist = _get(http.port, "/state/capacity/history?limit=1")
    assert status == 200 and len(hist["samples"]) == 1
    assert hist["samples"][0]["seq"] == second["seq"]
    status, hist = _get(http.port, "/state/capacity/history")
    assert status == 200
    assert len(hist["samples"]) <= hist["ringCapacity"]
    seqs = [s["seq"] for s in hist["samples"]]
    assert first["seq"] in seqs and second["seq"] in seqs

    status, diff = _get(
        http.port,
        f"/state/capacity/diff?from={first['seq']}&to={second['seq']}",
    )
    assert status == 200
    assert diff["structureChanged"] is True
    assert diff["nodes"] == 1
    assert "z2" in " ".join(diff["groupsAdded"])

    assert _get(http.port, "/state/capacity/diff?from=bad&to=1")[0] == 400
    assert _get(http.port, "/state/capacity/diff?from=999999&to=999998")[0] == 404


def test_capacity_gauges_render_in_plain_and_openmetrics(served_fifo):
    """Satellite: the new capacity gauges follow the PR 6 exposition
    rules — present in plain 0.0.4 text under every Accept header, and
    in the opt-in OpenMetrics flavour, which stays exemplar-valid."""
    api, scheduler, http = served_fifo
    _create_nodes(api)
    time.sleep(0.2)
    assert _get(http.port, "/state/capacity")[0] == 200  # forces a sample

    status, headers, raw = _get_raw(
        http.port, "/metrics", {"Accept": "text/plain;version=0.0.4"}
    )
    assert status == 200
    plain = raw.decode()
    assert "foundry_spark_scheduler_tpu_capacity_fragmentation" in plain
    assert "foundry_spark_scheduler_tpu_capacity_headroom" in plain
    assert 'dim="cpu"' in plain
    assert "# EOF" not in plain and "trace_id" not in plain

    status, headers, raw = _get_raw(http.port, "/metrics?format=openmetrics")
    assert status == 200
    assert headers.get("Content-Type").startswith("application/openmetrics-text")
    om = raw.decode()
    assert "foundry_spark_scheduler_tpu_capacity_fragmentation" in om
    assert om.rstrip().endswith("# EOF")

    # strict OpenMetrics Accept still gets plain text (PR 6 rule)
    status, headers, raw = _get_raw(
        http.port, "/metrics",
        {"Accept": "application/openmetrics-text;version=1.0.0"},
    )
    assert status == 200
    assert headers.get("Content-Type").startswith("text/plain")
    assert b"foundry_spark_scheduler_tpu_capacity_fragmentation" in raw


def test_traces_limit_param(served_fifo):
    api, scheduler, http = served_fifo
    _create_nodes(api)
    for i in range(3):
        driver_json, _ = _driver_pod_json(f"app-lim-{i}", executors=1)
        api.create(serde.pod_from_dict(driver_json))
        _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})
    status, body = _get(http.port, "/traces?limit=2")
    assert status == 200 and len(body["traces"]) == 2


def test_debug_criticalpath_endpoint(served_fifo):
    """ISSUE 11 satellite: /debug/criticalpath decomposes served
    requests into the named gating segments, and per-request records
    reconstruct the request total."""
    api, scheduler, http = served_fifo
    _create_nodes(api)
    for i in range(3):
        driver_json, _ = _driver_pod_json(f"app-cp-{i}", executors=1)
        api.create(serde.pod_from_dict(driver_json))
        _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})

    status, body = _get(http.port, "/debug/criticalpath")
    assert status == 200 and body["enabled"] is True
    assert body["requests"] >= 3 and body["window"] >= 3
    segs = body["segments"]
    for name in ("gate-queue", "lock-wait", "serde", "solve", "write-back", "other"):
        assert name in segs, segs.keys()
        assert segs[name]["p99Ms"] >= 0.0
    # the solver does the work in this configuration
    assert segs["solve"]["p50Ms"] > 0.0
    assert body["totalMs"]["p99"] > 0.0
    assert 0.0 <= body["coverage"]["p50"] <= 1.0
    assert body["dominant"], "dominant-segment counter empty"

    # per-request records: named segments reconstruct the request
    status, body = _get(http.port, "/debug/criticalpath?limit=2")
    assert status == 200 and len(body["recent"]) == 2
    for record in body["recent"]:
        total = record["totalMs"]
        assert total > 0.0
        reconstructed = sum(record["segments"].values())
        assert abs(reconstructed - total) / total < 0.10, record
        assert record["traceId"]


def test_debug_contention_endpoint(served_fifo):
    """ISSUE 11 satellite: /debug/contention serves per-lock wait/hold
    distributions with holder-phase attribution; ?lock= filters."""
    api, scheduler, http = served_fifo
    _create_nodes(api)
    driver_json, _ = _driver_pod_json("app-lock", executors=1)
    api.create(serde.pod_from_dict(driver_json))
    _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})

    status, body = _get(http.port, "/debug/contention")
    assert status == 200 and body["enabled"] is True
    locks = {entry["name"]: entry for entry in body["locks"]}
    assert "extender.predicate" in locks, sorted(locks)
    plock = locks["extender.predicate"]
    assert plock["acquisitions"] >= 1
    assert plock["sampleEvery"] == 1  # the predicate lock records every acquire
    assert plock["holdMs"]["count"] >= 1 and plock["holdMs"]["max"] > 0.0
    assert "http.request" in plock["byPhase"], plock["byPhase"]
    # the @guarded_by singletons are wrapped too (names = declaration site)
    assert any(name.endswith("._lock") for name in locks), sorted(locks)

    status, body = _get(http.port, "/debug/contention?lock=extender.predicate")
    assert status == 200
    assert [entry["name"] for entry in body["locks"]] == ["extender.predicate"]
    status, body = _get(http.port, "/debug/contention?lock=no-such-lock")
    assert status == 200 and body["locks"] == []


def test_contention_endpoints_empty_and_disabled(served):
    """Empty cluster: both endpoints answer 200 with empty-but-well-
    formed payloads.  A server wired with contention.enabled=false
    reports disabled instead of erroring."""
    _, _, http = served
    status, body = _get(http.port, "/debug/criticalpath")
    assert status == 200 and body["enabled"] is True
    assert body["requests"] == 0 and body["window"] == 0
    assert body["totalMs"]["p99"] == 0.0
    status, body = _get(http.port, "/debug/contention")
    assert status == 200 and body["enabled"] is True  # locks exist, idle

    from k8s_spark_scheduler_tpu.config import ContentionConfig

    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    scheduler = init_server_with_clients(
        api,
        Install(
            binpack_algo="tightly-pack",
            contention=ContentionConfig(enabled=False),
        ),
        demand_poll_interval=0.02,
    )
    http2 = ExtenderHTTPServer(scheduler, port=0)
    http2.start()
    try:
        status, body = _get(http2.port, "/debug/contention")
        assert status == 200 and body["enabled"] is False
        status, body = _get(http2.port, "/debug/criticalpath")
        assert status == 200 and body["enabled"] is False
    finally:
        http2.stop()
        scheduler.stop()


def test_contention_gauges_render_in_plain_and_openmetrics(served_fifo):
    """ISSUE 11 satellite: the new lock/criticalpath metrics follow the
    exposition rules — plain 0.0.4 text under every Accept header, and
    the opt-in OpenMetrics flavour stays well-formed."""
    api, scheduler, http = served_fifo
    _create_nodes(api)
    driver_json, _ = _driver_pod_json("app-lockmet", executors=1)
    api.create(serde.pod_from_dict(driver_json))
    _post(http.port, "/predicates", {"Pod": driver_json, "NodeNames": ["n0", "n1"]})
    # reading /debug/contention drains pending lock samples into the registry
    assert _get(http.port, "/debug/contention")[0] == 200

    status, headers, raw = _get_raw(
        http.port, "/metrics", {"Accept": "text/plain;version=0.0.4"}
    )
    assert status == 200
    plain = raw.decode()
    assert "foundry_spark_scheduler_tpu_lock_acquire_count" in plain
    assert "foundry_spark_scheduler_tpu_lock_hold_time" in plain
    assert 'lock="extender.predicate"' in plain
    assert "foundry_spark_scheduler_tpu_criticalpath_segment_time" in plain
    assert 'segment="solve"' in plain
    assert "# EOF" not in plain and "trace_id" not in plain

    status, headers, raw = _get_raw(http.port, "/metrics?format=openmetrics")
    assert status == 200
    assert headers.get("Content-Type").startswith("application/openmetrics-text")
    om = raw.decode()
    assert "foundry_spark_scheduler_tpu_lock_acquire_count" in om
    assert "foundry_spark_scheduler_tpu_criticalpath_segment_time" in om
    assert om.rstrip().endswith("# EOF")

    # strict OpenMetrics Accept still gets plain text (PR 6 rule)
    status, headers, raw = _get_raw(
        http.port, "/metrics",
        {"Accept": "application/openmetrics-text;version=1.0.0"},
    )
    assert status == 200
    assert headers.get("Content-Type").startswith("text/plain")
    assert b"foundry_spark_scheduler_tpu_lock_acquire_count" in raw
