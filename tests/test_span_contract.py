"""The span-name contract of the served path (ROADMAP "span names are a
contract"): which children a granted driver Filter leaves under
``predicate`` on each lane the CPU can serve, aggregate children, the
marker's one trace per scan, the profiler bridge, and the critical-path
decomposition over the new tree.  No wall-clock budgets (ROADMAP D10)."""

import glob
import json
import os
import time
import urllib.request

import pytest

from k8s_spark_scheduler_tpu import tracing
from k8s_spark_scheduler_tpu.contention import criticalpath
from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.tracing import Tracer
from k8s_spark_scheduler_tpu.types import serde

NODES = [f"n{i}" for i in range(6)]

# the driver's tree under ``predicate`` on the tensor-snapshot fast path,
# in order; a name maps to its children
HOST_LEAVES = (
    "fast_path.snapshot",
    "fast_path.queue_assemble",
    "fast_path.build_tensor",
    "fast_path.tensorize_apps",
    "fast_path.scale_problem",
)
FINISH = ("driver.finish", [("reservation.writeback", [("state.writeback.enqueue", [])])])
KERNEL_PHASES = [("device.dispatch", []), ("device.wait", [])]
EXPECTED = {
    "native": [
        *((name, []) for name in HOST_LEAVES),
        ("fifo_gate", [("kernel:fifo_queue", []), ("provenance.capture", [])]),
        ("binpack", [("kernel:solve_app", [])]),
        ("fast_path.decode", []),
        ("fast_path.efficiency", []),
        FINISH,
        ("provenance.finish", []),
    ],
    # the device lanes (the XLA scans, the Pallas kernels; plain and
    # min-frag alike) cross the boundary once each way: the queue pass and
    # the current driver's solve are one program, everything after the
    # read-back reads that one host copy
    "device": [
        *((name, []) for name in HOST_LEAVES),
        ("fifo_gate", [
            ("device.upload", []),  # a node-side and an app-side block
            ("kernel:fifo_queue", KERNEL_PHASES),
            ("device.readback", []),  # verdicts, the current driver's solve, the availability
            ("provenance.capture", []),
        ]),
        ("binpack", []),
        ("fast_path.decode", []),
        ("fast_path.efficiency", []),
        FINISH,
        ("provenance.finish", []),
    ],
}
DEVICE_LANES = {
    ("tpu-batch", "xla"): "xla",
    ("tpu-batch", "pallas"): "pallas",
    ("tpu-batch-minimal-fragmentation", "xla"): "minfrag-xla",
    ("tpu-batch-minimal-fragmentation", "pallas"): "pallas-minfrag",
}


# the same request under ``tpu-batch-single-az``: the queue pass's inputs
# go up in four arrays, the request's own app rides the pass as a probe
# (no second kernel), ``fifo_gate.zone_resolve`` is the valve's host time
# (an aggregate child, one phase per launch) and ``fast_path.zone_choice``
# the current driver's exact choice
SINGLE_AZ_TAIL = [
    ("binpack", [("fast_path.zone_choice", [])]),
    ("fast_path.decode", []),
    ("fast_path.efficiency", []),
    FINISH,
    ("provenance.finish", []),
]
EXPECTED_SINGLE_AZ = {
    "native": [
        *((name, []) for name in HOST_LEAVES),
        ("fifo_gate", [("kernel:fifo_queue_single_az", [])]),
        *SINGLE_AZ_TAIL,
    ],
    "xla": [
        *((name, []) for name in HOST_LEAVES),
        ("fifo_gate", [
            ("device.upload", []),  # what stays on the device between launches
            ("device.upload", []),  # the cluster's availability
            ("device.upload", []),  # forced zones and the start, per launch
            ("kernel:fifo_queue_single_az", KERNEL_PHASES),
            *[("device.readback", [])] * 5,  # the five verdict columns
            ("device.readback", []),  # the snapshots
            ("fifo_gate.zone_resolve", []),
        ]),
        *SINGLE_AZ_TAIL,
    ],
    "pallas": [
        *((name, []) for name in HOST_LEAVES),
        ("fifo_gate", [
            ("device.upload", []),  # availability and the per-node columns
            ("device.upload", []),  # the per-app columns and the scalars, per launch
            ("kernel:fifo_queue_single_az", KERNEL_PHASES),
            ("device.readback", []),  # the verdict columns
            ("device.readback", []),  # the snapshots
            ("fifo_gate.zone_resolve", []),
        ]),
        *SINGLE_AZ_TAIL,
    ],
}


# an executor's tree under ``predicate`` (``_select_executor_node``): the
# reservation look-ups are one aggregate child, a phase per look-up; an
# executor beyond its application's min is placed from the tensor mirror
# (``executor.fast_reschedule``) and recorded (``executor.soft_bind``);
# where the mirror's lane declines, or there is none, the Quantity path
# answers under a name of its own
LOOKUP = ("executor.reservation_lookup", [])
FAST = ("executor.fast_reschedule", [("executor.snapshot", []), ("executor.order", [])])
EXPECTED_EXECUTOR = {
    "reserved": [
        ("executor.select", [LOOKUP, ("state.writeback.enqueue", [])]),
        ("provenance.finish", []),
    ],
    "extra": [
        ("executor.select", [LOOKUP, FAST, ("executor.soft_bind", [])]),
        ("provenance.finish", []),
    ],
    "refused": [("executor.select", [LOOKUP]), ("provenance.finish", [])],
    "declined": [
        ("executor.select", [LOOKUP, FAST, ("executor.quantity_reschedule", []), ("executor.soft_bind", [])]),
        ("provenance.finish", []),
    ],
    "demoted": [
        ("executor.select", [LOOKUP, ("executor.quantity_reschedule", []), ("executor.soft_bind", [])]),
        ("provenance.finish", []),
    ],
}


def shape(span):
    return [(c.name, shape(c)) for c in span.children]


def names(span):
    out = [span.name]
    for child in span.children:
        out.extend(names(child))
    return out


def find(span, name):
    if span.name == name:
        return span
    for child in span.children:
        hit = find(child, name)
        if hit is not None:
            return hit
    return None


def served_harness(lane, binpack_algo="tpu-batch"):
    """The full wiring at a small size with a short pending queue, the
    warm delta-solve lane off so that ``solve_tensor`` serves, on the
    queue lane asked for."""
    h = Harness(binpack_algo=binpack_algo)
    for i, name in enumerate(NODES):
        h.new_node(name, zone=f"zone{1 + i % 2}" if "single-az" in binpack_algo else "zone1")
    h.extender.delta_engine = None
    h.extender.binpacker.queue_solver.backend = lane
    h.extender.binpacker.queue_solver.interpret = True  # the pallas lane on the CPU
    for i in range(3):
        queued = h.static_allocation_spark_pods(f"app-queued-{i}", 1)[0]
        queued.meta.creation_timestamp = time.time() - 100 + i
        h.create_pod(queued)
    return h


def roots_of(h):
    roots = []
    h.server.tracer.add_observer(roots.append)
    return roots


# -- (a) the documented children, once per lane --------------------------------


def granted_driver_root(h):
    # the first request of an idle server reconciles (and compiles)
    h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
    roots = roots_of(h)
    driver = h.static_allocation_spark_pods("app-new", 2)[0]
    h.assert_success(h.schedule(driver, NODES))
    (root,) = [r for r in roots if r.name == "predicate"]
    return root


def test_granted_driver_filter_has_exactly_the_documented_children_on_the_native_lane():
    h = served_harness("native")
    try:
        root = granted_driver_root(h)
        assert shape(root) == EXPECTED["native"]
        gate = find(root, "fifo_gate")
        assert gate.tags["lane"] == "native" and gate.tags["earlierApps"] == 3
        assert find(root, "fast_path.queue_assemble").tags["earlierApps"] == 3
        assert [n for n in names(root) if n.startswith("device.")] == []
    finally:
        h.close()


@pytest.mark.parametrize("binpack_algo,backend", sorted(DEVICE_LANES))
def test_granted_driver_filter_crosses_the_device_boundary_once_each_way(binpack_algo, backend):
    h = served_harness(backend, binpack_algo)
    try:
        root = granted_driver_root(h)
        assert shape(root) == EXPECTED["device"]
        gate = find(root, "fifo_gate")
        assert gate.tags["lane"] == DEVICE_LANES[binpack_algo, backend]
        assert gate.tags["earlierApps"] == 3 and gate.tags["earlierOk"] is True
        assert find(root, "kernel:fifo_queue").tags[mnames.TAG_LANE] == gate.tags["lane"]
        # the crossings of a request are these two tags: 2 arrays up, 1 down
        upload, readback = find(gate, "device.upload"), find(gate, "device.readback")
        assert upload.tags["arrays"] == 2 and upload.tags["bytes"] == 4 * (64 * 5 + 16 * 8)
        assert readback.tags == {"arrays": 1, "bytes": 4 * (4 * 64 + 16 + 2)}
        assert find(root, "binpack").tags["feasible"] is True
        device = [n for n in names(root) if n.startswith("device.")]
        assert sorted(device) == [
            "device.dispatch", "device.readback", "device.upload", "device.wait",
        ]
    finally:
        h.close()


@pytest.mark.parametrize(
    "binpack_algo",
    ["tpu-batch", "tpu-batch-distribute-evenly", "tpu-batch-minimal-fragmentation"],
)
def test_the_decode_says_what_it_built_in_tags_and_the_tree_stays(binpack_algo):
    """``fast_path.decode`` carries ``hostNodes`` (distinct nodes that
    received an executor) and ``objects`` (per-node Python objects the
    decode built): tags on a span the tree already had, no new child."""
    h = served_harness("native", binpack_algo)
    try:
        root = granted_driver_root(h)
        assert shape(root) == EXPECTED["native"]
        decode = find(root, "fast_path.decode")
        hosts = decode.tags["hostNodes"]
        assert hosts in (1, 2)  # the granted driver asked for two executors
        assert decode.tags == {"hostNodes": hosts, "objects": hosts}
    finally:
        h.close()


def dynamic_allocation_roots(h, min_count=1, max_count=3, app_id="app-da"):
    """A dynamic-allocation application's driver and all its executors,
    and one executor more than its max: {pod name: its ``predicate`` root}."""
    h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
    roots = roots_of(h)
    pods = h.dynamic_allocation_spark_pods(app_id, min_count, max_count)
    one_more = h.dynamic_allocation_spark_pods(app_id, min_count, max_count + 1)[-1]
    for pod in [*pods, one_more]:
        h.schedule(pod, NODES)
    return {r.tags["pod"]: r for r in roots if r.name == "predicate"}


@pytest.mark.parametrize("allocation", ["static", "dynamic"])
def test_a_driver_filter_has_the_tree_it_had_before_executors_were_named(allocation):
    """The executor spans (``executor.*``, ``da.compact``) are children of
    ``predicate`` on executor requests only: a driver's tree is the
    documented one, letter for letter, whether or not its application
    allocates dynamically, so ``lock_unnamed_ms`` (``predicate``'s self
    time on driver requests) reads what it read."""
    h = served_harness("native")
    try:
        if allocation == "static":
            root = granted_driver_root(h)
        else:
            root = dynamic_allocation_roots(h)["app-da-driver"]
        assert shape(root) == EXPECTED["native"]
        assert not [n for n in names(root) if n.startswith("executor.") or n == "da.compact"]
    finally:
        h.close()


def test_an_executor_filter_has_exactly_the_documented_children():
    h = served_harness("native")
    try:
        by_pod = dynamic_allocation_roots(h)
        reserved, extra, last_extra, refused = (by_pod[f"app-da-exec-{i}"] for i in (1, 2, 3, 4))
        assert shape(reserved) == EXPECTED_EXECUTOR["reserved"]
        assert find(reserved, "executor.reservation_lookup").tags == {"count": 2}  # already bound? unbound?
        assert shape(extra) == shape(last_extra) == EXPECTED_EXECUTOR["extra"]
        lookup = find(extra, "executor.reservation_lookup")
        assert type(lookup) is tracing.AggregateSpan and lookup.tags == {"count": 3}  # and the remaining count
        assert find(extra, "executor.fast_reschedule").tags == {"candidates": len(NODES), "hit": True}
        assert extra.tags["outcome"] == "success-scheduled-extra-executor"
        # max - min soft reservations are held: the next executor is refused before any placement
        assert shape(refused) == EXPECTED_EXECUTOR["refused"]
        assert refused.tags["outcome"] == "failure-unbound"
        metrics = h.server.metrics
        assert metrics.get_counter(mnames.SOFT_RESERVATION_BINDS) == 2
        assert metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "fast"}) == 2
        assert metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"}) == 0
    finally:
        h.close()


class _RescheduleLaneDemoted:
    """A lane-health table in which the mirror's executor lane is demoted."""

    def allow(self, lane):
        return lane != "tensor_reschedule"

    def __getattr__(self, name):
        return lambda *args, **kwargs: None


@pytest.mark.parametrize("why", ["declined", "demoted"])
def test_an_extra_executor_the_mirror_did_not_place_is_named_for_the_quantity_path(why):
    """The slow lane is no fallback (nothing raised, nothing is counted
    by ``host_fallbacks``): its name on the request is how a reader tells
    it from a mirror-served one."""
    h = served_harness("native")
    try:
        if why == "declined":
            h.extender._tensor_snapshot._exact = False  # as after a quantity the mirror cannot hold exactly
        else:
            h.extender._lane_health = _RescheduleLaneDemoted()
        extra = dynamic_allocation_roots(h)["app-da-exec-2"]
        assert shape(extra) == EXPECTED_EXECUTOR[why]
        assert find(extra, "executor.quantity_reschedule").tags == {"candidates": len(NODES)}
        assert extra.tags["outcome"] == "success-scheduled-extra-executor"
        if why == "declined":
            assert "hit" not in find(extra, "executor.fast_reschedule").tags
        assert h.extender.host_fallbacks() == 0
        assert h.server.metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"}) == 2
    finally:
        h.close()


def test_compaction_is_a_span_of_the_filter_that_found_something_to_compact():
    h = served_harness("native")
    try:
        pods = h.dynamic_allocation_spark_pods("app-da", 1, 3)
        for pod in pods:
            h.assert_success(h.schedule(pod, NODES))
        roots = roots_of(h)
        h.delete_pod(pods[1])  # the executor that held the hard slot
        replacement = h.dynamic_allocation_spark_pods("app-da", 1, 4)[4]
        h.assert_success(h.schedule(replacement, NODES))
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-after", 1)[0], NODES))
        first, after = [r for r in roots if r.name == "predicate"]
        assert [c.name for c in first.children] == ["da.compact", "executor.select", "provenance.finish"]
        compact = find(first, "da.compact")
        assert compact.tags["apps"] == 1 and 1 <= compact.tags["moved"] <= 2
        assert shape(compact) == [("state.writeback.enqueue", [])] * compact.tags["moved"]  # a slot's new binding each
        moved = sum(
            h.server.metrics.get_counter(mnames.SOFT_RESERVATION_COMPACTIONS, {"result": result})
            for result in ("same-node", "cross-node")
        )
        assert moved == compact.tags["moved"]
        assert find(after, "da.compact") is None  # nothing queued: no span
    finally:
        h.close()


@pytest.mark.parametrize("lane", ["native", "xla", "pallas"])
def test_single_az_driver_filter_has_exactly_the_documented_children(lane):
    h = served_harness(lane, "tpu-batch-single-az")
    try:
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
        roots = roots_of(h)
        driver = h.static_allocation_spark_pods("app-new", 2)[0]
        h.assert_success(h.schedule(driver, NODES))
        (root,) = [r for r in roots if r.name == "predicate"]
        assert shape(root) == EXPECTED_SINGLE_AZ[lane]
        gate = find(root, "fifo_gate")
        assert gate.tags["lane"] == lane and gate.tags["earlierApps"] == 3
        assert gate.tags["earlierOk"] is True
        if lane == "native":
            assert "launches" not in gate.tags
        else:
            assert gate.tags["launches"] == 1 and gate.tags["zoneResolved"] >= 0
            resolve = find(gate, "fifo_gate.zone_resolve")
            assert type(resolve) is tracing.AggregateSpan and resolve.tags["count"] == 1
            assert h.server.metrics.get_counter(
                mnames.FIFO_ZONE_CHOICE, {"result": "certified"}
            ) + h.server.metrics.get_counter(
                mnames.FIFO_ZONE_CHOICE, {"result": "resolved"}
            ) == 6  # every queued app of both requests, by who chose its zone
        assert find(root, "binpack").tags["lane"] == "host"
    finally:
        h.close()


@pytest.mark.parametrize("binpack_algo", ["tpu-batch", "tpu-batch-single-az"])
def test_spans_a_self_metric_reads_keep_no_child(binpack_algo):
    """``serde_ms``, ``executor_serde_ms`` and ``write_back_ms`` read the
    self time of these spans: a child under one would move an accepted
    metric."""
    h = served_harness("xla", binpack_algo)
    http = ExtenderHTTPServer(h.server, port=0)
    http.start()
    try:
        post_driver(h, http.port, "app-first")
        roots = roots_of(h)
        post_driver(h, http.port, "app-http")
        (root,) = [r for r in roots if r.name == "http.request"]
        assert [c.name for c in root.children] == [
            "http.read", "serde.decode", "predicate", "serde.encode", "http.write",
        ]
        for name in ("http.read", "serde.decode", "serde.encode", "state.writeback.enqueue"):
            assert find(root, name).children == [], name
        assert [c.name for c in find(root, "reservation.writeback").children] == [
            "state.writeback.enqueue"
        ]
        assert root.tags["status"] == 200 and "status" not in find(root, "http.write").tags
    finally:
        http.stop()
        h.close()


# -- (b) aggregate children ----------------------------------------------------


def test_aggregate_child_sums_its_phases_and_keeps_the_parents_self_time(monkeypatch):
    from k8s_spark_scheduler_tpu import timesource

    clock = [0.0]
    monkeypatch.setattr(timesource, "perf", lambda: clock[0])
    tracer = Tracer(capacity=4)
    with tracer.span("unschedulable.scan") as root:
        for _ in range(3):
            clock[0] += 1.0  # the parent's own time
            with root.aggregate("scan.solve"):
                assert tracing.current_span() is None  # what a phase calls opens no children
                assert tracing.child_span("kernel:x") is tracing.NOOP_SPAN
                clock[0] += 0.25
            assert tracing.current_span() is root
        with tracing.aggregate_span("scan.mark"):
            clock[0] += 0.5
    assert [c.name for c in root.children] == ["scan.solve", "scan.mark"]
    solve, mark = root.children
    assert solve.duration == pytest.approx(0.75) and solve.tags["count"] == 3
    assert mark.duration == pytest.approx(0.5) and mark.tags["count"] == 1
    assert root.duration == pytest.approx(4.25)
    assert root.duration - sum(c.duration for c in root.children) == pytest.approx(3.0)
    as_dict = tracer.traces()[0]["root"]
    assert [(c["name"], c["durationMs"], c["tags"]) for c in as_dict["children"]] == [
        ("scan.solve", 750.0, {"count": 3}),
        ("scan.mark", 500.0, {"count": 1}),
    ]
    assert all(c["parentId"] == as_dict["spanId"] for c in as_dict["children"])


def test_aggregate_phase_outside_any_trace_is_the_noop():
    assert tracing.aggregate_span("scan.solve") is tracing.NOOP_SPAN


# -- (c) the marker's scan -----------------------------------------------------


def aged_backlog(h, fitting=3, oversized=1):
    for i in range(fitting):
        pod = h.static_allocation_spark_pods(f"app-aged-{i}", 1 + i)[0]
        pod.meta.creation_timestamp = time.time() - 3600
        h.create_pod(pod)
    for i in range(oversized):
        pod = h.static_allocation_spark_pods(f"app-huge-{i}", 100)[0]
        pod.meta.creation_timestamp = time.time() - 3600
        h.create_pod(pod)


@pytest.mark.parametrize("lane", ["native", "xla"])
def test_one_scan_is_one_trace_with_three_aggregate_children_and_two_metrics(lane, monkeypatch):
    """One signature: one metadata build, ONE batch of verdicts (a device
    round on the XLA lane, whose upload and read-back open no span under
    the aggregate: its crossings are the aggregate's tags), a mark per
    pod."""
    from k8s_spark_scheduler_tpu.ops import fifo_solver

    if lane == "xla":  # as on a host with neither a TPU nor the C++ library
        monkeypatch.setattr(fifo_solver, "_native_selected", lambda backend: False)
    h = Harness(binpack_algo="tpu-batch")
    try:
        for name in NODES:
            h.new_node(name)
        aged_backlog(h)
        roots = roots_of(h)
        h.unschedulable_marker.scan_for_unschedulable_pods()
        (root,) = roots
        assert root.name == "unschedulable.scan" and root.parent is None
        assert root.tags == {
            "pods": 4, "verdictMisses": 4, "verdictBatches": 1, "signatures": 1, "conditionWrites": 4,
        }
        children = {c.name: c for c in root.children}
        assert {name: c.tags["count"] for name, c in children.items()} == {
            "scan.metadata": 1, "scan.solve": 1, "scan.mark": 4,
        }
        crossings = {k: v for k, v in children["scan.solve"].tags.items() if k != "count"}
        # the node block [64, 6], the app block [1024, 8] up, [1024] down, int32
        assert crossings == ({"arrays": 3, "bytes": 4 * (64 * 6 + 1024 * 9)} if lane == "xla" else {})
        assert all(type(c) is tracing.AggregateSpan and not c.children for c in root.children)
        assert sum(c.duration for c in root.children) <= root.duration
        metrics = h.server.metrics
        assert metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "tensor"}) == 4
        assert metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "host"}) == 0
        snapshot = json.dumps(metrics.snapshot())
        assert mnames.UNSCHEDULABLE_SCAN_TIME in snapshot
        # a second scan finds the conditions set: verdicts again, no writes
        h.unschedulable_marker.scan_for_unschedulable_pods()
        assert roots[1].tags["conditionWrites"] == 0 and roots[1].tags["verdictBatches"] == 1
        assert "scan.mark" not in [c.name for c in roots[1].children]
    finally:
        h.close()


def test_a_scan_under_a_host_policy_asks_no_batch():
    """No tensor solver: every verdict is a full pack on the host, one
    ``scan.solve`` phase per signature all the same."""
    h = Harness(binpack_algo="tightly-pack")
    try:
        for name in NODES:
            h.new_node(name)
        aged_backlog(h)
        roots = roots_of(h)
        h.unschedulable_marker.scan_for_unschedulable_pods()
        (root,) = roots
        assert root.tags == {
            "pods": 4, "verdictMisses": 4, "verdictBatches": 0, "signatures": 1, "conditionWrites": 4,
        }
        assert {c.name: c.tags for c in root.children}["scan.solve"] == {"count": 1}
        assert h.server.metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "host"}) == 4
    finally:
        h.close()


def test_a_root_that_is_no_request_is_ignored_by_the_request_shaped_consumers():
    h = Harness(binpack_algo="tpu-batch")
    try:
        for name in NODES:
            h.new_node(name)
        aged_backlog(h, fitting=1, oversized=0)
        roots = roots_of(h)
        h.unschedulable_marker.scan_for_unschedulable_pods()
        (root,) = roots
        assert root.name not in tracing.REQUEST_ROOTS
        assert criticalpath.decompose(root) is None
        assert h.server.tracer.find_by_tag("pod", "app-aged-0-driver") is None
        ledger = h.server.lifecycle
        latencies = []
        observe = ledger._slo.observe

        def recording(name, value, **kwargs):
            if name == "filter_latency":
                latencies.append(value)
            return observe(name, value, **kwargs)

        ledger._slo.observe = recording
        ledger._drain_traces()
        assert latencies == []  # the scan is no Filter's latency
        driver = h.static_allocation_spark_pods("app-after-scan", 1)[0]
        h.assert_success(h.schedule(driver, NODES))
        ledger._drain_traces()
        assert len(latencies) == 1
    finally:
        h.close()


# -- (d) the profiler bridge ---------------------------------------------------


def post_driver(h, port, app_id):
    """One driver Filter over the wire; the pod exists in the cluster
    before kube-scheduler asks, as it does there."""
    created = h.create_pod(h.static_allocation_spark_pods(app_id, 1)[0])
    assert h.wait_for_api(lambda: h.server.pod_informer.get(created.namespace, created.name) is not None)
    body = {"Pod": serde.pod_to_dict(created), "NodeNames": NODES}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predicates", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        answer = json.loads(resp.read())
    assert answer.get("NodeNames"), answer
    return answer


def test_spans_lie_on_the_profilers_clock_only_while_a_session_is_active(tmp_path):
    import jax
    from jax.profiler import ProfileData

    h = served_harness("xla")
    http = ExtenderHTTPServer(h.server, port=0)
    http.start()
    try:
        post_driver(h, http.port, "app-warm")  # compiles outside the session
        before = tracing.annotations_built()
        post_driver(h, http.port, "app-unprofiled")
        assert tracing.annotations_built() == before  # no session: nothing is built

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("client.filter_driver"):
                post_driver(h, http.port, "app-profiled")
        finally:
            jax.profiler.stop_trace()
        assert tracing.annotations_built() > before
        built = tracing.annotations_built()
        post_driver(h, http.port, "app-after")
        assert tracing.annotations_built() == built
    finally:
        http.stop()
        h.close()

    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    by_line = {}
    client = client_line = None
    for plane in ProfileData.from_file(path).planes:
        for index, line in enumerate(plane.lines):  # one line per thread; names repeat
            for ev in line.events:
                if ev.name.startswith(tracing.spans.PROFILER_PREFIX):
                    by_line.setdefault((plane.name, index), {}).setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
                elif ev.name == "client.filter_driver":
                    client, client_line = (ev.start_ns, ev.start_ns + ev.duration_ns), (plane.name, index)
    assert len(by_line) == 1, sorted(by_line)  # the handler's thread, and no other
    ((plane_name, handler_line), sched), = by_line.items()
    assert plane_name.startswith("/host:CPU") and (plane_name, handler_line) != client_line

    def inside(inners, outer):
        return any(outer[0] <= a and b <= outer[1] for a, b in inners)

    chain = ["sched.http.request", "sched.predicate", "sched.fifo_gate", "sched.device.upload"]
    for outer, inner in zip(chain, chain[1:]):
        (interval,) = sched[outer]
        assert inside(sched[inner], interval), (outer, inner)
    assert len(sched["sched.device.upload"]) == 1  # one round: both blocks together
    assert inside(sched["sched.http.request"], client)  # the same clock as the client's annotation
    assert {"sched.device.wait", "sched.device.readback", "sched.driver.finish"} <= set(sched)


def test_the_accepted_trace_reducer_does_not_see_the_bridge(tmp_path):
    """``benchmarks/trace_reduce.py:load_events`` keeps device planes and
    ``client.*`` names only."""
    import sys

    import jax

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import trace_reduce
    finally:
        sys.path.remove(bench)
    tracer = Tracer(capacity=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("client.filter_driver"):
            with tracer.span("http.request"):
                with tracer.span("predicate"):
                    pass
    finally:
        jax.profiler.stop_trace()
    assert [e["name"] for e in trace_reduce.load_events(str(tmp_path))] == ["client.filter_driver"]


# -- (e) one decomposition, two readers ----------------------------------------


def test_decompose_on_the_new_tree_sums_to_the_root_and_leaves_nothing_new_in_other():
    h = served_harness("xla")
    http = ExtenderHTTPServer(h.server, port=0)
    http.start()
    try:
        post_driver(h, http.port, "app-first")
        roots = roots_of(h)
        post_driver(h, http.port, "app-decomposed")
    finally:
        http.stop()
        h.close()
    (root,) = [r for r in roots if r.name == "http.request"]
    record = criticalpath.decompose(root)
    assert sum(record["segments"].values()) == pytest.approx(record["totalMs"], abs=0.01)
    for segment in ("assemble", "upload", "device-wait", "readback", "finish", "solve", "serde", "write-back"):
        assert record["segments"][segment] > 0.0, segment
    # ``other`` is the root's own time alone (less the measured waits): every
    # span of the new tree is classified, or inherits a classified ancestor's segment
    root_self = (root.duration - sum(c.duration for c in root.children)) * 1e3
    waits = float(root.tags.get("gateWaitMs") or 0.0) + float(root.tags.get("lockWaitMs") or 0.0)
    assert record["segments"]["other"] == pytest.approx(max(root_self - waits, 0.0), abs=0.01)
    assert set(record["segments"]) == set(criticalpath.SEGMENT_NAMES)
    assert all(c.name in criticalpath.SPAN_SEGMENTS for c in root.children)
    assert all(
        name in criticalpath.SPAN_SEGMENTS or name.startswith("kernel:") or name == "device.dispatch"
        for name in names(find(root, "predicate"))
    )
