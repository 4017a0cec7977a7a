"""The span-name contract of the served path (ROADMAP "span names are a
contract"): which children a granted driver Filter leaves under
``predicate`` on each lane the CPU can serve, aggregate children, the
marker's one trace per scan, the profiler bridge, and the critical-path
decomposition over the new tree.  No wall-clock budgets (ROADMAP D10)."""

import contextlib
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
from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL

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
    # single-AZ min-frag with single-AZ dynamic allocation on: the zone of the
    # application's running pods first, and the min-frag choice's keys (what
    # the application holds, each node's room) inside the order
    "extra-single-az-minfrag": [
        ("executor.select", [
            LOOKUP,
            ("executor.common_zone", []),
            ("executor.fast_reschedule", [
                ("executor.snapshot", []),
                ("executor.order", [("executor.app_attraction", [])]),
            ]),
            ("executor.soft_bind", []),
        ]),
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


# what a span may say of the runtime at its exit, beside what its call site tags
RUNTIME_TAGS = ("cpuMs", "gcMs", "gcRuns", "bg")


def own(tags):
    """A span's tags less the runtime's."""
    return {k: v for k, v in tags.items() if k not in RUNTIME_TAGS}


# the overhead fold lies under whichever snapshot first follows an
# event that marked a pod slot: a request's, or the
# capacity sampler's on its own thread; the trees are compared without
# it, and ``test_an_overhead_refresh_*`` pins where it lies
OVERHEAD_SPAN = "mirror.overhead"


def shape(span):
    return [(c.name, shape(c)) for c in span.children if c.name != OVERHEAD_SPAN]


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


def served_harness(lane, binpack_algo="tpu-batch", **harness_options):
    """The full wiring at a small size with a short pending queue, the
    warm delta-solve lane off so that ``solve_tensor`` serves, on the
    queue lane asked for."""
    h = Harness(binpack_algo=binpack_algo, **harness_options)
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
    """The roots the server's tracer finishes from here on, less the
    capacity sampler's: its thread samples after any node or
    reservation change, whenever the debounce lets it."""
    roots = []
    h.server.tracer.add_observer(lambda root: root.name == "capacity.sample" or roots.append(root))
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
        assert own(readback.tags) == {"arrays": 1, "bytes": 4 * (4 * 64 + 16 + 2)}
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
        assert own(decode.tags) == {"hostNodes": hosts, "objects": hosts}
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
        assert own(find(reserved, "executor.reservation_lookup").tags) == {"count": 2}  # already bound? unbound?
        assert shape(extra) == shape(last_extra) == EXPECTED_EXECUTOR["extra"]
        lookup = find(extra, "executor.reservation_lookup")
        assert type(lookup) is tracing.AggregateSpan and own(lookup.tags) == {"count": 3}  # and the remaining count
        assert own(find(extra, "executor.fast_reschedule").tags) == {"candidates": len(NODES), "hit": True}
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


@pytest.mark.parametrize(
    "binpack_algo,same_az,expected",
    [
        ("tpu-batch", False, "extra"),  # fifo10k-dynalloc's install
        ("tpu-batch-single-az-minimal-fragmentation", True, "extra-single-az-minfrag"),
    ],
)
def test_an_extra_executor_names_its_zone_and_its_applications_nodes_where_the_install_asks(
    binpack_algo, same_az, expected
):
    h = served_harness("native", binpack_algo, dynamic_allocation_single_az=same_az)
    try:
        extra = dynamic_allocation_roots(h)["app-da-exec-2"]
        assert shape(extra) == EXPECTED_EXECUTOR[expected]
        fast = find(extra, "executor.fast_reschedule")
        if not same_az:
            assert not {"executor.common_zone", "executor.app_attraction"} & set(names(extra))
            assert own(fast.tags) == {"candidates": len(NODES), "hit": True}
            return
        # the driver and the executor at min run, in one zone: the extra is held to it
        assert own(find(extra, "executor.common_zone").tags) == {"pods": 2, "zones": 1}
        driver_node = h.get_resource_reservation("app-da").spec.reservations["driver"].node
        zone = h.api.get("Node", "default", driver_node).labels[ZONE_LABEL]
        assert own(fast.tags) == {"candidates": len(NODES), "hit": True, "zone": zone}
        keys = own(find(extra, "executor.app_attraction").tags)
        assert keys["appNodes"] == 1 and 1 <= keys["fitting"] <= len(NODES)
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
        assert own(find(extra, "executor.quantity_reschedule").tags) == {"candidates": len(NODES)}
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
            assert gate.tags["zoneUnmemoised"] == 0  # the tightly-pack choice keeps its evidence
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
    assert [(c["name"], c["durationMs"], own(c["tags"])) for c in as_dict["children"]] == [
        ("scan.solve", 750.0, {"count": 3}),
        ("scan.mark", 500.0, {"count": 1}),
    ]
    assert all(c["parentId"] == as_dict["spanId"] for c in as_dict["children"])


def test_aggregate_phase_outside_any_trace_is_the_noop():
    assert tracing.aggregate_span("scan.solve") is tracing.NOOP_SPAN
    # and so is a phase's own aggregate child (``scan.overhead``)
    with tracing.aggregate_span("scan.metadata") as phase:
        assert phase.aggregate("scan.overhead") is tracing.NOOP_SPAN


def test_an_aggregate_child_of_a_phase_sums_its_own_phases_inside_the_parents(monkeypatch):
    """``scan.overhead`` inside ``scan.metadata``: the inner aggregate's
    time is part of the outer's, the root's self time and the outer's
    count are what they were without it."""
    from k8s_spark_scheduler_tpu import timesource

    clock = [0.0]
    monkeypatch.setattr(timesource, "perf", lambda: clock[0])
    tracer = Tracer(capacity=4)
    with tracer.span("unschedulable.scan") as root:
        for _ in range(2):
            clock[0] += 1.0
            with tracing.aggregate_span("scan.metadata") as phase:
                clock[0] += 0.5
                with phase.aggregate("scan.overhead"):
                    assert tracing.current_span() is None
                    clock[0] += 0.25
    assert shape(root) == [("scan.metadata", [("scan.overhead", [])])]
    (metadata,) = root.children
    (walk,) = metadata.children
    assert metadata.duration == pytest.approx(1.5) and metadata.tags["count"] == 2
    assert walk.duration == pytest.approx(0.5) and walk.tags["count"] == 2
    assert root.duration - metadata.duration == pytest.approx(2.0)
    as_dict = tracer.traces()[0]["root"]["children"][0]["children"][0]
    assert (as_dict["name"], as_dict["durationMs"], own(as_dict["tags"])) == ("scan.overhead", 500.0, {"count": 2})


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
        assert own(root.tags) == {
            "pods": 4, "verdictMisses": 4, "verdictBatches": 1, "signatures": 1, "conditionWrites": 4,
        }
        children = {c.name: c for c in root.children}
        assert {name: c.tags["count"] for name, c in children.items()} == {
            "scan.metadata": 1, "scan.solve": 1, "scan.mark": 4,
        }
        crossings = {k: v for k, v in own(children["scan.solve"].tags).items() if k != "count"}
        # the node block [64, 6], the app block [1024, 8] up, [1024] down, int32
        assert crossings == ({"arrays": 3, "bytes": 4 * (64 * 6 + 1024 * 9)} if lane == "xla" else {})
        assert all(type(c) is tracing.AggregateSpan for c in root.children)
        # the walk over the bound pods is the metadata's own aggregate child
        assert shape(root) == [
            ("scan.metadata", [("scan.overhead", [])]), ("scan.solve", []), ("scan.mark", []),
        ]
        walk = children["scan.metadata"].children[0]
        assert type(walk) is tracing.AggregateSpan and own(walk.tags) == {"count": 1}
        assert walk.duration <= children["scan.metadata"].duration
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
        assert own(root.tags) == {
            "pods": 4, "verdictMisses": 4, "verdictBatches": 0, "signatures": 1, "conditionWrites": 4,
        }
        assert {c.name: own(c.tags) for c in root.children}["scan.solve"] == {"count": 1}
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
    # the overhead refresh is its snapshot's: it inherits ``assemble``
    assert all(
        name in criticalpath.SPAN_SEGMENTS or name.startswith("kernel:")
        or name in ("device.dispatch", OVERHEAD_SPAN)
        for name in names(find(root, "predicate"))
    )


# -- (g) the mirror's overhead refresh and the gate's ``overheadRows`` --------------


def parents_of(span, name, parent=None):
    """The names of the spans whose child ``name`` is, one per occurrence."""
    found = [parent.name] if span.name == name and parent is not None else []
    for child in span.children:
        found.extend(parents_of(child, name, span))
    return found


def quiet_driver_root(h, stale):
    """A granted driver's ``predicate`` root, asked once the cluster is
    quiet (write-backs drained, the capacity sampler stopped, the mirror
    snapshotted): ``stale``, a bound pod no reservation holds appears
    just before the request, so the request's snapshot finds the
    overhead stale; otherwise nothing changes and it finds it current.
    Returns (root, the pod slots the mirror has to fold at the request:
    the daemon's alone, as the quiet snapshot folded the granted
    driver's)."""
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
    h.server.capacity.stop()
    assert h.wait_quiesced()
    mirror = h.server.tensor_snapshot
    mirror.snapshot()
    if stale:
        h.create_pod(Pod(
            meta=ObjectMeta(name="daemon-n0", namespace="kube-system"),
            scheduler_name="default-scheduler", node_name=NODES[0],
            containers=[Container("agent", Resources.of("100m", "128Mi"))], phase=PodPhase.RUNNING,
        ))
    daemon = {mirror._pod_slot[("kube-system", "daemon-n0")]} if stale else set()
    assert mirror._dirty_pods == daemon
    rows = len(daemon)
    roots = roots_of(h)
    h.assert_success(h.schedule(h.static_allocation_spark_pods("app-new", 2)[0], NODES))
    (root,) = [r for r in roots if r.name == "predicate"]
    return root, rows


@pytest.mark.parametrize("lane", ["native", "xla"])
def test_an_overhead_refresh_is_a_child_of_the_snapshot_that_ran_it_and_the_gate_counts_its_rows(lane):
    h = served_harness(lane)
    try:
        root, rows = quiet_driver_root(h, stale=True)
        assert parents_of(root, OVERHEAD_SPAN) == ["fast_path.snapshot"]
        refresh = find(root, OVERHEAD_SPAN)
        assert own(refresh.tags) == {"rows": rows} and rows == 1  # the daemon's pod, not every active row
        assert find(root, "fifo_gate").tags["overheadRows"] == rows
        assert shape(root) == EXPECTED["native" if lane == "native" else "device"]
    finally:
        h.close()


@pytest.mark.parametrize("lane", ["native", "xla"])
def test_a_snapshot_that_finds_the_overhead_current_opens_no_refresh_and_the_gate_reads_zero(lane):
    h = served_harness(lane)
    try:
        root, _ = quiet_driver_root(h, stale=False)
        assert OVERHEAD_SPAN not in names(root)
        assert find(root, "fifo_gate").tags["overheadRows"] == 0
    finally:
        h.close()


def test_an_extra_executors_refresh_lies_under_its_own_snapshot_and_nowhere_else():
    """A soft reservation makes the overhead stale: the next extra
    executor's ``executor.snapshot`` (or the sampler's) refreshes it;
    no refresh is a child of ``predicate``, whose self time a metric reads."""
    h = served_harness("native")
    try:
        by_pod = dynamic_allocation_roots(h)
        for pod, root in by_pod.items():
            assert set(parents_of(root, OVERHEAD_SPAN)) <= {"fast_path.snapshot", "executor.snapshot"}, pod
            for refresh in (s for s in [find(root, OVERHEAD_SPAN)] if s is not None):
                assert set(own(refresh.tags)) == {"rows"}
    finally:
        h.close()


def test_the_capacity_samplers_refresh_is_a_child_of_its_sample():
    h = served_harness("native")
    try:
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
        h.server.capacity.stop()
        assert h.wait_quiesced()
        samples = []
        h.server.tracer.add_observer(lambda root: root.name == "capacity.sample" and samples.append(root))
        mirror = h.server.tensor_snapshot
        driver = mirror._pod_slot[("default", h.static_allocation_spark_pods("app-first", 1)[0].name)]
        h.delete_pod(h.static_allocation_spark_pods("app-first", 1)[0])  # the reservation goes with it
        assert h.wait_for_api(lambda: h.get_resource_reservation("app-first") is None)
        assert mirror._dirty_pods == {driver}  # the reservation named only the deleted pod
        h.server.capacity.sample_now(tracer=h.server.tracer)
        (sample,) = samples
        assert [c.name for c in sample.children] == [OVERHEAD_SPAN]
        assert own(sample.children[0].tags)["rows"] == 1  # the driver's slot, not every active row
    finally:
        h.close()


# -- (f) what a span says of the runtime: cpuMs, gcMs / gcRuns, bg -------------------


def spans_of(span):
    yield span
    for child in span.children:
        yield from spans_of(child)


def dict_spans(span):
    yield span
    for child in span.get("children", ()):
        yield from dict_spans(child)


CLOCK_TOLERANCE_MS = 0.05  # two clocks; the CPU's is read inside the wall's interval


@pytest.mark.parametrize("kind", ["driver-xla", "driver-native", "executor"])
def test_the_gate_alone_reads_its_threads_cpu_clock_in_a_real_trace(kind):
    """``cpuMs`` stands where a call site asked for it (``cpu=True``):
    on ``fifo_gate``, whatever the lane, and on no other span of a
    request; an executor's trace has no gate and reads the clock never."""
    h = served_harness("native" if kind == "driver-native" else "xla")
    try:
        if kind == "executor":
            root = dynamic_allocation_roots(h)["app-da-exec-2"]
        else:
            root = granted_driver_root(h)
        spans = list(spans_of(root))
        assert len(spans) >= 8
        assert [s.name for s in spans if "cpuMs" in s.tags] == ([] if kind == "executor" else ["fifo_gate"])
        for span in spans:
            if "cpuMs" in span.tags:
                assert 0.0 <= span.tags["cpuMs"] <= span.duration * 1e3 + CLOCK_TOLERANCE_MS
    finally:
        h.close()


def test_no_span_carries_cpu_time_or_the_collectors_under_a_virtual_clock():
    import gc

    from k8s_spark_scheduler_tpu import timesource

    tracing.install_gc_hook()
    h = served_harness("native")
    try:
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-first", 1)[0], NODES))
        roots = roots_of(h)
        started = time.time()
        timesource.set_source(lambda: started)
        timesource.set_perf_source(lambda: 5.0)
        try:
            h.assert_success(h.schedule(h.static_allocation_spark_pods("app-virtual", 2)[0], NODES))
            with h.server.tracer.span("predicate"):
                with tracing.aggregate_span("phases"):
                    gc.collect()
        finally:
            timesource.reset()
        for root in roots:
            for span in spans_of(root):
                assert not set(span.tags) & {"cpuMs", "gcMs", "gcRuns"}, (span.name, span.tags)
                assert span.duration == 0.0
        assert len(roots) == 2 and len(list(spans_of(roots[0]))) >= 8
    finally:
        h.close()


def test_a_span_that_sleeps_reads_wall_far_above_cpu_and_one_that_spins_reads_its_cpu():
    tracer = Tracer(capacity=4)
    with tracer.span("predicate", cpu=True) as root:
        with tracer.span("sleeps", cpu=True) as sleeps:
            time.sleep(0.05)
        with tracing.child_span("spins", cpu=True) as spins:
            until = time.thread_time() + 0.03
            while time.thread_time() < until:
                pass
    assert sleeps.duration * 1e3 >= 50.0 and sleeps.tags["cpuMs"] < 10.0
    # every millisecond the thread burnt is the span's, and no more than the wall's
    assert 30.0 <= spins.tags["cpuMs"] <= spins.duration * 1e3 + CLOCK_TOLERANCE_MS
    assert root.tags["cpuMs"] >= sleeps.tags["cpuMs"] + spins.tags["cpuMs"]
    assert root.duration * 1e3 - root.tags["cpuMs"] >= 40.0  # what the thread did not run
    as_dict = tracer.traces()[0]["root"]
    assert [c["tags"]["cpuMs"] for c in as_dict["children"]] == [sleeps.tags["cpuMs"], spins.tags["cpuMs"]]


def test_the_thread_clock_is_read_only_where_a_call_site_asks_and_never_by_an_aggregate_phase(monkeypatch):
    """The clock can be a system call (20 µs a read on the benchmark's
    host): a span that did not ask makes none, and the marker's thousand
    phases make none."""
    reads = []
    cpu_ns = [0]

    def thread_ns():
        reads.append(tracing.current_span().name)
        return cpu_ns[0]

    monkeypatch.setattr(tracing.spans, "_thread_ns", thread_ns)
    tracer = Tracer(capacity=4)
    with tracer.span("unschedulable.scan") as root:
        for _ in range(3):
            with root.aggregate("scan.solve"):
                cpu_ns[0] += 250_000
        with tracing.aggregate_span("scan.mark"):
            pass
        with tracer.span("plain"):
            with tracing.child_span("asks", {"lane": "xla"}, cpu=True) as asks:
                cpu_ns[0] += 1_500_000
            with tracing.child_span("plain-too"):
                pass
    assert reads == ["asks", "asks"]
    assert asks.tags == {"lane": "xla", "cpuMs": 1.5}
    assert [s.name for s in spans_of(root) if "cpuMs" in s.tags] == ["asks"]
    assert {c.name: c.tags for c in root.children[:2]} == {"scan.solve": {"count": 3}, "scan.mark": {"count": 1}}


def test_a_collection_is_booked_to_the_span_it_ran_in_and_to_its_ancestors_and_to_no_other_thread():
    import gc
    import threading

    from k8s_spark_scheduler_tpu.metrics.registry import MetricsRegistry

    tracing.install_gc_hook()
    tracing.install_gc_hook()  # once, however often a process wires a server
    assert gc.callbacks.count(tracing.spans._on_gc) == 1
    tracer = Tracer(capacity=8)
    inside, release = threading.Event(), threading.Event()

    def other_thread():
        with tracer.span("http.request"):
            inside.set()
            release.wait(10.0)

    other = threading.Thread(target=other_thread)
    gc.disable()  # only the collections the test asks for
    try:
        other.start()
        assert inside.wait(10.0)
        with tracer.span("http.request") as root:
            with tracer.span("predicate") as predicate:
                with tracer.span("before"):
                    pass
                with tracer.span("collects") as collects:
                    gc.collect()
                    gc.collect(0)
                with predicate.aggregate("phases") as phases:
                    gc.collect()
                with predicate.aggregate("phases"):
                    pass
        release.set()
        other.join(10.0)
        assert not other.is_alive()
    finally:
        release.set()
        gc.enable()
    assert collects.tags["gcRuns"] == 2 and 0.0 < collects.tags["gcMs"] <= collects.duration * 1e3
    assert phases.tags["gcRuns"] == 1 and phases.tags["count"] == 2
    for ancestor in (predicate, root):
        assert ancestor.tags["gcRuns"] == 3
        assert ancestor.tags["gcMs"] == pytest.approx(collects.tags["gcMs"] + phases.tags["gcMs"], abs=1e-3)
    assert "gcMs" not in find(root, "before").tags and "gcRuns" not in find(root, "before").tags
    theirs, ours = (t["root"] for t in tracer.traces())  # newest first: theirs closed at the release
    assert ours["tags"]["gcRuns"] == 3 and not {"gcMs", "gcRuns"} & set(theirs["tags"])
    # the same hook feeds the operator's histogram, drained where the reporters tick
    metrics = MetricsRegistry()
    tracing.publish_gc_pauses(metrics)
    pauses = {
        tags: h for (name, tags), h in metrics._histograms.items() if name == mnames.RUNTIME_GC_PAUSE_TIME
    }
    assert {dict(tags)[mnames.TAG_GENERATION] for tags in pauses} >= {"0", "2"}
    assert sum(h.count for h in pauses.values()) >= 3
    tracing.publish_gc_pauses(metrics)  # drained: nothing is counted twice
    assert sum(h.count for (name, _), h in metrics._histograms.items() if name == mnames.RUNTIME_GC_PAUSE_TIME) == sum(
        h.count for h in pauses.values()
    )


class _Held:
    """Background work (``work()`` gives its context manager, on the
    thread that does it) held open on another thread until released."""

    def __init__(self, work):
        import threading

        self._inside, self._release = threading.Event(), threading.Event()
        self._thread = threading.Thread(target=self._run, args=(work,))

    def _run(self, work):
        with work():
            self._inside.set()
            self._release.wait(10.0)

    def __enter__(self):
        self._thread.start()
        assert self._inside.wait(10.0)
        return self

    def __exit__(self, *exc):
        self._release.set()
        self._thread.join(10.0)
        assert not self._thread.is_alive()


@contextlib.contextmanager
def _scan(tracer):
    """One unit of the marker's loop: the marker, and the scan's trace inside it."""
    with tracing.background("unschedulable.scan"):
        with tracer.span("unschedulable.scan") as root:
            with root.aggregate("scan.mark"):
                pass
            yield root


def test_a_span_names_the_background_work_it_overlapped_and_a_marker_leaves_no_trace():
    tracer = Tracer(capacity=8)
    with tracer.span("http.request") as quiet:
        with tracer.span("predicate"):
            pass
    assert all("bg" not in s.tags for s in spans_of(quiet))

    # the marker's loop: a marker and, inside it, a root that is no request's,
    # on another thread across the whole request
    with _Held(lambda: _scan(tracer)):
        with tracer.span("http.request") as beside_scan:
            with tracer.span("predicate"):
                with tracer.span("fifo_gate"):
                    pass
    assert [s.tags["bg"] for s in spans_of(beside_scan)] == ["unschedulable.scan"] * 3
    scan = tracer.traces()[0]["root"]
    # marked work's own spans name nothing: not themselves, and not what ran beside them
    assert scan["name"] == "unschedulable.scan"
    assert [s["tags"] for s in dict_spans(scan)] == [{}, {"count": 1}]
    # a root that is no request's is background work only where its loop marks it
    with _Held(lambda: tracer.span("unschedulable.scan")):
        with tracer.span("http.request") as beside_unmarked:
            pass
    assert "bg" not in beside_unmarked.tags

    # a marker: under way at the enter of one span, begun and ended inside another, sorted with a root's
    before = len(tracer)
    with tracer.span("http.request") as root:
        with _Held(lambda: tracing.background("writeback")):
            with tracer.span("predicate") as predicate:
                with tracer.span("early"):
                    pass
        with tracer.span("late") as late:
            pass
        with tracer.span("holds-one") as holds_one:
            with tracing.background("reporters"):
                pass
        with _Held(lambda: _scan(tracer)):
            with _Held(lambda: tracing.background("capacity.sample")):
                with tracer.span("both") as both:
                    pass
    assert predicate.tags["bg"] == find(root, "early").tags["bg"] == "writeback"
    assert "bg" not in late.tags  # it left before this one began
    assert holds_one.tags["bg"] == "reporters"
    assert both.tags["bg"] == "capacity.sample,unschedulable.scan"
    assert root.tags["bg"] == "capacity.sample,reporters,unschedulable.scan,writeback"
    assert len(tracer) == before + 2  # the request and the scan's root: a marker puts nothing into the ring
    with tracer.span("http.request") as after:
        pass
    assert "bg" not in after.tags


def test_a_disabled_tracer_still_returns_the_shared_noop_and_touches_no_table():
    mark = tracing.spans._BACKGROUND.mark
    tracer = Tracer(enabled=False)
    span = tracer.span("unschedulable.scan")
    assert span is tracing.NOOP_SPAN
    with span:
        assert tracing.current_span() is None
    assert tracing.spans._BACKGROUND.mark == mark and len(tracer) == 0


def test_the_background_table_loses_no_enter_and_no_leave_under_contention():
    """More workers than cores, a switch after every few bytecodes, and
    the repo's race detector watching the table it instrumented."""
    import sys
    import threading

    from k8s_spark_scheduler_tpu.analysis import racecheck

    workers, rounds = 16, 300
    idle_while_working = []

    def work(table, i):
        for _ in range(rounds):
            table.enter(f"loop-{i % 4}")
            if table.mark <= 0:
                idle_while_working.append(i)  # work is under way: the mark has to say so
            table.leave(f"loop-{i % 4}")

    interval = sys.getswitchinterval()
    detector = racecheck.enable(racecheck.RaceDetector())
    sys.setswitchinterval(1e-6)
    try:
        table = tracing.spans.BackgroundTable()  # instrumented as it is made
        threads = [threading.Thread(target=work, args=(table, i)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        racecheck.disable()
    assert detector.clean(), (detector.races, detector.hb_races, detector.lock_order_violations)
    assert not idle_while_working
    assert table._active == {} and table._ticks == 2 * workers * rounds and table.mark == -table._ticks
    assert table.since(0) == {f"loop-{i}" for i in range(4)} and table.since(table.mark) == set()


def test_traces_over_the_wire_carry_the_gates_cpu_time_and_name_the_scan_a_request_ran_beside():
    h = served_harness("xla")
    http = ExtenderHTTPServer(h.server, port=0)
    http.start()
    try:
        post_driver(h, http.port, "app-first")
        with _Held(lambda: _scan(h.server.tracer)):
            post_driver(h, http.port, "app-beside-the-scan")
        post_driver(h, http.port, "app-after")
        with urllib.request.urlopen(f"http://127.0.0.1:{http.port}/traces", timeout=30) as resp:
            traces = json.loads(resp.read())["traces"]
    finally:
        http.stop()
        h.close()
    by_pod = {
        next(s for s in dict_spans(t["root"]) if s["name"] == "predicate")["tags"]["pod"]: t["root"]
        for t in traces if t["root"]["name"] == "http.request"
    }
    beside, after = by_pod["app-beside-the-scan-driver"], by_pod["app-after-driver"]
    for root in (beside, after):
        spans = list(dict_spans(root))
        assert len(spans) >= 24 and [s["name"] for s in spans if "cpuMs" in s["tags"]] == ["fifo_gate"]
    gate = next(s for s in dict_spans(beside) if s["name"] == "fifo_gate")
    assert "unschedulable.scan" in beside["tags"]["bg"].split(",")
    assert "unschedulable.scan" in gate["tags"]["bg"].split(",")
    assert all("unschedulable.scan" not in s["tags"].get("bg", "") for s in dict_spans(after))


def test_each_loop_the_server_starts_marks_one_unit_of_its_work_under_its_name():
    """``writeback``, ``capacity.sample`` and ``lifecycle.drain`` follow a
    granted driver on their own threads; the reporters tick, the lazy
    demand informer polls and the marker scans on theirs.  Each unit of work runs with its
    name in the table, and none leaves a trace."""
    from k8s_spark_scheduler_tpu.metrics.reporters import ReporterSet
    from k8s_spark_scheduler_tpu.state.typed_caches import LazyDemandInformer

    table = tracing.spans._BACKGROUND
    seen = {}

    def watching(name, unit):
        def watched(*args, **kwargs):
            seen.setdefault(name, []).append(set(table._active))
            return unit(*args, **kwargs)
        return watched

    h = served_harness("native")
    try:
        server = h.server
        writer = server.resource_reservation_cache._async
        writer._do_create = watching("writeback", writer._do_create)
        server.capacity.maybe_sample = watching("capacity.sample", server.capacity.maybe_sample)
        server.lifecycle.maybe_drain = watching("lifecycle.drain", server.lifecycle.maybe_drain)
        reporters = ReporterSet(server, tick_seconds=0.01)
        reporters.report_once = watching("reporters", lambda: reporters.stop())
        polls = LazyDemandInformer(h.api, server.informer_factory, poll_interval=0.01)
        answers = iter([False, False])  # the CRD is not there yet: start() and one poll; then it is
        polls._check_crd = watching("demand.poll", lambda: next(answers, True))
        polls._become_ready = polls._ready.set
        marker = h.unschedulable_marker  # its scan is a trace of its own: stood in for, so that the ring's count stays
        marker._interval = 0.01
        marker.scan_for_unschedulable_pods = watching("unschedulable.scan", marker.stop)
        def traces_but_samples():
            return [t for t in server.tracer.traces() if t["root"]["name"] != "capacity.sample"]

        before = len(traces_but_samples())
        reporters.start()
        polls.start()
        marker.start()
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-marked", 1)[0], NODES))
        names = {"writeback", "capacity.sample", "lifecycle.drain", "reporters", "demand.poll", "unschedulable.scan"}
        assert h.wait_for_api(lambda: names <= set(seen) and polls.ready(), timeout=20.0), sorted(seen)
        for name in names - {"demand.poll"}:
            assert all(name in active for active in seen[name]), (name, seen[name])
        # start()'s own look, on the caller's thread, is no poll; the two polls after it are
        assert ["demand.poll" in active for active in seen["demand.poll"]] == [False, True, True]
        assert h.wait_for_api(lambda: not set(table._active) & names, timeout=20.0)
        # the request's trace and no marker's; each sample is a trace of its own
        assert len(traces_but_samples()) == before + 1
    finally:
        h.close()


def test_each_background_sample_is_one_capacity_sample_root_with_its_tags():
    """The sampler's loop opens one root ``capacity.sample`` per sample,
    from the server's tracer: ``pending`` pending drivers, each of whose
    rows came from its demand's stash (``stashedRows``), and the group
    index ``rebuild`` after a node event, ``hit`` after a reservation.
    Neither the root nor its spans carry ``bg``: it is the marked work."""
    h = Harness(binpack_algo="tpu-batch")
    try:
        sampler, tracer = h.server.capacity, h.server.tracer

        def sample_roots():  # oldest first
            return [t["root"] for t in reversed(tracer.traces()) if t["root"]["name"] == "capacity.sample"]

        def settled(count):
            """``count`` samples or more, each a root, the last of the node table as it stands."""
            return lambda: (
                len(sample_roots()) == sampler.stats()["samples"] >= count
                and sampler.latest().structure_key == h.server.tensor_snapshot.snapshot().structure_key
            )

        for name in NODES[:3]:
            h.new_node(name)
        for i in range(2):
            # behind the granted driver in the queue: created after it
            h.create_pod(h.static_allocation_spark_pods(f"app-waiting-{i}", 1, creation_timestamp=time.time() + 600)[0])
        assert h.wait_for_api(settled(1), timeout=20.0), sampler.stats()
        assert sample_roots()[-1]["tags"]["groupIndex"] == "rebuild"
        samples = len(sample_roots())
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-granted", 1)[0], NODES[:3]))
        assert h.wait_for_api(settled(samples + 1), timeout=20.0), sampler.stats()
        last = sample_roots()[-1]
        assert own(last["tags"]) == {"pending": 2, "groupIndex": "hit", "stashedRows": 2}
        for root in sample_roots():
            assert root["parentId"] is None
            assert all("bg" not in s["tags"] for s in dict_spans(root))
    finally:
        h.close()


def test_a_sample_refused_under_the_predicate_lock_opens_no_span():
    from k8s_spark_scheduler_tpu import capacity

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n0")
        sampler = h.server.capacity
        sampler.stop()
        tracer = Tracer(capacity=4)
        capacity.enter_predicate_lock()
        try:
            assert sampler.sample_now(trigger="in-lock", tracer=tracer) is None
        finally:
            capacity.exit_predicate_lock()
        assert len(tracer) == 0
        assert sampler.sample_now(trigger="untraced") is not None and len(tracer) == 0
        assert sampler.sample_now(trigger="traced", tracer=tracer) is not None
        (trace,) = tracer.traces()
        assert trace["root"]["name"] == "capacity.sample"
        assert set(trace["root"]["tags"]) >= {"pending", "groupIndex", "stashedRows"}
    finally:
        h.close()


def test_a_samples_own_spans_name_no_background_work_beside_them():
    """Under its loop's marker the sample's root takes no ``bg``, though a
    write-back runs beside it; the same sample unmarked would name it."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n0")
        sampler = h.server.capacity
        sampler.stop()
        tracer = Tracer(capacity=4)
        with _Held(lambda: tracing.background("writeback")):
            with tracing.background("capacity.sample"):
                sampler.sample_now(trigger="marked", tracer=tracer)
            sampler.sample_now(trigger="unmarked", tracer=tracer)
        unmarked, marked = (t["root"] for t in tracer.traces())
        assert "bg" not in marked["tags"]
        assert unmarked["tags"]["bg"] == "writeback"
    finally:
        h.close()
