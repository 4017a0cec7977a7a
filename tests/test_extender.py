"""Extender-level tests against the full wiring (reference
internal/extender/resource_test.go scenarios re-derived on the Harness)."""

import time

import pytest

from k8s_spark_scheduler_tpu.events.events import DEMAND_CREATED, DEMAND_DELETED
from k8s_spark_scheduler_tpu.scheduler.labels import SPARK_APP_ID_LABEL
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs


@pytest.fixture
def harness():
    h = Harness()
    yield h
    h.close()


def two_node_cluster(h: Harness):
    h.new_node("n1")
    h.new_node("n2")
    return ["n1", "n2"]


# -- TestScheduler (resource_test.go:27) ------------------------------------


def test_gang_schedule_happy_path(harness):
    nodes = two_node_cluster(harness)
    pods = harness.static_allocation_spark_pods("app-1", 2)
    driver, execs = pods[0], pods[1:]

    driver_node = harness.assert_success(harness.schedule(driver, nodes))
    assert driver_node in nodes
    rr = harness.get_resource_reservation("app-1")
    assert rr is not None
    assert len(rr.spec.reservations) == 3  # driver + 2 executors
    assert rr.status.pods["driver"] == driver.name

    for e in execs:
        node = harness.assert_success(harness.schedule(e, nodes))
        assert node in nodes
    rr = harness.get_resource_reservation("app-1")
    assert set(rr.status.pods.values()) == {driver.name, execs[0].name, execs[1].name}


def test_extra_executor_rejected_when_all_bound(harness):
    nodes = two_node_cluster(harness)
    pods = harness.static_allocation_spark_pods("app-1", 1)
    driver, exec1 = pods[0], pods[1]
    harness.assert_success(harness.schedule(driver, nodes))
    harness.assert_success(harness.schedule(exec1, nodes))

    # a second executor beyond the reservation count must be rejected
    extra = harness.static_allocation_spark_pods("app-1", 1)[1]
    extra.meta.name = "app-1-exec-extra"
    harness.assert_failure(harness.schedule(extra, nodes))


def test_executor_rebind_after_death(harness):
    nodes = two_node_cluster(harness)
    pods = harness.static_allocation_spark_pods("app-1", 1)
    driver, exec1 = pods[0], pods[1]
    harness.assert_success(harness.schedule(driver, nodes))
    bound_node = harness.assert_success(harness.schedule(exec1, nodes))

    # executor dies; replacement takes over the dead executor's reservation
    harness.terminate_pod(exec1)
    replacement = harness.static_allocation_spark_pods("app-1", 1)[1]
    replacement.meta.name = "app-1-exec-replacement"
    node = harness.assert_success(harness.schedule(replacement, nodes))
    assert node == bound_node
    rr = harness.get_resource_reservation("app-1")
    assert replacement.name in rr.status.pods.values()
    assert exec1.name not in rr.status.pods.values()


def test_idempotent_driver_replay(harness):
    nodes = two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods("app-1", 1)[0]
    first = harness.assert_success(harness.schedule(driver, nodes))
    # replayed Filter call returns the reserved node again
    replay = harness.extender.predicate(ExtenderArgs(pod=driver, node_names=list(nodes)))
    assert replay.node_names == [first]


def test_idempotent_executor_replay(harness):
    nodes = two_node_cluster(harness)
    pods = harness.static_allocation_spark_pods("app-1", 1)
    harness.assert_success(harness.schedule(pods[0], nodes))
    node = harness.assert_success(harness.schedule(pods[1], nodes))
    replay = harness.extender.predicate(ExtenderArgs(pod=pods[1], node_names=list(nodes)))
    assert replay.node_names == [node]


def test_gang_reject_when_cluster_too_small(harness):
    two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods("app-big", 32)[0]
    result = harness.schedule(driver, ["n1", "n2"])
    harness.assert_failure(result)
    # a demand was created for the whole application
    assert harness.wait_for_api(
        lambda: harness.api.list("Demand") and True or False
    )
    demands = harness.api.list("Demand")
    assert len(demands) == 1
    assert demands[0].name == f"demand-{driver.name}"
    units = demands[0].spec.units
    assert units[0].count == 1 and units[1].count == 32


def test_demand_deleted_after_success(harness):
    two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods("app-1", 32)[0]
    harness.assert_failure(harness.schedule(driver, ["n1", "n2"]))
    assert harness.wait_for_api(lambda: len(harness.api.list("Demand")) == 1)

    # capacity arrives
    harness.new_node("n3", cpu="64", memory="64Gi")
    harness.assert_success(harness.schedule(driver, ["n1", "n2", "n3"]))
    assert harness.wait_for_api(lambda: len(harness.api.list("Demand")) == 0)
    assert harness.server.event_log.by_name(DEMAND_CREATED)
    assert harness.server.event_log.by_name(DEMAND_DELETED)


def test_non_spark_pod_rejected(harness):
    from k8s_spark_scheduler_tpu.types.objects import ObjectMeta, Pod

    two_node_cluster(harness)
    pod = Pod(meta=ObjectMeta(name="random"), scheduler_name="spark-scheduler")
    result = harness.schedule(pod, ["n1", "n2"])
    harness.assert_failure(result)


# -- TestMinimalFragmentation (resource_test.go:73) -------------------------


def test_minimal_fragmentation_attracts_to_app_nodes():
    h = Harness(binpack_algo="single-az-minimal-fragmentation")
    try:
        h.new_node("n1", cpu="8", memory="8Gi")
        h.new_node("n2", cpu="8", memory="8Gi")
        nodes = ["n1", "n2"]
        pods = h.dynamic_allocation_spark_pods("app-1", 1, 3)
        driver, execs = pods[0], pods[1:]
        h.assert_success(h.schedule(driver, nodes))
        first_node = h.assert_success(h.schedule(execs[0], nodes))
        # extra executors prefer the node already hosting the app
        second_node = h.assert_success(h.schedule(execs[1], nodes))
        assert second_node == first_node
    finally:
        h.close()


# -- TestDynamicAllocationScheduling (resource_test.go:172) -----------------


def test_dynamic_allocation_min_hard_max_soft(harness):
    nodes = two_node_cluster(harness)
    pods = harness.dynamic_allocation_spark_pods("app-da", 1, 3)
    driver, execs = pods[0], pods[1:]

    harness.assert_success(harness.schedule(driver, nodes))
    rr = harness.get_resource_reservation("app-da")
    # only min executors get hard reservations
    assert len(rr.spec.reservations) == 2  # driver + 1

    # first executor binds the hard reservation
    harness.assert_success(harness.schedule(execs[0], nodes))
    sr, ok = harness.server.soft_reservation_store.get_soft_reservation("app-da")
    assert ok and len(sr.reservations) == 0

    # extras get soft reservations up to max - min = 2
    harness.assert_success(harness.schedule(execs[1], nodes))
    harness.assert_success(harness.schedule(execs[2], nodes))
    sr, _ = harness.server.soft_reservation_store.get_soft_reservation("app-da")
    assert set(sr.reservations) == {execs[1].name, execs[2].name}

    # a fourth executor exceeds max
    extra = harness.dynamic_allocation_spark_pods("app-da", 1, 3)[1]
    extra.meta.name = "app-da-exec-4"
    harness.assert_failure(harness.schedule(extra, nodes))


def test_fast_reschedule_lane_engages_and_matches_slow_lane():
    """The tensor-mirror executor lane must (a) actually serve the
    extra-executor/reschedule path and (b) make bit-identical decisions
    to the Quantity path across randomized DA scenarios with overhead
    pods and heterogeneous nodes, in both parity modes."""
    import random

    from k8s_spark_scheduler_tpu.config import Install
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    def overhead_pod(i, node, cpu, mem):
        return Pod(
            meta=ObjectMeta(name=f"sys-{i}", namespace="kube-system"),
            node_name=node,
            phase=PodPhase.RUNNING,
            containers=[Container(requests=Resources.of(cpu, mem))],
        )

    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder

    # variants: (binpack algo, single-az DA flag, executor label priority)
    # — "labels" exercises the lane's label-priority re-sort, "zone" its
    # single-AZ zone restriction (a key and a mask of the lane's
    # selection, ops/fast_path.py:first_in_executor_order)
    variants = {
        "plain": ("tightly-pack", False, None),
        "labels": (
            "tightly-pack",
            False,
            LabelPriorityOrder("pool", ["reserved", "spot"]),
        ),
        "zone": ("single-az-tightly-pack", True, None),
        # exercises the vectorized min-frag reschedule (app-attraction +
        # least-capacity, resource.go:675-703) against the Quantity loop,
        # on both the host policy name and its device-backed counterpart
        # (the variant selection keys on the name suffix)
        "minfrag-zone": ("single-az-minimal-fragmentation", True, None),
        "tpu-minfrag-zone": (
            "tpu-batch-single-az-minimal-fragmentation",
            True,
            None,
        ),
    }
    for variant, (algo, single_az, label_prio) in variants.items():
        for strict in (True, False):
            for seed in range(3):
                rng = random.Random(9000 + seed)
                n_nodes = rng.randint(2, 6)
                node_specs = [
                    (
                        f"n{i}",
                        str(rng.randint(3, 10)),
                        f"{rng.randint(8, 24)}Gi",
                        f"az-{rng.randint(0, 1)}",
                        rng.choice(["reserved", "spot", "other"]),
                    )
                    for i in range(n_nodes)
                ]
                oh_specs = [
                    (i, f"n{rng.randrange(n_nodes)}", str(rng.randint(0, 3)), "1Gi")
                    for i in range(rng.randint(0, 3))
                ]
                minc, maxc = 1, rng.randint(3, 6)

                results = {}
                lanes = {}
                for lane in ("fast", "slow"):
                    # extra_install REPLACES the harness-built Install, so
                    # every knob goes into it directly
                    h = Harness(
                        extra_install=Install(
                            fifo=False,
                            binpack_algo=algo,
                            should_schedule_dynamically_allocated_executors_in_same_az=single_az,
                            executor_prioritized_node_label=label_prio,
                            strict_reference_parity=strict,
                        ),
                    )
                    try:
                        for name, cpu, mem, zone, pool in node_specs:
                            h.new_node(
                                name, cpu=cpu, memory=mem, zone=zone,
                                labels={"pool": pool},
                            )
                        nodes = [s[0] for s in node_specs]
                        for spec in oh_specs:
                            h.create_pod(overhead_pod(*spec))
                        if lane == "slow":
                            h.server.extender._fast_path_ok = False
                        pods = h.dynamic_allocation_spark_pods("app-da", minc, maxc)
                        log = []
                        log.append(tuple(h.schedule(pods[0], nodes).node_names or []))
                        for p in pods[1:]:
                            log.append(tuple(h.schedule(p, nodes).node_names or []))
                        results[lane] = log
                        lanes[lane] = h.server.extender.last_reschedule_path
                    finally:
                        h.close()
                tag = f"{variant} strict={strict} seed={seed}"
                assert results["fast"] == results["slow"], f"{tag}: {results}"
                # the extra executors beyond min take the reschedule path;
                # the instrumented lane marker proves the fast lane served
                # it (when the driver was admitted at all)
                if any(results["fast"]):
                    assert lanes["fast"] == "fast", tag
                    assert lanes["slow"] == "slow", tag


# what each case of the selection's parity test builds: the cluster, the
# candidate list the extra executors are probed with, the install
SELECTION_CASES = {
    # sizes, zones, pools, overhead and usage all drawn
    "random": {},
    # identical nodes in one zone, two pods wide: every other executor
    # opens a node, the first by NAME, and names are drawn so that their
    # order is not the rows'
    "name-tie": {"zones": ["az-0"], "per_zone": 6, "cpus": [2], "mems": [16], "overhead": 0, "want": "first-name"},
    # the app placed on nodes that the probe's list leaves out, and the
    # same fresh nodes in every zone: equal zone totals, so the zone's
    # NAME decides, and zone ids are handed out in the other order
    "zone-tie": {"zones": ["az-z", "az-m", "az-a"], "fresh_per_zone": 3, "overhead": 0, "want": "first-zone"},
    # nodes that take no executor and still count in their zone's total
    "not-ready": {"sidelined": True},
    "duplicate-and-unknown-names": {"candidates": "noisy"},
    "strict-subset": {"candidates": "subset"},
    "label-priority": {"label_priority": True},
    "zone-restriction": {"algo": "single-az-tightly-pack", "single_az": True, "zone": True},
    "minimal-fragmentation": {"algo": "single-az-minimal-fragmentation"},  # over every zone
    "minimal-fragmentation-in-zone": {
        "algo": "tpu-batch-single-az-minimal-fragmentation", "single_az": True, "zone": True,
    },
    # every node filled by other pods after the min executors are placed
    "no-node-fits": {"fill": True, "want": "miss"},
}


@pytest.mark.parametrize("strict", [True, False], ids=["strict-parity", "overhead-once"])
@pytest.mark.parametrize("case", sorted(SELECTION_CASES))
def test_the_mirrors_selection_is_the_quantity_paths_first_fit(case, strict):
    """The extra executor's node as the mirror selects it (the minimum of
    (label rank, zone priority, memory, cpu, name) over the rows that fit,
    ops/fast_path.py:first_in_executor_order) is the node the Quantity
    path finds (`_node_sorter.potential_nodes`, then first fit or the
    min-frag loop), on the same state, executor after executor."""
    import random

    from k8s_spark_scheduler_tpu.config import Install
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder
    from k8s_spark_scheduler_tpu.scheduler.extender import SchedulingFailure
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_resources
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    spec = SELECTION_CASES[case]
    compared = 0
    for seed in range(4):
        rng = random.Random(f"{case}-{seed}")
        zones = spec.get("zones", ["az-1", "az-0", "az-2"][: rng.randint(2, 3)])
        h = Harness(
            extra_install=Install(
                fifo=False,
                binpack_algo=spec.get("algo", "tightly-pack"),
                should_schedule_dynamically_allocated_executors_in_same_az=spec.get("single_az", False),
                executor_prioritized_node_label=(
                    LabelPriorityOrder("pool", ["reserved", "spot"]) if spec.get("label_priority") else None
                ),
                strict_reference_parity=strict,
            ),
        )
        try:
            zone_of = {}

            def node(zone, cpu, mem, **flags):
                name = f"n{rng.randrange(10**6):06d}"
                zone_of[name] = zone
                labels = {"pool": rng.choice(["reserved", "spot", "other"])}
                h.new_node(name, cpu=str(cpu), memory=f"{mem}Gi", zone=zone, labels=labels, **flags)
                return name

            def other_pod(name, on, cpu, mem):
                h.create_pod(Pod(
                    meta=ObjectMeta(name=name, namespace="kube-system"),
                    node_name=on,
                    phase=PodPhase.RUNNING,
                    containers=[Container(requests=Resources.of(str(cpu), f"{mem}Gi"))],
                ))

            cpus, mems = spec.get("cpus", [3, 4, 6, 8, 10]), spec.get("mems", [8, 12, 16, 24])
            names = [node(zone, rng.choice(cpus), rng.choice(mems)) for zone in zones for _ in range(spec.get("per_zone", rng.randint(2, 4)))]
            if spec.get("sidelined"):
                for zone in zones:  # from nothing to more than the rest of the zone: they move it among the zones
                    names.append(node(zone, 8, rng.choice([1, 100, 300]), ready=False))
                    names.append(node(zone, 8, rng.choice([1, 100, 300]), unschedulable=True))
            for i in range(spec.get("overhead", rng.randint(0, 4))):
                other_pod(f"sys-{i}", rng.choice(names), rng.randint(0, 2), 1)

            most = rng.randint(4, 6)
            pods = h.dynamic_allocation_spark_pods("app-da", 2, most)
            placed = [h.assert_success(h.schedule(pod, names)) for pod in pods[:3]]  # the driver, the min executors
            probe = names
            if "fresh_per_zone" in spec:
                shapes = [(rng.choice(cpus), rng.choice(mems)) for _ in range(spec["fresh_per_zone"])]
                probe = [node(zone, cpu, mem) for zone in zones for cpu, mem in shapes]
            elif spec.get("candidates") == "subset":
                probe = rng.sample(names, len(names) - rng.randint(1, 2))
            elif spec.get("candidates") == "noisy":
                again = rng.choice(zones)  # one zone's nodes four times: counted once in its total
                probe = names + [n for n in names if zone_of[n] == again] * 3 + ["no-such-node", "nor-this-one"]
                rng.shuffle(probe)
            if spec.get("fill"):
                for i, name in enumerate(names):
                    other_pod(f"fill-{i}", name, 10, 24)
            zone = zone_of[placed[0]] if spec.get("zone") else None

            resources = spark_resources(pods[0]).executor_resources
            for executor in pods[3:]:
                h.create_pod(executor)
                fast = h.extender._try_fast_reschedule(executor, probe, resources, zone)
                try:
                    slow, _ = h.extender._quantity_reschedule(
                        executor, probe, resources, zone is not None, zone or "", "outcome"
                    )
                except SchedulingFailure:
                    slow = None
                tag = f"{case} strict={strict} seed={seed} {executor.name}"
                assert fast == (slow is not None, slow), tag
                assert h.extender.last_reschedule_path == "fast", tag
                compared += 1
                answer = h.schedule(executor, probe)  # and as served, which moves the state on
                assert (answer.node_names or [None]) == [slow], tag
                if spec.get("want") == "miss":
                    assert slow is None, tag
                    assert h.wait_for_api(lambda: h.api.list("Demand")), tag
                elif spec.get("want") == "first-name" and slow is not None:
                    untouched = sorted(set(probe) - set(placed))
                    assert slow in placed or slow == untouched[0], tag
                elif spec.get("want") == "first-zone" and executor is pods[3]:
                    assert zone_of[slow] == "az-a", tag
                if slow is not None:
                    placed.append(slow)
        finally:
            h.close()
    assert compared >= 8


def test_fastpath_lane_counters(harness):
    """Lane-engagement observability: driver and executor Filter calls
    record which lane served them."""
    nodes = two_node_cluster(harness)
    pods = harness.dynamic_allocation_spark_pods("app-metrics", 1, 3)
    for p in pods:
        harness.schedule(p, nodes)
    reg = harness.server.extender._metrics
    drv = sum(
        reg.get_counter("foundry.spark.scheduler.tpu.fastpath", {"path": "driver", "lane": lane})
        for lane in ("fast", "slow")
    )
    exe = sum(
        reg.get_counter("foundry.spark.scheduler.tpu.fastpath", {"path": "executor", "lane": lane})
        for lane in ("fast", "slow")
    )
    assert drv >= 1  # the driver Filter call
    assert exe >= 2  # the extra executors beyond min took the reschedule path


def test_dynamic_allocation_compaction_on_executor_death(harness):
    nodes = two_node_cluster(harness)
    pods = harness.dynamic_allocation_spark_pods("app-da", 1, 2)
    driver, execs = pods[0], pods[1:]
    harness.assert_success(harness.schedule(driver, nodes))
    harness.assert_success(harness.schedule(execs[0], nodes))  # hard
    harness.assert_success(harness.schedule(execs[1], nodes))  # soft

    # the hard-reserved executor dies → its RR spot frees; deleting it
    # queues the app for compaction
    harness.delete_pod(execs[0])
    # next predicate call triggers compaction: the soft executor moves to
    # the hard reservation
    probe = harness.static_allocation_spark_pods("probe", 0)[0]
    harness.schedule(probe, nodes)

    rr = harness.get_resource_reservation("app-da")
    assert execs[1].name in rr.status.pods.values()
    sr, _ = harness.server.soft_reservation_store.get_soft_reservation("app-da")
    assert execs[1].name not in sr.reservations


# -- FIFO (resource.go:309-319) ---------------------------------------------


def test_fifo_blocks_later_driver(harness):
    two_node_cluster(harness)
    t0 = time.time()
    # app-old needs more than the cluster has; app-new would fit
    old_driver = harness.static_allocation_spark_pods(
        "app-old", 32, creation_timestamp=t0 - 100
    )[0]
    new_driver = harness.static_allocation_spark_pods(
        "app-new", 1, creation_timestamp=t0
    )[0]
    harness.create_pod(old_driver)
    harness.assert_failure(harness.schedule(new_driver, ["n1", "n2"]))


def test_fifo_enforce_after_pod_age_skips_young_drivers():
    from k8s_spark_scheduler_tpu.config import FifoConfig

    h = Harness(fifo_config=FifoConfig(default_enforce_after_pod_age=3600.0))
    try:
        h.new_node("n1")
        h.new_node("n2")
        t0 = time.time()
        old_driver = h.static_allocation_spark_pods("app-old", 32, creation_timestamp=t0 - 100)[0]
        new_driver = h.static_allocation_spark_pods("app-new", 1, creation_timestamp=t0)[0]
        h.create_pod(old_driver)
        # old driver is younger than enforce-after → skipped from FIFO
        h.assert_success(h.schedule(new_driver, ["n1", "n2"]))
    finally:
        h.close()


def test_fifo_accounts_earlier_driver_usage(harness):
    # earlier driver fits and its usage must be subtracted before packing
    # the later driver: both fit only if accounting is correct
    two_node_cluster(harness)
    t0 = time.time()
    first = harness.static_allocation_spark_pods("app-a", 6, creation_timestamp=t0 - 100)[0]
    second = harness.static_allocation_spark_pods("app-b", 6, creation_timestamp=t0)[0]
    harness.create_pod(first)
    # cluster: 16 cpu total; app-a takes 7 (1 driver + 6); app-b takes 7;
    # fits → but the FIFO subtraction QUIRK (one executor per node) means
    # app-b sees more capacity than truly free; the final pack for app-b
    # still must succeed here
    harness.assert_success(harness.schedule(second, ["n1", "n2"]))


# -- unschedulable marker (unschedulablepods_test.go) -----------------------


def test_unschedulable_marker_flags_oversized_driver(harness):
    two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods("app-huge", 100)[0]
    driver.meta.creation_timestamp = time.time() - 3600
    created = harness.create_pod(driver)
    harness.unschedulable_marker.scan_for_unschedulable_pods()
    fresh = harness.api.get("Pod", "default", driver.name)
    cond = fresh.conditions.get("PodExceedsClusterCapacity")
    assert cond is not None and cond.status == "True"


def test_unschedulable_marker_gpu_exhaustion(harness):
    # nodes have 1 GPU each; an 8-GPU executor ask can never fit
    two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods(
        "app-gpu", 1, executor_gpu="8"
    )[0]
    driver.meta.creation_timestamp = time.time() - 3600
    harness.create_pod(driver)
    assert harness.unschedulable_marker.does_pod_exceed_cluster_capacity(driver)


def test_unschedulable_marker_clears_when_fits(harness):
    two_node_cluster(harness)
    driver = harness.static_allocation_spark_pods("app-ok", 1)[0]
    driver.meta.creation_timestamp = time.time() - 3600
    harness.create_pod(driver)
    harness.unschedulable_marker.scan_for_unschedulable_pods()
    fresh = harness.api.get("Pod", "default", driver.name)
    cond = fresh.conditions.get("PodExceedsClusterCapacity")
    assert cond is not None and cond.status == "False"


def per_pod_reference_scan(h, packer, timeout=600.0):
    """The scan as unschedulablepods.go:93-129 runs it, pod by pod on the
    host oracle: the condition writes it would make, in order."""
    from k8s_spark_scheduler_tpu.scheduler import labels as L
    from k8s_spark_scheduler_tpu.scheduler.overhead import node_overhead
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import AnnotationError, spark_resources
    from k8s_spark_scheduler_tpu.types.resources import (
        Resources,
        node_scheduling_metadata_for_nodes,
    )

    writes = []
    server = h.server
    for pod in server.pod_informer.list():
        if not (
            pod.scheduler_name == L.SPARK_SCHEDULER_NAME
            and pod.node_name == ""
            and pod.meta.deletion_timestamp is None
            and pod.labels.get(L.SPARK_ROLE_LABEL) == L.DRIVER
            and pod.creation_timestamp + timeout < time.time()
        ):
            continue
        try:
            app = spark_resources(pod)
        except AnnotationError:
            break
        nodes = server.node_informer.list_with_predicate(pod.matches_node)
        names = [n.name for n in nodes]
        _, non_schedulable = node_overhead(
            server.pod_informer.list(), server.resource_reservation_manager, names
        )
        metadata = node_scheduling_metadata_for_nodes(
            nodes, {n.name: Resources.zero() for n in nodes}, non_schedulable
        )
        fits = packer(
            app.driver_resources, app.executor_resources, app.min_executor_count,
            names, names, metadata,
        ).has_capacity
        status = "False" if fits else "True"
        current = pod.conditions.get("PodExceedsClusterCapacity")
        if current is None or current.status != status:
            writes.append((pod.name, status))
    return writes


@pytest.mark.parametrize("algo,oracle", [
    ("tightly-pack", "tightly-pack"),
    ("tpu-batch", "tightly-pack"),
    ("tpu-batch-minimal-fragmentation", "minimal-fragmentation"),
    ("tpu-batch-single-az", "single-az-tightly-pack"),
])
def test_two_pass_scan_writes_what_a_per_pod_scan_writes_in_its_order(algo, oracle):
    """Two affinity signatures, repeated verdict keys, a pod younger than
    the timeout, an annotation error in mid-list (the pods before it are
    judged and marked, those after it are not), then a second scan after
    the cluster grew: only the verdicts that turned are written."""
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker
    from k8s_spark_scheduler_tpu.scheduler import labels as L

    h = Harness(binpack_algo=algo)
    try:
        for i in range(3):
            h.new_node(f"big-{i}", cpu="16", memory="32Gi", instance_group="big", zone=f"z{i % 2}")
        h.new_node("small-0", cpu="4", memory="8Gi", instance_group="small")
        old = time.time() - 3600
        backlog = [  # (app, executors, instance group, age)
            ("a-fits", 4, "big", old),
            ("b-too-many", 60, "big", old),
            ("c-fits-small", 2, "small", old),
            ("d-too-many-small", 4, "small", old),
            ("e-repeats-a", 4, "big", old),
            ("f-young", 90, "big", time.time() - 5),
            ("g-repeats-d", 4, "small", old),
            ("h-broken", 1, "big", old),
            ("i-after-the-error", 60, "big", old),
        ]
        for app, count, group, created in backlog:
            pod = h.static_allocation_spark_pods(
                app, count, instance_group=group, creation_timestamp=created
            )[0]
            if app == "h-broken":
                del pod.meta.annotations[L.EXECUTOR_COUNT]
            h.create_pod(pod)
        written = []
        update = h.api.update

        def recording(obj):
            cond = getattr(obj, "conditions", {}).get("PodExceedsClusterCapacity")
            if obj.KIND == "Pod" and cond is not None:
                written.append((obj.name, cond.status))
            return update(obj)

        h.api.update = recording
        packer = select_binpacker(oracle).binpack_func  # the host policy
        want = per_pod_reference_scan(h, packer)
        listed = [p.name for p in h.server.pod_informer.list()]
        assert [name for name, _ in want] == [
            n for n in listed if n[0] in "abcdeg"
        ] and {s for _, s in want} == {"True", "False"}
        h.unschedulable_marker.scan_for_unschedulable_pods()
        assert written == want
        # the cluster grows: "b-too-many" now fits, nothing else turns
        for i in range(3, 8):
            h.new_node(f"big-{i}", cpu="16", memory="32Gi", instance_group="big", zone=f"z{i % 2}")
        del written[:]
        want = per_pod_reference_scan(h, packer)
        assert [name for name, _ in want] == ["b-too-many-driver"]
        h.unschedulable_marker.scan_for_unschedulable_pods()
        assert written == want
    finally:
        h.close()


def test_dynamic_allocation_cross_node_compaction_keeps_reservation_node(harness):
    """resourcereservations.go:326-335: when a soft-reserved executor runs
    on node A and the only unbound hard reservation is on node B, the
    compacted binding keeps the reservation on B (and it stays
    discoverable as unbound since the pod runs elsewhere)."""
    harness.new_node("n1", cpu="4", memory="4Gi")
    harness.new_node("n2", cpu="4", memory="4Gi")
    nodes = ["n1", "n2"]
    pods = harness.dynamic_allocation_spark_pods("app-x", 1, 2)
    driver, execs = pods[0], pods[1:]
    harness.assert_success(harness.schedule(driver, nodes))
    rr = harness.get_resource_reservation("app-x")
    hard_node = rr.spec.reservations["executor-1"].node

    # bind the hard reservation, then a soft executor
    harness.assert_success(harness.schedule(execs[0], nodes))
    harness.assert_success(harness.schedule(execs[1], nodes))
    sr, _ = harness.server.soft_reservation_store.get_soft_reservation("app-x")
    soft_node = sr.reservations[execs[1].name].node

    # kill the hard-reserved executor; compaction moves the soft executor
    # onto the freed hard reservation
    harness.delete_pod(execs[0])
    probe = harness.static_allocation_spark_pods("probe2", 0)[0]
    harness.schedule(probe, nodes)

    rr = harness.get_resource_reservation("app-x")
    assert rr.status.pods["executor-1"] == execs[1].name
    # the reservation's node must be unchanged even if the pod runs elsewhere
    assert rr.spec.reservations["executor-1"].node == hard_node


def test_heterogeneous_instance_groups():
    """Bench config (3): multi-instance-group nodes with node-selector
    affinity — apps must confine to their group and account capacity
    per group."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        for i in range(2):
            h.new_node(f"big-{i}", cpu="16", memory="32Gi", instance_group="batch-big")
        for i in range(3):
            h.new_node(f"small-{i}", cpu="4", memory="8Gi", instance_group="batch-small")
        all_nodes = [f"big-{i}" for i in range(2)] + [f"small-{i}" for i in range(3)]

        big_pods = h.static_allocation_spark_pods(
            "app-big", 4, driver_cpu="2", driver_mem="4Gi",
            executor_cpu="4", executor_mem="8Gi", instance_group="batch-big",
        )
        small_pods = h.static_allocation_spark_pods(
            "app-small", 2, instance_group="batch-small"
        )

        node = h.assert_success(h.schedule(big_pods[0], all_nodes))
        assert node.startswith("big-")
        node = h.assert_success(h.schedule(small_pods[0], all_nodes))
        assert node.startswith("small-")
        for p in big_pods[1:]:
            assert h.assert_success(h.schedule(p, all_nodes)).startswith("big-")
        for p in small_pods[1:]:
            assert h.assert_success(h.schedule(p, all_nodes)).startswith("small-")

        # a big-group app that exceeds the big group's remaining capacity
        # must fail even though the small group has room
        overflow = h.static_allocation_spark_pods(
            "app-overflow", 8, executor_cpu="4", executor_mem="8Gi",
            instance_group="batch-big",
        )[0]
        h.assert_failure(h.schedule(overflow, all_nodes))
    finally:
        h.close()


def test_single_az_dynamic_allocation_confinement():
    """resource.go:606-636: with a single-AZ packer + the DA-same-AZ
    flag, extra executors are confined to the zone the app runs in, and
    a zone-pinned demand is created when that zone is full."""
    h = Harness(
        binpack_algo="single-az-tightly-pack",
        dynamic_allocation_single_az=True,
    )
    try:
        h.new_node("a1", cpu="4", memory="4Gi", zone="az-a")
        h.new_node("a2", cpu="4", memory="4Gi", zone="az-a")
        h.new_node("b1", cpu="16", memory="16Gi", zone="az-b")
        nodes = ["a1", "a2", "b1"]

        # DA app: min 1, max 6 — driver + first executor land in one zone
        pods = h.dynamic_allocation_spark_pods(
            "app-zaz", 1, 6, executor_cpu="2", executor_mem="2Gi"
        )
        driver, execs = pods[0], pods[1:]
        driver_node = h.assert_success(h.schedule(driver, nodes))
        first = h.assert_success(h.schedule(execs[0], nodes))
        zone_of = {"a1": "az-a", "a2": "az-a", "b1": "az-b"}
        app_zone = zone_of[driver_node]
        assert zone_of[first] == app_zone

        # the app zone (az-a: 8 cpu total) fills; extra executors must
        # NOT spill into az-b even though b1 has plenty of room
        granted = []
        for e in execs[1:]:
            r = h.schedule(e, nodes)
            if r.node_names:
                assert zone_of[r.node_names[0]] == app_zone, r.node_names
                granted.append(r.node_names[0])
        assert granted, "some extras should fit in the app zone"
        assert len(granted) < 5, "zone confinement must reject the overflow"

        # the failed extras created zone-pinned demands
        assert h.wait_for_api(lambda: len(h.api.list("Demand")) >= 1)
        demand = h.api.list("Demand")[0]
        assert demand.spec.zone == app_zone
        assert demand.spec.enforce_single_zone_scheduling
    finally:
        h.close()


def test_autoscaler_fulfillment_end_to_end():
    """Full demand loop: no capacity -> demand -> fake autoscaler adds
    nodes + fulfills -> retry schedules -> demand deleted -> waste
    metrics attribute the phases."""
    from k8s_spark_scheduler_tpu.metrics import names
    from k8s_spark_scheduler_tpu.testing.fake_autoscaler import FakeAutoscaler

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1", cpu="2", memory="2Gi")
        demand_informer = h.server.lazy_demand_informer.informer()
        scaler = FakeAutoscaler(h.api, demand_informer)

        driver = h.static_allocation_spark_pods("app-auto", 6)[0]
        h.assert_failure(h.schedule(driver, ["n1"]))
        # the autoscaler reacts to the demand synchronously (watch events)
        assert h.wait_for_api(lambda: scaler.fulfilled)
        scaled = [n.name for n in h.api.list("Node") if n.name.startswith("scaled-")]
        assert scaled

        # kube-scheduler retries with the new node list
        result = h.schedule(driver, ["n1"] + scaled)
        node = h.assert_success(result)
        assert node in scaled or node == "n1"
        assert h.wait_for_api(lambda: len(h.api.list("Demand")) == 0)

        m = h.server.metrics
        fulfilled_waste = m.get_histogram(
            names.SCHEDULING_WASTE, {names.TAG_WASTE_TYPE: "after-demand-fulfilled"}
        )
        assert fulfilled_waste["count"] == 1
    finally:
        h.close()


def test_autoscaler_provisions_for_indivisible_units():
    """Unit sizes that don't divide node capacity must still get enough
    nodes (first-fit provisioning, not summed division)."""
    from k8s_spark_scheduler_tpu.testing.fake_autoscaler import FakeAutoscaler

    h = Harness(binpack_algo="tightly-pack")
    try:
        h.new_node("n1", cpu="1", memory="1Gi")
        scaler = FakeAutoscaler(
            h.api, h.server.lazy_demand_informer.informer(), node_cpu="16", node_memory="32Gi"
        )
        # 3 executors x 10 cpu: one fits per 16-cpu node -> needs 3 nodes
        driver = h.static_allocation_spark_pods(
            "app-indiv", 3, driver_cpu="1", driver_mem="1Gi",
            executor_cpu="10", executor_mem="4Gi",
        )[0]
        h.assert_failure(h.schedule(driver, ["n1"]))
        assert h.wait_for_api(lambda: scaler.fulfilled)
        scaled = [n.name for n in h.api.list("Node") if n.name.startswith("scaled-")]
        assert len(scaled) >= 3, scaled
        h.assert_success(h.schedule(driver, ["n1"] + scaled))
    finally:
        h.close()


def test_executor_rebind_storm():
    """Mass executor death: every replacement must take over a dead
    executor's reservation (reservation nodes unchanged), never leak
    spots, and reject the N+1th replacement."""
    import random

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        for i in range(8):
            h.new_node(f"n{i}", cpu="16", memory="16Gi")
        nodes = [f"n{i}" for i in range(8)]
        pods = h.static_allocation_spark_pods("app-storm", 50)
        driver, execs = pods[0], pods[1:]
        h.assert_success(h.schedule(driver, nodes))
        for e in execs:
            h.assert_success(h.schedule(e, nodes))

        rr_before = h.get_resource_reservation("app-storm")
        mapping_before = {
            name: r.node for name, r in rr_before.spec.reservations.items()
        }

        rng = random.Random(5)
        victims = rng.sample(execs, 25)
        for v in victims:
            h.delete_pod(v)

        replacements = []
        for i, v in enumerate(victims):
            rep = h.static_allocation_spark_pods("app-storm", 1)[1]
            rep.meta.name = f"app-storm-rep-{i}"
            node = h.assert_success(h.schedule(rep, nodes))
            replacements.append((rep, node))

        rr_after = h.get_resource_reservation("app-storm")
        # per-reservation node mapping unchanged; every replacement is bound
        assert {
            name: r.node for name, r in rr_after.spec.reservations.items()
        } == mapping_before
        bound = set(rr_after.status.pods.values())
        for rep, node in replacements:
            assert rep.name in bound
        # no victim remains bound
        assert not bound & {v.name for v in victims}

        # the 51st executor has no spot
        extra = h.static_allocation_spark_pods("app-storm", 1)[1]
        extra.meta.name = "app-storm-extra"
        h.assert_failure(h.schedule(extra, nodes))
    finally:
        h.close()


def test_unschedulable_scan_memoizes_per_affinity_group(harness):
    """The r5 scan memoization must keep per-group verdicts separate: a
    gang that exceeds its own (small) instance group's capacity is
    flagged even when another group could fit it, and vice versa."""
    for i in range(2):
        harness.new_node(f"big-{i}", cpu="32", memory="64Gi", instance_group="big")
    harness.new_node("small-0", cpu="2", memory="4Gi", instance_group="small")

    old = time.time() - 3600
    fits_big = harness.static_allocation_spark_pods(
        "app-big", 4, instance_group="big", creation_timestamp=old
    )[0]
    too_big_for_small = harness.static_allocation_spark_pods(
        "app-small", 4, instance_group="small", creation_timestamp=old
    )[0]
    harness.create_pod(fits_big)
    harness.create_pod(too_big_for_small)
    harness.unschedulable_marker.scan_for_unschedulable_pods()

    cond_big = harness.api.get("Pod", "default", fits_big.name).conditions.get(
        "PodExceedsClusterCapacity"
    )
    cond_small = harness.api.get(
        "Pod", "default", too_big_for_small.name
    ).conditions.get("PodExceedsClusterCapacity")
    assert cond_big is not None and cond_big.status == "False"
    assert cond_small is not None and cond_small.status == "True"
