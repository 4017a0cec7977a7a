"""Single-AZ minimal fragmentation with dynamic allocation kept in one zone
(``should-schedule-dynamically-allocated-executors-in-same-az``) on the
served path: a driver admitted at its min inside the zone of the best
minimal-fragmentation packing, every executor beyond min placed in the
zone of its application's running pods, on a node that already holds a
reservation of the application first, then on the node with the least
room for one more.  The served stack over HTTP, driven by the benchmark's
``dynalloc-mix`` verbs, against the benchmark's plain reference
(``benchmarks/references/fifo-gangs-single-az-dynalloc.py``, which
imports nothing of the program), exactly; then the rules the
configuration's ``guarantees`` add, one by one."""

import json
import os
import sys
import time

import pytest
from test_instance_groups import client_of
from test_span_contract import find, own

from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.testing.harness import Harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
CONFIG = "fifo10k-single-az-minfrag-dynalloc"
POLICIES = ["tpu-batch-single-az-minimal-fragmentation", "single-az-minimal-fragmentation"]
SAME_AZ = "should_schedule_dynamically_allocated_executors_in_same_az"
BLOCK = (132, 36)  # max and min executors of every block of 8 gangs


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plug-ins, importable while this module's tests run."""
    sys.path.insert(0, BENCH)
    try:
        import check
        import plugins
        import run
        import stack
        import traffic

        with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
            config = run.rehearsal_size(json.load(f))  # 1,024 nodes x 60 pending
        with open(os.path.join(BENCH, "traffic", "dynalloc-mix.json")) as f:
            mix = json.load(f)
        yield {
            "check": check, "stack": stack, "traffic": traffic, "config": config, "mix": mix,
            "generator": plugins.load("generators", config["generator"]),
            "objects": plugins.load("objects", config["objects"]),
            "reference": plugins.load("references", config["reference"]["model"]),
        }
    finally:
        sys.path.remove(BENCH)


def served_block(bench, seed, binpack_algo, same_az=True):
    """One block of the mix through the served stack: (cluster, its record,
    the requests' roots, what the registry counted)."""
    config, mix = bench["config"], bench["mix"]
    cluster = bench["generator"].make_cluster(config, seed, time.time())
    stream = bench["generator"].blocks(config, mix, seed, cluster.base_ts)
    install = {"binpack_algo": binpack_algo, "fifo": True, SAME_AZ: same_az}
    served = bench["stack"].start_stack(cluster, bench["objects"], install)
    roots = []
    try:
        served.scheduler.tracer.add_observer(roots.append)
        client = client_of(bench, served, cluster.names)
        record = bench["traffic"].run_block(client, bench["objects"], next(stream), mix["steps"])
        metrics = served.scheduler.metrics
        counted = {
            "slow": metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"}),
            "fallbacks": served.scheduler.extender.host_fallbacks(),
            "left": served.scheduler.soft_reservation_store.get_application_count(),
        }
    finally:
        served.stop()
    return cluster, record, roots, counted


def compared(bench, cluster, record, same_az=True):
    reference = bench["reference"].Reference(cluster, "single-az-minimal-fragmentation", same_az=same_az)
    return bench["check"].compare([record], reference, cluster.names, bench["mix"]["steps"])


@pytest.mark.parametrize("binpack_algo", POLICIES)
@pytest.mark.parametrize("seed", [5, 2**31 + 17, 3_000_000_019])
def test_the_served_stack_answers_as_the_plain_reference_does(bench, seed, binpack_algo):
    cluster, record, roots, counted = served_block(bench, seed, binpack_algo)
    # drivers, reservations (scheduler and API server), every executor reserved, extra and
    # replacement, the soft store after the ramp, after the loss and after retire: every limit 0
    checks = compared(bench, cluster, record)
    assert bench["check"].is_correct(checks), checks
    assert checks["answers_compared"]["value"] == 8 * 3 + BLOCK[0] + 8 * 4
    assert counted == {"slow": 0, "fallbacks": 0, "left": 0}
    gangs = record.gangs
    assert (sum(g.gang.executors for g in gangs), sum(g.gang.min_executors for g in gangs)) == BLOCK
    zone_of = dict(zip(cluster.names, cluster.zone))
    granted = bench["traffic"].granted
    for g in gangs:
        driver, slots = g.read["reservation"]
        assert len(slots) == g.gang.min_executors and {zone_of[n] for n in slots} == {zone_of[driver]}
        # no executor of the application, reserved, extra or replacement, leaves the driver's zone
        answers = [a for kind in ("executor", "replacement_executor") for a in g.answers.get(kind, [])]
        assert answers and {zone_of[granted(a[2])] for a in answers} == {zone_of[driver]}
    # an extra executor's request holds the two new spans, with their tags
    by_pod = {find(r, "predicate").tags["pod"]: r for r in roots if r.name == "http.request"}
    some = next(g.gang for g in gangs if g.gang.executors > g.gang.min_executors)
    extra = by_pod[f"{some.app_id}-exec-{some.executors}"]
    common = find(extra, "executor.common_zone")
    assert own(common.tags) == {"pods": some.executors, "zones": 1}  # the driver and every executor before it
    fast = find(extra, "executor.fast_reschedule")
    driver_zone = zone_of[next(g for g in gangs if g.gang is some).read["reservation"][0]]
    assert own(fast.tags) == {"candidates": len(cluster.names), "hit": True, "zone": driver_zone}
    attraction = find(fast, "executor.app_attraction")
    assert attraction is not None and attraction.parent is find(fast, "executor.order")
    assert 1 <= own(attraction.tags)["appNodes"] <= some.executors - 1
    assert own(attraction.tags)["fitting"] >= 1


def test_with_the_install_key_off_the_same_stream_reads_differently(bench):
    """The extras of the same block spread over the zones: the reference
    with the key on calls that wrong, the reference with it off right."""
    cluster, record, _, _ = served_block(bench, 9, POLICIES[0], same_az=False)
    assert bench["check"].is_correct(compared(bench, cluster, record, same_az=False))
    wrong = compared(bench, cluster, record, same_az=True)
    assert not bench["check"].is_correct(wrong)
    assert wrong["executor_answers_wrong"]["value"] > 0 and wrong["driver_answers_wrong"]["value"] == 0
    zone_of = dict(zip(cluster.names, cluster.zone))
    granted = bench["traffic"].granted
    assert any(
        zone_of[granted(a[2])] != zone_of[g.read["reservation"][0]]
        for g in record.gangs for a in g.answers.get("executor", [])
    )


def harness(binpack_algo, same_az=True):
    return Harness(binpack_algo=binpack_algo, dynamic_allocation_single_az=same_az)


def executor_pods(app_id, min_count, max_count):
    return Harness.dynamic_allocation_spark_pods(
        app_id, min_count, max_count, executor_cpu="2", executor_mem="2Gi"
    )


@pytest.mark.parametrize("binpack_algo", POLICIES)
def test_an_extra_executor_goes_to_a_node_of_its_application_before_an_earlier_node_that_fits(binpack_algo):
    """``a`` takes the driver alone; the two executors at min fit ``c``
    whole (minimal fragmentation: the smallest node that takes them all),
    not ``b``, which has room for one.  The extra fits ``b`` and ``c``, one
    each, and ``b`` comes first in executor priority order (less memory
    free): it goes to ``c``, which holds its application's slots, where
    the other policies' first fit over the same room would take ``b``."""
    h = harness(binpack_algo)
    try:
        h.new_node("a", cpu="1", memory="1Gi", gpu="0")
        h.new_node("b", cpu="2", memory="4Gi", gpu="0")
        h.new_node("c", cpu="6", memory="16Gi", gpu="0")
        names = ["a", "b", "c"]
        pods = executor_pods("app-da", 2, 3)
        assert [h.assert_success(h.schedule(pod, names)) for pod in pods] == ["a", "c", "c", "c"]
        held, _ = h.server.soft_reservation_store.get_soft_reservation("app-da")
        assert {name: r.node for name, r in held.reservations.items()} == {"app-da-exec-3": "c"}
    finally:
        h.close()


@pytest.mark.parametrize("same_az", [True, False])
def test_no_extra_executor_leaves_the_zone_of_its_applications_running_pods(same_az):
    """``a`` (zone1) holds the driver and the executor at min exactly and
    wins the zone score; ``z`` (zone2) has room for everything.  With the
    install key on the extra is refused, and a demand is asked for in
    zone1; with it off the extra goes to ``z``."""
    h = harness(POLICIES[0], same_az)
    try:
        h.new_node("a", cpu="3", memory="3Gi", gpu="0", zone="zone1")
        h.new_node("z", cpu="64", memory="64Gi", gpu="0", zone="zone2")
        names = ["a", "z"]
        driver, reserved, extra = executor_pods("app-da", 1, 2)
        assert h.assert_success(h.schedule(driver, names)) == "a"
        assert h.assert_success(h.schedule(reserved, names)) == "a"
        result = h.schedule(extra, names)
        if not same_az:
            assert h.assert_success(result) == "z"
            return
        h.assert_failure(result)
        assert set(result.failed_nodes.values()) == {"not enough capacity to reschedule the executor"}
        assert h.server.metrics.get_counter(mnames.SINGLE_AZ_DA_PACK_FAILURE_ZONED, {"zone": "zone1"}) == 1
        assert h.server.soft_reservation_store.get_application_count() == 1
        held, _ = h.server.soft_reservation_store.get_soft_reservation("app-da")
        assert held.reservations == {}
    finally:
        h.close()
