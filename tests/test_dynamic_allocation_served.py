"""Dynamic allocation (min/max executors, soft reservations) on the served
path: a driver admitted with its min executors, every executor beyond min
placed one by one from the tensor mirror against hard + soft usage and
held as a soft reservation, an executor lost and replaced, the compaction
at the next Filter, retire.  The served stack over HTTP, driven by the
benchmark's own ``dynalloc-mix`` verbs, against the benchmark's plain
reference (``benchmarks/references/fifo-gangs-dynalloc.py``, which imports
nothing of the program), exactly; then the rules a reader of the
configuration's ``guarantees`` relies on, one by one."""

import json
import os
import sys
import time

import pytest
from test_instance_groups import client_of
from test_span_contract import EXPECTED_EXECUTOR, find, own, shape

from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
POLICIES = {"tpu-batch": "tightly-pack", "tpu-batch-minimal-fragmentation": "minimal-fragmentation"}
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

        with open(os.path.join(BENCH, "configs", "fifo10k-dynalloc.json")) as f:
            config = run.rehearsal_size(json.load(f))  # 1,024 nodes x 60 pending
        with open(os.path.join(BENCH, "traffic", "dynalloc-mix.json")) as f:
            mix = json.load(f)
        yield {
            "check": check, "stack": stack, "traffic": traffic, "config": config, "mix": mix,
            "generator": plugins.load("generators", "dynamic-allocation"),
            "objects": plugins.load("objects", "dynamic-allocation"),
            "reference": plugins.load("references", "fifo-gangs-dynalloc"),
        }
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("binpack_algo", sorted(POLICIES))
@pytest.mark.parametrize("seed", [3, 2**31 + 29, 3_000_000_011])
def test_the_served_stack_answers_as_the_plain_reference_does(bench, seed, binpack_algo):
    config, mix = bench["config"], bench["mix"]
    cluster = bench["generator"].make_cluster(config, seed, time.time())
    stream = bench["generator"].blocks(config, mix, seed, cluster.base_ts)
    served = bench["stack"].start_stack(cluster, bench["objects"], {"binpack_algo": binpack_algo, "fifo": True})
    roots = []
    try:
        served.scheduler.tracer.add_observer(roots.append)
        client = client_of(bench, served, cluster.names)
        record = bench["traffic"].run_block(client, bench["objects"], next(stream), mix["steps"])
        metrics = served.scheduler.metrics
        counted = {
            "binds": metrics.get_counter(mnames.SOFT_RESERVATION_BINDS),
            "fast": metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "fast"}),
            "slow": metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"}),
            "fallbacks": served.scheduler.extender.host_fallbacks(),
            "left": served.scheduler.soft_reservation_store.get_application_count(),
        }
    finally:
        served.stop()
    # every driver answer and reservation, every executor answer (reserved, extra,
    # replacement), the soft store after the ramp, after loss + replacement and
    # after retire: as the benchmark's comparison replays them, every limit 0
    reference = bench["reference"].Reference(cluster, POLICIES[binpack_algo])
    checks = bench["check"].compare([record], reference, cluster.names, mix["steps"])
    assert bench["check"].is_correct(checks), checks
    assert set(checks) == {
        "answers_missing", "driver_answers_wrong", "reservations_wrong", "executor_answers_wrong",
        "soft_reservations_wrong", "replacement_answers_wrong", "api_reservations_wrong",
        "soft_reservations_left", "answers_compared",
    }
    most, least = BLOCK
    # 8 drivers, their reservations twice, 132 executors, 2 x 8 soft readings, 8 replacements, 8 after retire
    assert checks["answers_compared"]["value"] == 8 * 3 + most + 8 * 4
    gangs = record.gangs
    assert (sum(g.gang.executors for g in gangs), sum(g.gang.min_executors for g in gangs)) == BLOCK
    assert all(bench["traffic"].granted(a[2]) for g in gangs for kind in g.answers.values() for a in kind)
    for g in gangs:
        driver, slots = g.read["reservation"]
        assert len(slots) == g.gang.min_executors  # admitted at min
        after_ramp, after_loss = g.read["soft_reservations"]
        extras = g.gang.executors - g.gang.min_executors
        if extras == 0:
            assert after_ramp is None and after_loss is None  # never more than min: no soft entry at all
            continue
        # the executors beyond min, and only they, each on one node
        assert sorted(after_ramp) == list(range(g.gang.min_executors + 1, g.gang.executors + 1))
        assert len(after_loss) <= extras
        assert g.read["soft_left"] == (False, 0)
    # the extras were placed by the mirror's lane, none by the Quantity path, none by a fallback;
    # a replacement is one more where the compaction filled the freed slot from its own node
    replaced_as_extra = counted.pop("binds") - (most - least)
    assert 0 <= replaced_as_extra <= 8
    assert counted == {"fast": most - least + replaced_as_extra, "slow": 0, "fallbacks": 0, "left": 0}
    # the tree of each kind of executor request
    by_pod = {find(r, "predicate").tags["pod"]: find(r, "predicate") for r in roots if r.name == "http.request"}
    some = next(g.gang for g in gangs if g.gang.executors > g.gang.min_executors)
    assert shape(by_pod[f"{some.app_id}-exec-1"]) == EXPECTED_EXECUTOR["reserved"]
    extra = by_pod[f"{some.app_id}-exec-{some.executors}"]
    assert shape(extra) == EXPECTED_EXECUTOR["extra"]
    assert own(find(extra, "executor.fast_reschedule").tags) == {"candidates": len(cluster.names), "hit": True}
    replacement = by_pod[f"{some.app_id}-exec-{some.executors + 1}"]
    assert [c.name for c in replacement.children] == ["da.compact", "executor.select", "provenance.finish"]


def two_nodes():
    h = Harness(binpack_algo="tpu-batch")
    names = [h.new_node(f"n{i}", cpu="4", memory="8Gi", gpu="0").name for i in range(2)]
    return h, names


def test_an_application_never_holds_more_than_max_less_min_soft_reservations():
    h, names = two_nodes()
    try:
        pods = h.dynamic_allocation_spark_pods("app-da", 1, 3)
        for pod in pods:
            h.assert_success(h.schedule(pod, names))
        held, _ = h.server.soft_reservation_store.get_soft_reservation("app-da")
        assert sorted(held.reservations) == ["app-da-exec-2", "app-da-exec-3"]
        one_more = h.dynamic_allocation_spark_pods("app-da", 1, 4)[4]
        result = h.schedule(one_more, names)
        assert not result.node_names
        assert set(result.failed_nodes.values()) == {"application has no free executor spots to schedule this one"}
        assert h.server.metrics.get_counter(
            mnames.REQUEST_COUNTER,
            {"instanceGroup": "batch-medium-priority", "role": "executor", "outcome": "failure-unbound"},
        ) == 1
        held, _ = h.server.soft_reservation_store.get_soft_reservation("app-da")
        assert len(held.reservations) == 2
        # an executor that asks again is given the node it holds, and no second reservation
        assert h.assert_success(h.schedule(pods[3], names)) == held.reservations["app-da-exec-3"].node
        assert h.server.metrics.get_counter(mnames.SOFT_RESERVATION_BINDS) == 2
    finally:
        h.close()


def _add_a_smaller_node(h):
    h.new_node("n9", cpu="2", memory="4Gi", gpu="0")


def _change_node(name, **fields):
    def change(h):
        node = h.api.get("Node", "default", name)
        for field, value in fields.items():
            setattr(node, field, value)
        h.api.update(node)
    return change


def _move_to_a_zone_of_its_own(h):
    node = h.api.get("Node", "default", "n2")
    node.meta.labels = {**node.meta.labels, ZONE_LABEL: "zone2"}
    h.api.update(node)


# what happens between the third and the fourth executor: (the event, the
# list the fourth is asked with, how its candidate rows are come by, its node)
FOUR = ["n0", "n1", "n2", "n3"]
BETWEEN_TWO_EXTRAS = {
    "nothing": (lambda h: None, FOUR, "hit", "n1"),
    "node-added": (_add_a_smaller_node, FOUR + ["n9"], "miss", "n9"),
    # ... and left out of the list that is asked with, which is the kept one: the table changed all the same
    "node-added-unnamed": (_add_a_smaller_node, FOUR, "miss", "n1"),
    "node-deleted": (lambda h: h.api.delete("Node", "default", "n1"), FOUR, "miss", "n2"),
    "node-relabelled": (_move_to_a_zone_of_its_own, FOUR, "miss", "n2"),  # the zone with least free comes first
    "node-cordoned": (_change_node("n1", unschedulable=True), FOUR, "miss", "n2"),
    "node-not-ready": (_change_node("n1", ready=False), FOUR, "miss", "n2"),
    "another-list": (lambda h: None, ["n3", "n0", "n2", "n7"], "miss", "n2"),  # as long, one name unknown
}


@pytest.mark.parametrize("between", sorted(BETWEEN_TWO_EXTRAS))
def test_the_kept_candidate_rows_are_dropped_when_the_node_table_or_the_list_changes(between):
    """`executor.order` says how it came by the candidate rows (tag
    `rowsCache`, counted in `...fastpath.executorrows.reads`): computed for
    the first extra executor, kept for the next, and computed anew after
    any node event (a new `structure_key`) or for another list, so that
    the node chosen is the one the table as it stands gives."""
    event, asked_with, how, node = BETWEEN_TWO_EXTRAS[between]
    h = Harness(binpack_algo="tpu-batch")
    try:
        for name in FOUR:
            h.new_node(name, cpu="4", memory="8Gi", gpu="0")
        pods = h.dynamic_allocation_spark_pods("app-da", 1, 5)
        roots = []
        h.server.tracer.add_observer(roots.append)

        def reads():
            return {
                result: h.server.metrics.get_counter(mnames.EXECUTOR_ROWS_READS, {"result": result})
                for result in ("hit", "miss", "uncacheable")
            }

        before = reads()
        for pod in pods[:4]:  # the driver, the reserved executor and two extras fill n0
            assert h.assert_success(h.schedule(pod, FOUR)) == "n0"
        event(h)
        assert h.assert_success(h.schedule(pods[4], asked_with)) == node
        assert h.assert_success(h.schedule(pods[5], asked_with)) == node
        by_pod = {r.tags["pod"]: r for r in roots if r.name == "predicate"}
        tags = [own(find(by_pod[f"app-da-exec-{i}"], "executor.order").tags) for i in (2, 3, 4, 5)]
        assert tags == [{"rowsCache": "miss"}, {"rowsCache": "hit"}, {"rowsCache": how}, {"rowsCache": "hit"}]
        counted = {result: n - before[result] for result, n in reads().items()}
        assert counted == {"hit": 3 if how == "hit" else 2, "miss": 1 if how == "hit" else 2, "uncacheable": 0}
        # the tags the span contract pins are the reschedule's own, as they were
        fast = find(by_pod["app-da-exec-4"], "executor.fast_reschedule")
        assert own(fast.tags) == {"candidates": len(asked_with), "hit": True}
        assert [c.name for c in fast.children] == ["executor.snapshot", "executor.order"]
        assert h.extender.host_fallbacks() == 0
        assert h.server.metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"}) == 0
    finally:
        h.close()


@pytest.mark.parametrize("extras", [0, 2])
def test_a_driver_filter_that_follows_extras_sees_their_usage(extras):
    """Driver, its one reserved executor and two extras fill ``n0`` (4
    cpu): the next driver, which would have shared ``n0`` with the first
    application at its min, is packed on ``n1`` whole."""
    h, names = two_nodes()
    try:
        pods = h.dynamic_allocation_spark_pods("app-da", 1, 3)
        for pod in pods[: 2 + extras]:
            assert h.assert_success(h.schedule(pod, names)) == "n0"
        follower = h.static_allocation_spark_pods("app-next", 1)[0]
        node = h.assert_success(h.schedule(follower, names))
        slots = {r.node for r in h.get_resource_reservation("app-next").spec.reservations.values()}
        assert (node, slots) == (("n1", {"n1"}) if extras else ("n0", {"n0"}))
        assert h.extender.host_fallbacks() == 0
    finally:
        h.close()


def test_a_retired_application_leaves_no_soft_reservation_and_no_usage_behind():
    h, names = two_nodes()
    try:
        pods = h.dynamic_allocation_spark_pods("app-da", 1, 3)
        for pod in pods:
            h.assert_success(h.schedule(pod, names))
        store = h.server.soft_reservation_store
        assert (store.get_application_count(), store.get_active_extra_executor_count()) == (1, 2)
        for pod in reversed(pods):
            h.delete_pod(pod)
        assert h.wait_for_api(lambda: h.get_resource_reservation("app-da") is None)
        assert (store.get_application_count(), store.get_active_extra_executor_count()) == (0, 0)
        assert store.used_soft_reservation_resources() == {}
        snap = h.extender._tensor_snapshot.snapshot()
        assert not snap.usage.any() and not snap.overhead.any()
    finally:
        h.close()
