"""Tensor-snapshot fast-path parity: after arbitrary mutation sequences
(schedules, deaths, deletions, node churn), the event-driven integer
mirror must agree exactly with the Quantity-path recomputation, and
extender decisions through the fast path must equal the slow path."""

import random

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops.tensorize import _resources_to_base
from k8s_spark_scheduler_tpu.scheduler import labels as L
from k8s_spark_scheduler_tpu.scheduler.overhead import node_overhead
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.resources import (
    node_scheduling_metadata_for_nodes,
    pod_to_resources,
)


def _reference_overhead(harness, nodes):
    """(overhead, non-schedulable overhead) of ``nodes`` read from scratch."""
    server = harness.server
    return node_overhead(
        server.pod_informer.list(), server.resource_reservation_manager, [n.name for n in nodes]
    )


def _slowpath_rows(harness, nodes):
    """The Quantity path's availability, as base-unit int rows."""
    usage = harness.server.resource_reservation_manager.get_reserved_resources()
    overhead, _ = _reference_overhead(harness, nodes)
    metadata = node_scheduling_metadata_for_nodes(nodes, usage, overhead)
    rows = {}
    for name, md in metadata.items():
        row, exact = _resources_to_base(md.available)
        assert exact
        rows[name] = np.array(row, np.int64)
    return rows


def _assert_snapshot_matches(harness):
    snap = harness.server.tensor_snapshot.snapshot()
    assert snap.exact
    nodes = harness.server.node_informer.list()
    expected = _slowpath_rows(harness, nodes)
    actual = {name: snap.avail[i] for i, name in enumerate(snap.names)}
    assert set(actual) == set(expected)
    for name in expected:
        assert (actual[name] == expected[name]).all(), (
            name,
            actual[name],
            expected[name],
        )


def test_snapshot_tracks_random_churn():
    h = Harness(binpack_algo="tightly-pack")
    try:
        rng = random.Random(8080)
        for i in range(6):
            h.new_node(f"n{i}", cpu="16", memory="16Gi", zone=f"z{i % 2}")
        nodes = [f"n{i}" for i in range(6)]

        live = []
        for step in range(60):
            action = rng.random()
            if action < 0.45 or not live:
                app_id = f"app-{step}"
                da = rng.random() < 0.3
                if da:
                    pods = h.dynamic_allocation_spark_pods(app_id, 1, rng.randint(2, 3))
                else:
                    pods = h.static_allocation_spark_pods(app_id, rng.randint(1, 3))
                result = h.schedule(pods[0], nodes)
                if result.node_names:
                    scheduled = [pods[0]]
                    for p in pods[1:]:
                        r = h.schedule(p, nodes)
                        if r.node_names:
                            scheduled.append(p)
                    live.append((app_id, scheduled))
            elif action < 0.7 and live:
                # kill a random executor
                app_id, pods = rng.choice(live)
                if len(pods) > 1:
                    victim = pods.pop(rng.randrange(1, len(pods)))
                    h.delete_pod(victim)
            else:
                # tear down a whole app (driver + executors)
                app_id, pods = live.pop(rng.randrange(len(live)))
                for p in pods:
                    try:
                        h.delete_pod(p)
                    except Exception:
                        pass
                h.wait_quiesced()
            if step % 10 == 0:
                _assert_snapshot_matches(h)
        _assert_snapshot_matches(h)
    finally:
        h.close()


def test_snapshot_node_churn():
    h = Harness(binpack_algo="tightly-pack")
    try:
        h.new_node("n1")
        h.new_node("n2")
        pods = h.static_allocation_spark_pods("app-1", 2)
        for p in pods:
            h.schedule(p, ["n1", "n2"])
        _assert_snapshot_matches(h)
        # node removed while carrying reservations, then re-added
        h.api.delete("Node", "default", "n2")
        _assert_snapshot_matches(h)
        h.new_node("n2")
        _assert_snapshot_matches(h)
        h.new_node("n3", cpu="32", memory="32Gi")
        _assert_snapshot_matches(h)
    finally:
        h.close()


def test_fast_path_decisions_match_slow_path_under_churn():
    """Two harnesses, same scenario sequence: tpu-batch (fast path) vs
    tightly-pack (slow path) must produce identical decisions."""
    rng_seed = 777
    results = {}
    for algo in ("tightly-pack", "tpu-batch"):
        h = Harness(binpack_algo=algo, is_fifo=True)
        try:
            rng = random.Random(rng_seed)
            for i in range(5):
                h.new_node(f"n{i}", cpu="8", memory="8Gi", zone=f"z{i % 2}")
            nodes = [f"n{i}" for i in range(5)]
            log = []
            live = []
            for step in range(40):
                if rng.random() < 0.6 or not live:
                    pods = h.static_allocation_spark_pods(
                        f"app-{step}", rng.randint(1, 4)
                    )
                    r = h.schedule(pods[0], nodes)
                    log.append((f"d{step}", tuple(r.node_names or [])))
                    if r.node_names:
                        placed = [pods[0]]
                        for p in pods[1:]:
                            er = h.schedule(p, nodes)
                            log.append((p.name, tuple(er.node_names or [])))
                            if er.node_names:
                                placed.append(p)
                        live.append(placed)
                else:
                    placed = live.pop(rng.randrange(len(live)))
                    for p in placed:
                        try:
                            h.delete_pod(p)
                        except Exception:
                            pass
                    # drain the async write-back before continuing: the
                    # transient local/server divergence is reference-
                    # equivalent but timing-dependent, and this test
                    # compares two runs step-for-step
                    h.wait_quiesced()
                    log.append(("teardown", len(placed)))
            results[algo] = log
        finally:
            h.close()
    assert results["tightly-pack"] == results["tpu-batch"]


def _label_priority_cases():
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder

    dlp = LabelPriorityOrder("pool", ["reserved", "spot"])
    elp = LabelPriorityOrder("pool", ["spot", "reserved"])
    # asymmetric configs matter: an executor-only re-sort must NOT
    # perturb the driver rank order (and vice versa)
    return [(dlp, elp), (dlp, None), (None, elp)]


@pytest.mark.parametrize("dlp,elp", _label_priority_cases())
def test_fast_path_label_priority_order_matches_nodesorter(dlp, elp):
    """build_cluster_tensor's per-role label re-sort must replicate the
    NodeSorter's stable comparator sort exactly (nodesorting.go:161-180):
    same executor array order, same driver rank order, including nodes
    with unlisted or missing label values."""
    from k8s_spark_scheduler_tpu.ops.fast_path import build_cluster_tensor
    from k8s_spark_scheduler_tpu.ops.nodesort import NodeSorter
    from k8s_spark_scheduler_tpu.ops.tensorize import INT32_SAFE

    h = Harness(
        binpack_algo="tpu-batch",
        driver_prioritized_node_label=dlp,
        executor_prioritized_node_label=elp,
    )
    try:
        rng = random.Random(5)
        pools = ["reserved", "spot", "other", None]
        names = []
        for i in range(12):
            pool = pools[i % 4]
            h.new_node(
                f"n{i:02d}",
                cpu=str(rng.randint(2, 16)),
                memory=f"{rng.randint(2, 32)}Gi",
                zone=f"z{i % 3}",
                labels={"pool": pool} if pool else {},
            )
            names.append(f"n{i:02d}")
        candidates = names[:9]
        driver = h.static_allocation_spark_pods("app-lp", 2)[0]

        snap = h.server.tensor_snapshot.snapshot()
        built = build_cluster_tensor(
            snap, driver, candidates,
            driver_label_priority=dlp, executor_label_priority=elp,
        )
        assert built is not None
        cluster, _zones = built

        nodes = h.server.node_informer.list()
        usage = h.server.resource_reservation_manager.get_reserved_resources()
        overhead, _ = _reference_overhead(h, nodes)
        metadata = node_scheduling_metadata_for_nodes(nodes, usage, overhead)
        sorter = NodeSorter(dlp, elp)
        expect_driver, expect_executor = sorter.potential_nodes(metadata, candidates)

        got_executor = [
            n for n, ok in zip(cluster.node_names, cluster.exec_ok) if ok
        ]
        assert got_executor == expect_executor

        ranked = [
            (rank, n)
            for n, rank in zip(cluster.node_names, cluster.driver_rank)
            if rank < INT32_SAFE
        ]
        got_driver = [n for _, n in sorted(ranked)]
        assert got_driver == expect_driver
    finally:
        h.close()


def test_fast_path_decisions_match_slow_path_with_label_priority():
    """End-to-end: with per-role label priorities configured the fast
    path must stay engaged and produce the slow path's exact decisions."""
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder

    dlp = LabelPriorityOrder("pool", ["reserved", "spot"])
    elp = LabelPriorityOrder("pool", ["spot", "reserved"])
    results = {}
    for algo in ("tightly-pack", "tpu-batch"):
        h = Harness(
            binpack_algo=algo,
            is_fifo=True,
            driver_prioritized_node_label=dlp,
            executor_prioritized_node_label=elp,
        )
        try:
            rng = random.Random(31337)
            pools = ["reserved", "spot", "other"]
            for i in range(6):
                h.new_node(
                    f"n{i}",
                    cpu="8",
                    memory="8Gi",
                    zone=f"z{i % 2}",
                    labels={"pool": pools[i % 3]},
                )
            nodes = [f"n{i}" for i in range(6)]
            log = []
            live = []
            for step in range(30):
                if rng.random() < 0.6 or not live:
                    pods = h.static_allocation_spark_pods(
                        f"app-{step}", rng.randint(1, 4)
                    )
                    r = h.schedule(pods[0], nodes)
                    log.append((f"d{step}", tuple(r.node_names or [])))
                    if r.node_names:
                        placed = [pods[0]]
                        for p in pods[1:]:
                            er = h.schedule(p, nodes)
                            log.append((p.name, tuple(er.node_names or [])))
                            if er.node_names:
                                placed.append(p)
                        live.append(placed)
                else:
                    placed = live.pop(rng.randrange(len(live)))
                    for p in placed:
                        try:
                            h.delete_pod(p)
                        except Exception:
                            pass
                    h.wait_quiesced()
                    log.append(("teardown", len(placed)))
            if algo == "tpu-batch":
                # the fast lane must have engaged at least once
                calls = []
                original = h.extender._try_fast_driver_path

                def spy(*args, **kwargs):
                    out = original(*args, **kwargs)
                    calls.append(out is not None)
                    return out

                h.extender._try_fast_driver_path = spy
                probe = h.static_allocation_spark_pods("app-probe", 1)[0]
                h.schedule(probe, nodes)
                assert calls and calls[-1], (
                    "fast path fell back with label priority configured"
                )
                log.append(("probe", None))
            else:
                h.schedule(
                    h.static_allocation_spark_pods("app-probe", 1)[0], nodes
                )
                log.append(("probe", None))
            results[algo] = log
        finally:
            h.close()
    assert results["tightly-pack"] == results["tpu-batch"]


def test_fast_path_used_for_tpu_batch():
    """The fast path must actually engage (not silently fall back)."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1")
        h.new_node("n2")
        calls = []
        original = h.extender._try_fast_driver_path

        def spy(*args, **kwargs):
            out = original(*args, **kwargs)
            calls.append(out is not None)
            return out

        h.extender._try_fast_driver_path = spy
        driver = h.static_allocation_spark_pods("app-f", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))
        assert calls and calls[-1], "fast path did not engage"
    finally:
        h.close()


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "uncacheable"])
def test_executor_candidate_rows_are_kept_by_their_exact_key_and_no_more_of_them(keyed):
    """`executor_rows_keyed` keeps what an executor's reschedule needs of
    the node table per (structure revision, candidate tuple, executor
    label priority): the same object for an equal list, another for
    another list or priority, nothing for a snapshot no mirror stamped
    (`structure_key[0] < 0`), and never more entries than the bound."""
    import dataclasses

    from k8s_spark_scheduler_tpu.ops import fast_path
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder

    h = Harness(binpack_algo="tightly-pack")
    try:
        names = [h.new_node(f"n{i}", zone=f"z{i % 2}").name for i in range(5)]
        snap = h.extender._tensor_snapshot.snapshot()
        if not keyed:
            snap = dataclasses.replace(snap, structure_key=(-1, -1))
        size = len(fast_path._EXECUTOR_ROWS_CACHE)
        first = fast_path.executor_rows_keyed(snap, names, None)
        again = fast_path.executor_rows_keyed(snap, tuple(names), None)  # a tuple or a list, by value
        assert (again is first) == keyed
        assert len(fast_path._EXECUTOR_ROWS_CACHE) == min(size + keyed, fast_path._PREP_CACHE_MAX)
        assert first.exec_ok.tolist() == [True] * 5 and sorted(first.by_zone.tolist()) == [0, 1, 2, 3, 4]
        assert snap.zone_id[first.by_zone].tolist() == sorted(snap.zone_id.tolist())  # one zone after another
        other = fast_path.executor_rows_keyed(snap, names[:3] + ["unknown", names[0]], None)
        assert other is not first and other.exec_ok.tolist() == [True] * 3 + [False] * 2
        ranked = fast_path.executor_rows_keyed(snap, names, LabelPriorityOrder("pool", ["a"]))
        assert ranked is not first and ranked.label_rank.tolist() == [1] * 5 and first.label_rank is None
        for i in range(fast_path._PREP_CACHE_MAX + 3):
            fast_path.executor_rows_keyed(snap, names + [f"ghost-{i}"], None)
        assert len(fast_path._EXECUTOR_ROWS_CACHE) <= fast_path._PREP_CACHE_MAX
    finally:
        h.close()


def _full_executor_order(snap, idx, label_rank):
    """The whole executor priority order over the rows `idx`, by sorting,
    as the lane computed it before it selected (the oracle): zones by
    their exact totals then name, rows by (zone, memory, cpu, name),
    ready ∧ ¬unschedulable kept, a stable re-sort by label rank."""
    avail = snap.avail[idx]
    zone_id = snap.zone_id[idx]
    totals = np.zeros((len(snap.zone_names), 2), dtype=object)  # Python ints: no width to overflow
    for z, (cpu, mem, _) in zip(zone_id, avail.tolist()):
        totals[z] += np.array([mem, cpu], dtype=object)
    zones = sorted(set(zone_id.tolist()), key=lambda z: (totals[z][0], totals[z][1], snap.zone_names[z]))
    priority = {z: i for i, z in enumerate(zones)}
    order = sorted(
        range(len(idx)),
        key=lambda i: (priority[int(zone_id[i])], int(avail[i, 1]), int(avail[i, 0]), snap.names[idx[i]]),
    )
    order = [i for i in order if snap.ready[idx[i]] and not snap.unschedulable[idx[i]]]
    if label_rank is not None:
        order = sorted(order, key=lambda i: int(label_rank[idx[i]]))
    return [int(idx[i]) for i in order]


@pytest.mark.parametrize("seed", range(6))
def test_the_selection_is_the_head_of_the_sorted_order_among_the_rows_that_fit(seed):
    """`first_in_executor_order` against the sort it replaced, on drawn
    snapshots of 300 rows with many ties, sidelined nodes, a candidate
    list with duplicates, strangers and gaps, memory near 2**53 bytes a
    zone, with and without label ranks, leading keys and a mask that
    admits nothing."""
    from k8s_spark_scheduler_tpu.ops import fast_path
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshot

    rng = np.random.default_rng(seed)
    n, zones = 300, ["zone-c", "zone-a", "zone-b", "zone-d"]
    names = [f"node-{i:03d}" for i in rng.permutation(n)]
    big = 2**53 // 60  # 75 rows a zone: a zone's memory total lies near 2**53
    alloc = np.stack(
        [rng.choice([4000, 8000, 16000], n), big + rng.choice([0, 1, 2**30], n), np.zeros(n, np.int64)], axis=1
    ).astype(np.int64)
    usage = np.stack([rng.choice([0, 1000, 4000], n), rng.choice([0, 1, 2**30], n), np.zeros(n, np.int64)], axis=1)
    snap = TensorSnapshot(
        names=names, allocatable=alloc, usage=usage.astype(np.int64),
        overhead=np.zeros((n, 3), np.int64), zone_names=zones, zone_id=(np.arange(n) % 4).astype(np.int32),
        ready=rng.random(n) > 0.1, unschedulable=rng.random(n) < 0.1,
        labels=[{"pool": str(rng.choice(["a", "b", "c"]))} for _ in range(n)], exact=True,
        res_entries=np.zeros(n, bool), name_rank=np.argsort(np.argsort(np.array(names, dtype=object))).astype(np.int64),
    )
    for elp in (None, LabelPriorityOrder("pool", ["b", "a"])):
        listed = [names[i] for i in rng.choice(n, size=260)] + ["a-stranger"]
        rows = fast_path.executor_rows_keyed(snap, listed, elp)  # uncacheable: no mirror stamped this snapshot
        idx = np.array(sorted({snap.name_index[nm] for nm in listed if nm in snap.name_index}))
        order = _full_executor_order(snap, idx, rows.label_rank)
        assert order, "no executor candidate was drawn"
        avail = snap.avail
        for want in ([1000, 1, 0], [8000, big, 0], [16000, big + 2**30, 0], [10**9, 0, 0]):
            fits = fast_path.rows_fitting(avail, np.array(want, dtype=np.int64))
            assert fits.tolist() == (avail >= np.array(want)).all(axis=1).tolist()
            expected = next((r for r in order if fits[r]), -1)
            assert fast_path.first_in_executor_order(snap, rows, avail, fits) == expected
            # leading keys come before the order: a drawn class, then the order within it
            lead = rng.integers(0, 3, n)
            expected = min((r for r in order if fits[r]), key=lambda r: lead[r], default=-1)
            assert fast_path.first_in_executor_order(snap, rows, avail, fits, (lead,)) == expected
        assert fast_path.first_in_executor_order(snap, rows, avail, np.zeros(n, bool)) == -1


def test_the_zones_are_ranked_by_exact_integer_totals():
    """Two zones whose free memory differs by 5 bytes in 2**56: a float64
    total cannot tell them apart and would let cpu decide, the other way."""
    from k8s_spark_scheduler_tpu.ops import fast_path
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshot

    names = ["a0", "b0", "a1", "b1"]
    memory = np.array([2**55 + 1, 2**55, 2**55 + 4, 2**55], dtype=np.int64)  # zone a: 5 bytes more
    cpu = np.array([1000, 9000, 1000, 9000], dtype=np.int64)                 # ... and less cpu
    snap = TensorSnapshot(
        names=names, allocatable=np.stack([cpu, memory, np.zeros(4, np.int64)], axis=1),
        usage=np.zeros((4, 3), np.int64), overhead=np.zeros((4, 3), np.int64), zone_names=["a", "b"],
        zone_id=np.array([0, 1, 0, 1], np.int32), ready=np.ones(4, bool), unschedulable=np.zeros(4, bool),
        labels=[{}] * 4, exact=True, res_entries=np.zeros(4, bool), name_rank=np.array([0, 2, 1, 3], np.int64),
    )
    assert float(memory[[0, 2]].sum()) == float(memory[[1, 3]].sum())  # what a float sum would see
    rows = fast_path.executor_rows_keyed(snap, names, None)
    assert names[fast_path.first_in_executor_order(snap, rows, snap.avail, np.ones(4, bool))] == "b0"


# -- the overhead fold against the full walk ----------------------------------


class _NoFeed:
    """Stands for the informers and the reservation stores: the tests
    call the mirror's handlers themselves."""

    def add_event_handler(self, **_):
        pass

    def add_change_observer(self, _):
        pass


def _bare_mirror():
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshotCache

    return TensorSnapshotCache(_NoFeed(), _NoFeed(), _NoFeed(), _NoFeed())


class _Reservations:
    """What the reservation manager reads, as plain dicts fed the same
    events as the mirror: the ResourceReservation of each (namespace,
    app id) and a count of soft reservations per (app id, pod name)."""

    def __init__(self):
        self.hard = {}
        self.soft = {}

    def get(self, namespace, name):
        return self.hard.get((namespace, name))

    def executor_has_soft_reservation(self, pod):
        return self.soft.get((pod.labels.get(L.SPARK_APP_ID_LABEL), pod.name), 0) > 0

    def manager(self):
        """The reservation manager itself over these dicts: its
        ``pod_has_reservation`` is the rule under test."""
        from k8s_spark_scheduler_tpu.scheduler.reservations_manager import ResourceReservationManager

        return ResourceReservationManager(self, self, None, _NoFeed())


def _full_walk(mirror, pods, held):
    """The overhead rows a walk over every pod gives (overhead.go:120-153),
    read with the reservation manager's rule: each pod it does not hold
    adds its requests on its node, if the mirror knows the node."""
    rrm = held.manager()
    overhead = np.zeros((len(mirror._node_names), 3), np.int64)
    for pod in pods:
        node = mirror._node_slot.get(pod.node_name)
        if node is not None and not rrm.pod_has_reservation(pod):
            overhead[node] += _resources_to_base(pod_to_resources(pod))[0]
    return overhead


def _same(got, expected):
    """Two NodeGroupResources equal node for node, in exact Quantities."""
    return got.keys() == expected.keys() and all(got[n].eq(expected[n]) for n in expected)


def _folded(mirror):
    """The pod slots one snapshot folds, read off its ``mirror.overhead`` span."""
    from k8s_spark_scheduler_tpu import tracing
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import OVERHEAD_SPAN

    with tracing.Tracer().span("test") as root:
        mirror.snapshot()
    return sum(c.tags["rows"] for c in root.children if c.name == OVERHEAD_SPAN)


def _pod(namespace, name, node, requests, labels=None, scheduler_name=""):
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase

    return Pod(
        meta=ObjectMeta(name=name, namespace=namespace, labels=labels or {}), node_name=node,
        scheduler_name=scheduler_name, containers=[Container("main", requests)], phase=PodPhase.RUNNING,
    )


def _reservation(namespace, name, nodes, pods, requests):
    from k8s_spark_scheduler_tpu.types.objects import (
        ObjectMeta, Reservation, ResourceReservation, ResourceReservationSpec, ResourceReservationStatus,
    )

    return ResourceReservation(
        meta=ObjectMeta(name=name, namespace=namespace),
        spec=ResourceReservationSpec(
            reservations={f"r{i}": Reservation.for_resources(node, requests) for i, node in enumerate(nodes)}
        ),
        status=ResourceReservationStatus(pods={f"r{i}": pod for i, pod in enumerate(pods)}),
    )


_FOLD_COVERAGE = {
    "pod before its node", "node slot reused by another name before a fold", "pod moved", "pod unbound",
    "reservation moved its pods", "pod in two reservations", "pod listed by another application's reservation",
    "unlabelled pod listed by a reservation", "soft name of a driver", "soft name of another application's pod",
    "soft name in two namespaces", "soft count down from two", "reserved pod deleted",
    "deleted reserved pod bound again", "inexact quantity",
}


class _Churn:
    """Seeded random events into a bare mirror and the same events into
    plain dicts of what the reservations hold: the log that replays them
    into a fresh mirror, and the situations the sequence covered."""

    NAMESPACES = ("ns-a", "ns-b")
    NODES = [f"n{i}" for i in range(5)]
    PODS = [f"p{i}" for i in range(8)]
    APPS = ["rr0", "rr1", "rr2", "rr3"]  # a reservation is named for its application

    def __init__(self, seed):
        from k8s_spark_scheduler_tpu.types.resources import Resources

        self.rng = random.Random(seed)
        self.mirror = _bare_mirror()
        self.held = _Reservations()
        self.rrm = self.held.manager()
        self.log = []
        self.nodes = set()
        self.pods = {}          # (namespace, name) -> the pod as last seen, node_name "" unbound
        self.soft = {}          # (app id, pod name) -> [(node, requests)] held
        self.freed = {}         # node slot -> the name deleted from it since the last snapshot
        self.deleted_reserved = {}  # (namespace, name) -> the labels of a pod deleted while reserved
        self.covered = set()
        self.requests = [Resources.of("100m", "128Mi"), Resources.of("1", "1Gi"), Resources.of("250m", "512Mi", "1")]
        self.inexact = Resources.of("100m", "1500m")  # memory not a whole byte

    def apply(self, handler, *args):
        self.log.append((handler, args))
        getattr(self.mirror, handler)(*args)

    def named(self, name):
        return [pod for (_, pod_name), pod in self.pods.items() if pod_name == name]

    def step(self):
        from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
        from k8s_spark_scheduler_tpu.types.resources import Resources

        rng, action = self.rng, self.rng.random()
        if action < 0.12:
            name = rng.choice(self.NODES)
            if name not in self.nodes and name in {pod.node_name for pod in self.pods.values()}:
                self.covered.add("pod before its node")
            node = Node(meta=ObjectMeta(name=name, labels={"pool": rng.choice("ab")}),
                        allocatable=Resources.of("16", "64Gi"))
            self.apply("_on_node", node)
            if self.freed.get(self.mirror._node_slot[name], name) != name:
                self.covered.add("node slot reused by another name before a fold")
            self.nodes.add(name)
        elif action < 0.2 and self.nodes:
            name = rng.choice(sorted(self.nodes))
            self.freed[self.mirror._node_slot[name]] = name
            self.apply("_on_node_delete", Node(meta=ObjectMeta(name=name)))
            self.nodes.discard(name)
        elif action < 0.5:
            key = (rng.choice(self.NAMESPACES), rng.choice(self.PODS))
            node = "" if rng.random() < 0.15 else rng.choice(self.NODES)
            was = self.pods[key].node_name if key in self.pods else ""
            if was and node and node != was:
                self.covered.add("pod moved")
            if was and not node:
                self.covered.add("pod unbound")
            requests = rng.choice(self.requests)
            if rng.random() < 0.03:
                requests = self.inexact
                if node:  # the mirror tables bound pods alone
                    self.covered.add("inexact quantity")
            app = rng.choice(self.APPS + [None])
            labels = {} if app is None else {
                L.SPARK_APP_ID_LABEL: app, L.SPARK_ROLE_LABEL: rng.choice([L.DRIVER, L.EXECUTOR, L.EXECUTOR]),
            }
            if key in self.deleted_reserved and rng.random() < 0.5:
                labels = self.deleted_reserved[key]  # the same pod again, by name and application
            scheduler = L.SPARK_SCHEDULER_NAME if rng.random() < 0.7 else "default-scheduler"
            pod = _pod(*key, node, requests, labels, scheduler)
            if node and key in self.deleted_reserved and self.rrm.pod_has_reservation(pod):
                self.covered.add("deleted reserved pod bound again")
            self.apply("_on_pod", pod)
            if node or key in self.pods:
                self.pods[key] = pod
        elif action < 0.6 and self.pods:
            key = rng.choice(sorted(self.pods))
            pod = self.pods.pop(key)
            if self.rrm.pod_has_reservation(pod):
                self.covered.add("reserved pod deleted")
                self.deleted_reserved[key] = pod.labels
            self.apply("_on_pod_delete", pod)
        elif action < 0.85:
            name = rng.choice(self.APPS)
            old = next((rr for (_, app), rr in self.held.hard.items() if app == name), None)
            namespace = old.namespace if old is not None else rng.choice(self.NAMESPACES)
            new = None
            if old is None or rng.random() < 0.7:
                pods = rng.sample(self.PODS, rng.randint(1, 3))
                nodes = [rng.choice(self.NODES) for _ in pods]
                new = _reservation(namespace, name, nodes, pods, rng.choice(self.requests))
            before = set(old.status.pods.values()) if old is not None else set()
            after = set(new.status.pods.values()) if new is not None else set()
            if old is not None and before != after:
                self.covered.add("reservation moved its pods")
            others = {
                (rr.namespace, pod) for (_, app), rr in self.held.hard.items() if app != name
                for pod in rr.status.pods.values()
            }
            if {(namespace, pod) for pod in before | after} & others:
                self.covered.add("pod in two reservations")
            for pod_name in after:
                listed = self.pods.get((namespace, pod_name))
                if listed is not None and listed.labels.get(L.SPARK_APP_ID_LABEL) is None:
                    self.covered.add("unlabelled pod listed by a reservation")
                elif listed is not None and listed.labels[L.SPARK_APP_ID_LABEL] != name:
                    self.covered.add("pod listed by another application's reservation")
            self.apply("_on_rr_change", old, new)
            if new is None:
                del self.held.hard[(namespace, name)]
            else:
                self.held.hard[(namespace, name)] = new
        else:
            app, name = rng.choice(self.APPS), rng.choice(self.PODS)
            held = self.soft.setdefault((app, name), [])
            if held and rng.random() < 0.5:
                if len(held) == 2:
                    self.covered.add("soft count down from two")
                node, requests = held.pop(rng.randrange(len(held)))
                sign = -1
            else:
                node, requests = rng.choice(self.NODES), rng.choice(self.requests)
                held.append((node, requests))
                sign = +1
            for pod in self.named(name):
                if pod.labels.get(L.SPARK_APP_ID_LABEL) not in (None, app):
                    self.covered.add("soft name of another application's pod")
                elif pod.labels.get(L.SPARK_ROLE_LABEL) == L.DRIVER:
                    self.covered.add("soft name of a driver")
            if all((namespace, name) in self.pods for namespace in self.NAMESPACES):
                self.covered.add("soft name in two namespaces")
            self.held.soft[(app, name)] = len(held)
            self.apply("_on_soft_change", app, node, requests, sign, name)

    def replayed(self):
        fresh = _bare_mirror()
        for handler, args in self.log:
            getattr(fresh, handler)(*args)
        return fresh


@pytest.mark.parametrize("seed,answer", [
    *(pytest.param(seed, "rows", id=str(seed)) for seed in range(6)),
    *(pytest.param(seed, "non-schedulable", id=f"non-schedulable-{seed}") for seed in range(6)),
])
def test_the_overhead_fold_equals_the_full_walk_after_every_snapshot(seed, answer):
    """Every snapshot's overhead is a from-scratch walk with the
    reservation manager's rule, bit for bit, and the class digest is what
    a mirror that saw the same events and folded them once, at its first
    snapshot, stamps; or, in exact Quantities, the mirror's overhead and
    non-schedulable answers are what ``node_overhead`` reads."""
    churn = _Churn(seed)
    snapshots = 0
    for _ in range(400):
        churn.step()
        if churn.rng.random() < 0.25:
            mirror = churn.mirror
            snap = mirror.snapshot()
            assert not mirror._dirty_pods
            if answer == "rows":
                assert np.array_equal(mirror._node_overhead, _full_walk(mirror, churn.pods.values(), churn.held))
                live = [mirror._node_slot[name] for name in snap.names]
                assert np.array_equal(snap.overhead, mirror._node_overhead[live])
                fresh = churn.replayed()
                assert snap.class_digest[1] == fresh.snapshot().class_digest[1]
                assert snap.exact == fresh._exact == ("inexact quantity" not in churn.covered)
            else:
                names = sorted(churn.nodes)
                overhead, non_schedulable = node_overhead(churn.pods.values(), churn.rrm, names)
                assert _same(mirror.overhead(names), overhead)
                assert _same(mirror.non_schedulable_overhead(names), non_schedulable)
            churn.freed.clear()
            snapshots += 1
    assert snapshots > 50
    assert churn.covered == _FOLD_COVERAGE


# (pod labels, the application whose reservation lists the pod, the
# application whose soft reservation holds its name): none holds it
_NOT_HELD = {
    "listed by another application's reservation": ({L.SPARK_APP_ID_LABEL: "app-a", L.SPARK_ROLE_LABEL: L.EXECUTOR}, "app-b", None),
    "listed by a reservation without an app id": ({}, "app-a", None),
    "a driver with a soft-reserved executor's name": ({L.SPARK_APP_ID_LABEL: "app-a", L.SPARK_ROLE_LABEL: L.DRIVER}, None, "app-a"),
    "another application's executor with a soft-reserved name": (
        {L.SPARK_APP_ID_LABEL: "app-b", L.SPARK_ROLE_LABEL: L.EXECUTOR}, None, "app-a",
    ),
}


@pytest.mark.parametrize("case", sorted(_NOT_HELD))
def test_a_reservation_holds_only_its_own_applications_pods(case):
    """A pod counts as overhead unless its own application's reservation
    lists it, or, for an executor, its own application's soft reservation
    holds it (resourcereservations.go:115-132): the mirror's rows and
    answers count each of these pods, as the reference does."""
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import Resources

    labels, listed_by, soft_for = _NOT_HELD[case]
    mirror, held = _bare_mirror(), _Reservations()
    mirror._on_node(Node(meta=ObjectMeta(name="n0"), allocatable=Resources.of("16", "64Gi")))
    pod = _pod("ns", "p", "n0", Resources.of("1", "1Gi"), labels, L.SPARK_SCHEDULER_NAME)
    mirror._on_pod(pod)
    if listed_by is not None:
        held.hard[("ns", listed_by)] = _reservation("ns", listed_by, ["n0"], ["p"], Resources.of("2", "2Gi"))
        mirror._on_rr_change(None, held.hard[("ns", listed_by)])
    if soft_for is not None:
        held.soft[(soft_for, "p")] = 1
        mirror._on_soft_change(soft_for, "n0", Resources.of("1", "1Gi"), +1, "p")
    overhead, non_schedulable = node_overhead([pod], held.manager(), ["n0"])
    assert overhead["n0"].eq(Resources.of("1", "1Gi")) and non_schedulable["n0"].eq(Resources.zero())
    assert mirror.snapshot().overhead.tolist() == [_resources_to_base(overhead["n0"])[0]]
    assert _same(mirror.overhead(["n0"]), overhead)
    assert _same(mirror.non_schedulable_overhead(["n0"]), non_schedulable)


def test_the_quantity_answers_equal_the_reference_under_inexact_quantities():
    """Requests no int row holds exactly (a micro-cpu, a milli-byte): the
    mirror is inexact, and its Quantity answers are still the reference's
    to the last fraction, as they sum each pod's own Resources."""
    from k8s_spark_scheduler_tpu.types.resources import Resources

    h = Harness(binpack_algo="tpu-batch")
    try:
        for i in range(3):
            h.new_node(f"n{i}")
        for name, node, cpu, memory, scheduler in [
            ("d0", "n0", "100u", "500m", "default-scheduler"),
            ("d1", "n0", "1", "1500m", "default-scheduler"),
            ("d2", "n1", "333u", "1Gi", "default-scheduler"),
            ("stray", "n1", "100u", "500m", L.SPARK_SCHEDULER_NAME),  # ours, yet no reservation holds it
        ]:
            h.create_pod(_pod("kube-system", name, node, Resources.of(cpu, memory), scheduler_name=scheduler))
        mirror = h.server.tensor_snapshot
        assert not mirror.snapshot().exact
        nodes = h.server.node_informer.list()
        names = [n.name for n in nodes]
        overhead, non_schedulable = _reference_overhead(h, nodes)
        assert _same(mirror.overhead(names), overhead)
        assert _same(mirror.non_schedulable_overhead(names), non_schedulable)
        assert overhead["n0"].eq(Resources.of("1000100u", "2"))  # 500m + 1500m bytes: two whole bytes
        assert not overhead["n1"].eq(non_schedulable["n1"])
        assert overhead["n2"].eq(Resources.zero())
    finally:
        h.close()


def test_quantity_answers_asked_beside_pod_events_end_equal_to_the_reference():
    """Readers ask ``non_schedulable_overhead`` while writers bind, move
    and delete pods, with the interpreter switching threads every 10 µs:
    no reader fails, and once the writers stop the answer is the
    reference's (a lost or torn update would leave it off)."""
    import sys
    import threading

    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import Resources

    mirror, held = _bare_mirror(), _Reservations()
    names = [f"n{i}" for i in range(8)]
    for name in names:
        mirror._on_node(Node(meta=ObjectMeta(name=name), allocatable=Resources.of("64", "256Gi")))
    pods, errors, stop = {}, [], threading.Event()

    def write(w):
        rng = random.Random(w)
        for i in range(400):
            key = ("kube-system", f"w{w}-p{rng.randrange(40)}")
            if rng.random() < 0.25:
                mirror._on_pod_delete(_pod(*key, "", Resources.zero()))
                pods.pop(key, None)
            else:
                pod = _pod(*key, rng.choice(names), Resources.of(f"{rng.randint(1, 9)}00m", "1Gi"),
                           scheduler_name="default-scheduler")
                mirror._on_pod(pod)
                pods[key] = pod

    def read():
        try:
            while not stop.is_set():
                mirror.non_schedulable_overhead(names)
        except Exception as err:  # a reader must never fail
            errors.append(err)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=read) for _ in range(2)]
        writers = [threading.Thread(target=write, args=(w,)) for w in range(6)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers + writers) and errors == []
    _, non_schedulable = node_overhead(pods.values(), held.manager(), names)
    assert _same(mirror.non_schedulable_overhead(names), non_schedulable)


def test_one_event_folds_its_own_slots_on_a_mirror_of_fifty_thousand_bound_pods():
    """The fold follows the delta, not the table: a guard on the count of
    slots folded, which a return to the whole walk breaks at any speed."""
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import Resources

    mirror = _bare_mirror()
    for i in range(100):
        mirror._on_node(Node(meta=ObjectMeta(name=f"n{i}"), allocatable=Resources.of("64", "256Gi")))
    requests = Resources.of("100m", "128Mi")
    labels = {L.SPARK_APP_ID_LABEL: "app", L.SPARK_ROLE_LABEL: L.EXECUTOR}
    pods = {f"p{i}": _pod("ns", f"p{i}", f"n{i % 100}", requests, labels) for i in range(50_000)}
    for pod in pods.values():
        mirror._on_pod(pod)
    assert _folded(mirror) == 50_000  # start-up: the whole table, once
    assert _folded(mirror) == 0
    pods["p7"] = _pod("ns", "p7", "n8", requests, labels)  # a bound pod moves
    mirror._on_pod(pods["p7"])
    assert _folded(mirror) == 1
    held = _Reservations()
    held.hard[("ns", "app")] = _reservation("ns", "app", ["n1", "n2", "n3"], ["p1", "p2", "p3"], requests)
    mirror._on_rr_change(None, held.hard[("ns", "app")])
    assert _folded(mirror) == 3
    assert np.array_equal(mirror._node_overhead, _full_walk(mirror, pods.values(), held))

