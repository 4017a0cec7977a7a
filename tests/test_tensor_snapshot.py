"""Tensor-snapshot fast-path parity: after arbitrary mutation sequences
(schedules, deaths, deletions, node churn), the event-driven integer
mirror must agree exactly with the Quantity-path recomputation, and
extender decisions through the fast path must equal the slow path."""

import random

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops.tensorize import _resources_to_base
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.resources import (
    node_scheduling_metadata_for_nodes,
)


def _slowpath_rows(harness, nodes):
    """The Quantity path's availability, as base-unit int rows."""
    usage = harness.server.resource_reservation_manager.get_reserved_resources()
    overhead = harness.server.overhead_computer.get_overhead(nodes)
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
        overhead = h.server.overhead_computer.get_overhead(nodes)
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
