"""The current driver's decode on arrays (ops/batch_adapter.py): the
served path's ``minimal_fragmentation_rows`` held equal, order included,
to the host oracle's ``packers.minimal_fragmentation_from_capacities``
on one ``NodeAndExecutorCapacity`` per node; the count-to-list helpers
against the per-node loops they replaced; and the served decode builds
no per-node object (``fast_path.decode``'s ``hostNodes`` / ``objects``)."""

import random

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops import capacity
from k8s_spark_scheduler_tpu.ops.batch_adapter import (
    build_reserved,
    counts_to_evenly_list,
    counts_to_tightly_list,
    min_frag_zone_decode,
    minimal_fragmentation_order,
    minimal_fragmentation_rows,
    names_of_rows,
    unclamped_caps,
)
from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuFifoSolver
from k8s_spark_scheduler_tpu.ops.packers import minimal_fragmentation_from_capacities
from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster
from k8s_spark_scheduler_tpu.tracing import Tracer
from k8s_spark_scheduler_tpu.types.resources import NodeSchedulingMetadata, Resources

UNBOUNDED = 2**62  # min_frag_unclamped_caps under a zero requirement


def oracle_rows(cap, k):
    """The oracle's list as rows: what ``minimal_fragmentation_assignment``
    was before the decode went to arrays (one object per node)."""
    if k == 0:
        return []
    capacities = [
        capacity.NodeAndExecutorCapacity(i, int(c)) for i, c in enumerate(cap) if c > 0
    ]
    nodes, ok = minimal_fragmentation_from_capacities(k, capacities)
    return nodes if ok else None


def array_rows(cap, k):
    rows = minimal_fragmentation_rows(np.asarray(cap, dtype=np.int64), k)
    return None if rows is None else rows.tolist()


# name: (capacities in priority order, k, the reference's list or None)
EDGES = {
    "all-equal-priority-order-decides": ([3, 3, 3, 3], 7, [0, 0, 0, 1, 1, 1, 2]),
    "max-class-ties-drained-in-order": ([1, 4, 2, 4, 4, 1], 9, [1, 1, 1, 1, 3, 3, 3, 3, 0]),
    "subset-branch-succeeds": ([2, 3, 10, 2], 4, [1, 1, 1, 0]),
    "subset-branch-fails-whole-list-serves": ([1, 9, 1], 4, [1, 1, 1, 1]),
    "k-equals-max": ([5, 2, 5, 1], 5, [0, 0, 0, 0, 0]),
    "one-node-fits-all-smallest-that-fits": ([9, 4, 6, 3], 4, [1, 1, 1, 1]),
    "nothing-fits": ([2, 1, 2], 6, None),
    "k-zero": ([0, 5, 1], 0, []),
    "k-zero-on-no-capacity": ([0, 0], 0, []),
    "no-positive-capacity": ([0, -3, 0], 2, None),
    "no-node": ([], 3, None),
    "unbounded-beside-small-serves": ([1, UNBOUNDED, 1], 3, [1, 1, 1]),
    "unbounded-beside-small-that-serve": ([1, UNBOUNDED, 2, 1], 3, [2, 2, 0]),
    "unbounded-loses-to-the-subset": ([3, UNBOUNDED, 2], 3, [0, 0, 0]),
    "unbounded-twice-first-in-priority": ([1, UNBOUNDED, UNBOUNDED], 40, [1] * 40),
    "drain-then-smaller-node-fits-the-rest": ([2, 5, 5, 3], 7, [1, 1, 1, 1, 1, 0, 0]),
    "drain-then-class-member-fits-the-rest": ([1, 5, 5, 5], 12, [1] * 5 + [2] * 5 + [3] * 2),
    "drain-across-classes": ([3, 2, 3, 1, 2], 11, [0, 0, 0, 2, 2, 2, 1, 1, 4, 4, 3]),
    "drain-hits-zero-exactly": ([2, 4, 4], 8, [1, 1, 1, 1, 2, 2, 2, 2]),
}


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_array_assignment_equals_the_oracle_on_the_named_edges(edge):
    cap, k, expected = EDGES[edge]
    assert oracle_rows(cap, k) == expected  # the edge is what its name says
    assert array_rows(cap, k) == expected


def random_capacities(rng):
    n = rng.choice([1, 2, 3, 5, 8, 13, 40, 200, 1000, 10000])
    top = rng.choice([1, 2, 3, 5, 12, 40, 95])
    cap = np.array([rng.randint(-1, top) for _ in range(n)], dtype=np.int64)
    if rng.random() < 0.3:  # mostly full cluster
        cap[np.array([rng.random() < 0.8 for _ in range(n)])] = 0
    if rng.random() < 0.2:
        cap[rng.randrange(n)] = UNBOUNDED
    return cap


@pytest.mark.parametrize("seed", range(24))
def test_array_assignment_equals_the_oracle_on_random_capacities(seed):
    rng = random.Random(9100 + seed)
    placed = 0
    for trial in range(25):
        cap, k = random_capacities(rng), rng.randint(0, 64)
        expected = oracle_rows(cap, k)
        assert array_rows(cap, k) == expected, f"trial {trial}: k={k} cap={cap.tolist()[:64]}"
        placed += expected is not None
    assert placed > 0


@pytest.mark.parametrize("seed", range(6))
def test_counts_to_lists_equal_the_per_node_loops_they_replaced(seed):
    rng = random.Random(620 + seed)
    for n in (1, 7, 300, 5000):
        names = [f"n{i}" for i in range(n)]
        counts = np.zeros(n, dtype=rng.choice([np.int32, np.int64]))
        for i in rng.sample(range(n), min(n, rng.randint(0, 31))):
            counts[i] = rng.randint(1, 6)
        tightly = []
        for name, c in zip(names, counts):
            if c > 0:
                tightly.extend([name] * int(c))
        assert counts_to_tightly_list(names, counts) == tightly
        evenly = [
            name for sweep in range(7) for name, c in zip(names, counts) if c > sweep
        ]
        assert counts_to_evenly_list(names, counts) == evenly
        rows = np.array([names.index(nm) for nm in evenly], dtype=np.int64)
        assert names_of_rows(names, rows) == (evenly, int(np.count_nonzero(counts)))
        # the Quantity-side reserved map walks the same rows
        one = Resources.of("1", "2Gi", "1")
        reserved = build_reserved(names, counts, names[0], Resources.of("3", "1Gi"), one)
        assert set(reserved) == {names[0], *tightly}
        for name in set(tightly) - {names[0]}:
            assert reserved[name].cpu.exact == tightly.count(name)


@pytest.mark.parametrize("strict", [True, False], ids=["strict", "corrected"])
@pytest.mark.parametrize("seed", range(4))
def test_zone_decode_counts_are_a_count_of_its_own_rows(seed, strict):
    rng = np.random.default_rng(77 + seed)
    decoded_any = 0
    for _ in range(20):
        n = int(rng.choice([4, 30, 500]))
        avail = rng.integers(-2, 40, size=(n, 3)).astype(np.int64)
        executor = rng.integers(0, 5, size=3).astype(np.int32)
        driver = rng.integers(0, 3, size=3).astype(np.int32)
        zone_ok = rng.random(n) < 0.7
        d_idx, k = int(rng.integers(0, n)), int(rng.integers(0, 33))
        decoded = min_frag_zone_decode(avail, executor, zone_ok, d_idx, driver, k, strict)
        if decoded is None:
            continue
        decoded_any += 1
        rows, counts, eff_counts = decoded
        expected = np.zeros(n, dtype=np.int64)
        for i in rows.tolist():
            expected[i] += 1
        assert len(rows) == k and zone_ok[rows].all()
        assert counts.shape == (n,) and (counts == expected).all()
        assert (eff_counts == (0 if strict else expected)).all()
    assert decoded_any > 0


def order_of_placements(avail, executor, driver, placements):
    """``minimal_fragmentation_order`` given only what disjoint placements
    show, ``placements`` as (driver row, rows emitted): their hosts, the
    executors on each, the hosts' capacities with each driver subtracted
    on its node; the rows in the order it reads, placement by placement."""
    hosts, on_each, groups, here = [], [], [], []
    for g, (d_idx, rows) in enumerate(placements):
        h, c = np.unique(np.asarray(rows, dtype=np.int64), return_counts=True)
        a = avail[h].astype(np.int64)
        a[h == d_idx] -= driver
        hosts.append(h)
        on_each.append(c)
        groups.append(np.full(len(h), g))
        here.append(a)
    hosts, on_each = np.concatenate(hosts), np.concatenate(on_each)
    cap = unclamped_caps(np.concatenate(here), executor)
    order = minimal_fragmentation_order(cap, hosts, np.concatenate(groups))
    return np.repeat(hosts[order], on_each[order]).tolist()


@pytest.mark.parametrize("edge", sorted(e for e in EDGES if EDGES[e][2]))
def test_emission_order_from_the_hosts_alone_on_the_named_edges(edge):
    cap, k, expected = EDGES[edge]
    # one dimension carries the capacities; the others ask for nothing
    avail = np.array([[c, 0, 0] for c in cap], dtype=np.int64)
    executor, driver = np.array([1, 0, 0]), np.zeros(3, dtype=np.int64)
    assert order_of_placements(avail, executor, driver, [(0, expected)]) == expected


@pytest.mark.parametrize("seed", range(8))
def test_emission_order_from_the_hosts_alone_equals_the_decode(seed):
    """What the single-AZ valve reads of a device pass's min-frag
    placements, one per zone: each drain's emission order, from its hosts'
    capacities (the driver subtracted on its node), zone after zone,
    equal to the whole decodes'."""
    rng = np.random.default_rng(4100 + seed)
    compared = 0
    for _ in range(40):
        n = int(rng.choice([3, 12, 60, 600]))
        top = int(rng.choice([3, 8, 40]))
        avail = rng.integers(-2, top, size=(n, 3)).astype(np.int64)
        executor = rng.integers(0, 4, size=3).astype(np.int64)
        driver = rng.integers(0, 3, size=3).astype(np.int64)
        zone = rng.integers(0, 3, size=n)
        exec_ok = rng.random(n) < 0.7
        placements = []
        for z in range(3):
            members = np.flatnonzero(zone == z)
            if members.size == 0:
                continue
            d_idx, k = int(rng.choice(members)), int(rng.integers(1, 33))
            decoded = min_frag_zone_decode(
                avail, executor, exec_ok & (zone == z), d_idx, driver, k, True
            )
            if decoded is not None:
                placements.append((d_idx, decoded[0].tolist()))
        if not placements:
            continue
        expected = [row for _, rows in placements for row in rows]
        assert order_of_placements(avail, executor, driver, placements) == expected
        compared += 1
    assert compared > 10


# -- the served decode ----------------------------------------------------------

SERVED_NODES = 2304


def served_cluster(rng):
    metadata = {
        f"node-{i:04d}": NodeSchedulingMetadata(
            available=Resources.of(str(rng.randint(2, 48)), f"{rng.randint(4, 96)}Gi"),
            schedulable=Resources.of("64", "128Gi"),
            zone_label=f"z{i % 3}",
        )
        for i in range(SERVED_NODES)
    }
    order = list(metadata)
    return tensorize_cluster(metadata, order, order)


def gang(rng):
    return AppDemand(
        Resources.of("1", "1Gi"),
        Resources.of(str(rng.randint(1, 7)), f"{rng.randint(2, 15)}Gi"),
        rng.randint(1, 31),
    )


@pytest.mark.parametrize(
    "policy", ["tightly-pack", "minimal-fragmentation", "distribute-evenly"]
)
def test_served_decode_builds_no_per_node_object(policy, monkeypatch):
    """``_decode_current`` behind the native lane at 2,304 nodes: no
    ``NodeAndExecutorCapacity`` is constructed, ``names`` is indexed
    once per hosting node, and ``fast_path.decode`` says so."""
    built = []
    original = capacity.NodeAndExecutorCapacity

    def counting(*args, **kwargs):
        built.append(args)
        return original(*args, **kwargs)

    monkeypatch.setattr(capacity, "NodeAndExecutorCapacity", counting)
    rng = random.Random(35)
    cluster = served_cluster(rng)
    solver = TpuFifoSolver(policy, backend="native")
    tracer = Tracer()
    for _ in range(6):
        earlier = [gang(rng) for _ in range(20)]
        current = gang(rng)
        with tracer.span("predicate") as root:
            outcome = solver.solve_tensor(cluster, earlier, [True] * len(earlier), current)
        assert outcome.supported and outcome.result.has_capacity
        placed = outcome.result.executor_nodes
        assert len(placed) == current.min_executor_count
        (decode,) = [c for c in root.children if c.name == "fast_path.decode"]
        assert decode.children == []
        assert decode.tags["hostNodes"] == len(set(placed))
        assert decode.tags["objects"] == decode.tags["hostNodes"] <= current.min_executor_count
    assert built == []


def test_the_counting_patch_sees_the_oracle_build_one_object_per_node(monkeypatch):
    """What the test above would count had the decode stayed on the
    oracle's objects: the patch reaches the constructor."""
    built = []
    original = capacity.NodeAndExecutorCapacity
    monkeypatch.setattr(
        capacity, "NodeAndExecutorCapacity", lambda *a: built.append(a) or original(*a)
    )
    assert oracle_rows([2, 0, 5, 1], 3) == [0, 0, 3]
    assert len(built) == 3
