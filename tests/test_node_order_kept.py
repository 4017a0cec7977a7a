"""The node priority order kept between tensor builds (ops/fast_path.py).

`build_cluster_tensor` keeps the node order of each prep entry with the
selected rows' allocatable, usage and overhead it was sorted from; a
later request under the same key takes the order as it is where none of
those rows changed (`nodeOrder=kept`) and sorts every row again where one
did, or where the node table changed (`rebuilt`).  Every ClusterTensor it
hands out has to equal the whole sort's, field by field: the oracle here
is the same call on an unkeyed snapshot, which always sorts whole."""

import dataclasses
import itertools
import sys
import threading

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.ops import fast_path
from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder
from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshot
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.tracing import Tracer
from k8s_spark_scheduler_tpu.tracing.profiling import default_profiler

GIB = 2**30
ZONES = ["us-east-1a", "us-east-1b", "us-east-1c"]
GROUPS = ["batch-medium-priority", "batch-high-priority"]
_MIRRORS = itertools.count(1000)


class Cluster:
    """A node table and its usage, stamped like the tensor mirror stamps
    its snapshots: a structure revision bumped by node events, a content
    sequence bumped by every change."""

    def __init__(self, n, seed):
        self.rng = np.random.default_rng(seed)
        self.instance = next(_MIRRORS)
        self.structure = 0
        self.seq = 0
        self.names = [f"node-{i:05d}" for i in self.rng.permutation(n)]
        # two allocatable classes, so that many rows tie on (memory, cpu)
        big = self.rng.random(n) < 0.5
        self.alloc = np.stack(
            [np.where(big, 16000, 8000), np.where(big, 64 * GIB, 32 * GIB), np.zeros(n, np.int64)],
            axis=1,
        ).astype(np.int64)
        self.usage = np.zeros((n, 3), np.int64)
        self.overhead = np.zeros((n, 3), np.int64)
        self.zone = self.rng.integers(0, len(ZONES), n).astype(np.int32)
        self.labels = [
            {"resource_channel": GROUPS[g], "pool": ("spot", "reserved", "other")[p]}
            for g, p in zip(self.rng.integers(0, 2, n), self.rng.integers(0, 4, n) % 3)
        ]
        self.ready = self.rng.random(n) > 0.02
        self.unsched = self.rng.random(n) < 0.02
        self.live = []  # usage deltas of the gangs still running
        self.ranked = None

    def snapshot(self, keyed=True):
        if self.ranked != self.structure:
            self.ranks = np.argsort(np.argsort(np.array(self.names, dtype=object))).astype(np.int64)
            self.ranked = self.structure
        snap = TensorSnapshot(
            names=list(self.names),
            allocatable=self.alloc.copy(),
            usage=self.usage.copy(),
            overhead=self.overhead.copy(),
            zone_names=list(ZONES),
            zone_id=self.zone.copy(),
            ready=self.ready.copy(),
            unschedulable=self.unsched.copy(),
            labels=list(self.labels),
            exact=True,
            res_entries=np.zeros(len(self.names), bool),
            name_rank=self.ranks.copy(),
            structure_key=(self.instance, self.structure),
            content_key=(self.instance, self.seq),
        )
        if not keyed:
            snap = dataclasses.replace(snap, structure_key=(-1, -1), content_key=(-1, -1))
        return snap

    def gang(self, rows):
        """A reservation of one driver and executors on `rows`: cpu and
        memory in the units the cells ask for."""
        delta = np.zeros((len(self.names), 3), np.int64)
        for row in rows:
            delta[row] += (1000 * int(self.rng.integers(1, 3)), GIB * int(self.rng.integers(1, 5)), 0)
        return delta

    def apply(self, delta):
        self.usage += delta
        self.seq += 1

    def add_node(self, name):
        self.names.append(name)
        self.alloc = np.vstack([self.alloc, [[8000, 32 * GIB, 0]]])
        self.usage = np.vstack([self.usage, [[0, 0, 0]]])
        self.overhead = np.vstack([self.overhead, [[0, 0, 0]]])
        self.zone = np.append(self.zone, np.int32(0))
        self.labels.append({"resource_channel": GROUPS[0], "pool": "spot"})
        self.ready = np.append(self.ready, True)
        self.unsched = np.append(self.unsched, False)
        self.live = [np.vstack([d, [[0, 0, 0]]]) for d in self.live]
        self.structure += 1
        self.seq += 1

    def remove_node(self, row):
        keep = np.arange(len(self.names)) != row
        self.names = [nm for i, nm in enumerate(self.names) if i != row]
        self.labels = [lb for i, lb in enumerate(self.labels) if i != row]
        for attr in ("alloc", "usage", "overhead", "zone", "ready", "unsched"):
            setattr(self, attr, getattr(self, attr)[keep])
        self.live = [d[keep] for d in self.live]
        self.structure += 1
        self.seq += 1


def _driver(group):
    pod = Harness.static_allocation_spark_pods("app-order", 1)[0]
    pod.node_affinity = {"resource_channel": [group]}
    return pod


def _build(snap, group, candidates, dlp=None, elp=None, pod=None):
    tracer = Tracer()
    with tracer.span("fast_path.build_tensor") as sp:
        built = fast_path.build_cluster_tensor(
            snap, pod or _driver(group), candidates,
            driver_label_priority=dlp, executor_label_priority=elp,
        )
        tags = dict(sp.tags)
    return built, tags


def _assert_same(got, want):
    (cluster, zones), (oracle, oracle_zones) = got, want
    assert type(cluster.node_names) is list and cluster.node_names == oracle.node_names
    for field in ("avail", "sched", "driver_rank", "exec_ok", "zone_id", "valid"):
        a, b = getattr(cluster, field), getattr(oracle, field)
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert np.array_equal(a, b), field
    assert cluster.zone_names == oracle.zone_names and cluster.exact == oracle.exact
    assert zones == oracle_zones


def _assert_handed_out_alone(built, snap, group, candidates):
    """The tensor's arrays are read-only and share no buffer with the
    basis the kept order is compared against, nor with the snapshot."""
    cluster, _ = built
    kept = fast_path._build_prep(snap, _driver(group), candidates, None, None).node_order
    assert kept.avail is cluster.avail
    for field in ("avail", "sched", "driver_rank", "exec_ok", "zone_id", "valid"):
        array = getattr(cluster, field)
        assert not array.flags.writeable, field
        with pytest.raises(ValueError):
            array[...] = 0
        for other in (*kept.basis, snap.allocatable, snap.usage, snap.overhead):
            assert not np.shares_memory(array, other), field
    for held, now in zip(kept.basis, (snap.allocatable, snap.usage, snap.overhead)):
        assert not np.shares_memory(held, now)


def _frozen(built):
    cluster, zones = built
    return dataclasses.replace(
        cluster,
        node_names=list(cluster.node_names),
        **{f: getattr(cluster, f).copy() for f in ("avail", "sched", "driver_rank", "exec_ok", "zone_id", "valid")},
    ), dict(zones)


def _step(cluster, rng):
    """One random change of the kind a driver Filter sees between two
    requests; returns its kind."""
    n, live = len(cluster.names), cluster.live
    kind = rng.choice(
        ["nothing", "pair", "grant", "retire", "flip", "tie", "overhead", "allocatable", "many"],
        p=[0.06, 0.2, 0.2, 0.18, 0.08, 0.08, 0.08, 0.06, 0.06],
    )
    if kind == "pair":  # a grant retired before the next request: nothing left changed
        rows = rng.choice(n, int(rng.integers(1, 33)), replace=False)
        delta = cluster.gang(rows)
        cluster.apply(delta)
        cluster.apply(-delta)
    elif kind == "grant" or (kind == "retire" and not live):
        rows = rng.choice(n, int(rng.integers(1, 33)), replace=False)
        live.append(cluster.gang(rows))
        cluster.apply(live[-1])
    elif kind == "retire":
        cluster.apply(-live.pop(int(rng.integers(0, len(live)))))
    elif kind == "flip":  # fill one zone's nodes until it has less free than the others
        zone = int(rng.integers(0, len(ZONES)))
        rows = rng.choice(np.flatnonzero(cluster.zone == zone), 40, replace=False)
        delta = np.zeros_like(cluster.usage)
        delta[rows] = cluster.alloc[rows] // int(rng.integers(2, 5))
        delta[rows, 2] = 0
        live.append(delta)
        cluster.apply(delta)
    elif kind == "tie":  # rows brought to another row's (memory, cpu)
        rows = rng.choice(n, int(rng.integers(2, 20)), replace=False)
        target = cluster.alloc[rows[0]] - cluster.usage[rows[0]] - cluster.overhead[rows[0]]
        delta = np.zeros_like(cluster.usage)
        delta[rows] = cluster.alloc[rows] - cluster.overhead[rows] - target - cluster.usage[rows]
        live.append(delta)
        cluster.apply(delta)
    elif kind == "overhead":  # pods outside any reservation: schedulable moves too
        rows = rng.choice(n, int(rng.integers(1, 10)), replace=False)
        cluster.overhead[rows] += (250, GIB // 2, 0)
        cluster.seq += 1
    elif kind == "allocatable":
        rows = rng.choice(n, int(rng.integers(1, 4)), replace=False)
        cluster.alloc[rows, 0] += 1000
        cluster.seq += 1
    elif kind == "many":
        rows = rng.choice(n, n // 2, replace=False)
        live.append(cluster.gang(rows))
        cluster.apply(live[-1])
    return kind


def test_kept_order_equals_the_whole_sort_at_every_step():
    """A few hundred steps of grants, retires, pairs that cancel, zone
    flips, ties on (memory, cpu), overhead and allocatable changes, two
    instance groups interleaved, each with its own candidate list, and
    node events: every ClusterTensor equals the whole sort's, no later
    request writes into an earlier one's, and every read of the order is
    the one the step calls for."""
    cluster = Cluster(1024, seed=7)
    rng = np.random.default_rng(11)
    # one group's driver may go to any of its nodes, the other's to most
    share = dict(zip(GROUPS, (1.0, 0.9)))
    candidates = {
        group: tuple(nm for nm, lb in zip(cluster.names, cluster.labels) if lb["resource_channel"] == group and rng.random() < share[group])
        for group in GROUPS
    }
    seen = {"kept": 0, "rebuilt": 0}
    last_snap, handed = {}, {}
    for step in range(320):
        kind = _step(cluster, rng) if step else "first"
        if step and step % 97 == 0:  # a node event: the structure changes
            if step % 2:
                cluster.add_node(f"node-new-{step}")
            else:
                cluster.remove_node(int(rng.integers(0, len(cluster.names))))
            kind = "node"
        snap = cluster.snapshot()
        for group in GROUPS if step % 3 else GROUPS[::-1]:
            if group in handed:  # no later request wrote into what an earlier one was handed
                _assert_same(*handed[group])
            built, tags = _build(snap, group, candidates[group])
            handed[group] = (built, _frozen(built))
            oracle, oracle_tags = _build(cluster.snapshot(keyed=False), group, candidates[group])
            assert oracle_tags["nodeOrder"] == "rebuilt"
            _assert_same(built, oracle)
            seen[tags["nodeOrder"]] += 1
            if kind in ("first", "node"):
                assert tags["nodeOrder"] == "rebuilt", (step, kind)
            elif kind in ("nothing", "pair"):
                assert tags == {"nodeOrder": "kept", "orderRows": 0, "prepCache": "hit"}
            else:
                before = last_snap[group]
                sel = np.array([lb["resource_channel"] == group for lb in snap.labels])
                differs = (
                    (snap.allocatable != before.allocatable).any(axis=1)
                    | (snap.usage != before.usage).any(axis=1)
                    | (snap.overhead != before.overhead).any(axis=1)
                ) & sel
                want = ("rebuilt", int(sel.sum())) if differs.any() else ("kept", 0)
                assert (tags["nodeOrder"], tags["orderRows"]) == want, (step, kind)
            if step % 40 == 0:
                _assert_handed_out_alone(built, snap, group, candidates[group])
            last_snap[group] = snap
    assert min(seen.values()) >= 100, seen


@pytest.mark.parametrize(
    "dlp,elp",
    [
        (LabelPriorityOrder("pool", ["reserved", "spot"]), None),
        (None, LabelPriorityOrder("pool", ["spot"])),
        (LabelPriorityOrder("pool", ["spot"]), LabelPriorityOrder("pool", ["reserved", "other"])),
    ],
    ids=["driver", "executor", "both"],
)
def test_label_priorities_keep_or_sort_whole(dlp, elp):
    """Where label priorities re-sort the order, no change keeps it and a
    change sorts it whole; both equal the whole sort."""
    cluster = Cluster(256, seed=3)
    rng = np.random.default_rng(5)
    group = GROUPS[0]
    candidates = tuple(cluster.names)
    for step in range(40):
        kind = _step(cluster, rng) if step else "first"
        built, tags = _build(cluster.snapshot(), group, candidates, dlp, elp)
        oracle, _ = _build(cluster.snapshot(keyed=False), group, candidates, dlp, elp)
        _assert_same(built, oracle)
        if kind in ("nothing", "pair"):
            assert tags["nodeOrder"] == "kept"
        elif kind == "first":
            assert tags["nodeOrder"] == "rebuilt"


def _reads():
    return {
        result: default_profiler.metrics.get_counter(mnames.NODE_ORDER_READS, {"result": result})
        for result in ("kept", "rebuilt")
    }


def test_the_order_reads_kept_and_rebuilt_as_the_rows_changed():
    """A grant and its retire between two requests read `kept` with no
    row sorted; a grant alone reads `rebuilt` with every selected row;
    rows outside the selection read `kept`; a node event reads
    `rebuilt`; each read counted in `...fastpath.nodeorder.reads`."""
    cluster = Cluster(512, seed=1)
    group = GROUPS[1]
    candidates = tuple(cluster.names)
    mine = [r for r, lb in enumerate(cluster.labels) if lb["resource_channel"] == group]
    before = _reads()
    _, tags = _build(cluster.snapshot(), group, candidates)
    assert (tags["nodeOrder"], tags["orderRows"]) == ("rebuilt", len(mine))
    grant = cluster.gang(mine[:12])
    cluster.apply(grant)
    cluster.apply(-grant)
    _, tags = _build(cluster.snapshot(), group, candidates)
    assert (tags["nodeOrder"], tags["orderRows"]) == ("kept", 0)
    cluster.apply(grant)
    _, tags = _build(cluster.snapshot(), group, candidates)
    assert (tags["nodeOrder"], tags["orderRows"]) == ("rebuilt", len(mine))
    # rows outside the group's selection leave its order as it is
    other = [r for r, lb in enumerate(cluster.labels) if lb["resource_channel"] != group][:5]
    cluster.apply(cluster.gang(other))
    _, tags = _build(cluster.snapshot(), group, candidates)
    assert (tags["nodeOrder"], tags["orderRows"]) == ("kept", 0)
    cluster.add_node("node-late")
    built, tags = _build(cluster.snapshot(), group, candidates)
    assert tags["nodeOrder"] == "rebuilt"
    assert tags["orderRows"] == len(built[0].node_names)
    counted = {result: n - before[result] for result, n in _reads().items()}
    assert counted == {"kept": 2, "rebuilt": 3}


def test_an_uncacheable_affinity_keeps_no_order():
    """A driver whose affinity the prep cache cannot key sorts on every
    request, unchanged rows too, and leaves no order behind."""
    cluster = Cluster(256, seed=6)
    group = GROUPS[0]
    pod = _driver(group)
    pod.node_selector = {"pool": "spot"}
    candidates = tuple(cluster.names)
    for _ in range(3):
        built, tags = _build(cluster.snapshot(), group, candidates, pod=pod)
        assert tags["prepCache"] == "uncacheable" and tags["nodeOrder"] == "rebuilt"
        _assert_same(built, _build(cluster.snapshot(keyed=False), group, candidates, pod=pod)[0])


def test_zones_that_trade_places_are_sorted_again():
    """Two zones of one selected node each, both nodes changed so that the
    zones trade places: the order is sorted again, the zone ids move with
    it, and undoing the change is sorted back exactly."""
    cluster = Cluster(64, seed=4)
    group = GROUPS[0]
    mine = [r for r, lb in enumerate(cluster.labels) if lb["resource_channel"] == group]
    a, b = mine[:2]
    cluster.zone[:] = 2
    cluster.zone[[a, b]] = (0, 1)
    cluster.alloc[[a, b]] = (8000, 32 * GIB, 0)
    cluster.structure += 1
    candidates = tuple(cluster.names)
    delta = np.zeros_like(cluster.usage)
    delta[a] = (1000, GIB, 0)
    cluster.apply(delta)
    first, tags = _build(cluster.snapshot(), group, candidates)
    assert tags["nodeOrder"] == "rebuilt"
    swap = np.zeros_like(cluster.usage)
    swap[[a, b]] = ((-1000, -GIB, 0), (2000, 2 * GIB, 0))
    cluster.apply(swap)
    built, tags = _build(cluster.snapshot(), group, candidates)
    assert tags["nodeOrder"] == "rebuilt"
    assert not np.array_equal(first[0].zone_id, built[0].zone_id)
    _assert_same(built, _build(cluster.snapshot(keyed=False), group, candidates)[0])
    cluster.apply(-swap)
    back, tags = _build(cluster.snapshot(), group, candidates)
    assert tags["nodeOrder"] == "rebuilt"
    _assert_same(back, first)


def test_threads_building_from_snapshots_of_one_mirror_get_exact_tensors():
    """More threads than cores build from snapshots of one mirror taken at
    different moments, in any order, under a short switch interval: each
    tensor equals the whole sort of its own snapshot (an order kept for
    one snapshot and handed out for another would not)."""
    cluster = Cluster(512, seed=21)
    rng = np.random.default_rng(22)
    group, candidates = GROUPS[0], tuple(cluster.names)
    snaps = []
    for _ in range(16):
        _step(cluster, rng)
        snaps.append(cluster.snapshot())
    oracles = [_build(dataclasses.replace(s, structure_key=(-1, -1)), group, candidates)[0] for s in snaps]
    wrong, done = [], []

    def work(seed):
        picks = np.random.default_rng(seed).integers(0, len(snaps), 30)
        for i in picks.tolist():
            built = fast_path.build_cluster_tensor(snaps[i], _driver(group), candidates)
            try:
                _assert_same(built, oracles[i])
            except AssertionError as e:
                wrong.append((i, e))
        done.append(seed)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(s,)) for s in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(12)) and not wrong, wrong[:1]


def test_each_request_gets_its_own_list_of_names():
    """Two requests that keep one order are handed the same read-only
    arrays, but each its own list of names: a consumer that edits its
    list leaves the next request's as the sort made it."""
    cluster = Cluster(128, seed=8)
    group, candidates = GROUPS[0], tuple(cluster.names)
    (first, _), _ = _build(cluster.snapshot(), group, candidates)
    want = list(first.node_names)
    first.node_names.reverse()
    cluster.seq += 1
    (second, _), tags = _build(cluster.snapshot(), group, candidates)
    assert tags["nodeOrder"] == "kept"
    assert second.avail is first.avail and second.node_names is not first.node_names
    assert second.node_names == want


def test_a_served_grant_and_its_retire_keep_the_order():
    """Through the tensor mirror, as a served driver sees it: a driver's
    gang reserved and then retired between two builds leaves every row as
    it was, so the second build keeps the order; a build while a gang
    holds its nodes sorts again, and each equals the whole sort."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        names = []
        for i in range(96):
            h.new_node(f"n{i:02d}", cpu="8", memory="16Gi", zone=ZONES[i % 3])
            names.append(f"n{i:02d}")

        def build():
            snap = h.server.tensor_snapshot.snapshot()
            built, tags = _build(snap, GROUPS[0], names)
            _assert_same(built, _build(dataclasses.replace(snap, structure_key=(-1, -1)), GROUPS[0], names)[0])
            return snap, tags["nodeOrder"]

        assert build()[1] == "rebuilt"
        first = Harness.static_allocation_spark_pods("app-a", 3)[0]
        assert h.assert_success(h.schedule(first, names))
        h.delete_pod(first)
        assert h.wait_for_api(lambda: h.get_resource_reservation("app-a") is None)
        assert h.wait_quiesced()
        snap, read = build()
        assert not snap.usage.any() and read == "kept"
        second = Harness.static_allocation_spark_pods("app-b", 3)[0]
        assert h.assert_success(h.schedule(second, names))
        snap, read = build()
        assert snap.usage.any() and read == "rebuilt"
    finally:
        h.close()
