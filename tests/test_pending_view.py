"""The pending-driver view (scheduler/sparkpods.py) against a from-scratch
derivation: a seeded random stream of driver and executor pod events through
a real APIServer + Informer, and after EVERY event the view's answer for a set
of probe drivers equals what filtering, sorting and cutting the informer's
store gives at that moment: pods, demands, names and skip verdicts alike."""

import random
import threading

import pytest

from k8s_spark_scheduler_tpu.kube.apiserver import MODIFIED, APIServer
from k8s_spark_scheduler_tpu.kube.informer import InformerFactory
from k8s_spark_scheduler_tpu.scheduler import labels as L
from k8s_spark_scheduler_tpu.scheduler.sparkpods import (
    VIEW_HIT,
    VIEW_REBUILD,
    VIEW_STALE,
    AnnotationError,
    SparkPodLister,
    spark_app_demand_cached,
)
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.objects import Pod

LABEL = "resource_channel"
GROUPS = ("ig-a", "ig-b", None)  # None: a pod without an instance group
SCHEDULERS = (L.SPARK_SCHEDULER_NAME, "other-scheduler")
NAMESPACES = ("default", "team-b")
BASE = 1_700_000_000.0

# how often each event is drawn, per mix
MIXES = {
    "churn": dict(add=5, executor=2, annotate=2, bind=2, mark_deleted=1, delete=3, stale=1, relabel=1),
    "ties": dict(add=6, executor=1, annotate=1, bind=1, mark_deleted=1, delete=2, stale=1, relabel=0),
    "executor-heavy": dict(add=2, executor=8, annotate=1, bind=1, mark_deleted=0, delete=2, stale=1, relabel=0),
}


def lister_over(api, indexed=True):
    factory = InformerFactory(api)
    informer = factory.informer(
        Pod.KIND, index_labels=("spark-app-id", "spark-role") if indexed else ()
    )
    informer.start()
    return informer, SparkPodLister(informer, LABEL)


def driver_pod(name, stamp, group="ig-a", scheduler=L.SPARK_SCHEDULER_NAME, namespace="default", parses=True):
    pod = Harness.static_allocation_spark_pods(
        name, 1, instance_group=group or "unused", namespace=namespace, creation_timestamp=stamp
    )[0]
    if group is None:
        pod.node_affinity = {}
    pod.scheduler_name = scheduler
    if not parses:
        pod.meta.annotations[L.DRIVER_CPU] = "not-a-quantity"
    return pod


def from_scratch(informer, probe, skip_cutoff):
    """What the view has to answer, derived from the store alone."""
    pending = [
        p
        for p in informer.list()
        if p.labels.get(L.SPARK_ROLE_LABEL) == L.DRIVER
        and p.node_name == ""
        and p.meta.deletion_timestamp is None
        and p.scheduler_name == probe.scheduler_name
        and L.match_pod_instance_group(p, probe, LABEL)
    ]
    pending.sort(key=lambda p: (p.creation_timestamp, p.namespace, p.name))
    earlier = [p for p in pending if p.creation_timestamp < probe.creation_timestamp]
    demands, unparsed = [], False
    for p in earlier:
        try:
            demands.append(spark_app_demand_cached(p)[1])
        except AnnotationError:
            unparsed = True
    queue = None
    if not unparsed:
        queue = (
            demands,
            [p.creation_timestamp > skip_cutoff for p in earlier],
            [p.name for p in earlier],
        )
    return pending, earlier, queue


def keys(pods):
    return [(p.namespace, p.name, p.meta.resource_version) for p in pods]


def assert_view_equals_store(informer, lister, probes, skip_cutoff):
    for probe in probes:
        pending, earlier, queue = from_scratch(informer, probe, skip_cutoff)
        assert keys(lister.list_pending_drivers(probe)) == keys(pending)
        assert keys(lister.list_earlier_drivers(probe)) == keys(earlier)
        got, how = lister.pending_view.queue_ahead(probe, skip_cutoff)
        assert how == VIEW_HIT
        if queue is None:
            assert got is None  # an unparseable pod ahead: the caller walks the pods
            continue
        apps, skips, names = got
        assert len(apps) == len(queue[0]) and all(a is b for a, b in zip(apps, queue[0]))
        assert skips == queue[1]
        assert names == queue[2]


class Stream:
    """A seeded stream of pod events against one API server."""

    def __init__(self, api, informer, rng, mix, stamps):
        self.api, self.informer, self.rng = api, informer, rng
        self.ops = [op for op, weight in MIXES[mix].items() for _ in range(weight)]
        self.stamps = stamps
        self.serial = 0
        self.superseded = []  # copies of objects that a later write replaced

    def live(self, role=None):
        pods = self.api.list(Pod.KIND)
        if role is not None:
            pods = [p for p in pods if p.labels.get(L.SPARK_ROLE_LABEL) == role]
        return sorted(pods, key=lambda p: (p.namespace, p.name))

    def update(self, pod):
        self.superseded.append(self.api.get(Pod.KIND, pod.namespace, pod.name))
        self.api.update(pod)

    def step(self):
        rng = self.rng
        op = rng.choice(self.ops)
        drivers = self.live(L.DRIVER)
        if op == "add" or (op != "executor" and not drivers):
            self.serial += 1
            self.api.create(
                driver_pod(
                    f"app-{self.serial}",
                    rng.choice(self.stamps),
                    group=rng.choice(GROUPS + GROUPS[:1] * 3),
                    scheduler=rng.choice(SCHEDULERS + SCHEDULERS[:1]),
                    namespace=rng.choice(NAMESPACES),
                    parses=rng.random() > 0.08,
                )
            )
        elif op == "executor":
            self.serial += 1
            executor = Harness.static_allocation_spark_pods(
                f"app-x{self.serial}", 1, instance_group="ig-a", creation_timestamp=rng.choice(self.stamps)
            )[1]
            self.api.create(executor)
            if rng.random() < 0.5:
                executor = self.api.get(Pod.KIND, executor.namespace, executor.name)
                executor.node_name = "n1"
                self.update(executor)
        elif op == "annotate":
            pod = rng.choice(drivers)
            pod.meta.annotations[L.EXECUTOR_COUNT] = str(rng.randint(1, 9))
            if rng.random() < 0.2:  # breaks, or repairs, its annotations
                pod.meta.annotations[L.DRIVER_CPU] = rng.choice(("1", "not-a-quantity"))
            self.update(pod)
        elif op == "bind":
            pod = rng.choice(drivers)
            pod.node_name = "n1"
            self.update(pod)
        elif op == "mark_deleted":
            pod = rng.choice(drivers)
            pod.meta.deletion_timestamp = BASE
            self.update(pod)
        elif op == "relabel":  # a driver stops being one
            pod = rng.choice(drivers)
            pod.meta.labels[L.SPARK_ROLE_LABEL] = L.EXECUTOR
            self.update(pod)
        elif op == "delete":
            pod = rng.choice(self.live())
            self.api.delete(Pod.KIND, pod.namespace, pod.name)
        elif op == "stale" and self.superseded:
            # a late delivery of a state the server has since replaced: the
            # informer drops it on its resourceVersion, and so must the view
            old = rng.choice(self.superseded)
            revision = self.informer.revision
            self.informer._on_event(MODIFIED, old.deepcopy())
            assert self.informer.revision == revision


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", range(6))
def test_view_equals_a_from_scratch_derivation_after_every_event(seed, mix):
    rng = random.Random(f"{mix}-{seed}")
    api = APIServer()
    informer, lister = lister_over(api, indexed=seed % 2 == 0)
    # few distinct timestamps under "ties", so that most pods share one
    stamps = [BASE + i for i in range(3 if mix == "ties" else 40)]
    stream = Stream(api, informer, rng, mix, stamps)
    probes = [
        driver_pod("probe", stamp, group=group, scheduler=scheduler)
        for stamp in (stamps[0], stamps[len(stamps) // 2], stamps[-1] + 1)
        for group in GROUPS
        for scheduler in SCHEDULERS
    ]
    skip_cutoff = stamps[len(stamps) // 3]
    # the first read builds the view from what the store already holds
    for _ in range(5):
        stream.step()
    _, how = lister.pending_view.queue_ahead(probes[0], skip_cutoff)
    assert how == VIEW_REBUILD
    for _ in range(120):
        stream.step()
        assert_view_equals_store(informer, lister, probes, skip_cutoff)
        # a probe that is itself in the store is never ahead of itself
        for pod in stream.live(L.DRIVER)[:3]:
            assert_view_equals_store(informer, lister, [pod], skip_cutoff)


def test_equal_creation_timestamps_come_in_namespace_then_name_order():
    api = APIServer()
    informer, lister = lister_over(api)
    for namespace, name in (("team-b", "a"), ("default", "z"), ("default", "b"), ("team-a", "c")):
        api.create(driver_pod(name, BASE, namespace=namespace))
    probe = driver_pod("probe", BASE + 1)
    assert [(p.namespace, p.name) for p in lister.list_earlier_drivers(probe)] == [
        ("default", "b-driver"), ("default", "z-driver"), ("team-a", "c-driver"), ("team-b", "a-driver"),
    ]
    # strictly earlier: a pod of the probe's own timestamp is not ahead of it
    assert lister.list_earlier_drivers(driver_pod("probe", BASE)) == []


def test_an_event_that_fails_to_apply_sends_the_next_read_to_the_store(monkeypatch):
    api = APIServer()
    informer, lister = lister_over(api)
    view = lister.pending_view
    api.create(driver_pod("first", BASE))
    probe = driver_pod("probe", BASE + 100)
    assert view.queue_ahead(probe, BASE)[1] == VIEW_REBUILD

    def broken(key, pod):
        raise RuntimeError("planted")

    monkeypatch.setattr(view, "_insert", broken)
    api.create(driver_pod("second", BASE + 1))  # the informer's event does not fail
    monkeypatch.undo()
    assert informer.get("default", "second-driver") is not None
    api.create(driver_pod("third", BASE + 2))  # ignored: the view is stale already
    (apps, skips, names), how = view.queue_ahead(probe, BASE)
    assert how == VIEW_STALE
    assert names == ["first-driver", "second-driver", "third-driver"]
    assert skips == [False, True, True]
    assert view.queue_ahead(probe, BASE)[1] == VIEW_HIT
    assert_view_equals_store(informer, lister, [probe], BASE)


def test_executor_events_never_reach_the_columns():
    api = APIServer()
    informer, lister = lister_over(api)
    view = lister.pending_view
    api.create(driver_pod("first", BASE))
    probe = driver_pod("probe", BASE + 100)
    view.queue_ahead(probe, BASE)
    touched = []
    original = view._insert
    view._insert = lambda key, pod: (touched.append(key), original(key, pod))
    executor = Harness.static_allocation_spark_pods("app-x", 1, instance_group="ig-a")[1]
    api.create(executor)
    executor = api.get(Pod.KIND, executor.namespace, executor.name)
    executor.node_name = "n1"
    api.update(executor)
    api.delete(Pod.KIND, executor.namespace, executor.name)
    assert touched == []
    api.create(driver_pod("second", BASE + 1))
    assert touched == [("default", "second-driver")]


@pytest.mark.parametrize("reader", ["in-handler", "other-thread"])
def test_a_read_that_races_an_event_in_flight_sees_the_store(reader):
    """The informer calls its handlers after it released its lock: a view
    fed by a handler would still lack the pod whose handlers are running.
    The view is updated under the informer's lock, so a read made while an
    event is in flight (from a handler, or from another thread while a
    handler blocks) already has it, as ``informer.list`` does."""
    api = APIServer()
    informer, lister = lister_over(api)
    probe = driver_pod("probe", BASE + 100)
    api.create(driver_pod("first", BASE))
    lister.list_earlier_drivers(probe)  # built
    seen = {}
    in_flight, release = threading.Event(), threading.Event()

    def read():
        seen["store"] = sorted(
            p.name for p in informer.list(label_selector={L.SPARK_ROLE_LABEL: L.DRIVER})
        )
        seen["view"] = [p.name for p in lister.list_earlier_drivers(probe)]
        seen["queue"] = lister.pending_view.queue_ahead(probe, BASE)

    def on_add(pod):
        if pod.name != "second-driver":
            return
        if reader == "in-handler":
            read()
        else:
            in_flight.set()
            assert release.wait(10)

    informer.add_event_handler(on_add=on_add)
    if reader == "in-handler":
        api.create(driver_pod("second", BASE + 1))
    else:
        writer = threading.Thread(target=api.create, args=(driver_pod("second", BASE + 1),))
        writer.start()
        assert in_flight.wait(10)
        try:
            read()  # the event's handlers have not returned yet
        finally:
            release.set()
            writer.join(10)
    assert seen["store"] == ["first-driver", "second-driver"]
    assert seen["view"] == seen["store"]
    (apps, skips, names), how = seen["queue"]
    assert how == VIEW_HIT and names == seen["store"] and skips == [False, True]


def test_readers_and_writers_on_many_threads_never_see_a_torn_view():
    """Writers create, re-annotate and delete drivers through the API server
    while readers take the queue: every read is whole (columns of one
    length, in creation order, no hole), and the last state equals the
    store's.  More threads than cores, a short switch interval."""
    import sys
    import time

    api = APIServer()
    informer, lister = lister_over(api)
    probe = driver_pod("probe", BASE + 10_000)
    lister.list_earlier_drivers(probe)  # built
    stop = time.monotonic() + 1.5
    torn = []

    def write(worker):
        rng = random.Random(worker)
        mine = []
        serial = 0
        while time.monotonic() < stop:
            roll = rng.random()
            if roll < 0.5 or not mine:
                serial += 1
                name = f"w{worker}-{serial}"
                api.create(driver_pod(name, BASE + rng.randint(0, 50)))
                mine.append(f"{name}-driver")
            elif roll < 0.75:
                pod = api.get(Pod.KIND, "default", rng.choice(mine))
                pod.meta.annotations[L.EXECUTOR_COUNT] = str(rng.randint(1, 9))
                api.update(pod)
            else:
                api.delete(Pod.KIND, "default", mine.pop(rng.randrange(len(mine))))

    def read():
        while time.monotonic() < stop:
            (apps, skips, names), how = lister.pending_view.queue_ahead(probe, BASE + 25)
            pods = lister.list_earlier_drivers(probe)
            stamps = [p.creation_timestamp for p in pods]
            if (
                how != VIEW_HIT
                or not len(apps) == len(skips) == len(names)
                or None in apps
                or stamps != sorted(stamps)
                or skips != sorted(skips)
            ):
                torn.append((how, len(apps), len(skips), len(names)))

    threads = [threading.Thread(target=write, args=(i,)) for i in range(6)]
    threads += [threading.Thread(target=read) for _ in range(6)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert torn == []
    assert_view_equals_store(informer, lister, [probe], BASE + 25)
    assert len(lister.list_pending_drivers(probe)) == len(api.list(Pod.KIND))


def test_views_of_two_listers_on_one_informer_do_not_share_state():
    api = APIServer()
    informer, lister = lister_over(api)
    other = SparkPodLister(informer, "another_label")
    api.create(driver_pod("first", BASE))
    probe = driver_pod("probe", BASE + 1)
    assert [p.name for p in lister.list_earlier_drivers(probe)] == ["first-driver"]
    assert other.list_earlier_drivers(probe) == []  # no pod carries that label


# -- engagement: through the extender, as the benchmark's `drivers` mix runs ---

NODES = [f"n{i}" for i in range(6)]
READS = "foundry.spark.scheduler.fifo.queue.view.reads"


def serving(install_policy=False, binpack_algo="tpu-batch"):
    """The full wiring with three pending drivers that fit, hours old."""
    from k8s_spark_scheduler_tpu.config import FifoConfig, Install, PolicyConfig

    h = Harness(
        extra_install=Install(
            fifo=True,
            fifo_config=FifoConfig(),
            binpack_algo=binpack_algo,
            policy=PolicyConfig(enabled=install_policy, ordering="fifo"),
        )
    )
    for name in NODES:
        h.new_node(name)
    for i in range(3):
        h.create_pod(driver_pod(f"app-queued-{i}", BASE + i, group="batch-medium-priority"))
    return h


def reads(h):
    return {
        result: int(h.server.metrics.get_counter(READS, {"result": result}))
        for result in ("hit", "rebuild", "stale", "per-pod")
    }


def assemble_spans(roots):
    found = []

    def walk(span):
        if span.name == "fast_path.queue_assemble":
            found.append(span)
        for child in span.children:
            walk(child)

    for root in roots:
        walk(root)
    return found


def drivers_mix(h, count, wave="w"):
    """create, Filter, retire: what the `drivers` mix does around each Filter."""
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    decisions = []
    for i in range(count):
        driver = h.create_pod(
            Harness.static_allocation_spark_pods(f"app-{wave}-{i}", 1 + i % 3)[0]
        )
        result = h.extender.predicate(ExtenderArgs(pod=driver, node_names=list(NODES)))
        decisions.append((tuple(result.node_names), dict(result.failed_nodes or {})))
        h.delete_pod(driver)
    return decisions


@pytest.mark.parametrize(
    "binpack_algo, path",
    [
        ("tpu-batch", "fast"),  # _try_fast_driver_path: the span the benchmark reads
        ("tpu-batch-single-az", "fast"),  # the single-AZ solver takes tensors too (PR 29)
        # _try_device_fifo over Quantity metadata: where the tensor mirror cannot serve
        ("tpu-batch-single-az", "device-fifo"),
        ("tightly-pack", "host"),  # no queue solver: the host loop lists the view's pods
    ],
)
def test_every_driver_filter_of_the_drivers_mix_reads_the_view(binpack_algo, path):
    h = serving(binpack_algo=binpack_algo)
    if path == "device-fifo":
        h.extender._fast_path_ok = False
    try:
        first = 0 if path == "host" else 1
        drivers_mix(h, 1, "first")  # the process's first: builds the view from the store
        assert reads(h) == {"hit": 0, "rebuild": first, "stale": 0, "per-pod": 0}
        roots = []
        h.server.tracer.add_observer(roots.append)
        decisions = drivers_mix(h, 7)
        assert all(nodes for nodes, _ in decisions)
        assert reads(h) == {"hit": 7 * first, "rebuild": first, "stale": 0, "per-pod": 0}
        spans = assemble_spans(roots)
        if path == "fast":
            assert len(spans) == 7
            assert all(s.tags["queueView"] == "hit" and s.tags["earlierApps"] == 3 for s in spans)
        else:
            assert spans == []
            gates = [c for r in roots for c in r.children if c.name == "fifo_gate"]
            assert [g.tags["earlierApps"] for g in gates] == [3] * 7
        if path == "device-fifo":  # no span of its own: the tag lands on the request's
            assert {r.tags["queueView"] for r in roots if r.name == "predicate"} == {"hit"}
    finally:
        h.close()


def schedule_gangs(h, count):
    """create, Filter, bind; nothing is retired, so that no decision depends
    on when a reservation's release lands."""
    decisions = []
    for i in range(count):
        driver = Harness.static_allocation_spark_pods(f"app-gang-{i}", 1 + i % 4)[0]
        result = h.schedule(driver, NODES)
        decisions.append((tuple(result.node_names), dict(result.failed_nodes or {})))
    return decisions


def test_a_policy_engine_takes_the_per_pod_walk_and_decides_the_same():
    plain = serving()
    try:
        baseline = schedule_gangs(plain, 8)
        assert reads(plain) == {"hit": 7, "rebuild": 1, "stale": 0, "per-pod": 0}
    finally:
        plain.close()
    engine = serving(install_policy=True)
    try:
        assert engine.extender._policy is not None
        roots = []
        engine.server.tracer.add_observer(roots.append)
        assert schedule_gangs(engine, 8) == baseline
        assert all(nodes for nodes, _ in baseline)
        assert reads(engine) == {"hit": 0, "rebuild": 0, "stale": 0, "per-pod": 8}
        assert {s.tags["queueView"] for s in assemble_spans(roots)} == {"per-pod"}
        assert {s.tags["earlierApps"] for s in assemble_spans(roots)} == {3}
    finally:
        engine.close()


def test_an_unparseable_driver_ahead_takes_the_per_pod_walk_and_is_left_out(caplog):
    h = serving()
    try:
        drivers_mix(h, 1, "first")
        h.create_pod(driver_pod("app-broken", BASE + 10, group="batch-medium-priority", parses=False))
        roots = []
        h.server.tracer.add_observer(roots.append)
        with caplog.at_level("WARNING", logger="k8s_spark_scheduler_tpu.scheduler.extender"):
            assert all(nodes for nodes, _ in drivers_mix(h, 2))
        assert any("skipping driver app-broken-driver" in r.getMessage() for r in caplog.records)
        assert reads(h)["per-pod"] == 2
        (first, second) = assemble_spans(roots)
        assert first.tags["queueView"] == "per-pod" and first.tags["earlierApps"] == 3
        # other callers still list it
        probe = driver_pod("probe", BASE + 100, group="batch-medium-priority")
        assert "app-broken-driver" in [p.name for p in h.server.pod_lister.list_earlier_drivers(probe)]
        h.delete_pod(driver_pod("app-broken", BASE + 10))
        drivers_mix(h, 1, "last")
        assert reads(h)["per-pod"] == 2 and reads(h)["hit"] == 1
    finally:
        h.close()


def test_a_filter_that_races_an_event_in_flight_decides_on_the_store():
    """A blocker's create is in flight (applied to the informer's store, its
    handlers still running on the writer's thread) while a younger driver is
    Filtered on another thread: the Filter sees the blocker, as a listing of
    the store would, and refuses."""
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    h = serving()
    try:
        drivers_mix(h, 1, "first")
        in_flight, release = threading.Event(), threading.Event()

        def on_add(pod):
            if pod.name == "app-blocker-driver":
                in_flight.set()
                assert release.wait(30)

        h.server.pod_informer.add_event_handler(on_add=on_add)
        # an enforced driver whose gang can never fit (1 + 500 executors)
        blocker = Harness.static_allocation_spark_pods("app-blocker", 500, creation_timestamp=BASE + 50)[0]
        writer = threading.Thread(target=h.create_pod, args=(blocker,))
        writer.start()
        assert in_flight.wait(30)
        try:
            young = h.create_pod(Harness.static_allocation_spark_pods("app-young", 1)[0])
            result = h.extender.predicate(ExtenderArgs(pod=young, node_names=list(NODES)))
        finally:
            release.set()
            writer.join(30)
        assert not result.node_names
        assert "earlier drivers do not fit" in next(iter(result.failed_nodes.values()))
        assert reads(h)["stale"] == 0 and reads(h)["rebuild"] == 1
    finally:
        h.close()
