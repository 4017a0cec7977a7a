"""State layer tests: store, sharded queue, write-back cache, async client,
API server consistency model (reference store_test.go / queue_test.go
scenarios re-derived, plus conflict/retry behaviors)."""

import threading
import time

import pytest

from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
from k8s_spark_scheduler_tpu.kube.errors import (
    AlreadyExistsError,
    ConflictError,
    NotFoundError,
)
from k8s_spark_scheduler_tpu.kube.informer import InformerFactory
from k8s_spark_scheduler_tpu.state.cache import AsyncClient, TypedClient, WriteBackCache
from k8s_spark_scheduler_tpu.state.store import (
    CREATE,
    DELETE,
    ObjectStore,
    Request,
    ShardedUniqueQueue,
    create_request,
    delete_request,
    fnv32a,
    update_request,
)
from k8s_spark_scheduler_tpu.state.typed_caches import ResourceReservationCache
from k8s_spark_scheduler_tpu.types.objects import (
    ObjectMeta,
    Reservation,
    ResourceReservation,
    ResourceReservationSpec,
)
from k8s_spark_scheduler_tpu.types.resources import Resources


def rr(name, ns="default", node="n1"):
    return ResourceReservation(
        meta=ObjectMeta(name=name, namespace=ns),
        spec=ResourceReservationSpec(
            reservations={"driver": Reservation.for_resources(node, Resources.of(1, "1Gi"))}
        ),
    )


# -- ObjectStore ------------------------------------------------------------


def test_store_put_preserves_resource_version():
    s = ObjectStore()
    a = rr("a")
    a.meta.resource_version = 7
    s.put(a)
    newer = rr("a")
    newer.meta.resource_version = 3  # local writer doesn't know server RV
    s.put(newer)
    assert s.get(("default", "a")).meta.resource_version == 7


def test_store_override_rv_if_newer():
    s = ObjectStore()
    a = rr("a")
    a.meta.resource_version = 5
    s.put(a)
    ext = rr("a")
    ext.meta.resource_version = 9
    assert s.override_resource_version_if_newer(ext)
    assert s.get(("default", "a")).meta.resource_version == 9
    older = rr("a")
    older.meta.resource_version = 2
    assert not s.override_resource_version_if_newer(older)
    assert s.get(("default", "a")).meta.resource_version == 9


# -- ShardedUniqueQueue -----------------------------------------------------


def test_queue_dedupes_creates_and_updates():
    q = ShardedUniqueQueue(2)
    a = rr("a")
    q.add_if_absent(create_request(a))
    q.add_if_absent(update_request(a))  # compacted away
    q.add_if_absent(update_request(a))
    assert sum(q.queue_lengths()) == 1
    # deletes always enqueue
    q.add_if_absent(delete_request(("default", "a")))
    assert sum(q.queue_lengths()) == 2


def test_queue_shard_affinity():
    q = ShardedUniqueQueue(4)
    # same key always lands in the same shard
    shard = q._bucket(("ns", "obj"))
    for _ in range(5):
        assert q._bucket(("ns", "obj")) == shard


def test_queue_release_allows_reenqueue():
    q = ShardedUniqueQueue(1)
    a = rr("a")
    q.add_if_absent(create_request(a))
    consumer = q.get_consumers()[0]
    getter = consumer.get_nowait()
    req = getter()  # releases inflight marker
    assert req.type == CREATE
    q.add_if_absent(update_request(a))
    assert sum(q.queue_lengths()) == 1


def test_try_add_when_full():
    q = ShardedUniqueQueue(1, buffer_size=1)
    q.add_if_absent(create_request(rr("a")))
    assert not q.try_add_if_absent(create_request(rr("b")))
    # the failed add must not leak an inflight marker
    getter = q.get_consumers()[0].get_nowait()
    getter()
    assert q.try_add_if_absent(create_request(rr("b")))


def test_fnv32a_known_vectors():
    # standard FNV-1a test vectors
    assert fnv32a(b"") == 0x811C9DC5
    assert fnv32a(b"a") == 0xE40C292C
    assert fnv32a(b"foobar") == 0xBF9CF968


# -- APIServer consistency model -------------------------------------------


def test_apiserver_create_get_conflict():
    api = APIServer()
    created = api.create(rr("a"))
    assert created.meta.resource_version > 0
    with pytest.raises(AlreadyExistsError):
        api.create(rr("a"))

    stale = created.deepcopy()
    api.update(created)  # bumps RV
    with pytest.raises(ConflictError):
        api.update(stale)
    with pytest.raises(NotFoundError):
        api.get("ResourceReservation", "default", "nope")


def test_apiserver_owner_gc():
    from k8s_spark_scheduler_tpu.types.objects import OwnerReference, Pod

    api = APIServer()
    driver = api.create(Pod(meta=ObjectMeta(name="drv")))
    owned = rr("app-1")
    owned.meta.owner_references.append(
        OwnerReference(kind="Pod", name="drv", uid=driver.meta.uid)
    )
    api.create(owned)
    api.delete("Pod", "default", "drv")
    with pytest.raises(NotFoundError):
        api.get("ResourceReservation", "default", "app-1")


def test_apiserver_watch_replay_and_events():
    api = APIServer()
    api.create(rr("a"))
    events = []
    api.watch("ResourceReservation", lambda e, o: events.append((e, o.name)))
    assert events == [("ADDED", "a")]
    api.create(rr("b"))
    api.delete("ResourceReservation", "default", "a")
    assert ("ADDED", "b") in events and ("DELETED", "a") in events


# -- Async write-back end-to-end -------------------------------------------


def _wait_for(cond, timeout=5.0, tick=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return False


def test_reservation_cache_write_back():
    api = APIServer()
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    cache = ResourceReservationCache(api, informer)
    cache.run()
    try:
        cache.create(rr("app-1"))
        # visible locally immediately
        assert cache.get("default", "app-1") is not None
        # visible at the API server asynchronously
        assert _wait_for(lambda: len(api.list("ResourceReservation")) == 1)
        # update flows through and RV from the server folds back in
        obj = cache.get("default", "app-1").deepcopy()
        obj.spec.reservations["executor-1"] = Reservation.for_resources(
            "n2", Resources.of(1, "1Gi")
        )
        cache.update(obj)
        assert _wait_for(
            lambda: "executor-1"
            in api.get("ResourceReservation", "default", "app-1").spec.reservations
        )
        server_rv = api.get("ResourceReservation", "default", "app-1").meta.resource_version
        assert _wait_for(
            lambda: cache.get("default", "app-1").meta.resource_version == server_rv
        )
        # delete drains to the server
        cache.delete("default", "app-1")
        assert cache.get("default", "app-1") is None
        assert _wait_for(lambda: len(api.list("ResourceReservation")) == 0)
    finally:
        cache.stop()


def test_async_update_resolves_conflict():
    api = APIServer()
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    cache = ResourceReservationCache(api, informer)

    cache.create(rr("app-1"))
    cache.run()
    try:
        assert _wait_for(lambda: len(api.list("ResourceReservation")) == 1)
        # another writer bumps the server RV behind our back
        server_obj = api.get("ResourceReservation", "default", "app-1")
        api.update(server_obj)
        # our update now hits a conflict and must resolve it inline
        mine = cache.get("default", "app-1").deepcopy()
        mine.meta.resource_version = 1  # deliberately stale
        mine.spec.reservations["executor-1"] = Reservation.for_resources(
            "n9", Resources.of(1, "1Gi")
        )
        cache.update(mine)
        assert _wait_for(
            lambda: "executor-1"
            in api.get("ResourceReservation", "default", "app-1").spec.reservations
        )
    finally:
        cache.stop()


def test_create_in_terminating_namespace_drops_object():
    api = APIServer()
    api.mark_namespace_terminating("doomed")
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    cache = ResourceReservationCache(api, informer)
    cache.run()
    try:
        cache.create(rr("app-1", ns="doomed"))
        # async client sees namespace-terminating and drops from the store
        assert _wait_for(lambda: cache.get("doomed", "app-1") is None)
        assert api.list("ResourceReservation") == []
    finally:
        cache.stop()


def test_cache_seeds_from_lister():
    api = APIServer()
    api.create(rr("pre-existing"))
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    cache = ResourceReservationCache(api, informer)
    assert cache.get("default", "pre-existing") is not None


def test_informer_delete_removes_from_cache():
    api = APIServer()
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    cache = ResourceReservationCache(api, informer)
    cache.run()
    try:
        cache.create(rr("app-1"))
        assert _wait_for(lambda: len(api.list("ResourceReservation")) == 1)
        # external delete (e.g. owner GC) folds back via the informer
        api.delete("ResourceReservation", "default", "app-1")
        assert _wait_for(lambda: cache.get("default", "app-1") is None)
    finally:
        cache.stop()


def test_store_observer_replays_existing_content():
    s = ObjectStore()
    s.put_if_absent(rr("pre"))
    seen = []
    s.add_content_observer(lambda old, new: seen.append((old, new and new.name)))
    assert seen == [(None, "pre")]


def test_fold_resource_version_never_resurrects():
    s = ObjectStore()
    obj = rr("a")
    obj.meta.resource_version = 9
    assert not s.fold_resource_version(obj)  # absent → no insert
    assert s.get(("default", "a")) is None
    s.put_if_absent(rr("a"))
    assert s.fold_resource_version(obj)
    assert s.get(("default", "a")).meta.resource_version == 9


def test_rate_limited_writes():
    from k8s_spark_scheduler_tpu.kube.ratelimit import TokenBucket

    api = APIServer()
    factory = InformerFactory(api)
    informer = factory.informer("ResourceReservation")
    informer.start()
    # 20 writes/s with burst 2: 10 creates should take roughly >= 350ms
    cache = ResourceReservationCache(api, informer, rate_bucket=TokenBucket(20, 2))
    cache.run()
    try:
        t0 = time.time()
        for i in range(10):
            cache.create(rr(f"rl-{i}"))
        assert _wait_for(lambda: len(api.list("ResourceReservation")) == 10)
        elapsed = time.time() - t0
        assert elapsed >= 0.3, f"writes were not rate limited ({elapsed:.3f}s)"
    finally:
        cache.stop()


def test_informer_label_index():
    from k8s_spark_scheduler_tpu.kube.informer import Informer
    from k8s_spark_scheduler_tpu.types.objects import ObjectMeta, Pod

    api = APIServer()
    inf = Informer(api, "Pod", index_labels=("spark-app-id",))
    inf.start()
    for i in range(5):
        api.create(Pod(meta=ObjectMeta(name=f"p{i}", labels={"spark-app-id": f"app-{i % 2}"})))
    api.create(Pod(meta=ObjectMeta(name="unlabeled")))

    assert {p.name for p in inf.list(label_selector={"spark-app-id": "app-0"})} == {
        "p0", "p2", "p4"
    }
    # index tracks relabels and deletes
    p0 = api.get("Pod", "default", "p0")
    p0.meta.labels["spark-app-id"] = "app-1"
    api.update(p0)
    assert {p.name for p in inf.list(label_selector={"spark-app-id": "app-1"})} == {
        "p0", "p1", "p3"
    }
    api.delete("Pod", "default", "p1")
    assert {p.name for p in inf.list(label_selector={"spark-app-id": "app-1"})} == {
        "p0", "p3"
    }
    # combined selectors still filter correctly through the index
    assert inf.list(label_selector={"spark-app-id": "app-1", "other": "x"}) == []


def test_informer_remembers_departed_objects_beside_its_store_and_prunes_only_past_the_bound():
    """A store of more live objects than ``TOMBSTONES_KEPT`` (a busy
    cluster's bound pods) keeps every live object's resourceVersion and
    prunes only once the departed ones pass the bound: an event costs
    O(1), not a walk over the store; a stale event of a departed object
    is still dropped."""
    from k8s_spark_scheduler_tpu.kube.informer import MODIFIED, Informer
    from k8s_spark_scheduler_tpu.types.objects import ObjectMeta, Pod

    api = APIServer()
    inf = Informer(api, "Pod")
    inf.TOMBSTONES_KEPT = 4
    inf.start()
    for i in range(20):
        api.create(Pod(meta=ObjectMeta(name=f"p{i}")))
    assert len(inf._last_rv) == 20 and len(inf.list()) == 20
    stale = api.get("Pod", "default", "p0")
    for i in range(4):
        api.delete("Pod", "default", f"p{i}")
    assert len(inf._last_rv) == 20  # 16 live, 4 departed: within the bound
    inf._on_event(MODIFIED, stale)  # a late update of a deleted pod
    assert inf.get("default", "p0") is None
    for i in range(4, 10):
        api.delete("Pod", "default", f"p{i}")
    assert len(inf.list()) == 10
    assert len(inf._last_rv) <= 10 + inf.TOMBSTONES_KEPT + 1
