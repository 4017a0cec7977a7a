"""Instance groups (node pools) on the served path: a driver Filter is
answered from the nodes its required node affinity admits, behind its own
group's FIFO, at that group's shape bucket.  The served tensor lanes
against the benchmark's plain reference
(``benchmarks/references/fifo-gangs-groups.py``, which imports nothing of
the program) and against the host oracle (``ops/packers.py`` behind the
extender's host FIFO loop); a warm-up that compiles the shapes the groups
are served at; the counter that says where a compile happened."""

import json
import os
import sys
import time

import pytest
from test_span_contract import find

from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.ops import warmup
from k8s_spark_scheduler_tpu.ops.tensorize import APP_BUCKETS, bucket_size
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.tracing.profiling import default_profiler

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

# three and four groups of unequal size, 1,024 nodes or fewer
CLUSTERS = {
    "3-groups": {"nodes": 120, "backlog": 12, "groups": [("a", 70, 7, 4), ("b", 35, 4, 3), ("c", 15, 1, 1)]},
    "4-groups": {"nodes": 300, "backlog": 30, "groups": [("a", 150, 15, 3), ("b", 90, 9, 2), ("c", 40, 4, 2), ("d", 20, 2, 1)]},
}
LANES = ["native", "xla", "pallas"]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plug-ins (generator, adapter, reference, stack,
    traffic), importable while this module's tests run."""
    sys.path.insert(0, BENCH)
    try:
        import check
        import plugins
        import stack
        import traffic

        yield {
            "check": check, "plugins": plugins, "stack": stack, "traffic": traffic,
            "generator": plugins.load("generators", "instance-groups"),
            "objects": plugins.load("objects", "instance-groups"),
            "reference": plugins.load("references", "fifo-gangs-groups"),
        }
    finally:
        sys.path.remove(BENCH)


def client_of(bench, served, names):
    """The benchmark's lean client on source ports the system picks: its
    own private sequence starts where the process's last client started,
    and a test process makes many."""
    import http.client

    class Client(bench["stack"].Client):
        def _connect(self):
            conn = http.client.HTTPConnection("127.0.0.1", served.http.port, timeout=120)
            conn.connect()
            return conn

    return Client(served, names)


def config_of(shape):
    with open(os.path.join(BENCH, "configs", "fifo10k-groups.json")) as f:
        config = json.load(f)
    config["cluster"].update(
        nodes=shape["nodes"], backlog=shape["backlog"],
        instance_groups=[
            {"name": n, "nodes": nodes, "backlog": backlog, "block_gangs": per_block}
            for n, nodes, backlog, per_block in shape["groups"]
        ],
    )
    return config


def drivers_mix():
    with open(os.path.join(BENCH, "traffic", "drivers.json")) as f:
        return json.load(f)


def serve_blocks(bench, cluster, config, seed, install, lane=None, blocks=2):
    """``blocks`` blocks of the ``drivers`` mix over HTTP; ``lane`` puts
    the queue solver on that lane with the warm session lane off, so that
    ``solve_tensor`` serves.  Returns (block records, roots of the traces)."""
    mix = drivers_mix()
    stream = bench["generator"].blocks(config, mix, seed, cluster.base_ts)
    served = bench["stack"].start_stack(cluster, bench["objects"], install)
    roots = []
    try:
        if lane is not None:
            served.scheduler.extender.delta_engine = None
            served.solver.backend = lane
            served.solver.interpret = True  # the pallas lane on the CPU
        served.scheduler.tracer.add_observer(roots.append)
        client = client_of(bench, served, cluster.names)
        records = [
            bench["traffic"].run_block(client, bench["objects"], next(stream), mix["steps"])
            for _ in range(blocks)
        ]
        fallbacks = served.scheduler.extender.host_fallbacks()
        prep = {
            result: served.scheduler.metrics.get_counter(mnames.PREP_CACHE_READS, {"result": result})
            for result in ("hit", "miss", "uncacheable")
        }
    finally:
        served.stop()
    return records, roots, fallbacks, prep


def reservations(records):
    return [(g.gang.app_id, g.read["reservation"], g.read["api_reservation"]) for b in records for g in b.gangs]


_ORACLE = {}  # the host oracle's answers, once per cluster and seed (it is the slow side)


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("shape", sorted(CLUSTERS))
@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_served_tensor_lanes_answer_as_the_plain_reference_and_the_host_oracle_do(bench, seed, shape, lane):
    config = config_of(CLUSTERS[shape])
    cluster = bench["generator"].make_cluster(config, seed, time.time())
    records, roots, fallbacks, prep = serve_blocks(
        bench, cluster, config, seed, {"binpack_algo": "tpu-batch", "fifo": True}, lane=lane
    )
    assert fallbacks == 0
    assert {g.read["lane"] for b in records for g in b.gangs} == {lane}
    # every driver answer, reservation (scheduler's and durable copy) and executor slot
    # against the plain reference, as the benchmark's comparison does it
    reference = bench["reference"].Reference(cluster, "tightly-pack")
    checks = bench["check"].compare(records, reference, cluster.names, drivers_mix()["steps"])
    assert bench["check"].is_correct(checks), checks
    assert checks["answers_compared"]["value"] == 3 * 16
    granted = [g.read["reservation"] for b in records for g in b.gangs]
    assert all(r is not None for r in granted)
    group_of = dict(zip(cluster.names, cluster.group))
    for b in records:
        for g in b.gangs:
            driver, executors = g.read["reservation"]
            assert {group_of[n] for n in (driver, *executors)} == {g.gang.group}
    # and against the host oracle, once per cluster
    key = (shape, seed)
    if key not in _ORACLE:
        oracle_cluster = bench["generator"].make_cluster(config, seed, time.time())
        _ORACLE[key] = reservations(
            serve_blocks(bench, oracle_cluster, config, seed, {"binpack_algo": "tightly-pack", "fifo": True})[0]
        )
    assert reservations(records) == _ORACLE[key]
    # the request's shape on its fifo_gate span: its group's nodes and queue, and their buckets
    stated = {n: (nodes, backlog) for n, nodes, backlog, _ in CLUSTERS[shape]["groups"]}
    gates = {}
    for root in roots:
        if root.name != "http.request" or find(root, "fifo_gate") is None:
            continue
        gates[find(root, "predicate").tags["pod"]] = find(root, "fifo_gate").tags
    assert len(gates) == 16
    for b in records:
        for g in b.gangs:
            tags = gates[g.gang.app_id + "-driver"]
            nodes, backlog = stated[g.gang.group]
            assert (tags["eligibleNodes"], tags["earlierApps"]) == (nodes, backlog)
            assert tags["nodeBucket"] == bucket_size(nodes)
            assert tags["appBucket"] == bucket_size(backlog + 1, buckets=APP_BUCKETS)
            assert tags["requestCompiles"] >= 0
    # the avail-independent prework is built once per group and then found again
    assert prep == {"hit": 16 - len(stated), "miss": len(stated), "uncacheable": 0}


def two_pools(lane, algo="tpu-batch"):
    """Pool ``big`` (8 nodes) and pool ``small`` (2 nodes), one zone."""
    h = Harness(binpack_algo=algo)
    solver = h.extender.binpacker.queue_solver
    if solver is not None:
        h.extender.delta_engine = None
        solver.backend = lane
        solver.interpret = True
    names = []
    for i in range(8):
        names.append(h.new_node(f"big-{i}", cpu="8", memory="16Gi", gpu="0", instance_group="big").name)
    for i in range(2):
        names.append(h.new_node(f"small-{i}", cpu="8", memory="16Gi", gpu="0", instance_group="small").name)
    return h, names


def queue(h, app_id, executors, group, age):
    pod = h.static_allocation_spark_pods(app_id, executors, instance_group=group)[0]
    pod.meta.creation_timestamp = time.time() - age
    h.create_pod(pod)
    return pod


def answer(h, names, app_id, executors, group):
    driver = h.static_allocation_spark_pods(app_id, executors, instance_group=group)[0]
    result = h.schedule(driver, names)
    rr = h.get_resource_reservation(app_id)
    slots = None if rr is None else tuple(sorted((s, r.node) for s, r in rr.spec.reservations.items()))
    return (result.node_names[0] if result.node_names else None), slots


@pytest.mark.parametrize("lane", LANES + ["host"])
def test_a_driver_of_one_group_is_unmoved_by_a_backlog_that_does_not_fit_in_another(lane):
    """``small``'s oldest pending driver asks for more than ``small`` can
    ever hold (and for less than the whole cluster holds): every later
    driver of ``small`` is refused behind it, and ``big``'s drivers are
    answered as if ``small`` had no backlog at all."""
    algo = "tightly-pack" if lane == "host" else "tpu-batch"
    got = {}
    for blocked in (False, True):
        h, names = two_pools(lane, algo)
        try:
            queue(h, "big-queued", 3, "big", age=500)
            if blocked:
                queue(h, "small-too-big", 30, "small", age=900)  # 31 cpu: more than small's 16, less than 80
            got[blocked] = [
                answer(h, names, "big-new-1", 5, "big"),
                answer(h, names, "small-new", 1, "small"),
                answer(h, names, "big-new-2", 20, "big"),
            ]
            assert h.extender.host_fallbacks() == 0
        finally:
            h.close()
    free, behind = got[False], got[True]
    assert behind[0] == free[0] and behind[2] == free[2]  # big: the same nodes, slot for slot
    assert free[0][0].startswith("big-") and all(node.startswith("big-") for _, node in free[0][1])
    assert free[1][0].startswith("small-")
    assert behind[1] == (None, None)  # small: refused behind its own group's head, nothing reserved


@pytest.mark.parametrize("lane", ["native", "xla"])
def test_the_markers_scan_judges_each_group_against_its_own_nodes(lane):
    """One empty cluster and one batch of verdicts per affinity signature:
    a gang that the whole cluster could hold and its own pool cannot
    exceeds capacity."""
    h, names = two_pools(lane)
    try:
        roots = []
        h.server.tracer.add_observer(roots.append)
        fits_big = queue(h, "aged-big", 30, "big", age=3600)        # 31 cpu of big's 64
        over_small = queue(h, "aged-small-over", 30, "small", age=3600)  # 31 cpu of small's 16
        fits_small = queue(h, "aged-small", 10, "small", age=3600)
        h.unschedulable_marker.scan_for_unschedulable_pods()
        (scan,) = [r for r in roots if r.name == "unschedulable.scan"]
        assert scan.tags["signatures"] == 2 and scan.tags["verdictBatches"] == 2
        assert scan.tags["pods"] == 3 and scan.tags["verdictMisses"] == 3
        verdicts = {
            pod.name: h.api.get("Pod", pod.namespace, pod.name).conditions["PodExceedsClusterCapacity"].status
            for pod in (fits_big, over_small, fits_small)
        }
        assert verdicts == {fits_big.name: "False", over_small.name: "True", fits_small.name: "False"}
        assert h.server.metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "tensor"}) == 3
    finally:
        h.close()


# -- the warm-up ----------------------------------------------------------------


def test_warm_shapes_are_each_groups_bucket_and_one_group_is_todays_case():
    base = warmup._BASE_SHAPES
    assert base == ((64, 16), (256, 16), (1024, 16))
    # fifo10k-groups: 2,200 / 1,500 / 1,100 nodes share the 4,096 bucket (none lies between 1,024 and 4,096)
    groups = [(4400, 440), (2200, 220), (1500, 150), (1100, 110), (800, 80)]
    assert warmup.warm_shapes(groups) == base + ((5120, 1024), (4096, 256), (1024, 256))
    # the whole-cluster shape is warmed only where one group is the whole cluster
    assert (10240, 1024) not in warmup.warm_shapes(groups)
    assert warmup.warm_shapes([(10_000, 1_000)]) == base + ((10240, 1024),)
    # the bucket above is not warmed, however near the queue stands to it
    assert warmup.warm_shapes([(10_000, 1_022)]) == base + ((10240, 1024),)
    assert warmup.warm_shapes([(10_000, 1_024)]) == base + ((10240, 4096),)
    assert warmup.warm_shapes([(0, 50), (60, 3)]) == base


def test_observed_groups_counts_nodes_and_pending_drivers_by_group():
    nodes = ["a"] * 5 + ["b"] * 3 + [None] * 2 + ["c"]
    drivers = ["a", "b", "a", "gone", "a"]
    assert sorted(warmup.observed_groups(nodes, drivers)) == [(1, 0), (3, 1), (5, 3)]
    assert warmup.observed_groups([], ["a"]) == []
    assert warmup.observed_groups([None, None], []) == []  # unlabelled nodes are in no group


@pytest.mark.parametrize("is_fifo", [True, False], ids=["fifo", "no-fifo"])
def test_the_server_observes_its_groups_and_without_the_fifo_every_queue_is_empty(is_fifo):
    h = Harness(binpack_algo="tpu-batch", is_fifo=is_fifo)
    try:
        assert h.server.observed_groups() == []
        for i in range(8):
            h.new_node(f"big-{i}", instance_group="big")
        for i in range(2):
            h.new_node(f"small-{i}", instance_group="small")
        for i in range(3):
            queue(h, f"big-queued-{i}", 1, "big", age=100 - i)
        queue(h, "small-queued", 1, "small", age=50)
        queue(h, "nowhere-queued", 1, "no-such-pool", age=40)
        assert h.wait_for_api(lambda: len(h.server.node_informer.list()) == 10)
        # without the FIFO a driver's queue pass is empty: it is served at (its nodes' bucket, 16)
        want = [(2, 1), (8, 3)] if is_fifo else [(2, 0), (8, 0)]
        assert sorted(h.server.observed_groups()) == want
    finally:
        h.close()


def test_after_a_warmup_per_group_no_request_compiles_and_a_new_shape_is_counted(bench, monkeypatch):
    """On a lane that compiles (XLA, as on a host with neither a TPU nor
    the C++ library): the server finds three groups at start and warms
    each one's bucket, so the first driver Filter of each compiles
    nothing; a group that appears afterwards at an unwarmed shape compiles
    on its first request, and the counter says where."""
    from k8s_spark_scheduler_tpu.ops import batch_solver, fifo_solver

    monkeypatch.setattr(fifo_solver, "_native_selected", lambda backend: False)
    shape = {"nodes": 1300, "backlog": 30, "groups": [("a", 1100, 20, 4), ("b", 150, 8, 3), ("c", 50, 2, 1)]}
    config = config_of(shape)
    cluster = bench["generator"].make_cluster(config, 19, time.time())
    mix = drivers_mix()
    stream = bench["generator"].blocks(config, mix, 19, cluster.base_ts)
    warm0, request0 = default_profiler.compiles("warmup"), default_profiler.compiles("request")
    served = bench["stack"].start_stack(cluster, bench["objects"], {"binpack_algo": "tpu-batch", "fifo": True})
    try:
        served.scheduler.extender.delta_engine = None
        metrics = served.scheduler.metrics
        # group a's own shape, (4096, 64), is no base shape: the warm-up compiled it (and the marker's program)
        assert default_profiler.compiles("warmup") - warm0 >= 1
        assert metrics.get_counter(
            mnames.KERNEL_COMPILES, {"kernel": "fifo_queue", "lane": "xla", "phase": "warmup"}
        ) >= 1
        stats = batch_solver.compilation_cache_stats()
        roots = []
        served.scheduler.tracer.add_observer(roots.append)
        client = client_of(bench, served, cluster.names)
        rec = bench["traffic"].run_block(client, bench["objects"], next(stream), mix["steps"])
        assert {g.read["lane"] for g in rec.gangs} == {"xla"}
        assert all(g.read["reservation"] is not None for g in rec.gangs)
        assert default_profiler.compiles("request") == request0
        assert batch_solver.compilation_cache_stats() == stats
        gates = [find(r, "fifo_gate").tags for r in roots if r.name == "http.request" and find(r, "fifo_gate")]
        assert {(t["nodeBucket"], t["appBucket"]) for t in gates} == {(4096, 64), (256, 16), (64, 16)}
        assert {t["requestCompiles"] for t in gates} == {request0}

        # a pool that joins later, behind a queue of its own: (256, 64) was never warmed
        from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
        from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources

        late = [f"late-{i:03d}" for i in range(70)]
        for name in late:
            served.api.create(Node(
                meta=ObjectMeta(name=name, labels={ZONE_LABEL: "z0", "resource_channel": "late"}),
                allocatable=Resources.of("16", "64Gi"),
            ))
        for i in range(20):
            pod = Harness.static_allocation_spark_pods(
                f"late-queued-{i}", 1, instance_group="late", creation_timestamp=cluster.base_ts + 5000 + i
            )[0]
            served.api.create(pod)
        newcomer = Harness.static_allocation_spark_pods(
            "late-new", 2, instance_group="late", creation_timestamp=time.time()
        )[0]
        late_client = client_of(bench, served, cluster.names + late)
        deadline = time.monotonic() + 10.0
        while len(served.scheduler.node_informer.list()) < 1300 + 70 and time.monotonic() < deadline:
            time.sleep(0.01)
        _, _, body = late_client.filter(late_client.create(newcomer))
        assert json.loads(body)["NodeNames"][0].startswith("late-")
        assert default_profiler.compiles("request") == request0 + 1
        assert metrics.get_counter(
            mnames.KERNEL_COMPILES, {"kernel": "fifo_queue", "lane": "xla", "phase": "request"}
        ) == 1
        # the next request's gate reads the running total
        second = Harness.static_allocation_spark_pods(
            "late-new-2", 1, instance_group="late", creation_timestamp=time.time()
        )[0]
        late_client.filter(late_client.create(second))
        assert find(roots[-1], "fifo_gate").tags["requestCompiles"] == request0 + 1
    finally:
        served.stop()


def test_a_compile_with_no_request_and_no_warmup_around_it_is_background():
    from k8s_spark_scheduler_tpu.tracing import profiling
    from k8s_spark_scheduler_tpu.tracing.spans import Tracer

    tracer = Tracer(capacity=4)
    assert profiling.compile_phase() == "background"
    with default_profiler.warming():
        assert profiling.compile_phase() == "warmup"
        with tracer.span("predicate"):
            assert profiling.compile_phase() == "warmup"  # what the caller states wins
    with tracer.span("http.request"):
        with tracer.span("predicate"):
            with tracer.span("fifo_gate"):
                assert profiling.compile_phase() == "request"
    with tracer.span("unschedulable.scan") as scan:
        assert profiling.compile_phase() == "background"
        with scan.aggregate("scan.solve"):
            assert profiling.compile_phase() == "background"
