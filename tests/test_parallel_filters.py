"""Overlapping Filters on the path that serves them.

kube-scheduler runs one scheduling cycle at a time, but its retries can
overlap a request still in flight (httpTimeout expiry, a second
scheduler instance).  What the server owes those callers is
linearizability: whatever the arrival pattern, the answers and the
reservations are the ones a serial run of the same requests, in the
order the predicate lock admitted them, would have produced — and a
request the admission gate turned away is answered retriable, never
dropped.  The property test fans seeded workloads over 2/4/8 client
threads through ``_Handler._predicate_guarded`` (admission gate →
request deadline → ``extender.predicate``), records the order in which
``_predicate_locked`` ran, and replays that order one request at a time
on a fresh stack.
"""

import logging
import threading
import types

import pytest

from k8s_spark_scheduler_tpu.config import FifoConfig, Install, ResilienceConfig
from k8s_spark_scheduler_tpu.scheduler import invariants
from k8s_spark_scheduler_tpu.server.http import _Handler
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs
from k8s_spark_scheduler_tpu.types.objects import Pod, PodPhase, ResourceReservation

SHED_MESSAGE = "scheduler overloaded; retry"
# small enough that 4 and 8 clients shed on their own, and the test can
# fill it to make every client's first attempt a shed
GATE_SLOTS = 2


def _install(policy: str) -> Install:
    return Install(
        fifo=True,
        fifo_config=FifoConfig(),
        binpack_algo=policy,
        resilience=ResilienceConfig(admission_max_waiters=GATE_SLOTS),
    )


# -- the seeded workload (test_policy.py's idiom: varied sizes so some
#    apps fit, some hit failure-fit, refused ones gate later drivers) ---


def _seeded_workload(seed: int):
    import numpy as np

    rng = np.random.RandomState(seed)
    nodes = [
        (
            f"n{i}",
            str(int(rng.randint(2, 6))),
            f"{int(rng.randint(3, 7))}Gi",
            f"zone{i % 3 + 1}",
        )
        for i in range(6)
    ]
    apps = [
        (
            f"app-{seed}-{i}",
            int(rng.randint(0, 4)),
            str(int(rng.randint(1, 3))),
        )
        for i in range(8)
    ]
    return nodes, apps


def _build_cluster(h: Harness, seed: int):
    """Create nodes + every pod up front (creation timestamps fix the
    FIFO queue order) and return the flat request list — [driver,
    execs..] per app, app by app — and the candidate node names."""
    nodes, apps = _seeded_workload(seed)
    for name, cpu, mem, zone in nodes:
        h.new_node(name, cpu=cpu, memory=mem, zone=zone)
    node_names = [n[0] for n in nodes]
    flat = []
    for i, (app_id, executor_count, executor_cpu) in enumerate(apps):
        pods = h.static_allocation_spark_pods(
            app_id,
            executor_count,
            executor_cpu=executor_cpu,
            creation_timestamp=1000.0 + i,
        )
        for pod in pods:
            h.create_pod(pod)
            flat.append(pod)
    return flat, node_names


def _decision(result):
    return (
        tuple(result.node_names or ()),
        tuple(sorted((result.failed_nodes or {}).items())),
    )


def _is_shed(result, node_names):
    return not result.node_names and result.failed_nodes == {
        n: SHED_MESSAGE for n in node_names
    }


def _record_lock_order(h: Harness, order: list, violations: list) -> None:
    """Wrap ``_predicate_locked`` the way server/wiring.py's invariants
    wrapper does: inside the predicate lock, so what it sees and what it
    appends are the lock's own order.  The bind that follows a granted
    Filter happens here too — kube-scheduler binds before its next
    cycle, so the bind belongs to the request's turn, not to whichever
    client thread wakes first."""
    extender = h.extender
    original = extender._predicate_locked

    def recorded(args):
        result = original(args)
        violations.extend(invariants.check(h.server, raise_on_violation=False))
        order.append(args.pod.name)
        if result.node_names:
            bound = h.api.get(Pod.KIND, args.pod.namespace, args.pod.name)
            bound.node_name = result.node_names[0]
            bound.phase = PodPhase.RUNNING
            h.api.update(bound)
        return result

    extender._predicate_locked = recorded


def _reservations(h: Harness):
    assert h.wait_quiesced(10.0), "reservation write-back never settled"
    return {
        rr.name: (
            sorted(
                (slot, r.node, str(r.resources))
                for slot, r in rr.spec.reservations.items()
            ),
            sorted(rr.status.pods.items()),
        )
        for rr in h.api.list(ResourceReservation.KIND)
    }


def _args(h: Harness, pod, node_names) -> ExtenderArgs:
    fresh = h.server.pod_informer.get(pod.namespace, pod.name).deepcopy()
    return ExtenderArgs(pod=fresh, node_names=list(node_names))


def _run_parallel(policy: str, seed: int, n_threads: int):
    h = Harness(extra_install=_install(policy))
    try:
        flat, node_names = _build_cluster(h, seed)
        order, violations = [], []
        _record_lock_order(h, order, violations)
        shim = types.SimpleNamespace(scheduler=h.server)
        gate = h.server.resilience.gate
        answers, sheds, errors = {}, [], []
        first_shed = threading.Semaphore(0)

        def worker(idx: int):
            try:
                reported = False
                # each thread owns every (n_threads)-th request
                for j in range(idx, len(flat), n_threads):
                    while True:
                        args = _args(h, flat[j], node_names)
                        result = _Handler._predicate_guarded(shim, args)
                        if not _is_shed(result, node_names):
                            break
                        sheds.append(flat[j].name)
                        if not reported:
                            reported = True
                            first_shed.release()
                        # the retry a shed caller owes; real time, the
                        # gate frees when an admitted request finishes
                        threading.Event().wait(0.001)
                    answers[flat[j].name] = _decision(result)
            except BaseException as err:  # noqa: BLE001 - surfaced below
                errors.append((idx, err))
                first_shed.release()

        # the gate is full when the clients arrive: every client's first
        # attempt is shed, whatever the scheduler's timing
        held = [gate.admit() for _ in range(GATE_SLOTS)]
        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for _ in threads:
            assert first_shed.acquire(timeout=30), "a client was never shed"
        assert order == [], "a request ran while the gate was full"
        for slot in held:
            slot.__exit__(None, None, None)
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads), "a client never finished"
        return {
            "flat": [p.name for p in flat],
            "order": order,
            "answers": answers,
            "sheds": sheds,
            "shed_total": gate.shed_total,
            "violations": violations
            + invariants.check(h.server, raise_on_violation=False),
            "reservations": _reservations(h),
        }
    finally:
        h.close()


def _replay(policy: str, seed: int, order):
    h = Harness(extra_install=_install(policy))
    try:
        flat, node_names = _build_cluster(h, seed)
        by_name = {p.name: p for p in flat}
        replayed, violations = [], []
        _record_lock_order(h, replayed, violations)
        shim = types.SimpleNamespace(scheduler=h.server)
        answers = {}
        for name in order:
            result = _Handler._predicate_guarded(
                shim, _args(h, by_name[name], node_names)
            )
            answers[name] = _decision(result)
        assert replayed == list(order)
        assert violations == []
        assert h.server.resilience.gate.shed_total == 0
        return answers, _reservations(h)
    finally:
        h.close()


POLICIES = [
    "tpu-batch",
    "tpu-batch-distribute-evenly",
    "tpu-batch-minimal-fragmentation",
    "tpu-batch-single-az",
]


@pytest.mark.parametrize("n_threads", [2, 4, 8])
@pytest.mark.parametrize("policy", POLICIES)
def test_parallel_filters_linearize(policy, n_threads):
    seed = 11 + 13 * n_threads + POLICIES.index(policy)
    run = _run_parallel(policy, seed, n_threads)

    # every request ran under the lock exactly once: a shed never
    # reached the extender, and its retry did
    assert sorted(run["order"]) == sorted(run["flat"])
    assert set(run["answers"]) == set(run["flat"])
    # every shed was answered retriable (all candidates failed with the
    # overload message — _is_shed) and counted by the gate; each client
    # was shed at least once
    assert len(run["sheds"]) >= n_threads
    assert run["shed_total"] == len(run["sheds"])
    assert run["violations"] == []
    # the workload decides something: gangs were admitted
    assert run["reservations"], "no gang was admitted"
    assert any(nodes for nodes, _ in run["answers"].values())

    answers, reservations = _replay(policy, seed, run["order"])
    assert answers == run["answers"]
    assert reservations == run["reservations"]


# -- an install file written for the removed engine ---------------------


def test_install_with_removed_concurrent_block_warns_and_serves(caplog):
    """``concurrent:`` in an install file is input from outside the
    program: it is named in one warning and ignored — decisions were
    byte-identical with the engine on or off — and the stack built from
    that file answers Filters through the extender."""
    doc = {
        "fifo": True,
        "binpack": "tpu-batch",
        "concurrent": {"enabled": True, "multi-active": True},
    }
    with caplog.at_level(logging.WARNING, logger="k8s_spark_scheduler_tpu.config"):
        install = Install.from_dict(doc)
    warnings = [r for r in caplog.records if "concurrent" in r.getMessage()]
    assert len(warnings) == 1, [r.getMessage() for r in caplog.records]
    assert not hasattr(install, "concurrent")
    del doc["concurrent"]
    caplog.clear()
    assert Install.from_dict(doc) == install
    assert not caplog.records

    h = Harness(extra_install=install)
    try:
        assert not hasattr(h.server, "concurrent")
        h.new_node("n1", cpu="8", memory="8Gi")
        pods = h.static_allocation_spark_pods("app-legacy", 1)
        shim = types.SimpleNamespace(scheduler=h.server)
        for pod in pods:
            created = h.create_pod(pod)
            result = _Handler._predicate_guarded(
                shim, ExtenderArgs(pod=created, node_names=["n1"])
            )
            assert result.node_names == ["n1"], result.failed_nodes
    finally:
        h.close()


# -- AdmissionGate shed: terminal phase + provenance + revival ----------


def test_shed_leaves_audit_trail_and_revives_on_retry():
    """A shed Filter must leave the same audit trail a refusal does:
    a provenance DecisionRecord (``/explain`` answers for sheds too), a
    lifecycle ``shed`` phase, and pod/namespace/outcome tags on the
    trace span — then kube-scheduler's retry revives the gang out of
    ``shed`` into the live phases."""
    install = Install(
        fifo=True,
        fifo_config=FifoConfig(),
        binpack_algo="tightly-pack",
        resilience=ResilienceConfig(admission_max_waiters=1),
    )
    h = Harness(extra_install=install)
    try:
        h.new_node("n1", cpu="8", memory="8Gi")
        pods = h.static_allocation_spark_pods("app-shed", 0)
        driver = h.create_pod(pods[0])
        args = ExtenderArgs(pod=driver, node_names=["n1"])
        shim = types.SimpleNamespace(scheduler=h.server)
        kit = h.server.resilience
        with kit.gate.admit():  # occupy the only admission slot
            result = _Handler._predicate_guarded(shim, args)
        assert not result.node_names
        assert set(result.failed_nodes) == {"n1"}
        assert "overloaded" in result.failed_nodes["n1"]

        # provenance: the shed is explainable by pod name
        rec = h.server.provenance.explain(driver.name, source="test")
        assert rec is not None
        assert rec["outcome"] == "shed"
        assert rec["namespace"] == "default"

        # lifecycle: the gang carries the terminal-for-the-attempt phase
        gang = h.server.lifecycle.record("app-shed")
        assert gang is not None and gang["phase"] == "shed"

        # the retry (gate slot free now) admits and revives the record
        retry = _Handler._predicate_guarded(shim, args)
        assert retry.node_names == ["n1"]
        deadline = threading.Event()
        for _ in range(100):
            gang = h.server.lifecycle.record("app-shed")
            if gang["phase"] != "shed":
                break
            deadline.wait(0.05)
        assert gang["phase"] != "shed", gang
    finally:
        h.close()
