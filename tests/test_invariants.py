"""Debug invariant checker tests."""

import random

from k8s_spark_scheduler_tpu.scheduler import invariants
from k8s_spark_scheduler_tpu.testing.harness import Harness


def test_invariants_hold_through_churn():
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        rng = random.Random(123)
        for i in range(4):
            h.new_node(f"n{i}")
        nodes = [f"n{i}" for i in range(4)]
        live = []
        for step in range(30):
            if rng.random() < 0.6 or not live:
                pods = h.static_allocation_spark_pods(f"a{step}", rng.randint(1, 3))
                if h.schedule(pods[0], nodes).node_names:
                    placed = [pods[0]]
                    for p in pods[1:]:
                        if h.schedule(p, nodes).node_names:
                            placed.append(p)
                    live.append(placed)
            else:
                for p in live.pop(rng.randrange(len(live))):
                    try:
                        h.delete_pod(p)
                    except Exception:
                        pass
                h.wait_quiesced()
            assert invariants.check(h.server) == []
    finally:
        h.close()


def test_invariants_catch_corruption():
    h = Harness()
    try:
        h.new_node("n1")
        pods = h.static_allocation_spark_pods("app-c", 1)
        h.assert_success(h.schedule(pods[0], ["n1"]))
        # corrupt: bind a pod to a nonexistent reservation name
        rr = h.server.resource_reservation_cache.get("default", "app-c").deepcopy()
        rr.status.pods["executor-99"] = "ghost"
        h.server.resource_reservation_cache.update(rr)
        violations = invariants.check(h.server, raise_on_violation=False)
        assert any(v.startswith("I1") for v in violations)
    finally:
        h.close()


def test_invariant_i5_catches_a_mirror_blind_to_daemonset_pods(monkeypatch):
    """I5 holds the mirror to the overhead read from scratch: a mirror
    that never sees a pod another scheduler bound drifts on that node."""
    from k8s_spark_scheduler_tpu.scheduler import labels as L
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshotCache
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    real = TensorSnapshotCache._on_pod

    def blind(self, pod):
        if pod.scheduler_name == L.SPARK_SCHEDULER_NAME:
            real(self, pod)

    monkeypatch.setattr(TensorSnapshotCache, "_on_pod", blind)
    h = Harness(binpack_algo="tpu-batch")
    try:
        h.new_node("n1")
        h.new_node("n2")
        assert invariants.check(h.server) == []
        h.create_pod(Pod(
            meta=ObjectMeta(name="daemon-n1", namespace="kube-system"),
            scheduler_name="default-scheduler", node_name="n1",
            containers=[Container("agent", Resources.of("100m", "128Mi"))], phase=PodPhase.RUNNING,
        ))
        violations = invariants.check(h.server, raise_on_violation=False)
        assert [v.split(":")[0] for v in violations] == ["I5"] and "n1" in violations[0]
    finally:
        h.close()
