"""Serde round-trip property tests (reservations + demands, both
CRD versions)."""


def test_serde_roundtrip_properties():
    """Randomized round-trips: obj -> dict -> obj -> dict must be stable
    for reservations (both versions) and demands (both versions)."""
    import random

    from k8s_spark_scheduler_tpu.types import serde
    from k8s_spark_scheduler_tpu.types.objects import (
        Demand,
        DemandSpec,
        DemandStatus,
        DemandUnit,
        ObjectMeta,
        Reservation,
        ResourceReservation,
        ResourceReservationSpec,
        ResourceReservationStatus,
    )
    from k8s_spark_scheduler_tpu.types.resources import Resources

    rng = random.Random(2026)
    for trial in range(25):
        reservations = {}
        for i in range(rng.randint(1, 6)):
            name = "driver" if i == 0 else f"executor-{i}"
            reservations[name] = Reservation.for_resources(
                f"node-{rng.randint(0, 5)}",
                Resources.of(
                    rng.choice(["1", "500m", "2500m"]),
                    rng.choice(["1Gi", "512Mi", "3Gi"]),
                    str(rng.randint(0, 4)),
                ),
            )
        rr = ResourceReservation(
            meta=ObjectMeta(name=f"app-{trial}", labels={"spark-app-id": f"app-{trial}"}),
            spec=ResourceReservationSpec(reservations=reservations),
            status=ResourceReservationStatus(
                pods={n: f"pod-{n}" for n in list(reservations)[: rng.randint(0, len(reservations))]}
            ),
        )
        # v1beta2 round trip
        d2 = serde.rr_to_dict_v1beta2(rr)
        assert serde.rr_to_dict_v1beta2(serde.rr_from_dict_v1beta2(d2)) == d2
        # v1beta1 round trip through the hub is lossless on the spec
        d1 = serde.rr_to_dict_v1beta1(rr)
        back = serde.rr_from_dict_v1beta1(d1)
        assert serde.rr_to_dict_v1beta2(back)["spec"] == d2["spec"]
        assert back.status.pods == rr.status.pods

        demand = Demand(
            meta=ObjectMeta(name=f"demand-pod-{trial}"),
            spec=DemandSpec(
                units=[
                    DemandUnit(
                        resources=Resources.of(str(rng.randint(1, 8)), f"{rng.randint(1, 16)}Gi"),
                        count=rng.randint(1, 20),
                        pod_names_by_namespace={"default": [f"p{trial}"]} if rng.random() < 0.5 else {},
                    )
                    for _ in range(rng.randint(1, 3))
                ],
                instance_group="batch",
                enforce_single_zone_scheduling=rng.random() < 0.5,
                zone=rng.choice([None, "az-a"]),
            ),
            status=DemandStatus(phase=rng.choice(["", "pending", "fulfilled"])),
        )
        da2 = serde.demand_to_dict_v1alpha2(demand)
        assert serde.demand_to_dict_v1alpha2(serde.demand_from_dict_v1alpha2(da2)) == da2
        da1 = serde.demand_to_dict_v1alpha1(demand)
        back_d = serde.demand_from_dict_v1alpha1(da1)
        da2_back = serde.demand_to_dict_v1alpha2(back_d)
        assert da2_back["spec"] == da2["spec"]
        assert da2_back["status"] == da2["status"]


def test_pod_init_containers_round_trip_and_requests():
    """initContainers parse + serialize; pod requests = max(sum of
    containers, each init container) per dimension (overhead.go:195-209)."""
    from k8s_spark_scheduler_tpu.types.resources import pod_to_resources
    from k8s_spark_scheduler_tpu.types import serde
    from k8s_spark_scheduler_tpu.types.resources import Resources

    pod_json = {
        "metadata": {"name": "p"},
        "spec": {
            "containers": [
                {"name": "a", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}},
                {"name": "b", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}},
            ],
            "initContainers": [
                {"name": "init", "resources": {"requests": {"cpu": "4", "memory": "1Gi"}}},
            ],
        },
    }
    pod = serde.pod_from_dict(pod_json)
    assert [c.name for c in pod.init_containers] == ["init"]
    # cpu: init (4) > sum (2); memory: sum (2Gi) > init (1Gi)
    assert pod_to_resources(pod).eq(Resources.of("4", "2Gi"))

    again = serde.pod_from_dict(serde.pod_to_dict(pod))
    assert [c.requests.cpu.value() for c in again.init_containers] == [4]
    assert pod_to_resources(again).eq(Resources.of("4", "2Gi"))

    # pods without init containers keep a clean wire form
    no_init = serde.pod_to_dict(serde.pod_from_dict({"metadata": {"name": "q"}}))
    assert "initContainers" not in no_init["spec"]
