"""The single-AZ policy on the served tensor path
(TpuSingleAzFifoSolver.solve_tensor through the extender) against the
host oracle (packers.single_az_tightly_pack behind the extender's host
FIFO loop): the same cluster, the same backlog, the same stream of
drivers through two harnesses; driver node, executor nodes and their
order equal, refusals equal.  Also the valve: ties and near-ties are
decided exactly for that app alone, on the device lane."""

import random
import time

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.testing.harness import Harness

LANES = ["xla", "pallas", "native"]
# (nodes, pending drivers, zones)
SHAPES = [(64, 8, 2), (160, 24, 3), (512, 60, 4)]


def served(algo, lane=None):
    h = Harness(binpack_algo=algo)
    solver = h.extender.binpacker.queue_solver
    if solver is not None:
        h.extender.delta_engine = None
        solver.backend = lane
        solver.interpret = True  # the pallas lane on the CPU
    return h


def populate(h, rng, nodes, pending, zones, sizes=None, empty_zone=False):
    """Nodes over ``zones`` zones (one of them too small for anything
    where ``empty_zone``), ``pending`` aged drivers, the same for every
    harness given the same ``rng`` state."""
    names = []
    for i in range(nodes):
        zone = i % zones
        cpu, mem = sizes[i % len(sizes)] if sizes else (rng.randint(4, 32), rng.randint(8, 64))
        if empty_zone and zone == zones - 1:
            cpu, mem = 1, 1
        name = f"n{i:04d}"
        h.new_node(name, cpu=str(cpu), memory=f"{mem}Gi", gpu="0", zone=f"z{zone}")
        names.append(name)
    base = time.time() - 10_000
    for i in range(pending):
        pod = h.static_allocation_spark_pods(
            f"queued-{i:03d}", rng.randint(1, 6),
            executor_cpu=str(rng.randint(1, 3)), executor_mem=f"{rng.randint(1, 6)}Gi",
        )[0]
        pod.meta.creation_timestamp = base + i
        h.create_pod(pod)
    return names


def stream(rng, count):
    """New drivers behind the backlog: mostly fitting, one that cannot."""
    gangs = [
        (f"new-{j}", rng.randint(1, 8), rng.randint(1, 3), rng.randint(1, 6))
        for j in range(count)
    ]
    gangs.insert((count + 1) // 2, ("new-huge", 4000, 3, 6))
    return gangs


def answers(h, names, gangs):
    """What each driver of the stream is answered and what is reserved
    for it: (node or None, executor nodes in slot order)."""
    out = []
    for app_id, k, cpu, mem in gangs:
        driver = h.static_allocation_spark_pods(
            app_id, k, executor_cpu=str(cpu), executor_mem=f"{mem}Gi"
        )[0]
        result = h.schedule(driver, names)
        node = result.node_names[0] if result.node_names else None
        rr = h.get_resource_reservation(app_id)
        slots = None
        if rr is not None:
            by_slot = {s: r.node for s, r in rr.spec.reservations.items()}
            assert by_slot.pop("driver") == node
            slots = tuple(by_slot[s] for s in sorted(by_slot, key=lambda s: int(s.rsplit("-", 1)[1])))
        out.append((app_id, node, slots))
    return out


_ORACLE = {}  # the host oracle's answers, once per cluster and stream (it is the slow side)


def both(lane, seed, shape, drivers=4, **cluster):
    nodes, pending, zones = shape
    key = (seed, shape, drivers, repr(sorted(cluster.items())))
    got = {}
    for algo in ("tpu-batch-single-az", "single-az-tightly-pack"):
        if algo in _ORACLE.get(key, {}):
            got[algo] = _ORACLE[key][algo]
            continue
        rng = random.Random(seed)
        h = served(algo, lane)
        try:
            names = populate(h, rng, nodes, pending, zones, **cluster)
            got[algo] = answers(h, names, stream(rng, drivers))
            if not algo.startswith("tpu"):
                _ORACLE[key] = {algo: got[algo]}
            else:
                solver = h.extender.binpacker.queue_solver
                got["solver"] = (solver.last_path, solver.last_queue_lane, dict(solver.last_zone_choices))
                got["resolved"] = h.server.metrics.get_counter(
                    mnames.FIFO_ZONE_CHOICE, {"result": "resolved"}
                )
                got["host_queue"] = h.server.metrics.get_counter(
                    mnames.FIFO_ZONE_CHOICE, {"result": "host-queue"}
                )
                got["fallbacks"] = h.extender.host_fallbacks()
        finally:
            h.close()
    return got


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("lane", LANES)
def test_served_single_az_answers_as_the_host_oracle_does(lane, shape):
    # the host oracle takes minutes at the largest shape: a shorter stream there
    drivers = 4 if shape[0] < 512 else 1
    got = both(lane, 7_000 + shape[0], shape, drivers=drivers, empty_zone=shape[2] > 2)
    assert got["tpu-batch-single-az"] == got["single-az-tightly-pack"]
    granted = [a[0] for a in got["tpu-batch-single-az"] if a[1] is not None]
    refused = [a[0] for a in got["tpu-batch-single-az"] if a[1] is None]
    # the gang that cannot fit is refused, and so is every driver behind it (FIFO)
    half = (drivers + 1) // 2
    assert granted == [f"new-{j}" for j in range(half)]
    assert refused == ["new-huge"] + [f"new-{j}" for j in range(half, drivers)]
    path, queue_lane, _ = got["solver"]
    assert queue_lane == lane and path == ("native" if lane == "native" else "fused")
    assert got["fallbacks"] == 0 and got["host_queue"] == 0


# node sizes repeated across the zones, as the benchmark's stratified
# multisets repeat them: zones whose packings score the same
TYING = [
    ("one size", [(8, 16)]),
    ("two sizes", [(8, 16), (8, 16), (8, 16), (16, 32), (16, 32), (16, 32)]),
    ("three sizes", [(4, 8)] * 3 + [(8, 16)] * 3 + [(12, 48)] * 3),
]


@pytest.mark.parametrize("lane", ["xla", "pallas"])
@pytest.mark.parametrize("label,sizes", TYING, ids=[t[0] for t in TYING])
def test_ties_between_zones_are_resolved_exactly_on_the_device_lane(lane, label, sizes):
    got = both(lane, 99, (90, 20, 3), sizes=sizes)
    assert got["tpu-batch-single-az"] == got["single-az-tightly-pack"]
    assert got["solver"][:2] == ("fused", lane)
    assert got["resolved"] > 0 and got["host_queue"] == 0 and got["fallbacks"] == 0


def test_a_gap_below_one_quantisation_step_is_decided_in_float64():
    """Two zones whose true averages differ by 2.5e-7, a fifteenth of a
    step of the device's 18-bit score, so that both quantise to the same
    score: the later zone is the better one only in float64, and that is
    the one the oracle takes.  (Equal scores used to keep the earlier
    zone unexamined.)"""
    import copy

    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver
    from k8s_spark_scheduler_tpu.ops.packers import single_az_tightly_pack
    from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_resource_usage
    from k8s_spark_scheduler_tpu.types.resources import (
        NodeSchedulingMetadata,
        Resources,
        subtract_usage_if_exists,
    )

    def zone(avail_mem, label):
        return NodeSchedulingMetadata(
            available=Resources.of("64", str(avail_mem)),
            schedulable=Resources.of("64", "4000000"),
            zone_label=label,
        )

    # reserved memory 1,600,000 of 4,000,000 in z0 against 1,600,001 in z1
    metadata = {"a0": zone(2_500_000, "z0"), "a1": zone(2_499_999, "z1")}
    order = ["a0", "a1"]
    app = AppDemand(Resources.of("1", "50000"), Resources.of("1", "50000"), 1)
    pack = (app.driver_resources, app.executor_resources, 1, order, order)
    first = single_az_tightly_pack(*pack, metadata)
    assert first.driver_node == "a1"  # strictly better, by a hair
    after = copy.deepcopy(metadata)
    subtract_usage_if_exists(
        after, spark_resource_usage(*pack[:2], first.driver_node, first.executor_nodes)
    )
    second = single_az_tightly_pack(*pack, after)
    for lane in ("xla", "pallas"):
        solver = TpuSingleAzFifoSolver(backend=lane, interpret=True)
        # the earlier app is the near-tie; the request's own app lands where
        # the oracle puts it only if the earlier one went to a1
        outcome = solver.solve(metadata, order, order, [app], [False], app)
        assert solver.last_zone_choices == {"certified": 0, "resolved": 1, "unmemoised": 0}, lane
        assert solver.last_path == "fused" and solver.last_launches == 2, lane
        assert outcome.earlier_ok and outcome.result.has_capacity
        assert outcome.result.driver_node == second.driver_node, lane
        assert outcome.result.executor_nodes == second.executor_nodes, lane
        # on its own the near-tie goes where float64 says
        alone = solver.solve(metadata, order, order, [], [], app)
        assert alone.result.driver_node == "a1", lane
