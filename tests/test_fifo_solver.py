"""Device FIFO solver parity vs the extender's host loop, plus
end-to-end extender behavior under binpack: tpu-batch with FIFO."""

import random
import time

import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops import packers
from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuFifoSolver
from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_resource_usage
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types.resources import (
    NodeSchedulingMetadata,
    Resources,
    copy_metadata,
    subtract_usage_if_exists,
)

from test_batch_parity import orders_for, random_app, random_cluster


def host_fifo_oracle(
    metadata, driver_order, executor_order, earlier, skip_allowed, current,
    packer=None,
):
    """The reference's fitEarlierDrivers + final pack, on the oracles."""
    packer = packer or packers.tightly_pack
    meta = copy_metadata(metadata)
    for app, skippable in zip(earlier, skip_allowed):
        result = packer(
            app.driver_resources,
            app.executor_resources,
            app.min_executor_count,
            driver_order,
            executor_order,
            meta,
        )
        if not result.has_capacity:
            if skippable:
                continue
            return False, None
        subtract_usage_if_exists(
            meta,
            spark_resource_usage(
                app.driver_resources,
                app.executor_resources,
                result.driver_node,
                result.executor_nodes,
            ),
        )
    return True, packer(
        current.driver_resources,
        current.executor_resources,
        current.min_executor_count,
        driver_order,
        executor_order,
        meta,
    )


def test_fifo_solver_parity_random():
    rng = random.Random(31337)
    solver = TpuFifoSolver()
    for trial in range(25):
        metadata = random_cluster(rng, rng.randint(2, 20))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(0, 8))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)

        expected_ok, expected_result = host_fifo_oracle(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        outcome = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        assert outcome.supported
        assert outcome.earlier_ok == expected_ok, f"trial {trial}: earlier_ok"
        if expected_ok:
            assert outcome.result.has_capacity == expected_result.has_capacity, (
                f"trial {trial}: current feasibility"
            )
            if expected_result.has_capacity:
                assert outcome.result.driver_node == expected_result.driver_node, (
                    f"trial {trial}: driver node"
                )
                assert outcome.result.executor_nodes == expected_result.executor_nodes, (
                    f"trial {trial}: placement"
                )


def test_lazy_efficiencies_match_scalar_reference():
    """The vectorized efficiency columns must be bit-identical to the
    scalar value()/ratio computation (efficiency.go:80-105 semantics),
    and seq_max_avg must equal the metric path's sequential iteration."""
    import numpy as np

    from k8s_spark_scheduler_tpu.ops.fifo_solver import efficiencies_from_rows

    rng = np.random.RandomState(99)
    n = 200
    names = [f"n{i:03d}" for i in range(n)]
    sched = np.stack([
        rng.randint(0, 96001, n), rng.randint(0, 2**34, n), rng.randint(0, 8001, n),
    ], axis=1).astype(np.int64)
    avail = (sched * rng.uniform(0, 1, (n, 3))).astype(np.int64)
    reserved = ((sched - avail) * rng.uniform(0, 1, (n, 3))).astype(np.int64)

    lazy = efficiencies_from_rows(names, sched, avail, reserved)

    def ceil_div(v, d):
        return -((-int(v)) // d)

    maxes = []
    for i, name in enumerate(names):
        s_cpu = ceil_div(sched[i, 0], 1000)
        s_gpu = ceil_div(sched[i, 2], 1000)
        r = sched[i] - avail[i] + reserved[i]
        r_cpu = ceil_div(r[0], 1000)
        r_gpu = ceil_div(r[2], 1000)
        want_cpu = float(r_cpu) / float(s_cpu if s_cpu != 0 else 1)
        want_mem = float(int(r[1])) / float(int(sched[i, 1]) if sched[i, 1] != 0 else 1)
        want_gpu = 0.0 if s_gpu == 0 else float(r_gpu) / float(s_gpu)
        e = lazy[name]
        assert e.cpu == want_cpu and e.memory == want_mem and e.gpu == want_gpu, name
        maxes.append(max(want_gpu, want_cpu, want_mem))
    # Neumaier-compensated sum: the gauge's cross-lane bit-equality
    # contract needs an order-robust reduction (different lanes sum the
    # same maxes in different node orders), so seq_max_avg compensates
    # regardless of what THIS interpreter's builtin sum() does (plain
    # before CPython 3.12, Neumaier after)
    s = c = 0.0
    for x in maxes:
        t = s + x
        c += (s - t) + x if abs(s) >= abs(x) else (x - t) + s
        s = t
    assert lazy.seq_max_avg() == (s + c) / max(len(maxes), 1)

    # the full dict read protocol reflects all nodes, in node order,
    # regardless of which entries were materialized first
    partial = efficiencies_from_rows(names, sched, avail, reserved)
    _ = partial[names[57]]  # materialize one mid-list entry
    assert len(partial) == n and names[3] in partial and "nope" not in partial
    assert list(partial) == names and partial.keys() == names
    assert [e.node_name for e in partial.values()] == names
    assert [k for k, _v in partial.items()] == names
    assert set(partial) == set(names)
    assert partial.get("nope") is None
    assert bool(partial)


def test_extender_tpu_batch_fifo_end_to_end():
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1")
        h.new_node("n2")
        nodes = ["n1", "n2"]
        t0 = time.time()
        blocked = h.static_allocation_spark_pods("app-old", 64, creation_timestamp=t0 - 100)[0]
        newer = h.static_allocation_spark_pods("app-new", 1, creation_timestamp=t0)[0]
        h.create_pod(blocked)
        # FIFO through the device path blocks the newer driver
        result = h.schedule(newer, nodes)
        h.assert_failure(result)
        assert "earlier drivers" in list(result.failed_nodes.values())[0]

        # remove the blocker; the newer driver schedules via the device path
        h.delete_pod(blocked)
        h.assert_success(h.schedule(newer, nodes))
        rr = h.get_resource_reservation("app-new")
        assert rr is not None and len(rr.spec.reservations) == 2
    finally:
        h.close()


def test_extender_tpu_batch_gang_semantics_match_tightly():
    """The tpu-batch extender must make the same decisions as tightly-pack
    on an identical scenario sequence."""
    results = {}
    for algo in ("tightly-pack", "tpu-batch"):
        h = Harness(binpack_algo=algo, is_fifo=True)
        try:
            h.new_node("n1", cpu="6", memory="6Gi")
            h.new_node("n2", cpu="6", memory="6Gi")
            nodes = ["n1", "n2"]
            log = []
            for i, (app, execs) in enumerate([("a", 3), ("b", 4), ("c", 2)]):
                pods = h.static_allocation_spark_pods(f"app-{app}", execs)
                r = h.schedule(pods[0], nodes)
                log.append((f"driver-{app}", tuple(r.node_names or [])))
                if r.node_names:
                    for p in pods[1:]:
                        er = h.schedule(p, nodes)
                        log.append((p.name, tuple(er.node_names or [])))
            results[algo] = log
        finally:
            h.close()
    assert results["tightly-pack"] == results["tpu-batch"]


def host_single_az_fifo_oracle(
    metadata, driver_order, executor_order, earlier, skip_allowed, current, az_aware
):
    """The extender's host loop with the single-AZ oracles."""
    oracle = packers.az_aware_tightly_pack if az_aware else packers.single_az_tightly_pack
    meta = copy_metadata(metadata)
    for app, skippable in zip(earlier, skip_allowed):
        result = oracle(
            app.driver_resources, app.executor_resources, app.min_executor_count,
            driver_order, executor_order, meta,
        )
        if not result.has_capacity:
            if skippable:
                continue
            return False, None
        subtract_usage_if_exists(
            meta,
            spark_resource_usage(
                app.driver_resources, app.executor_resources,
                result.driver_node, result.executor_nodes,
            ),
        )
    return True, oracle(
        current.driver_resources, current.executor_resources,
        current.min_executor_count, driver_order, executor_order, meta,
    )


@pytest.mark.parametrize("az_aware", [False, True])
def test_single_az_fifo_solver_parity(az_aware):
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    rng = random.Random(60606 + az_aware)
    solver = TpuSingleAzFifoSolver(az_aware=az_aware, backend="xla")
    fused_trials = 0
    for trial in range(20):
        metadata = random_cluster(rng, rng.randint(2, 18))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(0, 6))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)

        expected_ok, expected = host_single_az_fifo_oracle(
            metadata, driver_order, executor_order, earlier, skip_allowed, current, az_aware
        )
        outcome = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        assert outcome.supported
        fused_trials += solver.last_path == "fused"
        assert outcome.earlier_ok == expected_ok, f"trial {trial}: earlier_ok"
        if expected_ok:
            assert outcome.result.has_capacity == expected.has_capacity, f"trial {trial}"
            if expected.has_capacity:
                assert outcome.result.driver_node == expected.driver_node, f"trial {trial}"
                assert outcome.result.executor_nodes == expected.executor_nodes, f"trial {trial}"
    # the randomized clusters satisfy the fused lane's numeric bounds, so
    # the one-dispatch path must actually be the one under test
    assert fused_trials >= 10, f"fused lane engaged in only {fused_trials}/20 trials"


def _two_zone_cluster(mem_avail_a, mem_avail_b, sched_mem="1000000"):
    from k8s_spark_scheduler_tpu.types.resources import (
        NodeSchedulingMetadata,
        Resources,
    )

    return {
        "a0": NodeSchedulingMetadata(
            available=Resources.of("64", str(mem_avail_a)),
            schedulable=Resources.of("64", sched_mem),
            zone_label="z0",
        ),
        "a1": NodeSchedulingMetadata(
            available=Resources.of("64", str(mem_avail_b)),
            schedulable=Resources.of("64", sched_mem),
            zone_label="z1",
        ),
    }


def _byte_app(k=1, mem="100000"):
    from k8s_spark_scheduler_tpu.types.resources import Resources

    return AppDemand(
        driver_resources=Resources.of("1", mem),
        executor_resources=Resources.of("1", mem),
        min_executor_count=k,
    )


def test_single_az_fused_symmetric_tie_keeps_first_zone():
    """Mathematically equal zone scores (identical zones) keep the
    earlier zone, exactly like the float64 oracle's strict-improvement
    rule (single_az.go:88-94) — decided by that rule itself: equal
    fixed-point scores certify nothing, so the app is resolved on the
    host and the device pass goes on from it."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    metadata = _two_zone_cluster(600000, 600000)
    order = ["a0", "a1"]
    earlier = [_byte_app()]
    current = _byte_app()
    solver = TpuSingleAzFifoSolver(az_aware=False, backend="xla")
    outcome = solver.solve(metadata, order, order, earlier, [False], current)
    assert solver.last_path == "fused"
    assert solver.last_zone_choices == {"certified": 0, "resolved": 1, "unmemoised": 0}
    expected_ok, expected = host_single_az_fifo_oracle(
        metadata, order, order, earlier, [False], current, az_aware=False
    )
    assert outcome.supported and outcome.earlier_ok == expected_ok
    assert outcome.result.driver_node == expected.driver_node
    assert outcome.result.executor_nodes == expected.executor_nodes


@pytest.mark.parametrize("inner_policy", ["tightly-pack", "minimal-fragmentation"])
@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_single_az_gate_counts_the_resolved_apps_no_memo_could_answer(lane, inner_policy):
    """The tie of identical zones is decided on the host in every request.
    Under tightly-pack the second request finds the first one's decision
    with the same evidence; the min-frag choice reads every node's
    capacity and keeps no evidence, so each such app is ``unmemoised``:
    ``fifo_gate``'s ``zoneUnmemoised`` tag (a part of ``zoneResolved``),
    and in ``last_zone_choices`` (the registry's labels, a partition of
    the queue) under ``unmemoised`` instead of ``resolved``."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver
    from k8s_spark_scheduler_tpu.tracing import Tracer

    metadata = _two_zone_cluster(600000, 600000)
    order = ["a0", "a1"]
    solver = TpuSingleAzFifoSolver(backend=lane, interpret=True, inner_policy=inner_policy)
    tracer = Tracer(capacity=4)
    tags, choices = [], []
    for _ in range(2):
        with tracer.span("predicate") as root:
            solver.solve(metadata, order, order, [_byte_app()], [False], _byte_app())
        (gate,) = [c for c in root.children if c.name == "fifo_gate"]
        tags.append((gate.tags["zoneResolved"], gate.tags["zoneUnmemoised"]))
        choices.append(dict(solver.last_zone_choices))
    unmemoised = 1 if inner_policy == "minimal-fragmentation" else 0
    assert tags == [(1, unmemoised)] * 2
    assert choices == [
        {"certified": 0, "resolved": 1 - unmemoised, "unmemoised": unmemoised}
    ] * 2


def test_single_az_fused_near_tie_is_resolved_for_that_app_alone():
    """Zone scores that are distinct but inside the fixed-point margin
    must flag `uncertain`: that app is decided exactly on the host, the
    device pass stays the lane that served, and the answer still matches
    the oracle decision-for-decision."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    # efficiencies 0.6 vs 0.599995 — a 5e-6 gap, ~1.3 fixed-point ulps at
    # EFF_SHIFT=18, far inside the 2(k+1)+2 certification band
    metadata = _two_zone_cluster(600000, 600005)
    order = ["a0", "a1"]
    earlier = [_byte_app()]
    current = _byte_app()
    solver = TpuSingleAzFifoSolver(az_aware=False, backend="xla")
    outcome = solver.solve(metadata, order, order, earlier, [False], current)
    assert solver.last_path == "fused" and solver.last_queue_lane == "xla"
    assert solver.last_zone_choices == {"certified": 0, "resolved": 1, "unmemoised": 0}
    expected_ok, expected = host_single_az_fifo_oracle(
        metadata, order, order, earlier, [False], current, az_aware=False
    )
    assert outcome.supported and outcome.earlier_ok == expected_ok
    assert outcome.result.driver_node == expected.driver_node
    assert outcome.result.executor_nodes == expected.executor_nodes


@pytest.mark.parametrize(
    "az_aware,inner_policy",
    [
        (False, "tightly-pack"),
        (True, "tightly-pack"),
        (False, "minimal-fragmentation"),
    ],
)
def test_single_az_pallas_solver_wiring(az_aware, inner_policy):
    """The solver's pallas branch (zone_vec build, [1]-shaped scale
    arrays, FusedQueueOut adaptation, min-frag inner routing) must
    produce the same outcomes as the XLA branch — run in interpreter
    mode so the wiring is covered on CPU, not just on TPU hardware."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    rng = random.Random(5151 + az_aware)
    compared = 0
    for trial in range(4):
        metadata = random_cluster(rng, rng.randint(3, 12))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(1, 5))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)
        args = (metadata, driver_order, executor_order, earlier, skip_allowed, current)

        xla = TpuSingleAzFifoSolver(
            az_aware=az_aware, backend="xla", inner_policy=inner_policy
        )
        ref = xla.solve(*args)
        if xla.last_path != "fused":
            continue
        pal = TpuSingleAzFifoSolver(
            az_aware=az_aware, backend="pallas", interpret=True,
            inner_policy=inner_policy,
        )
        got = pal.solve(*args)
        assert pal.last_path == "fused", f"trial {trial}"
        compared += 1
        assert got.earlier_ok == ref.earlier_ok, f"trial {trial}"
        if ref.earlier_ok:
            assert got.result.has_capacity == ref.result.has_capacity, f"trial {trial}"
            if ref.result.has_capacity:
                assert got.result.driver_node == ref.result.driver_node, f"trial {trial}"
                assert got.result.executor_nodes == ref.result.executor_nodes, f"trial {trial}"
    assert compared >= 2, f"only {compared}/4 trials exercised the pallas branch"


@pytest.mark.parametrize("az_aware", [False, True])
def test_single_az_fused_matches_forced_host_lane(az_aware, monkeypatch):
    """Differential: the fused one-dispatch lane and the per-driver host
    lane must agree on every decision for queues where the fused lane is
    certain (randomized, deeper queues than the oracle parity test)."""
    from k8s_spark_scheduler_tpu.ops import fifo_solver as fs

    rng = random.Random(424242 + az_aware)
    for trial in range(8):
        metadata = random_cluster(rng, rng.randint(4, 16))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(1, 10))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)

        solver = fs.TpuSingleAzFifoSolver(az_aware=az_aware, backend="xla")
        fused = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        if solver.last_path != "fused":
            continue
        with monkeypatch.context() as m:
            m.setattr(fs, "_fused_efficiency_inputs", lambda *a, **k: None)
            host_solver = fs.TpuSingleAzFifoSolver(az_aware=az_aware, backend="xla")
            host = host_solver.solve(
                metadata, driver_order, executor_order, earlier, skip_allowed, current
            )
            assert host_solver.last_path == "host"
        assert fused.earlier_ok == host.earlier_ok, f"trial {trial}"
        if fused.earlier_ok:
            assert fused.result.has_capacity == host.result.has_capacity, f"trial {trial}"
            if fused.result.has_capacity:
                assert fused.result.driver_node == host.result.driver_node, f"trial {trial}"
                assert fused.result.executor_nodes == host.result.executor_nodes, f"trial {trial}"


def test_min_frag_counts_kernel_differential():
    """The device min-frag kernel (sort + prefix-sum linearization of the
    drain loop) must reproduce minimal_fragmentation_from_capacities
    count-for-count, including capacity ties, unbounded sentinels, the
    (k+max)/2 subset attempt, k=0, and infeasible totals."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_spark_scheduler_tpu.ops.batch_solver import MF_SENT, min_frag_counts
    from k8s_spark_scheduler_tpu.ops.capacity import (
        MAX_CAPACITY,
        NodeAndExecutorCapacity,
    )
    from k8s_spark_scheduler_tpu.ops.packers import (
        minimal_fragmentation_from_capacities,
    )

    rng = random.Random(4242)
    mf_jit = jax.jit(min_frag_counts)
    for trial in range(400):
        n = rng.randint(1, 24)
        caps = []
        for _ in range(n):
            r = rng.random()
            if r < 0.1:
                caps.append(0)
            elif r < 0.2:
                caps.append(MF_SENT)  # unbounded (all-dims-zero requirement)
            elif r < 0.5:
                caps.append(rng.choice([1, 2, 3, 4, 5, 5, 8, 8]))  # dense ties
            else:
                caps.append(rng.randint(1, 60))
        k = rng.choice([0, 1, rng.randint(1, 30), rng.randint(1, 200)])

        host_caps = [
            NodeAndExecutorCapacity(f"n{i}", MAX_CAPACITY if c == MF_SENT else c)
            for i, c in enumerate(caps)
            if c > 0
        ]
        expected, ok = ([], True) if k == 0 else minimal_fragmentation_from_capacities(
            k, host_caps
        )
        dev = np.asarray(mf_jit(jnp.asarray(np.array(caps, np.int32)), jnp.int32(k)))
        exp_counts = np.zeros(n, np.int64)
        if ok and expected:
            for name in expected:
                exp_counts[int(name[1:])] += 1
        if ok:
            assert np.array_equal(dev[:n], exp_counts), (
                f"trial {trial}: k={k} caps={caps} host={exp_counts.tolist()} "
                f"dev={dev[:n].tolist()}"
            )
        else:
            assert not dev[:n].any(), f"trial {trial}: nonzero counts on infeasible"


def test_min_frag_fifo_solver_parity_random():
    """Whole-queue min-frag scan vs the extender host loop on the min-frag
    oracle (fused FIFO pass = one dispatch, VERDICT round-1 known gap)."""
    rng = random.Random(52525)
    solver = TpuFifoSolver(assignment_policy="minimal-fragmentation", backend="xla")
    for trial in range(25):
        metadata = random_cluster(rng, rng.randint(2, 20))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(0, 8))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)

        expected_ok, expected_result = host_fifo_oracle(
            metadata, driver_order, executor_order, earlier, skip_allowed, current,
            packer=packers.minimal_fragmentation_pack,
        )
        outcome = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        assert outcome.supported
        assert outcome.earlier_ok == expected_ok, f"trial {trial}: earlier_ok"
        if expected_ok:
            assert outcome.result.has_capacity == expected_result.has_capacity, (
                f"trial {trial}: current feasibility"
            )
            if expected_result.has_capacity:
                assert outcome.result.driver_node == expected_result.driver_node, (
                    f"trial {trial}: driver node"
                )
                assert (
                    outcome.result.executor_nodes == expected_result.executor_nodes
                ), f"trial {trial}: placement"


def test_extender_tpu_batch_min_frag_matches_host():
    """tpu-batch-minimal-fragmentation through the full extender (FIFO on)
    must decide identically to the host minimal-fragmentation policy."""
    results = {}
    for algo in ("minimal-fragmentation", "tpu-batch-minimal-fragmentation"):
        h = Harness(binpack_algo=algo, is_fifo=True)
        try:
            h.new_node("n1", cpu="6", memory="6Gi")
            h.new_node("n2", cpu="10", memory="10Gi")
            h.new_node("n3", cpu="4", memory="4Gi")
            nodes = ["n1", "n2", "n3"]
            log = []
            for app, execs in [("a", 3), ("b", 7), ("c", 2), ("d", 9)]:
                pods = h.static_allocation_spark_pods(f"app-{app}", execs)
                r = h.schedule(pods[0], nodes)
                log.append((f"driver-{app}", tuple(r.node_names or [])))
                if r.node_names:
                    for p in pods[1:]:
                        er = h.schedule(p, nodes)
                        log.append((p.name, tuple(er.node_names or [])))
            results[algo] = log
        finally:
            h.close()
    assert results["minimal-fragmentation"] == results["tpu-batch-minimal-fragmentation"]


def test_fifo_efficiency_metrics_match_host_lane():
    """The efficiency gauge must reflect POST-queue availability like the
    host lane, whose fitEarlierDrivers mutates the metadata the final
    pack's efficiencies are computed against (resource.go:255-259).  The
    device lane carries availability on device, so its result's
    efficiencies must be bit-equal to the host's mutated-metadata ones."""
    from k8s_spark_scheduler_tpu.ops.efficiency import (
        compute_avg_packing_efficiency,
    )

    rng = random.Random(7171)
    solver = TpuFifoSolver()
    checked = 0
    for trial in range(12):
        metadata = random_cluster(rng, rng.randint(3, 15))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(1, 6))]
        skip_allowed = [True] * len(earlier)  # queue never hard-fails
        current = random_app(rng)

        expected_ok, expected = host_fifo_oracle(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        outcome = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        assert outcome.supported and outcome.earlier_ok == expected_ok
        if not (expected_ok and expected.has_capacity):
            continue
        result = outcome.result
        # the extender's gauge inputs: avg over the result's efficiency map
        exp_avg = compute_avg_packing_efficiency(
            metadata, list(expected.packing_efficiencies.values())
        )
        act_avg = compute_avg_packing_efficiency(
            metadata, list(result.packing_efficiencies.values())
        )
        assert (exp_avg.cpu, exp_avg.memory, exp_avg.gpu, exp_avg.max) == (
            act_avg.cpu, act_avg.memory, act_avg.gpu, act_avg.max
        ), f"trial {trial}: gauge averages diverge"
        # spot-check per-node values on the placement nodes
        for node in {expected.driver_node, *expected.executor_nodes}:
            e, a = expected.packing_efficiencies[node], result.packing_efficiencies[node]
            assert (e.cpu, e.memory, e.gpu) == (a.cpu, a.memory, a.gpu), (
                f"trial {trial}: node {node}"
            )
        checked += 1
    assert checked >= 5  # the scenario generator must exercise the path


@pytest.mark.parametrize(
    "host_algo,device_algo",
    [
        ("tightly-pack", "tpu-batch"),
        ("distribute-evenly", "tpu-batch-distribute-evenly"),
        ("minimal-fragmentation", "tpu-batch-minimal-fragmentation"),
    ],
)
def test_extender_efficiency_gauge_matches_host_lane(host_algo, device_algo):
    """The packing.efficiency.max gauge must be bit-equal whichever lane
    serves the request — through the FULL extender (the tensor-snapshot
    fast lane, metadata containing a non-candidate unschedulable node,
    and a non-empty FIFO queue)."""
    import time as _t

    def run(algo):
        h = Harness(binpack_algo=algo, is_fifo=True)
        try:
            h.new_node("n1", cpu="8", memory="8Gi", gpu="0")
            h.new_node("n2", cpu="12", memory="12Gi", gpu="0")
            # in metadata (affinity-matching) but never a candidate:
            # the gauge averages over it on the host lane
            h.new_node("n3", cpu="6", memory="6Gi", gpu="0", unschedulable=True)
            t0 = _t.time()
            elder = h.static_allocation_spark_pods(
                "app-elder", 4, creation_timestamp=t0 - 50
            )
            newer = h.static_allocation_spark_pods("app-next", 2, creation_timestamp=t0)
            for p in elder + newer:
                h.create_pod(p)
            r = h.schedule(newer[0], ["n1", "n2", "n3"])
            assert r.node_names, (algo, r.failed_nodes, r.error)
            gauges = {
                k: v
                for k, v in h.extender._metrics.snapshot()["gauges"].items()
                if "packing.efficiency.max" in k
            }
            assert len(gauges) == 1
            return r.node_names[0], next(iter(gauges.values()))
        finally:
            h.close()

    host_node, host_gauge = run(host_algo)
    dev_node, dev_gauge = run(device_algo)
    assert host_node == dev_node
    assert host_gauge == dev_gauge, (
        f"{device_algo} gauge {dev_gauge!r} != {host_algo} gauge {host_gauge!r}"
    )


@pytest.mark.parametrize("strict", [True, False])
def test_single_az_min_frag_single_app_parity(strict):
    """TpuSingleAzBinpacker(inner minimal-fragmentation) vs the host
    single_az_minimal_fragmentation oracle, both parity modes (the
    strict mode's driver-only efficiencies steer the zone choice)."""
    from k8s_spark_scheduler_tpu.ops.batch_adapter import TpuSingleAzBinpacker

    rng = random.Random(60606)
    oracle = packers.make_single_az_minimal_fragmentation(strict)
    solver = TpuSingleAzBinpacker(
        az_aware=False,
        inner_policy="minimal-fragmentation",
        strict_reference_parity=strict,
    )
    checked = 0
    for trial in range(30):
        metadata = random_cluster(rng, rng.randint(2, 18))
        app = random_app(rng)
        driver_order, executor_order = orders_for(metadata, rng)
        expected = oracle(
            app.driver_resources, app.executor_resources, app.min_executor_count,
            driver_order, executor_order, copy_metadata(metadata),
        )
        actual = solver(
            app.driver_resources, app.executor_resources, app.min_executor_count,
            driver_order, executor_order, copy_metadata(metadata),
        )
        assert actual.has_capacity == expected.has_capacity, f"trial {trial}"
        if expected.has_capacity:
            checked += 1
            assert actual.driver_node == expected.driver_node, f"trial {trial}"
            assert actual.executor_nodes == expected.executor_nodes, f"trial {trial}"
    assert checked >= 8


@pytest.mark.parametrize("strict", [True, False])
def test_single_az_min_frag_fifo_solver_parity(strict):
    """TpuSingleAzFifoSolver(inner minimal-fragmentation) whole-queue
    decisions vs the extender host loop on the oracle."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    rng = random.Random(99)  # seed that exposed the ungated fused lane
    oracle = packers.make_single_az_minimal_fragmentation(strict)
    solver = TpuSingleAzFifoSolver(
        az_aware=False,
        backend="xla",
        inner_policy="minimal-fragmentation",
        strict_reference_parity=strict,
    )
    fused_served = 0
    for trial in range(40):
        metadata = random_cluster(rng, rng.randint(2, 16))
        driver_order, executor_order = orders_for(metadata, rng)
        # queues always non-empty: the regression this pins (the fused
        # tightly kernel serving the min-frag queue) only showed with
        # earlier drivers present
        earlier = [random_app(rng) for _ in range(rng.randint(1, 6))]
        skip_allowed = [rng.random() < 0.3 for _ in earlier]
        current = random_app(rng)

        expected_ok, expected = host_fifo_oracle(
            metadata, driver_order, executor_order, earlier, skip_allowed, current,
            packer=oracle,
        )
        outcome = solver.solve(
            metadata, driver_order, executor_order, earlier, skip_allowed, current
        )
        assert outcome.supported
        assert outcome.earlier_ok == expected_ok, f"trial {trial}"
        fused_served += solver.last_path == "fused"
        if expected_ok:
            assert outcome.result.has_capacity == expected.has_capacity, f"trial {trial}"
            if expected.has_capacity:
                assert outcome.result.driver_node == expected.driver_node, f"trial {trial}"
                assert (
                    outcome.result.executor_nodes == expected.executor_nodes
                ), f"trial {trial}"
    # the one-dispatch lane must actually serve these queues — decisions
    # matching via a silent host-lane fallback would not pin the kernel
    assert fused_served >= 30, fused_served


@pytest.mark.parametrize("strict", [True, False])
def test_single_az_min_frag_snapshot_choice_equals_the_zone_decode(strict, monkeypatch):
    """The valve and the current driver's zone choice read a snapshot's
    min-frag placements as they stand, every zone in one pass, each
    zone's hosts ordered from their own capacities; the valve decides
    every slot of a launch at once from their compacted view.  Every
    such choice equals the one that decodes every zone's drain again
    from the carry: zone, driver, counts and placement list; and the
    compacted view answers as the snapshots whole do."""
    from k8s_spark_scheduler_tpu.ops import fifo_solver as fs

    checked = []
    real_pick = fs._ZoneProblem.pick_from_snapshot
    real_decisions = fs.TpuSingleAzFifoSolver._min_frag_decisions

    def decoded(self, snapshot, app_idx):
        avail, packings = self.snapshot_packings(snapshot)
        return self._choose(avail, app_idx, packings)

    def pick(self, snapshot, app_idx):
        got, avail = real_pick(self, snapshot, app_idx)
        want = decoded(self, snapshot, app_idx)
        assert (got is None) == (want is None)
        if got is not None:
            assert (got.zone, got.driver_idx) == (want.zone, want.driver_idx)
            assert (got.counts == want.counts).all()
            assert got.executor_nodes == want.executor_nodes
        checked.append(app_idx)
        return got, avail

    def decisions(zones, view, snapshots_dev, flagged, slots, n_earlier):
        got = real_decisions(zones, view, snapshots_dev, flagged, slots, n_earlier)
        whole = np.asarray(snapshots_dev)
        apps = np.full(len(view), -1)
        for u, slot in zip(flagged.tolist(), slots.tolist()):
            if slot >= 0 and u < n_earlier:
                assert got[slot] == zones.candidate(decoded(zones, whole[slot], u))
                apps[slot] = u
                checked.append(u)
        assert (zones.placed_min_frag_zones(apps, snapshots=whole) == got).all()
        return got

    monkeypatch.setattr(fs._ZoneProblem, "pick_from_snapshot", pick)
    monkeypatch.setattr(fs.TpuSingleAzFifoSolver, "_min_frag_decisions", staticmethod(decisions))
    rng = random.Random(3303 + strict)
    solver = fs.TpuSingleAzFifoSolver(
        backend="xla", inner_policy="minimal-fragmentation", strict_reference_parity=strict
    )
    for _ in range(60):
        metadata = random_cluster(rng, rng.randint(2, 16))
        driver_order, executor_order = orders_for(metadata, rng)
        earlier = [random_app(rng) for _ in range(rng.randint(1, 6))]
        solver.solve(
            metadata, driver_order, executor_order, earlier,
            [False] * len(earlier), random_app(rng),
        )
    assert len(checked) >= 20, len(checked)


def test_extender_tpu_batch_single_az_min_frag_matches_host():
    """The new policy name through the full extender (FIFO + single-AZ
    DA) must decide identically to the host policy."""
    from k8s_spark_scheduler_tpu.config import Install

    results = {}
    for algo in (
        "single-az-minimal-fragmentation",
        "tpu-batch-single-az-minimal-fragmentation",
    ):
        h = Harness(
            extra_install=Install(
                fifo=True,
                binpack_algo=algo,
                should_schedule_dynamically_allocated_executors_in_same_az=True,
            )
        )
        try:
            h.new_node("a1", cpu="6", memory="6Gi", gpu="0", zone="az-1")
            h.new_node("a2", cpu="10", memory="10Gi", gpu="0", zone="az-1")
            h.new_node("b1", cpu="8", memory="8Gi", gpu="0", zone="az-2")
            nodes = ["a1", "a2", "b1"]
            log = []
            for app, execs in [("a", 3), ("b", 5), ("c", 2)]:
                pods = h.static_allocation_spark_pods(f"app-{app}", execs)
                r = h.schedule(pods[0], nodes)
                log.append((f"driver-{app}", tuple(r.node_names or [])))
                if r.node_names:
                    for p in pods[1:]:
                        er = h.schedule(p, nodes)
                        log.append((p.name, tuple(er.node_names or [])))
            da = h.dynamic_allocation_spark_pods("app-da", 1, 3)
            for p in da:
                r = h.schedule(p, nodes)
                log.append((p.name, tuple(r.node_names or [])))
            results[algo] = log
        finally:
            h.close()
    assert (
        results["single-az-minimal-fragmentation"]
        == results["tpu-batch-single-az-minimal-fragmentation"]
    )


def test_feasible_tensor_matches_binpack_has_capacity():
    """The marker's feasibility-only entry point must agree with
    binpack_func's has_capacity on random snapshots (it is the same
    work-conserving feasibility rule with the decode skipped)."""
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    rng = random.Random(20260730)
    for policy in ("tpu-batch", "tpu-batch-distribute-evenly",
                   "tpu-batch-minimal-fragmentation"):
        binpacker = select_binpacker(policy)
        solver = binpacker.queue_solver
        for _ in range(8):
            metadata = random_cluster(rng, rng.randint(2, 12))
            d_order, e_order = orders_for(metadata, rng)
            app = random_app(rng)
            cluster = tensorize_cluster(metadata, d_order, e_order)
            feasible = solver.feasible_tensor(cluster, app)
            result = binpacker.binpack_func(
                app.driver_resources,
                app.executor_resources,
                app.min_executor_count,
                d_order,
                e_order,
                metadata,
            )
            assert feasible is not None
            assert feasible == result.has_capacity, policy


# -- the marker's verdicts as one batch ----------------------------------------

BATCH_POLICIES = ["tpu-batch", "tpu-batch-distribute-evenly", "tpu-batch-minimal-fragmentation"]
BATCH_LANES = ["xla", "native"]
BATCH_SIZES = [1, 17, 300]


def batch_solver_on(policy, lane):
    """(the policy's binpacker, its queue solver held to ``lane``)."""
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker

    binpacker = select_binpacker(policy)
    binpacker.queue_solver.backend = lane
    return binpacker, binpacker.queue_solver


def has_capacity(binpacker, app, d_order, e_order, metadata):
    return binpacker.binpack_func(
        app.driver_resources, app.executor_resources, app.min_executor_count,
        d_order, e_order, metadata,
    ).has_capacity


def check_feasible_batch(policy, lane, n_apps):
    """A batch of ``n_apps`` random apps on a seeded random cluster:
    every verdict is the binpacker's ``has_capacity``, and the per-app
    entry's (all of them on the native lane, a stride of them on the
    XLA lane, where each is a program of its own)."""
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    binpacker, solver = batch_solver_on(policy, lane)
    rng = random.Random(f"{policy}/{n_apps}")  # both lanes see one problem
    metadata = random_cluster(rng, rng.randint(2, 8))
    d_order, e_order = orders_for(metadata, rng)
    cluster = tensorize_cluster(metadata, d_order, e_order)
    apps = [random_app(rng) for _ in range(n_apps)]
    verdicts = solver.feasible_batch(cluster, apps)
    assert len(verdicts) == n_apps and None not in verdicts
    want = [has_capacity(binpacker, app, d_order, e_order, metadata) for app in apps]
    assert verdicts == want
    stride = 1 if lane == "native" else max(1, n_apps // 8)
    assert [solver.feasible_tensor(cluster, app) for app in apps[::stride]] == want[::stride]
    return want


@pytest.mark.parametrize("n_apps", BATCH_SIZES)
@pytest.mark.parametrize("lane", BATCH_LANES)
@pytest.mark.parametrize("policy", BATCH_POLICIES)
def test_feasible_batch_is_has_capacity_app_by_app(policy, lane, n_apps):
    want = check_feasible_batch(policy, lane, n_apps)
    assert n_apps < 17 or {True, False} <= set(want)


def pinned_cluster():
    """One node only a 3-cpu driver fits, two that take an executor each,
    one overbooked."""
    sizes = {"big": ("4", "8Gi"), "small-a": ("1", "8Gi"), "small-b": ("1", "8Gi"), "over": ("-2", "8Gi")}
    return {
        name: NodeSchedulingMetadata(
            available=Resources.of(cpu, mem), schedulable=Resources.of("8", "8Gi"), zone_label="z0"
        )
        for name, (cpu, mem) in sizes.items()
    }


@pytest.mark.parametrize("lane", BATCH_LANES)
@pytest.mark.parametrize("policy", BATCH_POLICIES)
def test_feasible_batch_pinned_cases(policy, lane):
    """Infeasible gangs, a zero requirement, a gang that fits only with
    its driver on one particular node, and padding: three to seven rows
    of the program's 1,024 are apps, the rest never show."""
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    binpacker, solver = batch_solver_on(policy, lane)
    metadata = pinned_cluster()
    order = list(metadata)
    one, big_driver = Resources.of("1", "1Gi"), Resources.of("3", "1Gi")
    apps = [
        AppDemand(big_driver, one, 3),                     # driver on "big" only, one executor beside it, two elsewhere
        AppDemand(big_driver, one, 4),                     # one executor too many
        AppDemand(one, Resources.of("0", "1Gi"), 23),      # no cpu asked: memory alone, 8 a node on three nodes less the driver
        AppDemand(one, Resources.of("0", "1Gi"), 24),      # "over" is negative in cpu: 0 there whatever is asked
        AppDemand(Resources.of("5", "1Gi"), one, 0),       # the driver fits nowhere
        AppDemand(one, Resources.of("0", "0"), 10_000),    # nothing asked: any number fits
        AppDemand(one, one, 0),                            # a driver alone
    ]
    want = [True, False, True, False, False, True, True]
    for order_d, expected in ((order, want), (order[1:], [False, False, *want[2:]])):
        cluster = tensorize_cluster(metadata, order_d, order)
        assert solver.feasible_batch(cluster, apps) == expected
        assert [has_capacity(binpacker, a, order_d, order, metadata) for a in apps] == expected
        assert solver.feasible_batch(cluster, apps[:3]) == expected[:3]
    assert solver.feasible_batch(cluster, []) == []


@pytest.mark.parametrize("policy", ["tpu-batch", "tpu-batch-single-az"])
def test_feasible_batch_larger_than_one_app_block(policy):
    """1,029 apps go through the one program in two blocks: three arrays
    up, two down, the verdicts those of the native lane."""
    from k8s_spark_scheduler_tpu import tracing
    from k8s_spark_scheduler_tpu.ops.batch_solver import VERDICT_ROWS, feasible_apps
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    rng = random.Random(1029)
    metadata = random_cluster(rng, 30)
    d_order, e_order = orders_for(metadata, rng)
    cluster = tensorize_cluster(metadata, d_order, e_order)
    apps = [random_app(rng) for _ in range(VERDICT_ROWS + 5)]
    _, device = batch_solver_on(policy, "xla")
    binpacker, native = batch_solver_on(policy, "native")
    compiled = feasible_apps._cache_size()
    with tracing.Tracer().span("unschedulable.scan") as root:
        with root.aggregate("scan.solve") as phase:
            verdicts = device.feasible_batch(cluster, apps, span=phase)
        with root.aggregate("scan.solve") as phase:
            assert device.feasible_batch(cluster, apps[:7], span=phase) == verdicts[:7]
    assert verdicts == native.feasible_batch(cluster, apps)
    assert verdicts[-5:] == [has_capacity(binpacker, a, d_order, e_order, metadata) for a in apps[-5:]]
    nb = 64
    runtime = ("cpuMs", "gcMs", "gcRuns", "bg")  # what a span may say of the runtime at its exit
    assert {k: v for k, v in phase.tags.items() if k not in runtime} == {
        "count": 2,
        "arrays": (1 + 2 * 2) + (1 + 2),
        "bytes": (nb * 6 * 4 + 2 * VERDICT_ROWS * 9 * 4) + (nb * 6 * 4 + VERDICT_ROWS * 9 * 4),
    }
    assert not phase.children
    # both batches ran the one program of the node bucket
    assert feasible_apps._cache_size() <= compiled + 1


@pytest.mark.parametrize("lane", BATCH_LANES)
@pytest.mark.parametrize("policy", ["tpu-batch", "tpu-batch-single-az"])
def test_feasible_batch_with_one_inexact_app(policy, lane):
    """A sub-milli cpu ask has no exact base units: that app's verdict is
    the host path's (None), the others still get theirs; and where the
    cluster is inexact every verdict is None."""
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    binpacker, solver = batch_solver_on(policy, lane)
    rng = random.Random(50)
    metadata = random_cluster(rng, 12)
    d_order, e_order = orders_for(metadata, rng)
    cluster = tensorize_cluster(metadata, d_order, e_order)
    apps = [random_app(rng) for _ in range(9)]
    apps[4] = AppDemand(Resources.of("50u", "1Mi"), Resources.of("10u", "1Mi"), 2)
    verdicts = solver.feasible_batch(cluster, apps)
    assert verdicts[4] is None and solver.feasible_tensor(cluster, apps[4]) is None
    assert [v for i, v in enumerate(verdicts) if i != 4] == [
        has_capacity(binpacker, a, d_order, e_order, metadata) for i, a in enumerate(apps) if i != 4
    ]
    metadata["node-000"].available = Resources.of("100u", "1Gi")
    inexact = tensorize_cluster(metadata, d_order, e_order)
    assert solver.feasible_batch(inexact, apps[:3]) == [None] * 3


@pytest.mark.parametrize("lane", BATCH_LANES)
def test_feasible_batch_scales_apps_singly_where_the_joint_problem_does_not(lane):
    """Nodes of 2^20 * 3^13 bytes scale with an ask of 2^20 bytes and
    with one of 3^13, not with both (their joint GCD is a byte, and the
    nodes pass int32): each app is then scaled alone, and each still
    gets its tensor verdict."""
    from k8s_spark_scheduler_tpu.ops.tensorize import (
        scale_problem, tensorize_apps, tensorize_cluster,
    )

    binpacker, solver = batch_solver_on("tpu-batch", lane)
    memory = str(2**20 * 3**13)
    metadata = {
        f"n{i}": NodeSchedulingMetadata(
            available=Resources.of("64", memory), schedulable=Resources.of("64", memory), zone_label="z0"
        )
        for i in range(3)
    }
    order = list(metadata)
    cluster = tensorize_cluster(metadata, order, order)
    driver = Resources.of("1", "0")
    apps = [
        AppDemand(driver, Resources.of("1", str(2**20)), 191),  # 64 + 64 + 63 by cpu
        AppDemand(driver, Resources.of("1", str(3**13)), 192),
        AppDemand(driver, Resources.of("1", str(3**13)), 191),
    ]
    assert not scale_problem(cluster, tensorize_apps(apps)).ok
    assert all(scale_problem(cluster, tensorize_apps([a])).ok for a in apps)
    verdicts = solver.feasible_batch(cluster, apps)
    assert verdicts == [has_capacity(binpacker, a, order, order, metadata) for a in apps]
    assert verdicts == [True, False, True]


def test_earlier_tensor_cache_hit_matches_fresh_solver():
    """Repeated solve_tensor calls with the SAME earlier-apps list (the
    steady-state Filter pattern the identity cache serves) must decide
    identically to a fresh solver, including after availability-
    irrelevant re-solves."""
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster

    rng = random.Random(7)
    metadata = random_cluster(rng, 10)
    d_order, e_order = orders_for(metadata, rng)
    cluster = tensorize_cluster(metadata, d_order, e_order)
    earlier = [random_app(rng) for _ in range(5)]
    skip = [False] * len(earlier)
    current = random_app(rng)

    warm = select_binpacker("tpu-batch").queue_solver
    outs = [
        warm.solve_tensor(cluster, earlier, skip, current) for _ in range(3)
    ]
    fresh = TpuFifoSolver(assignment_policy="tightly-pack").solve_tensor(
        cluster, earlier, skip, current
    )
    for out in outs:
        assert out.supported == fresh.supported
        assert out.earlier_ok == fresh.earlier_ok
        if fresh.result is not None:
            assert out.result.has_capacity == fresh.result.has_capacity
            assert out.result.driver_node == fresh.result.driver_node
            assert out.result.executor_nodes == fresh.result.executor_nodes
