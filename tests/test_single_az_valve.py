"""The single-AZ queue pass's valve, below the solver: the pallas kernel
(interpret mode) and its XLA twin flag the same apps, leave the same
snapshots and resume the same way; a pass resumed from a handed-back
state with forced zones equals one uninterrupted pass with the same
choices; and the marker's feasibility verdict equals the binpacker's."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops.batch_solver import (
    DRIVER_BIT,
    FORCE_NONE,
    compact_snapshots,
    solve_queue_single_az,
)
from k8s_spark_scheduler_tpu.ops.fifo_solver import (
    TpuSingleAzFifoSolver,
    _fused_efficiency_inputs,
    _ZoneProblem,
)
from k8s_spark_scheduler_tpu.ops.pallas_queue import (
    pallas_solve_queue_single_az,
    pallas_solve_queue_single_az_packed,
)
from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
from k8s_spark_scheduler_tpu.ops.tensorize import scale_problem, tensorize_apps, tensorize_cluster
from k8s_spark_scheduler_tpu.types.resources import NodeSchedulingMetadata, Resources


def tying_problem(seed, nodes=90, apps=20, zones=3, az_aware=False, inner="tightly-pack"):
    """A cluster whose zones repeat the same few node sizes, as the
    benchmark's stratified multisets do, so that zone scores tie or
    nearly tie for several apps of the queue."""
    rng = random.Random(seed)
    sizes = [("4", "8Gi")] * 3 + [("8", "16Gi")] * 3 + [("12", "48Gi")] * 3
    metadata = {}
    for i in range(nodes):
        cpu, mem = sizes[i % len(sizes)]
        metadata[f"n{i:03d}"] = NodeSchedulingMetadata(
            available=Resources.of(cpu, mem),
            schedulable=Resources.of(cpu, mem),
            zone_label=f"z{i % zones}",
        )
    order = list(metadata)
    queue = [
        AppDemand(
            Resources.of("1", "1Gi"),
            Resources.of(str(rng.randint(1, 3)), f"{rng.randint(1, 6)}Gi"),
            rng.randint(1, 5),
        )
        for _ in range(apps)
    ]
    cluster = tensorize_cluster(metadata, order, order)
    problem = scale_problem(cluster, tensorize_apps(queue))
    assert problem.ok
    zones_of = _ZoneProblem(cluster, problem, az_aware, inner, True)
    score = _fused_efficiency_inputs(cluster, problem)
    assert score is not None
    return problem, zones_of, score


def twin(problem, zones_of, score, valid, avail=None, forced=None, start=0, n_slots=0, az_aware=False):
    s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = score
    masks = zones_of.zone_vec[None, :] == np.arange(zones_of.n_zones)[:, None]
    return solve_queue_single_az(
        jnp.asarray(problem.avail if avail is None else avail), jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok), jnp.asarray(masks), jnp.asarray(problem.driver),
        jnp.asarray(problem.executor), jnp.asarray(problem.count), jnp.asarray(valid),
        jnp.asarray(s_cpu), jnp.asarray(s_gpu), jnp.asarray(inv_m), jnp.asarray(th_m),
        jnp.int32(scale_c), jnp.int32(scale_g),
        None if forced is None else jnp.asarray(forced), jnp.int32(start),
        az_aware=az_aware, n_slots=n_slots,
    )


def kernel(problem, zones_of, score, valid, avail=None, forced=None, start=0, az_aware=False):
    """The kernel without slots (it halts at the first flagged app), in
    interpret mode: (placed, zone, driver node, flagged, avail_after)."""
    s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = score
    a = problem.count.shape[0]
    out = pallas_solve_queue_single_az(
        jnp.asarray(problem.avail if avail is None else avail), jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok), jnp.asarray(zones_of.zone_vec), jnp.asarray(problem.driver),
        jnp.asarray(problem.executor), jnp.asarray(problem.count), jnp.asarray(valid),
        jnp.asarray(s_cpu), jnp.asarray(s_gpu), jnp.asarray(inv_m), jnp.asarray(th_m),
        jnp.asarray(np.array([scale_c], np.int32)), jnp.asarray(np.array([scale_g], np.int32)),
        jnp.asarray(np.full(a, FORCE_NONE, np.int32) if forced is None else forced),
        jnp.asarray(np.array([start], np.int32)),
        n_zones=zones_of.n_zones, az_aware=az_aware, interpret=True,
    )
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("az_aware", [False, True])
@pytest.mark.parametrize("seed", [3, 17, 40])
def test_a_pass_resumed_with_forced_zones_equals_one_uninterrupted_pass(seed, az_aware):
    problem, zones_of, score = tying_problem(seed, az_aware=az_aware)
    valid = problem.app_valid.copy()
    # one pass that is never interrupted: slots for every flagged app, its own choices
    whole = twin(problem, zones_of, score, valid, n_slots=24, az_aware=az_aware)
    flagged = np.flatnonzero(np.asarray(whole.uncertain))
    assert flagged.size >= 2, "the cluster was built to tie"
    chosen = np.asarray(whole.zone_idx)
    # the kernel without slots halts at each of them; hand it back what it handed out
    a = valid.shape[0]
    forced = np.full(a, FORCE_NONE, np.int32)
    placed, zone, node = np.zeros(a, bool), np.full(a, -1), np.zeros(a, np.int64)
    avail, start, halts = None, 0, []
    for _ in range(a + 1):
        got_placed, got_zone, got_node, got_flag, avail_after = kernel(
            problem, zones_of, score, valid, avail, forced, start, az_aware
        )
        stops = np.flatnonzero(got_flag[start:])
        stop = start + int(stops[0]) if stops.size else a
        placed[start:stop], zone[start:stop], node[start:stop] = (
            got_placed[start:stop], got_zone[start:stop], got_node[start:stop]
        )
        if stop == a:
            break
        assert not got_placed[stop:].any()  # nothing ran past the halt
        halts.append(stop)
        in_zone = 0 <= chosen[stop] < zones_of.n_zones
        forced[stop] = chosen[stop] if in_zone else -1
        avail, start = avail_after, stop
    assert halts == flagged.tolist()
    assert (placed == np.asarray(whole.feasible)).all()
    assert (zone == chosen).all() and (node == np.asarray(whole.driver_idx)).all()
    assert (avail_after == np.asarray(whole.avail_after)).all()


@pytest.mark.parametrize("n_slots", [2, 24])
@pytest.mark.parametrize("seed", [3, 17])
def test_kernel_and_twin_leave_the_same_flags_slots_and_snapshots(seed, n_slots):
    problem, zones_of, score = tying_problem(seed)
    s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = score
    valid = problem.app_valid.astype(np.int32)
    probe = int(valid.sum()) - 1
    valid[probe] = 2  # the last app rides along as the request's own
    want = twin(problem, zones_of, score, valid, n_slots=n_slots)
    node_cols = np.stack(
        [problem.driver_rank, problem.exec_ok.astype(np.int32), zones_of.zone_vec,
         s_cpu, s_gpu, th_m, inv_m.view(np.int32)], axis=1,
    )
    app_cols = np.concatenate(
        [problem.driver, problem.executor, problem.count[:, None], valid[:, None],
         np.full((valid.shape[0], 1), FORCE_NONE, np.int32)], axis=1,
    )
    columns, avail_after, snapshots = pallas_solve_queue_single_az_packed(
        jnp.asarray(problem.avail), jnp.asarray(node_cols), jnp.asarray(app_cols),
        jnp.asarray(np.array([scale_c, scale_g, 0], np.int32)),
        n_zones=zones_of.n_zones, interpret=True, n_slots=n_slots,
    )
    columns = np.asarray(columns)
    for column, name in enumerate(("feasible", "driver_idx", "zone_idx", "uncertain", "slot")):
        assert (columns[:, column] == np.asarray(getattr(want, name)).astype(np.int32)).all(), name
    assert (np.asarray(avail_after) == np.asarray(want.avail_after)).all()
    used = int(columns[:, 4].max()) + 1
    assert used >= min(n_slots, 2)
    assert (np.asarray(snapshots)[:used] == np.asarray(want.snapshots)[:used]).all()
    if n_slots == 2:
        # out of slots the pass halts at the next flagged app and touches nothing more
        halted = np.flatnonzero((columns[:, 3] != 0) & (columns[:, 4] < 0))
        assert halted.size == 1 and not columns[halted[0]:, 0].any()
    else:
        # the probe is packed into a slot and places nothing
        assert columns[probe, 3] == 1 and columns[probe, 0] == 0 and columns[probe, 4] >= 0
        packed = np.asarray(snapshots)[columns[probe, 4], 3]
        assert ((packed >> DRIVER_BIT) != 0).sum() >= 1  # a driver's node per feasible zone
        pick, carry = zones_of.pick_from_snapshot(np.asarray(snapshots)[columns[probe, 4]], probe)
        host = zones_of.pick(carry, probe)
        assert (pick.zone, pick.driver_idx, pick.executor_nodes) == (
            host.zone, host.driver_idx, host.executor_nodes
        )


@pytest.mark.parametrize("seed", [3, 17])
def test_min_frag_valve_reads_the_compacted_slots_as_it_reads_them_whole(seed):
    """Under min-frag the launch also hands back every slot compacted
    (the nodes its packing plane occupies, in node order, and the four
    planes there) and the probe's slot whole; the valve decides every
    slot of the launch at once from that view, as it does from the slots
    whole and as the drain's decode does, and a view too narrow for a
    slot has the slots read whole."""
    problem, zones_of, score = tying_problem(seed, inner="minimal-fragmentation")
    s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = score
    valid = problem.app_valid.astype(np.int32)
    probe = int(valid.sum()) - 1
    valid[probe] = 2
    node_cols = np.stack(
        [problem.driver_rank, problem.exec_ok.astype(np.int32), zones_of.zone_vec,
         s_cpu, s_gpu, th_m, inv_m.view(np.int32)], axis=1,
    )
    app_cols = np.concatenate(
        [problem.driver, problem.executor, problem.count[:, None], valid[:, None],
         np.full((valid.shape[0], 1), FORCE_NONE, np.int32)], axis=1,
    )
    columns, _, snapshots_dev, view, probe_slot = pallas_solve_queue_single_az_packed(
        jnp.asarray(problem.avail), jnp.asarray(node_cols), jnp.asarray(app_cols),
        jnp.asarray(np.array([scale_c, scale_g, 0], np.int32)),
        n_zones=zones_of.n_zones, interpret=True, minfrag=True, n_slots=24, compact=True,
    )
    columns, whole, view = np.asarray(columns), np.asarray(snapshots_dev), np.asarray(view)
    flagged = np.flatnonzero(columns[:, 3])
    slots = columns[flagged, 4]
    assert (slots >= 0).all() and flagged[-1] == probe and flagged.size >= 3
    assert (np.asarray(probe_slot) == whole[slots[-1]]).all()
    width = (view.shape[1] - 1) // 5
    for slot in slots.tolist():
        nodes = np.flatnonzero(whole[slot, 3])
        assert view[slot, 0] == nodes.size <= width
        assert (view[slot, 1 : 1 + nodes.size] == nodes).all()
        planes = view[slot, 1 + width :].reshape(4, width)[:, : nodes.size]
        assert (planes == whole[slot][:, nodes]).all()
    apps = np.full(len(view), -1)
    apps[slots[:-1]] = flagged[:-1]
    got = zones_of.placed_min_frag_zones(apps, view=view)
    assert (got == zones_of.placed_min_frag_zones(apps, snapshots=whole)).all()
    for u, slot in zip(flagged[:-1].tolist(), slots[:-1].tolist()):
        avail, packings = zones_of.snapshot_packings(whole[slot])
        assert got[slot] == zones_of.candidate(zones_of._choose(avail, u, packings))
    narrow = np.asarray(compact_snapshots(jnp.asarray(whole), width=2))
    decide = TpuSingleAzFifoSolver._min_frag_decisions
    assert (decide(zones_of, narrow, snapshots_dev, flagged, slots, probe) == got).all()


# -- the marker's verdict ------------------------------------------------------

POLICIES = [
    "tpu-batch-single-az",
    "tpu-batch-az-aware",
    "tpu-batch-single-az-minimal-fragmentation",
]


@pytest.mark.parametrize("policy", POLICIES)
def test_feasible_tensor_is_the_binpackers_has_capacity(policy):
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker
    from test_batch_parity import orders_for, random_app, random_cluster

    binpacker = select_binpacker(policy)
    solver = binpacker.queue_solver
    rng = random.Random(len(policy))
    verdicts = set()
    for _ in range(30):
        metadata = random_cluster(rng, rng.randint(2, 14))
        order, _ = orders_for(metadata, rng)
        app = random_app(rng)
        cluster = tensorize_cluster(metadata, order, order)
        got = solver.feasible_tensor(cluster, app)
        want = binpacker.binpack_func(
            app.driver_resources, app.executor_resources, app.min_executor_count,
            order, order, metadata,
        ).has_capacity
        assert got is not None and got == want
        verdicts.add(want)
    assert verdicts == {True, False}


@pytest.mark.parametrize("n_apps", [1, 17, 300])
@pytest.mark.parametrize("lane", ["xla", "native"])
@pytest.mark.parametrize("policy", POLICIES)
def test_feasible_batch_is_has_capacity_app_by_app(policy, lane, n_apps):
    """The batch entry under the zone policies: the program's group
    column (XLA lane) and the host's per-zone solves (native lane)."""
    from test_fifo_solver import check_feasible_batch

    want = check_feasible_batch(policy, lane, n_apps)
    assert n_apps < 17 or {True, False} <= set(want)


@pytest.mark.parametrize("lane", ["xla", "native"])
@pytest.mark.parametrize("policy", POLICIES)
def test_feasible_batch_leaves_out_a_zone_with_no_executor_candidate(policy, lane):
    """Zone z1 holds the only node a 6-cpu driver fits, but none of its
    nodes may take an executor: it is no candidate zone (single_az.go:
    30-45), so under single-AZ the gang fits nowhere, while az-aware
    falls back to the whole cluster.  A gang with a small driver fits z0
    or z2 whole, never the two together."""
    from test_fifo_solver import batch_solver_on, has_capacity

    sizes = {"a0": ("4", "z0"), "a1": ("4", "z0"), "b0": ("8", "z1"), "c0": ("4", "z2"), "c1": ("2", "z2")}
    metadata = {
        name: NodeSchedulingMetadata(
            available=Resources.of(cpu, "64Gi"), schedulable=Resources.of("8", "64Gi"), zone_label=zone
        )
        for name, (cpu, zone) in sizes.items()
    }
    d_order = list(metadata)
    e_order = [n for n in d_order if n != "b0"]
    cluster = tensorize_cluster(metadata, d_order, e_order)
    one = Resources.of("1", "1Gi")
    apps = [
        AppDemand(Resources.of("6", "1Gi"), one, 1),  # the driver fits b0 alone
        AppDemand(one, one, 7),    # z0 whole: 4 + 4 less the driver
        AppDemand(one, one, 8),    # more than any one zone, less than z0 and z2 together
        AppDemand(one, one, 14),   # a0, a1, c0, c1 hold 14, with the driver on b0
        AppDemand(one, one, 15),
    ]
    binpacker, solver = batch_solver_on(policy, lane)
    whole_cluster = policy == "tpu-batch-az-aware"
    want = [whole_cluster, True, whole_cluster, whole_cluster, False]
    assert solver.feasible_batch(cluster, apps) == want
    assert [has_capacity(binpacker, a, d_order, e_order, metadata) for a in apps] == want


def test_the_markers_scan_takes_the_tensor_lane_under_single_az():
    import time

    from k8s_spark_scheduler_tpu.metrics import names as mnames
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    h = Harness(binpack_algo="tpu-batch-single-az")
    try:
        for i in range(6):
            h.new_node(f"n{i}", zone=f"z{i % 2}")
        for i, executors in enumerate((1, 2, 3, 100)):  # the last fits no zone
            pod = h.static_allocation_spark_pods(f"app-aged-{i}", executors)[0]
            pod.meta.creation_timestamp = time.time() - 3600
            h.create_pod(pod)
        h.unschedulable_marker.scan_for_unschedulable_pods()
        metrics = h.server.metrics
        assert metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "tensor"}) == 4
        assert metrics.get_counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "host"}) == 0
        from k8s_spark_scheduler_tpu.scheduler.unschedulable import POD_EXCEEDS_CLUSTER_CAPACITY

        marked = {
            f"app-aged-{i}": h.api.get("Pod", "default", f"app-aged-{i}-driver")
            .conditions[POD_EXCEEDS_CLUSTER_CAPACITY].status
            for i in range(4)
        }
        assert marked == {
            "app-aged-0": "False", "app-aged-1": "False", "app-aged-2": "False", "app-aged-3": "True",
        }
    finally:
        h.close()


# -- guesses from the last request -----------------------------------------------


def _queue_the_score_gets_wrong(lane):
    """A tying cluster and queue on which the score's own choice for some
    flagged app is not float64's (zones that score the same in exact
    arithmetic and differ by an ulp in the order the terms are added), so
    that the first request needs a second launch."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    sizes = [("4", "8Gi")] * 3 + [("8", "16Gi")] * 3 + [("12", "48Gi")] * 3
    metadata = {
        f"n{i:03d}": NodeSchedulingMetadata(
            available=Resources.of(*sizes[i % 9]), schedulable=Resources.of(*sizes[i % 9]),
            zone_label=f"z{i % 3}",
        )
        for i in range(90)
    }
    order = list(metadata)
    probe = AppDemand(Resources.of("1", "1Gi"), Resources.of("1", "1Gi"), 1)
    for seed in range(200):
        rng = random.Random(seed)
        queue = [
            AppDemand(
                Resources.of("1", "1Gi"),
                Resources.of(str(rng.randint(1, 3)), f"{rng.randint(1, 6)}Gi"),
                rng.randint(1, 5),
            )
            for _ in range(20)
        ]
        solver = TpuSingleAzFifoSolver(backend=lane, interpret=True)
        args = (metadata, order, order, queue, [False] * len(queue), probe)
        solver.solve(*args)
        if solver.last_launches == 2:
            return solver, args
    raise AssertionError("no queue found on which the score's choice is not float64's")


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_a_guess_from_the_last_request_saves_the_launch_and_never_the_answer(lane):
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    solver, args = _queue_the_score_gets_wrong("xla")
    fresh = TpuSingleAzFifoSolver(backend=lane, interpret=True)

    def request(s):
        outcome = s.solve(*args)
        return s.last_launches, dict(s.last_zone_choices), (
            outcome.result.driver_node, outcome.result.executor_nodes
        )

    # the score's choice for a flagged app is not float64's: one more launch
    launches, choices, first = request(fresh)
    assert launches == 2 and choices["resolved"] >= 1
    # the same request again: last time's decisions are the pass's guesses, and they hold
    assert request(fresh) == (1, choices, first)
    # a wrong guess is found out like a wrong choice of the score's
    fresh._zone_memo = {key: ((zone + 1) % 3, None) for key, (zone, _) in fresh._zone_memo.items()}
    launches, _, poisoned = request(fresh)
    assert launches >= 2 and poisoned == first
    # what is remembered is a decision together with what it was decided on: the same
    # evidence is not decided again, and other evidence is
    calls = []
    real = _ZoneProblem.pick_from_snapshot
    try:
        _ZoneProblem.pick_from_snapshot = lambda self, snap, u: calls.append(u) or real(self, snap, u)
        assert request(fresh) == (1, choices, first)
        flagged_and_probe = choices["resolved"] + 1
        assert len(calls) == 1  # the request's own app alone
        metadata = dict(args[0])
        name, node = next(iter(metadata.items()))
        metadata[name] = NodeSchedulingMetadata(
            available=node.available, schedulable=Resources.of("64", "64Gi"), zone_label=node.zone_label
        )
        fresh.solve(metadata, *args[1:])
        assert 1 < len(calls) <= 1 + flagged_and_probe  # a node's schedulable total changed
    finally:
        _ZoneProblem.pick_from_snapshot = real
    # and the exact lane agrees with all of it
    native = TpuSingleAzFifoSolver(backend="native")
    assert request(native)[2] == first
