"""One device round per driver Filter (batch_solver.solve_filter): the
fused program against the two calls it replaced, bit for bit, and the
solver's device lanes against each other through it."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from k8s_spark_scheduler_tpu.ops import batch_solver as bs
from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuFifoSolver, _filter_blocks
from k8s_spark_scheduler_tpu.ops.pallas_queue import (
    pallas_solve_queue,
    pallas_solve_queue_min_frag,
)
from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
from k8s_spark_scheduler_tpu.ops.tensorize import scale_problem, tensorize_apps, tensorize_cluster
from k8s_spark_scheduler_tpu.types.resources import Resources

from test_batch_parity import orders_for, random_app, random_cluster

POLICIES = ["tightly-pack", "distribute-evenly", "minimal-fragmentation"]
HUGE = AppDemand(Resources.of("1", "1Gi"), Resources.of("512", "4096Gi"), 4)


def two_calls(problem, n_earlier, policy, pallas):
    """What a driver Filter dispatched before: the queue pass over the
    earlier apps, then ``solve_single`` for row ``n_earlier`` on the
    availability the pass leaves, each argument an upload of its own."""
    queue_valid = problem.app_valid.copy()
    queue_valid[n_earlier:] = False
    args = tuple(jnp.asarray(a) for a in (
        problem.avail, problem.driver_rank, problem.exec_ok,
        problem.driver, problem.executor, problem.count, queue_valid,
    ))
    evenly = policy == "distribute-evenly"
    if policy == "minimal-fragmentation" and pallas:
        verdicts, _, avail_after = pallas_solve_queue_min_frag(*args, interpret=True)
    elif policy == "minimal-fragmentation":
        out = bs.solve_queue_min_frag(*args, with_placements=False)
        verdicts, avail_after = out.feasible, out.avail_after
    elif pallas:
        verdicts, _, avail_after = pallas_solve_queue(*args, evenly=evenly, interpret=True)
    else:
        out = bs.solve_queue(*args, evenly=evenly, with_placements=False)
        verdicts, avail_after = out.feasible, out.avail_after
    solve = bs.solve_single(
        avail_after, args[1], args[2], jnp.asarray(problem.driver[n_earlier]),
        jnp.asarray(problem.executor[n_earlier]), jnp.asarray(problem.count[n_earlier]),
    )
    return verdicts, avail_after, solve


def problems(scenario, seed, trials=5):
    rng = random.Random(seed)
    for _ in range(trials):
        metadata = random_cluster(rng, rng.randint(2, 40))
        apps = [random_app(rng) for _ in range(0 if scenario == "empty-queue" else rng.randint(1, 20))]
        apps.append(HUGE if scenario == "infeasible-current" else random_app(rng))
        cluster = tensorize_cluster(metadata, *orders_for(metadata, rng))
        problem = scale_problem(cluster, tensorize_apps(apps))
        assert problem.ok and bs.mf_sentinel_safe(problem.avail)
        yield problem, len(apps) - 1


@pytest.mark.parametrize("scenario", ["random", "empty-queue", "infeasible-current"])
@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("policy", POLICIES)
def test_the_fused_program_equals_the_two_calls_bit_for_bit(policy, pallas, scenario):
    feasible_seen = 0
    for trial, (problem, n_earlier) in enumerate(problems(scenario, 3030 + pallas)):
        verdicts, avail_after, solve = two_calls(problem, n_earlier, policy, pallas)
        node_cols, app_cols = _filter_blocks(problem, n_earlier)
        out = np.asarray(bs.solve_filter(
            jnp.asarray(node_cols), jnp.asarray(app_cols),
            policy=policy, pallas=pallas, interpret=True,
        ))
        nb, ab = problem.avail.shape[0], problem.count.shape[0]
        assert out.dtype == np.int32 and out.shape == (4 * nb + ab + 2,)
        tag = f"trial {trial}"
        assert (out[: 3 * nb].reshape(nb, 3) == np.asarray(avail_after)).all(), tag
        per_node = solve.exec_capacity if policy == "distribute-evenly" else solve.exec_counts
        assert (out[3 * nb : 4 * nb] == np.asarray(per_node)).all(), tag
        assert (out[4 * nb : 4 * nb + ab] == np.asarray(verdicts)).all(), tag
        assert not out[4 * nb + n_earlier : 4 * nb + ab].any(), tag  # the queue ends before the current app
        assert out[-2] == int(solve.feasible) and out[-1] == int(solve.driver_idx), tag
        feasible_seen += int(out[-2])
    if scenario == "infeasible-current":
        assert feasible_seen == 0
    else:
        assert feasible_seen > 0


def solver_case(rng, scenario):
    metadata = random_cluster(rng, rng.randint(3, 30))
    driver_order, executor_order = orders_for(metadata, rng)
    earlier = [random_app(rng) for _ in range(0 if scenario == "empty-queue" else rng.randint(1, 12))]
    # nothing enforced ahead of an infeasible current driver: the request reaches its solve
    skip_allowed = [scenario == "infeasible-current" or rng.random() < 0.5 for _ in earlier]
    current = HUGE if scenario == "infeasible-current" else random_app(rng)
    if scenario == "blocked-earlier":
        at = rng.randrange(len(earlier))
        earlier[at], skip_allowed[at] = HUGE, False
    return metadata, driver_order, executor_order, earlier, skip_allowed, current


@pytest.mark.parametrize(
    "scenario", ["random", "empty-queue", "infeasible-current", "blocked-earlier"]
)
@pytest.mark.parametrize("policy", POLICIES)
def test_the_solvers_device_lanes_agree_through_the_fused_program(policy, scenario):
    """The Pallas lane (interpreted) against the XLA lane, whose parity
    with the host oracle tests/test_fifo_solver.py proves."""
    rng = random.Random(77 + POLICIES.index(policy))
    minfrag = policy == "minimal-fragmentation"
    for trial in range(4):
        args = solver_case(rng, scenario)
        xla = TpuFifoSolver(policy, backend="xla")
        pal = TpuFifoSolver(policy, backend="pallas", interpret=True)
        ref, got = xla.solve(*args), pal.solve(*args)
        tag = f"trial {trial}"
        assert ref.supported and got.supported, tag
        assert xla.last_queue_lane == ("minfrag-xla" if minfrag else "xla"), tag
        assert pal.last_queue_lane == ("pallas-minfrag" if minfrag else "pallas"), tag
        assert got.earlier_ok == ref.earlier_ok, tag
        if scenario != "random":
            assert ref.earlier_ok == (scenario != "blocked-earlier"), tag
        if not ref.earlier_ok:
            assert got.result is None and ref.result is None, tag  # the current driver's solve is discarded
            continue
        assert got.result.has_capacity == ref.result.has_capacity, tag
        if scenario == "infeasible-current":
            assert not ref.result.has_capacity, tag
        if ref.result.has_capacity:
            assert got.result.driver_node == ref.result.driver_node, tag
            assert got.result.executor_nodes == ref.result.executor_nodes, tag
            assert dict(got.result.packing_efficiencies.items()) == dict(
                ref.result.packing_efficiencies.items()
            ), tag
