"""Differential tests for the native C++ minimal-fragmentation and
single-AZ FIFO queue solvers (native/fifo_solver.cpp): decision-identical
to the device scan (batch_solver.solve_queue_min_frag) and to the
single-AZ solver's exact host lane, same contract as test_native_fifo.py
holds the tightly/evenly lanes to."""

import numpy as np
import pytest

import jax.numpy as jnp

from k8s_spark_scheduler_tpu.native.fifo import (
    native_fifo_available,
    solve_queue_min_frag_native,
    solve_queue_single_az_native,
)
from k8s_spark_scheduler_tpu.ops.batch_solver import (
    BIG,
    solve_queue_min_frag,
    solve_single,
    solve_zones_jit,
)

pytestmark = pytest.mark.skipif(
    not native_fifo_available(), reason="native toolchain unavailable"
)


def _random_problem(rng, n, a, max_avail=300):
    avail = rng.randint(-10, max_avail, size=(n, 3)).astype(np.int32)
    rank = np.arange(n, dtype=np.int32)
    rng.shuffle(rank)
    rank = np.where(rng.rand(n) < 0.2, BIG, rank).astype(np.int32)
    exec_ok = rng.rand(n) < 0.85
    drivers = rng.randint(0, 8, size=(a, 3)).astype(np.int32)
    executors = rng.randint(0, 6, size=(a, 3)).astype(np.int32)  # incl. 0-req dims
    counts = rng.randint(0, 12, size=a).astype(np.int32)
    valid = rng.rand(a) < 0.9
    return avail, rank, exec_ok, drivers, executors, counts, valid


def test_min_frag_queue_differential_vs_device_scan():
    rng = np.random.RandomState(20260730)
    for _ in range(40):
        n, a = rng.randint(3, 150), rng.randint(1, 40)
        avail, rank, exec_ok, drivers, executors, counts, valid = _random_problem(
            rng, n, a
        )
        out = solve_queue_min_frag(
            jnp.asarray(avail), jnp.asarray(rank), jnp.asarray(exec_ok),
            jnp.asarray(drivers), jnp.asarray(executors), jnp.asarray(counts),
            jnp.asarray(valid), with_placements=False,
        )
        feas, didx, avail_after = solve_queue_min_frag_native(
            avail, rank, exec_ok, drivers, executors, counts, valid
        )
        np.testing.assert_array_equal(feas, np.asarray(out.feasible))
        np.testing.assert_array_equal(didx, np.asarray(out.driver_idx))
        np.testing.assert_array_equal(avail_after, np.asarray(out.avail_after))


def _host_oracle_single_az(
    avail0, rank, exec_ok, zone_masks, drivers, executors, counts, valid,
    sched, scale, az_aware, minfrag, strict,
):
    """The solver host lane (TpuSingleAzFifoSolver.pack_one +
    _choose_best_result semantics) assembled from the same building
    blocks production uses: device per-zone solves, exact float64 zone
    scores via efficiencies_from_rows, occurrence-ordered sums."""
    from k8s_spark_scheduler_tpu.ops.batch_adapter import (
        counts_to_tightly_list,
        min_frag_zone_decode,
        names_of_rows,
    )
    from k8s_spark_scheduler_tpu.ops.fifo_solver import efficiencies_from_rows

    nb = avail0.shape[0]
    n = sched.shape[0]
    names = [f"n{i}" for i in range(n)]
    avail = avail0.astype(np.int32).copy()
    z_count = zone_masks.shape[0]
    a_count = drivers.shape[0]
    feas_out = np.zeros(a_count, bool)
    zone_out = np.full(a_count, -1, np.int32)
    didx_out = np.full(a_count, nb, np.int32)

    for ai in range(a_count):
        if not valid[ai]:
            continue
        solves = solve_zones_jit(
            jnp.asarray(avail), jnp.asarray(rank), jnp.asarray(exec_ok),
            jnp.asarray(zone_masks), jnp.asarray(drivers[ai]),
            jnp.asarray(executors[ai]), jnp.asarray(counts[ai]),
        )
        zf = np.asarray(solves.feasible)
        zd = np.asarray(solves.driver_idx)
        zc = np.asarray(solves.exec_counts)
        best_avg = 0.0
        best = None
        for zi in range(z_count):
            if not zf[zi]:
                continue
            d_idx = int(zd[zi])
            if minfrag:
                decoded = min_frag_zone_decode(
                    avail.astype(np.int64)[:n], executors[ai],
                    (exec_ok & zone_masks[zi])[:n], d_idx, drivers[ai],
                    int(counts[ai]), strict,
                )
                if decoded is None:
                    continue
                rows, zcounts, eff_counts = decoded
                executor_nodes = names_of_rows(names, rows)[0]
            else:
                zcounts = zc[zi][:n].astype(np.int64)
                executor_nodes = counts_to_tightly_list(names, zcounts)
                eff_counts = zcounts
            eff_rows = (
                eff_counts.astype(np.int64)[:, None]
                * executors[ai].astype(np.int64)[None, :]
            )
            eff_rows[d_idx] += drivers[ai].astype(np.int64)
            effs = efficiencies_from_rows(
                names, sched,
                avail.astype(np.int64)[:n] * scale[None, :],
                eff_rows * scale[None, :],
            )
            max_sum = 0.0
            for nm in [names[d_idx]] + list(executor_nodes):
                e = effs[nm]
                max_sum += max(e.gpu, e.cpu, e.memory)
            avg = max_sum / max(float(1 + len(executor_nodes)), 1.0)
            if best_avg < avg:
                best_avg = avg
                best = (zi, d_idx, zcounts)
        if best is None and az_aware:
            cross = solve_single(
                jnp.asarray(avail), jnp.asarray(rank), jnp.asarray(exec_ok),
                jnp.asarray(drivers[ai]), jnp.asarray(executors[ai]),
                jnp.asarray(counts[ai]),
            )
            if bool(cross.feasible):
                best = (
                    z_count,
                    int(cross.driver_idx),
                    np.asarray(cross.exec_counts)[:n].astype(np.int64),
                )
        if best is None:
            continue
        zi, d_idx, zcounts = best
        feas_out[ai] = True
        zone_out[ai] = zi
        didx_out[ai] = d_idx
        # the reference's usage-subtraction quirk
        exec_mask = zcounts > 0
        delta = np.zeros((nb, 3), np.int32)
        delta[:n][exec_mask] = executors[ai]
        if not exec_mask[d_idx]:
            delta[d_idx] = drivers[ai]
        avail -= delta
    return feas_out, zone_out, didx_out, avail


def _random_zone_problem(rng, n, a, z):
    avail, rank, exec_ok, drivers, executors, counts, valid = _random_problem(
        rng, n, a
    )
    # disjoint zones over a subset of nodes (some nodes zoneless)
    zone_of = rng.randint(-1, z, size=n).astype(np.int32)
    zone_masks = np.stack([zone_of == zi for zi in range(z)])
    sched = np.abs(avail.astype(np.int64)) + rng.randint(
        1, 500, size=(n, 3)
    ).astype(np.int64)
    scale = np.array([100, 2**20, 1000], np.int64)
    sched *= scale[None, :]  # base units
    return (avail, rank, exec_ok, zone_of, zone_masks, drivers, executors,
            counts, valid, sched, scale)


@pytest.mark.parametrize(
    "az_aware,minfrag,strict",
    [
        (False, False, True),
        (True, False, True),
        (False, True, True),
        (False, True, False),
    ],
)
def test_single_az_queue_differential_vs_host_lane(az_aware, minfrag, strict):
    rng = np.random.RandomState(123 + int(az_aware) * 7 + int(minfrag) * 13)
    for _ in range(15):
        n, a, z = rng.randint(4, 80), rng.randint(1, 20), rng.randint(1, 4)
        (avail, rank, exec_ok, zone_of, zone_masks, drivers, executors,
         counts, valid, sched, scale) = _random_zone_problem(rng, n, a, z)
        ref = _host_oracle_single_az(
            avail, rank, exec_ok, zone_masks, drivers, executors, counts,
            valid, sched, scale, az_aware, minfrag, strict,
        )
        got = solve_queue_single_az_native(
            avail, rank, exec_ok, zone_of, drivers, executors, counts, valid,
            sched, scale, n_zones=z, az_aware=az_aware, minfrag=minfrag,
            strict=strict,
        )
        np.testing.assert_array_equal(got[0], ref[0])  # feasible
        np.testing.assert_array_equal(got[1], ref[1])  # zone choice
        np.testing.assert_array_equal(got[2], ref[2])  # driver idx
        np.testing.assert_array_equal(got[3], ref[3])  # avail carry


def _scenario_metadata(rng, n, zones=("z0", "z1", "z2")):
    from k8s_spark_scheduler_tpu.types.resources import (
        NodeSchedulingMetadata,
        Resources,
    )

    return {
        f"n{i:02d}": NodeSchedulingMetadata(
            available=Resources.of(
                str(int(rng.randint(1, 32))), f"{int(rng.randint(1, 64))}Gi"
            ),
            schedulable=Resources.of("32", "64Gi"),
            zone_label=zones[i % len(zones)],
        )
        for i in range(n)
    }


def _scenario_apps(rng, count):
    from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
    from k8s_spark_scheduler_tpu.types.resources import Resources

    return [
        AppDemand(
            driver_resources=Resources.of("1", "1Gi"),
            executor_resources=Resources.of(
                str(int(rng.randint(1, 4))), f"{int(rng.randint(1, 8))}Gi"
            ),
            min_executor_count=int(rng.randint(1, 6)),
        )
        for _ in range(count)
    ]


def _assert_outcomes_equal(a, b):
    assert a.supported == b.supported
    assert a.earlier_ok == b.earlier_ok
    if a.result is not None or b.result is not None:
        assert a.result.has_capacity == b.result.has_capacity
        assert a.result.driver_node == b.result.driver_node
        assert a.result.executor_nodes == b.result.executor_nodes


@pytest.mark.parametrize("strict", [True, False])
def test_fifo_solver_native_minfrag_backend_matches_xla(strict):
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuFifoSolver

    rng = np.random.RandomState(1001)
    for _ in range(8):
        metadata = _scenario_metadata(rng, int(rng.randint(4, 30)), zones=("z0",))
        order = list(metadata)
        apps = _scenario_apps(rng, int(rng.randint(1, 7)))
        earlier, current = apps[:-1], apps[-1]
        skip = [bool(rng.rand() < 0.5) for _ in earlier]
        outs, solvers = {}, {}
        for backend in ("native", "xla"):
            solvers[backend] = TpuFifoSolver(
                assignment_policy="minimal-fragmentation", backend=backend,
                strict_reference_parity=strict,
            )
            outs[backend] = solvers[backend].solve(
                metadata, order, order, earlier, skip, current
            )
        if earlier:
            assert solvers["native"].last_queue_lane == "native-minfrag"
            assert solvers["xla"].last_queue_lane == "minfrag-xla"
        _assert_outcomes_equal(outs["native"], outs["xla"])


@pytest.mark.parametrize(
    "az_aware,inner_policy",
    [
        (False, "tightly-pack"),
        (True, "tightly-pack"),
        (False, "minimal-fragmentation"),
    ],
)
def test_single_az_solver_native_backend_matches_host(az_aware, inner_policy):
    """TpuSingleAzFifoSolver end-to-end: native lane vs the fused+valve
    XLA lane (whose uncertain cases re-solve on the exact host path) on
    randomized multi-zone snapshots."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    rng = np.random.RandomState(77 + int(az_aware))
    for _ in range(8):
        metadata = _scenario_metadata(rng, int(rng.randint(6, 30)))
        order = list(metadata)
        apps = _scenario_apps(rng, int(rng.randint(1, 7)))
        earlier, current = apps[:-1], apps[-1]
        skip = [bool(rng.rand() < 0.5) for _ in earlier]
        outs, solvers = {}, {}
        for backend in ("native", "xla"):
            solvers[backend] = TpuSingleAzFifoSolver(
                az_aware=az_aware, backend=backend, inner_policy=inner_policy
            )
            outs[backend] = solvers[backend].solve(
                metadata, order, order, earlier, skip, current
            )
        if earlier:
            assert solvers["native"].last_path == "native"
            assert solvers["xla"].last_path in ("fused", "host")
        _assert_outcomes_equal(outs["native"], outs["xla"])


def test_single_az_minfrag_sentinel_collision_gates_native_lane():
    """A scaled availability reaching MF_SENT would alias the native
    drain's int32 unbounded sentinel — such snapshots must fall through
    to the exact host lane (whose decode uses a 2^62 sentinel), exactly
    like the fused device lanes are gated by mf_sentinel_safe."""
    from k8s_spark_scheduler_tpu.ops.batch_solver import MF_SENT
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver
    from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
    from k8s_spark_scheduler_tpu.types.resources import (
        NodeSchedulingMetadata,
        Resources,
    )

    # one huge node: memory availability = MF_SENT bytes (scale 1)
    metadata = {
        "big": NodeSchedulingMetadata(
            available=Resources.of("64", str(MF_SENT)),
            schedulable=Resources.of("64", str(MF_SENT)),
            zone_label="z0",
        ),
        "small": NodeSchedulingMetadata(
            available=Resources.of("64", "1001"),
            schedulable=Resources.of("64", str(MF_SENT)),
            zone_label="z0",
        ),
    }
    order = list(metadata)
    app = AppDemand(
        driver_resources=Resources.of("1", "1"),
        executor_resources=Resources.of("1", "1"),
        min_executor_count=2,
    )
    solver = TpuSingleAzFifoSolver(
        az_aware=False, backend="native", inner_policy="minimal-fragmentation"
    )
    out = solver.solve(metadata, order, order, [app], [False], app)
    assert out.supported and out.earlier_ok
    assert solver.last_path == "host"  # native lane must NOT have served

    # sentinel-safe snapshots still ride the native lane
    safe_md = {
        k: NodeSchedulingMetadata(
            available=Resources.of("8", "1000"),
            schedulable=Resources.of("8", "1000"),
            zone_label="z0",
        )
        for k in ("a", "b")
    }
    solver2 = TpuSingleAzFifoSolver(
        az_aware=False, backend="native", inner_policy="minimal-fragmentation"
    )
    out2 = solver2.solve(safe_md, list(safe_md), list(safe_md), [app], [False], app)
    assert out2.supported
    assert solver2.last_path == "native"


def test_forced_native_backend_raises_without_library(monkeypatch):
    """ADVICE r3: an explicitly forced 'native' backend must fail loudly
    when the C++ lane can't be built, never silently degrade to the
    ~8x-slower XLA scan."""
    from k8s_spark_scheduler_tpu.native import fifo as native_fifo
    from k8s_spark_scheduler_tpu.ops import fifo_solver

    monkeypatch.setattr(native_fifo, "native_fifo_available", lambda: False)
    with pytest.raises(RuntimeError, match="forced"):
        fifo_solver._native_selected("native")
    # auto still degrades gracefully
    assert fifo_solver._native_selected("auto") in (True, False)
