"""Benchmark: HTTP Filter latency on the BASELINE north-star snapshot —
10k nodes × 1k pending apps through the REAL extender server.

One process, one platform, decided once from the environment: whatever
``jax.default_backend()`` reports when the run starts.  On a TPU host
that is the chip — the Pallas queue kernel lane and the request-level
phase both run there, and the run exits non-zero if any phase fails, if
the device lane did not serve, or if the extender counted a host
fallback.  With ``JAX_PLATFORMS=cpu`` it is the CPU run the contract
test uses (native C++ lane); every result names its ``platform``,
``device_kind`` and device count, so a CPU number can never be read as
a chip number.

The HEADLINE is request-level: the p99 of POST /predicates round trips
measured at the HTTP boundary (config5-e2e — server/http.py → serde →
Predicate → tensor mirror → queue lane → reservation create), at steady
state: every timed probe driver is deleted (with its reservation) after
its sample, so all ≥200 samples measure the same 10k×1k problem with
probe apps drawn from the same 1-32-executor distribution as the queue.
The solver-only lanes (pallas / native C++ / XLA scan chained queue
solves) are recorded as diagnostics in the same artifact; when the e2e
phase is switched off (``BENCH_E2E_PROBES=0``) the headline is the
solver lane under the honest name ``p99_queue_solve_…`` so a solver
microbench can never masquerade as the Filter SLO.

Solver-lane method: CHAIN data-dependent solves are chained on device
(each consumes the previous carry) and one scalar is fetched at the end,
so a sample is chain_total / CHAIN with the host dispatch amortised.
p99 is taken over repeated chain runs.

Prints ONE JSON line:
  {"metric": ..., "value": p99_ms, "unit": "ms", "vs_baseline": 50/p99}
vs_baseline > 1 means faster than the 50 ms north-star target.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# canonical BASELINE config (5) shape; env overrides exist for smoke
# tests only — the driver runs with the defaults
N_NODES = int(os.environ.get("BENCH_NODES", "10000"))
N_APPS = int(os.environ.get("BENCH_APPS", "1000"))
TARGET_MS = 50.0
CHAIN = int(os.environ.get("BENCH_CHAIN", "20"))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "15"))

# every measured lane lands here ({name: {p99_ms, p50_ms, ...}}) and is
# written to BENCH_RESULT.json at the end — the durable all-lane record
LANES: dict = {}
SECONDARY: dict = {}


def _machine_fingerprint() -> str:
    """Short hash of the executing host's CPU identity, recorded with
    the artifact so numbers from different hosts are not compared."""
    import hashlib
    import platform

    bits = [platform.machine(), platform.system()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags")):
                    bits.append(line.strip())
                    if len(bits) >= 4:
                        break
    except OSError:
        pass
    import jaxlib

    bits.append(jaxlib.__version__)
    return hashlib.sha1("|".join(bits).encode()).hexdigest()[:12]


def _host_info() -> dict:
    """Host context recorded with every artifact so cross-round numbers
    are comparable (the r1→r3 spread was load noise with no record)."""
    import platform

    info = {
        "fingerprint": _machine_fingerprint(),
        "platform": platform.platform(),
        "nproc": os.cpu_count(),
    }
    try:
        info["loadavg_1m"], info["loadavg_5m"], info["loadavg_15m"] = [
            round(v, 2) for v in os.getloadavg()
        ]
    except OSError:
        pass
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    info["cpu_model"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return info


def _device_info() -> dict:
    """The device every number of this run was taken on, as JAX reports
    it.  Rides on the headline, every solver result and the artifact."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def build_problem():
    from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand
    from k8s_spark_scheduler_tpu.ops.tensorize import (
        scale_problem,
        tensorize_apps,
        tensorize_cluster,
    )
    from k8s_spark_scheduler_tpu.types.resources import (
        NodeSchedulingMetadata,
        Resources,
    )

    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    metadata = {}
    for i in range(N_NODES):
        metadata[f"node-{i:05d}"] = NodeSchedulingMetadata(
            available=Resources.of(
                str(int(rng.randint(4, 96))), f"{int(rng.randint(8, 256))}Gi"
            ),
            schedulable=Resources.of("96", "256Gi"),
            zone_label=f"z{i % 3}",
        )
    order = list(metadata)
    apps = [
        AppDemand(
            driver_resources=Resources.of("1", "2Gi"),
            executor_resources=Resources.of(
                str(int(rng.randint(1, 8))), f"{int(rng.randint(2, 16))}Gi"
            ),
            min_executor_count=int(rng.randint(1, 32)),
        )
        for _ in range(N_APPS)
    ]
    cluster = tensorize_cluster(metadata, order, order)
    app_tensor = tensorize_apps(apps)
    problem = scale_problem(cluster, app_tensor)
    marshal_s = time.perf_counter() - t0
    assert problem.ok, "bench snapshot must be exactly tensorizable"
    return problem, marshal_s


def _device_args(problem):
    import jax.numpy as jnp

    return (
        jnp.asarray(problem.avail),
        jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok),
        jnp.asarray(problem.driver),
        jnp.asarray(problem.executor),
        jnp.asarray(problem.count),
        jnp.asarray(problem.app_valid),
    )


def _measure_chained(one_solve, args, label: str):
    """Compile + run the chained measurement; returns (lat_ms array,
    feasible_count)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("chain",))
    def chained(avail, *rest, chain=CHAIN):
        total = jnp.int32(0)
        for _ in range(chain):
            feas, avail_after = one_solve(avail, rest)
            total = total + jnp.sum(feas)
            avail = avail_after
        return total

    t0 = time.perf_counter()
    total = chained(*args)  # warmup/compile
    feasible_count = int(total) // CHAIN
    compile_s = time.perf_counter() - t0

    lat_ms = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        int(chained(*args))
        lat_ms.append((time.perf_counter() - t0) / CHAIN * 1000.0)
    lat = np.array(lat_ms)
    LANES[label] = _lane_stats(lat, feasible_count, compile_s=compile_s)
    print(
        f"# [{label}] p99={np.percentile(lat, 99):.2f}ms "
        f"p50={np.percentile(lat, 50):.2f}ms mean={lat.mean():.2f}ms "
        f"max={lat.max():.2f}ms compile={compile_s:.1f}s "
        f"feasible={feasible_count}/{N_APPS}",
        file=sys.stderr,
    )
    return lat, feasible_count


def _lane_stats(lat, feasible_count, compile_s=None) -> dict:
    stats = {
        "p99_ms": round(float(np.percentile(lat, 99)), 3),
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "mean_ms": round(float(lat.mean()), 3),
        "max_ms": round(float(lat.max()), 3),
        "rounds": int(lat.size),
        "feasible": int(feasible_count),
    }
    if compile_s is not None:
        stats["compile_s"] = round(compile_s, 2)
    return stats


def _emit(lat, feasible_count, marshal_s, backend: str) -> dict:
    p99 = float(np.percentile(lat, 99))
    device = _device_info()
    result = {
        # solver-lane metric: a chained whole-queue solve on prebuilt
        # tensors.  Deliberately NOT named "filter latency" — the Filter
        # is the HTTP request, measured by config5-e2e; main() promotes
        # that request-level number to the headline.
        "metric": "p99_queue_solve_10k_nodes_x_1k_apps_batched_repack",
        "value": round(p99, 3),
        "unit": "ms",
        # the floor only guards the division (tiny smoke shapes can
        # round to 0.0); the reported value is raw
        "vs_baseline": round(TARGET_MS / max(p99, 1e-3), 3),
        # which lane produced the number, and on what
        "backend": backend,
        **device,
    }
    print(
        f"# p50={np.percentile(lat, 50):.2f}ms mean={lat.mean():.2f}ms "
        f"max={lat.max():.2f}ms "
        f"feasible={feasible_count}/{N_APPS} marshal={marshal_s:.2f}s "
        f"platform={device['platform']} devices={device['device_count']} "
        f"backend={backend} chain={CHAIN}",
        file=sys.stderr,
    )
    return result


def tpu_solver_lane() -> dict:
    """The Pallas queue kernel as the served path dispatches it
    (``apps_per_step=1``), plus the other policies' kernels as stderr
    diagnostics.  Runs in this process, on this process's chip."""
    import jax.numpy as jnp

    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_app
    from k8s_spark_scheduler_tpu.ops.pallas_queue import pallas_solve_queue

    problem, marshal_s = build_problem()
    # production semantics (TpuFifoSolver): the current driver (the last
    # real app) is EXCLUDED from the queue pass and decoded separately
    # against the post-queue availability
    problem.app_valid[N_APPS - 1] = False
    args = _device_args(problem)

    def one_solve(avail, rest):
        # the production Filter cost: the queue pass PLUS the current
        # driver's placement decode (TpuFifoSolver's program runs
        # solve_app on the post-queue availability to produce the
        # executor list: batch_solver.solve_filter) — fold the decode
        # outputs into the carry so the decode is actually
        # materialized every solve
        rank, exec_ok, drivers, executors, counts, valid = rest
        feas, didx, avail_after = pallas_solve_queue(avail, *rest)
        # the current driver's decode (excluded from the queue above,
        # exactly as TpuFifoSolver runs it); feasible ⟹ placements
        # sum to k, so the conjunction preserves the feasibility
        # count while making the placement compute non-dead code
        last = N_APPS - 1
        decode = solve_app(
            avail_after, rank, exec_ok, drivers[last], executors[last], counts[last]
        )
        feas = feas.at[last].set(
            decode.feasible & (jnp.sum(decode.exec_counts) == counts[last])
        )
        return feas, avail_after

    lat, feasible_count = _measure_chained(
        one_solve, args, label="pallas apps_per_step=1"
    )
    result = _emit(lat, feasible_count, marshal_s, backend="pallas")
    _single_az_diag(problem)
    _min_frag_diag(problem)
    return result


def _min_frag_diag(problem) -> None:
    """Secondary diagnostic: the minimal-fragmentation whole-queue pass —
    the pallas VMEM kernel (the production TPU lane,
    pallas_solve_queue_min_frag) and the fused XLA scan
    (solve_queue_min_frag, the comparison point) on the same snapshot
    (stderr only)."""
    import jax
    import jax.numpy as jnp

    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_queue_min_frag
    from k8s_spark_scheduler_tpu.ops.pallas_queue import (
        pallas_solve_queue_min_frag,
    )

    rest = (
        jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok),
        jnp.asarray(problem.driver),
        jnp.asarray(problem.executor),
        jnp.asarray(problem.count),
        jnp.asarray(problem.app_valid),
    )
    a0 = jnp.asarray(problem.avail)

    def measure(label, one, chain):
        @functools.partial(jax.jit, static_argnames=("c",))
        def chained(a, c=chain):
            tot = jnp.int32(0)
            for _ in range(c):
                feas, a = one(a)
                tot = tot + jnp.sum(feas)
            return tot

        t0 = time.perf_counter()
        int(chained(a0))  # compile
        compile_s = time.perf_counter() - t0
        lat = []
        for _ in range(6):
            t0 = time.perf_counter()
            int(chained(a0))
            lat.append((time.perf_counter() - t0) / chain * 1000.0)
        print(
            f"# min-frag whole-queue ({label}): "
            f"median={float(np.median(lat)):.1f}ms/queue "
            f"compile={compile_s:.1f}s",
            file=sys.stderr,
        )

    def pallas_one(a):
        feas, _, a2 = pallas_solve_queue_min_frag(a, *rest)
        return feas, a2

    def xla_one(a):
        out = solve_queue_min_frag(a, *rest, with_placements=False)
        return out.feasible, out.avail_after

    measure("pallas kernel", pallas_one, chain=4)
    measure("fused scan", xla_one, chain=2)


def _single_az_diag(problem) -> None:
    """Secondary diagnostic: the single-AZ whole-queue kernel
    (pallas_solve_queue_single_az) on the same snapshot with a synthetic
    3-zone split — the single-AZ policies' FIFO cost (stderr only)."""
    import jax
    import jax.numpy as jnp

    from k8s_spark_scheduler_tpu.ops.batch_solver import snapshot_slots
    from k8s_spark_scheduler_tpu.ops.pallas_queue import (
        pallas_solve_queue_single_az,
    )

    nb = problem.avail.shape[0]
    # without slots the pass halts at the first app it cannot certify
    slots = snapshot_slots(nb)
    zone_vec = (np.arange(nb) % 3).astype(np.int32)
    sched = np.full(nb, 96000, np.int32)  # uniform synthetic schedulables
    no_gpu = np.zeros(nb, np.int32)
    inv_m = np.full(nb, 1.0 / 256.0, np.float32)
    th_m = np.full(nb, 256, np.int32)
    rest = (
        jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok),
        jnp.asarray(zone_vec),
        jnp.asarray(problem.driver),
        jnp.asarray(problem.executor),
        jnp.asarray(problem.count),
        jnp.asarray(problem.app_valid),
        jnp.asarray(sched),
        jnp.asarray(no_gpu),
        jnp.asarray(inv_m),
        jnp.asarray(th_m),
        jnp.asarray(np.array([1000], np.int32)),
        jnp.asarray(np.array([1000], np.int32)),
    )

    diag_chain = 4

    @functools.partial(jax.jit, static_argnames=("chain",))
    def chained(a, chain=diag_chain):
        tot = jnp.int32(0)
        for _ in range(chain):
            feas, _z, _d, unc, a2 = pallas_solve_queue_single_az(
                a, *rest, n_zones=3, az_aware=True, n_slots=slots
            )
            tot = tot + jnp.sum(feas) + jnp.sum(unc)
            a = a2
        return tot
    a0 = jnp.asarray(problem.avail)
    int(chained(a0))  # compile
    lat = []
    for _ in range(6):
        t0 = time.perf_counter()
        int(chained(a0))
        lat.append((time.perf_counter() - t0) / diag_chain * 1000.0)
    print(
        f"# single-az az-aware whole-queue (pallas, 3 zones): "
        f"median={float(np.median(lat)):.1f}ms/queue",
        file=sys.stderr,
    )

    # the single-az minimal-fragmentation pass: pallas kernel (the
    # production TPU lane) vs the fused XLA scan
    mf_chain = 2

    @functools.partial(jax.jit, static_argnames=("chain",))
    def mf_pallas_chained(a, chain=mf_chain):
        tot = jnp.int32(0)
        for _ in range(chain):
            feas, _z, _d, unc, a = pallas_solve_queue_single_az(
                a, *rest, n_zones=3, az_aware=False, minfrag=True, strict=True,
                n_slots=slots,
            )
            tot = tot + jnp.sum(feas) + jnp.sum(unc)
        return tot

    int(mf_pallas_chained(a0))  # compile
    lat = []
    for _ in range(6):
        t0 = time.perf_counter()
        int(mf_pallas_chained(a0))
        lat.append((time.perf_counter() - t0) / mf_chain * 1000.0)
    print(
        f"# single-az min-frag whole-queue (pallas, 3 zones): "
        f"median={float(np.median(lat)):.1f}ms/queue",
        file=sys.stderr,
    )

    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_queue_single_az

    nb = problem.avail.shape[0]
    zone_masks = np.stack([(np.arange(nb) % 3) == z for z in range(3)])
    mf_rest = (
        jnp.asarray(problem.driver_rank),
        jnp.asarray(problem.exec_ok),
        jnp.asarray(zone_masks),
        jnp.asarray(problem.driver),
        jnp.asarray(problem.executor),
        jnp.asarray(problem.count),
        jnp.asarray(problem.app_valid),
        *rest[7:11],  # s_cpu, s_gpu, inv_m, th_m planes
        jnp.int32(1000),
        jnp.int32(1000),
    )

    @functools.partial(jax.jit, static_argnames=("chain",))
    def mf_chained(a, chain=mf_chain):
        tot = jnp.int32(0)
        for _ in range(chain):
            out = solve_queue_single_az(
                a, *mf_rest, az_aware=False, minfrag=True, strict=True, n_slots=slots
            )
            tot = tot + jnp.sum(out.feasible)
            a = out.avail_after
        return tot

    int(mf_chained(a0))  # compile
    lat = []
    for _ in range(6):
        t0 = time.perf_counter()
        int(mf_chained(a0))
        lat.append((time.perf_counter() - t0) / mf_chain * 1000.0)
    print(
        f"# single-az min-frag whole-queue (fused scan, 3 zones): "
        f"median={float(np.median(lat)):.1f}ms/queue",
        file=sys.stderr,
    )


def cpu_solver_lane() -> dict:
    """The solver lanes of a CPU host (``JAX_PLATFORMS=cpu``): the native
    C++ queue solver that serves there, its session/explain/probe
    siblings, and the XLA scan as a diagnostic.  Every lane name says
    cpu."""
    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_app, solve_queue

    problem, marshal_s = build_problem()
    # same operation as the TPU lane: queue over the earlier apps,
    # separate decode for the current driver
    problem.app_valid[N_APPS - 1] = False

    # the production CPU lane (TpuFifoSolver backend="auto" on a
    # CPU-only host) is the native C++ queue solver — decision-identical
    # to the device scan (tests/test_native_fifo.py); it is the CPU
    # run's solver number, with the XLA scan kept as a diagnostic
    native = _native_cpu_measure(problem)
    _deltasolve_measure(problem)
    _provenance_measure(problem)
    _capacity_probe_measure(problem)
    _preemption_whatif_measure(problem)
    _class_compressed_measure()

    args = _device_args(problem)

    # note: sharding the scan across virtual CPU devices was measured
    # 18x SLOWER than single-device (per-step collective overhead);
    # the CPU run stays single-device on purpose
    def one_solve(avail, rest):
        import jax.numpy as jnp

        rank, exec_ok, drivers, executors, counts, valid = rest
        out = solve_queue(avail, *rest, evenly=False, with_placements=False)
        last = N_APPS - 1
        decode = solve_app(
            out.avail_after, rank, exec_ok, drivers[last], executors[last], counts[last]
        )
        feas = out.feasible.at[last].set(
            decode.feasible & (jnp.sum(decode.exec_counts) == counts[last])
        )
        return feas, out.avail_after

    lat, feasible_count = _measure_chained(one_solve, args, label="xla-scan cpu")
    _native_policy_diag(problem)
    if native is not None:
        nat_lat, nat_feasible = native
        return _emit(nat_lat, nat_feasible, marshal_s, backend="native-cpp")
    return _emit(lat, feasible_count, marshal_s, backend="xla-scan")


def _native_policy_diag(problem) -> None:
    """Native C++ lanes for the remaining policies on the same snapshot:
    whole-queue minimal-fragmentation and the single-AZ zone-choice pass
    (3 synthetic zones) — the CPU-host story for every policy, not just
    tightly/evenly."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        native_fifo_available,
        solve_queue_min_frag_native,
        solve_queue_native,
        solve_queue_single_az_native,
    )

    if not native_fifo_available():
        return
    nb = problem.avail.shape[0]

    def measure(label, one, reps=8):
        one()  # warm
        lat_ms = []
        for _ in range(reps):
            t0 = time.perf_counter()
            feasible = one()
            lat_ms.append((time.perf_counter() - t0) * 1000.0)
        lat = np.array(lat_ms)
        LANES[label] = _lane_stats(lat, feasible)
        print(
            f"# [{label}] p99={np.percentile(lat, 99):.2f}ms "
            f"p50={np.percentile(lat, 50):.2f}ms feasible={feasible}/{N_APPS}",
            file=sys.stderr,
        )

    measure(
        "native-cpp evenly cpu",
        lambda: int(
            solve_queue_native(
                problem.avail, problem.driver_rank, problem.exec_ok,
                problem.driver, problem.executor, problem.count,
                problem.app_valid, evenly=True,
            )[0].sum()
        ),
    )
    measure(
        "native-cpp minfrag cpu",
        lambda: int(
            solve_queue_min_frag_native(
                problem.avail, problem.driver_rank, problem.exec_ok,
                problem.driver, problem.executor, problem.count,
                problem.app_valid,
            )[0].sum()
        ),
    )

    zone_vec = (np.arange(nb) % 3).astype(np.int32)
    sched = np.abs(problem.avail.astype(np.int64)) * 2 + 1000
    scale = np.array([100, 2**20, 1000], np.int64)
    sched *= scale[None, :]
    measure(
        "native-cpp single-az cpu",
        lambda: int(
            solve_queue_single_az_native(
                problem.avail, problem.driver_rank, problem.exec_ok,
                zone_vec, problem.driver, problem.executor, problem.count,
                problem.app_valid, sched, scale, n_zones=3,
            )[0].sum()
        ),
    )


def _native_cpu_measure(problem):
    """Measure the native C++ queue solver (queue pass + current-driver
    decode, the TpuFifoSolver CPU-lane program).  Returns (lat_ms array,
    feasible_count) or None when the toolchain is unavailable."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        native_fifo_available,
        solve_app_native,
        solve_queue_native,
    )

    if not native_fifo_available():
        return None
    last = N_APPS - 1

    def one():
        feas, _, avail_after = solve_queue_native(
            problem.avail, problem.driver_rank, problem.exec_ok,
            problem.driver, problem.executor, problem.count,
            problem.app_valid, evenly=False,
        )
        fb, _db, cb, _caps = solve_app_native(
            avail_after, problem.driver_rank, problem.exec_ok,
            problem.driver[last], problem.executor[last],
            int(problem.count[last]),
        )
        return int(feas.sum()) + int(fb and cb.sum() == problem.count[last])

    feasible_count = one()  # warm the code path
    lat_ms = []
    for _ in range(max(ROUNDS, 15)):
        t0 = time.perf_counter()
        one()
        lat_ms.append((time.perf_counter() - t0) * 1000.0)
    lat = np.array(lat_ms)
    LANES["native-cpp cpu"] = _lane_stats(lat, feasible_count)
    print(
        f"# [native-cpp cpu] p99={np.percentile(lat, 99):.2f}ms "
        f"p50={np.percentile(lat, 50):.2f}ms mean={lat.mean():.2f}ms "
        f"feasible={feasible_count}/{N_APPS}",
        file=sys.stderr,
    )
    return lat, feasible_count


def _deltasolve_measure(problem) -> None:
    """Delta-solve session lane: cold full solve (basis load + whole
    queue) vs warm full-prefix resume on the SAME session at the bench
    shape.  Records both distributions so the acceptance bound — warm
    p50 at least 3x below the cold full-solve p50 — is durable in the
    artifact (the perf guard pins the same bound in CI)."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        NativeFifoSession,
        native_session_available,
    )

    if not native_session_available():
        return
    packed = np.hstack(
        [
            problem.driver, problem.executor,
            problem.count[:, None],
            problem.app_valid.astype(np.int32)[:, None],
        ]
    ).astype(np.int32)
    sess = NativeFifoSession()
    try:
        def cold():
            sess.load(
                problem.avail, problem.driver_rank, problem.exec_ok, 0
            )
            return sess.solve(packed)

        def warm():
            return sess.solve(packed)

        _, feas_cold, _, after_cold = cold()
        resume, feas_warm, _, after_warm = warm()
        assert resume == packed.shape[0]
        assert np.array_equal(feas_warm, feas_cold)
        assert np.array_equal(after_warm, after_cold)
        reps = max(ROUNDS, 15)
        cold_ms, warm_ms = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            cold()
            cold_ms.append((time.perf_counter() - t0) * 1000.0)
        for _ in range(reps):
            t0 = time.perf_counter()
            warm()
            warm_ms.append((time.perf_counter() - t0) * 1000.0)
        cold_lat, warm_lat = np.array(cold_ms), np.array(warm_ms)
        feasible = int(feas_cold.sum())
        stats = _lane_stats(warm_lat, feasible)
        stats["cold_p50_ms"] = round(float(np.percentile(cold_lat, 50)), 3)
        stats["warm_p50_ms"] = round(float(np.percentile(warm_lat, 50)), 3)
        stats["warm_speedup_p50"] = round(
            float(np.percentile(cold_lat, 50))
            / max(float(np.percentile(warm_lat, 50)), 1e-6),
            1,
        )
        LANES["deltasolve-session cpu"] = stats
        SECONDARY["deltasolve_cold_p50_ms"] = stats["cold_p50_ms"]
        SECONDARY["deltasolve_warm_p50_ms"] = stats["warm_p50_ms"]
        print(
            f"# [deltasolve-session cpu] cold_p50={stats['cold_p50_ms']}ms "
            f"warm_p50={stats['warm_p50_ms']}ms "
            f"speedup={stats['warm_speedup_p50']}x",
            file=sys.stderr,
        )
    finally:
        sess.close()


def _provenance_measure(problem) -> None:
    """Provenance overhead contract (PR 6): the explain path (shortfall
    + blocker replay at the bench shape) and the flight-recorder
    note+persist cost, as their own diagnostic lane.  Explain is
    on-demand (a refusal or an /explain request), so its budget is
    'about one cold solve', not microseconds — the lane pins that it
    stays in that regime; the perf guard separately pins the capture
    cost on the request path at < 5% (enabled) / zero (disabled)."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        explain_queue_native,
        native_explain_available,
    )
    from k8s_spark_scheduler_tpu.provenance.recorder import FlightRecorder
    from k8s_spark_scheduler_tpu.provenance.tracker import SolveArtifacts

    if not native_explain_available():
        return
    packed = np.hstack(
        [
            problem.driver, problem.executor,
            problem.count[:, None],
            problem.app_valid.astype(np.int32)[:, None],
        ]
    ).astype(np.int32)
    target = int(packed.shape[0] - 1)
    reps = max(ROUNDS, 10)
    explain_ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        explain_queue_native(
            problem.avail, problem.driver_rank, problem.exec_ok,
            packed, 0, target,
        )
        explain_ms.append((time.perf_counter() - t0) * 1000.0)
    n_earlier = target
    art = SolveArtifacts(
        policy_code=0,
        lane="bench",
        basis=problem.avail,
        driver_rank=problem.driver_rank,
        exec_ok=problem.exec_ok,
        packed=packed,
        n_earlier=n_earlier,
        feasible=np.ones(n_earlier, dtype=bool),
        didx=np.zeros(n_earlier, dtype=np.int32),
        resume=0,
        avail_after=problem.avail,
    )
    note_ms = []
    with tempfile.TemporaryDirectory() as tmp:
        rec = FlightRecorder(
            capacity=8, out_dir=tmp, max_nodes=problem.avail.shape[0]
        )
        for _ in range(reps):
            t0 = time.perf_counter()
            rec.note(art, "bench-pod", "failure-fit")
            note_ms.append((time.perf_counter() - t0) * 1000.0)
        t0 = time.perf_counter()
        path = rec.persist("bench")
        persist_ms = (time.perf_counter() - t0) * 1000.0
        bundle_bytes = os.path.getsize(path) if path else 0
    lat = np.array(explain_ms)
    stats = _lane_stats(lat, 0)
    stats["explain_p50_ms"] = round(float(np.percentile(lat, 50)), 3)
    stats["recorder_note_p50_ms"] = round(
        float(np.percentile(np.array(note_ms), 50)), 3
    )
    stats["persist_ms"] = round(persist_ms, 3)
    stats["bundle_file_bytes"] = int(bundle_bytes)
    LANES["provenance-explain cpu"] = stats
    SECONDARY["provenance_explain_p50_ms"] = stats["explain_p50_ms"]
    print(
        f"# [provenance-explain cpu] explain_p50={stats['explain_p50_ms']}ms "
        f"note_p50={stats['recorder_note_p50_ms']}ms "
        f"persist={stats['persist_ms']}ms bundle={bundle_bytes}B",
        file=sys.stderr,
    )


def _capacity_probe_measure(problem) -> None:
    """Capacity-observatory contract (PR 7): the batched what-if
    headroom probe at the bench node shape × 16 gang shapes, as its own
    diagnostic lane.  The probe is the sampler's unit of work (one per
    (group, zone) combo per state change), so its latency budget is
    'milliseconds at 10k nodes', and the bisection depth (solves per
    shape) should stay a handful — both are pinned by the bench
    contract."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        native_probe_available,
        probe_headroom_native,
    )

    if not native_probe_available():
        return
    n_shapes = 16
    take = min(n_shapes, problem.driver.shape[0])
    shapes = np.zeros((n_shapes, 6), dtype=np.int32)
    shapes[:take, 0:3] = problem.driver[:take]
    shapes[:take, 3:6] = problem.executor[:take]
    if take < n_shapes:  # pad by cycling (smoke shapes have few apps)
        for i in range(take, n_shapes):
            shapes[i] = shapes[i % max(take, 1)]
    reps = max(ROUNDS, 10)
    probe_ms = []
    solves = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        out = probe_headroom_native(
            problem.avail, problem.driver_rank, problem.exec_ok,
            shapes, 1_000_000,
        )
        probe_ms.append((time.perf_counter() - t0) * 1000.0)
        solves = int(out[2].sum())
    lat = np.array(probe_ms)
    stats = _lane_stats(lat, int((out[0] > 0).sum()))
    stats["probe_p50_ms"] = round(float(np.percentile(lat, 50)), 3)
    stats["shapes"] = n_shapes
    stats["solves_per_probe"] = solves
    stats["solves_per_shape_p50"] = round(
        float(np.percentile(out[2], 50)), 1
    )
    LANES["capacity-probe cpu"] = stats
    SECONDARY["capacity_probe_p50_ms"] = stats["probe_p50_ms"]
    print(
        f"# [capacity-probe cpu] probe_p50={stats['probe_p50_ms']}ms "
        f"({n_shapes} shapes, {solves} feasibility solves/probe)",
        file=sys.stderr,
    )


def _preemption_whatif_measure(problem) -> None:
    """Policy-engine contract (ISSUE 14): the preemption what-if solve
    at the bench node shape × 16 preemptor gangs, as its own lane.  A
    what-if validates one candidate victim set — ``gang_feasible`` on
    ``avail + freed`` — and the selector runs up to ``max_victims`` of
    them per refused driver, so its per-call latency bounds the cost a
    preemption attempt adds to a Filter round.  Pure numpy (the
    fallback when no warm delta-solve session exists), so the lane is
    unconditional."""
    from k8s_spark_scheduler_tpu.policy.victims import whatif_fits

    n_nodes = problem.avail.shape[0]
    n_gangs = 16
    take = max(min(n_gangs, problem.driver.shape[0]), 1)
    gangs = [
        (
            problem.driver[i % take],
            problem.executor[i % take],
            int(problem.count[i % take]),
        )
        for i in range(n_gangs)
    ]
    # a victim set's freed capacity: a few whole applications'
    # worth of executors returned across a handful of nodes
    # (deterministic; the verdict itself is irrelevant to latency)
    rng = np.random.default_rng(7)
    freed = np.zeros((n_nodes, 3), dtype=problem.avail.dtype)
    victim_nodes = rng.choice(n_nodes, size=min(8, n_nodes), replace=False)
    for i, node in enumerate(victim_nodes):
        freed[node] = problem.executor[i % take] * 3
    reps = max(ROUNDS, 10)
    whatif_ms = []
    fits = 0
    for _ in range(reps):
        fits = 0
        for gang in gangs:
            t0 = time.perf_counter()
            ok = whatif_fits(
                problem.avail, problem.exec_ok, problem.driver_rank,
                freed, gang,
            )
            whatif_ms.append((time.perf_counter() - t0) * 1000.0)
            fits += int(ok)
    lat = np.array(whatif_ms)
    stats = _lane_stats(lat, fits)
    stats["whatif_p50_ms"] = round(float(np.percentile(lat, 50)), 3)
    stats["gangs"] = n_gangs
    LANES["preemption-whatif cpu"] = stats
    SECONDARY["preemption_whatif_p50_ms"] = stats["whatif_p50_ms"]
    print(
        f"# [preemption-whatif cpu] whatif_p50={stats['whatif_p50_ms']}ms "
        f"p99={stats['p99_ms']}ms ({n_gangs} gangs, {n_nodes} nodes)",
        file=sys.stderr,
    )


def _class_compressed_measure() -> None:
    """Equivalence-class lane (ROADMAP 2): the class-compressed native
    solver at 100k nodes × 10k apps — the scale where per-app O(nodes)
    row sweeps stop fitting in a Filter budget and O(classes + diverged
    overlay) keeps working.  Runs at its OWN shape (``BENCH_CLASS_NODES``
    × ``BENCH_CLASS_APPS``; 10× the main shape when unset so smoke runs
    scale down honestly), proves byte-identical verdicts against a
    row-level cold solve of the same inputs every run, and records the
    compression evidence (class count, ratio, rebuilds) alongside the
    latencies — the speedup claim is only as good as the parity + the
    partition it rode on."""
    from k8s_spark_scheduler_tpu.native.fifo import (
        NativeFifoSession,
        native_classes_available,
        solve_packed_classes,
        solve_packed_cold,
    )

    if not native_classes_available():
        return
    cn = int(os.environ.get("BENCH_CLASS_NODES", str(N_NODES * 10)))
    ca = int(os.environ.get("BENCH_CLASS_APPS", str(N_APPS * 10)))
    rng = np.random.RandomState(20)
    # fleet-shaped: ~24 machine shapes, salted with near-duplicates
    # (one unit off) so the partition is earned, not gifted
    shapes = rng.randint(20, 200, size=(24, 3)).astype(np.int32)
    avail = shapes[rng.randint(0, 24, size=cn)].copy()
    near = rng.choice(cn, size=max(1, cn // 50), replace=False)
    avail[near, rng.randint(0, 3, size=len(near))] += 1
    rank = np.arange(cn, dtype=np.int32)
    rng.shuffle(rank)
    eok = rng.rand(cn) > 0.05
    drv = rng.randint(0, 3, size=(ca, 3)).astype(np.int32)
    exe = rng.randint(1, 5, size=(ca, 3)).astype(np.int32)
    cnt = rng.randint(1, 8, size=ca).astype(np.int32)
    packed = np.hstack(
        [drv, exe, cnt[:, None], np.ones((ca, 1), np.int32)]
    ).astype(np.int32)

    # parity first: the speedup only counts if the bits agree
    feas, didx, after, evidence = solve_packed_classes(
        0, avail, rank, eok, packed
    )
    ref_f, ref_d, ref_a = solve_packed_cold(0, avail, rank, eok, packed)
    assert np.array_equal(feas, ref_f)
    assert np.array_equal(didx, ref_d)
    assert np.array_equal(after, ref_a)

    # the row-level reference is seconds per solve at this shape:
    # a few reps give a stable p50 without eating the bench budget
    row_reps = max(3, min(ROUNDS, 5))
    row_ms = []
    for _ in range(row_reps):
        t0 = time.perf_counter()
        solve_packed_cold(0, avail, rank, eok, packed)
        row_ms.append((time.perf_counter() - t0) * 1000.0)
    cls_reps = max(ROUNDS, 10)
    cold_ms = []
    for _ in range(cls_reps):
        t0 = time.perf_counter()
        solve_packed_classes(0, avail, rank, eok, packed)
        cold_ms.append((time.perf_counter() - t0) * 1000.0)

    # warm lane: a persistent class-mode session resolving the same
    # queue (full-prefix resume — the steady Filter retry path)
    warm_ms = []
    sess = NativeFifoSession()
    try:
        if sess.set_classes(True):
            sess.load(avail, rank, eok, 0)
            sess.solve(packed)
            for _ in range(cls_reps):
                t0 = time.perf_counter()
                sess.solve(packed)
                warm_ms.append((time.perf_counter() - t0) * 1000.0)
    finally:
        sess.close()

    row_lat, cold_lat = np.array(row_ms), np.array(cold_ms)
    stats = _lane_stats(cold_lat, int(feas.sum()))
    stats["nodes"] = cn
    stats["apps"] = ca
    stats["row_p50_ms"] = round(float(np.percentile(row_lat, 50)), 3)
    stats["speedup_p50"] = round(
        float(np.percentile(row_lat, 50))
        / max(float(np.percentile(cold_lat, 50)), 1e-6),
        1,
    )
    stats["classes_initial"] = int(evidence["classes_initial"])
    stats["classes_last"] = int(evidence["classes_last"])
    stats["rebuilds"] = int(evidence["rebuilds"])
    stats["overlay_peak"] = int(evidence["overlay_peak"])
    stats["compression_ratio"] = round(
        cn / max(int(evidence["classes_initial"]), 1), 1
    )
    stats["parity"] = "byte-identical"
    LANES["class-compressed cold"] = stats
    if warm_ms:
        warm_lat = np.array(warm_ms)
        wstats = _lane_stats(warm_lat, int(feas.sum()))
        wstats["nodes"] = cn
        wstats["apps"] = ca
        LANES["class-compressed warm"] = wstats
    SECONDARY["class_cold_p50_ms"] = stats["p50_ms"]
    SECONDARY["class_row_p50_ms"] = stats["row_p50_ms"]
    SECONDARY["class_speedup_p50"] = stats["speedup_p50"]
    print(
        f"# [class-compressed cold] {cn}x{ca} p50={stats['p50_ms']}ms "
        f"row_p50={stats['row_p50_ms']}ms "
        f"speedup={stats['speedup_p50']}x "
        f"classes={stats['classes_initial']} "
        f"ratio={stats['compression_ratio']}x "
        f"rebuilds={stats['rebuilds']}",
        file=sys.stderr,
    )


def _check_load() -> bool:
    """Annotate the artifact loudly when another heavy process owns
    the core at run start, so cross-round deltas mean
    something.  Threshold: on this nproc-core host a 1-minute load
    above 0.5·nproc means the bench shares its core(s)."""
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        return True
    ok = load1 <= 0.5 * (os.cpu_count() or 1)
    if not ok:
        print(
            f"# WARNING: loadavg_1m={load1:.2f} at bench start — another "
            "process is using the core; latencies are NOT comparable "
            "across rounds (artifact carries load_ok=false)",
            file=sys.stderr,
        )
    return ok


def main() -> int:
    from k8s_spark_scheduler_tpu.utils.compilecache import configure_compile_cache

    load_ok = _check_load()
    cache_dir = configure_compile_cache()
    # the platform is decided ONCE, here, from the environment; nothing
    # below switches it, and nothing answers for a platform that is not
    # there
    device = _device_info()
    print(
        f"# platform={device['platform']} device_kind={device['device_kind']} "
        f"devices={device['device_count']} compile_cache={cache_dir}",
        file=sys.stderr,
    )
    if device["platform"] == "tpu":
        solver = tpu_solver_lane()
    elif device["platform"] == "cpu":
        solver = cpu_solver_lane()
    else:
        raise SystemExit(
            f"bench.py measures a TPU or (JAX_PLATFORMS=cpu) the CPU host "
            f"lane, not {device['platform']!r}"
        )
    solver["load_ok"] = load_ok
    # write the durable artifact BEFORE the secondary configs: a kill
    # during those (they are unbounded harness runs) must not cost the
    # solver-lane evidence; rewritten afterwards with SECONDARY + the
    # request-level headline filled in
    _write_bench_result(solver, device)
    _secondary_configs()
    e2e = _config5_e2e()
    if e2e is not None:
        # the headline is the request-level number measured at the HTTP
        # boundary; the solver lane rides along so the two can never be
        # confused
        p99 = e2e["p99_ms"]
        headline = {
            "metric": "p99_filter_latency_10k_nodes_x_1k_apps_batched_repack",
            "value": round(p99, 3),
            "unit": "ms",
            "vs_baseline": round(TARGET_MS / max(p99, 1e-3), 3),
            "backend": e2e["backend"],
            **device,
            "samples": e2e["rounds"],
            "p50_ms": e2e["p50_ms"],
            "p95_ms": e2e.get("p95_ms"),
            "measured_at": "http",
            "fallbacks": e2e["fallbacks"],
            "solver_p99_ms": solver.get("value"),
            "solver_backend": solver.get("backend"),
            "load_ok": load_ok,
        }
        # delta-solve evidence rides on the headline: steady-state warm
        # hit rate + resume depth from the e2e phase, warm/cold solver
        # p50s from the session lane (contract-pinned)
        if "warm_hit_rate" in e2e:
            headline["warm_hit_rate"] = e2e["warm_hit_rate"]
            headline["resume_depth_p50"] = e2e.get("resume_depth_p50")
        ds = LANES.get("deltasolve-session cpu")
        if ds is not None:
            headline["warm_solve_p50_ms"] = ds["warm_p50_ms"]
            headline["cold_solve_p50_ms"] = ds["cold_p50_ms"]
        # contention-observatory evidence: how much of the request the
        # decomposition explains, and which segment dominates
        if "criticalpath_coverage_p50" in e2e:
            headline["criticalpath_coverage_p50"] = e2e["criticalpath_coverage_p50"]
            headline["criticalpath_dominant"] = e2e.get("criticalpath_dominant")
    else:
        # request-level phase switched off: the solver lane stands,
        # under its own honest p99_queue_solve_… name
        headline = solver
    _write_bench_result(headline, device)
    # the headline is the FINAL stdout line, emitted after everything
    # that could possibly spew — a tail-window capture (the driver's)
    # can never lose it to later output
    print(json.dumps(headline))
    return 0


def _write_bench_result(headline: dict, device: dict) -> None:
    """Durable all-lane artifact: BENCH_RESULT.json on disk, so the
    run's evidence survives even when a stdout capture doesn't.
    Non-canonical (smoke) shapes write to a side path so they can never
    clobber canonical evidence."""
    canonical = (N_NODES, N_APPS) == (10000, 1000)
    artifact = {
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "headline": headline,
        "device": device,
        "lanes": LANES,
        "secondary_configs": SECONDARY,
        "host": _host_info(),
        "shape": {"nodes": N_NODES, "apps": N_APPS, "chain": CHAIN, "rounds": ROUNDS},
        "target_ms": TARGET_MS,
    }
    name = "BENCH_RESULT.json" if canonical else "BENCH_RESULT_smoke.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")


def _secondary_configs() -> None:
    """BASELINE.json configs (1), (2), (3), (4) measured end-to-end
    through the extender harness on this run's platform (stderr
    diagnostics; the headline metric above is config (5))."""
    import logging

    h = None
    try:
        from k8s_spark_scheduler_tpu.testing.harness import Harness

        # synthetic old pods trip the slow-schedule warnings; keep the
        # diagnostics readable
        logging.disable(logging.WARNING)

        # (1) tightly-pack: 1 driver + 8 executors on a 32-node snapshot
        h = Harness(binpack_algo="tpu-batch", is_fifo=True)
        for i in range(32):
            h.new_node(f"n{i:02d}", cpu="16", memory="32Gi")
        nodes = [f"n{i:02d}" for i in range(32)]
        pods = Harness.static_allocation_spark_pods("warmup", 8)
        h.schedule(pods[0], nodes)
        t0 = time.perf_counter()
        pods = Harness.static_allocation_spark_pods("cfg1", 8)
        result = h.schedule(pods[0], nodes)
        assert result.node_names, result.failed_nodes
        cfg1_ms = (time.perf_counter() - t0) * 1000
        SECONDARY["config1_tightly_pack_e2e_ms"] = round(cfg1_ms, 1)
        print(f"# config1 tightly-pack 1+8@32nodes: {cfg1_ms:.1f}ms e2e", file=sys.stderr)

        # (2) FIFO queue of 128 static apps drained in order
        drivers = []
        base = time.time()
        for i in range(128):
            d = Harness.static_allocation_spark_pods(
                f"q{i:03d}", 2, creation_timestamp=base - 1000 + i
            )[0]
            h.create_pod(d)
            drivers.append(d)
        t0 = time.perf_counter()
        granted = sum(1 for d in drivers if h.schedule(d, nodes).node_names)
        cfg2_ms = (time.perf_counter() - t0) * 1000
        SECONDARY["config2_fifo128_ms_per_app"] = round(cfg2_ms / 128, 2)
        SECONDARY["config2_fifo128_granted"] = granted
        print(
            f"# config2 FIFO 128 apps: {cfg2_ms:.0f}ms total "
            f"({cfg2_ms / 128:.1f}ms/app, {granted} granted)",
            file=sys.stderr,
        )

        # (4) dynamic allocation with soft reservations
        da = Harness.dynamic_allocation_spark_pods("cfg4", 2, 8)
        t0 = time.perf_counter()
        result = h.schedule(da[0], nodes)
        assert result.node_names, result.failed_nodes
        for p in da[1:]:
            h.schedule(p, nodes)
        cfg4_ms = (time.perf_counter() - t0) * 1000
        SECONDARY["config4_da_e2e_ms"] = round(cfg4_ms, 1)
        sr, _ = h.server.soft_reservation_store.get_soft_reservation("cfg4")
        print(
            f"# config4 DA min2/max8: {cfg4_ms:.0f}ms for driver+8 executors, "
            f"{len(sr.reservations)} soft reservations",
            file=sys.stderr,
        )
        h.close()
        h = None

        # (3) heterogeneous multi-instance-group nodes with label-priority
        # sort (exercises the label-aware fast path)
        _config3(nodes_per_group=16)
    finally:
        if h is not None:
            h.close()
        logging.disable(logging.NOTSET)


def _config5_e2e() -> dict | None:
    """(5) end-to-end, the HEADLINE phase: the north-star snapshot
    through the REAL HTTP extender — N_NODES nodes, N_APPS pending FIFO
    drivers, Filter latency measured at the request level
    (server/http.py → serde → Predicate → tensor mirror → native/device
    queue lane; reference path resource.go:128-183 +
    cmd/endpoints.go:29-41).

    Sampling: ≥200 timed probes drawn from the SAME 1-32-executor /
    1-8-cpu / 2-16Gi distribution as the queue.  After each sample the
    probe pod is deleted and its reservation collected (the app-finished
    flow), and the next probe waits for that settling — so every sample
    measures the identical steady-state 10k×1k problem instead of a
    growing queue.  Returns the lane stats dict (with `backend` = the
    queue lane that actually served), or None when the phase is switched
    off (``BENCH_E2E_PROBES=0``).  Raises when the server does not
    become ready, when the extender counted a host fallback, or when on
    a TPU any lane but the Pallas kernel served."""
    import json as _json
    import logging
    import urllib.request

    probes = int(os.environ.get("BENCH_E2E_PROBES", "200"))
    if probes <= 0:
        return None
    http = scheduler = None
    try:
        from k8s_spark_scheduler_tpu.config import Install
        from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
        from k8s_spark_scheduler_tpu.kube.crd import (
            DEMAND_CRD_NAME,
            demand_crd_spec,
        )
        from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
        from k8s_spark_scheduler_tpu.server.wiring import init_server_with_clients
        from k8s_spark_scheduler_tpu.testing.harness import Harness
        from k8s_spark_scheduler_tpu.types import serde
        from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
        from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources

        logging.disable(logging.WARNING)
        t_setup = time.perf_counter()
        api = APIServer()
        api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
        scheduler = init_server_with_clients(
            api, Install(binpack_algo="tpu-batch", fifo=True),
            demand_poll_interval=0.5,
        )
        rng = np.random.RandomState(5)
        names = []
        for i in range(N_NODES):
            name = f"n{i:05d}"
            names.append(name)
            api.create(
                Node(
                    meta=ObjectMeta(
                        name=name,
                        labels={
                            ZONE_LABEL: f"z{i % 3}",
                            "resource_channel": "batch-medium-priority",
                        },
                    ),
                    allocatable=Resources.of(
                        str(int(rng.randint(4, 96))),
                        f"{int(rng.randint(8, 256))}Gi",
                    ),
                )
            )
        base = time.time() - 10_000.0
        for i in range(N_APPS):
            d = Harness.static_allocation_spark_pods(
                f"queue-{i:04d}",
                int(rng.randint(1, 32)),
                executor_cpu=str(int(rng.randint(1, 8))),
                executor_mem=f"{int(rng.randint(2, 16))}Gi",
                creation_timestamp=base + i,
            )[0]
            api.create(d)
        http = ExtenderHTTPServer(scheduler, port=0)
        http.start()
        # the readiness condition a deployment gates traffic on: caches
        # synced AND solver warmup done (its compiler threads would
        # otherwise contend with the timed probes for the core)
        if not scheduler.wait_ready(timeout=600.0):
            raise RuntimeError("config5-e2e: server not ready after 600s")
        setup_s = time.perf_counter() - t_setup

        def post_filter(pod):
            payload = {
                "Pod": serde.pod_to_dict(pod),
                "NodeNames": names,
            }
            req = urllib.request.Request(
                f"http://127.0.0.1:{http.port}/predicates",
                data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as resp:
                body = _json.loads(resp.read())
            return (time.perf_counter() - t0) * 1000.0, body

        rr_cache = scheduler.resource_reservation_cache

        def retire_probe(pod, app_id):
            """The app-finished flow: delete the probe pod (owner GC
            collects its reservation — or the dangling-owner check does,
            if the async create lands later) and wait until the
            reservation cache has dropped the app, so the next sample
            sees the exact steady-state shape again."""
            api.delete("Pod", pod.namespace, pod.name)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if rr_cache.get(pod.namespace, app_id) is None:
                    return True
                time.sleep(0.002)
            return False

        def one_probe(i):
            d = Harness.static_allocation_spark_pods(
                f"probe-{i:04d}",
                int(rng.randint(1, 32)),
                executor_cpu=str(int(rng.randint(1, 8))),
                executor_mem=f"{int(rng.randint(2, 16))}Gi",
                creation_timestamp=base + N_APPS + i,
            )[0]
            pod = api.create(d)
            ms, body = post_filter(pod)
            ok = bool(body.get("NodeNames") or body.get("nodeNames"))
            settled = retire_probe(pod, pod.labels.get("spark-app-id", ""))
            return ms, ok, settled

        # warmups absorb compile / tensor-mirror build / cache priming
        warm_ms, _, _ = one_probe(0)
        one_probe(1)
        lat_ms = []
        granted = 0
        unsettled = 0
        for i in range(2, probes + 2):
            ms, ok, settled = one_probe(i)
            lat_ms.append(ms)
            granted += ok
            unsettled += not settled
        lat = np.array(lat_ms)
        p99 = float(np.percentile(lat, 99))
        stats = _lane_stats(lat, granted)
        stats["p95_ms"] = round(float(np.percentile(lat, 95)), 3)
        stats["setup_s"] = round(setup_s, 1)
        stats["warmup_ms"] = round(warm_ms, 1)
        stats["unsettled"] = unsettled
        solver = getattr(scheduler.extender.binpacker, "queue_solver", None)
        lane = getattr(solver, "last_queue_lane", None)
        stats["backend"] = {
            "native": "native-cpp", "native-minfrag": "native-cpp",
            "native-session": "native-cpp",
            "pallas": "pallas", "pallas-minfrag": "pallas",
            "xla": "xla-scan", "minfrag-xla": "xla-scan",
        }.get(lane, lane or "unknown")
        # delta-solve engine evidence for the steady-state phase: how
        # often the persistent session served warm, and how deep into
        # the queue the prefix cache resumed (contract-pinned by
        # tests/test_bench_contract.py)
        engine = getattr(scheduler.extender, "delta_engine", None)
        if engine is not None:
            es = engine.stats()
            stats["warm_hit_rate"] = round(float(es["warm_hit_rate"]), 4)
            stats["resume_depth_p50"] = es["resume_depth_p50"]
            stats["deltasolve_sessions"] = es["sessions"]
            stats["deltasolve_misses"] = es["misses"]
        # contention-observatory scrape: the critical-path decomposition
        # of the probes just measured (acceptance: named segments must
        # reconstruct the server-side request) plus the predicate lock's
        # wait/hold picture — one more lane in the durable artifact
        def get_json(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}{path}", timeout=30
            ) as resp:
                return _json.loads(resp.read())

        cp = get_json("/debug/criticalpath")
        con = get_json("/debug/contention?lock=extender.predicate")
        seg = cp.get("segments", {})
        lane = {
            "window": cp.get("window", 0),
            "total_p99_ms": cp.get("totalMs", {}).get("p99", 0.0),
            "coverage_p50": cp.get("coverage", {}).get("p50", 0.0),
            "gate_queue_p99_ms": seg.get("gate-queue", {}).get("p99Ms", 0.0),
            "lock_wait_p99_ms": seg.get("lock-wait", {}).get("p99Ms", 0.0),
            "serde_p99_ms": seg.get("serde", {}).get("p99Ms", 0.0),
            "solve_p99_ms": seg.get("solve", {}).get("p99Ms", 0.0),
            "write_back_p99_ms": seg.get("write-back", {}).get("p99Ms", 0.0),
            "other_p99_ms": seg.get("other", {}).get("p99Ms", 0.0),
        }
        locks = {l["name"]: l for l in con.get("locks", [])}
        plock = locks.get("extender.predicate")
        if plock is not None:
            lane["lock_acquisitions"] = plock["acquisitions"]
            lane["lock_contended"] = plock["contended"]
            lane["lock_wait_ms_p95"] = plock["waitMs"]["p95"]
            lane["lock_hold_ms_p95"] = plock["holdMs"]["p95"]
            lane["lock_hold_ms_p99"] = plock["holdMs"]["p99"]
        LANES["contention http"] = lane
        stats["criticalpath_coverage_p50"] = lane["coverage_p50"]
        stats["criticalpath_dominant"] = max(
            cp.get("dominant", {}) or {"": 0},
            key=lambda k: cp["dominant"].get(k, 0),
        )
        print(
            f"# contention: coverage p50={lane['coverage_p50']} "
            f"solve p99={lane['solve_p99_ms']:.1f}ms "
            f"serde p99={lane['serde_p99_ms']:.1f}ms "
            f"write-back p99={lane['write_back_p99_ms']:.1f}ms "
            f"lock hold p95={lane.get('lock_hold_ms_p95', 0.0)}ms",
            file=sys.stderr,
        )
        # every Filter above must have been answered by the lane this
        # platform is benched for
        stats["fallbacks"] = scheduler.extender.host_fallbacks()
        if stats["fallbacks"]:
            raise RuntimeError(
                f"config5-e2e: {stats['fallbacks']} request(s) fell back from "
                "the device lane to the host path"
            )
        if _device_info()["platform"] == "tpu" and stats["backend"] != "pallas":
            raise RuntimeError(
                f"config5-e2e on a TPU was served by {stats['backend']!r}, "
                "not the Pallas queue kernel"
            )
        LANES["config5-e2e http"] = stats
        SECONDARY["config5_e2e_p99_ms"] = round(p99, 1)
        SECONDARY["config5_e2e_p50_ms"] = round(float(np.percentile(lat, 50)), 1)
        SECONDARY["config5_e2e_granted"] = granted
        print(
            f"# config5-e2e HTTP Filter {N_NODES}x{N_APPS}: "
            f"p99={p99:.1f}ms p95={stats['p95_ms']:.1f}ms "
            f"p50={np.percentile(lat, 50):.1f}ms n={len(lat_ms)} "
            f"granted={granted}/{len(lat_ms)} lane={stats['backend']} "
            f"unsettled={unsettled} warmup={warm_ms:.0f}ms "
            f"setup={setup_s:.0f}s",
            file=sys.stderr,
        )
        return stats
    finally:
        if http is not None:
            http.stop()
        if scheduler is not None:
            scheduler.stop()
        logging.disable(logging.NOTSET)


def _config3(nodes_per_group: int) -> None:
    from k8s_spark_scheduler_tpu.ops.nodesort import LabelPriorityOrder
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    h = Harness(
        binpack_algo="tpu-batch",
        is_fifo=True,
        driver_prioritized_node_label=LabelPriorityOrder("pool", ["reserved", "spot"]),
        executor_prioritized_node_label=LabelPriorityOrder("pool", ["spot", "reserved"]),
    )
    try:
        nodes = []
        for g, (ig, pool) in enumerate(
            [("batch", "reserved"), ("batch", "spot"), ("ml", "reserved")]
        ):
            for i in range(nodes_per_group):
                name = f"g{g}-n{i:02d}"
                h.new_node(
                    name,
                    cpu="16",
                    memory="32Gi",
                    instance_group=ig,
                    labels={"pool": pool},
                )
                nodes.append(name)
        batch_nodes = [n for n in nodes if not n.startswith("g2-")]
        warm = Harness.static_allocation_spark_pods("warm3", 4, instance_group="batch")
        res = h.schedule(warm[0], batch_nodes)
        assert res.node_names, res.failed_nodes
        t0 = time.perf_counter()
        pods = Harness.static_allocation_spark_pods("cfg3", 8, instance_group="batch")
        result = h.schedule(pods[0], batch_nodes)
        assert result.node_names, result.failed_nodes
        cfg3_ms = (time.perf_counter() - t0) * 1000
        SECONDARY["config3_label_priority_e2e_ms"] = round(cfg3_ms, 1)
        print(
            f"# config3 heterogeneous 3-group label-priority: {cfg3_ms:.1f}ms e2e "
            f"(driver on {result.node_names[0]})",
            file=sys.stderr,
        )
    finally:
        h.close()


if __name__ == "__main__":
    sys.exit(main())
