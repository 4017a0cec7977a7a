#!/usr/bin/env python
"""Large-scale device-vs-oracle differential fuzz (the BASELINE gate:
zero gang-feasibility regressions, SURVEY §6).

Random clusters (heterogeneous sizes, zones, unschedulable nodes, GPU
rows, fractional quantities) × random gangs, solved by every device
policy and compared decision-for-decision (has_capacity, driver node,
exact executor list) against its host oracle.  Any mismatch is a
failure.  CI runs a modest budget; scale --trials for soak runs.

    python tools/parity_fuzz.py --trials 150 --seed 987654
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# parity is platform-independent integer math: the fuzz compares the
# forced native and forced XLA lanes on the host CPU
jax.config.update("jax_platforms", "cpu")

from k8s_spark_scheduler_tpu.ops import packers
from k8s_spark_scheduler_tpu.ops.batch_adapter import (
    TpuBatchBinpacker,
    TpuSingleAzBinpacker,
)
from k8s_spark_scheduler_tpu.ops.nodesort import NodeSorter
from k8s_spark_scheduler_tpu.types.resources import (
    NodeSchedulingMetadata,
    Resources,
)

PAIRS = [
    ("tightly-pack", TpuBatchBinpacker("tightly-pack"), packers.tightly_pack),
    (
        "distribute-evenly",
        TpuBatchBinpacker("distribute-evenly"),
        packers.distribute_evenly,
    ),
    (
        "minimal-fragmentation",
        TpuBatchBinpacker("minimal-fragmentation"),
        packers.minimal_fragmentation_pack,
    ),
    (
        "minimal-fragmentation/corrected",  # strict-reference-parity: false
        TpuBatchBinpacker("minimal-fragmentation", strict_reference_parity=False),
        packers.make_minimal_fragmentation_pack(False),
    ),
    (
        "single-az-tightly-pack",
        TpuSingleAzBinpacker(az_aware=False),
        packers.single_az_tightly_pack,
    ),
    (
        "az-aware-tightly-pack",
        TpuSingleAzBinpacker(az_aware=True),
        packers.az_aware_tightly_pack,
    ),
    (
        "single-az-minimal-fragmentation",
        TpuSingleAzBinpacker(inner_policy="minimal-fragmentation"),
        packers.single_az_minimal_fragmentation,
    ),
    (
        "single-az-minimal-fragmentation/corrected",
        TpuSingleAzBinpacker(
            inner_policy="minimal-fragmentation", strict_reference_parity=False
        ),
        packers.make_single_az_minimal_fragmentation(False),
    ),
]


def random_cluster(rng: random.Random, n_nodes: int) -> dict:
    metadata = {}
    for i in range(n_nodes):
        if rng.random() < 0.3:
            cpu = f"{rng.randint(1, 64)}500m"
        else:
            cpu = str(rng.randint(1, 64))
        if rng.random() < 0.3:
            mem = f"{rng.randint(512, 65536)}Mi"
        else:
            mem = f"{rng.randint(1, 64)}Gi"
        gpu = str(rng.randint(0, 8)) if rng.random() < 0.25 else "0"
        # overbooked nodes (overhead > allocatable drives availability
        # negative, resources.go:61-100 has no floor)
        if rng.random() < 0.05:
            cpu = str(-rng.randint(1, 8))
        if rng.random() < 0.03:
            mem = f"-{rng.randint(1, 8)}Gi"
        metadata[f"n{i:04d}"] = NodeSchedulingMetadata(
            available=Resources.of(cpu, mem, gpu),
            schedulable=Resources.of("64", "64Gi", "8"),
            zone_label=f"z{rng.randint(0, 3)}",
            unschedulable=rng.random() < 0.08,
            ready=rng.random() > 0.05,
        )
    return metadata


def random_gang(rng: random.Random, n_nodes: int):
    driver = Resources.of(
        str(rng.randint(1, 4)), f"{rng.randint(1, 8)}Gi",
        str(rng.randint(0, 1)) if rng.random() < 0.2 else "0",
    )
    executor = Resources.of(
        str(rng.randint(1, 16)) if rng.random() > 0.06 else "0",
        f"{rng.randint(1, 16)}Gi" if rng.random() > 0.06 else "0",
        str(rng.randint(0, 2)) if rng.random() < 0.2 else "0",
    )
    count = rng.randint(0, max(2 * n_nodes, 4))
    return driver, executor, count


def host_fifo_loop(metadata, driver_order, executor_order, queue, current, packer):
    """fitEarlierDrivers + final pack on the host oracle (resource.go:
    224-262); every earlier driver is enforced (skip never allowed)."""
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_resource_usage
    from k8s_spark_scheduler_tpu.types.resources import (
        copy_metadata,
        subtract_usage_if_exists,
    )

    meta = copy_metadata(metadata)
    for driver_res, executor_res, count in queue:
        result = packer(driver_res, executor_res, count, driver_order, executor_order, meta)
        if not result.has_capacity:
            return False, None
        subtract_usage_if_exists(
            meta,
            spark_resource_usage(
                driver_res, executor_res, result.driver_node, result.executor_nodes
            ),
        )
    return True, packer(*current, driver_order, executor_order, meta)


def queue_fuzz(rng, metadata, driver_order, executor_order, report):
    """FIFO queue solvers (one-dispatch device scans) vs the host loop."""
    from k8s_spark_scheduler_tpu.ops.fifo_solver import (
        TpuFifoSolver,
        TpuSingleAzFifoSolver,
    )
    from k8s_spark_scheduler_tpu.ops.sparkapp import AppDemand

    # every policy × both serving lanes: "native" forces the C++
    # solvers (raising loudly if the toolchain is missing, so the lane
    # can never silently degrade to an XLA re-run and fuzz green with
    # zero native coverage), "xla" forces the fused device scans — both
    # against the same host oracle
    from k8s_spark_scheduler_tpu.native.fifo import native_fifo_available

    backends = ["xla"]
    if native_fifo_available():
        backends.insert(0, "native")
    else:
        print(
            "WARNING: native C++ solver unavailable — fuzzing the XLA "
            "lane only (no native differential coverage this run)",
            file=sys.stderr,
        )
    queue_pairs = []
    for backend in backends:
        tag = f"queue[{backend}]"
        queue_pairs += [
            (
                f"{tag}/tightly-pack",
                TpuFifoSolver("tightly-pack", backend=backend),
                packers.tightly_pack,
            ),
            (
                f"{tag}/distribute-evenly",
                TpuFifoSolver("distribute-evenly", backend=backend),
                packers.distribute_evenly,
            ),
            (
                f"{tag}/minimal-fragmentation",
                TpuFifoSolver("minimal-fragmentation", backend=backend),
                packers.minimal_fragmentation_pack,
            ),
            (
                f"{tag}/single-az",
                TpuSingleAzFifoSolver(az_aware=False, backend=backend),
                packers.single_az_tightly_pack,
            ),
            (
                f"{tag}/az-aware",
                TpuSingleAzFifoSolver(az_aware=True, backend=backend),
                packers.az_aware_tightly_pack,
            ),
            (
                f"{tag}/single-az-minimal-fragmentation",
                TpuSingleAzFifoSolver(
                    inner_policy="minimal-fragmentation", backend=backend
                ),
                packers.single_az_minimal_fragmentation,
            ),
        ]
    n_nodes = len(metadata)
    queue = [random_gang(rng, n_nodes) for _ in range(rng.randint(1, 6))]
    current = random_gang(rng, n_nodes)
    apps = [AppDemand(*g) for g in queue]
    cur_app = AppDemand(*current)
    bad = 0
    ran = 0
    for name, solver, oracle in queue_pairs:
        want_ok, want = host_fifo_loop(
            metadata, driver_order, executor_order, queue, current, oracle
        )
        got = solver.solve(
            metadata, driver_order, executor_order, apps, [False] * len(apps), cur_app
        )
        if not got.supported:
            continue  # snapshot outside the device lane's bounds
        ran += 1
        mismatch = got.earlier_ok != want_ok
        if not mismatch and want_ok:
            mismatch = got.result.has_capacity != want.has_capacity or (
                want.has_capacity
                and (
                    got.result.driver_node != want.driver_node
                    or got.result.executor_nodes != want.executor_nodes
                )
            )
        if mismatch:
            bad += 1
            report(name, got, want_ok, want)
    return bad, ran


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=150)
    ap.add_argument("--seed", type=int, default=987654)
    ap.add_argument("--min-nodes", type=int, default=3)
    ap.add_argument("--max-nodes", type=int, default=700)
    ap.add_argument(
        "--queue-max-nodes", type=int, default=120,
        help="node cap for the (slower) FIFO-queue differential section",
    )
    args = ap.parse_args()

    rng = random.Random(args.seed)
    sorter = NodeSorter()
    mismatches = 0
    comparisons = 0
    t0 = time.time()
    for trial in range(args.trials):
        n_nodes = rng.randint(args.min_nodes, args.max_nodes)
        metadata = random_cluster(rng, n_nodes)
        driver_order, executor_order = sorter.potential_nodes(metadata, list(metadata))
        driver_res, executor_res, count = random_gang(rng, n_nodes)
        for name, device_fn, oracle_fn in PAIRS:
            got = device_fn(
                driver_res, executor_res, count, driver_order, executor_order, metadata
            )
            want = oracle_fn(
                driver_res, executor_res, count, driver_order, executor_order, metadata
            )
            comparisons += 1
            eff_mismatch = got.has_capacity and (
                {
                    n: (e.cpu, e.memory, e.gpu)
                    for n, e in got.packing_efficiencies.items()
                }
                != {
                    n: (e.cpu, e.memory, e.gpu)
                    for n, e in want.packing_efficiencies.items()
                }
            )
            if (
                got.has_capacity != want.has_capacity
                or got.driver_node != want.driver_node
                or got.executor_nodes != want.executor_nodes
                or eff_mismatch
            ):
                mismatches += 1
                print(
                    f"MISMATCH trial={trial} policy={name} nodes={n_nodes} "
                    f"count={count}\n  device: {got.has_capacity} "
                    f"{got.driver_node} {got.executor_nodes[:8]}...\n"
                    f"  oracle: {want.has_capacity} {want.driver_node} "
                    f"{want.executor_nodes[:8]}...",
                    file=sys.stderr,
                )
        if n_nodes <= args.queue_max_nodes:

            def report(name, got, want_ok, want):
                print(
                    f"QUEUE MISMATCH trial={trial} policy={name} nodes={n_nodes}\n"
                    f"  device: earlier_ok={got.earlier_ok} result={got.result}\n"
                    f"  oracle: earlier_ok={want_ok} result={want}",
                    file=sys.stderr,
                )

            bad, ran = queue_fuzz(rng, metadata, driver_order, executor_order, report)
            mismatches += bad
            comparisons += ran
        if (trial + 1) % 25 == 0:
            print(
                f"# {trial + 1}/{args.trials} trials, {comparisons} comparisons, "
                f"{mismatches} mismatches, {time.time() - t0:.0f}s",
                file=sys.stderr,
            )
    print(
        f"parity fuzz: {comparisons} comparisons over {args.trials} trials, "
        f"{mismatches} mismatches"
    )
    return 1 if mismatches else 0


if __name__ == "__main__":
    sys.exit(main())
