"""Bridge between the scheduler's SparkBinPackFunction interface and the
JAX batch solver: marshals snapshots to tensors, runs the jitted kernel,
and decodes device results into the reference's exact placement lists.

Safety net: any problem that can't be represented exactly in scaled
int32 (tensorize.scale_problem.ok == False) falls back to the host
oracle, so `binpack: tpu-batch` can never produce a wrong decision from
numeric representation.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import compat
from ..types.resources import NodeGroupSchedulingMetadata, Resources
from . import packers
from .efficiency import compute_packing_efficiencies
from .packers import PackingResult, empty_packing_result
from .registry import Binpacker, TPU_BATCH
from .tensorize import (
    ClusterTensor,
    ScaledProblem,
    scale_problem,
    tensorize_apps,
    tensorize_cluster,
)

logger = logging.getLogger(__name__)


def evenly_counts(cap: np.ndarray, k: int) -> np.ndarray:
    """Exact distribute-evenly per-node counts from per-node capacities
    (distribute_evenly.go:34-73): t complete round-robin sweeps plus a
    partial sweep over the first r capacity-remaining nodes in priority
    order."""
    cap = cap.astype(np.int64)
    if k <= 0:
        return np.zeros_like(cap)
    total = int(cap.sum())
    assert total >= k, "evenly_counts called on infeasible problem"

    # S(t) = Σ min(cap, t) is monotone; find t_full = max{t : S(t) ≤ k}
    lo, hi = 0, int(cap.max())
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if int(np.minimum(cap, mid).sum()) <= k:
            lo = mid
        else:
            hi = mid - 1
    t_full = lo
    counts = np.minimum(cap, t_full)
    r = k - int(counts.sum())
    if r > 0:
        open_nodes = np.flatnonzero(cap > t_full)[:r]
        counts[open_nodes] += 1
    return counts


def build_reserved(
    names: List[str],
    counts: np.ndarray,
    driver_node: str,
    driver_resources: Resources,
    executor_resources: Resources,
) -> dict:
    """Per-node reserved map for efficiency computation, identical to the
    oracle's mutation of `reserved` (driver + count x executor per node),
    in O(#hosting-nodes) exact arithmetic."""
    from ..utils.quantity import Quantity

    reserved = {driver_node: driver_resources}
    counts = np.asarray(counts)
    hosts = np.flatnonzero(counts > 0)
    for i, c in zip(hosts.tolist(), counts[hosts].tolist()):
        total = Resources(
            Quantity(executor_resources.cpu.exact * c),
            Quantity(executor_resources.memory.exact * c),
            Quantity(executor_resources.nvidia_gpu.exact * c),
        )
        reserved[names[i]] = reserved.get(names[i], Resources.zero()).add(total)
    return reserved


def min_frag_unclamped_caps(
    avail: np.ndarray, exec_row: np.ndarray, exec_ok: np.ndarray, driver_idx: int,
    driver_row: np.ndarray,
) -> np.ndarray:
    """Exact UNCLAMPED per-node capacities (int64) for the min-frag
    decode, from scaled integer availability rows with the driver
    subtracted on its node (capacity.go:36-75; negative dims are 0 even
    under a zero requirement — the reserved>available short-circuit)."""
    avail = avail.astype(np.int64).copy()
    avail[driver_idx] -= driver_row.astype(np.int64)
    return np.where(exec_ok, unclamped_caps(avail, exec_row), 0)


def unclamped_caps(avail: np.ndarray, exec_row: np.ndarray) -> np.ndarray:
    """``min_frag_unclamped_caps`` of rows that already show every
    subtraction, for every row; ``exec_row`` one executor [3] or one per
    row [n, 3]."""
    avail = avail.astype(np.int64)
    exec_row = np.broadcast_to(exec_row.astype(np.int64), avail.shape)
    per_dim = np.where(
        exec_row == 0,
        np.where(avail >= 0, np.int64(2**62), np.int64(0)),
        np.floor_divide(avail, np.maximum(exec_row, 1)),
    )
    return np.clip(per_dim.min(axis=1), 0, None)


def minimal_fragmentation_rows(cap: np.ndarray, k: int) -> Optional[np.ndarray]:
    """Minimal-fragmentation placement on arrays: the hosting rows of
    ``cap`` (per-node integer capacities in priority order, unclamped),
    one entry per executor in the order the reference emits them
    (minimal_fragmentation.go:59-137), or None where ``k`` do not fit.

    The served path's decode.  The same algorithm as the host oracle's
    ``packers.minimal_fragmentation_from_capacities``, which walks a
    sorted list of one ``NodeAndExecutorCapacity`` per node: here the
    sort is one stable argsort of the positive capacities (ties keep
    priority order, as the oracle's ``sorted``), "first node that fits
    what is left" a searchsorted, ``remaining`` a prefix length, and the
    Python loop turns once per drained capacity class (at most ``k``
    times), never per node.  tests/test_minfrag_rows.py holds the two
    equal, order included.  Capacities reach 2**62 (a zero requirement):
    int64 throughout, and such a node always fits what is left."""
    if k == 0:
        return np.zeros(0, dtype=np.int64)
    cap = np.asarray(cap, dtype=np.int64)
    positive = np.flatnonzero(cap > 0)
    if positive.size == 0:
        return None
    rows = positive[np.argsort(cap[positive], kind="stable")]
    caps = cap[rows]
    max_capacity = int(caps[-1])
    if k < max_capacity:
        # try a subset that excludes the 'emptiest' nodes
        below_target = int(np.searchsorted(caps, (k + max_capacity) // 2, side="left"))
        placed = _drain_sorted(rows, caps, below_target, k)
        if placed is not None:
            return placed
    return _drain_sorted(rows, caps, len(rows), k)


def _drain_sorted(
    rows: np.ndarray, caps: np.ndarray, length: int, k: int
) -> Optional[np.ndarray]:
    """minimal_fragmentation.go:96-137 over the first ``length`` entries
    of the ascending ``caps`` (``rows`` their nodes): the oracle's
    ``remaining`` is ``caps[:length]``, once more followed by what a
    drain left of its capacity class."""
    hosts, on_each = [], []
    while length > 0:
        # first node that can fit everything that's left
        position = int(np.searchsorted(caps[:length], k, side="left"))
        if position < length:
            hosts.append(rows[position : position + 1])
            on_each.append(k)
            break
        # drain max-capacity nodes, in priority order
        max_capacity = int(caps[length - 1])
        first_max = int(np.searchsorted(caps[:length], max_capacity, side="left"))
        drained = min(k // max_capacity, length - first_max)
        hosts.append(rows[first_max : first_max + drained])
        on_each.extend([max_capacity] * drained)
        k -= drained * max_capacity
        if k == 0:
            break
        if first_max + drained < length:
            # the class outlasted the drain: what is left (< its capacity)
            # fits its next node, unless a smaller node fits it first
            position = int(np.searchsorted(caps[:first_max], k, side="left"))
            if position == first_max:
                position += drained
            hosts.append(rows[position : position + 1])
            on_each.append(k)
            break
        length = first_max
    else:
        return None
    return np.repeat(np.concatenate(hosts), on_each)


def minimal_fragmentation_order(
    cap: np.ndarray, rows: np.ndarray, groups: np.ndarray
) -> np.ndarray:
    """The positions of ``rows``, the hosts of min-frag placements already
    made (``cap`` their capacities, the driver subtracted on its node;
    ``groups`` tells disjoint placements apart), in the order
    ``minimal_fragmentation_rows`` emits them, read from the hosts alone
    with no sort of the other nodes.  The drain takes capacity classes
    from the largest down, each in priority order, and the node that
    takes what is left comes last: its capacity is below every drained
    class's, or it follows them in their own class.  So each placement's
    hosts come by descending capacity, then row."""
    return np.lexsort((rows, -cap, groups))


def tightly_rows(counts: np.ndarray) -> np.ndarray:
    """The hosting rows of per-node ``counts``, one entry per executor,
    in priority order (tightly-pack's emission order)."""
    counts = np.asarray(counts)
    hosts = np.flatnonzero(counts > 0)
    return np.repeat(hosts, counts[hosts])


def evenly_rows(counts: np.ndarray) -> np.ndarray:
    """Round-robin visit order: sweep t emits every node with count > t,
    in priority order (matches the Go loop's append order)."""
    counts = np.asarray(counts).astype(np.int64)
    idx = np.flatnonzero(counts)
    if idx.size == 0:
        return idx
    # (sweep, priority position) pairs for each emitted executor
    sweeps = np.concatenate([np.arange(counts[i]) for i in idx])
    positions = np.repeat(idx, counts[idx])
    return positions[np.lexsort((positions, sweeps))]


def names_of_rows(names: List[str], rows: np.ndarray) -> Tuple[List[str], int]:
    """(the placement list of ``rows``, the distinct nodes in it).  The
    one place a decode turns rows into Python objects: ``names`` is
    indexed once per hosting node, never walked."""
    hosts, slot = np.unique(rows, return_inverse=True)
    host_names = [names[i] for i in hosts.tolist()]
    return [host_names[j] for j in slot.tolist()], len(host_names)


def min_frag_zone_decode(
    avail_rows: np.ndarray,
    exec_row: np.ndarray,
    zone_exec_ok: np.ndarray,
    d_idx: int,
    driver_row: np.ndarray,
    k: int,
    strict_reference_parity: bool,
):
    """Per-zone minimal-fragmentation decode on arrays, shared by the
    single-AZ single-app adapter and the FIFO solver's zone choice:
    exact placements on device-equal capacities (``minimal_fragmentation_rows``:
    the hosting rows, one per executor in emission order; by name through
    ``names_of_rows``), the true per-node counts (for the usage carry),
    and the efficiency-side counts: zeroed under strict parity, where
    the reference's no-write-back quirk makes the zone choice see only
    the driver's reservation.
    Returns (rows, counts, eff_counts) or None (infeasible)."""
    zcap = min_frag_unclamped_caps(avail_rows, exec_row, zone_exec_ok, d_idx, driver_row)
    rows = minimal_fragmentation_rows(zcap, k)
    if rows is None:
        return None
    counts = np.bincount(rows, minlength=len(zcap))
    eff_counts = np.zeros_like(counts) if strict_reference_parity else counts
    return rows, counts, eff_counts


def counts_to_tightly_list(names: List[str], counts: np.ndarray) -> List[str]:
    return names_of_rows(names, tightly_rows(counts))[0]


def counts_to_evenly_list(names: List[str], counts: np.ndarray) -> List[str]:
    return names_of_rows(names, evenly_rows(counts))[0]


class TpuBatchBinpacker:
    """A drop-in SparkBinPackFunction backed by the JAX solver.

    assignment_policy: 'tightly-pack' or 'distribute-evenly' — controls
    the executor placement list (feasibility and driver choice are
    policy-invariant, see batch_solver docstring).
    """

    def __init__(
        self,
        assignment_policy: str = "tightly-pack",
        verify_against_oracle: bool = False,
        strict_reference_parity: bool = compat.DEFAULT_STRICT,
    ):
        self.assignment_policy = assignment_policy
        self.verify_against_oracle = verify_against_oracle
        self.strict_reference_parity = strict_reference_parity

    def __call__(
        self,
        driver_resources: Resources,
        executor_resources: Resources,
        executor_count: int,
        driver_node_priority_order: Sequence[str],
        executor_node_priority_order: Sequence[str],
        metadata: NodeGroupSchedulingMetadata,
    ) -> PackingResult:
        from .sparkapp import app_resources_of  # lazy tiny helper

        cluster = tensorize_cluster(
            metadata, driver_node_priority_order, executor_node_priority_order
        )
        apps = tensorize_apps(
            [app_resources_of(driver_resources, executor_resources, executor_count)]
        )
        problem = scale_problem(cluster, apps)
        oracle = {
            "tightly-pack": packers.tightly_pack,
            "minimal-fragmentation": packers.make_minimal_fragmentation_pack(
                self.strict_reference_parity
            ),
        }.get(self.assignment_policy, packers.distribute_evenly)
        if not problem.ok:
            logger.warning("snapshot not exactly tensorizable; using host oracle")
            return oracle(
                driver_resources,
                executor_resources,
                executor_count,
                driver_node_priority_order,
                executor_node_priority_order,
                metadata,
            )

        result = self._solve_and_decode(cluster, problem, executor_count, metadata)

        if self.verify_against_oracle:
            expected = oracle(
                driver_resources,
                executor_resources,
                executor_count,
                driver_node_priority_order,
                executor_node_priority_order,
                metadata,
            )
            if (
                expected.has_capacity != result.has_capacity
                or expected.driver_node != result.driver_node
                or expected.executor_nodes != result.executor_nodes
            ):
                logger.error(
                    "tpu-batch solver disagreed with oracle (solver %s@%s vs oracle %s@%s); "
                    "using oracle",
                    result.has_capacity,
                    result.driver_node,
                    expected.has_capacity,
                    expected.driver_node,
                )
                return expected
        return result

    def _solve_and_decode(
        self,
        cluster: ClusterTensor,
        problem: ScaledProblem,
        executor_count: int,
        metadata: NodeGroupSchedulingMetadata,
    ) -> PackingResult:
        import jax.numpy as jnp

        from .batch_solver import solve_single

        solve = solve_single(
            jnp.asarray(problem.avail),
            jnp.asarray(problem.driver_rank),
            jnp.asarray(problem.exec_ok),
            jnp.asarray(problem.driver[0]),
            jnp.asarray(problem.executor[0]),
            jnp.asarray(problem.count[0]),
        )
        feasible = bool(solve.feasible)
        if not feasible:
            return empty_packing_result()

        driver_idx = int(solve.driver_idx)
        names = cluster.node_names
        driver_node = names[driver_idx]

        if self.assignment_policy == "tightly-pack":
            counts = np.asarray(solve.exec_counts)[: len(names)]
            executor_nodes = counts_to_tightly_list(names, counts)
        elif self.assignment_policy == "minimal-fragmentation":
            # min-frag's (k+max)/2 subset threshold needs UNCLAMPED
            # capacities (the device clamps to k for overflow safety):
            # recompute exactly from the scaled integer rows, with the
            # driver subtracted on its node
            cap = min_frag_unclamped_caps(
                problem.avail[: len(names)],
                problem.executor[0],
                np.asarray(problem.exec_ok[: len(names)]),
                driver_idx,
                problem.driver[0],
            )
            rows = minimal_fragmentation_rows(cap, executor_count)
            if rows is None:
                return empty_packing_result()
            executor_nodes = names_of_rows(names, rows)[0]
            # the reference's min-frag does NOT fold executor placements
            # into reserved for efficiency (packers.minimal_fragmentation
            # QUIRK, switchable) — under strict parity efficiency
            # accounting sees only the driver; corrected mode folds the
            # placements in, mirroring the oracle's write-back
            counts = np.zeros(len(names), dtype=np.int64)
            if not self.strict_reference_parity:
                counts = np.bincount(rows, minlength=len(names))
        else:
            cap = np.asarray(solve.exec_capacity)[: len(names)]
            counts = evenly_counts(cap, executor_count)
            executor_nodes = counts_to_evenly_list(names, counts)

        # efficiencies as the reference computes them: driver + per-node
        # executor reservations folded into `reserved`
        reserved = {driver_node: Resources.zero()}
        # build reserved the same way the oracle mutates it
        dr = metadata[driver_node]  # noqa: F841 (existence check)
        reserved[driver_node] = self._scale_back(problem, problem.driver[0])
        hosts = np.flatnonzero(counts > 0)
        for i, c in zip(hosts.tolist(), counts[hosts].tolist()):
            add = self._scale_back(problem, problem.executor[0] * c)
            reserved[names[i]] = reserved.get(names[i], Resources.zero()).add(add)
        return PackingResult(
            driver_node=driver_node,
            executor_nodes=executor_nodes,
            has_capacity=True,
            packing_efficiencies=compute_packing_efficiencies(metadata, reserved),
        )

    @staticmethod
    def _scale_back(problem: ScaledProblem, row: np.ndarray) -> Resources:
        from fractions import Fraction

        from ..utils.quantity import Quantity

        cpu_m, mem_b, gpu_m = (
            int(row[0]) * int(problem.scale[0]),
            int(row[1]) * int(problem.scale[1]),
            int(row[2]) * int(problem.scale[2]),
        )
        return Resources(
            Quantity(Fraction(cpu_m, 1000)),
            Quantity(mem_b),
            Quantity(Fraction(gpu_m, 1000)),
        )


def tpu_batch_binpacker() -> Binpacker:
    from .fifo_solver import TpuFifoSolver

    return Binpacker(
        name=TPU_BATCH,
        binpack_func=TpuBatchBinpacker(assignment_policy="tightly-pack"),
        is_single_az=False,
        queue_solver=TpuFifoSolver(assignment_policy="tightly-pack"),
    )


def tpu_batch_evenly_binpacker() -> Binpacker:
    from .fifo_solver import TpuFifoSolver

    return Binpacker(
        name="tpu-batch-distribute-evenly",
        binpack_func=TpuBatchBinpacker(assignment_policy="distribute-evenly"),
        is_single_az=False,
        queue_solver=TpuFifoSolver(assignment_policy="distribute-evenly"),
    )


def tpu_batch_min_frag_binpacker(
    strict_reference_parity: bool = compat.DEFAULT_STRICT,
) -> Binpacker:
    from .fifo_solver import TpuFifoSolver

    return Binpacker(
        name="tpu-batch-minimal-fragmentation",
        binpack_func=TpuBatchBinpacker(
            assignment_policy="minimal-fragmentation",
            strict_reference_parity=strict_reference_parity,
        ),
        is_single_az=False,
        queue_solver=TpuFifoSolver(
            assignment_policy="minimal-fragmentation",
            strict_reference_parity=strict_reference_parity,
        ),
    )


def candidate_zone_masks(driver_order, executor_order, metadata, names, nb):
    """Zone ordering + per-zone node masks shared by the single-AZ gang
    and FIFO device paths (single_az.go:30-45 first-appearance order;
    zones without executor candidates are dropped)."""
    driver_zones_in_order, _ = packers.group_nodes_by_zone(driver_order, metadata)
    _, executor_by_zone = packers.group_nodes_by_zone(executor_order, metadata)
    candidate_zones = [z for z in driver_zones_in_order if z in executor_by_zone]
    zone_of = {name: metadata[name].zone_label for name in names}
    zone_masks = np.zeros((max(len(candidate_zones), 1), nb), dtype=bool)
    for zi, zone in enumerate(candidate_zones):
        for i, name in enumerate(names):
            zone_masks[zi, i] = zone_of[name] == zone
    return candidate_zones, zone_masks


class TpuSingleAzBinpacker:
    """Single-AZ combinator on device (single_az.go:23-55): all zones
    solved in one vmapped call, zone chosen on host with the oracle's
    exact efficiency math (_choose_best_result).  az_aware=True adds the
    cross-zone fallback (az_aware_pack_tightly.go:27-38).

    inner_policy selects the per-zone distribution: "tightly-pack"
    (device counts) or "minimal-fragmentation"
    (single_az_minimal_fragmentation semantics: zone feasibility and
    driver choice are policy-invariant, so the vmapped zone solves are
    shared; placements come from ``minimal_fragmentation_rows`` on
    device-equal capacities, and under strict parity the reference's
    no-efficiency-write-back quirk makes the zone choice see only the
    driver's reservation)."""

    def __init__(
        self,
        az_aware: bool = False,
        inner_policy: str = "tightly-pack",
        strict_reference_parity: bool = compat.DEFAULT_STRICT,
    ):
        self.az_aware = az_aware
        self.inner_policy = inner_policy
        self.strict_reference_parity = strict_reference_parity

    def __call__(
        self,
        driver_resources: Resources,
        executor_resources: Resources,
        executor_count: int,
        driver_node_priority_order: Sequence[str],
        executor_node_priority_order: Sequence[str],
        metadata: NodeGroupSchedulingMetadata,
    ) -> PackingResult:
        import jax.numpy as jnp

        from .batch_solver import solve_single, solve_zones_jit
        from .sparkapp import app_resources_of

        cluster = tensorize_cluster(
            metadata, driver_node_priority_order, executor_node_priority_order
        )
        apps = tensorize_apps(
            [app_resources_of(driver_resources, executor_resources, executor_count)]
        )
        problem = scale_problem(cluster, apps)
        if self.inner_policy == "minimal-fragmentation":
            oracle = packers.make_single_az_minimal_fragmentation(
                self.strict_reference_parity
            )
        else:
            oracle = (
                packers.az_aware_tightly_pack
                if self.az_aware
                else packers.single_az_tightly_pack
            )
        if not problem.ok:
            logger.warning("snapshot not exactly tensorizable; using host oracle")
            return oracle(
                driver_resources,
                executor_resources,
                executor_count,
                driver_node_priority_order,
                executor_node_priority_order,
                metadata,
            )

        names = cluster.node_names
        n = len(names)
        nb = problem.avail.shape[0]
        candidate_zones, zone_masks = candidate_zone_masks(
            driver_node_priority_order, executor_node_priority_order, metadata, names, nb
        )

        solves = solve_zones_jit(
            jnp.asarray(problem.avail),
            jnp.asarray(problem.driver_rank),
            jnp.asarray(problem.exec_ok),
            jnp.asarray(zone_masks),
            jnp.asarray(problem.driver[0]),
            jnp.asarray(problem.executor[0]),
            jnp.asarray(problem.count[0]),
        )
        feasible = np.asarray(solves.feasible)
        driver_idx = np.asarray(solves.driver_idx)
        counts = np.asarray(solves.exec_counts)

        results = []
        exec_ok_arr = np.asarray(problem.exec_ok[:n])
        for zi, zone in enumerate(candidate_zones):
            if not feasible[zi]:
                continue
            d_idx = int(driver_idx[zi])
            driver_node = names[d_idx]
            if self.inner_policy == "minimal-fragmentation":
                decoded = min_frag_zone_decode(
                    problem.avail[:n],
                    problem.executor[0],
                    exec_ok_arr & zone_masks[zi][:n],
                    d_idx,
                    problem.driver[0],
                    executor_count,
                    self.strict_reference_parity,
                )
                if decoded is None:  # unreachable: zone feasibility proven
                    continue
                rows, _counts, eff_counts = decoded
                executor_nodes = names_of_rows(names, rows)[0]
            else:
                zone_counts = counts[zi][:n]
                executor_nodes = counts_to_tightly_list(names, zone_counts)
                eff_counts = zone_counts
            results.append(
                PackingResult(
                    driver_node=driver_node,
                    executor_nodes=executor_nodes,
                    has_capacity=True,
                    packing_efficiencies=compute_packing_efficiencies(
                        metadata,
                        build_reserved(
                            names, eff_counts, driver_node, driver_resources, executor_resources
                        ),
                    ),
                )
            )

        if results:
            best = packers._choose_best_result(metadata, results)
            # _choose_best_result can return the empty result when every
            # candidate has zero avg efficiency (the documented quirk) —
            # az-aware must then still take the cross-zone fallback, like
            # az_aware_pack_tightly.go:34-37's has_capacity check
            if best.has_capacity or not self.az_aware:
                return best
        if self.az_aware:
            # cross-zone fallback: plain tightly-pack on device
            return TpuBatchBinpacker(assignment_policy="tightly-pack")(
                driver_resources,
                executor_resources,
                executor_count,
                driver_node_priority_order,
                executor_node_priority_order,
                metadata,
            )
        return empty_packing_result()


def tpu_batch_single_az_binpacker() -> Binpacker:
    from .fifo_solver import TpuSingleAzFifoSolver

    return Binpacker(
        name="tpu-batch-single-az",
        binpack_func=TpuSingleAzBinpacker(az_aware=False),
        is_single_az=True,
        queue_solver=TpuSingleAzFifoSolver(az_aware=False),
    )


def tpu_batch_single_az_min_frag_binpacker(
    strict_reference_parity: bool = compat.DEFAULT_STRICT,
) -> Binpacker:
    from .fifo_solver import TpuSingleAzFifoSolver

    return Binpacker(
        name="tpu-batch-single-az-minimal-fragmentation",
        binpack_func=TpuSingleAzBinpacker(
            az_aware=False,
            inner_policy="minimal-fragmentation",
            strict_reference_parity=strict_reference_parity,
        ),
        is_single_az=True,
        queue_solver=TpuSingleAzFifoSolver(
            az_aware=False,
            inner_policy="minimal-fragmentation",
            strict_reference_parity=strict_reference_parity,
        ),
    )


def tpu_batch_az_aware_binpacker() -> Binpacker:
    from .fifo_solver import TpuSingleAzFifoSolver

    return Binpacker(
        name="tpu-batch-az-aware",
        binpack_func=TpuSingleAzBinpacker(az_aware=True),
        is_single_az=True,
        queue_solver=TpuSingleAzFifoSolver(az_aware=True),
    )
