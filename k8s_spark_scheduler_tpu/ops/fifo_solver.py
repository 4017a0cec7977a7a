"""FIFO queue solver: the extender's earlier-drivers pass on device.

Replaces the host loop of resource.go:224-262 (binpack every earlier
driver, subtract its usage, fail if an enforced driver doesn't fit) with
ONE whole-queue device solve (batch_solver.solve_queue), then packs the
current driver against the resulting availability.  Decisions are
bit-identical to the oracle loop (tests/test_fifo_solver.py); problems
that can't be exactly tensorized fall back to the host path.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from .. import compat
from ..tracing import spans as tracing
from ..tracing.profiling import default_profiler
from ..types.resources import NodeGroupSchedulingMetadata
from .batch_adapter import (
    build_reserved,
    candidate_zone_masks,
    counts_to_evenly_list,
    counts_to_tightly_list,
    evenly_counts,
    min_frag_unclamped_caps,
    min_frag_zone_decode,
    minimal_fragmentation_assignment,
)
from .efficiency import compute_packing_efficiencies
from .packers import PackingResult, empty_packing_result
from .sparkapp import AppDemand
from .tensorize import _resources_to_base as _res_rows
from .tensorize import scale_problem, tensorize_apps, tensorize_cluster

logger = logging.getLogger(__name__)


def _ceil_div(v: int, d: int) -> int:
    return -((-v) // d)


def _upload(*arrays):
    """Host arrays handed to the device under a ``device.upload`` span,
    which times what the host pays for the ``jnp.asarray`` calls; where
    the transfers land is the profiler's to show (nothing blocks here)."""
    import jax.numpy as jnp

    with tracing.child_span(
        "device.upload",
        {"arrays": len(arrays), "bytes": sum(a.nbytes for a in arrays)},
    ):
        return tuple(jnp.asarray(a) for a in arrays)


def _readback(value, to=np.asarray):
    """A device value brought to the host under a ``device.readback``
    span (``to`` converts: ``np.asarray``, ``bool``, ``int``)."""
    with tracing.child_span("device.readback"):
        return to(value)


def _on_host(value, to=np.asarray):
    """``_readback`` for the native lanes, whose values are host arrays
    already: no transfer, no span."""
    return to(value)


def _pallas_selected(backend: str) -> bool:
    """Shared backend choice: 'pallas' forces the kernel, 'auto' uses it
    exactly when the default backend is a TPU."""
    if backend == "pallas":
        return True
    if backend == "auto":
        import jax

        return jax.default_backend() == "tpu"
    return False


def _native_selected(backend: str) -> bool:
    """Host lane choice: 'native' forces the C++ queue solver; 'auto'
    uses it exactly when no accelerator backs jax (CPU deployments —
    the XLA scan costs ~280ms/queue at 10k×1k on one host core vs ~35ms
    native, decision-identical per tests/test_native_fifo.py).  A FORCED
    'native' with no working toolchain raises — a silent 8× degrade to
    the XLA scan must never hide behind an explicit backend choice
    (mirrors how a forced 'pallas' fails loudly off-TPU)."""
    if backend not in ("native", "auto"):
        return False
    from ..native.fifo import native_fifo_available

    if backend == "native":
        if not native_fifo_available():
            raise RuntimeError(
                "backend='native' was forced but the C++ fifo solver could "
                "not be built/loaded (see native.fifo build log); use "
                "backend='auto' for graceful degradation"
            )
        return True
    import jax

    if jax.default_backend() != "cpu":
        return False
    return native_fifo_available()


class LazyEfficiencies(dict):
    """Per-node PackingEfficiency mapping backed by vectorized float64
    columns.  The zone choice reads only the placement nodes' entries
    and the metrics path needs only the average of per-node maxes, so
    building 10k dataclasses per Filter request (the dominant host cost
    of the driver fast lane) is deferred: [] / .get materialize single
    entries; values()/items() materialize everything (only the exact
    Quantity-parity consumers do that)."""

    def __init__(self, names, cpu, mem, gpu):
        super().__init__()
        self._names = list(names)
        # name → column dict built on first materialization: most
        # requests only read the scalar average (seq_max_avg), and a
        # 10k-entry dict per Filter is measurable on the request path
        self._col_idx_lazy = None
        self._cpu = cpu
        self._mem = mem
        self._gpu = gpu

    @property
    def _col_idx(self):
        if self._col_idx_lazy is None:
            self._col_idx_lazy = dict(
                zip(self._names, range(len(self._names)))
            )
        return self._col_idx_lazy

    def __missing__(self, name):
        from .efficiency import PackingEfficiency

        i = self._col_idx[name]
        e = PackingEfficiency(
            node_name=name,
            cpu=float(self._cpu[i]),
            memory=float(self._mem[i]),
            gpu=float(self._gpu[i]),
        )
        self[name] = e
        return e

    def get(self, name, default=None):
        try:
            return self[name]
        except KeyError:
            return default

    # the full dict read protocol must reflect ALL nodes (not just the
    # materialized subset), and iteration must stay in node order so
    # order-sensitive float accumulations (compute_avg_packing_
    # efficiency) see exactly the sequence the eager dict produced
    def __iter__(self):
        return iter(self._names)

    def __len__(self):
        return len(self._names)

    def __contains__(self, name):
        return name in self._col_idx

    def keys(self):
        return list(self._names)

    def values(self):
        return [self[n] for n in self._names]

    def items(self):
        return [(n, self[n]) for n in self._names]

    def seq_max_avg(self) -> float:
        """sum(max(gpu, cpu, memory)) / n for the extender's
        packing-efficiency gauge, Neumaier-compensated: the gauge's
        cross-lane bit-equality contract (test_extender_efficiency_
        gauge_matches_host_lane) sums the same per-node maxes in
        different orders on different lanes, and compensation makes the
        rounded result order-robust — exact whenever the true sum is
        representable, which plain left-to-right addition is not (the
        host lane's uncompensated loop can land an ulp off in ITS order;
        compensation recovers the representable value either way)."""
        if not self._names:
            return 0.0
        maxes = np.maximum(np.maximum(self._cpu, self._mem), self._gpu)
        try:
            from ..native.fifo import neumaier_sum_f64_native

            total = neumaier_sum_f64_native(maxes)
        except Exception:
            total = None
        if total is None:
            # same algorithm at Python speed (native lane unavailable)
            s = 0.0
            c = 0.0
            for x in maxes.tolist():
                t = s + x
                if abs(s) >= abs(x):
                    c += (s - t) + x
                else:
                    c += (x - t) + s
                s = t
            total = s + c
        return total / float(len(self._names))


def efficiencies_from_rows(names, sched_rows, avail_rows, reserved_rows):
    """compute_packing_efficiencies from exact base-unit int rows —
    bit-identical floats to the Quantity path (efficiency.go:80-105):
    per-dim reserved = schedulable − available + newly_reserved, then
    Quantity.value() semantics (ceil to canonical units) and ratio —
    computed as vectorized int64/float64 columns (identical IEEE results
    to the scalar loop) behind a lazily-materialized mapping."""
    n = len(names)
    s = np.asarray(sched_rows)[:n].astype(np.int64)
    r = (
        s
        - np.asarray(avail_rows)[:n].astype(np.int64)
        + np.asarray(reserved_rows)[:n].astype(np.int64)
    )
    s_cpu = _ceil_div(s[:, 0], 1000)
    s_gpu = _ceil_div(s[:, 2], 1000)
    r_cpu = _ceil_div(r[:, 0], 1000)
    r_gpu = _ceil_div(r[:, 2], 1000)
    # Go divides by normalize(schedulable)=1 when schedulable is 0
    cpu = r_cpu / np.maximum(s_cpu, 1)
    mem = r[:, 1] / np.maximum(s[:, 1], 1)
    gpu = np.where(s_gpu != 0, r_gpu / np.maximum(s_gpu, 1), 0.0)
    return LazyEfficiencies(names, cpu, mem, gpu)


def _patch_available(metadata, names, avail_rows):
    """Metadata view whose candidate-node availability is replaced by the
    post-queue scan carry (exact base-unit ints → exact Quantities):
    host-lane parity for efficiency metrics, which the reference computes
    against the metadata mutated by fitEarlierDrivers
    (resource.go:255-259)."""
    from dataclasses import replace
    from fractions import Fraction

    from ..types.resources import Resources
    from ..utils.quantity import Quantity

    patched = dict(metadata)
    for i, name in enumerate(names):
        patched[name] = replace(
            metadata[name],
            available=Resources(
                Quantity(Fraction(int(avail_rows[i, 0]), 1000)),
                Quantity(int(avail_rows[i, 1])),
                Quantity(Fraction(int(avail_rows[i, 2]), 1000)),
            ),
        )
    return patched


@dataclass
class FifoOutcome:
    """Result of the combined earlier-drivers + current-driver solve."""

    supported: bool  # False → caller must use the host oracle path
    earlier_ok: bool = True  # False → an enforced earlier driver doesn't fit
    result: Optional[PackingResult] = None  # current driver's packing


class TpuFifoSolver:
    """One device round for the whole FIFO queue + the current driver.

    backend: "auto" (pallas kernel on TPU, native C++ solver on CPU
    hosts, XLA scan otherwise), "xla", "pallas", or "native".  The
    pallas queue kernel (ops/pallas_queue) keeps the availability carry
    VMEM-resident across the whole queue — it is the program the
    headline bench measures, so production Filter requests pay exactly
    the benched cost (queue pass + one O(N) decode solve for the
    current driver's placements).  The native lane
    (native/fifo_solver.cpp) serves accelerator-less deployments with
    the same decisions at ~8× the XLA-scan speed for every policy
    (tightly/evenly via fifo_solve_queue, minimal-fragmentation via
    fifo_solve_queue_minfrag)."""

    def __init__(
        self,
        assignment_policy: str = "tightly-pack",
        backend: str = "auto",
        strict_reference_parity: bool = compat.DEFAULT_STRICT,
    ):
        self.assignment_policy = assignment_policy
        self.backend = backend
        # min-frag only: whether the reference's no-efficiency-write-back
        # quirk applies to the current driver's reported efficiencies
        self.strict_reference_parity = strict_reference_parity
        # which lane served the last queue pass — one of "native",
        # "native-minfrag", "pallas", "pallas-minfrag", "xla",
        # "minfrag-xla"; None = no queue pass ran — observable for tests
        # and the tpu.fastpath lane counters
        self.last_queue_lane: Optional[str] = None
        # (ids, strong refs, AppTensor) of the last earlier-apps list:
        # consecutive Filters tensorize the same pending queue, and the
        # per-request Python loop over ~1k apps is measurable.  The
        # cached list holds strong references, so an id can never be
        # reused while the entry lives — id-tuple equality therefore
        # proves the SAME AppDemand objects (stable per pod version via
        # sparkpods._cached_entry), making the hit exact.
        self._earlier_tensor_cache = None
        # decision provenance (provenance/tracker.py): wiring points
        # this at ProvenanceTracker.capture when provenance is enabled;
        # None (the default) keeps solve_tensor capture-free.
        self.capture_sink = None

    def _use_pallas(self) -> bool:
        return _pallas_selected(self.backend)

    def _use_native(self) -> bool:
        return not self._use_pallas() and _native_selected(self.backend)

    def solve(
        self,
        metadata: NodeGroupSchedulingMetadata,
        driver_order: Sequence[str],
        executor_order: Sequence[str],
        earlier_apps: List[AppDemand],
        earlier_skip_allowed: List[bool],
        current_app: AppDemand,
    ) -> FifoOutcome:
        cluster = tensorize_cluster(metadata, driver_order, executor_order)
        return self.solve_tensor(
            cluster, earlier_apps, earlier_skip_allowed, current_app, metadata=metadata
        )

    def _tensorize_with_cache(self, earlier, current_app):
        """AppTensor for earlier + [current]: the earlier block is
        cached by object identity (see _earlier_tensor_cache) and the
        current app's rows are appended."""
        from .tensorize import AppTensor, _app_base_rows

        key = tuple(map(id, earlier))
        cached = self._earlier_tensor_cache
        if cached is not None and cached[0] == key:
            base = cached[2]
        else:
            base = tensorize_apps(earlier)
            self._earlier_tensor_cache = (key, earlier, base)
        drow, erow, exact = _app_base_rows(current_app)
        a = base.driver.shape[0]
        driver = np.empty((a + 1, 3), dtype=np.int64)
        driver[:a] = base.driver
        driver[a] = drow
        executor = np.empty((a + 1, 3), dtype=np.int64)
        executor[:a] = base.executor
        executor[a] = erow
        count = np.empty(a + 1, dtype=np.int64)
        count[:a] = base.count
        count[a] = current_app.min_executor_count
        return AppTensor(
            driver=driver,
            executor=executor,
            count=count,
            valid=np.ones(a + 1, dtype=bool),
            exact=base.exact and exact,
        )

    def feasible_tensor(self, cluster, app: AppDemand) -> Optional[bool]:
        """Feasibility of one app against a prebuilt ClusterTensor with
        no placement decode and no efficiency math — the
        unschedulable-marker's empty-cluster verdict (its scan runs
        every interval over the whole pending backlog, so the full
        solve_tensor cost per pod was pure waste).  Feasibility is
        policy-invariant across tightly/evenly/min-frag (the
        work-conserving drain rule, batch_solver docstring), identical
        to binpack_func's has_capacity.  None = not exactly
        tensorizable (caller uses the host path)."""
        apps = tensorize_apps([app])
        problem = scale_problem(cluster, apps)
        if not problem.ok:
            return None
        if self._use_native():
            from ..native.fifo import solve_app_native

            feas, _, _, _ = solve_app_native(
                problem.avail, problem.driver_rank, problem.exec_ok,
                problem.driver[0], problem.executor[0], int(problem.count[0]),
            )
            return bool(feas)
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
        return bool(solve.feasible)

    def solve_tensor(
        self,
        cluster,
        earlier_apps: List[AppDemand],
        earlier_skip_allowed: List[bool],
        current_app: AppDemand,
        metadata: Optional[NodeGroupSchedulingMetadata] = None,
    ) -> FifoOutcome:
        """Solve from a prebuilt ClusterTensor (the tensor-snapshot fast
        path passes one directly; `metadata` is only used for the
        Quantity-based efficiency computation when provided)."""
        from .batch_solver import solve_queue, solve_queue_min_frag

        with tracing.child_span("fast_path.tensorize_apps"):
            apps = self._tensorize_with_cache(list(earlier_apps), current_app)
        self.last_queue_lane = None
        evenly = self.assignment_policy == "distribute-evenly"
        minfrag = self.assignment_policy == "minimal-fragmentation"
        with tracing.child_span("fast_path.scale_problem"):
            problem = scale_problem(cluster, apps)
            if not problem.ok:
                return FifoOutcome(supported=False)
            if minfrag:
                from .batch_solver import mf_sentinel_safe

                if not mf_sentinel_safe(problem.avail):
                    # a real capacity could collide with the device kernel's
                    # unbounded-capacity sentinel (batch_solver.MF_SENT)
                    return FifoOutcome(supported=False)
        n_earlier = len(earlier_apps)
        # the native C++ lane serves every policy; decisions are
        # differential-tested bit-identical to the device scans
        use_native = self._use_native()

        didx_all = None  # native lanes keep per-position driver indices
        if n_earlier > 0:
            # whole-queue pass over the earlier drivers only.  The
            # fifo_gate span is the request's "earlier drivers fit?"
            # phase; the kernel profiles inside it split the dispatch
            # into jit-compile vs execute time (tracing/profiling.py).
            with tracing.child_span(
                "fifo_gate", {"earlierApps": n_earlier}
            ) as gate_span:
                queue_valid = problem.app_valid.copy()
                queue_valid[n_earlier:] = False
                if use_native and minfrag:
                    from ..native.fifo import solve_queue_min_frag_native

                    self.last_queue_lane = "native-minfrag"
                    with default_profiler.profile(
                        "fifo_queue", lane="native-minfrag", jit=False
                    ):
                        feasible_all, didx_all, avail_after = solve_queue_min_frag_native(
                            problem.avail, problem.driver_rank, problem.exec_ok,
                            problem.driver, problem.executor, problem.count,
                            queue_valid,
                        )
                    feasible = feasible_all[:n_earlier]
                elif use_native:
                    from ..native.fifo import solve_queue_native

                    self.last_queue_lane = "native"
                    with default_profiler.profile(
                        "fifo_queue", lane="native", jit=False
                    ):
                        feasible_all, didx_all, avail_after = solve_queue_native(
                            problem.avail, problem.driver_rank, problem.exec_ok,
                            problem.driver, problem.executor, problem.count,
                            queue_valid, evenly=evenly,
                        )
                    feasible = feasible_all[:n_earlier]
                else:
                    queue_args = _upload(
                        problem.avail,
                        problem.driver_rank,
                        problem.exec_ok,
                        problem.driver,
                        problem.executor,
                        problem.count,
                        queue_valid,
                    )
                    if minfrag and self._use_pallas():
                        from .pallas_queue import pallas_solve_queue_min_frag

                        self.last_queue_lane = "pallas-minfrag"
                        with default_profiler.profile(
                            "fifo_queue", lane="pallas-minfrag",
                            fn=pallas_solve_queue_min_frag,
                        ) as rec:
                            feasible_dev, _, avail_after = pallas_solve_queue_min_frag(
                                *queue_args
                            )
                            rec.sync(avail_after)
                        feasible = _readback(feasible_dev)[:n_earlier]
                    elif minfrag:
                        self.last_queue_lane = "minfrag-xla"
                        with default_profiler.profile(
                            "fifo_queue", lane="minfrag-xla",
                            fn=solve_queue_min_frag,
                        ) as rec:
                            out = solve_queue_min_frag(*queue_args, with_placements=False)
                            rec.sync(out.avail_after)
                        feasible = _readback(out.feasible)[:n_earlier]
                        avail_after = out.avail_after
                    elif self._use_pallas():
                        from .pallas_queue import pallas_solve_queue

                        self.last_queue_lane = "pallas"
                        with default_profiler.profile(
                            "fifo_queue", lane="pallas", fn=pallas_solve_queue
                        ) as rec:
                            feasible_dev, _, avail_after = pallas_solve_queue(
                                *queue_args, evenly=evenly
                            )
                            rec.sync(avail_after)
                        feasible = _readback(feasible_dev)[:n_earlier]
                    else:
                        self.last_queue_lane = "xla"
                        with default_profiler.profile(
                            "fifo_queue", lane="xla", fn=solve_queue
                        ) as rec:
                            out = solve_queue(*queue_args, evenly=evenly, with_placements=False)
                            rec.sync(out.avail_after)
                        feasible = _readback(out.feasible)[:n_earlier]
                        avail_after = out.avail_after
                gate_span.tag("lane", self.last_queue_lane)
                # capture BEFORE the blocked-earlier verdict below: a
                # FAILURE_EARLIER_DRIVER refusal is exactly the decision
                # the provenance explainer must be able to decompose
                if self.capture_sink is not None:
                    self._capture_solve(
                        cluster, problem, earlier_skip_allowed, n_earlier,
                        feasible, didx_all, avail_after,
                    )
                # an enforced (old-enough) earlier driver that doesn't fit
                # fails the whole request (resource.go:244-253)
                for i in range(n_earlier):
                    if not feasible[i] and not earlier_skip_allowed[i]:
                        gate_span.tag("earlierOk", False)
                        return FifoOutcome(supported=True, earlier_ok=False)
                gate_span.tag("earlierOk", True)
        else:
            with tracing.child_span("fifo_gate", {"earlierApps": 0, "earlierOk": True}):
                avail_after = problem.avail if use_native else _upload(problem.avail)[0]
            feasible = np.zeros(0, dtype=bool)
            if self.capture_sink is not None:
                self._capture_solve(
                    cluster, problem, earlier_skip_allowed, n_earlier,
                    feasible, didx_all, avail_after,
                )

        return self._pack_current(
            cluster, problem, avail_after, n_earlier, current_app,
            metadata=metadata, use_native=use_native,
        )

    def _capture_solve(
        self, cluster, problem, earlier_skip_allowed, n_earlier,
        feasible, didx_all, avail_after,
    ) -> None:
        """Hand the queue solve's inputs + verdicts to the provenance
        sink (provenance/tracker.py).  Array references, no copies but
        the post-queue availability read back from a device lane; only
        runs when wiring installed a sink."""
        try:
            from .batch_solver import queue_policy_code
            from ..provenance.tracker import SolveArtifacts

            policy_code = queue_policy_code(self.assignment_policy)
            if policy_code is None:
                return
            with tracing.child_span("provenance.capture"):
                avail_host = (
                    avail_after
                    if isinstance(avail_after, np.ndarray)
                    else _readback(avail_after)
                )
                na = n_earlier + 1
                packed = np.empty((na, 8), dtype=np.int32)
                packed[:, 0:3] = problem.driver[:na]
                packed[:, 3:6] = problem.executor[:na]
                packed[:, 6] = problem.count[:na]
                packed[:, 7] = problem.app_valid[:na]
                self.capture_sink(SolveArtifacts(
                    policy_code=int(policy_code),
                    lane=self.last_queue_lane or "none",
                    basis=problem.avail,
                    driver_rank=problem.driver_rank,
                    exec_ok=problem.exec_ok,
                    packed=packed,
                    n_earlier=n_earlier,
                    feasible=np.asarray(feasible, dtype=bool),
                    didx=(
                        np.asarray(didx_all, dtype=np.int32)
                        if didx_all is not None
                        else None
                    ),
                    resume=0,
                    avail_after=np.asarray(avail_host, dtype=np.int32),
                    scale=problem.scale,
                    node_names=cluster.node_names,
                    zone_names=cluster.zone_names,
                    zone_id=cluster.zone_id,
                    skip_allowed=list(earlier_skip_allowed),
                ))
        except Exception:
            logger.exception("provenance capture failed (diagnostic only)")

    def _pack_current(
        self,
        cluster,
        problem,
        avail_after,
        n_earlier: int,
        current_app: AppDemand,
        metadata: Optional[NodeGroupSchedulingMetadata] = None,
        use_native: bool = False,
    ) -> FifoOutcome:
        """The current driver's gang pack against the post-queue
        availability carry: solve + placement decode + efficiency rows.
        Shared tail of solve_tensor and the delta-solve engine
        (ops/deltasolve.py), which substitutes its session's warm carry
        for the cold queue pass and hands the identical arguments here."""
        from .batch_solver import solve_single

        evenly = self.assignment_policy == "distribute-evenly"
        minfrag = self.assignment_policy == "minimal-fragmentation"
        to_host = _on_host if use_native else _readback
        with tracing.child_span(
            "binpack", {"policy": self.assignment_policy}
        ) as binpack_span:
            if use_native:
                from ..native.fifo import solve_app_native

                binpack_span.tag("lane", "native")
                with default_profiler.profile(
                    "solve_app", lane="native", jit=False
                ):
                    nat_feas, nat_didx, nat_counts, nat_caps = solve_app_native(
                        np.asarray(avail_after), problem.driver_rank, problem.exec_ok,
                        problem.driver[n_earlier], problem.executor[n_earlier],
                        int(problem.count[n_earlier]),
                    )
                from .batch_solver import AppSolve

                solve = AppSolve(
                    feasible=np.bool_(nat_feas),
                    driver_idx=np.int32(nat_didx),
                    exec_counts=nat_counts,
                    exec_capacity=nat_caps,
                )
            else:
                binpack_span.tag("lane", "xla")
                single_args = _upload(
                    problem.driver_rank,
                    problem.exec_ok,
                    problem.driver[n_earlier],
                    problem.executor[n_earlier],
                    problem.count[n_earlier],
                )
                with default_profiler.profile(
                    "solve_single", lane="xla", fn=solve_single
                ) as rec:
                    solve = solve_single(avail_after, *single_args)
                    rec.sync(solve.exec_counts)
            feasible = to_host(solve.feasible, bool)
            binpack_span.tag("feasible", feasible)
        if not feasible:
            return FifoOutcome(supported=True, earlier_ok=True, result=empty_packing_result())

        names = cluster.node_names
        k = current_app.min_executor_count
        with tracing.child_span("fast_path.decode"):
            driver_idx = to_host(solve.driver_idx, int)
            driver_node = names[driver_idx]
            if evenly:
                cap = to_host(solve.exec_capacity)[: len(names)]
                counts = evenly_counts(cap, k)
                executor_nodes = counts_to_evenly_list(names, counts)
            elif minfrag:
                cap = min_frag_unclamped_caps(
                    to_host(avail_after)[: len(names)],
                    problem.executor[n_earlier],
                    np.asarray(problem.exec_ok[: len(names)]),
                    driver_idx,
                    problem.driver[n_earlier],
                )
                executor_nodes = minimal_fragmentation_assignment(names, cap, k)
                if executor_nodes is None:  # unreachable: feasibility proven above
                    return FifoOutcome(
                        supported=True, earlier_ok=True, result=empty_packing_result()
                    )
                # reference quirk: min-frag reports only the driver in
                # reserved/efficiencies under strict parity (packers.
                # make_minimal_fragmentation QUIRK, switchable)
                counts = np.zeros(len(names), dtype=np.int64)
                if not self.strict_reference_parity:
                    pos = {name: i for i, name in enumerate(names)}
                    for node in executor_nodes:
                        counts[pos[node]] += 1
            else:
                counts = to_host(solve.exec_counts)[: len(names)]
                executor_nodes = counts_to_tightly_list(names, counts)

        # efficiencies feed metrics only on this path (non-single-AZ
        # policies); the host lane computes them against the metadata
        # MUTATED by the earlier-drivers pass (resource.go:255-259 then
        # binpack on the same map), so both branches use the post-queue
        # availability carried out of the device scan.  Domain contract:
        # the rows branch averages over cluster.node_names, which the
        # production caller (build_cluster_tensor) populates with EVERY
        # affinity-matching node — the same domain as the host lane's
        # metadata — not just schedulable candidates.
        def post_queue_avail_rows():
            if n_earlier == 0:
                # no queue pass ran: skip the device→host sync + multiply
                return cluster.avail[: len(names)]
            scale = problem.scale.astype(np.int64)
            return (
                to_host(avail_after)[: len(names)].astype(np.int64)
                * scale[None, :]
            )

        with tracing.child_span("fast_path.efficiency"):
            if metadata is not None:
                reserved = build_reserved(
                    names, counts, driver_node, current_app.driver_resources,
                    current_app.executor_resources,
                )
                eff_meta = metadata
                if n_earlier > 0:
                    eff_meta = _patch_available(metadata, names, post_queue_avail_rows())
                efficiencies = compute_packing_efficiencies(eff_meta, reserved)
            else:
                # per-node reserved = count × executor (+ driver on its node)
                reserved_rows = np.zeros_like(cluster.avail)
                drv_row, _ = _res_rows(current_app.driver_resources)
                exec_row, _ = _res_rows(current_app.executor_resources)
                reserved_rows[driver_idx] += np.array(drv_row, np.int64)
                reserved_rows[: len(names)] += (
                    counts.astype(np.int64)[:, None] * np.array(exec_row, np.int64)[None, :]
                )
                efficiencies = efficiencies_from_rows(
                    names, cluster.sched, post_queue_avail_rows(), reserved_rows
                )
            result = PackingResult(
                driver_node=driver_node,
                executor_nodes=executor_nodes,
                has_capacity=True,
                packing_efficiencies=efficiencies,
                max_avg_efficiency=(
                    efficiencies.seq_max_avg()
                    if isinstance(efficiencies, LazyEfficiencies)
                    else None
                ),
            )
        return FifoOutcome(supported=True, earlier_ok=True, result=result)


def _fused_efficiency_inputs(cluster, problem):
    """Device inputs + numeric-range guards for the on-device zone-
    efficiency score (batch_solver.solve_queue_single_az).  Returns None
    when any bound fails and the host zone-choice loop must take over.
    The bounds guarantee: int32 exactness of every reserved numerator
    (r_base = sched_base − m·scale), f32 exactness of all ratio operands
    (ints ≤ 2^24), ratios ≤ 1 (avail ≤ schedulable), and an int32-safe
    score accumulator ((k+1)·2^EFF_SHIFT < 2^31)."""
    n = len(cluster.node_names)
    nb = problem.avail.shape[0]
    sched = cluster.sched[:n]  # int64 base units (milli-cpu, bytes, milli-gpu)
    avail_base = cluster.avail[:n]
    scale = problem.scale.astype(np.int64)
    k_max = int(problem.count.max()) if problem.count.size else 0
    if k_max + 1 > 4096:
        return None
    if n == 0:
        return None
    if (sched[:, 0] <= 0).any() or (sched[:, 1] <= 0).any():
        # zero-schedulable dims hit the normalize(0)→1 divisor and can
        # produce efficiencies ≫ 1 — exact f64 host path handles those
        return None
    if (sched[:, 0] > 2**31 - 1024).any() or (sched[:, 2] > 2**31 - 1024).any():
        return None
    if (avail_base > sched).any():
        return None
    if int(scale[0]) > 2**31 - 1 or int(scale[2]) > 2**31 - 1:
        return None
    th_mem = _ceil_div(sched[:, 1], int(scale[1]))
    den_c = _ceil_div(sched[:, 0], 1000)
    den_g = _ceil_div(sched[:, 2], 1000)
    if (th_mem > 2**24).any() or (den_c > 2**24).any() or (den_g > 2**24).any():
        return None

    s_cpu = np.zeros(nb, np.int32)
    s_cpu[:n] = sched[:, 0]
    s_gpu = np.zeros(nb, np.int32)
    s_gpu[:n] = sched[:, 2]
    inv_m = np.zeros(nb, np.float32)
    inv_m[:n] = (float(scale[1]) / sched[:, 1].astype(np.float64)).astype(np.float32)
    th = np.zeros(nb, np.int32)
    th[:n] = th_mem
    return s_cpu, s_gpu, inv_m, th, int(scale[0]), int(scale[2])


class TpuSingleAzFifoSolver:
    """FIFO pass for the single-AZ policies.

    Fast lane (one dispatch): batch_solver.solve_queue_single_az scans
    the whole earlier-driver queue on device — per-zone tightly-pack
    solves, the zone-efficiency choice in certified fixed point
    (batch_solver.EFF_SHIFT), the az-aware cross-zone fallback, and the
    carried usage subtraction all fused into a single XLA program.  On
    accelerator-less hosts (backend "auto" on CPU, or "native") the C++
    lane (native/fifo_solver.cpp::fifo_solve_queue_single_az) runs the
    same per-zone solves with the zone chosen by EXACT float64
    efficiency math — host-lane decisions with no uncertainty valve, at
    native speed.

    Exactness valve: any app whose zone scores land inside the
    fixed-point margin is flagged `uncertain`, and the whole queue is
    re-solved on the host lane — per-driver vmapped zone solves
    (solve_zones) with the zone choice in the oracle's float64
    efficiency math — restoring bit-exact reference parity.  Snapshots
    outside the fused lane's numeric bounds (_fused_efficiency_inputs)
    go straight to the host lane.  The current app's packing is always
    chosen with the exact host math.  `last_path` records which lane ran
    ("fused" / "native" / "host") for tests and diagnostics."""

    def __init__(
        self,
        az_aware: bool = False,
        backend: str = "auto",
        interpret: bool = False,
        inner_policy: str = "tightly-pack",
        strict_reference_parity: bool = compat.DEFAULT_STRICT,
    ):
        # inner_policy "minimal-fragmentation" gives the
        # single-az-minimal-fragmentation semantics: zone feasibility and
        # driver choice are shared with tightly (work-conserving drain),
        # placements come from the min-frag kernel / host bisect, and the
        # zone choice sees driver-only reserved under strict parity (the
        # reference's no-write-back quirk).  Both fused one-dispatch
        # lanes serve it (XLA scan with minfrag=True; pallas kernel with
        # the min-frag drain per zone); az_aware has no min-frag variant
        # in the reference.
        assert not (az_aware and inner_policy == "minimal-fragmentation")
        self.az_aware = az_aware
        self.backend = backend
        self.inner_policy = inner_policy
        self.strict_reference_parity = strict_reference_parity
        # interpret=True runs the pallas kernel in interpreter mode so the
        # solver-side pallas wiring is testable on CPU
        self.interpret = interpret
        self.last_path: Optional[str] = None
        # which program ran the last queue pass — "pallas" / "xla" (the
        # two fused one-dispatch lanes), "native" or "host"; None = no
        # queue pass ran.  last_path says how the answer was reached,
        # this says on what.
        self.last_queue_lane: Optional[str] = None

    def _use_pallas(self) -> bool:
        return _pallas_selected(self.backend)

    def solve(
        self,
        metadata: NodeGroupSchedulingMetadata,
        driver_order: Sequence[str],
        executor_order: Sequence[str],
        earlier_apps: List[AppDemand],
        earlier_skip_allowed: List[bool],
        current_app: AppDemand,
    ) -> FifoOutcome:
        import jax.numpy as jnp

        from . import packers
        from .batch_solver import solve_queue_single_az, solve_zones_jit

        cluster = tensorize_cluster(metadata, driver_order, executor_order)
        all_apps = list(earlier_apps) + [current_app]
        apps = tensorize_apps(all_apps)
        problem = scale_problem(cluster, apps)
        self.last_queue_lane = None
        if not problem.ok:
            self.last_path = None
            return FifoOutcome(supported=False)

        names = cluster.node_names
        n = len(names)
        nb = problem.avail.shape[0]
        scale = problem.scale.astype(np.int64)

        candidate_zones, zone_masks = candidate_zone_masks(
            driver_order, executor_order, metadata, names, nb
        )
        zone_masks_dev = jnp.asarray(zone_masks)
        rank_dev = jnp.asarray(problem.driver_rank)
        exec_dev = jnp.asarray(problem.exec_ok)

        avail = problem.avail.astype(np.int32).copy()  # scaled, mutated per driver

        minfrag_inner = self.inner_policy == "minimal-fragmentation"
        exec_ok_arr = np.asarray(problem.exec_ok[:n])

        def pack_one(app_idx: int):
            """Device zone solves + host zone choice for one app.
            Returns (driver_idx, counts) or None when infeasible."""
            if not candidate_zones:
                return None  # no zone has both driver and executor candidates
            solves = solve_zones_jit(
                jnp.asarray(avail),
                rank_dev,
                exec_dev,
                zone_masks_dev,
                jnp.asarray(problem.driver[app_idx]),
                jnp.asarray(problem.executor[app_idx]),
                jnp.asarray(problem.count[app_idx]),
            )
            feasible = np.asarray(solves.feasible)
            driver_idx = np.asarray(solves.driver_idx)
            counts_all = np.asarray(solves.exec_counts)

            results = []
            per_zone = []
            for zi, zone in enumerate(candidate_zones):
                if not feasible[zi]:
                    continue
                d_idx = int(driver_idx[zi])
                if minfrag_inner:
                    # exact host bisect on the carried scaled availability
                    # (capacities are scale-invariant); placement order is
                    # the drain order, not priority order
                    decoded = min_frag_zone_decode(
                        names,
                        avail.astype(np.int64)[:n],
                        problem.executor[app_idx],
                        exec_ok_arr & zone_masks[zi][:n],
                        d_idx,
                        problem.driver[app_idx],
                        int(problem.count[app_idx]),
                        self.strict_reference_parity,
                    )
                    if decoded is None:  # unreachable: zone feasible
                        continue
                    executor_nodes, zone_counts, eff_counts = decoded
                    eff_rows = _reserved_rows(n, d_idx, eff_counts, problem, app_idx)
                else:
                    zone_counts = counts_all[zi][:n]
                    executor_nodes = counts_to_tightly_list(names, zone_counts)
                    eff_rows = _reserved_rows(n, d_idx, zone_counts, problem, app_idx)
                results.append(
                    PackingResult(
                        driver_node=names[d_idx],
                        executor_nodes=executor_nodes,
                        has_capacity=True,
                        packing_efficiencies=efficiencies_from_rows(
                            names,
                            cluster.sched,
                            avail.astype(np.int64) * scale[None, :],
                            eff_rows * scale[None, :],
                        ),
                    )
                )
                per_zone.append((d_idx, zone_counts))
            if not results:
                return None
            best = packers._choose_best_result(metadata, results)
            if not best.has_capacity:
                # the all-zero-efficiency quirk: single-az yields nothing;
                # the caller's az_aware fallback handles the cross-zone pack
                return None
            choice = results.index(best)
            d_idx, counts = per_zone[choice]
            return d_idx, counts, best

        def plain_fallback(app_idx):
            return self._plain_pack(app_idx, avail, problem, n)

        n_earlier = len(earlier_apps)
        fused_done = False
        # None = no queue pass ran (empty queue); "fused"/"native"/"host"
        # report which lane actually processed earlier drivers
        self.last_path = None
        # min-frag inner: all fast lanes (native, XLA scan, pallas
        # kernel) run the min-frag drain with the int32 MF_SENT
        # sentinel, so the sentinel-collision guard gates every one of
        # them; pathological snapshots take the exact host lane (its
        # decode uses a 2^62 sentinel no int32 capacity can reach).
        from .batch_solver import mf_sentinel_safe

        mf_fused_ok = not minfrag_inner or mf_sentinel_safe(problem.avail)
        # shared by the native and pallas lanes: disjoint zone masks →
        # one zone index per node (-1 = in no candidate zone), and the
        # queue-only validity mask
        zone_vec = np.full(avail.shape[0], -1, np.int32)
        for zi in range(len(candidate_zones)):
            zone_vec[zone_masks[zi]] = zi
        queue_valid = problem.app_valid.copy()
        queue_valid[n_earlier:] = False

        if (
            n_earlier > 0
            and mf_fused_ok
            and not self._use_pallas()
            and _native_selected(self.backend)
        ):
            # native C++ lane: per-zone solves with the zone chosen by
            # EXACT float64 efficiency math — same decisions as the host
            # lane with no uncertainty valve, at native speed
            from ..native.fifo import solve_queue_single_az_native

            with tracing.child_span(
                "fifo_gate", {"lane": "native", "earlierApps": n_earlier}
            ) as gate_span:
                with default_profiler.profile(
                    "fifo_queue_single_az", lane="native", jit=False
                ):
                    feas_n, _zone_n, _didx_n, avail_after_n = solve_queue_single_az_native(
                        avail, problem.driver_rank, np.asarray(problem.exec_ok),
                        zone_vec, problem.driver, problem.executor, problem.count,
                        queue_valid, cluster.sched, scale,
                        n_zones=len(candidate_zones), az_aware=self.az_aware,
                        minfrag=minfrag_inner, strict=self.strict_reference_parity,
                    )
                self.last_path = self.last_queue_lane = "native"
                for i in range(n_earlier):
                    if not feas_n[i] and not earlier_skip_allowed[i]:
                        gate_span.tag("earlierOk", False)
                        return FifoOutcome(supported=True, earlier_ok=False)
                gate_span.tag("earlierOk", True)
                avail[:] = avail_after_n
                fused_done = True

        if not fused_done and n_earlier > 0 and mf_fused_ok:
            eff_inputs = _fused_efficiency_inputs(cluster, problem)
            if eff_inputs is not None:
                s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = eff_inputs
                if self._use_pallas():
                    from .pallas_queue import pallas_solve_queue_single_az

                    from .batch_solver import ZoneQueueSolve

                    with default_profiler.profile(
                        "fifo_queue_single_az", lane="pallas",
                        fn=pallas_solve_queue_single_az,
                    ) as rec:
                        feas_d, zone_d, didx_d, uncertain_d, avail_after_d = (
                            pallas_solve_queue_single_az(
                                jnp.asarray(avail),
                                rank_dev,
                                exec_dev,
                                jnp.asarray(zone_vec),
                                jnp.asarray(problem.driver),
                                jnp.asarray(problem.executor),
                                jnp.asarray(problem.count),
                                jnp.asarray(queue_valid),
                                jnp.asarray(s_cpu),
                                jnp.asarray(s_gpu),
                                jnp.asarray(inv_m),
                                jnp.asarray(th_m),
                                jnp.asarray(np.array([scale_c], np.int32)),
                                jnp.asarray(np.array([scale_g], np.int32)),
                                n_zones=len(candidate_zones),
                                az_aware=self.az_aware,
                                interpret=self.interpret,
                                minfrag=minfrag_inner,
                                strict=self.strict_reference_parity,
                            )
                        )
                        rec.sync(avail_after_d)
                    out = ZoneQueueSolve(
                        feasible=feas_d,
                        zone_idx=zone_d,
                        driver_idx=didx_d,
                        uncertain=uncertain_d,
                        avail_after=avail_after_d,
                    )
                else:
                    with default_profiler.profile(
                        "fifo_queue_single_az", lane="xla",
                        fn=solve_queue_single_az,
                    ) as rec:
                        out = solve_queue_single_az(
                            jnp.asarray(avail),
                            rank_dev,
                            exec_dev,
                            zone_masks_dev,
                            jnp.asarray(problem.driver),
                            jnp.asarray(problem.executor),
                            jnp.asarray(problem.count),
                            jnp.asarray(queue_valid),
                            jnp.asarray(s_cpu),
                            jnp.asarray(s_gpu),
                            jnp.asarray(inv_m),
                            jnp.asarray(th_m),
                            jnp.int32(scale_c),
                            jnp.int32(scale_g),
                            az_aware=self.az_aware,
                            minfrag=minfrag_inner,
                            strict=self.strict_reference_parity,
                        )
                        rec.sync(out.avail_after)
                if not bool(np.asarray(out.uncertain)[:n_earlier].any()):
                    # the one-dispatch lane's answer is certain — it is
                    # the lane that served this request, whatever the
                    # FIFO verdict
                    self.last_path = "fused"
                    self.last_queue_lane = "pallas" if self._use_pallas() else "xla"
                    feasible = np.asarray(out.feasible)[:n_earlier]
                    with tracing.child_span(
                        "fifo_gate",
                        {
                            "lane": "fused",
                            "kernel": self.last_queue_lane,
                            "earlierApps": n_earlier,
                        },
                    ) as gate_span:
                        for i in range(n_earlier):
                            if not feasible[i] and not earlier_skip_allowed[i]:
                                gate_span.tag("earlierOk", False)
                                return FifoOutcome(supported=True, earlier_ok=False)
                        gate_span.tag("earlierOk", True)
                    # keep the closure binding: copy the carried result
                    # into the same array pack_one reads
                    avail[:] = np.asarray(out.avail_after)
                    fused_done = True

        if not fused_done and n_earlier > 0:
            # host lane: per-driver vmapped zone solves with the exact
            # float64 zone choice (the uncertainty/guard fallback)
            self.last_path = self.last_queue_lane = "host"
            with tracing.child_span(
                "fifo_gate", {"lane": "host", "earlierApps": n_earlier}
            ) as gate_span:
                for i, app in enumerate(earlier_apps):
                    packed = pack_one(i)
                    if packed is None and self.az_aware:
                        fallback = plain_fallback(i)
                        packed = fallback if fallback is None else (*fallback, None)
                    if packed is None:
                        if earlier_skip_allowed[i]:
                            continue
                        gate_span.tag("earlierOk", False)
                        return FifoOutcome(supported=True, earlier_ok=False)
                    d_idx, counts = packed[0], packed[1]
                    self._subtract(avail, d_idx, counts, problem, i, n)
                gate_span.tag("earlierOk", True)

        with tracing.child_span(
            "binpack", {"policy": self.inner_policy, "azAware": self.az_aware}
        ) as bp_span:
            packed = pack_one(len(earlier_apps))
            if packed is None and self.az_aware:
                fallback = plain_fallback(len(earlier_apps))
                packed = fallback if fallback is None else (*fallback, None)
            bp_span.tag("feasible", packed is not None)
        if packed is None:
            return FifoOutcome(supported=True, earlier_ok=True, result=empty_packing_result())
        d_idx, counts, chosen = packed
        if chosen is None:
            # cross-zone fallback path: build the result from counts
            chosen = PackingResult(
                driver_node=names[d_idx],
                executor_nodes=counts_to_tightly_list(names, counts),
                has_capacity=True,
                packing_efficiencies=efficiencies_from_rows(
                    names,
                    cluster.sched,
                    avail.astype(np.int64) * scale[None, :],
                    _reserved_rows(n, d_idx, counts, problem, len(earlier_apps))
                    * scale[None, :],
                ),
            )
        return FifoOutcome(supported=True, earlier_ok=True, result=chosen)

    @staticmethod
    def _plain_pack(app_idx, avail, problem, n):
        """Cross-zone tightly-pack (the az-aware fallback)."""
        import jax.numpy as jnp

        from .batch_solver import solve_single

        solve = solve_single(
            jnp.asarray(avail),
            jnp.asarray(problem.driver_rank),
            jnp.asarray(problem.exec_ok),
            jnp.asarray(problem.driver[app_idx]),
            jnp.asarray(problem.executor[app_idx]),
            jnp.asarray(problem.count[app_idx]),
        )
        if not bool(solve.feasible):
            return None
        return int(solve.driver_idx), np.asarray(solve.exec_counts)[:n]

    @staticmethod
    def _subtract(avail, d_idx, counts, problem, app_idx, n):
        """The reference's usage-overwrite quirk in scaled int space."""
        exec_mask = counts > 0
        delta = np.zeros((avail.shape[0], 3), np.int32)
        delta[:n][exec_mask] = problem.executor[app_idx]
        if not exec_mask[d_idx]:
            delta[d_idx] = problem.driver[app_idx]
        avail -= delta


def _reserved_rows(n, d_idx, counts, problem, app_idx):
    rows = np.zeros((n, 3), np.int64)
    rows += counts.astype(np.int64)[:, None] * problem.executor[app_idx].astype(np.int64)[None, :]
    rows[d_idx] += problem.driver[app_idx].astype(np.int64)
    return rows
