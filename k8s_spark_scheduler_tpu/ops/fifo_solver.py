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
from ..state.tensor_snapshot import OVERHEAD_SPAN
from ..tracing import spans as tracing
from ..tracing.profiling import PHASE_REQUEST, default_profiler
from ..types.resources import NodeGroupSchedulingMetadata
from .batch_adapter import (
    build_reserved,
    evenly_counts,
    evenly_rows,
    min_frag_unclamped_caps,
    min_frag_zone_decode,
    minimal_fragmentation_order,
    minimal_fragmentation_rows,
    names_of_rows,
    tightly_rows,
    unclamped_caps,
)
from .efficiency import compute_packing_efficiencies
from .packers import PackingResult, empty_packing_result
from .sparkapp import AppDemand
from .tensorize import _resources_to_base as _res_rows
from .tensorize import (
    INT32_SAFE,
    AppTensor,
    _app_base_rows,
    scale_problem,
    tensorize_apps,
    tensorize_cluster,
)

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


def _readback(value):
    """A device array brought to the host under a ``device.readback``
    span, tagged as ``device.upload`` is."""
    with tracing.child_span("device.readback", {"arrays": 1, "bytes": value.nbytes}):
        return np.asarray(value)


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
    return LazyEfficiencies(names, *_efficiency_columns(s, r))


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


def _tensorize_with_cache(solver, earlier, current_app):
    """AppTensor for earlier + [current]: the earlier block is
    cached by object identity (see TpuFifoSolver._earlier_tensor_cache) and the
    current app's rows are appended."""
    key = tuple(map(id, earlier))
    cached = solver._earlier_tensor_cache
    if cached is not None and cached[0] == key:
        base = cached[2]
    else:
        base = tensorize_apps(earlier)
        solver._earlier_tensor_cache = (key, earlier, base)
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


def _node_block(problem, *more) -> np.ndarray:
    """The node side of a scaled problem as one int32 block: availability
    (3 columns), driver rank, executor ok, then each [N] column of ``more``."""
    node_cols = np.empty((problem.avail.shape[0], 5 + len(more)), np.int32)
    node_cols[:, 0:3] = problem.avail
    node_cols[:, 3] = problem.driver_rank
    node_cols[:, 4] = problem.exec_ok
    for i, column in enumerate(more):
        node_cols[:, 5 + i] = column
    return node_cols


def _app_block(problem) -> np.ndarray:
    """The app side as one int32 block [A, 8]: driver (3), executor (3),
    count, valid."""
    app_cols = np.empty((problem.count.shape[0], 8), np.int32)
    app_cols[:, 0:3] = problem.driver
    app_cols[:, 3:6] = problem.executor
    app_cols[:, 6] = problem.count
    app_cols[:, 7] = problem.app_valid
    return app_cols


def _filter_blocks(problem, n_earlier: int):
    """batch_solver.solve_filter's two inputs from a scaled problem whose
    row ``n_earlier`` is the request's own app: the node-side block
    [N, 5] and the app-side block [A, 8], int32."""
    from .batch_solver import APP_CURRENT, APP_QUEUED

    app_cols = _app_block(problem)
    app_cols[:n_earlier, 7] = np.where(problem.app_valid[:n_earlier], APP_QUEUED, 0)
    app_cols[n_earlier:, 7] = 0
    app_cols[n_earlier, 7] = APP_CURRENT
    return _node_block(problem), app_cols


def _gate_tags(cluster, problem, n_earlier: int) -> dict:
    """What a ``fifo_gate`` span says of its request's shape: the nodes
    the driver's affinity admitted and the drivers ahead of it in its
    instance group's queue, the bucket each was padded to (the compiled
    program's shape), the programs request threads had compiled
    before it (0 on a server whose warm-up covered its groups), and the
    pod slots the request's overhead folds took (0 where its snapshot
    found no slot marked)."""
    return {
        "earlierApps": n_earlier,
        "eligibleNodes": cluster.n_nodes,
        "nodeBucket": problem.avail.shape[0],
        "appBucket": problem.count.shape[0],
        "requestCompiles": default_profiler.compiles(PHASE_REQUEST),
        "overheadRows": gate_overhead_rows(),
    }


def gate_overhead_rows() -> int:
    """``fifo_gate``'s ``overheadRows``: the pod slots folded by the
    ``mirror.overhead`` refreshes of the request so far."""
    return tracing.trace_tag_total(OVERHEAD_SPAN, "rows")


def _earlier_ok(gate_span, feasible, earlier_skip_allowed) -> bool:
    """An enforced (old-enough) earlier driver that doesn't fit fails the
    whole request (resource.go:244-253)."""
    blocked = ~np.asarray(feasible, bool) & ~np.asarray(earlier_skip_allowed, bool)
    ok = not blocked.any()
    gate_span.tag("earlierOk", ok)
    return ok


def _feasible_batch(solver, cluster, apps: Sequence[AppDemand], span) -> List[Optional[bool]]:
    """``feasible_batch`` of both solvers: every app's verdict against
    the same ``cluster``, as one problem.  The apps are tensorized once
    and scaled once with the cluster (one GCD over them all); a device
    lane then pays one round for the batch (the node block and the app
    block up, ``batch_solver.feasible_apps``, [A] int32 down; a batch
    over VERDICT_ROWS goes through in blocks of the one program), the
    native lane loops over the rows on the host.  What the solvers
    differ in is the node's group (``_verdict_groups``) and the host's
    loop (``_host_verdicts``).

    An app without exact base units gets None (the caller's host path)
    and spoils no other verdict; where the joint problem still does not
    scale (an int32 bound that a GCD over many apps misses), each app
    is scaled alone, and None is what still fails.  ``span`` takes the
    rounds' ``arrays`` and ``bytes`` as tags, summed over its phases."""
    verdicts: List[Optional[bool]] = [None] * len(apps)
    if not apps or not cluster.exact:
        return verdicts
    tensor = tensorize_apps(apps)
    rows = np.arange(len(apps))
    if not tensor.exact:
        rows = rows[[_app_base_rows(app)[2] for app in apps]]
    native = solver._use_native()

    def judge(rows) -> bool:
        bucket = None
        if not native:
            from .batch_solver import VERDICT_ROWS

            bucket = -(-len(rows) // VERDICT_ROWS) * VERDICT_ROWS
        problem = scale_problem(cluster, _take_apps(tensor, rows), app_bucket=bucket)
        if problem.ok:
            feasible = (
                solver._host_verdicts(cluster, problem, len(rows))
                if native
                else _device_verdicts(solver, cluster, problem, len(rows), span)
            )
            for i, fits in zip(rows, feasible):
                verdicts[i] = bool(fits)
        return problem.ok

    if len(rows) and not judge(rows) and len(rows) > 1:
        for i in rows:
            judge([i])
    return verdicts


def _take_apps(apps: AppTensor, rows) -> AppTensor:
    return AppTensor(
        driver=apps.driver[rows],
        executor=apps.executor[rows],
        count=apps.count[rows],
        valid=apps.valid[rows],
        exact=True,
    )


def _device_verdicts(solver, cluster, problem, n_apps: int, span) -> np.ndarray:
    """One device round for the problem's first ``n_apps`` verdicts (one
    more launch and read-back per further block of VERDICT_ROWS apps)."""
    from .batch_solver import VERDICT_ROWS, feasible_apps

    app_cols = _app_block(problem)
    nodes_dev, *blocks_dev = _upload(
        _node_block(problem, solver._verdict_groups(cluster, problem)),
        *(app_cols[i : i + VERDICT_ROWS] for i in range(0, len(app_cols), VERDICT_ROWS)),
    )
    outs = []
    for block_dev in blocks_dev:
        with default_profiler.profile("feasible_apps", lane="xla", fn=feasible_apps) as rec:
            outs.append(feasible_apps(nodes_dev, block_dev))
            rec.sync(outs[-1])
    feasible = np.concatenate([_readback(out) for out in outs])
    span.tag("arrays", span.tags.get("arrays", 0) + 1 + 2 * len(outs))
    span.tag(
        "bytes",
        span.tags.get("bytes", 0) + nodes_dev.nbytes + app_cols.nbytes + feasible.nbytes,
    )
    return feasible[:n_apps] != 0


@dataclass
class FifoOutcome:
    """Result of the combined earlier-drivers + current-driver solve."""

    supported: bool  # False → caller must use the host oracle path
    earlier_ok: bool = True  # False → an enforced earlier driver doesn't fit
    result: Optional[PackingResult] = None  # current driver's packing


class TpuFifoSolver:
    """One device round for the whole FIFO queue + the current driver:
    two uploads, one program (batch_solver.solve_filter), one read-back.

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
        interpret: bool = False,
    ):
        self.assignment_policy = assignment_policy
        self.backend = backend
        # interpret=True runs the pallas kernels in interpreter mode so the
        # solver's pallas lane is testable on CPU
        self.interpret = interpret
        # min-frag only: whether the reference's no-efficiency-write-back
        # quirk applies to the current driver's reported efficiencies
        self.strict_reference_parity = strict_reference_parity
        # which lane served the last queue pass — one of "native",
        # "native-minfrag", "pallas", "pallas-minfrag", "xla",
        # "minfrag-xla"; None = no queue pass ran (a device lane runs its
        # program on an empty queue too) — observable for tests and the
        # tpu.fastpath lane counters
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
        return _tensorize_with_cache(self, earlier, current_app)

    def feasible_batch(
        self, cluster, apps: Sequence[AppDemand], span=tracing.NOOP_SPAN
    ) -> List[Optional[bool]]:
        """Feasibility of each app against one prebuilt ClusterTensor, as
        one batch (``_feasible_batch``), with no placement decode and no
        efficiency math: the unschedulable-marker's empty-cluster
        verdicts.  Feasibility is policy-invariant across
        tightly/evenly/min-frag (the work-conserving drain rule,
        batch_solver docstring), identical to binpack_func's
        has_capacity.  None = not exactly tensorizable (caller uses the
        host path)."""
        return _feasible_batch(self, cluster, apps, span)

    def feasible_tensor(self, cluster, app: AppDemand) -> Optional[bool]:
        """The batch of one."""
        return self.feasible_batch(cluster, [app])[0]

    def _verdict_groups(self, cluster, problem):
        return 0  # every node in the one group: the gang may spread over them all

    def _host_verdicts(self, cluster, problem, n_apps: int):
        from ..native.fifo import solve_app_native

        return [
            solve_app_native(
                problem.avail, problem.driver_rank, problem.exec_ok,
                problem.driver[i], problem.executor[i], int(problem.count[i]),
            )[0]
            for i in range(n_apps)
        ]

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
        with tracing.child_span("fast_path.tensorize_apps"):
            apps = self._tensorize_with_cache(list(earlier_apps), current_app)
        self.last_queue_lane = None
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
        # the native C++ lane serves every policy; decisions are
        # differential-tested bit-identical to the device scans
        solve = self._solve_native if self._use_native() else self._solve_on_device
        return solve(cluster, problem, earlier_skip_allowed, current_app, metadata)

    def _solve_native(self, cluster, problem, earlier_skip_allowed, current_app, metadata):
        """The queue pass, then the current driver's pack, in the C++
        library on the host."""
        n_earlier = len(earlier_skip_allowed)
        minfrag = self.assignment_policy == "minimal-fragmentation"
        # the fifo_gate span is the request's "earlier drivers fit?" phase
        with tracing.child_span(
            "fifo_gate", _gate_tags(cluster, problem, n_earlier), cpu=True
        ) as gate_span:
            feasible, didx_all, avail_after = np.zeros(0, dtype=bool), None, problem.avail
            if n_earlier > 0:
                queue_valid = problem.app_valid.copy()
                queue_valid[n_earlier:] = False
                queue_args = (
                    problem.avail, problem.driver_rank, problem.exec_ok,
                    problem.driver, problem.executor, problem.count, queue_valid,
                )
                self.last_queue_lane = "native-minfrag" if minfrag else "native"
                with default_profiler.profile(
                    "fifo_queue", lane=self.last_queue_lane, jit=False
                ):
                    if minfrag:
                        from ..native.fifo import solve_queue_min_frag_native

                        feasible_all, didx_all, avail_after = solve_queue_min_frag_native(
                            *queue_args
                        )
                    else:
                        from ..native.fifo import solve_queue_native

                        feasible_all, didx_all, avail_after = solve_queue_native(
                            *queue_args,
                            evenly=self.assignment_policy == "distribute-evenly",
                        )
                feasible = feasible_all[:n_earlier]
                gate_span.tag("lane", self.last_queue_lane)
            # capture BEFORE the blocked-earlier verdict below: a
            # FAILURE_EARLIER_DRIVER refusal is exactly the decision
            # the provenance explainer must be able to decompose
            if self.capture_sink is not None:
                self._capture_solve(
                    cluster, problem, earlier_skip_allowed, n_earlier,
                    feasible, didx_all, avail_after,
                )
            if not _earlier_ok(gate_span, feasible, earlier_skip_allowed):
                return FifoOutcome(supported=True, earlier_ok=False)
        return self._pack_current(
            cluster, problem, avail_after, n_earlier, current_app, metadata=metadata
        )

    def _solve_on_device(self, cluster, problem, earlier_skip_allowed, current_app, metadata):
        """One device round: the queue pass and the current driver's
        solve as one program (batch_solver.solve_filter), two uploads and
        one read-back.  With no earlier driver the same program runs on
        an empty queue."""
        from .batch_solver import solve_filter

        n_earlier = len(earlier_skip_allowed)
        minfrag = self.assignment_policy == "minimal-fragmentation"
        pallas = self._use_pallas()
        if minfrag:
            lane = "pallas-minfrag" if pallas else "minfrag-xla"
        else:
            lane = "pallas" if pallas else "xla"
        self.last_queue_lane = lane
        nb = problem.avail.shape[0]
        with tracing.child_span(
            "fifo_gate", {**_gate_tags(cluster, problem, n_earlier), "lane": lane}, cpu=True
        ) as gate_span:
            nodes_dev, apps_dev = _upload(*_filter_blocks(problem, n_earlier))
            with default_profiler.profile("fifo_queue", lane=lane, fn=solve_filter) as rec:
                out_dev = solve_filter(
                    nodes_dev, apps_dev, policy=self.assignment_policy,
                    pallas=pallas, interpret=self.interpret,
                )
                rec.sync(out_dev)
            out = _readback(out_dev)
            avail_after = out[: 3 * nb].reshape(nb, 3)
            per_node = out[3 * nb : 4 * nb]
            feasible = out[4 * nb : 4 * nb + n_earlier] != 0
            # capture BEFORE the blocked-earlier verdict, as the native lane does
            if self.capture_sink is not None:
                self._capture_solve(
                    cluster, problem, earlier_skip_allowed, n_earlier,
                    feasible, None, avail_after,
                )
            if not _earlier_ok(gate_span, feasible, earlier_skip_allowed):
                return FifoOutcome(supported=True, earlier_ok=False)
        # the current driver was solved in the program above: what is
        # left of its step on the host is reading the verdict
        with tracing.child_span(
            "binpack", {"policy": self.assignment_policy, "lane": "xla"}
        ) as binpack_span:
            current_fits, driver_idx = bool(out[-2]), int(out[-1])
            binpack_span.tag("feasible", current_fits)
        if not current_fits:
            return FifoOutcome(supported=True, earlier_ok=True, result=empty_packing_result())
        return self._decode_current(
            cluster, problem, avail_after, n_earlier, current_app, metadata,
            driver_idx, per_node,
        )

    def _capture_solve(
        self, cluster, problem, earlier_skip_allowed, n_earlier,
        feasible, didx_all, avail_after,
    ) -> None:
        """Hand the queue solve's inputs + verdicts to the provenance
        sink (provenance/tracker.py).  Array references, no copies;
        only runs when wiring installed a sink."""
        try:
            from .batch_solver import queue_policy_code
            from ..provenance.tracker import SolveArtifacts

            policy_code = queue_policy_code(self.assignment_policy)
            if policy_code is None:
                return
            with tracing.child_span("provenance.capture"):
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
                    avail_after=np.asarray(avail_after, dtype=np.int32),
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
    ) -> FifoOutcome:
        """The current driver's gang pack against the post-queue
        availability carry on the native lane: solve, then the decode
        the device lanes share.  The tail of ``_solve_native`` and of the
        delta-solve engine (ops/deltasolve.py), which substitutes its
        session's warm carry for the cold queue pass and hands the
        identical arguments here."""
        from ..native.fifo import solve_app_native

        avail_after = np.asarray(avail_after)
        with tracing.child_span(
            "binpack", {"policy": self.assignment_policy, "lane": "native"}
        ) as binpack_span:
            with default_profiler.profile("solve_app", lane="native", jit=False):
                feasible, driver_idx, counts, caps = solve_app_native(
                    avail_after, problem.driver_rank, problem.exec_ok,
                    problem.driver[n_earlier], problem.executor[n_earlier],
                    int(problem.count[n_earlier]),
                )
            binpack_span.tag("feasible", bool(feasible))
        if not feasible:
            return FifoOutcome(supported=True, earlier_ok=True, result=empty_packing_result())
        evenly = self.assignment_policy == "distribute-evenly"
        return self._decode_current(
            cluster, problem, avail_after, n_earlier, current_app, metadata,
            int(driver_idx), caps if evenly else counts,
        )

    def _decode_current(
        self,
        cluster,
        problem,
        avail_after: np.ndarray,
        n_earlier: int,
        current_app: AppDemand,
        metadata: Optional[NodeGroupSchedulingMetadata],
        driver_idx: int,
        per_node: np.ndarray,
    ) -> FifoOutcome:
        """The feasible current driver's placements and efficiency rows,
        on the host from host arrays: ``avail_after`` the post-queue
        carry, ``per_node`` the solve's executor counts (its capacities
        after the driver under distribute-evenly; min-frag reads
        neither and assigns from the carry).

        The decode computes on those arrays and makes Python objects for
        the hosting nodes only: each policy yields the hosting rows, one
        per executor in the reference's emission order (batch_adapter's
        ``tightly_rows`` / ``evenly_rows`` / ``minimal_fragmentation_rows``;
        the last is held equal to the host oracle's
        ``packers.minimal_fragmentation_from_capacities``, which the
        served path does not run, by tests/test_minfrag_rows.py), and
        ``names_of_rows`` names them.  ``fast_path.decode`` says so in two
        tags: ``hostNodes``, the distinct nodes that received an
        executor, and ``objects``, the entries of the one per-node
        Python list the decode built."""
        evenly = self.assignment_policy == "distribute-evenly"
        minfrag = self.assignment_policy == "minimal-fragmentation"
        names = cluster.node_names
        k = current_app.min_executor_count
        with tracing.child_span("fast_path.decode") as decode_span:
            driver_node = names[driver_idx]
            if evenly:
                counts = evenly_counts(per_node[: len(names)], k)
                rows = evenly_rows(counts)
            elif minfrag:
                cap = min_frag_unclamped_caps(
                    avail_after[: len(names)],
                    problem.executor[n_earlier],
                    np.asarray(problem.exec_ok[: len(names)]),
                    driver_idx,
                    problem.driver[n_earlier],
                )
                rows = minimal_fragmentation_rows(cap, k)
                if rows is None:  # unreachable: feasibility proven above
                    return FifoOutcome(
                        supported=True, earlier_ok=True, result=empty_packing_result()
                    )
                # reference quirk: min-frag reports only the driver in
                # reserved/efficiencies under strict parity (packers.
                # make_minimal_fragmentation QUIRK, switchable)
                if self.strict_reference_parity:
                    counts = np.zeros(len(names), dtype=np.int64)
                else:
                    counts = np.bincount(rows, minlength=len(names))
            else:
                counts = per_node[: len(names)]
                rows = tightly_rows(counts)
            executor_nodes, host_nodes = names_of_rows(names, rows)
            decode_span.tag("hostNodes", host_nodes)
            decode_span.tag("objects", host_nodes)

        # efficiencies feed metrics only on this path (non-single-AZ
        # policies); the host lane computes them against the metadata
        # MUTATED by the earlier-drivers pass (resource.go:255-259 then
        # binpack on the same map), so both branches use the post-queue
        # availability carried out of the queue pass.  Domain contract:
        # the rows branch averages over cluster.node_names, which the
        # production caller (build_cluster_tensor) populates with EVERY
        # affinity-matching node — the same domain as the host lane's
        # metadata — not just schedulable candidates.
        def post_queue_avail_rows():
            if n_earlier == 0:
                # the queue was empty: skip the multiply
                return cluster.avail[: len(names)]
            scale = problem.scale.astype(np.int64)
            return avail_after[: len(names)].astype(np.int64) * scale[None, :]

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


def _efficiency_columns(s: np.ndarray, r: np.ndarray):
    """(cpu, memory, gpu) float64 efficiency columns from int64
    base-unit schedulable rows ``s`` and reserved rows ``r``
    (efficiency.go:80-105 with Quantity.value() semantics: cpu and gpu
    rounded up to whole units, memory in bytes; Go divides by
    normalize(schedulable) = 1 when schedulable is 0)."""
    s_cpu = _ceil_div(s[:, 0], 1000)
    s_gpu = _ceil_div(s[:, 2], 1000)
    cpu = _ceil_div(r[:, 0], 1000) / np.maximum(s_cpu, 1)
    mem = r[:, 1] / np.maximum(s[:, 1], 1)
    gpu = np.where(s_gpu != 0, _ceil_div(r[:, 2], 1000) / np.maximum(s_gpu, 1), 0.0)
    return cpu, mem, gpu


def _host_gang_solve(avail, rank, exec_ok, driver, executor, k):
    """batch_solver.solve_app in numpy over the given rows (one zone's,
    in array order): (driver position, executor counts) or None.  The
    same integers as the device computes: the capacity-total identity
    for the driver, the tightly-pack greedy fill for the executors."""
    avail = avail.astype(np.int64)
    driver = np.asarray(driver, np.int64)
    executor = np.asarray(executor, np.int64)

    def caps(a):
        per_dim = np.where(
            executor[None, :] == 0,
            np.where(a >= 0, INT32_SAFE, 0),
            a // np.maximum(executor, 1)[None, :],
        )
        return np.where(exec_ok, np.clip(per_dim.min(axis=1), 0, k), 0)

    base = caps(avail)
    with_driver = caps(avail - driver[None, :])
    fits = (avail >= driver[None, :]).all(axis=1) & (rank < INT32_SAFE)
    feasible = fits & (int(base.sum()) - base + with_driver >= k)
    if not feasible.any():
        return None
    d = int(np.argmin(np.where(feasible, rank, INT32_SAFE)))
    cap = base
    cap[d] = with_driver[d]
    counts = np.clip(k - (np.cumsum(cap) - cap), 0, cap)
    return d, counts


def _occupied_rows(keep: np.ndarray, view=None, snapshots=None):
    """(slot, node, the four planes there [L, 4]) of every node the
    packing plane of a kept slot occupies, slot by slot in node order,
    from a compacted view (``batch_solver.compact_snapshots``) or from the
    snapshots [S, 4, N] whole."""
    if view is not None:
        width = (view.shape[1] - 1) // 5
        inside = (np.arange(width)[None, :] < view[:, :1]) & keep[:, None]
        slot, at = np.nonzero(inside)
        planes = view[:, 1 + width :].reshape(len(view), 4, width)
        return slot, view[slot, 1 + at], planes[slot, :, at]
    slot, nodes = np.nonzero((snapshots[:, 3] != 0) & keep[:, None])
    return slot, nodes, snapshots[slot, :, nodes]


def _same_evidence(a, b) -> bool:
    return a is not None and b is not None and a.shape == b.shape and bool((a == b).all())


@dataclass
class _ZonePick:
    """One app's exact single-AZ decision."""

    zone: int                 # index into the candidate zones; their number = cross-zone
    driver_idx: int
    counts: np.ndarray        # [n] executors per node (the usage carry reads > 0)
    executor_nodes: List[str]  # in the order the inner policy emits them


class _ZoneProblem:
    """What the exact zone choice reads of one request: the scaled
    problem, the candidate zones in the reference's order and each
    zone's rows."""

    def __init__(self, cluster, problem, az_aware, inner_policy, strict):
        self.cluster = cluster
        self.problem = problem
        self.az_aware = az_aware
        self.minfrag = inner_policy == "minimal-fragmentation"
        self.strict = strict
        self.names = cluster.node_names
        n = self.n = len(self.names)
        self.scale = problem.scale.astype(np.int64)
        self.rank = problem.driver_rank[:n]
        self.exec_ok = np.asarray(problem.exec_ok[:n])
        # candidate zones: first appearance in the driver's priority
        # order, kept where the zone has an executor candidate
        # (single_az.go:30-45)
        zone_id = cluster.zone_id[:n]
        members = [zone_id == z for z in range(len(cluster.zone_names))]
        first_rank = [
            int(self.rank[m].min()) if m.any() else INT32_SAFE for m in members
        ]
        order = [
            z for z in np.argsort(first_rank, kind="stable").tolist()
            if first_rank[z] < INT32_SAFE and (self.exec_ok & members[z]).any()
        ]
        self.n_zones = len(order)
        self.zone_vec = np.full(problem.avail.shape[0], -1, np.int32)
        for zi, z in enumerate(order):
            self.zone_vec[:n][members[z]] = zi
        self._zone_rows = None

    @property
    def zone_rows(self) -> List[np.ndarray]:
        """Each candidate zone's rows, in array order (the host's own
        packing reads them; a device pass's snapshots do not)."""
        if self._zone_rows is None:
            self._zone_rows = [
                np.flatnonzero(self.zone_vec[: self.n] == zi) for zi in range(self.n_zones)
            ]
        return self._zone_rows

    def _average(self, avail, app_idx, d_idx, hosts, on_each, reserved_each, order=None) -> float:
        """The packing's average efficiency as single_az.go:75-97 takes
        it: per-node max efficiency with the gang reserved, summed in
        float64 over the driver's node and then each executor's, one
        term per pod, over their number.  ``hosts`` are the executors'
        nodes in array order with ``on_each`` executors each (the order
        tightly-pack emits them in; ``order`` where the inner policy
        emits another) and ``reserved_each`` as the efficiencies see them."""
        problem = self.problem
        at = int(np.searchsorted(hosts, d_idx))
        if at == len(hosts) or hosts[at] != d_idx:  # the driver's node hosts no executor
            hosts = np.concatenate([hosts[:at], [d_idx], hosts[at:]])
            on_each = np.concatenate([on_each[:at], [0], on_each[at:]])
            reserved_each = np.concatenate([reserved_each[:at], [0], reserved_each[at:]])
        reserved = reserved_each[:, None].astype(np.int64) * problem.executor[app_idx].astype(np.int64)
        reserved[at] += problem.driver[app_idx]
        s = self.cluster.sched[hosts]
        r = s - (avail[hosts].astype(np.int64) - reserved) * self.scale
        cpu, mem, gpu = _efficiency_columns(s, r)
        node_max = np.maximum(np.maximum(cpu, mem), gpu).tolist()
        total = node_max[at]
        if order is None:
            for term, times in zip(node_max, on_each.tolist()):
                for _ in range(times):
                    total += term
        else:
            where = {node: i for i, node in enumerate(hosts.tolist())}
            for node in order:
                total += node_max[where[node]]
        return total / float(int(on_each.sum()) + 1)

    def pick(self, avail: np.ndarray, app_idx: int) -> Optional[_ZonePick]:
        """The app's packing in the zone the reference chooses, every
        zone packed here on the host from the carry ``avail``."""
        problem = self.problem
        driver, executor = problem.driver[app_idx], problem.executor[app_idx]
        k = int(problem.count[app_idx])
        packings = []
        for rows in self.zone_rows:
            solved = _host_gang_solve(
                avail[rows], self.rank[rows], self.exec_ok[rows], driver, executor, k
            )
            if solved is None:
                packings.append(None)
            else:
                hosts = np.flatnonzero(solved[1])
                packings.append((int(rows[solved[0]]), rows[hosts], solved[1][hosts]))
        return self._choose(avail, app_idx, packings)

    def pick_from_snapshot(self, snapshot: np.ndarray, app_idx: int):
        """The same decision from a snapshot of the device pass ([4, n]:
        the carry's three planes and every zone's packing in one row),
        with no solve on the host: (the pick or None, the carry [n, 3])."""
        if self.minfrag:
            return self._pick_placed_min_frag(snapshot, app_idx), snapshot[:3].T
        avail, packings = self.snapshot_packings(snapshot)
        return self._choose(avail, app_idx, packings), avail

    def zone_from_snapshot(self, snapshot: np.ndarray, app_idx: int) -> int:
        """The candidate zone of ``pick_from_snapshot``'s pick, which is all
        the valve asks of it (min-frag's asks ``placed_min_frag_zones``)."""
        return self.candidate(self.pick_from_snapshot(snapshot, app_idx)[0])

    def placed_min_frag_zones(self, apps: np.ndarray, view=None, snapshots=None) -> np.ndarray:
        """The zone the reference chooses for the app of each slot of a
        pass (``apps`` [S]; -1 = a slot not asked about, which reads -1),
        from the slots as they stand: ``view`` compacted
        (``batch_solver.compact_snapshots``) or ``snapshots`` [S, 4, N]
        whole.  Every slot in one pass of ``_placed_min_frag``."""
        rows = _occupied_rows(apps >= 0, view=view, snapshots=snapshots)
        return self._placed_min_frag(apps, *rows)[0]

    def _placed_min_frag(self, apps, slot, nodes, values):
        """``_choose`` over min-frag placements as they stand, with no
        decode, for many snapshots at once (``apps`` [S]: the app each was
        left for; ``slot``, ``nodes``, ``values`` [L, 4]: the nodes their
        packing planes occupy, slot by slot in node order, and the four
        planes there): every zone of every snapshot scored in one pass
        (the zones are disjoint), the hosts' emission order read from
        their own capacities (``minimal_fragmentation_order``), and each
        zone's sum taken term by term in float64, the driver's node
        first, as ``_average`` takes it; the earlier zone on a tie.  (each
        snapshot's zone or -1 [S]; per node: its zone, executor count,
        driver flag; the nodes' emission order)."""
        from .batch_solver import DRIVER_BIT

        problem = self.problem
        app = apps[slot]
        driver = problem.driver[app].astype(np.int64)
        executor = problem.executor[app].astype(np.int64)
        zone = self.zone_vec[nodes]
        counts = (values[:, 3] & ((1 << DRIVER_BIT) - 1)).astype(np.int64)
        is_driver = (values[:, 3] >> DRIVER_BIT) != 0
        here = values[:, :3].astype(np.int64)
        # the score sees the driver alone under strict parity (no write-back)
        seen = np.zeros_like(counts) if self.strict else counts
        reserved = seen[:, None] * executor + is_driver[:, None] * driver
        s = self.cluster.sched[nodes]
        cpu, mem, gpu = _efficiency_columns(s, s - (here - reserved) * self.scale)
        terms = np.maximum(np.maximum(cpu, mem), gpu)
        cap = unclamped_caps(here - is_driver[:, None] * driver, executor)
        n_zones = max(self.n_zones, 1)
        group = slot * n_zones + zone
        order = minimal_fragmentation_order(cap, nodes, group)
        # a zone packed where its driver is: its sum starts at that node,
        # then one term per executor in emission order, each zone's terms
        # a row of ``ledger`` added up column by column (zeros pad the end)
        heads = np.flatnonzero(is_driver)
        on_each = counts[order]
        g = np.concatenate([group[heads], np.repeat(group[order], on_each)])
        t = np.concatenate([terms[heads], np.repeat(terms[order], on_each)])
        by_group = np.argsort(g, kind="stable")
        g, t = g[by_group], t[by_group]
        at = np.arange(len(g)) - np.searchsorted(g, g)
        n_groups = len(apps) * n_zones
        ledger = np.zeros((n_groups, int(at.max(initial=0)) + 1))
        ledger[g, at] = t
        total = ledger[:, 0].copy()
        for column in ledger.T[1:]:
            total += column
        placed = np.bincount(group, weights=counts, minlength=n_groups)
        packed = np.zeros(n_groups, bool)
        packed[group[heads]] = True
        avg = np.where(packed, total / (placed + 1.0), -np.inf).reshape(len(apps), n_zones)
        avg[np.isnan(avg)] = -np.inf
        best = np.argmax(avg, axis=1)
        best = np.where(avg[np.arange(len(apps)), best] > 0.0, best, -1)
        return best, zone, counts, is_driver, order

    def _pick_placed_min_frag(self, snapshot: np.ndarray, app_idx: int) -> Optional[_ZonePick]:
        """The pick of ``_placed_min_frag``'s zone for one snapshot."""
        slot, nodes, values = _occupied_rows(np.ones(1, bool), snapshots=snapshot[None])
        best, zone, counts, is_driver, order = self._placed_min_frag(
            np.array([app_idx]), slot, nodes, values
        )
        best = int(best[0])
        if best < 0:
            return None
        inside = zone == best
        mine = order[zone[order] == best]
        pick_counts = np.zeros(self.n, np.int64)
        pick_counts[nodes[inside]] = counts[inside]
        rows = np.repeat(nodes[mine], counts[mine])
        return _ZonePick(
            best, int(nodes[inside & is_driver][0]), pick_counts,
            names_of_rows(self.names, rows)[0],
        )

    def snapshot_packings(self, snapshot: np.ndarray):
        """(the carry [n, 3], each zone's packing or None) of a snapshot."""
        from .batch_solver import DRIVER_BIT

        avail = snapshot[:3].T
        packed = snapshot[3]
        occupied = np.flatnonzero(packed)
        zone = self.zone_vec[occupied]
        counts = (packed[occupied] & ((1 << DRIVER_BIT) - 1)).astype(np.int64)
        is_driver = (packed[occupied] >> DRIVER_BIT) != 0
        packings = []
        for zi in range(self.n_zones):
            inside = zone == zi
            drivers = occupied[inside & is_driver]
            if drivers.size == 0:
                packings.append(None)
                continue
            hosts = inside & (counts > 0)
            packings.append((int(drivers[0]), occupied[hosts], counts[hosts]))
        return avail, packings

    def evidence(self, snapshot: np.ndarray, app_idx: int):
        """Everything ``pick_from_snapshot`` reads to choose the app's
        zone: the packings, and the carry, schedulable totals and zones
        of the nodes they occupy, the app's demand and the scale.  None
        under min-frag, whose choice is not memoised."""
        if self.minfrag:
            return None
        problem = self.problem
        occupied = np.flatnonzero(snapshot[3])
        return np.concatenate([
            occupied, snapshot[:, occupied].ravel(), self.cluster.sched[occupied].ravel(),
            self.zone_vec[occupied], self.scale, problem.driver[app_idx],
            problem.executor[app_idx], problem.count[app_idx : app_idx + 1],
        ], dtype=np.int64)

    def _choose(self, avail, app_idx, packings) -> Optional[_ZonePick]:
        """_choose_best_result over the zones' tightly-pack packings
        ((driver node, hosting nodes in array order, executors on each)
        or None, in zone order): strict improvement from 0.0, all in the
        oracle's float64; the cross-zone pack where az-aware finds no
        zone."""
        problem, n = self.problem, self.n
        driver, executor = problem.driver[app_idx], problem.executor[app_idx]
        k = int(problem.count[app_idx])
        best, best_avg = None, 0.0
        for zi, packing in enumerate(packings):
            if packing is None:
                continue
            d_idx, hosts, on_each = packing
            rows = None
            if self.minfrag:
                # placements and their order are the drain's, from the
                # exact host bisect on the same capacities
                decoded = min_frag_zone_decode(
                    avail[:n].astype(np.int64), executor,
                    self.exec_ok & (self.zone_vec[:n] == zi), d_idx, driver, k, self.strict,
                )
                if decoded is None:  # unreachable: the zone is feasible
                    continue
                rows, counts, reserved_counts = decoded
                hosts = np.flatnonzero(counts)
                on_each = counts[hosts]
                avg = self._average(
                    avail, app_idx, d_idx, hosts, on_each, reserved_counts[hosts],
                    order=rows.tolist(),
                )
            else:
                avg = self._average(avail, app_idx, d_idx, hosts, on_each, on_each)
            if best_avg < avg:
                best, best_avg = (zi, d_idx, hosts, on_each, rows), avg
        nodes = None
        if best is not None:
            zi, d_idx, hosts, on_each, rows = best
            if rows is not None:
                nodes = names_of_rows(self.names, rows)[0]
        elif self.az_aware:
            # az_aware_pack_tightly.go:34-37: plain tightly-pack across zones
            solved = _host_gang_solve(avail[:n], self.rank, self.exec_ok, driver, executor, k)
            if solved is None:
                return None
            zi, d_idx, nodes = self.n_zones, int(solved[0]), None
            hosts = np.flatnonzero(solved[1])
            on_each = solved[1][hosts]
        else:
            return None
        counts = np.zeros(n, np.int64)
        counts[hosts] = on_each
        if nodes is None:
            nodes = [self.names[i] for i in np.repeat(hosts, on_each).tolist()]
        return _ZonePick(zi, d_idx, counts, nodes)

    def candidate(self, choice) -> int:
        """The candidate zone of an exact pick (or None) or of a zone
        index as a pass reports it; -1 for none and for the az-aware
        cross-zone pack, which is the pass's own fallback once no zone
        is forced on it."""
        zone = choice.zone if isinstance(choice, _ZonePick) else -1 if choice is None else choice
        return zone if 0 <= zone < self.n_zones else -1

    def subtract(self, avail: np.ndarray, pick: _ZonePick, app_idx: int) -> None:
        """The reference's usage-overwrite quirk in scaled int space."""
        hosts = pick.counts > 0
        avail[: self.n][hosts] -= self.problem.executor[app_idx]
        if not hosts[pick.driver_idx]:
            avail[pick.driver_idx] -= self.problem.driver[app_idx]


class TpuSingleAzFifoSolver:
    """FIFO pass for the single-AZ policies, from a cluster tensor.

    The queue pass runs on the device: the pallas kernel on a TPU
    (ops/pallas_queue.pallas_solve_queue_single_az), its XLA twin
    otherwise (batch_solver.solve_queue_single_az) — per-zone
    tightly-pack solves, the zone-efficiency choice in certified fixed
    point (batch_solver.EFF_SHIFT), the az-aware cross-zone fallback and
    the carried usage subtraction, all in one program.  On
    accelerator-less hosts (backend "auto" on CPU, or "native") the C++
    lane (native/fifo_solver.cpp::fifo_solve_queue_single_az) runs the
    same per-zone solves with the zone chosen by exact float64 math.

    Exactness valve, one app at a time: where the device cannot certify
    an app's zone the pass halts with the carry untouched; that app
    alone is decided on the host in the oracle's float64 arithmetic over
    its packings' own nodes (``_ZoneProblem.pick``), and the pass is
    launched again from that app with its zone forced.  The current
    app's packing is always chosen with the same exact host math.
    Snapshots outside the device score's numeric bounds
    (_fused_efficiency_inputs) run the whole queue through that host
    decision, app by app.

    ``last_path`` records how the queue was answered: "fused" (the
    device pass, resolved apps included), "native" or "host";
    ``last_queue_lane`` on what: "pallas" / "xla" / "native" / "host";
    None = no queue pass ran.  ``last_zone_choices`` counts the last
    request's queue apps by who chose their zone, each app under one key;
    a device pass's host decisions are ``resolved`` where a memo could
    answer them and ``unmemoised`` where the policy's choice keeps no
    evidence (min-frag's)."""

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
        # reference's no-write-back quirk).  az_aware has no min-frag
        # variant in the reference.
        assert not (az_aware and inner_policy == "minimal-fragmentation")
        self.az_aware = az_aware
        self.backend = backend
        self.inner_policy = inner_policy
        self.strict_reference_parity = strict_reference_parity
        # the reference policy's name; no whole-queue session lane has a
        # code for it (batch_solver.queue_policy_code), so the delta-solve
        # engine stands aside
        self.assignment_policy = (
            "az-aware-tightly-pack" if az_aware else "single-az-" + inner_policy
        )
        # interpret=True runs the pallas kernel in interpreter mode so the
        # solver-side pallas wiring is testable on CPU
        self.interpret = interpret
        self.last_path: Optional[str] = None
        self.last_queue_lane: Optional[str] = None
        self.last_zone_choices: dict = {}
        self.last_launches = 0  # device launches of the last queue pass
        self._earlier_tensor_cache = None  # as TpuFifoSolver's
        # id(app) -> (the zone decided exactly for it in the last request,
        # the evidence it was decided on); ids are stable while the
        # tensor cache holds the apps
        self._zone_memo: dict = {}

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
        """The metadata entry (policy engines, snapshots the tensor
        mirror cannot hold): tensorize, then the same core."""
        cluster = tensorize_cluster(metadata, driver_order, executor_order)
        return self.solve_tensor(cluster, earlier_apps, earlier_skip_allowed, current_app)

    def feasible_batch(
        self, cluster, apps: Sequence[AppDemand], span=tracing.NOOP_SPAN
    ) -> List[Optional[bool]]:
        """Whether some zone takes each gang on ``cluster``, as one batch
        (``_feasible_batch``): the unschedulable-marker's empty-cluster
        verdicts, equal to binpack_func's has_capacity: zone feasibility
        is the inner policies' shared tightly-pack feasibility (integers
        only: the float64 score chooses which zone, never whether), and
        a feasible packing reserves something (the driver asks for more
        than nothing), so the all-zero-efficiency quirk cannot turn the
        verdict.  None = not exactly tensorizable (caller uses the host
        path)."""
        return _feasible_batch(self, cluster, apps, span)

    def feasible_tensor(self, cluster, app: AppDemand) -> Optional[bool]:
        """The batch of one."""
        return self.feasible_batch(cluster, [app])[0]

    def _verdict_groups(self, cluster, problem):
        """Per node the candidate zone a gang on it has to fit whole, -1
        outside the candidates; the whole cluster as one group where the
        policy falls back across zones."""
        if self.az_aware:
            return 0
        return _ZoneProblem(
            cluster, problem, False, "tightly-pack", self.strict_reference_parity
        ).zone_vec

    def _host_verdicts(self, cluster, problem, n_apps: int):
        """The zones' tightly-pack solves in numpy, as the host decides a
        zone: one ``_ZoneProblem`` and each zone's rows gathered once for
        the batch."""
        zones = _ZoneProblem(
            cluster, problem, self.az_aware, "tightly-pack", self.strict_reference_parity
        )
        groups = [slice(0, zones.n)] if self.az_aware else zones.zone_rows
        parts = [(problem.avail[rows], zones.rank[rows], zones.exec_ok[rows]) for rows in groups]
        return [
            any(
                _host_gang_solve(
                    *part, problem.driver[i], problem.executor[i], int(problem.count[i])
                ) is not None
                for part in parts
            )
            for i in range(n_apps)
        ]

    def solve_tensor(
        self,
        cluster,
        earlier_apps: List[AppDemand],
        earlier_skip_allowed: List[bool],
        current_app: AppDemand,
    ) -> FifoOutcome:
        """Solve from a prebuilt ClusterTensor, as the extender calls
        TpuFifoSolver.solve_tensor: zones, schedulable totals and
        availability all come from the tensor."""
        with tracing.child_span("fast_path.tensorize_apps"):
            apps = _tensorize_with_cache(self, list(earlier_apps), current_app)
        self.last_path = self.last_queue_lane = None
        self.last_zone_choices, self.last_launches = {}, 0
        with tracing.child_span("fast_path.scale_problem"):
            problem = scale_problem(cluster, apps)
            if not problem.ok:
                return FifoOutcome(supported=False)
            zones = _ZoneProblem(
                cluster, problem, self.az_aware, self.inner_policy,
                self.strict_reference_parity,
            )
        n_earlier = len(earlier_apps)
        # the carry after the queue, scaled, on the host; from a device
        # pass also the request's own app as the pass packed it (a snapshot)
        avail, probe = problem.avail, None
        if n_earlier > 0:
            with tracing.child_span(
                "fifo_gate", _gate_tags(cluster, problem, n_earlier), cpu=True
            ) as gate_span:
                feasible, avail, probe = self._queue_pass(zones, n_earlier, gate_span)
                gate_span.tag("lane", self.last_queue_lane)
                blocked = ~feasible & ~np.asarray(earlier_skip_allowed, bool)
                gate_span.tag("earlierOk", not blocked.any())
                if blocked.any():
                    # an enforced earlier driver that doesn't fit fails
                    # the whole request (resource.go:244-253)
                    return FifoOutcome(supported=True, earlier_ok=False)
        else:
            with tracing.child_span(
                "fifo_gate", {**_gate_tags(cluster, problem, 0), "earlierOk": True}, cpu=True
            ):
                pass

        with tracing.child_span(
            "binpack", {"policy": self.inner_policy, "azAware": self.az_aware, "lane": "host"}
        ) as bp_span:
            with tracing.child_span("fast_path.zone_choice"):
                if probe is None:
                    pick = zones.pick(avail, n_earlier)
                else:
                    pick, _ = zones.pick_from_snapshot(probe, n_earlier)
            bp_span.tag("feasible", pick is not None)
        if pick is None:
            return FifoOutcome(supported=True, earlier_ok=True, result=empty_packing_result())
        with tracing.child_span("fast_path.decode"):
            driver_node = zones.names[pick.driver_idx]
        with tracing.child_span("fast_path.efficiency"):
            n = zones.n
            # min-frag under strict parity reports the driver only
            # (packers.make_minimal_fragmentation QUIRK)
            reported = (
                np.zeros(n, np.int64)
                if zones.minfrag and self.strict_reference_parity
                else pick.counts[:n].astype(np.int64)
            )
            efficiencies = efficiencies_from_rows(
                zones.names,
                cluster.sched,
                avail[:n].astype(np.int64) * zones.scale[None, :],
                _reserved_rows(n, pick.driver_idx, reported, problem, n_earlier)
                * zones.scale[None, :],
            )
            result = PackingResult(
                driver_node=driver_node,
                executor_nodes=pick.executor_nodes,
                has_capacity=True,
                packing_efficiencies=efficiencies,
                max_avg_efficiency=efficiencies.seq_max_avg(),
            )
        return FifoOutcome(supported=True, earlier_ok=True, result=result)

    # -- the queue pass ---------------------------------------------------

    def _queue_pass(self, zones: _ZoneProblem, n_earlier: int, gate_span):
        """(feasible[n_earlier] bool, the carried availability on the
        host, the request's own app as a device pass packed it or None)
        for the earlier drivers, on the lane this host serves from."""
        problem = zones.problem
        queue_valid = problem.app_valid.copy()
        queue_valid[n_earlier:] = False
        from .batch_solver import mf_sentinel_safe

        # min-frag inner: every fast lane runs the drain with the int32
        # MF_SENT sentinel, so the collision guard gates them all
        fast_ok = not zones.minfrag or mf_sentinel_safe(problem.avail)
        if fast_ok and self._use_native():
            from ..native.fifo import solve_queue_single_az_native

            self.last_path = self.last_queue_lane = "native"
            with default_profiler.profile("fifo_queue_single_az", lane="native", jit=False):
                feasible, _zone, _didx, avail_after = solve_queue_single_az_native(
                    problem.avail, problem.driver_rank, np.asarray(problem.exec_ok),
                    zones.zone_vec, problem.driver, problem.executor, problem.count,
                    queue_valid, zones.cluster.sched, zones.scale,
                    n_zones=zones.n_zones, az_aware=self.az_aware,
                    minfrag=zones.minfrag, strict=self.strict_reference_parity,
                )
            return np.asarray(feasible[:n_earlier], bool), avail_after, None
        score_inputs = _fused_efficiency_inputs(zones.cluster, problem) if fast_ok else None
        if score_inputs is None or zones.n_zones == 0:
            return self._host_queue_pass(zones, n_earlier)
        return self._device_queue_pass(zones, n_earlier, queue_valid, score_inputs, gate_span)

    def _host_queue_pass(self, zones: _ZoneProblem, n_earlier: int):
        """Every earlier app decided exactly on the host, in order: the
        lane for snapshots outside the device score's numeric bounds."""
        self.last_path = self.last_queue_lane = "host"
        self.last_zone_choices = {"host-queue": n_earlier}
        avail = zones.problem.avail.astype(np.int32).copy()
        feasible = np.zeros(n_earlier, bool)
        for i in range(n_earlier):
            pick = zones.pick(avail, i)
            if pick is not None:
                feasible[i] = True
                zones.subtract(avail, pick, i)
        return feasible, avail, None

    def _device_queue_pass(self, zones, n_earlier, queue_valid, score_inputs, gate_span):
        """The device pass and its valve.  The pass flags every app whose
        zone its score cannot certify, goes on with the score's choice
        and leaves a snapshot; each flagged app is decided here in
        float64 from its snapshot, and only where that differs from the
        score's choice is the pass launched again, from that app, with
        its zone forced; a pass that ran out of slots and halted is
        launched again from the app it halted at, undecided.  The
        request's own app rides along as a probe: its snapshot is what
        ``binpack`` chooses from."""
        from .batch_solver import FORCE_NONE, HINT_BASE

        pallas = self._use_pallas()
        self.last_path = "fused"
        self.last_queue_lane = "pallas" if pallas else "xla"
        valid = queue_valid.astype(np.int32)
        valid[n_earlier] = 2
        launch = self._launcher(zones, valid, score_inputs, pallas)
        forced = np.full(valid.shape[0], FORCE_NONE, np.int32)
        # what was decided for an app last time is the best guess for a
        # pass that cannot tell this time: consecutive requests see much
        # the same queue on much the same cluster.  A guess is checked
        # like the score's own choice; a stale one costs a launch, never
        # an answer.
        app_keys = self._earlier_tensor_cache[0]
        if self._zone_memo and zones.n_zones < HINT_BASE:
            for u, key in enumerate(app_keys):
                known = self._zone_memo.get(key)
                if known is not None and 0 <= known[0] < zones.n_zones:
                    forced[u] = HINT_BASE + known[0]
        memo = {}
        feasible = np.zeros(n_earlier, bool)
        # resolved: flagged apps decided on the host; unmemoised: those among
        # them whose policy's choice keeps no evidence, so no memo could answer
        carry, start, launches, resolved, unmemoised = None, 0, 0, 0, 0
        while True:
            columns, avail_dev, left = launch(carry, forced, start)
            launches += 1
            flagged = start + np.flatnonzero(columns[start : n_earlier + 1, 3])
            slots = columns[flagged, 4]
            snapshots = view = probe = None
            if (slots >= 0).any():
                if zones.minfrag:
                    # min-frag's valve reads the slots compacted, and the
                    # probe's whole where this launch packed it
                    left, view_dev, probe_dev = left
                    view = _readback(view_dev)
                    if slots[-1] >= 0 and flagged[-1] == n_earlier:
                        probe = _readback(probe_dev)
                else:
                    snapshots = _readback(left)
            redo = avail = None
            with tracing.aggregate_span("fifo_gate.zone_resolve"):
                decided = None
                if view is not None:
                    decided = self._min_frag_decisions(zones, view, left, flagged, slots, n_earlier)
                for u, slot in zip(flagged.tolist(), slots.tolist()):
                    if slot < 0:  # out of slots: the pass halted here
                        redo = u
                        break
                    if u == n_earlier:
                        if snapshots is not None:
                            probe = snapshots[slot]
                        break
                    if decided is not None:
                        # min-frag's choice keeps no evidence, so no memo answers it
                        evidence, zone = None, int(decided[slot])
                        unmemoised += 1
                    else:
                        # the decision is a function of what the snapshot shows of
                        # the nodes its packings occupy: the same evidence as last
                        # time is the same decision, with no arithmetic
                        evidence = zones.evidence(snapshots[slot], u)
                        known = self._zone_memo.get(app_keys[u])
                        if known is not None and _same_evidence(known[1], evidence):
                            zone = known[0]
                        else:
                            zone = zones.zone_from_snapshot(snapshots[slot], u)
                    memo[app_keys[u]] = (zone, evidence)
                    resolved += 1
                    if zone != zones.candidate(int(columns[u, 2])):
                        forced[u] = zone
                        whole = snapshots if snapshots is not None else _readback(left)
                        redo, avail = u, whole[slot][:3].T
                        break
                stop = n_earlier if redo is None else redo
                feasible[start:stop] = columns[start:stop, 0] != 0
            if redo is None:
                break
            if avail is None:
                if redo == n_earlier:
                    avail = _readback(avail_dev)
                    break  # the probe found no slot: binpack packs on the host
                # out of slots: the next launch starts at the halted app,
                # which it flags into its first slot
                carry = avail_dev
            else:
                carry = _upload(np.ascontiguousarray(avail, np.int32))[0]
            start = redo
        gate_span.tag("zoneResolved", resolved).tag("launches", launches)
        gate_span.tag("zoneUnmemoised", unmemoised)
        self._zone_memo = memo
        self.last_launches = launches
        self.last_zone_choices = {
            "certified": n_earlier - resolved, "resolved": resolved - unmemoised,
            "unmemoised": unmemoised,
        }
        if probe is not None:
            avail = np.ascontiguousarray(probe[:3].T)
        elif avail is None:
            avail = _readback(avail_dev)
        return feasible, avail, probe

    @staticmethod
    def _min_frag_decisions(zones, view, snapshots_dev, flagged, slots, n_earlier):
        """The zone of every queue app a launch packed into a slot, by
        slot, from the compacted view; a slot that occupies more nodes
        than the view keeps has the snapshots read whole."""
        apps = np.full(len(view), -1, np.int64)
        asked = (slots >= 0) & (flagged < n_earlier)
        apps[slots[asked]] = flagged[asked]
        width = (view.shape[1] - 1) // 5
        if (view[slots[asked], 0] > width).any():
            return zones.placed_min_frag_zones(apps, snapshots=_readback(snapshots_dev))
        return zones.placed_min_frag_zones(apps, view=view)

    def _launcher(self, zones, valid, score_inputs, pallas):
        """launch(carry | None, forced, start) -> (host verdict columns
        [A, 5]: placed, driver node, zone, flagged, snapshot slot; device
        availability afterwards; device snapshots, under min-frag with
        their compacted view and the probe's slot beside them).  What
        does not change between the launches of one request is uploaded
        once."""
        from .batch_solver import snapshot_slots

        problem = zones.problem
        s_cpu, s_gpu, inv_m, th_m, scale_c, scale_g = score_inputs
        n_slots = snapshot_slots(problem.avail.shape[0], compacted=zones.minfrag)
        if pallas:
            from .pallas_queue import pallas_solve_queue_single_az_packed as kernel

            node_cols = np.stack(
                [problem.driver_rank, problem.exec_ok.astype(np.int32), zones.zone_vec,
                 s_cpu, s_gpu, th_m, inv_m.view(np.int32)], axis=1,
            )
            avail0, nodes_dev = _upload(problem.avail, node_cols)
            app_cols = np.concatenate(
                [problem.driver, problem.executor, problem.count[:, None], valid[:, None]],
                axis=1,
            )

            def launch(carry, forced, start):
                apps_dev, scalars = _upload(
                    np.concatenate([app_cols, forced[:, None]], axis=1),
                    np.array([scale_c, scale_g, start], np.int32),
                )
                with default_profiler.profile(
                    "fifo_queue_single_az", lane="pallas", fn=kernel
                ) as rec:
                    columns, avail_after, *left = kernel(
                        avail0 if carry is None else carry,
                        nodes_dev, apps_dev, scalars,
                        n_zones=zones.n_zones, az_aware=self.az_aware,
                        interpret=self.interpret, minfrag=zones.minfrag,
                        strict=self.strict_reference_parity, n_slots=n_slots,
                        compact=zones.minfrag,
                    )
                    rec.sync(avail_after)
                return _readback(columns), avail_after, left[0] if len(left) == 1 else left

            return launch

        from .batch_solver import compacted_with_probe, solve_queue_single_az

        zone_masks = zones.zone_vec[None, :] == np.arange(max(zones.n_zones, 1))[:, None]
        fixed = _upload(
            problem.driver_rank, problem.exec_ok, zone_masks, problem.driver,
            problem.executor, problem.count, valid, s_cpu, s_gpu, inv_m, th_m,
        )
        avail0 = _upload(problem.avail)[0]

        def launch(carry, forced, start):
            forced_dev, start_dev = _upload(forced.copy(), np.int32(start))
            with default_profiler.profile(
                "fifo_queue_single_az", lane="xla", fn=solve_queue_single_az
            ) as rec:
                out = solve_queue_single_az(
                    avail0 if carry is None else carry, *fixed,
                    np.int32(scale_c), np.int32(scale_g), forced_dev, start_dev,
                    az_aware=self.az_aware, minfrag=zones.minfrag,
                    strict=self.strict_reference_parity, n_slots=n_slots,
                )
                rec.sync(out.avail_after)
            columns = np.stack(
                [_readback(col) for col in
                 (out.feasible, out.driver_idx, out.zone_idx, out.uncertain, out.slot)],
                axis=1,
            ).astype(np.int32)
            if not zones.minfrag:
                return columns, out.avail_after, out.snapshots
            probe_slot = np.int32(columns[valid == 2, 4].max(initial=0))
            return columns, out.avail_after, (
                out.snapshots, *compacted_with_probe(out.snapshots, probe_slot)
            )

        return launch


def _reserved_rows(n, d_idx, counts, problem, app_idx):
    rows = np.zeros((n, 3), np.int64)
    rows += counts.astype(np.int64)[:, None] * problem.executor[app_idx].astype(np.int64)[None, :]
    rows[d_idx] += problem.driver[app_idx].astype(np.int64)
    return rows
