"""Unschedulable-pod marker (reference
``internal/extender/unschedulablepods.go``).

Periodically scans pending drivers older than the timeout and checks
whether the gang could fit an *otherwise-empty* cluster (zero usage, but
still subtracting non-schedulable overhead — daemonset pods etc.,
unschedulablepods.go:149-151).  Sets/clears the
``PodExceedsClusterCapacity`` pod condition.

A scan is two passes: collect the aged drivers, then judge them in one
batch per affinity signature (every verdict of a signature is against
the same empty cluster, so a policy with a tensor solver answers them
in one ``feasible_batch`` call: one device round on a TPU host), then
mark them in the order found.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from .. import timesource
from .. import tracing
from ..kube.apiserver import APIServer
from ..kube.informer import Informer
from ..metrics import names as mnames
from ..ops.registry import Binpacker
from ..state.tensor_snapshot import TensorSnapshotCache
from ..types.objects import Pod, PodCondition
from ..types.resources import Resources, node_scheduling_metadata_for_nodes
from . import labels as L
from .sparkpods import AnnotationError, spark_app_demand_cached

logger = logging.getLogger(__name__)

POD_EXCEEDS_CLUSTER_CAPACITY = "PodExceedsClusterCapacity"
UNSCHEDULABLE_POLLING_INTERVAL_SECONDS = 60.0
DEFAULT_TIMEOUT_SECONDS = 600.0


class UnschedulablePodMarker:
    def __init__(
        self,
        api: APIServer,
        node_informer: Informer,
        pod_informer: Informer,
        tensor_snapshot: TensorSnapshotCache,
        binpacker: Binpacker,
        timeout_seconds: float = DEFAULT_TIMEOUT_SECONDS,
        polling_interval_seconds: float = UNSCHEDULABLE_POLLING_INTERVAL_SECONDS,
        tracer: Optional[tracing.Tracer] = None,
        metrics=None,
    ):
        if timeout_seconds <= 0:
            timeout_seconds = DEFAULT_TIMEOUT_SECONDS
        self._api = api
        self._node_informer = node_informer
        self._pod_informer = pod_informer
        self._mirror = tensor_snapshot
        self._binpacker = binpacker
        self._timeout = timeout_seconds
        self._interval = polling_interval_seconds
        # the server's tracer and registry (wiring); without them the
        # scan runs untraced and uncounted
        self._tracer = tracer
        self._metrics = metrics
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True, name="unschedulable-marker")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                with tracing.background("unschedulable.scan"):
                    self.scan_for_unschedulable_pods()
            except Exception:
                logger.exception("unschedulable pod scan failed")

    def scan_for_unschedulable_pods(self) -> None:
        """unschedulablepods.go:93-129, in two passes.

        The verdict is a pure function of (eligible node set, zero-usage
        metadata, app resource triple), and a deep pending backlog
        shares a handful of affinity shapes.  So the scan first walks
        the pods and collects each aged driver with its verdict key;
        then builds the empty-cluster metadata and tensor once per
        affinity signature and asks the solver ONCE for the verdicts of
        all the signature's distinct keys (``feasible_batch``: one
        device round on a TPU host, where a verdict per pod was an
        upload, a launch and a blocking read each, thousands of times
        against the request thread); then marks the pods in the order
        it found them, yielding between them.  The conditions written
        and their order are those of judging and marking pod by pod.

        One root span ``unschedulable.scan`` per scan; its verdict
        batches, metadata builds and condition writes are three
        aggregate children (``scan.solve``, one phase per signature;
        ``scan.metadata``, which holds ``scan.overhead``, the mirror's
        non-schedulable overhead; ``scan.mark``), the walk and the
        yields between pods stay in the root's self time."""
        span = (
            self._tracer.span("unschedulable.scan")
            if self._tracer is not None
            else tracing.NOOP_SPAN
        )
        t0 = time.perf_counter()
        with span:
            self._scan(span)
        if self._metrics is not None:
            self._metrics.histogram(
                mnames.UNSCHEDULABLE_SCAN_TIME, time.perf_counter() - t0
            )

    def _scan(self, span) -> None:
        now = timesource.now()
        aged = []  # (pod, its verdict key, its demand), in list order
        verdicts: dict = {}
        pods = signatures = batches = writes = 0
        try:
            for pod in self._pod_informer.list():
                if (
                    pod.scheduler_name == L.SPARK_SCHEDULER_NAME
                    and pod.node_name == ""
                    and pod.meta.deletion_timestamp is None
                    and pod.labels.get(L.SPARK_ROLE_LABEL) == L.DRIVER
                    and pod.creation_timestamp + self._timeout < now
                ):
                    pods += 1
                    try:
                        aged.append((pod, *self._verdict_key(pod)))
                    except AnnotationError:
                        # the pods before it are judged and marked, the
                        # scan then ends
                        logger.exception("failed to check if pod was unschedulable")
                        break
            verdicts, signatures, batches = self._judge(aged)
            for pod, key, _ in aged:
                exceeds = verdicts[key]
                if exceeds:
                    logger.info("marking pod %s as exceeds capacity", pod.name)
                writes += self._mark_pod_cluster_capacity_status(pod, exceeds)
                # yield between pods: the scan is a background janitor —
                # a deep backlog must not monopolize a small host's core
                # against live Filter requests for seconds at a stretch
                time.sleep(0.0005)
        finally:
            span.tag("pods", pods)
            span.tag("verdictMisses", len(verdicts))
            span.tag("verdictBatches", batches)
            span.tag("signatures", signatures)
            span.tag("conditionWrites", writes)

    @staticmethod
    def _affinity_sig(pod: Pod):
        """Hashable signature of the node-matching constraints (the only
        pod inputs to the eligible-node set)."""
        return (
            tuple(sorted(pod.node_selector.items())),
            tuple(sorted((k, tuple(v)) for k, v in pod.node_affinity.items())),
            tuple(
                tuple((k, op, tuple(vals)) for k, op, vals in term)
                for term in pod.affinity_terms
            ),
        )

    def _verdict_key(self, driver: Pod):
        """(key, the app's demand): everything the verdict depends on.
        Quantity is hashable (exact-value eq/hash); the Resources
        dataclass is not, so the key carries its quantities.  The demand
        is the one the Filter's queue pass reads for this pod version
        (parsed and brought to base units once, sparkpods' cache)."""
        _, demand = spark_app_demand_cached(driver)
        key = (
            self._affinity_sig(driver),
            *(
                (r.cpu, r.memory, r.nvidia_gpu)
                for r in (demand.driver_resources, demand.executor_resources)
            ),
            demand.min_executor_count,
        )
        return key, demand

    def _empty_cluster(self, driver: Pod):
        """(node names, zero-usage metadata, its ClusterTensor or None,
        the tensor solver or None) of the nodes ``driver``'s affinity
        signature admits."""
        with tracing.aggregate_span("scan.metadata") as metadata_phase:
            nodes = self._node_informer.list_with_predicate(
                lambda n: driver.matches_node(n)
            )
            node_names = [n.name for n in nodes]
            zero_usage = {n.name: Resources.zero() for n in nodes}
            # the mirror's non-schedulable overhead of the signature's
            # nodes: an aggregate child of the metadata's own, so the
            # scan's other readings keep their times
            with metadata_phase.aggregate("scan.overhead"):
                overhead = self._mirror.non_schedulable_overhead(node_names)
            # chunked: one unbroken 10k-node Quantity build holds the
            # GIL for ~0.5-1s and was the single biggest tail spike
            # live Filters saw from this janitor
            metadata = {}
            for i in range(0, len(nodes), 512):
                chunk = nodes[i : i + 512]
                metadata.update(
                    node_scheduling_metadata_for_nodes(chunk, zero_usage, overhead)
                )
                time.sleep(0.0005)
            cluster = None
            solver = getattr(self._binpacker, "queue_solver", None)
            if solver is not None and hasattr(solver, "feasible_batch"):
                # the tensor is pod-independent within the signature:
                # build once, then the signature's verdicts are one
                # feasibility-only batch on the device/native lane
                # (identical to binpack_func's has_capacity, per the
                # differential suites)
                from ..ops.tensorize import tensorize_cluster

                cluster = tensorize_cluster(metadata, node_names, node_names)
        return node_names, metadata, cluster, solver

    def _judge(self, aged):
        """(``verdicts[key]``, True = exceeds, for every distinct key of
        ``aged``; signatures; batches asked of the tensor solver): per
        affinity signature, in the order first met, one empty cluster
        and one batch of verdicts."""
        by_signature: dict = {}
        for pod, key, demand in aged:
            by_signature.setdefault(key[0], {}).setdefault(key, (pod, demand))
        verdicts: dict = {}
        batches = 0
        for wanted in by_signature.values():
            first_pod = next(iter(wanted.values()))[0]
            node_names, metadata, cluster, solver = self._empty_cluster(first_pod)
            demands = [demand for _, demand in wanted.values()]
            with tracing.aggregate_span("scan.solve") as phase:
                feasible = [None] * len(demands)
                if cluster is not None:
                    batches += 1
                    feasible = solver.feasible_batch(cluster, demands, span=phase)
                for key, demand, fits in zip(wanted, demands, feasible):
                    if fits is None:
                        fits = self._binpacker.binpack_func(
                            demand.driver_resources,
                            demand.executor_resources,
                            demand.min_executor_count,
                            node_names,
                            node_names,
                            metadata,
                        ).has_capacity
                        # a full pack on the host holds the interpreter:
                        # yield after each, as between marks
                        time.sleep(0.0005)
                    verdicts[key] = not fits
            if self._metrics is not None:
                on_host = feasible.count(None)
                for lane, n in (("tensor", len(feasible) - on_host), ("host", on_host)):
                    if n:
                        self._metrics.counter(
                            mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": lane}, n
                        )
        return verdicts, len(by_signature), batches

    def does_pod_exceed_cluster_capacity(self, driver: Pod) -> bool:
        """unschedulablepods.go:132-166: binpack against zero usage plus
        non-schedulable overhead.  The scan's batch of one."""
        key, demand = self._verdict_key(driver)
        return self._judge([(driver, key, demand)])[0][key]

    def _mark_pod_cluster_capacity_status(self, driver: Pod, exceeds: bool) -> bool:
        """unschedulablepods.go:168-180 (condition update only when
        changed).  True when a write was attempted."""
        status = "True" if exceeds else "False"
        current = driver.conditions.get(POD_EXCEEDS_CLUSTER_CAPACITY)
        if current is not None and current.status == status:
            return False
        from ..kube.conflict import run_with_conflict_retry

        state = {"fresh": None}

        def refresh() -> bool:
            state["fresh"] = self._api.get(Pod.KIND, driver.namespace, driver.name)
            return True

        def attempt():
            fresh = state["fresh"]
            fresh.conditions[POD_EXCEEDS_CLUSTER_CAPACITY] = PodCondition(
                type=POD_EXCEEDS_CLUSTER_CAPACITY,
                status=status,
                transition_time=timesource.now(),
            )
            return self._api.update(fresh)

        with tracing.aggregate_span("scan.mark"):
            try:
                # the kubelet and other controllers write pod status too, so
                # 409s here are routine — resolve them through the shared
                # conflict-retry discipline instead of swallowing the write
                refresh()
                run_with_conflict_retry(attempt, refresh, kind=Pod.KIND)
            except Exception:
                # per-pod failure (e.g. pod deleted concurrently) must not
                # abort the scan of the remaining drivers
                logger.exception("failed to mark pod cluster capacity status")
        return True
