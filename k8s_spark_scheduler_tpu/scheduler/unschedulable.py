"""Unschedulable-pod marker (reference
``internal/extender/unschedulablepods.go``).

Periodically scans pending drivers older than the timeout and checks
whether the gang could fit an *otherwise-empty* cluster (zero usage, but
still subtracting non-schedulable overhead — daemonset pods etc.,
unschedulablepods.go:149-151).  Sets/clears the
``PodExceedsClusterCapacity`` pod condition.
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
from ..types.objects import Pod, PodCondition
from ..types.resources import Resources, node_scheduling_metadata_for_nodes
from . import labels as L
from .overhead import OverheadComputer
from .sparkpods import AnnotationError, spark_resources

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
        overhead_computer: OverheadComputer,
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
        self._overhead = overhead_computer
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
                self.scan_for_unschedulable_pods()
            except Exception:
                logger.exception("unschedulable pod scan failed")

    def scan_for_unschedulable_pods(self) -> None:
        """unschedulablepods.go:93-129.

        A deep pending backlog shares a handful of affinity shapes and
        app sizes, and the verdict is a pure function of (eligible node
        set, zero-usage metadata, app resource triple) — so the scan
        memoizes the empty-cluster metadata per affinity signature and
        the binpack verdict per (signature, app triple) within one
        sweep.  Without this, a 1k-deep backlog rebuilt 10k-node
        Quantity metadata and ran a full pack PER POD every interval
        (tens of seconds of CPU that, on a small host, came straight
        out of live Filter latency).

        One root span ``unschedulable.scan`` per scan; its solves,
        metadata builds and condition writes are three aggregate
        children (``scan.solve``, ``scan.metadata``, ``scan.mark``), the
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
        meta_cache: dict = {}
        verdict_cache: dict = {}
        pods = writes = 0
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
                        exceeds = self._pod_exceeds_cached(pod, meta_cache, verdict_cache)
                    except AnnotationError:
                        logger.exception("failed to check if pod was unschedulable")
                        return
                    if exceeds:
                        logger.info("marking pod %s as exceeds capacity", pod.name)
                    writes += self._mark_pod_cluster_capacity_status(pod, exceeds)
                    # yield between pods: the scan is a background janitor —
                    # a deep backlog must not monopolize a small host's core
                    # against live Filter requests for seconds at a stretch
                    time.sleep(0.0005)
        finally:
            span.tag("pods", pods)
            span.tag("verdictMisses", len(verdict_cache))
            span.tag("signatures", len(meta_cache))
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

    def _pod_exceeds_cached(self, driver: Pod, meta_cache: dict, verdict_cache: dict) -> bool:
        sig = self._affinity_sig(driver)
        app_resources = spark_resources(driver)
        # Quantity is hashable (exact-value eq/hash); the Resources
        # dataclass is not, so the key carries its quantities
        key = (
            sig,
            *(
                (r.cpu, r.memory, r.nvidia_gpu)
                for r in (
                    app_resources.driver_resources,
                    app_resources.executor_resources,
                )
            ),
            app_resources.min_executor_count,
        )
        hit = verdict_cache.get(key)
        if hit is not None:
            return hit
        cached = meta_cache.get(sig)
        if cached is None:
            with tracing.aggregate_span("scan.metadata"):
                nodes = self._node_informer.list_with_predicate(
                    lambda n: driver.matches_node(n)
                )
                node_names = [n.name for n in nodes]
                zero_usage = {n.name: Resources.zero() for n in nodes}
                overhead = self._overhead.get_non_schedulable_overhead(nodes)
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
                if solver is not None and hasattr(solver, "feasible_tensor"):
                    # the tensor is pod-independent within the signature:
                    # build once, then each verdict is one feasibility-only
                    # solve on the device/native lane (identical to
                    # binpack_func's has_capacity, per the differential
                    # suites)
                    from ..ops.tensorize import tensorize_cluster

                    cluster = tensorize_cluster(metadata, node_names, node_names)
                cached = (node_names, metadata, cluster, solver)
                meta_cache[sig] = cached
        node_names, metadata, cluster, solver = cached
        exceeds = None
        lane = "tensor"
        with tracing.aggregate_span("scan.solve"):
            if cluster is not None:
                from ..ops.sparkapp import AppDemand

                feasible = solver.feasible_tensor(
                    cluster,
                    AppDemand(
                        app_resources.driver_resources,
                        app_resources.executor_resources,
                        app_resources.min_executor_count,
                    ),
                )
                if feasible is not None:
                    exceeds = not feasible
            if exceeds is None:
                lane = "host"
                result = self._binpacker.binpack_func(
                    app_resources.driver_resources,
                    app_resources.executor_resources,
                    app_resources.min_executor_count,
                    node_names,
                    node_names,
                    metadata,
                )
                exceeds = not result.has_capacity
        if self._metrics is not None:
            self._metrics.counter(mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": lane})
        verdict_cache[key] = exceeds
        return exceeds

    def does_pod_exceed_cluster_capacity(self, driver: Pod) -> bool:
        """unschedulablepods.go:132-166: binpack against zero usage plus
        non-schedulable overhead."""
        return self._pod_exceeds_cached(driver, {}, {})

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
