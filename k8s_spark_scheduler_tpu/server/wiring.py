"""Server wiring: constructs every component bottom-up
(reference ``cmd/server.go:65-237`` InitServerWithClients).

Exported for tests and the HTTP server alike — the Harness builds on
this exactly as the reference's extendertest harness builds on
InitServerWithClients.
"""

from __future__ import annotations

from dataclasses import dataclass
from ..config import Install
from ..demands.manager import DemandManager
from ..events.events import EventLog
from ..kube import crd
from ..kube.apiserver import APIServer
from ..kube.informer import Informer, InformerFactory
from ..metrics.registry import MetricsRegistry
from ..metrics.reporters import ReporterSet
from ..metrics.waste import WasteMetricsReporter
from ..ops.nodesort import NodeSorter
from ..ops.registry import select_binpacker
from ..resilience import ResilienceKit, build_kit
from ..scheduler.demand_gc import start_demand_gc
from ..scheduler.extender import SparkSchedulerExtender
from ..scheduler.reservations_manager import ResourceReservationManager
from ..scheduler.sparkpods import SparkPodLister
from ..scheduler.unschedulable import UnschedulablePodMarker
from ..state.softreservations import SoftReservationStore
from ..state.tensor_snapshot import TensorSnapshotCache
from ..state.typed_caches import (
    LazyDemandInformer,
    ResourceReservationCache,
    SafeDemandCache,
)
from ..tracing import Tracer, install_gc_hook
from ..tracing import profiling as kernel_profiling
from ..types.objects import Node, Pod, ResourceReservation


class SolverWarmupError(RuntimeError):
    """The configured policy's kernels did not compile on this platform."""


@dataclass
class Server:
    """Everything InitServerWithClients wires up."""

    api: APIServer
    install: Install
    informer_factory: InformerFactory
    pod_informer: Informer
    node_informer: Informer
    rr_informer: Informer
    resource_reservation_cache: ResourceReservationCache
    lazy_demand_informer: LazyDemandInformer
    demand_cache: SafeDemandCache
    demand_manager: DemandManager
    soft_reservation_store: SoftReservationStore
    pod_lister: SparkPodLister
    resource_reservation_manager: ResourceReservationManager
    extender: SparkSchedulerExtender
    tensor_snapshot: TensorSnapshotCache
    unschedulable_marker: UnschedulablePodMarker
    metrics: MetricsRegistry
    event_log: EventLog
    tracer: Tracer = None
    reporters: "ReporterSet" = None
    waste_reporter: "WasteMetricsReporter" = None
    resilience: ResilienceKit = None
    provenance: object = None  # ProvenanceTracker (provenance/tracker.py)
    capacity: object = None  # CapacitySampler (capacity/observatory.py)
    contention: object = None  # LockTimekeeper (contention/locktime.py)
    criticalpath: object = None  # CriticalPathAnalyzer (contention/criticalpath.py)
    policy: object = None  # PolicyEngine (policy/engine.py)
    ha: object = None  # HAFabric (ha/__init__.py)
    lifecycle: object = None  # LifecycleLedger (lifecycle/ledger.py)
    slo: object = None  # SloEngine (lifecycle/slo.py)

    def start_background(self) -> None:
        """Start async writers + periodic loops (cmd/server.go:221-230)."""
        self.resource_reservation_cache.run()
        self.lazy_demand_informer.start()
        self.unschedulable_marker.start()
        if self.reporters is not None:
            self.reporters.start()
        if self.capacity is not None:
            self.capacity.start()
        if self.lifecycle is not None:
            self.lifecycle.start()
        if self.ha is not None and self.install.ha.background:
            self.ha.start()
        self._warm_solver_async()

    def warmup_complete(self) -> bool:
        """True once the background solver warmup has finished (or never
        started).  Readiness gates on this: traffic admitted before the
        kernels are compiled pays jit latency on the request path, and —
        worse on a small host — the warmup's compiler threads compete
        with live Filter requests for cores.  A warmup that FAILED never
        completes: a policy whose kernels this platform cannot compile
        must not serve (its answers would all come from the host
        fallback)."""
        ev = getattr(self, "_warm_done", None)
        return ev is None or ev.is_set()

    @property
    def warmup_error(self) -> "BaseException | None":
        """What the solver warmup raised, if it failed (fatal to
        start-up: server/__main__.py exits non-zero on it)."""
        return getattr(self, "_warm_error", None)

    def wait_ready(self, timeout: float = 120.0) -> bool:
        """Block until caches are synced AND the solver warmup finished
        (the readiness condition) — what a deployment's readiness probe
        polls for before kube-scheduler sends the first Filter.  Raises
        SolverWarmupError as soon as the warmup has failed."""
        import time as _time

        deadline = _time.monotonic() + timeout  # schedlint: disable=TS002 -- readiness-probe wait bounds real wall time for a live kubelet
        if not self.informer_factory.wait_for_cache_sync():
            return False
        ev = getattr(self, "_warm_done", None)
        while ev is not None and not ev.wait(0.05):
            if self.warmup_error is not None:
                raise SolverWarmupError(
                    f"solver warmup failed for {self.install.binpack_algo}"
                ) from self.warmup_error
            if _time.monotonic() >= deadline:  # schedlint: disable=TS002 -- remaining budget of the same real-time probe deadline
                return False
        return True

    def observed_groups(self) -> "list[tuple[int, int]]":
        """(nodes, pending drivers) of each instance group as the
        informers hold them: a server that starts against a populated
        cluster warms the shape each group's drivers are served at
        (ops/warmup.py).  Without the FIFO no driver is packed behind
        another, so every queue counts as empty."""
        from ..ops.warmup import observed_groups
        from ..scheduler import labels as L

        label = self.install.instance_group_label
        found = (
            L.find_instance_group_from_pod_spec(pod, label)
            for pod in self.pod_informer.list(
                label_selector={L.SPARK_ROLE_LABEL: L.DRIVER}
            )
            if self.install.fifo and not pod.node_name
        )
        return observed_groups(
            (node.labels.get(label) for node in self.node_informer.list()),
            (group for group, ok in found if ok),
        )

    def _warm_solver_async(self) -> None:
        """Pre-compile, in the background, the kernels the configured
        policy dispatches on this platform (ops/warmup.py) so the first
        Filter request doesn't pay jit latency (first compile is seconds
        on TPU).  A compile error is recorded in ``warmup_error`` and
        ``_warm_done`` stays unset: readiness never turns true.

        The thread is joined (bounded) in stop(): a daemon thread killed
        mid-XLA-compile at interpreter shutdown aborts the whole process
        ("FATAL: exception not rethrown" from pthread teardown inside
        the compiler).  It stays a daemon thread so a compile stuck on
        a dead device can never block process exit outright."""
        import threading

        self._warm_done = threading.Event()
        self._warm_error = None
        if self.extender.binpacker.queue_solver is None:
            self._warm_done.set()
            return

        def warm():
            import logging

            from ..ops.warmup import warm_queue_solver, warm_shapes

            try:
                warm_queue_solver(
                    self.install.binpack_algo,
                    self.install.strict_reference_parity,
                    warm_shapes(self.observed_groups()),
                    should_stop=self._warm_stop.is_set,
                )
            except Exception as err:
                self._warm_error = err
                logging.getLogger(__name__).error(
                    "solver warmup failed for %s; this instance will not "
                    "become ready",
                    self.install.binpack_algo,
                    exc_info=True,
                )
                return
            self._warm_done.set()

        self._warm_stop = threading.Event()
        self._warm_thread = threading.Thread(
            target=warm, daemon=True, name="solver-warmup"
        )
        self._warm_thread.start()

    def stop(self) -> None:
        import time as _time

        deadline = _time.monotonic() + 20.0  # headroom inside the k8s  # schedlint: disable=TS002 -- shutdown grace period is real wall time granted by the kubelet
        # default 30s termination grace period, measured from stop() entry
        warm_thread = getattr(self, "_warm_thread", None)
        if warm_thread is not None:
            self._warm_stop.set()  # signal first; join after the other stops
        if self.reporters is not None:
            self.reporters.stop()
        if self.capacity is not None:
            self.capacity.stop()
        if self.lifecycle is not None:
            self.lifecycle.stop()
        if self.ha is not None:
            self.ha.stop()
            try:
                # graceful handoff: expire our own lease so the standby
                # takes over in one step instead of waiting out the TTL
                self.ha.elector.step_down()
            except Exception:
                pass
        self.unschedulable_marker.stop()
        self.resource_reservation_cache.stop()
        self.demand_cache.stop()
        if self.resilience is not None:
            # the journal keeps its pending (unlanded) intents on disk
            # for the next instance's failover replay
            self.resilience.journal.close()
        if self.policy is not None:
            # same contract for the evict journal
            self.policy.close()
        if warm_thread is not None:
            # a healthy compile finishes in seconds; a wedged device must
            # not stall shutdown past the grace period, so give up at the
            # deadline (the daemon flag then lets the process exit, at
            # worst uncleanly)
            warm_thread.join(timeout=max(0.0, deadline - _time.monotonic()))  # schedlint: disable=TS002 -- remaining real-time budget of the shutdown grace period
            if warm_thread.is_alive():
                import logging

                logging.getLogger(__name__).warning(
                    "solver warmup still compiling at shutdown deadline; abandoning it"
                )


def init_server_with_clients(
    api: APIServer,
    install: Install,
    start_background: bool = True,
    demand_poll_interval: float = 1.0,
    unschedulable_polling_interval: float = 60.0,
) -> Server:
    """cmd/server.go:65-237, bottom-up."""
    # contention observatory switchboard FIRST: the guarded singletons
    # constructed below get their sampling stride from it, and enabling
    # before construction means their very first acquires record
    contention_keeper = None
    if install.contention.enabled:
        from ..contention import locktime

        locktime.set_default_sample_every(install.contention.sample_every)
        contention_keeper = locktime.enable()
    metrics = MetricsRegistry()
    event_log = EventLog()
    # request tracing + kernel profiling sinks.  The profiler is a
    # module-level singleton (solvers are built without wiring access);
    # rebinding it here points kernel metrics/spans at THIS server —
    # correct for the one-server-per-process production shape.
    tracer = Tracer(capacity=256, metrics=metrics)
    install_gc_hook()
    kernel_profiling.default_profiler.configure(metrics=metrics, tracer=tracer)
    # critical-path extraction rides trace completion: every finished
    # request tree decomposes into gate-queue / lock-wait / serde /
    # solve / write-back segments (contention/criticalpath.py)
    criticalpath_analyzer = None
    if install.contention.enabled:
        from ..contention import CriticalPathAnalyzer

        criticalpath_analyzer = CriticalPathAnalyzer(
            metrics=metrics, capacity=install.contention.ring_size
        )
        tracer.add_observer(criticalpath_analyzer.on_trace)
    # node-name interning counters land in THIS server's registry (the
    # interner is module-level for the same reason the profiler is)
    from ..types import serde as _serde

    _serde.names_interner.metrics = metrics

    # CRD ensure (cmd/server.go:83-85)
    crd.ensure_resource_reservations_crd(
        api,
        install.resource_reservation_crd_annotations,
        conversion_webhook=install.conversion_webhook,
    )

    # informer factories + sync (cmd/server.go:91-127)
    factory = InformerFactory(api)
    pod_informer = factory.informer(
        Pod.KIND, index_labels=("spark-app-id", "spark-role")
    )
    node_informer = factory.informer(Node.KIND)
    rr_informer = factory.informer(ResourceReservation.KIND)
    factory.start()

    # caches (cmd/server.go:129-155); one shared write-rate bucket per
    # process, like the kube clientsets' QPS/Burst (cmd/clients.go:53-54)
    from ..kube.ratelimit import TokenBucket

    # overload protection: admission gate, write-back breaker + intent
    # journal, kernel-lane health, tri-state readiness (resilience/)
    resilience_kit = build_kit(install.resilience, metrics=metrics)

    rate_bucket = TokenBucket(install.qps, install.burst) if install.qps > 0 else None
    rr_cache = ResourceReservationCache(
        api,
        rr_informer,
        install.async_client.max_retry_count,
        rate_bucket=rate_bucket,
        breaker=resilience_kit.breaker,
        journal=resilience_kit.journal,
        registry=metrics,
    )
    # failover: intents journaled by a previous instance (durable
    # journal-path) replay through the idempotent write path before any
    # scheduling decision reads the cache
    rr_cache.recover_from_journal()
    lazy_demand_informer = LazyDemandInformer(api, factory, poll_interval=demand_poll_interval)
    binpacker = select_binpacker(
        install.binpack_algo, strict_reference_parity=install.strict_reference_parity
    )
    demand_cache = SafeDemandCache(
        lazy_demand_informer,
        api,
        install.async_client.max_retry_count,
        rate_bucket=rate_bucket,
        registry=metrics,
    )
    demand_manager = DemandManager(
        demand_cache, binpacker, install.instance_group_label, event_log
    )
    start_demand_gc(pod_informer, demand_manager)

    # stores + managers (cmd/server.go:157-167)
    soft_store = SoftReservationStore(pod_informer)
    pod_lister = SparkPodLister(pod_informer, install.instance_group_label)
    rrm = ResourceReservationManager(
        rr_cache, soft_store, pod_lister, pod_informer, metrics=metrics, tracer=tracer
    )
    # event-driven integer snapshot for the tpu-batch fast path, and
    # the one keeper of node overhead every path reads
    tensor_snapshot = TensorSnapshotCache(node_informer, pod_informer, rr_cache, soft_store)

    # waste reporter (cmd/server.go:171-191 NewWasteMetricsReporter)
    waste_reporter = WasteMetricsReporter(metrics, install.instance_group_label)
    waste_reporter.start(pod_informer, lazy_demand_informer)

    # decision provenance: unschedulability explainer + shortfall
    # telemetry + anomaly flight recorder (provenance/)
    provenance_tracker = None
    if install.provenance.enabled:
        from ..provenance.tracker import ProvenanceTracker

        provenance_tracker = ProvenanceTracker(
            enabled=True,
            ring_size=install.provenance.ring_size,
            recorder_size=install.provenance.recorder_size,
            bundle_dir=install.provenance.bundle_dir,
            max_bundle_nodes=install.provenance.max_bundle_nodes,
            metrics=metrics,
            trigger_min_interval=install.provenance.trigger_min_interval_seconds,
        )
        # write-back breaker opening is a flight-recorder trigger: the
        # recent decisions leading into an open breaker are exactly the
        # forensic record an operator wants
        resilience_kit.breaker.on_open = (
            lambda name: provenance_tracker.on_trigger(
                "breaker-open", f"breaker {name} opened"
            )
        )

    # capacity observatory: fragmentation/headroom analytics + the
    # /state/capacity timeline, sampled off-lock on ChangeFeed triggers
    capacity_sampler = None
    if install.capacity.enabled:
        from ..capacity import CapacitySampler

        capacity_sampler = CapacitySampler(
            tensor_snapshot,
            pod_lister=pod_lister,
            waste_reporter=waste_reporter,
            metrics=metrics,
            instance_group_label=install.instance_group_label,
            ring_size=install.capacity.ring_size,
            debounce_seconds=install.capacity.debounce_seconds,
            interval_seconds=install.capacity.interval_seconds,
            max_shapes=install.capacity.max_shapes,
            max_group_zones=install.capacity.max_group_zones,
            max_queue=install.capacity.max_queue,
            tracer=tracer,
        )

    # scheduling-policy engine (policy/): priority ordering, backfill,
    # gang-aware preemption, DRF.  None when disabled — the extender's
    # hooks then cost one attribute check and decisions are
    # byte-identical to pre-policy behavior.
    policy_engine = None
    if install.policy.enabled:
        from ..policy import PolicyEngine

        policy_engine = PolicyEngine(
            install.policy,
            pod_lister=pod_lister,
            tensor_snapshot=tensor_snapshot,
            rr_cache=rr_cache,
            api=api,
            journal_path=install.resilience.journal_path,
            metrics=metrics,
            provenance=provenance_tracker,
        )
        # failover: evict intents journaled by a previous instance
        # replay exactly-once before any scheduling decision runs
        # (mirrors rr_cache.recover_from_journal above)
        policy_engine.recover()

    # gang lifecycle ledger + SLO engine (lifecycle/): per-application
    # state machine fed off informer threads and drain cursors — never
    # under the predicate lock.  The waste reporter's slo_sink makes
    # WasteMetricsReporter the single source of truth for the
    # eviction_waste objective.
    lifecycle_ledger = None
    slo_engine = None
    if install.lifecycle.enabled:
        from ..lifecycle import LifecycleLedger, SloEngine

        slo_engine = SloEngine(
            metrics=metrics,
            window_scale=install.lifecycle.window_scale,
            sample_cap=install.lifecycle.sample_cap,
            overrides=install.lifecycle.objectives,
        )
        waste_reporter.slo_sink = slo_engine.waste_sample
        lifecycle_ledger = LifecycleLedger(
            event_log=event_log,
            tracer=tracer,
            feed=tensor_snapshot.feed,
            policy=policy_engine,
            slo=slo_engine,
            metrics=metrics,
            ring_size=install.lifecycle.ring_size,
            debounce_seconds=install.lifecycle.debounce_seconds,
            interval_seconds=install.lifecycle.interval_seconds,
        )
        lifecycle_ledger.wire_informers(
            pod_informer=pod_informer, rr_informer=rr_informer
        )

    # extender (cmd/server.go:171-191)
    node_sorter = NodeSorter(
        install.driver_prioritized_node_label, install.executor_prioritized_node_label
    )
    extender = SparkSchedulerExtender(
        node_informer=node_informer,
        pod_lister=pod_lister,
        resource_reservation_cache=rr_cache,
        soft_reservation_store=soft_store,
        resource_reservation_manager=rrm,
        demands_manager=demand_manager,
        is_fifo=install.fifo,
        fifo_config=install.fifo_config,
        binpacker=binpacker,
        should_schedule_dynamically_allocated_executors_in_same_az=(
            install.should_schedule_dynamically_allocated_executors_in_same_az
        ),
        tensor_snapshot_cache=tensor_snapshot,
        instance_group_label=install.instance_group_label,
        node_sorter=node_sorter,
        metrics=metrics,
        event_log=event_log,
        waste_reporter=waste_reporter,
        strict_reference_parity=install.strict_reference_parity,
        tracer=tracer,
        resilience=resilience_kit,
        delta_solve=install.delta_solve,
        provenance=provenance_tracker,
        policy=policy_engine,
    )
    if policy_engine is not None:
        # what-if victim validation rides the extender's warm
        # delta-solve sessions (ops/deltasolve.py latest_basis)
        policy_engine._delta_engine = extender.delta_engine
    if slo_engine is not None:
        # decision traces carry the active SLO alert states (one
        # precomputed-attribute read; never a burn-rate computation on
        # the Filter path — evaluate() runs at ledger drain time)
        extender.slo_alert_source = lambda: slo_engine.alert_tag
    if provenance_tracker is not None and extender.delta_engine is not None:
        # warm≠cold parity guard: every Nth warm hit re-proves the
        # session verdicts against the stateless cold solver and fires
        # the flight recorder on divergence (0 = off)
        extender.delta_engine.parity_interval = (
            install.provenance.parity_check_interval
        )
        extender.delta_engine.parity_hooks = (
            provenance_tracker.on_parity_ok,
            provenance_tracker.on_parity_mismatch,
        )
    if extender.delta_engine is not None:
        # equivalence-class aggregation (Install.classes): the O(1)
        # digest warm tier + class-compressed native solves at scale
        extender.delta_engine.classes_enabled = install.classes.enabled
        extender.delta_engine.classes_min_nodes = install.classes.min_nodes
    marker = UnschedulablePodMarker(
        api,
        node_informer,
        pod_informer,
        tensor_snapshot,
        binpacker,
        timeout_seconds=install.unschedulable_pod_timeout_seconds,
        polling_interval_seconds=unschedulable_polling_interval,
        tracer=tracer,
        metrics=metrics,
    )

    server = Server(
        api=api,
        install=install,
        informer_factory=factory,
        pod_informer=pod_informer,
        node_informer=node_informer,
        rr_informer=rr_informer,
        resource_reservation_cache=rr_cache,
        lazy_demand_informer=lazy_demand_informer,
        demand_cache=demand_cache,
        demand_manager=demand_manager,
        soft_reservation_store=soft_store,
        pod_lister=pod_lister,
        resource_reservation_manager=rrm,
        extender=extender,
        tensor_snapshot=tensor_snapshot,
        unschedulable_marker=marker,
        metrics=metrics,
        event_log=event_log,
        tracer=tracer,
        waste_reporter=waste_reporter,
        resilience=resilience_kit,
        provenance=provenance_tracker,
        capacity=capacity_sampler,
        contention=contention_keeper,
        criticalpath=criticalpath_analyzer,
        policy=policy_engine,
        lifecycle=lifecycle_ledger,
        slo=slo_engine,
    )
    server.reporters = ReporterSet(server)

    # HA failover fabric (ha/): lease election + fencing + takeover
    # reconciliation.  Built AFTER the boot-time journal recovery above
    # on purpose: a cold replica's own replay must not be fenced (the
    # gates are installed here, so everything before this line runs
    # unfenced; everything after is epoch-checked).
    if install.ha.enabled:
        import os as _os
        import socket as _socket

        from ..ha import FencedWriter, FenceState, HAFabric
        from ..ha.lease import LeaderElector
        from ..ha.reconcile import Reconciler

        identity = install.ha.identity or (
            f"{_socket.gethostname()}-{_os.getpid()}"
        )
        fence = FenceState(metrics=metrics)
        elector = LeaderElector(
            api,
            identity,
            fence,
            namespace=install.ha.lease_namespace,
            name=install.ha.lease_name,
            duration_seconds=install.ha.lease_duration_seconds,
        )
        # read-through gate: every fenced write re-reads the lease, so a
        # deposed leader's first post-pause write refuses deterministically
        gate = FencedWriter(fence, lease_reader=elector.peek, metrics=metrics)
        # decision traces carry the epoch they were served under (one
        # lock-free-ish counter read; never a lease fetch on the Filter
        # path)
        extender.epoch_source = fence.epoch
        if lifecycle_ledger is not None:
            # lifecycle records stamp the epoch each transition was
            # observed under (epoch continuity across failover)
            lifecycle_ledger.epoch_source = fence.epoch
        rr_cache.install_fence(gate)
        demand_cache.install_fence(gate)
        if policy_engine is not None and policy_engine.coordinator is not None:
            policy_engine.coordinator.install_fence(gate)
        server.ha = HAFabric(
            elector,
            fence,
            reconciler=Reconciler(server, metrics=metrics),
            metrics=metrics,
            renew_interval_seconds=install.ha.renew_interval_seconds,
            writer=gate,
        )

    from ..scheduler import invariants

    if invariants.enabled():
        # wrap INSIDE the predicate lock so the check always sees
        # quiesced post-predicate state (no races with a concurrent
        # Filter call mid-mutation)
        original = extender._predicate_locked

        def checked_predicate_locked(args):
            result = original(args)
            invariants.check(server, raise_on_violation=False)
            return result

        extender._predicate_locked = checked_predicate_locked
    if start_background:
        server.start_background()
    return server
