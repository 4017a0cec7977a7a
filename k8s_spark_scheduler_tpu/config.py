"""Install-time configuration (reference ``config/config.go:24-84``).

The reference binds ``var/conf/install.yml`` into the Install struct; we
accept the same shape from a dict — the server CLI parses JSON natively
and YAML when pyyaml is installed (the optional ``[yaml]`` extra).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Dict, Optional

from . import compat
from .ops.nodesort import LabelPriorityOrder
from .scheduler.labels import DEFAULT_INSTANCE_GROUP_LABEL


@dataclass
class FifoConfig:
    """config.go:58-64: enforce FIFO only after a driver is older than
    this (seconds), per instance group."""

    default_enforce_after_pod_age: float = 0.0
    enforce_after_pod_age_by_instance_group: Dict[str, float] = field(default_factory=dict)


@dataclass
class AsyncClientConfig:
    """config.go:72-77."""

    max_retry_count: int = 5


@dataclass
class ResilienceConfig:
    """Overload protection / degraded mode (resilience/).

    ``request_deadline_seconds`` mirrors kube-scheduler's extender
    ``httpTimeout`` (examples/extender.yml: 30s); the server answers
    fail-fast ``deadline_margin_seconds`` before the caller hangs up.
    """

    request_deadline_seconds: float = 30.0
    deadline_margin_seconds: float = 1.0
    # concurrent /predicates requests admitted (holding + queued on the
    # extender lock) before excess requests are shed with a retriable
    # failure
    admission_max_waiters: int = 16
    # consecutive API-server write failures before the write-back
    # breaker opens and diverts reservation writes to the intent journal
    breaker_failure_threshold: int = 5
    breaker_cooloff_seconds: float = 30.0
    # durable JSONL intent journal; None keeps intents in memory only
    # (still replayed on in-process recovery, lost on process death)
    journal_path: Optional[str] = None
    # consecutive kernel-lane failures (or over-budget successes) before
    # the lane is demoted to the host/native path
    lane_failure_threshold: int = 3
    lane_cooloff_seconds: float = 60.0
    lane_latency_budget_seconds: Optional[float] = 5.0
    # journal compaction: rewrite the file to pending-only once dead
    # records (acked / superseded puts + ack markers) exceed this
    # fraction of the file, but never below the record floor — small
    # journals aren't worth the rewrite churn
    journal_compact_fraction: float = 0.5
    journal_compact_min_records: int = 64

    @staticmethod
    def from_dict(d: dict) -> "ResilienceConfig":
        return ResilienceConfig(
            request_deadline_seconds=d.get("request-deadline-seconds", 30.0),
            deadline_margin_seconds=d.get("deadline-margin-seconds", 1.0),
            admission_max_waiters=d.get("admission-max-waiters", 16),
            breaker_failure_threshold=d.get("breaker-failure-threshold", 5),
            breaker_cooloff_seconds=d.get("breaker-cooloff-seconds", 30.0),
            journal_path=d.get("journal-path"),
            lane_failure_threshold=d.get("lane-failure-threshold", 3),
            lane_cooloff_seconds=d.get("lane-cooloff-seconds", 60.0),
            lane_latency_budget_seconds=d.get("lane-latency-budget-seconds", 5.0),
            journal_compact_fraction=d.get("journal-compact-fraction", 0.5),
            journal_compact_min_records=d.get("journal-compact-min-records", 64),
        )


@dataclass
class ProvenanceConfig:
    """Decision provenance (provenance/): unschedulability explainer,
    shortfall telemetry, anomaly flight recorder.

    Diagnostic only — decisions are identical enabled or disabled.
    ``bundle_dir`` (or the ``SCHED_PROVENANCE_DIR`` env var) is where
    trigger-fired flight-recorder bundles persist; None keeps the
    bundle ring in memory only.  ``parity_check_interval`` > 0 re-runs
    every Nth warm delta-solve against the stateless cold solver and
    fires the flight recorder on divergence (a full cold solve per
    check — leave 0 in latency-sensitive production)."""

    enabled: bool = True
    ring_size: int = 128
    recorder_size: int = 8
    bundle_dir: Optional[str] = None
    max_bundle_nodes: int = 4096
    parity_check_interval: int = 0
    # per-trigger persist debounce (seconds): an overload-driven trigger
    # storm writes one bundle file per trigger type per interval, not
    # one per failed request
    trigger_min_interval_seconds: float = 30.0

    @staticmethod
    def from_dict(d: dict) -> "ProvenanceConfig":
        return ProvenanceConfig(
            enabled=d.get("enabled", True),
            ring_size=d.get("ring-size", 128),
            recorder_size=d.get("recorder-size", 8),
            bundle_dir=d.get("bundle-dir"),
            max_bundle_nodes=d.get("max-bundle-nodes", 4096),
            parity_check_interval=d.get("parity-check-interval", 0),
            trigger_min_interval_seconds=d.get(
                "trigger-min-interval-seconds", 30.0
            ),
        )


@dataclass
class CapacityConfig:
    """Capacity observatory (capacity/): fragmentation/headroom
    analytics, queue-pressure forecasts, and the ``/state/capacity``
    timeline.  Diagnostic only — no scheduling decision consumes an
    observatory output.

    Sampling is change-triggered (the state layer's ChangeFeed wakes
    the sampler thread, debounced) with ``interval_seconds`` as the
    idle-heartbeat fallback.  Cardinality caps bound both the probe
    cost and the label sets the headroom gauge can emit."""

    enabled: bool = True
    ring_size: int = 256
    debounce_seconds: float = 0.25
    interval_seconds: float = 15.0
    max_shapes: int = 16
    max_group_zones: int = 16
    max_queue: int = 64

    @staticmethod
    def from_dict(d: dict) -> "CapacityConfig":
        return CapacityConfig(
            enabled=d.get("enabled", True),
            ring_size=d.get("ring-size", 256),
            debounce_seconds=d.get("debounce-seconds", 0.25),
            interval_seconds=d.get("interval-seconds", 15.0),
            max_shapes=d.get("max-shapes", 16),
            max_group_zones=d.get("max-group-zones", 16),
            max_queue=d.get("max-queue", 64),
        )


@dataclass
class LifecycleConfig:
    """Gang lifecycle ledger + SLO engine (lifecycle/): per-application
    state machine, burn-rate objectives, and the ``/slo`` +
    ``/lifecycle`` scorecard endpoints.  Diagnostic only — no
    scheduling decision consumes a ledger or SLO output.

    Draining is change-triggered (EventLog emits and the state layer's
    ChangeFeed wake the ledger thread, debounced) with
    ``interval_seconds`` as the idle-heartbeat fallback.
    ``window_scale`` multiplies every SLO alert window (1 h/5 m and
    6 h/30 m) so short virtual sim timelines can compress the policy
    without changing the algebra; ``objectives`` overrides per-objective
    ``target``/``threshold`` (keys: time_to_admit, filter_latency,
    eviction_waste, fairness_gap)."""

    enabled: bool = True
    ring_size: int = 2048
    debounce_seconds: float = 0.05
    interval_seconds: float = 5.0
    window_scale: float = 1.0
    sample_cap: int = 4096
    objectives: Dict[str, Dict[str, float]] = field(default_factory=dict)

    @staticmethod
    def from_dict(d: dict) -> "LifecycleConfig":
        return LifecycleConfig(
            enabled=d.get("enabled", True),
            ring_size=d.get("ring-size", 2048),
            debounce_seconds=d.get("debounce-seconds", 0.05),
            interval_seconds=d.get("interval-seconds", 5.0),
            window_scale=d.get("window-scale", 1.0),
            sample_cap=d.get("sample-cap", 4096),
            objectives=d.get("objectives", {}),
        )


@dataclass
class ContentionConfig:
    """Contention observatory (contention/): lock wait/hold telemetry
    and per-request critical-path decomposition behind
    ``/debug/contention`` + ``/debug/criticalpath``.  Diagnostic only.

    ``enabled`` turns the process-wide timekeeper on (TimedLock
    wrappers exist regardless; disabled they cost one attribute read
    per acquire).  ``ring_size`` bounds the per-request decomposition
    ring; ``sample_every`` is the uncontended-acquire sampling stride
    for ``@guarded_by`` locks (contended acquires always record)."""

    enabled: bool = True
    ring_size: int = 256
    sample_every: int = 64

    @staticmethod
    def from_dict(d: dict) -> "ContentionConfig":
        return ContentionConfig(
            enabled=d.get("enabled", True),
            ring_size=d.get("ring-size", 256),
            sample_every=d.get("sample-every", 64),
        )


@dataclass
class PolicyConfig:
    """Scheduling-policy engine (policy/): priority classes, pluggable
    queue ordering, conservative backfill, gang-aware preemption, and
    DRF fair share.

    ``enabled=False`` (the default) constructs no engine at all —
    extender decisions are byte-identical to pre-policy behavior
    (pinned by tests/test_policy.py).  ``ordering`` is one of ``fifo``,
    ``priority-then-fifo``, ``drf``; ``bands`` maps band name → rank
    (higher = more important) read from the driver pod's ``band_label``
    label.  Preemption evicts WHOLE applications only, each victim set
    validated by a what-if solve and journaled before any delete."""

    enabled: bool = False
    ordering: str = "fifo"
    band_label: str = "spark-priority-band"
    bands: Dict[str, int] = field(
        default_factory=lambda: {"low": 0, "normal": 1, "high": 2}
    )
    default_band: str = "normal"
    tenant_label: str = "spark-tenant"
    preemption_enabled: bool = False
    # a preemptor must outrank a victim by at least this many bands
    preemption_min_band_gap: int = 1
    max_victims: int = 4
    backfill: bool = False
    # backfill may never skip a queue head older than this (I-P3)
    starvation_age_seconds: float = 600.0
    tenant_weights: Dict[str, float] = field(default_factory=dict)
    recent_evictions: int = 64

    @staticmethod
    def from_dict(d: dict) -> "PolicyConfig":
        return PolicyConfig(
            enabled=d.get("enabled", False),
            ordering=d.get("ordering", "fifo"),
            band_label=d.get("band-label", "spark-priority-band"),
            bands=dict(d.get("bands", {"low": 0, "normal": 1, "high": 2})),
            default_band=d.get("default-band", "normal"),
            tenant_label=d.get("tenant-label", "spark-tenant"),
            preemption_enabled=d.get("preemption-enabled", False),
            preemption_min_band_gap=d.get("preemption-min-band-gap", 1),
            max_victims=d.get("max-victims", 4),
            backfill=d.get("backfill", False),
            starvation_age_seconds=d.get("starvation-age-seconds", 600.0),
            tenant_weights=dict(d.get("tenant-weights", {})),
            recent_evictions=d.get("recent-evictions", 64),
        )


@dataclass
class HAConfig:
    """HA failover fabric (ha/): lease-fenced multi-replica operation.

    Disabled (the default) wires nothing — no elector, no fence gates,
    single-replica behavior byte-identical to pre-HA builds.  Enabled,
    the replica elects over a coordination lease, stamps every fenced
    write with its epoch, and runs full state reconciliation on
    takeover.
    """

    enabled: bool = False
    lease_namespace: str = "default"
    lease_name: str = "tpu-gang-scheduler"
    # how stale a lease may go before a candidate may steal it; mirrors
    # client-go's LeaseDuration default (resource.go:57-59)
    lease_duration_seconds: float = 15.0
    # background renewal cadence (prod); the sim and tests step the
    # elector manually under the virtual clock
    renew_interval_seconds: float = 5.0
    # replica identity on the lease; "" = <hostname>-<pid> at wiring
    identity: str = ""
    # start the background renewal thread from start_background();
    # the sim/tests disable this and drive fabric.step() themselves
    background: bool = True

    @staticmethod
    def from_dict(d: dict) -> "HAConfig":
        return HAConfig(
            enabled=d.get("enabled", False),
            lease_namespace=d.get("lease-namespace", "default"),
            lease_name=d.get("lease-name", "tpu-gang-scheduler"),
            lease_duration_seconds=d.get("lease-duration-seconds", 15.0),
            renew_interval_seconds=d.get("renew-interval-seconds", 5.0),
            identity=d.get("identity", ""),
            background=d.get("background", True),
        )


@dataclass
class ClassesConfig:
    """Equivalence-class node aggregation (ROADMAP 2): class-compressed
    native solves, the O(1) class-digest warm tier in the delta-solve
    engine, and per-class observatory analytics.

    Decisions are byte-identical enabled or disabled — the compressed
    solver expands to concrete nodes at bind time and the property
    suite (tests/test_class_compression.py) pins parity — so
    ``enabled`` is an operator kill switch, not a semantics switch.
    ``min_nodes`` keeps the compressed session solver off small fleets
    where partition upkeep isn't worth it (the 10k perf-gate lanes run
    the row-level path unchanged)."""

    enabled: bool = True
    min_nodes: int = 20000

    @staticmethod
    def from_dict(d: dict) -> "ClassesConfig":
        return ClassesConfig(
            enabled=d.get("enabled", True),
            min_nodes=d.get("min-nodes", 20000),
        )


@dataclass
class ConversionWebhookConfig:
    """Where the apiserver reaches the CRD conversion webhook (the
    reference wires this from the witchcraft server's service identity,
    conversionwebhook/resource_reservation.go:44-98).  ca_bundle_file
    holds the PEM CA the apiserver must trust — conversion is HTTPS-only
    on a real cluster."""

    service_namespace: str = "spark"
    service_name: str = "spark-scheduler"
    service_port: int = 443
    path: str = "/convert"
    ca_bundle_file: Optional[str] = None


@dataclass
class Install:
    """config.go:24-47."""

    fifo: bool = False
    fifo_config: FifoConfig = field(default_factory=FifoConfig)
    qps: float = 0.0
    burst: int = 0
    binpack_algo: str = "distribute-evenly"
    should_schedule_dynamically_allocated_executors_in_same_az: bool = False
    instance_group_label: str = DEFAULT_INSTANCE_GROUP_LABEL
    async_client: AsyncClientConfig = field(default_factory=AsyncClientConfig)
    unschedulable_pod_timeout_seconds: float = 600.0
    driver_prioritized_node_label: Optional[LabelPriorityOrder] = None
    executor_prioritized_node_label: Optional[LabelPriorityOrder] = None
    resource_reservation_crd_annotations: Dict[str, str] = field(default_factory=dict)
    conversion_webhook: Optional[ConversionWebhookConfig] = None
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    # replicate the reference's accidental-but-load-bearing behaviors
    # (see compat.py for the list); off = corrected semantics
    strict_reference_parity: bool = compat.DEFAULT_STRICT
    # incremental delta-solve engine (ops/deltasolve.py): persistent
    # native solver sessions + prefix-feasibility reuse on the driver
    # fast path.  Decisions are identical either way (the kill switch
    # exists for operators, not semantics).
    delta_solve: bool = True
    # decision provenance: explainer + shortfall telemetry + flight
    # recorder (provenance/) — diagnostic only, decisions unchanged
    provenance: ProvenanceConfig = field(default_factory=ProvenanceConfig)
    # capacity observatory: fragmentation/headroom analytics and the
    # /state/capacity timeline (capacity/) — diagnostic only
    capacity: CapacityConfig = field(default_factory=CapacityConfig)
    # contention observatory: lock wait/hold telemetry + critical-path
    # decomposition (contention/) — diagnostic only
    contention: ContentionConfig = field(default_factory=ContentionConfig)
    # scheduling policy: priority bands, ordering, backfill, preemption,
    # DRF (policy/) — disabled = byte-identical FIFO decisions
    policy: PolicyConfig = field(default_factory=PolicyConfig)
    # HA failover fabric: leader election + fencing + takeover
    # reconciliation (ha/) — disabled = single-replica, nothing wired
    ha: HAConfig = field(default_factory=HAConfig)
    # gang lifecycle ledger + SLO burn-rate engine (lifecycle/) —
    # diagnostic only, decisions unchanged
    lifecycle: LifecycleConfig = field(default_factory=LifecycleConfig)
    # equivalence-class aggregation: class-compressed solves at scale +
    # class-digest warm tier (state/classindex.py, ops/deltasolve.py) —
    # byte-identical decisions either way
    classes: ClassesConfig = field(default_factory=ClassesConfig)

    @staticmethod
    def from_dict(d: dict) -> "Install":
        fifo_cfg = d.get("fifo-config", {})
        driver_label = d.get("driver-prioritized-node-label")
        executor_label = d.get("executor-prioritized-node-label")
        if "concurrent" in d:
            logging.getLogger(__name__).warning(
                "install config: the 'concurrent:' block is ignored — the "
                "concurrent admission engine was removed; decisions are unchanged"
            )
        return Install(
            fifo=d.get("fifo", False),
            fifo_config=FifoConfig(
                default_enforce_after_pod_age=fifo_cfg.get(
                    "default-enforce-after-pod-age-seconds", 0.0
                ),
                enforce_after_pod_age_by_instance_group=fifo_cfg.get(
                    "enforce-after-pod-age-by-instance-group", {}
                ),
            ),
            qps=d.get("qps", 0.0),
            burst=d.get("burst", 0),
            binpack_algo=d.get("binpack", "distribute-evenly"),
            should_schedule_dynamically_allocated_executors_in_same_az=d.get(
                "should-schedule-dynamically-allocated-executors-in-same-az", False
            ),
            # back-compat default (cmd/server.go:67-71)
            instance_group_label=d.get("instance-group-label", DEFAULT_INSTANCE_GROUP_LABEL),
            async_client=AsyncClientConfig(
                max_retry_count=d.get("async-client", {}).get("max-retry-count", 5)
            ),
            unschedulable_pod_timeout_seconds=d.get(
                "unschedulable-pod-timeout-seconds", 600.0
            ),
            driver_prioritized_node_label=(
                LabelPriorityOrder(
                    driver_label["name"], driver_label["descending-priority-values"]
                )
                if driver_label
                else None
            ),
            executor_prioritized_node_label=(
                LabelPriorityOrder(
                    executor_label["name"], executor_label["descending-priority-values"]
                )
                if executor_label
                else None
            ),
            resource_reservation_crd_annotations=d.get(
                "resource-reservation-crd-annotations", {}
            ),
            # only present keys are passed so the dataclass defaults stay
            # the single source of truth
            conversion_webhook=(
                ConversionWebhookConfig(
                    **{
                        field_name: wh[key]
                        for key, field_name in (
                            ("service-namespace", "service_namespace"),
                            ("service-name", "service_name"),
                            ("service-port", "service_port"),
                            ("path", "path"),
                            ("ca-bundle-file", "ca_bundle_file"),
                        )
                        if key in wh
                    }
                )
                if (wh := d.get("conversion-webhook")) is not None
                else None
            ),
            strict_reference_parity=d.get(
                "strict-reference-parity", compat.DEFAULT_STRICT
            ),
            delta_solve=d.get("delta-solve", True),
            resilience=ResilienceConfig.from_dict(d.get("resilience", {})),
            provenance=ProvenanceConfig.from_dict(d.get("provenance", {})),
            capacity=CapacityConfig.from_dict(d.get("capacity", {})),
            contention=ContentionConfig.from_dict(d.get("contention", {})),
            policy=PolicyConfig.from_dict(d.get("policy", {})),
            ha=HAConfig.from_dict(d.get("ha", {})),
            lifecycle=LifecycleConfig.from_dict(d.get("lifecycle", {})),
            classes=ClassesConfig.from_dict(d.get("classes", {})),
        )
