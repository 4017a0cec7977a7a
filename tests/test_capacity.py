"""Capacity observatory (ISSUE 7): probe/solver agreement, the
fragmentation report, the ChangeFeed-triggered sampler, forecasts, and
the cardinality/lock-discipline contracts.

The load-bearing property is probe/solver AGREEMENT: any gang the
headroom probe calls feasible must be admitted by the real solver on
the same state, and headroom+1 must be refused — across all three queue
policies (tightly-pack, distribute-evenly, minimal-fragmentation),
whose feasibility rule the probe replicates exactly.
"""

import threading

import numpy as np
import pytest

from k8s_spark_scheduler_tpu import capacity as cap_pkg
from k8s_spark_scheduler_tpu import timesource
from k8s_spark_scheduler_tpu.capacity import CapacitySampler
from k8s_spark_scheduler_tpu.capacity.probe import (
    DEFAULT_K_MAX,
    frag_report,
    probe_headroom,
    probe_headroom_numpy,
)
from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.metrics.registry import MetricsRegistry
from k8s_spark_scheduler_tpu.native.fifo import (
    native_fifo_available,
    native_probe_available,
    probe_headroom_native,
    solve_packed_cold,
)
from k8s_spark_scheduler_tpu.testing.harness import Harness

POLICIES = (0, 1, 2)  # tightly-pack, distribute-evenly, min-frag


def _random_problem(seed, n=400, n_shapes=6):
    rng = np.random.RandomState(seed)
    avail = rng.randint(-5, 300, size=(n, 3)).astype(np.int32)
    rank = np.arange(n, dtype=np.int32)
    rng.shuffle(rank)
    # some nodes are driver-only / executor-ineligible
    rank[rng.rand(n) < 0.2] = 2**31 - 1
    exec_ok = rng.rand(n) > 0.15
    shapes = np.hstack(
        [rng.randint(0, 5, size=(n_shapes, 3)), rng.randint(1, 7, size=(n_shapes, 3))]
    ).astype(np.int32)
    return avail, rank, exec_ok, shapes


@pytest.mark.skipif(
    not native_fifo_available(), reason="native toolchain unavailable"
)
def test_probe_solver_agreement_5_seeds_x_3_policies():
    """ISSUE 7 acceptance: for 5 random seeds × 3 policies, every
    (shape, count ≤ probed headroom) gang admits and every
    (shape, headroom+1) gang is refused on the same snapshot."""
    assert native_probe_available()
    K = 100_000
    for seed in range(5):
        avail, rank, exec_ok, shapes = _random_problem(20260804 + seed)
        headroom, usable, probes = probe_headroom_native(
            avail, rank, exec_ok, shapes, K
        )
        rng = np.random.RandomState(seed)
        for policy in POLICIES:
            for s in range(shapes.shape[0]):
                h = int(headroom[s])
                checks = []
                if h > 0:
                    checks.append((h, True))
                    checks.append((rng.randint(1, h + 1), True))
                if h < K:
                    checks.append((h + 1, False))
                if h == 0:
                    checks.append((1, False))
                for k, want in checks:
                    app = (
                        np.concatenate([shapes[s], [k, 1]])
                        .astype(np.int32)
                        .reshape(1, 8)
                    )
                    feas, _, _ = solve_packed_cold(
                        policy, avail, rank, exec_ok, app
                    )
                    assert bool(feas[0]) == want, (
                        seed, policy, s, k, h, want
                    )
        # bisection cost stays a handful of solves per shape
        assert int(probes.max()) <= 2 + int(np.ceil(np.log2(K))) + 1


@pytest.mark.skipif(
    not native_probe_available(), reason="native probe unavailable"
)
def test_probe_numpy_twin_matches_native():
    """The numpy fallback and the native lane are the same math."""
    for seed in (1, 2, 3):
        avail, rank, exec_ok, shapes = _random_problem(seed, n=200)
        native = probe_headroom_native(avail, rank, exec_ok, shapes, 50_000)
        twin = probe_headroom_numpy(
            avail.astype(np.int64), rank, exec_ok, shapes.astype(np.int64),
            50_000,
        )
        np.testing.assert_array_equal(native[0], twin[0])
        np.testing.assert_array_equal(native[1], twin[1])


def test_probe_dispatcher_scales_base_units():
    """The dispatcher probes base-unit int64 rows (milli-cpu / bytes):
    headroom is scale-invariant and usable comes back in base units."""
    avail = np.array(
        [[8000, 8 << 30, 0], [8000, 8 << 30, 0]], dtype=np.int64
    )
    rank = np.zeros(2, dtype=np.int64)
    exec_ok = np.ones(2, dtype=bool)
    # driver 1cpu/1Gi, executor 1cpu/1Gi
    shapes = np.array(
        [[1000, 1 << 30, 0, 1000, 1 << 30, 0]], dtype=np.int64
    )
    headroom, usable, probes, lane = probe_headroom(
        avail, rank, exec_ok, shapes, DEFAULT_K_MAX
    )
    # 16 executor slots total, driver consumes one slot's worth on its
    # node: the solver admits at most 15 executors alongside the driver
    assert int(headroom[0]) == 15
    assert int(usable[0][0]) == 16000  # base milli-cpu reachable
    assert lane in ("native", "numpy")


@pytest.mark.skipif(
    not native_fifo_available(), reason="native toolchain unavailable"
)
def test_frag_report_native_lane_matches_numpy_twin():
    """frag_report's one-sweep native lane (GCD-scaled int32 rows,
    totals unscaled back) agrees exactly with the numpy twin on
    base-unit int64 rows."""
    from k8s_spark_scheduler_tpu.native import scale_rows_int32
    from k8s_spark_scheduler_tpu.native.fifo import frag_report_native

    rng = np.random.RandomState(7)
    for _ in range(5):
        n = 50
        avail = rng.randint(-3, 40, size=(n, 3)).astype(np.int64) * (1 << 28)
        mask = rng.rand(n) > 0.2
        # the dispatcher's answer (native lane when it engages)
        total, largest, free_nodes, overdrawn, frag = frag_report(avail, mask)
        # the pure numpy twin, computed by hand
        rows = avail[mask]
        pos = np.maximum(rows, 0)
        np.testing.assert_array_equal(total, pos.sum(axis=0))
        np.testing.assert_array_equal(largest, pos.max(axis=0))
        np.testing.assert_array_equal(free_nodes, (rows > 0).sum(axis=0))
        np.testing.assert_array_equal(overdrawn, (rows < 0).sum(axis=0))
        # and the native symbol really is reachable on this input
        ok, avail_s, _, scale = scale_rows_int32(
            avail, np.zeros((0, 3), dtype=np.int64), n
        )
        assert ok
        out = frag_report_native(avail_s[:n], mask)
        assert out is not None
        np.testing.assert_array_equal(out[:, 0] * scale, total)
        np.testing.assert_array_equal(out[:, 1] * scale, largest)


def test_frag_report_math():
    avail = np.array(
        [[10, 100, 0], [5, 50, 0], [-3, 0, 0]], dtype=np.int64
    )
    exec_ok = np.array([True, True, True])
    total, largest, free_nodes, overdrawn, frag = frag_report(avail, exec_ok)
    assert total.tolist() == [15, 150, 0]
    assert largest.tolist() == [10, 100, 0]
    assert free_nodes.tolist() == [2, 2, 0]
    assert overdrawn.tolist() == [1, 0, 0]
    assert frag[0] == pytest.approx(1.0 - 10 / 15)
    assert frag[2] == 0.0
    # ineligible rows don't count
    total2, _, _, _, _ = frag_report(avail, np.array([True, False, True]))
    assert total2.tolist() == [10, 100, 0]


# -- sampler ------------------------------------------------------------------


def test_sampler_seq_gating_ring_bounds_and_diff():
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.server.capacity.stop()  # drive sampling explicitly
        sampler = CapacitySampler(
            h.server.tensor_snapshot,
            pod_lister=h.server.pod_lister,
            waste_reporter=h.server.waste_reporter,
            metrics=h.server.metrics,
            instance_group_label=h.server.install.instance_group_label,
            ring_size=4,
        )
        h.new_node("n1", zone="z1")
        h.new_node("n2", zone="z2")
        first = sampler.maybe_sample(trigger="t")
        assert first is not None and first.nodes == 2
        # unchanged feed → O(1) skip
        assert sampler.maybe_sample(trigger="t") is None
        assert sampler.stats()["skipped_unchanged"] == 1
        # two zones → two (group, zone) combos with their own frag
        assert len(first.groups) == 2
        # ring stays bounded under node churn
        for i in range(10):
            h.new_node(f"extra-{i}", zone="z1")
            sampler.maybe_sample(trigger="churn")
        assert sampler.stats()["ring"] <= 4
        history = sampler.history(limit=2)
        assert len(history) == 2
        # newest first
        assert history[0].seq >= history[1].seq
        # diff across a node-structure change
        d = sampler.diff(history[1].seq, history[0].seq)
        assert d is not None and d["structureChanged"] is True
        assert d["nodes"] == history[0].nodes - history[1].nodes
        # unknown seqs → None
        assert sampler.diff(-1, history[0].seq) is None
    finally:
        h.close()


def test_sampler_refuses_to_probe_under_predicate_lock():
    """ISSUE 7 acceptance: the sampler runs ZERO solves while the
    extender lock is held — an in-lock invocation is refused and
    counted, never served."""
    h = Harness()
    try:
        h.new_node("n1")
        sampler = h.server.capacity
        sampler.stop()
        cap_pkg.enter_predicate_lock()
        try:
            assert sampler.sample_now(trigger="in-lock") is None
        finally:
            cap_pkg.exit_predicate_lock()
        assert sampler.lock_violations == 1
        # off-lock sampling works again immediately
        assert sampler.sample_now(trigger="off-lock") is not None
        assert sampler.lock_violations == 1
    finally:
        h.close()


def test_sampler_lock_flag_is_set_during_predicates():
    """The extender actually marks lock tenure: a probe attempted from
    inside a Filter decision must hit the refusal path."""
    h = Harness()
    seen = []
    try:
        h.new_node("n1")
        h.new_node("n2")
        sampler = h.server.capacity
        sampler.stop()
        extender = h.server.extender
        original = extender._predicate_locked

        def probing_predicate(args):
            seen.append(cap_pkg.in_predicate_lock())
            assert sampler.sample_now(trigger="inside") is None
            return original(args)

        extender._predicate_locked = probing_predicate
        driver = h.static_allocation_spark_pods("app-lockflag", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))
        assert seen == [True]
        assert sampler.lock_violations >= 1
        assert not cap_pkg.in_predicate_lock()
    finally:
        h.close()


def test_sampler_queue_forecast_states_and_pressure():
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        sampler = h.server.capacity
        sampler.stop()
        h.new_node("n1", cpu="8", memory="8Gi")
        h.new_node("n2", cpu="8", memory="8Gi")

        # a gang that cannot fit (32 cpu of executors on a 16-cpu
        # cluster) stays pending and creates a demand
        big = h.static_allocation_spark_pods(
            "app-big", 8, executor_cpu="4", executor_mem="1Gi"
        )[0]
        result = h.schedule(big, ["n1", "n2"])
        assert result.failed_nodes
        sample = sampler.sample_now(trigger="test")
        assert sample is not None
        assert sample.queued_gangs == 1
        assert sample.pressure == 1
        (entry,) = sample.queue
        assert entry["pod"] == big.name
        assert entry["state"] == "needs-scaleup"
        assert entry["fitsNow"] is False
        assert entry["forecastSeconds"] is None
        assert entry["gangSize"] == 8
        assert entry["headroom"] < 8
        # the waste reporter has seen the failed attempt + demand
        assert entry.get("demandState") in (
            "demand-pending", "demand-fulfilled", "no-demand"
        )

        # a fitting gang forecasts admission
        small = h.static_allocation_spark_pods("app-small", 1)[0]
        h.create_pod(small)
        sample2 = sampler.sample_now(trigger="test2")
        by_pod = {e["pod"]: e for e in sample2.queue}
        assert by_pod[small.name]["fitsNow"] is True
        assert by_pod[small.name]["state"] in (
            "admitting-next", "queued-behind"
        )
        # no admissions observed yet: a queued-behind wait is UNKNOWN
        # (null), never 0.0 — only admitting-next forecasts 0.0
        if by_pod[small.name]["state"] == "queued-behind":
            assert by_pod[small.name]["forecastSeconds"] is None
        assert sample2.pressure == 1  # still only the big gang
    finally:
        h.close()


def test_sampler_queue_truncation_is_counted():
    """Pending drivers beyond max_queue are dropped from the forecast
    list but counted (queueTruncated), never silently — and pressure
    still covers ALL pending gangs, not just the emitted entries."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.server.capacity.stop()
        sampler = CapacitySampler(
            h.server.tensor_snapshot,
            pod_lister=h.server.pod_lister,
            instance_group_label=h.server.install.instance_group_label,
            max_queue=2,
        )
        h.new_node("n1", cpu="8", memory="8Gi")
        for i in range(5):
            # 16-cpu executors can never fit the 8-cpu node: all five
            # gangs are backlog
            h.create_pod(
                h.static_allocation_spark_pods(
                    f"app-q{i}", 1, executor_cpu="16"
                )[0]
            )
        sample = sampler.sample_now(trigger="test")
        assert sample.queued_gangs == 5
        assert len(sample.queue) == 2
        assert sample.queue_truncated == 3
        assert sample.to_dict()["queueTruncated"] == 3
        # the autoscaler-facing signal must NOT cap at max_queue
        assert sample.pressure == 5
    finally:
        h.close()


def test_forecast_rate_spans_the_departure_interval():
    """The admission rate divides departures by the inter-sample
    interval they happened in, not by the instant since they were
    observed — a single departure batch must not make every queued
    gang forecast ~0 seconds."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    t = [1000.0]
    timesource.set_source(lambda: t[0])
    try:
        h.server.capacity.stop()
        sampler = CapacitySampler(
            h.server.tensor_snapshot,
            pod_lister=h.server.pod_lister,
            instance_group_label=h.server.install.instance_group_label,
        )
        h.new_node("n1", cpu="32", memory="64Gi")
        first = h.static_allocation_spark_pods("app-r0", 1)[0]
        h.create_pod(first)
        pods = [
            h.static_allocation_spark_pods(f"app-r{i}", 1)[0]
            for i in range(1, 4)
        ]
        for p in pods:
            h.create_pod(p)
        sampler.sample_now(trigger="t0")  # anchors the interval at t=1000

        # one gang departs over a 50s interval...
        t[0] = 1050.0
        h.delete_pod(first)
        sample = sampler.sample_now(trigger="t1")
        by_pos = {e["queuePosition"]: e for e in sample.queue}
        # ...so rate = 1/50 gangs/s and position 1 forecasts ~50s — the
        # old observation-time anchoring would have given ~0s
        f = by_pos[1]["forecastSeconds"]
        assert f is not None and f >= 25.0, sample.queue
    finally:
        timesource.reset()
        h.close()


def test_concurrent_samples_keep_ring_ordered():
    """An HTTP freshen racing the background thread must not interleave
    ring appends: whole samples are serialized, so seqs stay
    nondecreasing and newest-last."""
    import concurrent.futures

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        sampler = h.server.capacity
        sampler.stop()
        h.new_node("n0")

        def churn_and_sample(i):
            h.new_node(f"cc-{i}")
            return sampler.sample_now(trigger=f"t{i}")

        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
            list(ex.map(churn_and_sample, range(8)))
        seqs = [s.seq for s in sampler.timeline()]
        assert seqs == sorted(seqs)
        assert len(seqs) == len(set(seqs))
    finally:
        h.close()


def test_capacity_label_cardinality_budget():
    """Satellite: the per-(instance-group, zone, shape) capacity labels
    stay under a configured budget — the sampler truncates (and counts)
    instead of exploding the registry."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.server.capacity.stop()
        metrics = MetricsRegistry()
        sampler = CapacitySampler(
            h.server.tensor_snapshot,
            pod_lister=h.server.pod_lister,
            metrics=metrics,
            instance_group_label="zone-group",
            max_shapes=4,
            max_group_zones=6,
        )
        # 12 distinct (group, zone) combos, 6 queued gang shapes
        for i in range(12):
            h.new_node(
                f"n{i:02d}", zone=f"z{i % 12}", cpu="32", memory="64Gi"
            )
        for i in range(6):
            pod = h.static_allocation_spark_pods(
                f"app-shape-{i}", 1, executor_cpu=str(i + 1)
            )[0]
            h.create_pod(pod)
        sample = sampler.sample_now(trigger="test")
        assert sample.groups_dropped == 6
        assert sample.shapes_dropped >= 1
        assert len(sample.groups) == 6
        assert len(sample.headroom) <= 4
        series = metrics.series_stats()
        budget = (6 + 1) * 4  # (combos + cluster-wide) × shapes
        assert series.get(mnames.CAPACITY_HEADROOM, 0) <= budget
        # fragmentation gauges are per-dim only — never per group
        assert series.get(mnames.CAPACITY_FRAGMENTATION, 0) == 3

        # shapes churn: once the queue drains, the next sample PRUNES
        # the vanished (shape, group, zone) series instead of exporting
        # their last values forever — live cardinality tracks the
        # sampler caps, cumulatively, not just per sample
        for pod in list(h.api.list("Pod")):
            h.delete_pod(pod)
        sample2 = sampler.sample_now(trigger="drained")
        assert len(sample2.headroom) == 1  # the default canary shape
        series2 = metrics.series_stats()
        assert series2.get(mnames.CAPACITY_HEADROOM, 0) == 1 + len(
            sample2.groups
        )
    finally:
        h.close()


def test_registry_series_gauge_reports_cardinality():
    """Satellite: …tpu.metrics.registry.series reports per-metric
    label-set cardinality (the label-explosion canary)."""
    h = Harness()
    try:
        h.new_node("n1")
        metrics = h.server.metrics
        metrics.counter("foundry.spark.scheduler.requests", {"outcome": "a"})
        metrics.counter("foundry.spark.scheduler.requests", {"outcome": "b"})
        h.server.reporters.report_registry_series()
        g = metrics.get_gauge(
            mnames.METRICS_REGISTRY_SERIES,
            {"metric": "foundry.spark.scheduler.requests"},
        )
        assert g is not None and g >= 2
        # the canary never counts itself (it would ratchet forever)
        assert (
            metrics.get_gauge(
                mnames.METRICS_REGISTRY_SERIES,
                {"metric": mnames.METRICS_REGISTRY_SERIES},
            )
            is None
        )
        # a vanished metric name stops exporting its stale series count
        with metrics._lock:
            for k in [
                k
                for k in metrics._counters
                if k[0] == "foundry.spark.scheduler.requests"
            ]:
                del metrics._counters[k]
        h.server.reporters.report_registry_series()
        assert (
            metrics.get_gauge(
                mnames.METRICS_REGISTRY_SERIES,
                {"metric": "foundry.spark.scheduler.requests"},
            )
            is None
        )
    finally:
        h.close()


def test_changefeed_wakeup_event_fires_on_publish():
    h = Harness()
    try:
        wake = threading.Event()
        h.server.tensor_snapshot.feed.attach_wakeup(wake)
        assert not wake.is_set()
        h.new_node("n-wake")
        assert wake.wait(timeout=5.0)
    finally:
        h.close()


# -- waste phases under the virtual clock (satellite) ------------------------


def test_waste_cleanup_fires_on_sim_time_not_wall_time():
    """The 6h DEMAND_FULFILLED_AGE_CLEANUP_SECONDS horizon must be
    measured in semantic (virtual) time: entries created at virtual t0
    survive cleanup until the virtual clock passes t0+6h, regardless of
    wall time."""
    from k8s_spark_scheduler_tpu.metrics.waste import (
        DEMAND_FULFILLED_AGE_CLEANUP_SECONDS,
        WasteMetricsReporter,
    )
    from k8s_spark_scheduler_tpu.types.objects import ObjectMeta, Pod

    t = [1_000_000.0]
    timesource.set_source(lambda: t[0])
    try:
        reporter = WasteMetricsReporter(MetricsRegistry(), "zone-group")
        pod = Pod(meta=ObjectMeta(name="w-driver", namespace="ns"))
        reporter.mark_failed_scheduling_attempt(pod, "failure-fit")
        assert reporter.scheduling_info("ns", "w-driver") is not None

        # wall time passes, virtual time doesn't: nothing is cleaned
        reporter.cleanup_metric_cache()
        assert reporter.scheduling_info("ns", "w-driver") is not None

        # just before the virtual horizon: still retained
        t[0] += DEMAND_FULFILLED_AGE_CLEANUP_SECONDS - 1.0
        reporter.cleanup_metric_cache()
        assert reporter.scheduling_info("ns", "w-driver") is not None

        # past the virtual horizon: cleaned
        t[0] += 2.0
        reporter.cleanup_metric_cache()
        assert reporter.scheduling_info("ns", "w-driver") is None
    finally:
        timesource.reset()


def test_sim_summary_carries_capacity_and_waste_columns():
    """The runner folds the capacity timeline + waste phase durations
    into the summary JSON (the first ROADMAP-5 scorecard columns), and
    the sampler ran zero solves under the extender lock."""
    from k8s_spark_scheduler_tpu.sim import Scenario, Simulation

    sc = Scenario.from_dict(
        {
            "name": "capacity-smoke",
            "seed": 11,
            "duration": 120,
            "retry_interval": 15,
            "fifo": True,
            "binpack_algo": "tpu-batch",
            "cluster": {"nodes": 3, "cpu": "8", "memory": "16Gi", "zones": ["z1"]},
            "workload": {
                "process": "poisson",
                "rate_per_min": 3,
                "executors": {"min": 1, "max": 3},
                "lifetime": {"min": 30, "max": 60},
            },
        }
    )
    result = Simulation(sc).run()
    assert result.violations == []
    capsum = result.summary["capacity"]
    assert capsum is not None and capsum["samples"] > 0
    assert capsum["lock_violations"] == 0
    assert 0.0 <= capsum["fragmentation_max_dim"]["max"] <= 1.0
    assert capsum["headroom_executors"]["p50"] >= 0
    assert capsum["queue_pressure"]["max"] >= 0
    # the timeline artifact is non-empty, bounded, and ordered
    assert result.capacity_timeline
    assert len(result.capacity_timeline) == capsum["timeline_ring"]
    seqs = [s["seq"] for s in result.capacity_timeline]
    assert seqs == sorted(seqs)
    assert "waste_phases" in result.summary


# -- one sample in arrays, against the plain loops it replaced (PR 39) ---------


class _PlainLoops(CapacitySampler):
    """The sampler with PR 38's ``_gang_rows``, ``_per_group`` and
    ``_tenants`` bodies: a loop over the pending gangs converting each
    demand twice, and two loops over the nodes.  The oracle the arrays
    are held to, field for field."""

    @property
    def _group_label(self):  # the sampler keeps its label in the group index
        return self._groups._label

    def _gang_rows(self, pending):
        return [self._gang_rows_of(pod) for pod in pending], 0

    def _gang_rows_of(self, pod):
        try:
            from k8s_spark_scheduler_tpu.ops.tensorize import _resources_to_base
            from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_app_demand_cached

            _, demand = spark_app_demand_cached(pod)
            drow, de = _resources_to_base(demand.driver_resources)
            erow, ee = _resources_to_base(demand.executor_resources)
            if not (de and ee):
                return None
            return (
                tuple(int(x) for x in drow),
                tuple(int(x) for x in erow),
                int(demand.min_executor_count),
            )
        except Exception:
            return None

    def _per_group(self, snap, group_ids, group_names, avail, eligible, shape_list, shape_rows, sample):
        combos = {}
        for i in range(len(snap.names)):
            group = snap.labels[i].get(self._group_label, "")
            zone = (
                snap.zone_names[snap.zone_id[i]]
                if 0 <= snap.zone_id[i] < len(snap.zone_names)
                else ""
            )
            combos.setdefault((group, zone), []).append(i)
        ordered = sorted(combos.items())
        if len(ordered) > self.max_group_zones:
            sample.groups_dropped = len(ordered) - self.max_group_zones
            ordered = ordered[: self.max_group_zones]
        for (group, zone), rows in ordered:
            idx = np.array(rows, dtype=np.int64)
            sub_avail = avail[idx]
            sub_elig = eligible[idx]
            total, largest, _, _, frag = frag_report(sub_avail, sub_elig)
            entry = {
                "nodes": len(rows),
                "readyNodes": int(sub_elig.sum()),
                "free": [int(x) for x in total],
                "largestChunk": [int(x) for x in largest],
                "fragIndex": [round(float(x), 6) for x in frag],
                "headroom": {},
            }
            if sub_elig.any():
                rank = np.where(sub_elig, np.int64(0), np.int64(2**31 - 1))
                headroom, _, probes, _ = probe_headroom(sub_avail, rank, sub_elig, shape_rows, self.k_max)
                sample.probe_solves += int(probes.sum())
                for i, (key, _) in enumerate(shape_list):
                    entry["headroom"][key] = int(headroom[i])
            sample.groups["|".join((group, zone))] = entry

    def _tenants(self, snap, group_ids, group_names, sample):
        groups = {}
        usage = snap.usage
        alloc = snap.allocatable
        cluster_used = np.maximum(usage, 0).sum(axis=0)
        for i in range(len(snap.names)):
            group = snap.labels[i].get(self._group_label, "")
            g = groups.get(group)
            if g is None:
                g = groups[group] = {
                    "used": np.zeros(3, dtype=np.int64),
                    "allocatable": np.zeros(3, dtype=np.int64),
                }
            g["used"] += np.maximum(usage[i], 0)
            g["allocatable"] += np.maximum(alloc[i], 0)
        for group in sorted(groups):
            g = groups[group]
            used, allocatable = g["used"], g["allocatable"]
            with np.errstate(divide="ignore", invalid="ignore"):
                util = float(np.max(np.where(allocatable > 0, used / np.maximum(allocatable, 1), 0.0)))
                share = np.where(cluster_used > 0, used / np.maximum(cluster_used, 1), 0.0)
            sample.tenants[group] = {
                "used": [int(x) for x in used],
                "allocatable": [int(x) for x in allocatable],
                "utilization": round(util, 6),
                "share": [round(float(x), 6) for x in share],
            }


def _without_the_clock(sample):
    """``to_dict()`` less the fields that read the wall clock."""
    d = sample.to_dict()
    for key in ("t", "sampleMs"):
        d.pop(key)
    d["classes"].pop("expandMs", None)
    for entry in d["queue"]:
        entry.pop("ageSeconds")
    return d


GROUP_LABEL = "resource_channel"


class _RandomCluster:
    """A snapshot source holding one random cluster: nodes without the
    group label or with an empty one, zone ids below and past the zone
    list (a zone name repeated, and ""), overdrawn nodes and negative
    usage rows, memory past 2**53 bytes, a group of nodes that have no
    allocatable and a group whose nodes are all down."""

    def __init__(self, seed):
        from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshot

        rng = np.random.RandomState(seed)
        n = int(rng.randint(60, 240))
        zone_names = ["us-b", "us-a", "", "us-c", "us-a"][: int(rng.randint(1, 6))]
        zone_id = rng.randint(-2, len(zone_names) + 2, size=n).astype(np.int32)
        group_of = rng.choice(["ig-b", "ig-a", "ig-c", "", None, "ig-empty", "ig-down"], size=n)
        labels = [{} if g is None else {GROUP_LABEL: str(g), "other": "x"} for g in group_of]
        alloc = np.stack(
            [
                rng.randint(0, 96_000, size=n),
                # up to 2**56 bytes, odd ones among them: a float64 sum would round
                (rng.randint(0, 1 << 20, size=n).astype(np.int64) << 36) + rng.randint(0, 1 << 30, size=n),
                rng.randint(0, 8_000, size=n),
            ],
            axis=1,
        ).astype(np.int64)
        alloc[group_of == "ig-empty"] = 0
        usage = (alloc * rng.uniform(-0.3, 1.3, size=(n, 3))).astype(np.int64)  # < 0 and > allocatable
        ready = rng.rand(n) > 0.1
        ready[group_of == "ig-down"] = False
        self.snap = TensorSnapshot(
            names=[f"node-{i:04d}" for i in range(n)],
            allocatable=alloc,
            usage=usage,
            overhead=rng.randint(0, 500, size=(n, 3)).astype(np.int64),
            zone_names=zone_names,
            zone_id=zone_id,
            ready=ready,
            unschedulable=rng.rand(n) < 0.1,
            labels=labels,
            exact=True,
            res_entries=np.zeros(n, dtype=bool),
            name_rank=np.arange(n, dtype=np.int64),
            structure_key=(seed, 0),
            content_key=(seed, 1),
        )

    def snapshot(self):
        return self.snap


@pytest.fixture(scope="module")
def queued_gangs():
    """A pending queue of repeated shapes, one gang whose annotations
    do not parse and one whose demand is not exact in base units."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    h.server.capacity.stop()
    for i in range(12):
        h.create_pod(
            h.static_allocation_spark_pods(
                f"app-g{i}", 1 + i % 4, executor_cpu=str(1 + i % 3), creation_timestamp=1000.0 + i
            )[0]
        )
    bad = h.static_allocation_spark_pods("app-unparseable", 2, creation_timestamp=1100.0)[0]
    bad.meta.annotations = {**bad.meta.annotations, "spark-executor-count": "many"}
    h.create_pod(bad)
    h.create_pod(h.static_allocation_spark_pods("app-inexact", 2, driver_cpu="1500u", creation_timestamp=1101.0)[0])
    yield h
    h.close()


@pytest.mark.parametrize("seed", [3, 2718281828, 20261015, 4, 5, 6])
def test_a_sample_in_arrays_is_the_plain_loops_sample_field_for_field(seed, queued_gangs):
    cluster = _RandomCluster(seed)

    def sampler(cls):
        return cls(
            cluster,
            pod_lister=queued_gangs.server.pod_lister,
            instance_group_label=GROUP_LABEL,
            max_group_zones=5,
            k_max=64,
        )

    got = sampler(CapacitySampler).sample_now(trigger="t")
    want = sampler(_PlainLoops).sample_now(trigger="t")
    assert _without_the_clock(got) == _without_the_clock(want)
    # the cases the cluster is there for
    assert got.groups_dropped > 0
    assert set(got.tenants) >= {"", "ig-empty", "ig-down"}
    assert got.tenants["ig-empty"]["allocatable"] == [0, 0, 0]
    assert max(t["allocatable"][1] for t in got.tenants.values()) > 2**53
    states = [e["state"] for e in got.queue]
    assert states.count("unparseable") == 2 and got.queued_gangs == 14


def test_group_index_is_kept_per_node_table_revision_and_counted():
    """A hit while the node table stands (reservations come and go); a
    rebuild after a label change, a node added and a node removed.  The
    registry counts each read, and every sample equals the loops'."""
    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.server.capacity.stop()
        metrics = MetricsRegistry()

        def sampler(cls, metrics=None):
            return cls(
                h.server.tensor_snapshot,
                pod_lister=h.server.pod_lister,
                metrics=metrics,
                instance_group_label=h.server.install.instance_group_label,
            )

        arrays, loops = sampler(CapacitySampler, metrics), sampler(_PlainLoops)
        nodes = ["n1", "n2", "n3"]
        for i, name in enumerate(nodes):
            h.new_node(name, zone=f"z{i % 2}", instance_group=f"ig-{i % 2}")

        def read(want):
            sample = arrays.sample_now(trigger="t")
            assert _without_the_clock(sample) == _without_the_clock(loops.sample_now(trigger="t"))
            counts = {
                r: metrics.get_counter(mnames.CAPACITY_GROUP_INDEX_READS, {"result": r}) or 0
                for r in ("hit", "rebuild")
            }
            assert counts == want
            return sample

        assert set(read({"hit": 0, "rebuild": 1}).tenants) == {"ig-0", "ig-1"}
        read({"hit": 1, "rebuild": 1})
        h.assert_success(h.schedule(h.static_allocation_spark_pods("app-1", 1, instance_group="ig-0")[0], nodes))
        used = read({"hit": 2, "rebuild": 1})
        assert sum(t["used"][0] for t in used.tenants.values()) > 0

        node = h.api.get("Node", "default", "n1")
        node.meta.labels = {**node.meta.labels, "resource_channel": "ig-2"}
        h.api.update(node)
        assert set(read({"hit": 2, "rebuild": 2}).tenants) == {"ig-0", "ig-1", "ig-2"}
        h.new_node("n4", instance_group="ig-3")
        assert read({"hit": 2, "rebuild": 3}).nodes == 4
        h.api.delete("Node", "default", "n4")
        assert set(read({"hit": 2, "rebuild": 4}).tenants) == {"ig-0", "ig-1", "ig-2"}
        read({"hit": 3, "rebuild": 4})
    finally:
        h.close()
