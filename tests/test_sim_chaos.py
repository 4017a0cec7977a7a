"""Tier-1 degraded-mode chaos: the simulator drives the resilience
layer (ISSUE 3 acceptance).  An inline scenario combining
``apiserver_outage`` + ``kernel_fault`` (+ a latency spike and classic
churn faults) must complete with zero invariant violations (I1–I5 and
the lost-intent checks J1/J2), zero lost reservation intents, a drained
journal at the end, a byte-identical digest when re-run from the same
seed, and a bounded count of requests that pay for the degraded lane.

The same scenario also runs under the lockset race detector
(``SCHEDLINT_RACECHECK=1``): fault injection exercises the write-back
workers, journal replay, and lane-health probes concurrently, and the
run must produce zero race reports and zero lock-order cycles."""

import os

from k8s_spark_scheduler_tpu.analysis import racecheck
from k8s_spark_scheduler_tpu.metrics import names as mnames
from k8s_spark_scheduler_tpu.sim import Scenario, Simulation

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples", "sim"
)


def _chaos_dict():
    return {
        "name": "degraded-smoke",
        "seed": 23,
        "duration": 300,
        "retry_interval": 15,
        "fifo": True,
        "binpack_algo": "tightly-pack",
        "cluster": {"nodes": 4, "cpu": "16", "memory": "32Gi", "zones": ["zone1", "zone2"]},
        "workload": {
            "process": "burst",
            "burst_interval": 60,
            "burst_size": 2,
            "executors": {"min": 1, "max": 4},
            # DA extras take the executor-reschedule path, whose fast
            # lane (tensor_reschedule) is what kernel_fault must demote
            "dynamic_fraction": 0.9,
            "lifetime": {"min": 60, "max": 150},
        },
        "autoscaler": {"enabled": True, "delay": 20, "max_nodes": 8},
        "faults": [
            {"at": 55, "kind": "apiserver_outage", "duration": 60},
            {"at": 50, "kind": "kernel_fault", "duration": 130},
            {"at": 170, "kind": "apiserver_latency", "duration": 40},
            {"at": 230, "kind": "executor_storm", "apps": 1, "fraction": 0.5},
        ],
    }


def test_degraded_chaos_scenario_runs_clean_and_reproducibly():
    result = Simulation(Scenario.from_dict(_chaos_dict())).run()
    assert result.violations == []
    s = result.summary
    assert s["invariant_violations"] == 0
    assert s["apps"]["arrived"] > 0 and s["decisions"] > 0
    # the outage window produced activity (apps kept being admitted from
    # the local cache while writes were diverted)
    outage_events = [
        e for e in result.event_log if 60 <= e["t"] < 120 and e["decisions"]
    ]
    assert outage_events, "no scheduling activity during the outage window"
    # digest reproducible from the seed (run twice, byte-identical log)
    again = Simulation(Scenario.from_dict(_chaos_dict())).run()
    assert again.digest == result.digest
    assert again.violations == []


def test_degraded_chaos_digest_survives_a_lagging_writeback_worker(monkeypatch):
    """What a loaded machine does to the run above, made deterministic:
    the write-back workers fall behind the request thread, so an
    executor's bind queues an update while its reservation's create is
    still being attempted — and during the ``apiserver_latency`` window
    that first attempt times out.  The create's retry then folds into
    the queued update (one pending write per key); the update has to
    upsert.  Before it did, the reservation never reached the API
    server: J1 (lost intent), a write-back that never quiesced, and a
    digest that differed from the unloaded run's."""
    import time

    from k8s_spark_scheduler_tpu.state.cache import AsyncClient

    unloaded = Simulation(Scenario.from_dict(_chaos_dict())).run()
    for name in ("_do_create", "_do_update", "_do_delete"):
        write = getattr(AsyncClient, name)

        def lagging(self, r, _write=write):
            time.sleep(0.02)
            return _write(self, r)

        monkeypatch.setattr(AsyncClient, name, lagging)
    lagged = Simulation(Scenario.from_dict(_chaos_dict())).run()
    assert lagged.violations == []
    assert lagged.digest == unloaded.digest


def test_chaos_recovery_drains_journal_and_reconverges():
    sim = Simulation(Scenario.from_dict(_chaos_dict()))
    result = sim.run()
    assert result.violations == []
    kit = sim.harness.server.resilience
    # nothing left diverted once the outage cleared: every reservation
    # intent landed (zero lost intents)
    assert kit.journal.depth() == 0
    assert kit.breaker.state == "closed"
    # the journal actually engaged during the run — the scenario is only
    # meaningful if writes were diverted and replayed
    counters = sim.harness.server.metrics.snapshot()["counters"]
    appended = sum(
        v for k, v in counters.items() if "resilience.journal.appended" in k
    )
    replayed = sum(
        v for k, v in counters.items() if "resilience.journal.replayed" in k
    )
    assert appended > 0, "the outage never diverted a write to the journal"
    assert replayed > 0, "recovery never replayed a journaled intent"
    # the kernel fault demoted at least one lane along the way
    demotions = sum(
        v for k, v in counters.items() if "resilience.lane.demotion" in k
    )
    assert demotions > 0, "the kernel fault never demoted a lane"


def test_degraded_decision_latency_stays_bounded():
    """While degraded (kernel lane demoted, writes journaled) the
    decisions that ARE served stay cheap — counted, not timed (a CPU
    run proves counts, never a time): once the faulting lane is demoted
    its requests go straight to the next lane; the only requests that
    pay a doomed attempt and a second solve are the ones that demote
    the lane and one probe per elapsed cooloff; none ran an explain and
    none outlived its deadline."""
    d = _chaos_dict()
    sim = Simulation(Scenario.from_dict(d))
    chaos = sim.run()
    assert chaos.violations == []
    server = sim.harness.server
    lanes = server.resilience.lanes
    fault = next(f for f in d["faults"] if f["kind"] == "kernel_fault")

    def counted(name, **tags):
        return sum(
            v
            for k, v in server.metrics.snapshot()["counters"].items()
            if k.startswith(name) and all(f"{t}={x}" in k for t, x in tags.items())
        )

    demotions = counted(mnames.RESILIENCE_LANE_DEMOTIONS, lane="tensor_reschedule")
    assert demotions >= 1, "the kernel fault never demoted the lane"
    # demoted: the executor path dispatches the host lane directly ...
    assert counted(mnames.TPU_FASTPATH, path="executor", lane="slow") > 0
    # ... so a doomed attempt (the lane raised, the host answered: two
    # solves for one answer) is paid failure_threshold times per
    # demotion and once per re-probe the fault window has room for
    doomed = counted(mnames.TPU_FASTPATH, lane="fallback")
    probes = int(fault["duration"] // lanes.cooloff_seconds)
    assert 0 < doomed <= lanes.failure_threshold * demotions + probes
    # the lane serves again once the fault has cleared and a probe passed
    assert counted(mnames.TPU_FASTPATH, path="executor", lane="fast") > 0
    assert lanes.demoted_lanes() == []
    # every decision was answered once, with no explain on its path and
    # inside its deadline
    assert chaos.summary["decisions"] == sum(
        len(e["decisions"]) for e in chaos.event_log
    )
    assert counted(mnames.PROVENANCE_EXPLAIN_COUNT) == 0
    assert counted(mnames.RESILIENCE_DEADLINE_EXPIRED_COUNT) == 0


def test_chaos_scenario_runs_clean_under_race_detector(monkeypatch):
    """The full degraded-mode chaos scenario with the Eraser-style
    lockset detector instrumenting every guarded lock and shared-state
    mutation: zero unprotected shared writes, zero lock-order cycles,
    and the usual zero-violation audit still holds."""
    monkeypatch.setenv(racecheck.ENV_FLAG, "1")
    # the env flag is read by the harness/sim runner at build time; make
    # sure no detector from another test is lingering
    racecheck.disable()
    try:
        result = Simulation(Scenario.from_dict(_chaos_dict())).run()
    finally:
        detector = racecheck.disable()
    assert result.violations == []
    assert detector is not None, "the sim runner never enabled the detector"
    assert detector._instances, "no guarded instances were instrumented"
    assert detector.races == [], "\n".join(detector.report_lines())
    # the vector-clock detector runs alongside the lockset over the same
    # checkpoints: zero happens-before races either
    assert detector.hb_races == [], "\n".join(detector.report_lines())
    assert detector.lock_order_violations == [], "\n".join(detector.report_lines())
    assert detector.clean()


def test_chaos_with_delta_engine_enabled_runs_clean_and_bounded():
    """The same degraded chaos scenario with the ``tpu-batch`` policy:
    the delta-solve engine serves the driver fast path through outages,
    kernel faults, and node churn with zero invariant violations, and
    its resident native state stays bounded (the soak's bounded-size
    contract, asserted here at tier-1 scale)."""
    d = _chaos_dict()
    d["name"] = "degraded-smoke-deltasolve"
    d["binpack_algo"] = "tpu-batch"
    sim = Simulation(Scenario.from_dict(d))
    result = sim.run()
    assert result.violations == []
    assert result.summary["invariant_violations"] == 0
    assert result.summary["decisions"] > 0
    # decision provenance rode along for every decision and stayed
    # bounded (the ISSUE 6 soak contract at chaos scale)
    tracker = sim.harness.server.provenance
    assert tracker is not None
    pstats = tracker.stats()
    assert pstats["ring"]["recorded"] >= result.summary["decisions"]
    assert pstats["ring"]["size"] <= pstats["ring"]["capacity"]
    assert pstats["recorder"]["size"] <= pstats["recorder"]["capacity"]
    # ISSUE 7 acceptance: the chaos run carries a non-empty, bounded
    # capacity timeline, the sampler ran zero solves under the extender
    # lock, and the summary folds the scorecard columns in
    capsum = result.summary["capacity"]
    assert capsum is not None and capsum["samples"] > 0
    assert capsum["lock_violations"] == 0
    assert result.capacity_timeline
    sampler = sim.harness.server.capacity
    assert len(result.capacity_timeline) <= sampler.stats()["ring_capacity"]
    assert 0.0 <= capsum["fragmentation_max_dim"]["max"] <= 1.0
    engine = sim.harness.server.extender.delta_engine
    from k8s_spark_scheduler_tpu.native.fifo import native_session_available

    if engine is None or not native_session_available():
        return  # toolchain-less host: the fallback lanes already audited
    stats = engine.stats()
    # the engine was consulted (served or declined-with-reason) …
    assert (
        stats["cold_solves"] + stats["warm_hits"] + sum(stats["misses"].values())
        > 0
    )
    # … and its resident state stayed bounded: session count at the LRU
    # cap and native buffers within the per-session roof (basis + tail +
    # working planes + ≤24 checkpoints + queue cache at this node scale)
    assert stats["sessions"] <= engine.MAX_SESSIONS
    max_nodes = 4096 + 16  # scenario cluster + autoscaler cap « bucket
    assert stats["session_bytes"] <= engine.MAX_SESSIONS * (
        30 * max_nodes * 12 + 2**21
    )


def test_chaos_with_delta_engine_runs_clean_under_race_detector(monkeypatch):
    """The engine-enabled chaos scenario under the lockset detector: the
    new guarded state (DeltaSolveEngine sessions/stats, the tensor
    mirror's ChangeFeed, the serde intern/encoder caches) must produce
    zero race reports and zero lock-order cycles alongside the usual
    zero-violation audit."""
    monkeypatch.setenv(racecheck.ENV_FLAG, "1")
    racecheck.disable()
    d = _chaos_dict()
    d["name"] = "degraded-smoke-deltasolve-racecheck"
    d["binpack_algo"] = "tpu-batch"
    try:
        result = Simulation(Scenario.from_dict(d)).run()
    finally:
        detector = racecheck.disable()
    assert result.violations == []
    assert detector is not None
    tracked = {name.split("#")[0] for name in detector._instances.values()}
    assert "ChangeFeed" in tracked, tracked
    assert "DeltaSolveEngine" in tracked, tracked
    # the provenance ring + flight recorder are guarded state on the
    # decision path now: they must be instrumented and race-free too
    assert "ProvenanceRing" in tracked, tracked
    assert "FlightRecorder" in tracked, tracked
    assert "ProvenanceTracker" in tracked, tracked
    # the capacity sampler's ring/stats are guarded shared state on the
    # sim's sampling path: instrumented and race-free too
    assert "CapacitySampler" in tracked, tracked
    # PR 9's LK004 sweep promoted the remaining locked classes into the
    # registry: the tensor mirror, the informers, the metrics registry
    # and the sim clock are all under both detectors now
    assert "TensorSnapshotCache" in tracked, tracked
    assert "Informer" in tracked, tracked
    assert "MetricsRegistry" in tracked, tracked
    # (VirtualClock is constructed before the runner enables the
    # detector, so it is deliberately skipped — see racecheck docstring)
    assert detector.races == [], "\n".join(detector.report_lines())
    assert detector.hb_races == [], "\n".join(detector.report_lines())
    assert detector.lock_order_violations == [], "\n".join(
        detector.report_lines()
    )
    assert detector.clean()


def test_degraded_example_scenario_parses():
    sc = Scenario.from_file(os.path.join(_EXAMPLES, "degraded.json"))
    kinds = {f.kind for f in sc.faults}
    assert {"apiserver_outage", "apiserver_latency", "kernel_fault"} <= kinds
    assert all(
        f.duration > 0
        for f in sc.faults
        if f.kind in ("apiserver_outage", "apiserver_latency", "kernel_fault")
    )
