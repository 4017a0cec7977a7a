"""CI perf-regression guard for the native C++ FIFO lane (no hardware
needed): on a small canonical shape the native solver must stay decision-
identical to the XLA scan AND meaningfully faster than it.  A relative
bound is load-robust (both lanes run on the same machine under the same
load), so a C++ lane regression fails CI instead of surfacing as a lost
round artifact.  Analog of the reference's verify gate
(.circleci/config.yml:341-368).

Measured context: at 10k nodes x 1k apps the native lane is ~8x faster
than the XLA scan (35ms vs 286ms; ~15x after the r5 dim-at-a-time
pass); the 4x bound leaves margin.  The bound is host-shape dependent —
the XLA CPU scan can parallelize across cores while the native lane is
single-threaded — so a many-core CI host can override it via
PERF_GUARD_MIN_SPEEDUP (ADVICE r4 #1).
"""

import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from k8s_spark_scheduler_tpu.native.fifo import (
    native_fifo_available,
    solve_queue_native,
)
from k8s_spark_scheduler_tpu.ops.batch_solver import BIG, solve_queue

N_NODES = 2000
N_APPS = 200
MIN_SPEEDUP = float(os.environ.get("PERF_GUARD_MIN_SPEEDUP", "4.0"))


def _problem():
    rng = np.random.RandomState(20260731)
    avail = rng.randint(0, 400, size=(N_NODES, 3)).astype(np.int32)
    rank = np.arange(N_NODES, dtype=np.int32)
    rng.shuffle(rank)
    exec_ok = np.ones(N_NODES, dtype=bool)
    drivers = rng.randint(0, 4, size=(N_APPS, 3)).astype(np.int32)
    executors = rng.randint(1, 6, size=(N_APPS, 3)).astype(np.int32)
    counts = rng.randint(1, 16, size=N_APPS).astype(np.int32)
    valid = np.ones(N_APPS, dtype=bool)
    return avail, rank, exec_ok, drivers, executors, counts, valid


def _best_of(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


@pytest.mark.skipif(
    not native_fifo_available(), reason="native toolchain unavailable"
)
def test_native_lane_beats_xla_scan_by_4x():
    avail, rank, exec_ok, drivers, executors, counts, valid = _problem()
    dev_args = (
        jnp.asarray(avail), jnp.asarray(rank), jnp.asarray(exec_ok),
        jnp.asarray(drivers), jnp.asarray(executors), jnp.asarray(counts),
        jnp.asarray(valid),
    )

    def run_xla():
        out = solve_queue(*dev_args, evenly=False, with_placements=False)
        out.avail_after.block_until_ready()
        return out

    def run_native():
        return solve_queue_native(
            avail, rank, exec_ok, drivers, executors, counts, valid
        )

    ref = run_xla()  # compile + warm
    got = run_native()  # warm the ctypes path

    # (a) decision equality on this shape
    np.testing.assert_array_equal(got[0], np.asarray(ref.feasible))
    np.testing.assert_array_equal(got[1], np.asarray(ref.driver_idx))
    np.testing.assert_array_equal(got[2], np.asarray(ref.avail_after))

    # (b) relative perf bound
    xla_s = _best_of(run_xla)
    native_s = _best_of(run_native)
    speedup = xla_s / max(native_s, 1e-9)
    assert speedup >= MIN_SPEEDUP, (
        f"native lane regression: only {speedup:.1f}x faster than the XLA "
        f"scan at {N_NODES}x{N_APPS} (native {native_s * 1e3:.1f}ms vs "
        f"xla {xla_s * 1e3:.1f}ms); bound is {MIN_SPEEDUP}x"
    )


# -- delta-solve warm-path guard ----------------------------------------------
#
# The persistent-session warm path must stay decisively cheaper than a
# cold full solve: at the north-star 10k×1k shape the cold native queue
# solve is ~19ms while a full-prefix warm resume is a few hundred µs
# (checkpoint restore + prefix memcmp).  The CI bound is a relative 3×
# (the bench acceptance bound) with the real shape, which also keeps the
# guard load-robust — both paths run back-to-back on the same core.

WARM_MIN_SPEEDUP = float(os.environ.get("PERF_GUARD_WARM_MIN_SPEEDUP", "3.0"))


@pytest.mark.skipif(
    not native_fifo_available(), reason="native toolchain unavailable"
)
def test_deltasolve_warm_path_beats_cold_solve_3x_at_10k_x_1k():
    from k8s_spark_scheduler_tpu.native.fifo import (
        NativeFifoSession,
        native_session_available,
    )

    if not native_session_available():
        pytest.skip("prebuilt native library lacks the session API")

    nodes, apps = 10240, 1024
    rng = np.random.RandomState(20260804)
    avail = rng.randint(0, 400, size=(nodes, 3)).astype(np.int32)
    rank = np.arange(nodes, dtype=np.int32)
    rng.shuffle(rank)
    eok = np.ones(nodes, dtype=bool)
    packed = np.hstack(
        [
            rng.randint(0, 4, size=(apps, 3)),
            rng.randint(1, 6, size=(apps, 3)),
            rng.randint(1, 16, size=(apps, 1)),
            np.ones((apps, 1), dtype=int),
        ]
    ).astype(np.int32)

    sess = NativeFifoSession()
    try:
        def cold():
            sess.load(avail, rank, eok, 0, stride=64)
            return sess.solve(packed)

        def warm():
            return sess.solve(packed)

        r0, feas_cold, _, after_cold = cold()
        assert r0 == 0
        r1, feas_warm, _, after_warm = warm()
        assert r1 == apps  # full prefix reuse
        np.testing.assert_array_equal(feas_warm, feas_cold)
        np.testing.assert_array_equal(after_warm, after_cold)

        cold_s = _best_of(cold)
        warm_s = _best_of(warm)
        speedup = cold_s / max(warm_s, 1e-9)
        assert speedup >= WARM_MIN_SPEEDUP, (
            f"warm-path regression: only {speedup:.1f}x faster than cold at "
            f"{nodes}x{apps} (warm {warm_s * 1e3:.2f}ms vs cold "
            f"{cold_s * 1e3:.1f}ms); bound is {WARM_MIN_SPEEDUP}x"
        )
    finally:
        sess.close()


# -- tracing overhead guard --------------------------------------------------
#
# The observability layer must never silently regress the predicate hot
# path.  Two bounds:
#
# (a) layer microbench: a full simulated request tree (root + 6 child
#     spans + tags, serialized into the ring) must stay under a fixed
#     per-request budget — catches an accidentally-expensive Span/ring
#     implementation in isolation, load-robustly (best-of batches);
# (b) end-to-end: predicate latency with tracing enabled stays within a
#     relative+absolute budget of the same predicate with the tracer
#     disabled (the no-op context-manager path).

TRACE_TREE_BUDGET_US = float(os.environ.get("PERF_GUARD_TRACE_TREE_US", "500"))


@pytest.mark.parametrize("beside", ["nothing", "background-work"])
def test_span_tree_overhead_budget(beside):
    """The tree a granted driver Filter leaves on a device lane (24
    spans, 25 with provenance on; docs/observability.md).  The budget per span is what it was
    when the tree had 7 (120 µs / 7 ≈ 17 µs, ~3x the ~5 µs measured).  It
    holds with the runtime's tags on (``cpuMs`` on the gate, the
    collector's hook installed), and on the tags' slow path too: beside
    background work every span asks the table for names and writes ``bg``."""
    import threading
    from contextlib import contextmanager, nullcontext

    from k8s_spark_scheduler_tpu import tracing
    from k8s_spark_scheduler_tpu.tracing import Tracer, child_span

    tracer = Tracer(capacity=64)
    tracing.install_gc_hook()

    def leaves(*names):
        for name in names:
            with child_span(name):
                pass

    def one_request():
        with tracer.span("http.request", {"path": "/predicates"}):
            leaves("http.read", "serde.decode")
            with tracer.span("predicate", {"pod": "p", "namespace": "d"}) as sp:
                leaves(
                    "fast_path.snapshot", "fast_path.queue_assemble", "fast_path.build_tensor",
                    "fast_path.tensorize_apps", "fast_path.scale_problem",
                )
                with child_span("fifo_gate", {"earlierApps": 3, "lane": "xla"}, cpu=True):
                    with child_span("device.upload", {"arrays": 2, "bytes": 238_000}):
                        pass
                    with tracer.span("kernel:fifo_queue", {"lane": "xla"}) as k:
                        leaves("device.dispatch", "device.wait")
                        k.tag("executeMs", 0.2)
                    with child_span("device.readback", {"arrays": 1, "bytes": 168_000}):
                        pass
                with child_span("binpack", {"policy": "tightly-pack", "lane": "xla"}):
                    pass
                leaves("fast_path.decode", "fast_path.efficiency")
                with tracer.span("driver.finish"):
                    with tracer.span("reservation.writeback", {"app": "a"}):
                        leaves("state.writeback.enqueue")
                leaves("provenance.finish")
                sp.tag("outcome", "success")
            leaves("serde.encode", "http.write")

    def batch():
        for _ in range(200):
            one_request()

    @contextmanager
    def a_worker_writes_back():
        # on a thread of its own: marked work's own spans would take no ``bg``
        inside, release = threading.Event(), threading.Event()

        def item():
            with tracing.background("writeback"):
                inside.set()
                release.wait(120.0)

        worker = threading.Thread(target=item)
        worker.start()
        assert inside.wait(10.0)
        try:
            yield
        finally:
            release.set()
            worker.join(10.0)

    with a_worker_writes_back() if beside == "background-work" else nullcontext():
        batch()  # warm
        per_request_s = _best_of(batch) / 200.0
    assert per_request_s * 1e6 <= TRACE_TREE_BUDGET_US, (
        f"tracing layer costs {per_request_s * 1e6:.1f}µs per request tree; "
        f"budget is {TRACE_TREE_BUDGET_US}µs"
    )

    def spans(span):
        yield span
        for child in span.get("children", ()):
            yield from spans(child)

    last = list(spans(tracer.traces(limit=1)[0]["root"]))
    assert len(last) == 24 and [s["name"] for s in last if "cpuMs" in s["tags"]] == ["fifo_gate"]
    assert [s["tags"].get("bg") for s in last] == [
        "writeback" if beside == "background-work" else None
    ] * 24


# -- simulator throughput guard ----------------------------------------------
#
# The discrete-event simulator is the load/soak/chaos evidence layer for
# every later perf PR, so its own overhead (quiesce polling, per-event
# auditing, state fingerprinting) must not silently regress.  Budget is
# simulated scheduling decisions per wall-clock second on CPU over the
# bundled smoke scenario; measured ~140-150/s on the dev host, so the
# default bound leaves ~5x margin for slower CI hosts
# (override via SIM_MIN_DECISIONS_PER_SEC).

SIM_MIN_DECISIONS_PER_SEC = float(os.environ.get("SIM_MIN_DECISIONS_PER_SEC", "25"))


def test_sim_throughput_budget():
    from k8s_spark_scheduler_tpu.sim import Scenario, Simulation

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sc = Scenario.from_file(os.path.join(here, "examples", "sim", "smoke.json"))
    result = Simulation(sc).run()
    assert result.violations == []
    rate = result.summary["decisions_per_sec_wall"]
    assert rate is not None and rate >= SIM_MIN_DECISIONS_PER_SEC, (
        f"simulator throughput regression: {rate} simulated scheduling "
        f"decisions/sec (budget {SIM_MIN_DECISIONS_PER_SEC}/s); "
        f"{result.summary['decisions']} decisions in "
        f"{result.summary['wall_duration_s']}s wall"
    )
    # the virtual clock must buy real compression: ≥20x sim over wall
    assert result.summary["sim_speedup"] >= 20.0


# -- resilience overhead guard ------------------------------------------------
#
# The overload-protection layer must be ~free on the happy path: a bound
# deadline costs one contextvar read + monotonic call per phase boundary,
# the admission gate one small critical section per request.  Budget is
# 5% relative over the bare predicate (ISSUE 3 acceptance) plus a small
# absolute slack so a sub-millisecond baseline isn't flaky under CI load.


def test_deadline_and_gate_overhead_within_budget():
    from k8s_spark_scheduler_tpu.resilience import deadline as req_deadline
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    h = Harness()
    try:
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-res-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR

        extender = h.server.extender
        kit = h.server.resilience
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])
        n = 50

        # idempotent driver replay: stable, reservation-backed request
        def bare_batch():
            for _ in range(n):
                extender.predicate(args)

        def guarded_batch():
            # exactly what the HTTP layer adds per request
            for _ in range(n):
                with kit.gate.admit():
                    with req_deadline.bind(kit.request_timeout):
                        extender.predicate(args)

        bare_batch()
        guarded_batch()  # warm both
        bare_s = _best_of(bare_batch)
        guarded_s = _best_of(guarded_batch)

        budget = bare_s * 1.05 + n * 0.2e-3  # 5% relative + 0.2ms/request
        assert guarded_s <= budget, (
            f"resilience overhead: {guarded_s * 1e3:.2f}ms per {n}-request batch "
            f"guarded vs {bare_s * 1e3:.2f}ms bare (budget {budget * 1e3:.2f}ms)"
        )
    finally:
        h.close()


def test_provenance_overhead_within_budget():
    """ISSUE 6 acceptance: decision provenance costs < 5% of Filter
    latency enabled, and disabled it reduces structurally to one None
    check per request (sinks unset, every lifecycle call guarded by
    ``prov is None or not prov.enabled``).  Measured here as
    enabled-vs-disabled on the same harness — same pattern and budget
    as the resilience guard (5% relative + absolute CI-noise slack)."""
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-prov-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR

        extender = h.server.extender
        prov = h.server.provenance
        assert prov is not None and prov.enabled
        solver = extender.binpacker.queue_solver
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])
        n = 50

        def batch():
            for _ in range(n):
                extender.predicate(args)

        def set_enabled(on: bool) -> None:
            prov.enabled = on
            sink = prov.capture if on else None
            solver.capture_sink = sink
            if extender.delta_engine is not None:
                extender.delta_engine.capture_sink = sink

        batch()  # warm caches/jit on both paths
        set_enabled(False)
        disabled_s = _best_of(batch)
        set_enabled(True)
        enabled_s = _best_of(batch)

        budget = disabled_s * 1.05 + n * 0.5e-3  # 5% relative + 0.5ms/request
        assert enabled_s <= budget, (
            f"provenance overhead: {enabled_s * 1e3:.2f}ms per {n}-request "
            f"batch enabled vs {disabled_s * 1e3:.2f}ms disabled "
            f"(budget {budget * 1e3:.2f}ms)"
        )
        # enabled requests actually recorded provenance (the guard must
        # not pass because capture silently stopped running)
        assert len(prov.ring) > 0
    finally:
        h.close()


def test_capacity_sampler_overhead_within_budget():
    """ISSUE 7 acceptance: the capacity observatory adds ~nothing to
    the Filter path — sampling is change-triggered on a background
    thread and NEVER runs under the extender lock, so the only hot-path
    cost is the ChangeFeed's wakeup Event.set.  Counted, not timed:
    while Filters admit gangs (every grant moves the feed) the sampler
    does sample, every sample runs on its own thread, none on the
    request thread and none inside the lock."""
    import threading

    from k8s_spark_scheduler_tpu.capacity import in_predicate_lock
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1", cpu="32", memory="32Gi")
        h.new_node("n2", cpu="32", memory="32Gi")
        sampler = h.server.capacity
        assert sampler is not None
        request_thread = threading.get_ident()
        sampled = []  # (thread, thread name, inside the extender lock)
        build = sampler._build_sample

        def recording(snap, trigger, span):
            t = threading.current_thread()
            sampled.append((t.ident, t.name, in_predicate_lock()))
            return build(snap, trigger, span)

        sampler._build_sample = recording
        before = sampler.stats()["samples"]
        n = 8
        for i in range(n):
            for pod in h.static_allocation_spark_pods(f"app-cap-{i}", 1):
                h.assert_success(h.schedule(pod, ["n1", "n2"]))
        feed = h.server.tensor_snapshot.feed
        assert h.wait_for_api(
            lambda: sampler.latest() is not None and sampler.latest().seq == feed.seq
        ), "the sampler never caught up with the feed"

        # (a sample still in flight has been recorded, not yet counted)
        assert len(sampled) >= sampler.stats()["samples"] - before >= 1
        assert all(ident != request_thread for ident, _, _ in sampled), sampled
        assert {name for _, name, _ in sampled} == {"capacity-sampler"}
        assert not any(locked for _, _, locked in sampled)
        # and it never probed from inside the extender lock
        assert sampler.lock_violations == 0
    finally:
        h.close()


def test_lifecycle_ledger_overhead_within_budget():
    """Lifecycle-ledger acceptance: the gang ledger adds zero work
    under the predicate lock — everything originating inside the
    predicate is pulled by cursor on the background drain thread, so
    the only hot-path cost is the EventLog wakeup Event.set.  Budget
    mirrors the capacity-sampler guard: enabled ≤ disabled × 1.05 plus
    absolute CI-noise slack, and the structural check that the drain
    never ran under the lock."""
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-ledger-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))

        extender = h.server.extender
        ledger = h.server.lifecycle
        assert ledger is not None
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])
        n = 50

        def batch():
            for _ in range(n):
                extender.predicate(args)

        batch()  # warm caches/jit
        ledger.stop()
        disabled_s = _best_of(batch)
        ledger.start()
        batch()  # warm with the thread alive
        enabled_s = _best_of(batch)

        budget = disabled_s * 1.05 + n * 0.5e-3  # 5% relative + 0.5ms/request
        assert enabled_s <= budget, (
            f"lifecycle ledger overhead: {enabled_s * 1e3:.2f}ms per "
            f"{n}-request batch enabled vs {disabled_s * 1e3:.2f}ms disabled "
            f"(budget {budget * 1e3:.2f}ms)"
        )
        # and it never drained from inside the extender lock
        assert ledger.lock_violations == 0
    finally:
        h.close()


def test_racecheck_disabled_overhead_within_budget():
    """The race-detector checkpoints stay in the hot paths permanently,
    so their disabled cost is a contract: one module-attribute read and
    a None check.  Pinned relative to an equivalent no-op call through
    the same calling convention (load-robust), plus an absolute
    per-call ceiling so the relative bound can't hide a regression to
    microseconds."""
    from k8s_spark_scheduler_tpu.analysis import racecheck

    assert not racecheck.active(), "detector must be disabled for this guard"

    class Owner:
        pass

    owner = Owner()
    n = 200_000

    def noop(obj, field, write=True):
        d = None
        if d is not None:  # same shape: read + None check + branch
            raise AssertionError

    def run_noop():
        for _ in range(n):
            noop(owner, "f")

    def run_note_access():
        for _ in range(n):
            racecheck.note_access(owner, "f")

    run_noop(); run_note_access()  # warm
    base_s = _best_of(run_noop)
    note_s = _best_of(run_note_access)
    per_call_us = note_s / n * 1e6
    budget_s = base_s * 4.0 + n * 1.5e-6  # 4x a no-op call + 1.5µs/call
    assert note_s <= budget_s, (
        f"disabled note_access {per_call_us:.3f}µs/call exceeds budget "
        f"(no-op baseline {base_s / n * 1e6:.3f}µs/call)"
    )
    # hard ceiling independent of the baseline: the disabled path must
    # never grow real work
    assert per_call_us < 5.0, f"disabled note_access {per_call_us:.3f}µs/call"


def test_locktime_disabled_overhead_within_budget():
    """ISSUE 11 acceptance (disabled half): with no timekeeper enabled
    a TimedLock acquire/release is one module-attribute read + a None
    check on top of the raw lock — same contract (and same budget
    shape) as the disabled racecheck checkpoint above."""
    import threading

    from k8s_spark_scheduler_tpu.contention import locktime

    prev = locktime.get()
    locktime.disable()
    try:
        raw = threading.Lock()
        timed = locktime.TimedLock(threading.Lock(), "perf.guard")
        n = 200_000

        def run_raw():
            for _ in range(n):
                with raw:
                    pass

        def run_timed():
            for _ in range(n):
                with timed:
                    pass

        run_raw(); run_timed()  # warm
        base_s = _best_of(run_raw)
        timed_s = _best_of(run_timed)
        per_call_us = timed_s / n * 1e6
        budget_s = base_s * 4.0 + n * 1.5e-6  # 4x the raw lock + 1.5µs/call
        assert timed_s <= budget_s, (
            f"disabled TimedLock {per_call_us:.3f}µs/acquire exceeds budget "
            f"(raw lock baseline {base_s / n * 1e6:.3f}µs/acquire)"
        )
        # hard ceiling independent of the baseline: the disabled path
        # must never grow real work (no clock reads, no reservoirs)
        assert per_call_us < 5.0, f"disabled TimedLock {per_call_us:.3f}µs/acquire"
    finally:
        if prev is not None:
            locktime.enable(prev)


def test_locktime_enabled_overhead_within_budget():
    """ISSUE 11 acceptance (enabled half): timing mode on the Filter
    path stays within disabled × 1.05 plus absolute CI-noise slack.
    The sampled reservoir (stride 64) + pending-buffer append is the
    entire enabled cost — no publishing happens on the lock path."""
    from k8s_spark_scheduler_tpu.contention import locktime
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    h = Harness()
    try:
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-lock-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR

        extender = h.server.extender
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])
        n = 50
        prev = locktime.get()
        assert prev is not None, "harness wiring must enable the timekeeper"

        def batch():
            for _ in range(n):
                extender.predicate(args)

        batch()  # warm caches/jit
        locktime.disable()
        try:
            disabled_s = _best_of(batch)
        finally:
            locktime.enable(prev)
        batch()  # warm the timed path
        enabled_s = _best_of(batch)

        budget = disabled_s * 1.05 + n * 0.5e-3  # 5% relative + 0.5ms/request
        assert enabled_s <= budget, (
            f"lock-timing overhead: {enabled_s * 1e3:.2f}ms per {n}-request "
            f"batch enabled vs {disabled_s * 1e3:.2f}ms disabled "
            f"(budget {budget * 1e3:.2f}ms)"
        )
        # enabled requests actually recorded stats (the guard must not
        # pass because timing silently stopped running)
        snap = extender._predicate_lock.snapshot()
        assert snap["acquisitions"] > 0
    finally:
        h.close()


def test_policy_engine_overhead_within_budget():
    """ISSUE 14 acceptance: with ``policy.enabled=false`` the Filter
    path must carry NO policy cost — structurally the engine is never
    constructed (``extender._policy is None``; every hook is one None
    check), and measurably an engine running the fifo ordering stays
    within disabled × 1.05 plus absolute CI-noise slack (same pattern
    as the provenance/locktime guards)."""
    from k8s_spark_scheduler_tpu.config import FifoConfig, Install, PolicyConfig
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    # structural half: the default install constructs no engine at all
    h0 = Harness(is_fifo=True)
    try:
        assert h0.server.extender._policy is None
        assert getattr(h0.server, "policy", None) is None
    finally:
        h0.close()

    # measured half: fifo-ordering engine vs the engine detached
    install = Install(
        fifo=True,
        fifo_config=FifoConfig(),
        policy=PolicyConfig(enabled=True, ordering="fifo"),
    )
    h = Harness(is_fifo=True, extra_install=install)
    try:
        extender = h.server.extender
        assert extender._policy is not None
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-pol-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])
        n = 50

        def batch():
            for _ in range(n):
                extender.predicate(args)

        engine = extender._policy
        batch()  # warm caches/jit on the enabled path
        extender._policy = None
        try:
            disabled_s = _best_of(batch)
        finally:
            extender._policy = engine
        batch()  # warm the enabled path again
        enabled_s = _best_of(batch)

        budget = disabled_s * 1.05 + n * 0.5e-3  # 5% relative + 0.5ms/request
        assert enabled_s <= budget, (
            f"policy-engine overhead: {enabled_s * 1e3:.2f}ms per {n}-request "
            f"batch with the fifo-ordering engine vs {disabled_s * 1e3:.2f}ms "
            f"detached (budget {budget * 1e3:.2f}ms)"
        )
    finally:
        h.close()


def test_ha_fabric_overhead_within_budget():
    """HA failover-fabric acceptance: fencing + crash-point checks add
    nothing to the Filter hot path.  Structurally, fencing gates only
    the async write-back workers and the preemption executor — the
    predicate never reads the lease — and the disabled crash-point
    traversal is one module-attribute read.  Measured as an HA-enabled
    harness vs the default install (no fabric) running the same
    50-request batch: enabled ≤ disabled × 1.05 plus absolute CI-noise
    slack (same budget shape as the policy/provenance guards)."""
    from k8s_spark_scheduler_tpu import capacity
    from k8s_spark_scheduler_tpu.config import FifoConfig, HAConfig, Install
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

    def predicate_batch_time(h, app_id):
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods(app_id, 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR
        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])

        def batch():
            for _ in range(50):
                h.server.extender.predicate(args)

        batch()  # warm caches/jit
        return _best_of(batch)

    # baseline: the default install constructs no fabric at all
    h0 = Harness(is_fifo=True)
    try:
        assert h0.server.ha is None
        disabled_s = predicate_batch_time(h0, "app-ha-perf")
    finally:
        h0.close()

    install = Install(
        fifo=True,
        fifo_config=FifoConfig(),
        ha=HAConfig(enabled=True, background=False, identity="perf-guard"),
    )
    h = Harness(is_fifo=True, extra_install=install)
    try:
        fabric = h.server.ha
        assert fabric is not None
        fabric.step()  # elected: writes pass the fence, nothing refuses
        assert fabric.is_leader()
        enabled_s = predicate_batch_time(h, "app-ha-perf")

        budget = disabled_s * 1.05 + 50 * 0.5e-3  # 5% relative + 0.5ms/request
        assert enabled_s <= budget, (
            f"HA fabric overhead: {enabled_s * 1e3:.2f}ms per 50-request "
            f"batch with fencing armed vs {disabled_s * 1e3:.2f}ms without "
            f"the fabric (budget {budget * 1e3:.2f}ms)"
        )
        # the batch's write-backs all passed the fence (nothing refused,
        # nothing stale) — the guard measured the real armed path
        st = fabric.fence.state()
        assert st["refusals"] == {} and st["staleCommits"] == 0

        # structural half: an election round invoked from a thread that
        # holds the predicate lock refuses to do lease I/O (leader
        # election must never stretch a scheduling decision's lock hold)
        peeks = []
        orig_peek = fabric.elector.peek
        fabric.elector.peek = lambda: (peeks.append(1), orig_peek())[1]
        try:
            capacity.enter_predicate_lock()
            try:
                assert fabric.step()  # still reports leadership...
            finally:
                capacity.exit_predicate_lock()
            assert peeks == [], (
                "fabric.step() performed lease I/O under the predicate lock"
            )
            fabric.step()  # ...and off the lock the round really runs
            assert peeks, "sanity: the peek counter never wired in"
        finally:
            fabric.elector.peek = orig_peek
    finally:
        h.close()


def test_predicate_latency_with_tracing_within_budget():
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    h = Harness()
    try:
        h.new_node("n1")
        h.new_node("n2")
        driver = h.static_allocation_spark_pods("app-trace-perf", 1)[0]
        h.assert_success(h.schedule(driver, ["n1", "n2"]))  # creates the RR

        tracer = h.server.tracer
        extender = h.server.extender
        from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

        args = ExtenderArgs(pod=driver, node_names=["n1", "n2"])

        # idempotent driver replay: a stable, reservation-backed request
        # the harness can repeat without mutating cluster state
        def batch():
            for _ in range(50):
                extender.predicate(args)

        batch()  # warm both paths (jit, caches)
        tracer.enabled = False
        untraced_s = _best_of(batch)
        tracer.enabled = True
        traced_s = _best_of(batch)

        budget = untraced_s * 1.5 + 50 * 2e-3  # 50% relative + 2ms/request
        assert traced_s <= budget, (
            f"tracing overhead: {traced_s * 1e3:.2f}ms per 50-request batch vs "
            f"{untraced_s * 1e3:.2f}ms untraced (budget {budget * 1e3:.2f}ms)"
        )
    finally:
        h.close()
