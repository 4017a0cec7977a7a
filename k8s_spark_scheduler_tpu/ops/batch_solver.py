"""JAX batch gang-packing solver — the TPU-native replacement for the
reference's first-fit loops (SURVEY §3.2 hot loops; BASELINE.json north
star).

The key identity making the O(driver-candidates × nodes) Go loop an
O(nodes) vector program: for the tightly-pack / distribute-evenly
policies, executor distribution over a candidate set succeeds iff the
total per-node executor capacity is ≥ k (both fill every node to its
capacity in the limit), and placing the driver on node d only changes
node d's capacity.  So

    T_d = S − cap_d + cap'_d          (S = Σ min(cap_n, k))

for every driver candidate d at once, and the chosen driver is the
first-priority d with (driver fits d) ∧ (T_d ≥ k) — bit-identical to
``SparkBinPack`` + ``tightlyPackExecutors`` / ``distributeExecutorsEvenly``
(reference lib/pkg/binpack/binpack.go:60-87, pack_tightly.go:34-63,
distribute_evenly.go:34-73), proven by the parity suite in
tests/test_batch_parity.py.

The FIFO earlier-drivers pass (resource.go:224-262) is a ``lax.scan``
over apps carrying availability, reproducing the reference's
usage-subtraction quirk (one executor's worth per hosting node,
driver overwritten — sparkpods.go:139-146).

All arrays are int32 (see tensorize.scale_problem for the exactness
guarantee); everything here is shape-static and jit/vmap/shard_map
compatible, with the node axis shardable over a device mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

# plain int (not a jnp scalar): creating a device array at import time
# would initialize the JAX backend as a side effect of merely importing
# this module; int32 ops promote it correctly
BIG = 2**31 - 1


class AppSolve(NamedTuple):
    """Per-app gang decision."""

    feasible: jnp.ndarray      # [] bool
    driver_idx: jnp.ndarray    # [] int32 (index into node axis; N if infeasible)
    exec_counts: jnp.ndarray   # [N] int32 tightly-pack fill counts
    exec_capacity: jnp.ndarray  # [N] int32 per-node capacity after driver placement


def node_capacity(avail: jnp.ndarray, executor: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Per-node executor capacity clamped to [0, k]
    (capacity.go:36-75: floor division per dim, zero-requirement → ∞ —
    but a dimension whose availability is already negative is 0 even
    when the requirement is 0: reserved(0) > available short-circuits
    before the zero-requirement check, capacity.go:37-44)."""
    safe = jnp.maximum(executor, 1)
    per_dim = jnp.where(
        executor[None, :] == 0,
        jnp.where(avail >= 0, BIG, 0),
        jnp.floor_divide(avail, safe[None, :]),
    )
    cap = jnp.min(per_dim, axis=1)
    return jnp.clip(cap, 0, k)


def _driver_candidates(avail, driver_rank, exec_ok, driver, executor, k):
    """What a gang decision reads of each node: whether the driver fits
    it, and its executor capacity without and with the driver on it."""
    # driver fit mask (Resources.GreaterThan: any-dim; fits = all dims ≤)
    driver_fits = jnp.all(avail >= driver[None, :], axis=1) & (driver_rank < BIG)
    base_cap = jnp.where(exec_ok, node_capacity(avail, executor, k), 0)
    cap_with_driver = jnp.where(
        exec_ok, node_capacity(avail - driver[None, :], executor, k), 0
    )
    return driver_fits, base_cap, cap_with_driver


def solve_app(
    avail: jnp.ndarray,        # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32 — driver priority position, BIG if not a candidate
    exec_ok: jnp.ndarray,      # [N] bool — in executor priority list (array order = that list)
    driver: jnp.ndarray,       # [3] int32
    executor: jnp.ndarray,     # [3] int32
    k: jnp.ndarray,            # [] int32
) -> AppSolve:
    """One gang decision, O(N) vector ops."""
    n = avail.shape[0]
    driver_fits, base_cap, cap_with_driver = _driver_candidates(
        avail, driver_rank, exec_ok, driver, executor, k
    )

    total = jnp.sum(base_cap)
    # total capacity if driver lands on d (only node d's capacity changes)
    total_d = total - base_cap + cap_with_driver

    feasible_d = driver_fits & (total_d >= k)
    # first feasible node in DRIVER priority order (ranks are unique)
    masked_rank = jnp.where(feasible_d, driver_rank, BIG)
    driver_idx = jnp.argmin(masked_rank).astype(jnp.int32)
    feasible = masked_rank[driver_idx] < BIG
    driver_idx = jnp.where(feasible, driver_idx, jnp.int32(n))

    safe_idx = jnp.minimum(driver_idx, n - 1)
    cap = jnp.where(
        jnp.arange(n, dtype=jnp.int32) == safe_idx, cap_with_driver, base_cap
    )
    cap = jnp.where(feasible, cap, jnp.zeros_like(cap))

    # tightly-pack greedy fill: x_n = clip(k − Σ_{m<n} cap_m, 0, cap_n)
    cum_excl = jnp.cumsum(cap) - cap
    exec_counts = jnp.clip(k - cum_excl, 0, cap)
    exec_counts = jnp.where(feasible, exec_counts, jnp.zeros_like(exec_counts))

    return AppSolve(
        feasible=feasible,
        driver_idx=jnp.where(feasible, driver_idx, jnp.int32(n)),
        exec_counts=exec_counts,
        exec_capacity=cap,
    )


def evenly_exec_mask(cap: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Which nodes receive ≥1 executor under distribute-evenly: the first
    min(k, #nodes-with-capacity) capacity-bearing nodes in priority order
    (sweep 0 of the round-robin)."""
    has = (cap > 0).astype(jnp.int32)
    rank_excl = jnp.cumsum(has) - has
    return (cap > 0) & (rank_excl < k)


def usage_delta(
    solve: AppSolve,
    driver: jnp.ndarray,
    executor: jnp.ndarray,
    n: int,
    evenly: bool,
) -> jnp.ndarray:
    """The reference's post-placement subtraction QUIRK
    (sparkpods.go:139-146 + resources.go:129-135): nodes hosting ≥1
    executor lose ONE executor's worth; the driver node loses the driver —
    unless it also hosts executors, in which case the executor entry
    overwrites the driver's."""
    if evenly:
        exec_mask = evenly_exec_mask(solve.exec_capacity, jnp.sum(solve.exec_counts))
        exec_mask = exec_mask & solve.feasible
    else:
        exec_mask = solve.exec_counts > 0
    is_driver = jnp.arange(n, dtype=jnp.int32) == solve.driver_idx
    delta = jnp.where(
        exec_mask[:, None],
        executor[None, :],
        jnp.where(is_driver[:, None], driver[None, :], jnp.zeros_like(driver)[None, :]),
    )
    return jnp.where(solve.feasible, delta, jnp.zeros_like(delta))


class QueueSolve(NamedTuple):
    feasible: jnp.ndarray     # [A] bool
    driver_idx: jnp.ndarray   # [A] int32
    exec_counts: jnp.ndarray  # [A, N] int32 (tightly-pack counts)
    exec_capacity: jnp.ndarray  # [A, N] int32
    avail_after: jnp.ndarray  # [N, 3] int32


@functools.partial(jax.jit, static_argnames=("evenly", "with_placements"))
def solve_queue(
    avail: jnp.ndarray,      # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,    # [N]
    drivers: jnp.ndarray,    # [A, 3] int32
    executors: jnp.ndarray,  # [A, 3] int32
    counts: jnp.ndarray,     # [A] int32
    app_valid: jnp.ndarray,  # [A] bool
    evenly: bool = False,
    with_placements: bool = True,
) -> QueueSolve:
    """Whole-FIFO-queue gang solve: scan apps in order, carrying
    availability.  Infeasible apps are skipped (no subtraction), exactly
    like a queue of Filter calls draining one by one.

    with_placements=False returns only the per-app decisions (feasible,
    driver_idx) and the final availability — the decision-latency path;
    any single app's placement is recomputable via solve_single.
    """
    n = avail.shape[0]

    def step(carry_avail, app):
        driver, executor, k, valid = app
        solve = solve_app(carry_avail, driver_rank, exec_ok, driver, executor, k)
        feasible = solve.feasible & valid
        solve = AppSolve(
            feasible=feasible,
            driver_idx=jnp.where(feasible, solve.driver_idx, jnp.int32(n)),
            exec_counts=jnp.where(feasible, solve.exec_counts, jnp.zeros_like(solve.exec_counts)),
            exec_capacity=solve.exec_capacity,
        )
        delta = usage_delta(solve, driver, executor, n, evenly)
        if with_placements:
            out = solve
        else:
            out = (feasible, solve.driver_idx)
        return carry_avail - delta, out

    avail_after, outs = lax.scan(step, avail, (drivers, executors, counts, app_valid))
    if with_placements:
        return QueueSolve(
            feasible=outs.feasible,
            driver_idx=outs.driver_idx,
            exec_counts=outs.exec_counts,
            exec_capacity=outs.exec_capacity,
            avail_after=avail_after,
        )
    feasible, driver_idx = outs
    return QueueSolve(
        feasible=feasible,
        driver_idx=driver_idx,
        exec_counts=jnp.zeros((0,), jnp.int32),
        exec_capacity=jnp.zeros((0,), jnp.int32),
        avail_after=avail_after,
    )


# Unbounded-capacity stand-in for the min-frag kernel (host uses
# 2^63-1, capacity.go:45-48).  Capacities here must stay UNCLAMPED for
# the (k+max)/2 subset threshold, so the sentinel lives just above any
# real capacity: callers guard max(avail) ≤ 2^31-3 (tensorize's GCD
# scaling makes this essentially always true) so a real capacity can
# never collide with it.
MF_SENT = 2**31 - 2


def min_frag_capacity(
    avail: jnp.ndarray, executor: jnp.ndarray, exec_ok: jnp.ndarray
) -> jnp.ndarray:
    """UNCLAMPED per-node executor capacity (capacity.go:36-75) for the
    minimal-fragmentation kernel; MF_SENT marks unbounded nodes."""
    safe = jnp.maximum(executor, 1)
    per_dim = jnp.where(
        executor[None, :] == 0,
        jnp.where(avail >= 0, MF_SENT, 0),
        jnp.floor_divide(avail, safe[None, :]),
    )
    cap = jnp.min(per_dim, axis=1)
    return jnp.where(exec_ok, jnp.clip(cap, 0, MF_SENT), 0)


def min_frag_counts(cap: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Minimal-fragmentation per-node executor counts from unclamped
    capacities — the whole of minimal_fragmentation.go:59-137 as
    sort-free vector ops, no data-dependent loop.

    The drain loop linearizes over capacity *value classes*: with
    T(v) = Σ_{cap ≥ v} cap, a class v is fully drained iff T(v) < k, so
    the stop class v* = max{v : T(v) ≥ k} (binary-searched in 31
    probes).  Entering v* with R = k − Σ_{cap > v*} cap remaining,
    t* = ⌈R/v*⌉ − 1 of its nodes (earliest in priority order) drain
    fully and the final k* = R − t*·v* executors go to the smallest
    remaining capacity ≥ k* (earliest priority among equals) — exactly
    the host's ascending bisect.  Probe sums clamp per-term to k so
    everything stays int32 (Σ min(cap,k) ≤ N·k, the scale_problem
    guard); drained classes all have cap < k so the exact prefix sum
    Σ_{cap > v*} cap < k needs no widening.  The (k+max)/2
    "avoid mostly-empty nodes" subset attempt
    (minimal_fragmentation.go:71-87) is the same computation under a
    tighter eligibility mask.  Only valid when Σ min(cap, k) ≥ k (the
    caller's solve_app feasibility); returns zeros otherwise and for
    k = 0."""
    n = cap.shape[0]
    elig = cap > 0
    d = jnp.where(elig, cap, 0)
    iota = jnp.arange(n, dtype=jnp.int32)

    def run(sub):
        """One _internal_minimal_fragmentation pass over the eligibility
        mask `sub`.  Returns (ok, counts-by-node)."""
        dd = jnp.where(sub, d, 0)
        dc = jnp.minimum(dd, k)  # probe terms, int32-safe to sum
        ok = (jnp.sum(dc) >= k) & (k > 0)

        def body(_, lohi):
            lo, hi = lohi
            mid = lo + (hi - lo + 1) // 2
            good = jnp.sum(jnp.where(dd >= mid, dc, 0)) >= k
            return (jnp.where(good, mid, lo), jnp.where(good, hi, mid - 1))

        # fixed 31 probes cover the full int32 capacity domain.  A
        # lax.while_loop bounded by max(dd) (~7 probes for real
        # capacities) is a candidate speedup, unmeasured on hardware.
        vstar, _ = lax.fori_loop(
            0, 31, body, (jnp.int32(1), jnp.int32(MF_SENT))
        )
        s = jnp.sum(jnp.where(dd > vstar, dd, 0))  # drained classes, < k
        r = k - s
        tstar = jnp.maximum(r - 1, 0) // vstar
        kstar = r - tstar * vstar
        at = sub & (dd == vstar)
        at_i = at.astype(jnp.int32)
        at_rank = jnp.cumsum(at_i) - at_i  # class position in priority order
        drained = (sub & (dd > vstar)) | (at & (at_rank < tstar))
        # final placement: smallest capacity ≥ k* among the not-drained,
        # ties to the earliest priority index (the ascending bisect)
        cand = sub & ~drained & (dd >= kstar)
        vp = jnp.min(jnp.where(cand, dd, BIG))
        partial = jnp.argmax(cand & (dd == vp)).astype(jnp.int32)
        counts = jnp.where(drained, dd, 0)
        counts = counts + jnp.where((iota == partial) & ok, kstar, 0)
        return ok, jnp.where(ok, counts, jnp.zeros_like(counts))

    max_cap = jnp.max(d)
    has_sent = jnp.any(elig & (d == MF_SENT))
    # exact (k + max)//2 without int32 overflow; with an unbounded node
    # the host threshold (k + 2^63-1)//2 admits every bounded capacity
    target = (k // 2) + (max_cap // 2) + (((k & 1) + (max_cap & 1)) // 2)
    subset = elig & jnp.where(has_sent, d < MF_SENT, d < target)
    attempt = has_sent | (k < max_cap)
    sub_ok, sub_counts = run(subset & attempt)
    full_ok, full_counts = run(elig)
    counts = jnp.where(attempt & sub_ok, sub_counts, full_counts)
    return jnp.where(full_ok, counts, jnp.zeros_like(counts))


def min_frag_step_counts(carry_avail, feasible, driver_idx, driver, executor, exec_ok, k):
    """Shared per-step min-frag placement: subtract the driver on its
    chosen node, run the capacity + drain kernels over the eligible
    mask, zero when infeasible.  Used by both the plain min-frag queue
    scan and the single-AZ scan's per-zone solves so capacity-semantics
    fixes can never diverge between lanes."""
    n = carry_avail.shape[0]
    is_drv = (jnp.arange(n, dtype=jnp.int32) == driver_idx) & feasible
    avail_eff = carry_avail - jnp.where(is_drv[:, None], driver[None, :], 0)
    mf = min_frag_counts(min_frag_capacity(avail_eff, executor, exec_ok), k)
    return jnp.where(feasible, mf, jnp.zeros_like(mf))


def mf_sentinel_safe(avail) -> bool:
    """Host-side guard shared by the fused min-frag lanes: every scaled
    availability value must stay below MF_SENT − 1 so a real capacity
    can never collide with the unbounded-capacity sentinel."""
    import numpy as _np

    a = _np.asarray(avail)
    return a.size == 0 or int(a.max()) <= MF_SENT - 1


# queue-scan assignment policies every whole-queue lane implements (the
# XLA scan, the pallas kernel, the native C++ solver, and the native
# delta-solve session — native/fifo_solver.cpp::FifoSession uses these
# exact integer codes); single-AZ policies are a separate solver family
QUEUE_POLICY_CODES = {
    "tightly-pack": 0,
    "distribute-evenly": 1,
    "minimal-fragmentation": 2,
}


def queue_policy_code(assignment_policy: str):
    """Native session policy code for a TpuFifoSolver assignment policy,
    or None when no whole-queue session lane serves it."""
    return QUEUE_POLICY_CODES.get(assignment_policy)


@functools.partial(jax.jit, static_argnames=("with_placements",))
def solve_queue_min_frag(
    avail: jnp.ndarray,      # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,    # [N]
    drivers: jnp.ndarray,    # [A, 3] int32
    executors: jnp.ndarray,  # [A, 3] int32
    counts: jnp.ndarray,     # [A] int32
    app_valid: jnp.ndarray,  # [A] bool
    with_placements: bool = True,
) -> QueueSolve:
    """Whole-FIFO-queue solve under the minimal-fragmentation policy in
    ONE dispatch (minimal_fragmentation.go:59-137 × resource.go:224-262).
    Feasibility and driver choice equal tightly-pack's (the drain is
    work-conserving, so distribution succeeds iff Σ capacity ≥ k); only
    the placement — and therefore the carried usage subtraction — needs
    the min-frag kernel."""
    n = avail.shape[0]

    def step(carry_avail, app):
        driver, executor, k, valid = app
        solve = solve_app(carry_avail, driver_rank, exec_ok, driver, executor, k)
        feasible = solve.feasible & valid
        didx = jnp.where(feasible, solve.driver_idx, jnp.int32(n))
        mf = min_frag_step_counts(
            carry_avail, feasible, didx, driver, executor, exec_ok, k
        )
        mf_solve = AppSolve(
            feasible=feasible, driver_idx=didx, exec_counts=mf, exec_capacity=mf
        )
        delta = usage_delta(mf_solve, driver, executor, n, evenly=False)
        out = (feasible, didx, mf) if with_placements else (feasible, didx)
        return carry_avail - delta, out

    avail_after, outs = lax.scan(step, avail, (drivers, executors, counts, app_valid))
    if with_placements:
        feasible, didx, mf = outs
        return QueueSolve(
            feasible=feasible,
            driver_idx=didx,
            exec_counts=mf,
            exec_capacity=jnp.zeros((0,), jnp.int32),
            avail_after=avail_after,
        )
    feasible, didx = outs
    return QueueSolve(
        feasible=feasible,
        driver_idx=didx,
        exec_counts=jnp.zeros((0,), jnp.int32),
        exec_capacity=jnp.zeros((0,), jnp.int32),
        avail_after=avail_after,
    )


@jax.jit
def solve_single(
    avail: jnp.ndarray,
    driver_rank: jnp.ndarray,
    exec_ok: jnp.ndarray,
    driver: jnp.ndarray,
    executor: jnp.ndarray,
    k: jnp.ndarray,
) -> AppSolve:
    """Single-app entry point (ops/batch_adapter.py: the policies' own
    ``binpack_func``)."""
    return solve_app(avail, driver_rank, exec_ok, driver, executor, k)


# app rows of one ``feasible_apps`` program, whatever the node bucket: a
# larger batch goes through the same program in blocks, so one compile
# per node bucket is everything a scan of the marker runs
VERDICT_ROWS = 1024


@jax.jit
def feasible_apps(
    node_cols: jnp.ndarray,  # [N, 6] int32: availability (3), driver rank, executor ok, group
    app_cols: jnp.ndarray,   # [VERDICT_ROWS, 8] int32: driver (3), executor (3), count, valid
) -> jnp.ndarray:
    """Whether each app's gang fits the SAME availability: ``solve_app``'s
    feasibility rule (some node the driver fits whose group holds ``k``
    executors with the driver on it) for every app row, with no carry
    from one row to the next.  ``group`` is 0 on every node under the
    plain policies; under single-AZ it is the node's candidate zone, -1
    outside them, and the gang has to fit one zone whole: ``solve_zones``'
    masked per-zone solves, any zone, from one evaluation of the
    capacities.  int32 [VERDICT_ROWS], 1 = feasible; padding rows are the
    caller's to drop.  A row is one step of a scan over O(N) vectors:
    nothing of size [apps, N] exists, and the program compiles as fast
    as ``solve_single`` (a vmapped app axis takes the TPU compiler
    minutes at 10,240 nodes)."""
    avail, driver_rank = node_cols[:, 0:3], node_cols[:, 3]
    exec_ok, group = node_cols[:, 4] != 0, node_cols[:, 5]
    n_groups = jnp.max(group) + 1

    def one_app(app):
        driver, k = app[0:3], app[6]
        driver_fits, base_cap, cap_with_driver = _driver_candidates(
            avail, driver_rank, exec_ok, driver, app[3:6], k
        )

        def add_group(z, total):
            member = group == z
            return jnp.where(member, jnp.sum(jnp.where(member, base_cap, 0)), total)

        # per node, the total capacity of its own group
        total = lax.fori_loop(0, n_groups, add_group, jnp.zeros_like(base_cap))
        total_d = total - base_cap + cap_with_driver
        return jnp.any(driver_fits & (group >= 0) & (total_d >= k))

    return lax.map(one_app, app_cols).astype(jnp.int32)


# ``valid`` column of ``solve_filter``'s app block: a queue app, the
# request's own app (as in the single-AZ passes, where 2 marks the probe)
APP_QUEUED = 1
APP_CURRENT = 2


@functools.partial(jax.jit, static_argnames=("policy", "pallas", "interpret"))
def solve_filter(
    node_cols: jnp.ndarray,  # [N, 5] int32: availability (3), driver rank, executor ok
    app_cols: jnp.ndarray,   # [A, 8] int32: driver (3), executor (3), count, valid
    policy: str,
    pallas: bool,
    interpret: bool = False,
) -> jnp.ndarray:
    """A driver Filter's whole device work as one program: the queue
    pass over the apps marked APP_QUEUED (the Pallas kernel of the
    policy, or its XLA scan), then ``solve_app`` for the row marked
    APP_CURRENT on the availability the pass leaves; the same calls on
    the same values as the pass followed by ``solve_single``.  The inputs
    are two arrays and the result is one (an upload and a read-back cost
    the host the same whatever their size), int32 [4N + A + 2]:
    avail_after [N, 3] row-major, then per node the current app's
    executor counts (its capacities under distribute-evenly), the
    queue's verdicts [A], the current app's feasible and driver node."""
    avail, driver_rank, exec_ok = node_cols[:, 0:3], node_cols[:, 3], node_cols[:, 4] != 0
    drivers, executors, counts = app_cols[:, 0:3], app_cols[:, 3:6], app_cols[:, 6]
    queued = app_cols[:, 7] == APP_QUEUED
    queue_args = (avail, driver_rank, exec_ok, drivers, executors, counts, queued)
    evenly = policy == "distribute-evenly"
    if policy == "minimal-fragmentation":
        if pallas:
            from .pallas_queue import pallas_solve_queue_min_frag

            verdicts, _, avail_after = pallas_solve_queue_min_frag(*queue_args, interpret=interpret)
        else:
            out = solve_queue_min_frag(*queue_args, with_placements=False)
            verdicts, avail_after = out.feasible, out.avail_after
    elif pallas:
        from .pallas_queue import pallas_solve_queue

        verdicts, _, avail_after = pallas_solve_queue(*queue_args, evenly=evenly, interpret=interpret)
    else:
        out = solve_queue(*queue_args, evenly=evenly, with_placements=False)
        verdicts, avail_after = out.feasible, out.avail_after
    current = app_cols[jnp.argmax(app_cols[:, 7] == APP_CURRENT)]
    solve = solve_app(avail_after, driver_rank, exec_ok, current[0:3], current[3:6], current[6])
    return jnp.concatenate([
        avail_after.reshape(-1),
        solve.exec_capacity if evenly else solve.exec_counts,
        verdicts.astype(jnp.int32),
        jnp.stack([solve.feasible.astype(jnp.int32), solve.driver_idx]),
    ])


class ZoneQueueSolve(NamedTuple):
    """Per-app outcome of the fused single-AZ FIFO scan."""

    feasible: jnp.ndarray    # [A] bool
    zone_idx: jnp.ndarray    # [A] int32 — chosen zone; Z = cross-zone fallback, -1 = none
    driver_idx: jnp.ndarray  # [A] int32
    uncertain: jnp.ndarray   # [A] bool — flagged: zone choice within the fixed-point margin, or a probe
    avail_after: jnp.ndarray  # [N, 3] int32
    slot: jnp.ndarray | None = None       # [A] int32 — the flagged app's snapshot, -1 = none
    snapshots: jnp.ndarray | None = None  # [max(n_slots, 1), 4, N] int32


# Fixed-point bits for the on-device zone-efficiency score.  The zone
# choice (single_az.go:75-97: highest average of per-occurrence max
# packing efficiency, strict improvement in zone order) is computed as
# Q_z = Σ_n w_n · round(2^EFF_SHIFT · maxEff_n) with integer weights
# w_n = executor count + driver indicator.  Because every feasible zone
# places exactly k executors + 1 driver, comparing averages equals
# comparing these sums.  Per-term quantization error is < 0.6 fixed-point
# ulps, so |Q_a − Q_b| > 2(k+1)+2 certifies that the float64 oracle
# orders the true sums the same way.  Anything closer — equal Q
# included: the same Q from different inputs proves nothing about the
# true sums, and equal sums added up in a different node order need not
# compare equal in float64 — flags the app: the caller decides it, that
# one app, in the oracle's float64 arithmetic, from a snapshot the pass
# leaves of the carry and of every zone's packing, and launches the
# pass again from that app only where it decides otherwise.  See
# docs/design.md § "Single-AZ zone choice on device".
EFF_SHIFT = 18
# per-app ``forced`` zone of the single-AZ queue passes: the pass chooses
FORCE_NONE = -2
# ``forced`` = HINT_BASE + z is a guess, not a decision: zone z is taken
# only where the score cannot certify the app, which stays flagged
HINT_BASE = 64
# a snapshot's packing plane: executor count | the driver's node << 30
DRIVER_BIT = 30


def snapshot_slots(n_nodes: int, compacted: bool = False) -> int:
    """Snapshot slots of a single-AZ pass over ``n_nodes``: as many as 6
    MiB hold (four int32 rows of the node axis each; the pallas kernel
    keeps them in its fast memory), 32 at the most.  Where the host
    reads the slots ``compacted`` (``compact_snapshots``: min-frag's
    valve), what it reads no longer grows with the node axis, and the
    slots may take 40 MiB, 256 at the most: a launch per 256 flagged
    apps instead of per 32."""
    budget, most = ((40 << 20), 256) if compacted else ((6 << 20), 32)
    return max(1, min(most, budget // (16 * max(n_nodes, 1))))


# nodes a compacted snapshot keeps per slot: every zone's placement of a
# gang of up to 41 executors in three zones; a slot that occupies more is
# read whole (the caller checks the count)
COMPACT_NODES = 128


def compact_snapshots(snapshots: jnp.ndarray, width: int = COMPACT_NODES) -> jnp.ndarray:
    """The snapshots [S, 4, N] as the min-frag valve reads them, [S, 1 +
    5 * width] int32: per slot the number of nodes its packing plane
    occupies, the first ``width`` of them in node order (N past the
    count), then the four planes at those nodes, plane by plane.  A slot
    the pass never filled reads as garbage, as it does whole."""
    s, _, n = snapshots.shape
    width = min(width, n)
    occupied = snapshots[:, 3] != 0
    count = occupied.sum(axis=1, dtype=jnp.int32)
    # the largest keys are the occupied nodes, the lowest node first
    key = jnp.where(occupied, -jnp.arange(n, dtype=jnp.int32)[None, :], jnp.int32(-n - 1))
    _, first = lax.top_k(key, width)
    nodes = jnp.where(jnp.arange(width, dtype=jnp.int32)[None, :] < count[:, None], first, n)
    values = jnp.take_along_axis(snapshots, jnp.minimum(nodes, n - 1)[:, None, :], axis=2)
    return jnp.concatenate(
        [count[:, None], nodes.astype(jnp.int32), values.reshape(s, 4 * width)], axis=1
    )


@jax.jit
def compacted_with_probe(snapshots: jnp.ndarray, probe_slot: jnp.ndarray):
    """(``compact_snapshots``, the probe's slot whole [4, N]) of a pass's
    snapshots, for a lane whose pass returns them apart."""
    return compact_snapshots(snapshots), snapshots[jnp.maximum(probe_slot, 0)]


def _zone_score(
    carry_avail: jnp.ndarray,  # [N, 3] int32 scaled
    solve: AppSolve,
    driver: jnp.ndarray,
    executor: jnp.ndarray,
    s_cpu_milli: jnp.ndarray,  # [N] int32 schedulable cpu, base milli units
    s_gpu_milli: jnp.ndarray,  # [N] int32
    inv_mem: jnp.ndarray,      # [N] f32 = scale_mem / schedulable_mem_bytes
    th_mem: jnp.ndarray,       # [N] int32 = ceil(sched_mem_bytes / scale_mem)
    scale_cpu: jnp.ndarray,    # [] int32
    scale_gpu: jnp.ndarray,    # [] int32
    eff_counts: jnp.ndarray | None = None,  # [N] int32 — reservation-side
    # counts for the efficiency numerators when they differ from the
    # occurrence weights (min-frag strict parity: the no-write-back
    # quirk makes efficiencies see only the driver, while occurrences
    # still weight every executor placement)
):
    """(Q, nonzero): the fixed-point zone score for one zone's packing and
    the exact S > 0 indicator (efficiency.go:80-156 semantics: value()
    ceil to cores for cpu/gpu, bytes for memory; gpu efficiency 0 on
    gpu-less nodes; per-node max over dims; occurrence-weighted sum)."""
    n = carry_avail.shape[0]
    is_driver = (jnp.arange(n, dtype=jnp.int32) == solve.driver_idx) & solve.feasible
    counts = solve.exec_counts
    w = counts + is_driver.astype(jnp.int32)
    res_counts = counts if eff_counts is None else eff_counts
    new = res_counts[:, None] * executor[None, :] + jnp.where(
        is_driver[:, None], driver[None, :], 0
    )
    m = carry_avail - new  # scaled availability net of this packing; ≥ 0 where w > 0

    # reserved numerators in exact base units (bounded int32 by the
    # caller's guards): r_dim = sched_base − m·scale
    num_cq = s_cpu_milli - m[:, 0] * scale_cpu
    num_gq = s_gpu_milli - m[:, 2] * scale_gpu
    num_cores = lax.div(num_cq + 999, jnp.int32(1000))
    num_gcores = lax.div(num_gq + 999, jnp.int32(1000))
    den_cores = jnp.maximum(lax.div(s_cpu_milli + 999, jnp.int32(1000)), 1)
    den_gcores = jnp.maximum(lax.div(s_gpu_milli + 999, jnp.int32(1000)), 1)
    has_gpu = s_gpu_milli > 0

    ratio_c = num_cores.astype(jnp.float32) / den_cores.astype(jnp.float32)
    ratio_g = jnp.where(
        has_gpu, num_gcores.astype(jnp.float32) / den_gcores.astype(jnp.float32), 0.0
    )
    ratio_m = jnp.maximum(1.0 - m[:, 1].astype(jnp.float32) * inv_mem, 0.0)
    eff = jnp.maximum(jnp.maximum(ratio_c, ratio_m), ratio_g)
    q = jnp.floor(eff * jnp.float32(2**EFF_SHIFT) + 0.5).astype(jnp.int32)
    score = jnp.sum(jnp.where(w > 0, w * q, 0))
    # exact S > 0: some occupied node has a strictly positive reserved
    # quantity in a dimension that counts (the all-zero-efficiency quirk)
    nonzero = jnp.any(
        (w > 0) & ((num_cq > 0) | (m[:, 1] < th_mem) | (has_gpu & (num_gq > 0)))
    )
    return score, nonzero


@functools.partial(jax.jit, static_argnames=("az_aware", "minfrag", "strict", "n_slots"))
def solve_queue_single_az(
    avail: jnp.ndarray,        # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,      # [N] bool
    zone_masks: jnp.ndarray,   # [Z, N] bool
    drivers: jnp.ndarray,      # [A, 3] int32
    executors: jnp.ndarray,    # [A, 3] int32
    counts: jnp.ndarray,       # [A] int32
    app_valid: jnp.ndarray,    # [A] bool, or int32 with 2 = a probe
    s_cpu_milli: jnp.ndarray,  # [N] int32
    s_gpu_milli: jnp.ndarray,  # [N] int32
    inv_mem: jnp.ndarray,      # [N] f32
    th_mem: jnp.ndarray,       # [N] int32
    scale_cpu: jnp.ndarray,    # [] int32
    scale_gpu: jnp.ndarray,    # [] int32
    forced: jnp.ndarray | None = None,  # [A] int32 — FORCE_NONE, -1 or a zone
    start: jnp.ndarray | None = None,   # [] int32 — first app this call solves
    az_aware: bool = False,
    minfrag: bool = False,
    strict: bool = True,
    n_slots: int = 0,
) -> ZoneQueueSolve:
    """Whole-FIFO-queue single-AZ gang solve in ONE dispatch
    (single_az.go:23-97 × resource.go:224-262): scan apps in order; each
    step solves every zone (inner tightly-pack, or the min-frag kernel
    when minfrag=True — single-az-minimal-fragmentation semantics, with
    driver-only efficiency numerators under strict parity), scores
    feasible zones with the fixed-point efficiency comparator (see
    EFF_SHIFT), applies the strict-improvement choice in zone order,
    optionally falls back to a cross-zone pack
    (az_aware_pack_tightly.go:27-38; no min-frag variant), and carries
    availability with the reference's subtraction quirk.

    An app whose zone the score cannot certify is *flagged*.  While a
    snapshot slot is free (``n_slots``) the pass goes on with the
    score's own choice and leaves in the slot what the exact decision
    needs: the carry as it stood before the app and every zone's
    packing (one row, the zones are disjoint: executor count, bit
    DRIVER_BIT = the driver's node).  The caller checks each flagged app
    in float64; where it decides otherwise it comes back with the
    slot's carry, ``start`` at the app (earlier apps do nothing) and
    the zone in ``forced`` (FORCE_NONE = the pass's own choice, -1 = no
    zone, z = take zone z, HINT_BASE + z = a guess: zone z if the app is
    flagged, and it stays flagged).  Out of slots, the pass halts at the flagged
    app: the carry stays as it stood, every later app does nothing, and
    ``avail_after`` is that carry.  ``app_valid`` 2 marks a probe (the
    request's own app): every zone packed into a slot, nothing placed."""
    assert not (az_aware and minfrag)
    n = avail.shape[0]
    a = drivers.shape[0]
    z_count = zone_masks.shape[0]
    if forced is None:  # schedlint: disable=JX001 -- None is the argument's absence, static under jit
        forced = jnp.full((a,), FORCE_NONE, jnp.int32)
    if start is None:  # schedlint: disable=JX001 -- None is the argument's absence, static under jit
        start = jnp.int32(0)
    iota = jnp.arange(n, dtype=jnp.int32)

    def step(carry, app):
        carry_avail, halted, taken, snapshots = carry
        driver, executor, k, valid, force, index = app
        active = (valid != 0) & ~halted & (index >= start)
        probe = valid == 2
        hinted = force >= HINT_BASE
        is_forced = (force != FORCE_NONE) & ~hinted
        band = 2 * (k + 1) + 2

        def zone_solve(mask):
            """One zone's packing + fixed-point score.  vmapped over
            zones so the scan body holds exactly ONE fori_loop — several
            per step (an unrolled zone loop around the min-frag kernel)
            sends XLA compile time pathological, like the while_loop
            note on min_frag_counts."""
            solve = solve_app(
                carry_avail,
                jnp.where(mask, driver_rank, BIG),
                exec_ok & mask,
                driver,
                executor,
                k,
            )
            if minfrag:
                mf = min_frag_step_counts(
                    carry_avail, solve.feasible, solve.driver_idx,
                    driver, executor, exec_ok & mask, k,
                )
                solve = AppSolve(
                    feasible=solve.feasible,
                    driver_idx=solve.driver_idx,
                    exec_counts=mf,
                    exec_capacity=solve.exec_capacity,
                )
                eff_counts = jnp.zeros_like(mf) if strict else mf
            else:
                eff_counts = None
            score, nz = _zone_score(
                carry_avail, solve, driver, executor,
                s_cpu_milli, s_gpu_milli, inv_mem, th_mem, scale_cpu, scale_gpu,
                eff_counts=eff_counts,
            )
            return solve.feasible, solve.driver_idx, solve.exec_counts, score, nz

        zf, zdidx, zcounts, zscore, znz = jax.vmap(zone_solve)(zone_masks)

        best_q = jnp.int32(0)
        best_zone = jnp.int32(-1)
        uncertain = jnp.zeros((), bool)
        chosen_counts = jnp.zeros((n,), jnp.int32)
        chosen_didx = jnp.int32(n)

        for z in range(z_count):
            f, score, nz = zf[z], zscore[z], znz[z]
            first = best_zone < 0
            better = f & jnp.where(first, nz, score > best_q)
            uncertain = uncertain | (f & ~first & (jnp.abs(score - best_q) <= band))
            best_q = jnp.where(better, score, best_q)
            take = jnp.where(is_forced, f & (force == z), better)
            best_zone = jnp.where(take, jnp.int32(z), best_zone)
            chosen_counts = jnp.where(take, zcounts[z], chosen_counts)
            chosen_didx = jnp.where(take, zdidx[z], chosen_didx)

        flagged = ((uncertain & ~is_forced) | probe) & active
        # where the score cannot tell, the caller's guess stands in for its choice
        guess = jnp.clip(force - HINT_BASE, 0, z_count - 1)
        use_hint = hinted & uncertain & zf[guess] & (force - HINT_BASE < z_count)
        best_zone = jnp.where(use_hint, guess, best_zone)
        chosen_counts = jnp.where(use_hint, zcounts[guess], chosen_counts)
        chosen_didx = jnp.where(use_hint, zdidx[guess], chosen_didx)
        keep = flagged & (taken < n_slots)
        halt = flagged & ~keep
        packings = jnp.sum(
            zcounts + ((iota[None, :] == zdidx[:, None]).astype(jnp.int32) << DRIVER_BIT),
            axis=0,
        )
        snapshots = lax.cond(
            keep,
            lambda s: s.at[taken].set(
                jnp.concatenate([carry_avail.T, packings[None, :]], axis=0)
            ),
            lambda s: s,
            snapshots,
        )

        if az_aware:
            cross = solve_app(carry_avail, driver_rank, exec_ok, driver, executor, k)
            use_cross = (best_zone < 0) & cross.feasible
            best_zone = jnp.where(use_cross, jnp.int32(z_count), best_zone)
            chosen_counts = jnp.where(use_cross, cross.exec_counts, chosen_counts)
            chosen_didx = jnp.where(use_cross, cross.driver_idx, chosen_didx)

        placed = (best_zone >= 0) & active & ~halt & ~probe
        chosen_counts = jnp.where(placed, chosen_counts, jnp.zeros_like(chosen_counts))
        chosen_didx = jnp.where(placed, chosen_didx, jnp.int32(n))

        # the reference's usage-subtraction quirk: one executor's worth on
        # hosting nodes, executor entry overwriting the driver's
        exec_mask = chosen_counts > 0
        is_driver = jnp.arange(n, dtype=jnp.int32) == chosen_didx
        delta = jnp.where(
            exec_mask[:, None],
            executor[None, :],
            jnp.where(is_driver[:, None], driver[None, :], jnp.zeros_like(driver)[None, :]),
        )
        delta = jnp.where(placed, delta, jnp.zeros_like(delta))
        out = (
            placed, jnp.where(placed, best_zone, jnp.int32(-1)), chosen_didx, flagged,
            jnp.where(keep, taken, jnp.int32(-1)),
        )
        return (carry_avail - delta, halted | halt, taken + keep, snapshots), out

    (avail_after, _, _, snapshots), outs = lax.scan(
        step,
        (
            avail, jnp.zeros((), bool), jnp.int32(0),
            jnp.zeros((max(n_slots, 1), 4, n), jnp.int32),
        ),
        (
            drivers, executors, counts, app_valid.astype(jnp.int32), forced,
            jnp.arange(a, dtype=jnp.int32),
        ),
    )
    placed, zone_idx, chosen_didx, uncertain, slot = outs
    return ZoneQueueSolve(
        feasible=placed,
        zone_idx=zone_idx,
        driver_idx=chosen_didx,
        uncertain=uncertain,
        avail_after=avail_after,
        slot=slot,
        snapshots=snapshots,
    )


def solve_zones(
    avail: jnp.ndarray,        # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,      # [N] bool
    zone_masks: jnp.ndarray,   # [Z, N] bool — node membership per zone
    driver: jnp.ndarray,       # [3] int32
    executor: jnp.ndarray,     # [3] int32
    k: jnp.ndarray,            # [] int32
) -> AppSolve:
    """Per-zone gang solves in one shot (the single-AZ combinator's inner
    loop, single_az.go:23-55): restrict driver candidates and executor
    capacity to each zone and solve every zone at once via vmap.  Zone
    selection (best avg packing efficiency) happens on host with the
    oracle's float64 math for exact parity."""

    def one_zone(mask):
        return solve_app(
            avail,
            jnp.where(mask, driver_rank, BIG),
            exec_ok & mask,
            driver,
            executor,
            k,
        )

    return jax.vmap(one_zone)(zone_masks)


solve_zones_jit = jax.jit(solve_zones)


def compilation_cache_stats() -> dict:
    """Entry counts of each jitted solver kernel's compilation cache —
    the profiling hook behind the kernel cache-hit metrics
    (tracing/profiling.py) and the periodic jit-cache gauge
    (metrics/reporters.py).  A steadily growing count in steady state
    means shape buckets are leaking recompiles onto the request path."""
    out = {}
    for name, fn in (
        ("solve_queue", solve_queue),
        ("solve_queue_min_frag", solve_queue_min_frag),
        ("solve_single", solve_single),
        ("feasible_apps", feasible_apps),
        ("solve_filter", solve_filter),
        ("solve_queue_single_az", solve_queue_single_az),
        ("solve_zones", solve_zones_jit),
    ):
        try:
            out[name] = fn._cache_size()
        except Exception:
            continue
    return out
