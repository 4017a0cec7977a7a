"""Pallas TPU kernel for the whole-FIFO-queue gang solve.

The XLA `lax.scan` path (batch_solver.solve_queue) pays per-iteration
dispatch + HBM round-trips for the availability carry; at 1k apps that
overhead dominates (~90µs/step).  This kernel instead runs the queue as
a single `pallas_call` with grid=(A,):

- the cluster availability lives in VMEM scratch, initialized from HBM
  on the first grid step and updated in place after each app — TPU grid
  steps execute sequentially on a core, so the scratch IS the scan
  carry, with zero HBM traffic per step;
- per-app demands are int32 scalars in SMEM via scalar prefetch;
- node arrays are laid out [R, 128] (row-major flattening of the
  priority order) so capacity math runs full-width on the VPU, with
  the flattened-order prefix sums done as lane-cumsum + row-offset.

Decision semantics are identical to batch_solver.solve_app (same
parity guarantees); this kernel returns per-app decisions (feasible,
driver node index) plus the final availability.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .batch_solver import DRIVER_BIT, EFF_SHIFT, FORCE_NONE, HINT_BASE, MF_SENT, compact_snapshots

LANES = 128
BIG = 2**31 - 1  # plain int: a module-level jnp scalar would be a captured const in the kernel


def _row_layout(n: int) -> Tuple[int, int]:
    rows = (n + LANES - 1) // LANES
    # sublane multiple of 8 for int32 tiling
    rows = ((rows + 7) // 8) * 8
    return rows, rows * LANES


def _inclusive_scan(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Hillis–Steele inclusive prefix sum via log-step circular shifts
    (mosaic has no cumsum primitive).  Wrapped lanes are masked off."""
    size = x.shape[axis]
    ids = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = 1
    while d < size:
        shifted = pltpu.roll(x, shift=d, axis=axis)
        x = x + jnp.where(ids >= d, shifted, 0)
        d *= 2
    return x


def _flat_cumsum_exclusive(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum of a [R, 128] int32 array in row-major
    (flattened) order: lane-axis scan within rows plus an exclusive
    row-offset scan across rows."""
    within = _inclusive_scan(x, axis=1)
    row_tot = jnp.broadcast_to(within[:, -1:], x.shape)
    row_incl = _inclusive_scan(row_tot, axis=0)  # lane-constant
    row_off = row_incl - row_tot
    return within + row_off - x


def _queue_kernel(
    # scalar prefetch (SMEM): per-app demand vectors
    dcpu, dmem, dgpu, ecpu, emem, egpu, ks, valids,
    # array inputs (VMEM)
    avail0,        # [R, 128] cpu plane (availability split into 3 planes)
    availm0,       # [R, 128] memory plane
    availg0,       # [R, 128] gpu plane
    rank_ref,      # [R, 128] int32 driver rank (BIG = not a candidate)
    execok_ref,    # [R, 128] int32 0/1
    # outputs
    feas_ref,      # per-app rows (lane 0 = feasible, lane 1 = driver idx)
    avail_out,     # [R, 128] ×3 final availability planes
    availm_out,
    availg_out,
    # scratch: availability carry
    ac, am, ag,
    *,
    evenly: bool,
    n_apps: int,
    apps_per_step: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ac[...] = avail0[...]
        am[...] = availm0[...]
        ag[...] = availg0[...]

    rank = rank_ref[...]
    exec_ok = execok_ref[...] != 0
    rows, lanes = rank.shape
    row_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    lane_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    node_ids = row_ids * lanes + lane_ids
    out_lanes = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    # the grid sequences blocks of `apps_per_step` apps; the inner loop is
    # unrolled at trace time, amortizing per-grid-step overhead (grid
    # pipelining + output DMA) over several apps
    for j in range(apps_per_step):
        a = i * apps_per_step + j
        dr = jnp.array([dcpu[a], dmem[a], dgpu[a]], dtype=jnp.int32)
        ex = jnp.array([ecpu[a], emem[a], egpu[a]], dtype=jnp.int32)
        k = ks[a]
        valid = valids[a]

        cpu, mem, gpu = ac[...], am[...], ag[...]

        feasible0, flat_idx, is_driver0, cap0 = _gang_core(
            cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids
        )
        feasible = feasible0 & (valid != 0)
        is_driver = is_driver0 & feasible
        cap = jnp.where(feasible, cap0, 0)

        if evenly:
            has = (cap > 0).astype(jnp.int32)
            rank_excl = _flat_cumsum_exclusive(has)
            exec_mask = (cap > 0) & (rank_excl < k)
        else:
            cum_excl = _flat_cumsum_exclusive(cap)
            x = jnp.clip(k - cum_excl, 0, cap)
            exec_mask = x > 0
        exec_mask = exec_mask & feasible

        # the reference's usage-subtraction quirk: executor overwrites driver
        dc = jnp.where(exec_mask, ex[0], jnp.where(is_driver, dr[0], 0))
        dm = jnp.where(exec_mask, ex[1], jnp.where(is_driver, dr[1], 0))
        dg = jnp.where(exec_mask, ex[2], jnp.where(is_driver, dr[2], 0))
        ac[...] = cpu - dc
        am[...] = mem - dm
        ag[...] = gpu - dg

        # outputs: 8 app-rows per (8, 128) tile
        idx_val = jnp.where(feasible, flat_idx, jnp.int32(rows * lanes))
        out_row = jnp.where(
            out_lanes == 0,
            feasible.astype(jnp.int32),
            jnp.where(out_lanes == 1, idx_val, 0),
        )
        feas_ref[pl.ds((i * apps_per_step + j) % 8, 1), :] = out_row

    @pl.when(i == (n_apps // apps_per_step) - 1)
    def _final():
        avail_out[...] = ac[...]
        availm_out[...] = am[...]
        availg_out[...] = ag[...]


def _gang_core(cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids):
    """The shared gang-solve core on [R, 128] planes (zone-maskable via
    rank/exec_ok), used by both queue kernels: driver selection by the
    capacity-total identity.  Returns (feasible, flat_idx, is_driver,
    cap) with cap already driver-adjusted and zeroed when infeasible."""

    def caps(c, m, g):
        def dim(avail_d, req):
            # zero-requirement → ∞ unless the dimension is already
            # negative (reserved(0) > available → 0, capacity.go:37-44)
            unbounded = jnp.where(avail_d >= 0, BIG, 0)
            return jnp.where(req == 0, unbounded, lax.div(avail_d, jnp.maximum(req, 1)))

        cap = jnp.minimum(jnp.minimum(dim(c, ex[0]), dim(m, ex[1])), dim(g, ex[2]))
        return jnp.clip(cap, 0, k)

    base_cap = jnp.where(exec_ok, caps(cpu, mem, gpu), 0)
    cap_with_driver = jnp.where(
        exec_ok, caps(cpu - dr[0], mem - dr[1], gpu - dr[2]), 0
    )
    driver_fits = (cpu >= dr[0]) & (mem >= dr[1]) & (gpu >= dr[2]) & (rank < BIG)
    total = jnp.sum(base_cap)
    total_d = total - base_cap + cap_with_driver
    feasible_d = driver_fits & (total_d >= k)

    masked_rank = jnp.where(feasible_d, rank, BIG)
    best_rank = jnp.min(masked_rank)
    feasible = best_rank < BIG
    flat_idx = jnp.min(jnp.where(masked_rank == best_rank, node_ids, BIG))
    is_driver = (node_ids == flat_idx) & feasible

    cap = jnp.where(is_driver, cap_with_driver, base_cap)
    cap = jnp.where(feasible, cap, 0)
    return feasible, flat_idx, is_driver, cap


def _mf_caps(cpu, mem, gpu, ex, exec_ok):
    """UNCLAMPED per-node capacity planes for the min-frag drain
    (batch_solver.min_frag_capacity): MF_SENT marks unbounded nodes."""

    def dim(avail_d, req):
        unbounded = jnp.where(avail_d >= 0, MF_SENT, 0)
        return jnp.where(req == 0, unbounded, lax.div(avail_d, jnp.maximum(req, 1)))

    cap = jnp.minimum(jnp.minimum(dim(cpu, ex[0]), dim(mem, ex[1])), dim(gpu, ex[2]))
    cap = jnp.clip(cap, 0, MF_SENT)
    return jnp.where(exec_ok, cap, 0)


def _mf_stop_class(dd, dc, k):
    """The drain's stop class v* = max{v : Σ_{dd ≥ v} min(dd, k) ≥ k}
    (1 when no class qualifies), searched only where it can lie.  With
    m = max(dd): if m ≥ k the node of capacity m alone contributes k and
    no class lies above it, so v* = m with no probe; otherwise v* ≤ m and
    the binary search over [1, m] takes at most ⌈log₂ m⌉ probes.  The
    same answer as batch_solver.min_frag_counts' 31 probes over the whole
    int32 domain whenever k > 0 (k = 0 zeroes the placement either way).
    Returns (vstar, probes)."""
    m = jnp.max(dd)
    hi = jnp.maximum(m, 1)
    lo = jnp.where(m >= k, hi, 1)

    def cond(c):
        lo, hi, _ = c
        return lo < hi

    def body(c):
        lo, hi, probes = c
        mid = lo + (hi - lo + 1) // 2
        good = jnp.sum(jnp.where(dd >= mid, dc, 0)) >= k
        return jnp.where(good, mid, lo), jnp.where(good, hi, mid - 1), probes + 1

    vstar, _, probes = lax.while_loop(cond, body, (lo, hi, jnp.int32(0)))
    return vstar, probes


def _mf_run(d, sub, k, node_ids):
    """One _internal_minimal_fragmentation pass over eligibility mask
    `sub` (batch_solver.min_frag_counts.run on [R,128] planes): the
    drain-stop value class (_mf_stop_class), then the drained mask and
    the final partial placement.  Returns (ok, drained,
    partial_flat_idx, kstar)."""
    dd = jnp.where(sub, d, 0)
    dc = jnp.minimum(dd, k)
    ok = (jnp.sum(dc) >= k) & (k > 0)
    vstar, _ = _mf_stop_class(dd, dc, k)
    s = jnp.sum(jnp.where(dd > vstar, dd, 0))  # drained classes, < k
    r = k - s
    tstar = jnp.maximum(r - 1, 0) // vstar
    kstar = r - tstar * vstar
    at = sub & (dd == vstar)
    at_rank = _flat_cumsum_exclusive(at.astype(jnp.int32))
    drained = (sub & (dd > vstar)) | (at & (at_rank < tstar))
    cand = sub & (~drained) & (dd >= kstar)
    vp = jnp.min(jnp.where(cand, dd, BIG))
    partial = jnp.min(jnp.where(cand & (dd == vp), node_ids, BIG))
    # empty candidate set → index 0, replicating the host argmax default
    partial = jnp.where(partial == BIG, 0, partial)
    return ok, drained, partial, kstar


def _solve_min_frag(cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids):
    """_gang_core feasibility/driver choice + the min-frag drain
    placement (batch_solver.min_frag_step_counts).  Returns (feasible,
    flat_idx, is_driver, counts) where counts carry the full drain
    values (n_i executors on node i; usage subtraction only needs
    counts > 0, zone scores need the values)."""
    feasible, flat_idx, is_driver, _cap = _gang_core(
        cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids
    )
    ce = cpu - jnp.where(is_driver, dr[0], 0)
    me = mem - jnp.where(is_driver, dr[1], 0)
    ge = gpu - jnp.where(is_driver, dr[2], 0)
    d = _mf_caps(ce, me, ge, ex, exec_ok)
    elig = d > 0

    max_cap = jnp.max(d)
    has_sent = jnp.any(elig & (d == MF_SENT))
    # exact (k + max)//2 without int32 overflow (batch_solver quirk:
    # an unbounded node's host threshold admits every bounded capacity)
    target = (k // 2) + (max_cap // 2) + (((k & 1) + (max_cap & 1)) // 2)
    # select the scalar threshold, not the masks: mosaic cannot legalize
    # a select whose operands are i1 vectors
    subset = elig & (d < jnp.where(has_sent, MF_SENT, target))
    attempt = has_sent | (k < max_cap)

    sub_ok, sub_drained, sub_partial, sub_kstar = _mf_run(
        d, subset & attempt, k, node_ids
    )
    full_ok, full_drained, full_partial, full_kstar = _mf_run(
        d, elig, k, node_ids
    )
    use_sub = attempt & sub_ok
    partial = jnp.where(use_sub, sub_partial, full_partial)
    kstar = jnp.where(use_sub, sub_kstar, full_kstar)
    # int32 planes for the same reason as the threshold above
    drained_counts = jnp.where(
        use_sub, jnp.where(sub_drained, d, 0), jnp.where(full_drained, d, 0)
    )
    counts = drained_counts + jnp.where(node_ids == partial, kstar, 0)
    counts = jnp.where(full_ok & feasible, counts, 0)
    return feasible, flat_idx, is_driver, counts


def _minfrag_queue_kernel(
    # scalar prefetch (SMEM)
    dcpu, dmem, dgpu, ecpu, emem, egpu, ks, valids,
    # VMEM planes
    avail0, availm0, availg0, rank_ref, execok_ref,
    # outputs
    feas_ref, avail_out, availm_out, availg_out,
    # scratch
    ac, am, ag,
    *,
    n_apps: int,
):
    """Whole minimal-fragmentation FIFO queue in one VMEM-resident
    kernel (batch_solver.solve_queue_min_frag decision semantics:
    tightly-pack feasibility/driver identity, min-frag drain placement,
    the usage-subtraction quirk on the carry)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ac[...] = avail0[...]
        am[...] = availm0[...]
        ag[...] = availg0[...]

    rank = rank_ref[...]
    exec_ok = execok_ref[...] != 0
    rows, lanes = rank.shape
    row_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    lane_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    node_ids = row_ids * lanes + lane_ids
    out_lanes = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    dr = jnp.array([dcpu[i], dmem[i], dgpu[i]], dtype=jnp.int32)
    ex = jnp.array([ecpu[i], emem[i], egpu[i]], dtype=jnp.int32)
    k = ks[i]
    valid = valids[i]

    cpu, mem, gpu = ac[...], am[...], ag[...]
    feasible0, flat_idx, is_driver0, counts = _solve_min_frag(
        cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids
    )
    feasible = feasible0 & (valid != 0)
    is_driver = is_driver0 & feasible
    exec_mask = (counts > 0) & feasible

    dc = jnp.where(exec_mask, ex[0], jnp.where(is_driver & ~exec_mask, dr[0], 0))
    dm = jnp.where(exec_mask, ex[1], jnp.where(is_driver & ~exec_mask, dr[1], 0))
    dg = jnp.where(exec_mask, ex[2], jnp.where(is_driver & ~exec_mask, dr[2], 0))
    ac[...] = cpu - dc
    am[...] = mem - dm
    ag[...] = gpu - dg

    idx_val = jnp.where(feasible, flat_idx, jnp.int32(rows * lanes))
    out_row = jnp.where(
        out_lanes == 0,
        feasible.astype(jnp.int32),
        jnp.where(out_lanes == 1, idx_val, 0),
    )
    feas_ref[pl.ds(i % 8, 1), :] = out_row

    @pl.when(i == n_apps - 1)
    def _final():
        avail_out[...] = ac[...]
        availm_out[...] = am[...]
        availg_out[...] = ag[...]


def _solve_tightly(cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids):
    """_gang_core + the tightly-pack greedy fill.  Returns (feasible,
    flat_idx, is_driver, exec_counts)."""
    feasible, flat_idx, is_driver, cap = _gang_core(
        cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids
    )
    cum_excl = _flat_cumsum_exclusive(cap)
    x = jnp.clip(k - cum_excl, 0, cap)
    x = jnp.where(feasible, x, 0)
    return feasible, flat_idx, is_driver, x


def _singleaz_kernel(
    # scalar prefetch (SMEM)
    dcpu, dmem, dgpu, ecpu, emem, egpu, ks, valids, scale_c_ref, scale_g_ref,
    forced_ref, start_ref,
    # VMEM planes
    avail0, availm0, availg0, rank_ref, execok_ref, zone_ref,
    scpu_ref, sgpu_ref, thm_ref, invm_ref,
    # outputs
    feas_ref, avail_out, availm_out, availg_out, snap_ref,
    # scratch: availability carry; SMEM [halted, snapshot slots taken]
    ac, am, ag, state,
    *,
    n_zones: int,
    az_aware: bool,
    n_apps: int,
    n_slots: int,
    minfrag: bool = False,
    strict: bool = True,
):
    """Whole single-AZ FIFO queue in one VMEM-resident kernel: the
    pallas counterpart of batch_solver.solve_queue_single_az (same
    decision semantics: per-zone tightly-pack — or the min-frag drain
    when minfrag=True, with driver-only efficiency reservations under
    strict parity — certified fixed-point zone score at EFF_SHIFT=18,
    strict-improvement choice in zone order, az-aware cross-zone
    fallback, subtraction quirk).

    An app whose zone the score cannot certify is *flagged*.  While a
    snapshot slot is free the pass goes on with the score's own choice
    and leaves in the slot what the exact decision needs: the carry as
    it stood before the app and every zone's packing (one plane, the
    zones are disjoint: executor count, bit 30 = the driver's node).
    The caller checks each flagged app in float64 afterwards; where it
    decides otherwise it launches again from that app (``start``:
    earlier steps do nothing) with the slot's carry and the zone in
    ``forced`` (FORCE_NONE = the kernel's choice, -1 = no zone, z = take
    zone z, HINT_BASE + z = a guess: zone z if the app is flagged, and it
    stays flagged).  Out of slots, the pass halts at the flagged app: the
    carry stays as it stood, every later step does nothing, and
    ``avail_out`` is that carry.  ``valid`` 2 marks a probe (the
    request's own app): every zone packed into a slot, nothing placed.
    Padding apps (valid 0) are skipped outright."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ac[...] = avail0[...]
        am[...] = availm0[...]
        ag[...] = availg0[...]
        state[0] = 0
        state[1] = 0

    rows, lanes = rank_ref.shape
    out_lanes = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    active = (i >= start_ref[0]) & (state[0] == 0) & (valids[i] != 0)

    @pl.when(jnp.logical_not(active))
    def _skip():
        feas_ref[pl.ds(i % 8, 1), :] = jnp.where(
            out_lanes == 1,
            jnp.int32(rows * lanes),
            jnp.where((out_lanes == 2) | (out_lanes == 4), -1, 0),
        )

    @pl.when(active)
    def _step():
        rank = rank_ref[...]
        exec_ok = execok_ref[...] != 0
        zone_plane = zone_ref[...]
        s_cpu = scpu_ref[...]
        s_gpu = sgpu_ref[...]
        th_m = thm_ref[...]
        inv_m = invm_ref[...]
        scale_c = scale_c_ref[0]
        scale_g = scale_g_ref[0]
        row_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
        lane_ids = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
        node_ids = row_ids * lanes + lane_ids

        dr = jnp.array([dcpu[i], dmem[i], dgpu[i]], dtype=jnp.int32)
        ex = jnp.array([ecpu[i], emem[i], egpu[i]], dtype=jnp.int32)
        k = ks[i]
        forced = forced_ref[i]
        hinted = forced >= HINT_BASE  # a guess for a flagged app, checked like the score's own
        is_forced = (forced != FORCE_NONE) & jnp.logical_not(hinted)
        band = 2 * (k + 1) + 2

        cpu, mem, gpu = ac[...], am[...], ag[...]
        den_c = jnp.maximum(lax.div(s_cpu + 999, jnp.int32(1000)), 1)
        den_g = jnp.maximum(lax.div(s_gpu + 999, jnp.int32(1000)), 1)
        has_gpu = s_gpu > 0

        best_q = jnp.int32(0)
        best_zone = jnp.int32(-1)
        uncertain = jnp.int32(0)
        # int32 planes (not bool): mosaic cannot legalize a select over i1
        # vectors with a scalar predicate
        chosen_exec = jnp.zeros((rows, lanes), jnp.int32)
        chosen_driver = jnp.zeros((rows, lanes), jnp.int32)
        chosen_idx = jnp.int32(rows * lanes)
        hint_exec = jnp.zeros((rows, lanes), jnp.int32)
        hint_driver = jnp.zeros((rows, lanes), jnp.int32)
        hint_idx = jnp.int32(rows * lanes)
        hint_fits = jnp.int32(0)
        packings = jnp.zeros((rows, lanes), jnp.int32)

        def score(x, is_driver, res=None):
            # x weights the occurrences; `res` (default x) is the
            # reservation seen by the efficiency numerators — they differ
            # only under min-frag strict parity (the no-write-back quirk)
            res = x if res is None else res
            w = x + is_driver.astype(jnp.int32)
            new_c = res * ex[0] + jnp.where(is_driver, dr[0], 0)
            new_m = res * ex[1] + jnp.where(is_driver, dr[1], 0)
            new_g = res * ex[2] + jnp.where(is_driver, dr[2], 0)
            m_c = cpu - new_c
            m_m = mem - new_m
            m_g = gpu - new_g
            num_cq = s_cpu - m_c * scale_c
            num_gq = s_gpu - m_g * scale_g
            num_cores = lax.div(num_cq + 999, jnp.int32(1000))
            num_gcores = lax.div(num_gq + 999, jnp.int32(1000))
            ratio_c = num_cores.astype(jnp.float32) / den_c.astype(jnp.float32)
            ratio_g = jnp.where(
                has_gpu, num_gcores.astype(jnp.float32) / den_g.astype(jnp.float32), 0.0
            )
            ratio_m = jnp.maximum(1.0 - m_m.astype(jnp.float32) * inv_m, 0.0)
            eff = jnp.maximum(jnp.maximum(ratio_c, ratio_m), ratio_g)
            q = jnp.floor(eff * jnp.float32(2**EFF_SHIFT) + 0.5).astype(jnp.int32)
            q_sum = jnp.sum(jnp.where(w > 0, w * q, 0))
            nz = jnp.any(
                (w > 0) & ((num_cq > 0) | (m_m < th_m) | (has_gpu & (num_gq > 0)))
            )
            return q_sum, nz

        for z in range(n_zones):
            mask = zone_plane == z
            if minfrag:
                f, flat_idx, is_driver, x = _solve_min_frag(
                    cpu, mem, gpu,
                    jnp.where(mask, rank, BIG), exec_ok & mask, dr, ex, k, node_ids,
                )
                res = jnp.zeros_like(x) if strict else x
                q_sum, nz = score(x, is_driver, res=res)
            else:
                f, flat_idx, is_driver, x = _solve_tightly(
                    cpu, mem, gpu,
                    jnp.where(mask, rank, BIG), exec_ok & mask, dr, ex, k, node_ids,
                )
                q_sum, nz = score(x, is_driver)
            first = best_zone < 0
            better = f & jnp.where(first, nz, q_sum > best_q)
            # inside the band the float64 oracle may order the two either
            # way — equal scores included: equal Q from different inputs is
            # no proof of equal sums, and equal sums added up in another
            # order need not compare equal in float64 either
            uncertain = uncertain | (
                f & (~first) & (jnp.abs(q_sum - best_q) <= band)
            ).astype(jnp.int32)
            best_q = jnp.where(better, q_sum, best_q)
            packings = packings + x + (is_driver.astype(jnp.int32) << DRIVER_BIT)
            take = jnp.where(is_forced, f & (forced == z), better)
            best_zone = jnp.where(take, jnp.int32(z), best_zone)
            chosen_exec = jnp.where(take, (x > 0).astype(jnp.int32), chosen_exec)
            chosen_driver = jnp.where(take, is_driver.astype(jnp.int32), chosen_driver)
            chosen_idx = jnp.where(take, flat_idx, chosen_idx)
            guess = hinted & f & (forced == HINT_BASE + z)
            hint_exec = jnp.where(guess, (x > 0).astype(jnp.int32), hint_exec)
            hint_driver = jnp.where(guess, is_driver.astype(jnp.int32), hint_driver)
            hint_idx = jnp.where(guess, flat_idx, hint_idx)
            hint_fits = hint_fits | guess.astype(jnp.int32)

        # a forced app was decided exactly by the caller; any other app the
        # score cannot certify, and a probe, is flagged: into a slot while
        # there is one, a halt before the carry is touched once there is none
        probe = valids[i] == 2
        flagged = jnp.where(is_forced, 0, uncertain) | probe.astype(jnp.int32)
        # where the score cannot tell, the caller's guess (what it decided
        # for this app last time) stands in for the score's own choice
        use_hint = (uncertain != 0) & (hint_fits != 0)
        best_zone = jnp.where(use_hint, forced - HINT_BASE, best_zone)
        chosen_exec = jnp.where(use_hint, hint_exec, chosen_exec)
        chosen_driver = jnp.where(use_hint, hint_driver, chosen_driver)
        chosen_idx = jnp.where(use_hint, hint_idx, chosen_idx)
        slot = state[1]
        keep = (flagged != 0) & (slot < n_slots)
        halt = (flagged != 0) & (slot >= n_slots)
        state[0] = halt.astype(jnp.int32)

        @pl.when(keep)
        def _snapshot():
            snap_ref[slot, 0] = cpu
            snap_ref[slot, 1] = mem
            snap_ref[slot, 2] = gpu
            snap_ref[slot, 3] = packings
            state[1] = slot + 1

        if az_aware:
            f, flat_idx, is_driver, x = _solve_tightly(
                cpu, mem, gpu, rank, exec_ok, dr, ex, k, node_ids
            )
            use_cross = (best_zone < 0) & f
            chosen_exec = jnp.where(use_cross, (x > 0).astype(jnp.int32), chosen_exec)
            chosen_driver = jnp.where(use_cross, is_driver.astype(jnp.int32), chosen_driver)
            chosen_idx = jnp.where(use_cross, flat_idx, chosen_idx)
            best_zone = jnp.where(use_cross, jnp.int32(n_zones), best_zone)

        placed = (best_zone >= 0) & jnp.logical_not(halt | probe)
        exec_mask = (chosen_exec != 0) & placed
        driver_mask = (chosen_driver != 0) & placed & ~exec_mask

        ac[...] = cpu - jnp.where(exec_mask, ex[0], jnp.where(driver_mask, dr[0], 0))
        am[...] = mem - jnp.where(exec_mask, ex[1], jnp.where(driver_mask, dr[1], 0))
        ag[...] = gpu - jnp.where(exec_mask, ex[2], jnp.where(driver_mask, dr[2], 0))

        idx_val = jnp.where(placed, chosen_idx, jnp.int32(rows * lanes))
        zone_val = jnp.where(placed, best_zone, jnp.int32(-1))
        out_row = jnp.where(
            out_lanes == 0,
            placed.astype(jnp.int32),
            jnp.where(
                out_lanes == 1,
                idx_val,
                jnp.where(
                    out_lanes == 2,
                    zone_val,
                    jnp.where(
                        out_lanes == 3,
                        flagged,
                        jnp.where(out_lanes == 4, jnp.where(keep, slot, -1), 0),
                    ),
                ),
            ),
        )
        feas_ref[pl.ds(i % 8, 1), :] = out_row

    @pl.when(i == n_apps - 1)
    def _final():
        avail_out[...] = ac[...]
        availm_out[...] = am[...]
        availg_out[...] = ag[...]


def _single_az_call(
    avail, driver_rank, exec_ok, zone_id, drivers, executors, counts, app_valid,
    s_cpu_milli, s_gpu_milli, inv_mem, th_mem, scale_cpu, scale_gpu, forced, start,
    *, n_zones, az_aware, interpret, minfrag, strict, n_slots,
):
    """The single-AZ ``pallas_call``: per-app rows [A, 128] int32 (lane
    0 placed, 1 driver node, 2 zone, 3 flagged, 4 snapshot slot or -1),
    avail_after [N, 3] and the snapshots [max(n_slots, 1), 4, N]
    (availability planes cpu, memory, gpu, then the packings)."""
    assert not (az_aware and minfrag)
    n = avail.shape[0]
    a = drivers.shape[0]
    rows, padded = _row_layout(n)

    def plane(v, fill=0, dtype=jnp.int32):
        flat = jnp.full((padded,), fill, dtype=dtype)
        flat = flat.at[:n].set(v.astype(dtype))
        return flat.reshape(rows, LANES)

    slots = max(n_slots, 1)
    kernel = functools.partial(
        _singleaz_kernel, n_zones=n_zones, az_aware=az_aware, n_apps=a,
        n_slots=n_slots, minfrag=minfrag, strict=strict,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=12,
        grid=(a,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0))] * 10,
        out_specs=[
            pl.BlockSpec((8, LANES), lambda i, *refs: (i // 8, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((slots, 4, rows, LANES), lambda i, *refs: (0, 0, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)] * 3
        + [pltpu.SMEM((2,), jnp.int32)],
    )
    out_shape = [
        jax.ShapeDtypeStruct((a, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((slots, 4, rows, LANES), jnp.int32),
    ]
    feas, c_out, m_out, g_out, snaps = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="pallas_solve_queue_single_az",  # the device trace's name for it
    )(
        drivers[:, 0], drivers[:, 1], drivers[:, 2],
        executors[:, 0], executors[:, 1], executors[:, 2],
        counts, app_valid.astype(jnp.int32),
        scale_cpu.astype(jnp.int32), scale_gpu.astype(jnp.int32),
        forced.astype(jnp.int32), start.astype(jnp.int32),
        plane(avail[:, 0]), plane(avail[:, 1]), plane(avail[:, 2]),
        plane(driver_rank, fill=int(BIG)),
        plane(exec_ok.astype(jnp.int32)),
        plane(zone_id, fill=-1),
        plane(s_cpu_milli), plane(s_gpu_milli),
        plane(th_mem),
        plane(inv_mem, fill=0, dtype=jnp.float32),
    )
    avail_after = jnp.stack(
        [c_out.reshape(-1)[:n], m_out.reshape(-1)[:n], g_out.reshape(-1)[:n]], axis=1
    )
    return feas, avail_after, snaps.reshape(slots, 4, padded)[:, :, :n]


_SINGLE_AZ_STATIC = ("n_zones", "az_aware", "interpret", "minfrag", "strict", "n_slots")


@functools.partial(jax.jit, static_argnames=_SINGLE_AZ_STATIC)
def pallas_solve_queue_single_az(
    avail: jnp.ndarray,        # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,      # [N] bool
    zone_id: jnp.ndarray,      # [N] int32 (zone index; -1 = no candidate zone)
    drivers: jnp.ndarray,      # [A, 3] int32
    executors: jnp.ndarray,    # [A, 3] int32
    counts: jnp.ndarray,       # [A] int32
    app_valid: jnp.ndarray,    # [A] bool
    s_cpu_milli: jnp.ndarray,  # [N] int32
    s_gpu_milli: jnp.ndarray,  # [N] int32
    inv_mem: jnp.ndarray,      # [N] f32
    th_mem: jnp.ndarray,       # [N] int32
    scale_cpu: jnp.ndarray,    # [1] int32
    scale_gpu: jnp.ndarray,    # [1] int32
    forced: jnp.ndarray | None = None,  # [A] int32 — FORCE_NONE, -1 or a zone
    start: jnp.ndarray | None = None,   # [1] int32 — first app this launch solves
    n_zones: int = 1,
    az_aware: bool = False,
    interpret: bool = False,
    minfrag: bool = False,
    strict: bool = True,
    n_slots: int = 0,
):
    """Single-kernel single-AZ FIFO solve.  Returns (feasible[A],
    zone_idx[A], driver_idx[A], flagged[A], avail_after[N, 3]) with
    decisions identical to batch_solver.solve_queue_single_az
    (tests/test_pallas_queue.py proves it on randomized queues),
    ``forced``, ``start`` and the flagged apps included (see
    ``_singleaz_kernel``; without slots, the default here, the pass
    halts at the first one).  minfrag=True gives the
    single-az-minimal-fragmentation inner policy (no az_aware variant
    exists in the reference; caller guards mf_sentinel_safe)."""
    a = drivers.shape[0]
    if forced is None:  # schedlint: disable=JX001 -- None is the argument's absence, static under jit
        forced = jnp.full((a,), FORCE_NONE, jnp.int32)
    if start is None:  # schedlint: disable=JX001 -- None is the argument's absence, static under jit
        start = jnp.zeros((1,), jnp.int32)
    feas, avail_after, _ = _single_az_call(
        avail, driver_rank, exec_ok, zone_id, drivers, executors, counts, app_valid,
        s_cpu_milli, s_gpu_milli, inv_mem, th_mem, scale_cpu, scale_gpu, forced, start,
        n_zones=n_zones, az_aware=az_aware, interpret=interpret, minfrag=minfrag,
        strict=strict, n_slots=n_slots,
    )
    feasible = feas[:, 0] != 0
    driver_idx = jnp.where(feasible, feas[:, 1], jnp.int32(avail.shape[0]))
    return feasible, feas[:, 2], driver_idx, feas[:, 3] != 0, avail_after


@functools.partial(
    jax.jit,
    static_argnames=("n_zones", "az_aware", "interpret", "minfrag", "strict", "n_slots", "compact"),
)
def pallas_solve_queue_single_az_packed(
    avail: jnp.ndarray,      # [N, 3] int32 — the carry this launch starts from
    node_cols: jnp.ndarray,  # [N, 7] int32: driver rank, executor ok, zone, schedulable
    # milli-cpu, schedulable milli-gpu, memory threshold, the bits of f32 inv_mem
    app_cols: jnp.ndarray,   # [A, 9] int32: driver (3), executor (3), count, valid, forced
    scalars: jnp.ndarray,    # [3] int32: scale_cpu, scale_gpu, start
    n_zones: int = 1,
    az_aware: bool = False,
    interpret: bool = False,
    minfrag: bool = False,
    strict: bool = True,
    n_slots: int = 0,
    compact: bool = False,
):
    """``pallas_solve_queue_single_az`` as the served path launches it:
    the inputs in four arrays (an upload costs the host the same
    whatever its size; ``valid`` 2 marks the probe) and the per-app
    results in one, [A, 5] int32 (placed, driver node, zone, flagged,
    snapshot slot), then avail_after [N, 3] and the snapshots
    [n_slots, 4, N].  ``compact``: the snapshots also as
    ``batch_solver.compact_snapshots`` has them and the probe's slot
    whole [4, N], which is what the min-frag valve reads back."""
    feas, avail_after, snaps = _single_az_call(
        avail, node_cols[:, 0], node_cols[:, 1], node_cols[:, 2],
        app_cols[:, 0:3], app_cols[:, 3:6], app_cols[:, 6], app_cols[:, 7],
        node_cols[:, 3], node_cols[:, 4],
        lax.bitcast_convert_type(node_cols[:, 6], jnp.float32), node_cols[:, 5],
        scalars[0:1], scalars[1:2], app_cols[:, 8], scalars[2:3],
        n_zones=n_zones, az_aware=az_aware, interpret=interpret, minfrag=minfrag,
        strict=strict, n_slots=n_slots,
    )
    node = jnp.where(feas[:, 0] != 0, feas[:, 1], jnp.int32(avail.shape[0]))
    columns = feas[:, 0:5].at[:, 1].set(node)
    if not compact:
        return columns, avail_after, snaps
    probe_slot = jnp.max(jnp.where(app_cols[:, 7] == 2, feas[:, 4], 0))
    return columns, avail_after, snaps, compact_snapshots(snaps), snaps[probe_slot]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_solve_queue_min_frag(
    avail: jnp.ndarray,        # [N, 3] int32
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,      # [N] bool
    drivers: jnp.ndarray,      # [A, 3] int32
    executors: jnp.ndarray,    # [A, 3] int32
    counts: jnp.ndarray,       # [A] int32
    app_valid: jnp.ndarray,    # [A] bool
    interpret: bool = False,
):
    """Whole minimal-fragmentation FIFO queue in ONE pallas kernel.
    Returns (feasible[A] bool, driver_idx[A] int32, avail_after[N,3])
    with decisions identical to batch_solver.solve_queue_min_frag
    (tests/test_pallas_queue.py::test_pallas_min_frag_matches_xla).
    Caller guards batch_solver.mf_sentinel_safe, like the XLA lane."""
    n = avail.shape[0]
    a = drivers.shape[0]
    rows, padded = _row_layout(n)

    def plane(v, fill=0):
        flat = jnp.full((padded,), fill, dtype=jnp.int32)
        flat = flat.at[:n].set(v.astype(jnp.int32))
        return flat.reshape(rows, LANES)

    kernel = functools.partial(_minfrag_queue_kernel, n_apps=a)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(a,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0))] * 5,
        out_specs=[
            pl.BlockSpec((8, LANES), lambda i, *refs: (i // 8, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)] * 3,
    )
    out_shape = [
        jax.ShapeDtypeStruct((a, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    ]
    feas, c_out, m_out, g_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        drivers[:, 0], drivers[:, 1], drivers[:, 2],
        executors[:, 0], executors[:, 1], executors[:, 2],
        counts, app_valid.astype(jnp.int32),
        plane(avail[:, 0]), plane(avail[:, 1]), plane(avail[:, 2]),
        plane(driver_rank, fill=int(BIG)),
        plane(exec_ok.astype(jnp.int32)),
    )
    feasible = feas[:, 0] != 0
    driver_idx = jnp.where(feasible, feas[:, 1], jnp.int32(n))
    avail_after = jnp.stack(
        [c_out.reshape(-1)[:n], m_out.reshape(-1)[:n], g_out.reshape(-1)[:n]], axis=1
    )
    return feasible, driver_idx, avail_after


@functools.partial(
    jax.jit, static_argnames=("evenly", "interpret", "apps_per_step")
)
def pallas_solve_queue(
    avail: jnp.ndarray,        # [N, 3] int32 (N multiple of LANES*8 preferred)
    driver_rank: jnp.ndarray,  # [N] int32
    exec_ok: jnp.ndarray,      # [N] bool
    drivers: jnp.ndarray,      # [A, 3] int32
    executors: jnp.ndarray,    # [A, 3] int32
    counts: jnp.ndarray,       # [A] int32
    app_valid: jnp.ndarray,    # [A] bool
    evenly: bool = False,
    interpret: bool = False,
    apps_per_step: int = 1,
):
    """Returns (feasible[A] bool, driver_idx[A] int32, avail_after[N,3]).

    apps_per_step batches several apps per grid step (unrolled in the
    kernel body) to amortize per-step overhead; must divide the app
    count and 8 (the output tile height).
    """
    n = avail.shape[0]
    a = drivers.shape[0]
    if apps_per_step <= 0 or a % apps_per_step or 8 % apps_per_step:
        raise ValueError(
            f"apps_per_step={apps_per_step} must be positive and divide {a} and 8"
        )
    rows, padded = _row_layout(n)

    def plane(v, fill=0):
        flat = jnp.full((padded,), fill, dtype=jnp.int32)
        flat = flat.at[:n].set(v.astype(jnp.int32))
        return flat.reshape(rows, LANES)

    cpu_p = plane(avail[:, 0])
    mem_p = plane(avail[:, 1])
    gpu_p = plane(avail[:, 2])
    rank_p = plane(driver_rank, fill=int(BIG))
    exec_p = plane(exec_ok.astype(jnp.int32))

    kernel = functools.partial(
        _queue_kernel, evenly=evenly, n_apps=a, apps_per_step=apps_per_step
    )
    g = apps_per_step
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(a // g,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0))] * 5,
        out_specs=[
            pl.BlockSpec((8, LANES), lambda i, *refs: ((i * g) // 8, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, *refs: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)] * 3,
    )
    out_shape = [
        jax.ShapeDtypeStruct((a, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    ]
    feas, c_out, m_out, g_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        drivers[:, 0], drivers[:, 1], drivers[:, 2],
        executors[:, 0], executors[:, 1], executors[:, 2],
        counts, app_valid.astype(jnp.int32),
        cpu_p, mem_p, gpu_p, rank_p, exec_p,
    )
    feasible = feas[:, 0] != 0
    driver_idx = jnp.where(feasible, feas[:, 1], jnp.int32(n))
    avail_after = jnp.stack(
        [c_out.reshape(-1)[:n], m_out.reshape(-1)[:n], g_out.reshape(-1)[:n]], axis=1
    )
    return feasible, driver_idx, avail_after
