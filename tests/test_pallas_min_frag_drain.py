"""The min-frag drain inside the Pallas kernels, node by node.

The queue-level parity tests (test_pallas_queue.py) see a drain only
through `counts > 0` on the carry, so a wrong stop class that keeps the
same mask would pass them.  Here `_solve_min_frag` runs alone in a
one-step interpret-mode `pallas_call` and its per-node counts are
compared with `batch_solver.min_frag_step_counts` (the XLA lane's
31-probe search).  `_mf_stop_class`'s probe count is held to its bound:
none when the largest capacity reaches k, at most ⌈log₂ m⌉ otherwise.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_spark_scheduler_tpu.ops import pallas_queue as pq
from k8s_spark_scheduler_tpu.ops.batch_solver import (
    MF_SENT,
    min_frag_capacity,
    min_frag_step_counts,
    solve_app,
)

N = 1024
ROWS, PADDED = pq._row_layout(N)


def _plane(v, fill=0):
    flat = jnp.full((PADDED,), fill, jnp.int32).at[: v.shape[0]].set(v.astype(jnp.int32))
    return flat.reshape(ROWS, pq.LANES)


def _drain_kernel(s_ref, c_ref, m_ref, g_ref, rank_ref, ok_ref, counts_ref, meta_ref):
    rows, lanes = rank_ref.shape
    node_ids = (
        lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
        + lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    )
    dr = jnp.array([s_ref[0], s_ref[1], s_ref[2]], dtype=jnp.int32)
    ex = jnp.array([s_ref[3], s_ref[4], s_ref[5]], dtype=jnp.int32)
    feasible, flat_idx, _, counts = pq._solve_min_frag(
        c_ref[...], m_ref[...], g_ref[...], rank_ref[...], ok_ref[...] != 0,
        dr, ex, s_ref[6], node_ids,
    )
    counts_ref[...] = counts
    lane = lax.broadcasted_iota(jnp.int32, (8, pq.LANES), 1)
    meta_ref[...] = jnp.where(lane == 0, feasible.astype(jnp.int32), flat_idx)


@jax.jit
def _drain(avail, rank, exec_ok, driver, executor, k):
    """One app through `_solve_min_frag`: (feasible, driver node, counts[N])."""
    block = pl.BlockSpec((ROWS, pq.LANES), lambda i, s: (0, 0))
    counts, meta = pl.pallas_call(
        _drain_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[block] * 5,
            out_specs=[block, pl.BlockSpec((8, pq.LANES), lambda i, s: (0, 0))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((ROWS, pq.LANES), jnp.int32),
            jax.ShapeDtypeStruct((8, pq.LANES), jnp.int32),
        ],
        interpret=True,
    )(
        jnp.concatenate([driver, executor, k[None]]).astype(jnp.int32),
        _plane(avail[:, 0]), _plane(avail[:, 1]), _plane(avail[:, 2]),
        _plane(rank, fill=pq.BIG), _plane(exec_ok),
    )
    return meta[0, 0] != 0, meta[0, 1], counts.reshape(-1)[: avail.shape[0]]


def _stop_class_kernel(k_ref, dd_ref, out_ref):
    k = k_ref[pl.program_id(0)]
    dd = dd_ref[0]
    vstar, probes = pq._mf_stop_class(dd, jnp.minimum(dd, k), k)
    lane = lax.broadcasted_iota(jnp.int32, (8, pq.LANES), 1)
    out_ref[0] = jnp.where(lane == 0, vstar, probes)


@jax.jit
def _stop_classes(dd, ks):
    """`_mf_stop_class` over each [N] row of `dd` with its k: (vstar, probes)."""
    p = dd.shape[0]
    planes = jax.vmap(_plane)(dd)
    out = pl.pallas_call(
        _stop_class_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p,),
            in_specs=[pl.BlockSpec((1, ROWS, pq.LANES), lambda i, s: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 8, pq.LANES), lambda i, s: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((p, 8, pq.LANES), jnp.int32),
        interpret=True,
    )(ks.astype(jnp.int32), planes)
    return out[:, 0, 0], out[:, 0, 1]


def _stop_class_31(dd, k):
    """The XLA lane's search, in Python: 31 probes over [1, MF_SENT]."""
    dc = np.minimum(dd, k).astype(np.int64)
    lo, hi = 1, MF_SENT
    for _ in range(31):
        mid = lo + (hi - lo + 1) // 2
        if dc[dd >= mid].sum() >= k:
            lo = mid
        else:
            hi = mid - 1
    return lo


def _cpu_caps(caps):
    """A cluster whose executor capacities under executor (1, 1, 0) are
    `caps`: cpu = capacity, memory and gpu never bind."""
    avail = np.zeros((N, 3), np.int64)
    avail[:, 0] = caps
    avail[:, 1] = 10**6
    return avail


def _case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    caps = np.zeros(N, np.int64)
    executor, k = (1, 1, 0), 31
    if name == "m>=k":
        caps[:600] = rng.integers(0, 41, 600)
        caps[rng.integers(0, 600)] = 40
    elif name == "m<k, ties at the stop class":
        caps[rng.permutation(N)[:40]] = 7
        caps[rng.permutation(N)[:10]] = 3
        k = 50
    elif name == "m=k-1 on one node":
        # the largest class alone falls one short: v* lies below it
        caps[:300] = rng.integers(0, 11, 300)
        caps[rng.integers(0, 300)] = 30
    elif name == "MF_SENT nodes":
        caps[:500] = rng.integers(0, 5, 500)
        executor = (0, 0, 0)  # every node whose availability is whole is unbounded
    elif name == "k=0":
        caps[:200] = rng.integers(0, 41, 200)
        k = 0
    elif name.startswith("k="):
        k = int(name[2:])
        hi = {1: 5, 31: 41, 1000: 41, 100000: 301}[k]
        caps[:] = rng.integers(hi // 3, hi, N)
    elif name == "empty mask":
        caps[:] = 20
    elif name == "subset fails, full mask":
        # (k + max) // 2 = 20 admits the three 1s alone: 3 < k, so the
        # drain falls back to the full mask and the 30
        caps[[5, 17, 300]] = 1
        caps[640] = 30
        k = 10
    avail = _cpu_caps(caps)
    if executor == (0, 0, 0):
        avail[rng.permutation(N)[:100], 2] = -1  # unschedulable: capacity 0
    exec_ok = np.ones(N, bool) if name != "empty mask" else np.zeros(N, bool)
    rank = rng.permutation(N).astype(np.int64)
    return avail, rank, exec_ok, np.array([0, 1, 0]), np.array(executor), k


CASES = [
    "m>=k", "m<k, ties at the stop class", "m=k-1 on one node", "MF_SENT nodes", "k=1", "k=31", "k=1000",
    "k=100000", "empty mask", "k=0", "subset fails, full mask",
]


@pytest.mark.parametrize("name", CASES)
def test_kernel_drain_matches_the_xla_oracle_node_by_node(name):
    avail, rank, exec_ok, driver, executor, k = _case(name)
    args = (
        jnp.asarray(avail, jnp.int32), jnp.asarray(rank, jnp.int32), jnp.asarray(exec_ok),
        jnp.asarray(driver, jnp.int32), jnp.asarray(executor, jnp.int32), jnp.int32(k),
    )
    ref = solve_app(*args)
    want = np.asarray(min_frag_step_counts(args[0], ref.feasible, ref.driver_idx, *args[3:5], args[2], args[5]))
    feasible, driver_idx, counts = _drain(*args)
    assert bool(feasible) == bool(ref.feasible)
    if feasible:
        assert int(driver_idx) == int(ref.driver_idx)
    np.testing.assert_array_equal(np.asarray(counts), want)
    if name in ("empty mask", "k=0"):
        assert not want.any()
    else:
        assert want.sum() == k


def _fifo10k_minfrag_queue(seed, n_nodes=N, n_apps=60):
    """fifo10k-minfrag's ranges (benchmarks/configs/fifo10k-minfrag.json)
    at 1,024 x 60: nodes of 4-95 cpu and 8-255 Gi, gangs of 1-31
    executors of 1-7 cpu and 2-15 Gi, drivers of 1 cpu and 1 Gi."""
    rng = np.random.default_rng(seed)
    avail = np.zeros((n_nodes, 3), np.int64)
    avail[:, 0] = rng.integers(4, 96, n_nodes)
    avail[:, 1] = rng.integers(8, 256, n_nodes)
    apps = [
        (np.array([1, 1, 0]), np.array([rng.integers(1, 8), rng.integers(2, 16), 0]), int(rng.integers(1, 32)))
        for _ in range(n_apps)
    ]
    return avail, rng.permutation(n_nodes), apps


@pytest.mark.parametrize("seed", [3141592653, 2718281828])
def test_stop_class_probes_are_bounded_on_a_fifo10k_minfrag_queue(seed):
    avail, rank, apps = _fifo10k_minfrag_queue(seed)
    exec_ok = np.ones(N, bool)
    planes, ks = [], []
    for driver, executor, k in apps:
        args = (
            jnp.asarray(avail, jnp.int32), jnp.asarray(rank, jnp.int32), jnp.asarray(exec_ok),
            jnp.asarray(driver, jnp.int32), jnp.asarray(executor, jnp.int32), jnp.int32(k),
        )
        feasible, driver_idx, counts = (np.asarray(x) for x in _drain(*args))
        avail_eff = avail.copy()
        if feasible:
            avail_eff[int(driver_idx)] -= driver
        d = np.asarray(min_frag_capacity(jnp.asarray(avail_eff, jnp.int32), args[4], args[2]))
        elig = d > 0
        m = int(d.max())
        has_sent = bool((d[elig] == MF_SENT).any())
        # the two masks `_solve_min_frag` drains over: the subset attempt
        # (empty where it is not attempted) and the full mask
        subset = elig & (d < (MF_SENT if has_sent else (k + m) // 2))
        attempt = has_sent or k < m
        planes += [np.where(subset & attempt, d, 0), np.where(elig, d, 0)]
        ks += [k, k]
        # the carry: executors overwrite the driver on a shared node
        placed = counts > 0
        if feasible:
            avail[int(driver_idx)] -= driver * (not placed[int(driver_idx)])
        avail[placed] -= executor
    vstar, probes = (np.asarray(x) for x in _stop_classes(jnp.asarray(np.stack(planes), jnp.int32), jnp.asarray(ks)))
    for dd, k, v, p in zip(planes, ks, vstar, probes):
        m = int(dd.max())
        assert v == _stop_class_31(dd, k)
        assert p == 0 if m >= k else p <= max(m, 1).bit_length()
    none = float(np.mean(probes == 0))
    print(f"seed {seed}: {none:.1%} of {len(ks)} stop-class searches took no probe, the most {probes.max()}")
    assert none >= 0.5


@pytest.mark.parametrize("hi,with_sent", [(5, False), (41, False), (201, True), (2**20, False)])
def test_stop_class_equals_the_31_probe_search(hi, with_sent):
    rng = np.random.default_rng(hi)
    planes, ks = [], []
    for j in range(24):
        dd = rng.integers(0, hi, N)
        dd[rng.random(N) < 0.5 + 0.02 * j] = 0  # half the nodes or more masked out
        if with_sent:
            dd[rng.random(N) < 0.05] = MF_SENT
        planes.append(dd)
        ks.append(int(rng.choice([1, 2, 7, 31, 100, 1000, 100000])))
    vstar, probes = (np.asarray(x) for x in _stop_classes(jnp.asarray(np.stack(planes), jnp.int32), jnp.asarray(ks)))
    for dd, k, v, p in zip(planes, ks, vstar, probes):
        m = int(dd.max())
        assert v == _stop_class_31(dd, k)
        assert p == 0 if m >= k else p <= max(m, 1).bit_length()
