"""Driver-path fast lane: TensorSnapshot → solver tensors with no
Quantity arithmetic.

Replicates, in vectorized integer math, exactly what the slow path
derives from Quantity metadata:

- the AZ-aware node priority order (nodesorting.go:95-122): zones
  ascending by total (memory, cpu) of *available* resources, nodes by
  (zone priority, memory, cpu, name) — int64 lexsorts, name ties via a
  precomputed rank;
- driver candidates = priority ∩ kube-scheduler's list; executor
  candidates = ready ∧ ¬unschedulable (nodesorting.go:41-64);
- the per-role label-priority stable re-sort
  (nodesorting.go:161-180): configured label values map to ascending
  ranks, any other/missing value sorts last, ties keep the base order —
  a stable integer argsort over precomputed rank arrays;
- the required-node-affinity filter over snapshot label dicts
  (resource.go:292-295).

A driver's tensor build keeps that order, and the arrays that follow
from it, with the request's prep entry (`_NodeOrder`): the next request
under the same key whose selected rows still hold the allocatable, usage
and overhead the order was sorted from is handed the same read-only
arrays; any change to them sorts the rows whole again.

An executor's reschedule (resource.go:594-663) needs only the head of
that order among the nodes that fit: `first_in_executor_order` selects
it as a lexicographic minimum over candidate rows kept between requests
(`executor_rows_keyed`), sorting nothing.

Only usable when the snapshot is exact; callers fall back to the
Quantity path otherwise.
"""

from __future__ import annotations

import itertools
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..metrics.names import EXECUTOR_ROWS_READS, NODE_ORDER_READS, PREP_CACHE_READS
from ..state.tensor_snapshot import TensorSnapshot
from .nodesort import LabelPriorityOrder
from .tensorize import INT32_SAFE, ClusterTensor


def _label_ranks(labels_list, order: LabelPriorityOrder) -> np.ndarray:
    """Integer sort keys replicating _label_less_than: configured values
    get their list position, anything else (including a missing label)
    a rank past the end so it sorts last; stability preserves the base
    priority order within equal ranks."""
    value_ranks = {v: i for i, v in enumerate(order.descending_priority_values)}
    big = len(order.descending_priority_values)
    return np.fromiter(
        (value_ranks.get(labels.get(order.name), big) for labels in labels_list),
        dtype=np.int64,
        count=len(labels_list),
    )


def _base_priority_order(
    snap: TensorSnapshot, idx: np.ndarray, avail: np.ndarray
) -> np.ndarray:
    """AZ-aware base node priority over the selected rows
    (nodesorting.go:95-122), shared by the driver and executor fast
    lanes: zones ascending by total (memory, cpu, name) of the selected
    availability; nodes by (zone priority, memory, cpu, name).  Returns
    positions into `idx`."""
    zone_id = snap.zone_id[idx]
    n_zones = len(snap.zone_names)
    zone_mem = np.zeros(n_zones, dtype=np.int64)
    zone_cpu = np.zeros(n_zones, dtype=np.int64)
    np.add.at(zone_mem, zone_id, avail[:, 1])
    np.add.at(zone_cpu, zone_id, avail[:, 0])
    zone_name_rank = np.argsort(np.argsort(np.array(snap.zone_names, dtype=object)))
    zone_order = np.lexsort((zone_name_rank, zone_cpu, zone_mem))
    zone_priority = np.empty(n_zones, dtype=np.int64)
    zone_priority[zone_order] = np.arange(n_zones)

    # snapshot-maintained integer name ranks order exactly like the
    # names; lexsort needs only the ordering, not dense subset ranks
    return np.lexsort(
        (snap.name_rank[idx], avail[:, 0], avail[:, 1], zone_priority[zone_id])
    )


@dataclass
class _ExecutorRows:
    """What an executor's reschedule needs of the node TABLE and the
    request's candidate list (resource.go:594-663's metadata restricted to
    kube-scheduler's names), in the snapshot's row space: nothing here
    depends on usage or overhead, so it is kept between requests, keyed
    like `_BuildPrep`."""

    # [N] bool: named by the request (a name twice counts once, like the
    # slow path's metadata dict; an unknown name is no row) ∧ ready ∧
    # ¬unschedulable
    exec_ok: np.ndarray
    # the candidate rows, not-ready ones included, one zone after another,
    # so that the zone totals are one reduceat
    by_zone: np.ndarray
    zone_starts: np.ndarray     # where each zone that has a candidate starts in by_zone
    zone_ids: np.ndarray        # those zones' ids, in by_zone's order
    zone_name_rank: np.ndarray  # and the rank of each one's name among them
    label_rank: Optional[np.ndarray]  # [N], executor label priority
    name_index: Dict[str, int]  # node name → row


def _compute_executor_rows(snap, candidate_names, elp) -> _ExecutorRows:
    nidx = snap.name_index
    rows = np.fromiter(
        map(nidx.get, candidate_names, itertools.repeat(-1)),
        dtype=np.int64,
        count=len(candidate_names),
    )
    is_cand = np.zeros(len(snap.names), dtype=bool)
    is_cand[rows[rows >= 0]] = True
    idx = np.flatnonzero(is_cand)
    by_zone = idx[np.argsort(snap.zone_id[idx], kind="stable")]
    zone_ids, zone_starts = np.unique(snap.zone_id[by_zone], return_index=True)
    zone_names = np.array([snap.zone_names[z] for z in zone_ids], dtype=object)
    return _ExecutorRows(
        exec_ok=is_cand & snap.ready & ~snap.unschedulable,
        by_zone=by_zone,
        zone_starts=zone_starts,
        zone_ids=zone_ids,
        zone_name_rank=np.argsort(np.argsort(zone_names)),
        label_rank=_label_ranks(snap.labels, elp) if elp is not None else None,
        name_index=nidx,
    )


def rows_fitting(avail: np.ndarray, row: np.ndarray) -> np.ndarray:
    """[N] bool: avail ≥ row in every dimension (column by column: a
    reduction along the short axis of [N, 3] costs several times as much)."""
    fits = avail[:, 0] >= row[0]
    for dim in range(1, avail.shape[1]):
        fits &= avail[:, dim] >= row[dim]
    return fits


def first_in_executor_order(
    snap: TensorSnapshot,
    rows: _ExecutorRows,
    avail: np.ndarray,
    mask: np.ndarray,
    lead_keys=(),
) -> int:
    """The head of the executor priority order (nodesorting.go:95-122 and
    161-180) among the candidates that `mask` admits, as a selection: the
    row that is the lexicographic minimum of (lead_keys..., label rank
    where configured, zone priority, memory, cpu, name), which is where
    the stable sorts put it, with no sort over the rows and no name built.

    `avail`, `mask` and the keys are in the snapshot's row space.  The
    zones are ranked by their exact int64 totals of `avail` over every
    candidate, not-ready nodes included, as the slow path's metadata has
    them.  Returns -1 where the mask admits no executor candidate."""
    cand = np.flatnonzero(mask & rows.exec_ok)
    if not len(cand):
        return -1
    mem, cpu = avail[:, 1], avail[:, 0]
    zone_mem = np.add.reduceat(mem[rows.by_zone], rows.zone_starts)
    zone_cpu = np.add.reduceat(cpu[rows.by_zone], rows.zone_starts)
    zone_priority = np.zeros(len(snap.zone_names), dtype=np.int64)
    zone_priority[
        rows.zone_ids[np.lexsort((rows.zone_name_rank, zone_cpu, zone_mem))]
    ] = np.arange(len(rows.zone_ids))
    for key in (
        *lead_keys,
        rows.label_rank,
        zone_priority[snap.zone_id],
        mem,
        cpu,
        snap.name_rank,
    ):
        if len(cand) == 1:
            break
        if key is not None:
            of_cand = key[cand]
            cand = cand[of_cand == of_cand.min()]
    return int(cand[0])


@dataclass
class _BuildPrep:
    """Avail-independent prework of build_cluster_tensor — everything
    derivable from the node TABLE (names/labels/zones/flags) and the
    request's candidate list, cacheable across Filter requests keyed by
    the snapshot's structure revision (the FIFO hot path rebuilds the
    same structures per request; at 10k nodes this was ~20ms of the
    ~24ms build cost)."""

    idx: np.ndarray            # eligible rows into the snapshot
    names: List[str]
    names_arr: np.ndarray      # object array of names (for permuting)
    is_cand: np.ndarray        # [len(idx)] bool — in the candidate list
    exec_ok_base: np.ndarray   # [len(idx)] bool — ready ∧ ¬unschedulable
    d_keys: Optional[np.ndarray]
    e_keys: Optional[np.ndarray]
    zones: Dict[str, str]      # eligible node → zone name
    # the node order the last request under this entry's key sorted
    # (build_cluster_tensor)
    node_order: Optional["_NodeOrder"] = None


_PREP_CACHE: OrderedDict = OrderedDict()
_EXECUTOR_ROWS_CACHE: OrderedDict = OrderedDict()
_PREP_CACHE_MAX = 32  # of each
_prep_lock = threading.Lock()


def _single_in_sig(driver_pod):
    """Hashable signature of the dominant affinity shape (one In
    constraint); None = uncacheable shape."""
    if (
        not driver_pod.node_selector
        and not driver_pod.affinity_terms
        and len(driver_pod.node_affinity) == 1
    ):
        ((key, values),) = driver_pod.node_affinity.items()
        return (key, tuple(sorted(values)))
    return None


def _lp_sig(lp: Optional[LabelPriorityOrder]):
    return None if lp is None else (lp.name, tuple(lp.descending_priority_values))


def _compute_prep(snap, driver_pod, candidate_names, dlp, elp) -> _BuildPrep:
    n = len(snap.names)
    # required node affinity + nodeSelector filter (metadata membership),
    # via the same matcher the slow path uses.  The dominant real-world
    # shape — a single In-constraint on one label (the instance group) —
    # is vectorized; anything else falls back to the general matcher.
    single_in = _single_in_sig(driver_pod)
    if single_in is not None:
        key, values = single_in
        allowed = set(values)
        eligible = np.fromiter(
            (labels.get(key) in allowed for labels in snap.labels),
            dtype=bool,
            count=n,
        )
    else:
        eligible = np.fromiter(
            (driver_pod.matches_labels(labels) for labels in snap.labels),
            dtype=bool,
            count=n,
        )
    idx = np.flatnonzero(eligible)
    if len(idx) == 0:
        idx = np.zeros(0, dtype=np.int64)
    names = [snap.names[i] for i in idx]
    candidate_set = set(candidate_names)
    is_cand = np.fromiter(
        (nm in candidate_set for nm in names), dtype=bool, count=len(names)
    )
    need_labels = dlp is not None or elp is not None
    labels_sel = [snap.labels[i] for i in idx] if need_labels else None
    zone_sel = snap.zone_id[idx]
    return _BuildPrep(
        idx=idx,
        names=names,
        names_arr=np.array(names, dtype=object),
        is_cand=is_cand,
        exec_ok_base=snap.ready[idx] & ~snap.unschedulable[idx],
        d_keys=_label_ranks(labels_sel, dlp) if dlp is not None else None,
        e_keys=_label_ranks(labels_sel, elp) if elp is not None else None,
        zones={
            nm: snap.zone_names[zone_sel[i]] for i, nm in enumerate(names)
        },
    )


def _kept(cache: OrderedDict, key, tag: str, counter: str, compute):
    """`compute()`, or what an earlier request computed under the same
    exact key (None = uncacheable).  How it was come by (hit, miss,
    uncacheable) is a tag on the active span and a count in the server's
    registry, which the kernel profiler is bound to."""
    from ..tracing import add_tag
    from ..tracing.profiling import default_profiler

    value = None
    if key is not None:
        with _prep_lock:
            value = cache.get(key)
            if value is not None:
                cache.move_to_end(key)
    result = "uncacheable" if key is None else "miss" if value is None else "hit"
    # a prep miss at 10k nodes is ~20ms of the request — worth seeing on
    # the span when hunting a latency outlier
    add_tag(tag, result)
    default_profiler.metrics.counter(counter, {"result": result})
    if value is None:
        value = compute()
        if key is not None:
            with _prep_lock:
                cache[key] = value
                while len(cache) > _PREP_CACHE_MAX:
                    cache.popitem(last=False)
    return value


def build_prep_keyed(snap, driver_pod, candidate_names, dlp, elp):
    """(prep, key): the avail-independent prework plus the exact cache
    key it lives under — (structure revision, affinity signature,
    candidate tuple, label-priority signatures) — or key=None when the
    affinity shape is uncacheable.  The delta-solve engine keys its
    native solver sessions by the same identity, so a session can only
    ever be consulted for the cluster/candidate shape it was built for."""
    aff = _single_in_sig(driver_pod)
    key = None
    if aff is not None and snap.structure_key[0] >= 0:
        key = (
            snap.structure_key,
            aff,
            # the tuple itself, not its hash: a hash collision would
            # silently reuse another request's candidate mask
            tuple(candidate_names),
            _lp_sig(dlp),
            _lp_sig(elp),
        )
    prep = _kept(
        _PREP_CACHE, key, "prepCache", PREP_CACHE_READS,
        lambda: _compute_prep(snap, driver_pod, candidate_names, dlp, elp),
    )
    return prep, key


def executor_rows_keyed(snap, candidate_names, elp) -> _ExecutorRows:
    """The candidate rows of an executor's reschedule, kept per
    (structure revision, candidate tuple, executor label priority): the
    node table changes with node events only and kube-scheduler sends the
    same list with every pod (the interned tuple on the HTTP path), so on
    a hit no name is looked up."""
    key = None
    if snap.structure_key[0] >= 0:
        key = (snap.structure_key, tuple(candidate_names), _lp_sig(elp))
    return _kept(
        _EXECUTOR_ROWS_CACHE, key, "rowsCache", EXECUTOR_ROWS_READS,
        lambda: _compute_executor_rows(snap, candidate_names, elp),
    )


def _build_prep(snap, driver_pod, candidate_names, dlp, elp) -> _BuildPrep:
    return build_prep_keyed(snap, driver_pod, candidate_names, dlp, elp)[0]


@dataclass(frozen=True)
class _NodeOrder:
    """A driver tensor's node order and the ClusterTensor arrays that
    follow from it, with the selected rows' allocatable, usage and
    overhead it was sorted from.  Never written once built: its arrays
    are read-only and handed to every request that finds the same rows."""

    basis: Tuple[np.ndarray, np.ndarray, np.ndarray]  # [len(idx), 3] each
    names: Tuple[str, ...]
    avail: np.ndarray
    sched: np.ndarray
    driver_rank: np.ndarray
    exec_ok: np.ndarray
    zone_id: np.ndarray
    valid: np.ndarray


def _sorted_whole(snap: TensorSnapshot, prep: _BuildPrep, basis) -> _NodeOrder:
    """The node order of the selected rows, sorted whole, and the arrays
    that follow from it."""
    allocatable, usage, overhead = basis
    avail = allocatable - usage - overhead
    sched = allocatable - overhead
    zone_id = snap.zone_id[prep.idx]

    # AZ-aware base priority
    order = _base_priority_order(snap, prep.idx, avail)

    # per-role label-priority re-sort on top of the base order
    # (nodesorting.go:161-180).  The array order is the EXECUTOR priority
    # order (the solver packs executors in array order); the driver order
    # lives in driver_rank, so the two roles can be re-sorted
    # independently, exactly like the slow path's two stable sorts.
    perm = order
    if prep.e_keys is not None:
        perm = perm[np.argsort(prep.e_keys[perm], kind="stable")]

    # driver order = BASE order ∩ candidates (never the executor-resorted
    # order), stable-sorted by the driver label rank when configured;
    # ranks are then scattered into final array positions
    cand_base_positions = order[np.flatnonzero(prep.is_cand[order])]
    if prep.d_keys is not None:
        cand_base_positions = cand_base_positions[
            np.argsort(prep.d_keys[cand_base_positions], kind="stable")
        ]
    pos_in_array = np.empty(len(perm), dtype=np.int64)
    pos_in_array[perm] = np.arange(len(perm))
    driver_rank = np.full(len(perm), INT32_SAFE, dtype=np.int64)
    driver_rank[pos_in_array[cand_base_positions]] = np.arange(
        len(cand_base_positions)
    )

    arrays = (
        np.take(avail, perm, axis=0),
        np.take(sched, perm, axis=0),
        driver_rank.astype(np.int32),
        prep.exec_ok_base[perm],
        zone_id[perm].astype(np.int32),
        np.ones(len(perm), dtype=bool),
    )
    for array in arrays:
        array.setflags(write=False)
    return _NodeOrder(basis, tuple(prep.names_arr[perm]), *arrays)


def build_cluster_tensor(
    snap: TensorSnapshot,
    driver_pod,
    candidate_names: List[str],
    driver_label_priority: Optional[LabelPriorityOrder] = None,
    executor_label_priority: Optional[LabelPriorityOrder] = None,
) -> Optional[Tuple[ClusterTensor, Dict[str, str]]]:
    """(cluster tensor, node→zone map) or None when the fast path can't
    represent the snapshot exactly.

    The node order is kept with the prep entry: a request whose selected
    rows read as the last sort under the same key left them takes it as
    it is (`nodeOrder=kept` on the span, `orderRows` 0); the first
    request, a node event (a new key), an uncacheable affinity shape or
    any changed row sorts them all (`rebuilt`, `orderRows` the rows
    sorted).  The tensor's arrays are read-only: later requests are
    handed the same ones until a row changes."""
    from ..tracing import add_tag
    from ..tracing.profiling import default_profiler

    if not snap.exact:
        return None
    n = len(snap.names)
    if n == 0:
        # no eligible nodes: an empty tensor is still valid input
        empty = ClusterTensor(
            node_names=[],
            avail=np.zeros((0, 3), np.int64),
            sched=np.zeros((0, 3), np.int64),
            driver_rank=np.zeros(0, np.int32),
            exec_ok=np.zeros(0, bool),
            zone_id=np.zeros(0, np.int32),
            zone_names=[],
            valid=np.zeros(0, bool),
            exact=True,
        )
        return empty, {}

    prep = _build_prep(
        snap, driver_pod, candidate_names, driver_label_priority,
        executor_label_priority,
    )
    idx = prep.idx
    # np.take copies whole rows several times faster than fancy indexing
    basis = tuple(np.take(a, idx, axis=0) for a in (snap.allocatable, snap.usage, snap.overhead))
    kept = prep.node_order
    if kept is not None and all(map(np.array_equal, basis, kept.basis)):
        read = "kept"
    else:
        # an uncacheable affinity shape's prep entry is its request's alone
        read = "rebuilt"
        kept = prep.node_order = _sorted_whole(snap, prep, basis)
    add_tag("nodeOrder", read)
    add_tag("orderRows", 0 if read == "kept" else len(idx))
    default_profiler.metrics.counter(NODE_ORDER_READS, {"result": read})

    cluster = ClusterTensor(
        node_names=list(kept.names),
        avail=kept.avail,
        sched=kept.sched,
        driver_rank=kept.driver_rank,
        exec_ok=kept.exec_ok,
        zone_id=kept.zone_id,
        zone_names=list(snap.zone_names),
        valid=kept.valid,
        exact=True,
    )
    return cluster, prep.zones
