"""Event-driven integer-tensor mirror of cluster state — the steady-state
fast path for `binpack: tpu-batch` at 10k-node scale.

The reference recomputes its scheduling snapshot from scratch on every
Filter request: GetReservedResources walks every reservation
(resourcereservations.go:258-263), GetOverhead walks every pod on every
candidate node (overhead.go:120-153), and NodeSchedulingMetadataForNodes
re-derives availability per node (resources.go:61-100) — all in
arbitrary-precision quantity arithmetic.  That is O(cluster) of host
work per request, which caps honest end-to-end latency long before the
device solve does.

This cache keeps the same state as O(delta)-updated int64 arrays:

- nodes: allocatable/zone/labels/ready from node informer events;
- reservation usage: per-node deltas from ResourceReservationCache and
  SoftReservationStore change observers (this process is the sole
  writer of both, so the mirror is exact);
- overhead: a pod table (requests, node, scheduler flag) from pod
  informer events plus the reserved-pod keys maintained from the same
  reservation observers; each event marks the pod slots whose counted
  state it may change, and a snapshot folds only those slots into the
  per-node sums.  This is the one keeper of the overhead rule
  (overhead.go): the Quantity paths ask it through ``overhead()`` and
  ``non_schedulable_overhead()``, which sum each counted pod's exact
  requests.

Exactness: every quantity is converted to base units once, at event
time; anything not exactly representable poisons the affected row and
``snapshot()`` reports exact=False so the caller falls back to the
Quantity path.  Decisions from this snapshot are bit-identical to the
slow path (tests/test_tensor_snapshot.py proves it on randomized
mutation sequences).
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from .. import tracing
from ..analysis import racecheck
from ..ops.tensorize import _resources_to_base
from ..scheduler import labels as L
from ..types.objects import Node, Pod
from ..types.resources import ZONE_LABEL, ZONE_LABEL_PLACEHOLDER, NodeGroupResources, Resources
from ..types.resources import pod_to_resources
from ..analysis.guarded import guarded_by
from .classindex import ClassIndex
from .store import (
    DELTA_NODE,
    DELTA_NODE_STRUCTURE,
    DELTA_POD,
    DELTA_RESERVATION,
    DELTA_SOFT_RESERVATION,
    ChangeFeed,
)

_GROW = 256

# the span of one overhead fold, tagged ``rows``: the pod slots it
# folded; a ``fifo_gate`` carries its request's sum as ``overheadRows``
OVERHEAD_SPAN = "mirror.overhead"

# nodes per lock hold of a Quantity answer: the gather for a 10,000-node
# ask of a busy cluster (~98,000 pods) is tens of milliseconds of host
# Python, which a Filter's snapshot would otherwise wait out whole
_ANSWER_CHUNK = 256


def _holds(pod: Pod) -> Tuple[Optional[tuple], Optional[tuple]]:
    """The keys under which a reservation holds ``pod``: its own
    application's ResourceReservation listing its name in status.pods
    (namespace, app id, name), and for an executor its application's
    soft reservation (app id, name) (resourcereservations.go:115-132,
    softreservations.go:83-93).  A pod without an app id has neither."""
    app_id = pod.labels.get(L.SPARK_APP_ID_LABEL)
    if app_id is None:
        return None, None
    soft = (app_id, pod.name) if pod.labels.get(L.SPARK_ROLE_LABEL) == L.EXECUTOR else None
    return (pod.namespace, app_id, pod.name), soft


@dataclass
class TensorSnapshot:
    """A point-in-time view (copies — safe to use off-lock)."""

    names: List[str]                 # [N] node names
    allocatable: np.ndarray          # [N, 3] int64
    usage: np.ndarray                # [N, 3] int64 (hard + soft reservations)
    overhead: np.ndarray             # [N, 3] int64 (non-reservation pods)
    zone_names: List[str]
    zone_id: np.ndarray              # [N] int32
    ready: np.ndarray                # [N] bool
    unschedulable: np.ndarray        # [N] bool
    labels: List[Dict[str, str]]     # [N]
    exact: bool
    # nodes referenced by ≥1 (hard or soft) reservation — i.e. nodes that
    # would have an entry in GetReservedResources' usage map.  The
    # reschedule path's double-overhead quirk (resource.go:638-643)
    # applies only to such nodes, entry-ness included zero-valued
    # reservations, so a resource-row test cannot stand in for it.
    res_entries: np.ndarray          # [N] bool
    # lexicographic rank of each node's name among live nodes — an int
    # sort key equivalent to sorting the names themselves, maintained on
    # topology changes so per-request orderings never sort object arrays
    name_rank: np.ndarray            # [N] int64

    # (maintainer instance, structure revision): changes whenever the
    # node TABLE changes (add/remove/labels/zone/ready/unschedulable —
    # not usage), letting per-request consumers cache structure-derived
    # work (ops/fast_path._build_prep) across Filter requests
    structure_key: tuple = (-1, -1)

    # (maintainer instance, change-feed sequence): changes on EVERY
    # mutation the mirror absorbs — an equal content_key across two
    # snapshots proves their contents are identical, so consumers
    # (ops/deltasolve.py) can skip even the content compare
    content_key: tuple = (-1, -1)

    # (maintainer instance, XOR node-content digest) from the class
    # index (state/classindex.py): equal digests across snapshots of
    # the same mirror imply equal node rows up to 64-bit collisions —
    # the delta-solve engine's O(1) warm-basis tier between content_key
    # equality and the O(N) row compare.  Survives same-content churn
    # (a reserve+release pair cancels in the XOR) that content_key,
    # which counts every mutation, cannot.
    class_digest: tuple = (-1, -1)

    # class-structure revision: bumps only when the class MULTISET
    # changes, so class-derived caches survive same-class node churn
    class_rev: int = -1

    _name_index: Optional[Dict[str, int]] = None

    @property
    def avail(self) -> np.ndarray:
        return self.allocatable - self.usage - self.overhead

    @property
    def schedulable(self) -> np.ndarray:
        return self.allocatable - self.overhead

    @property
    def name_index(self) -> Dict[str, int]:
        """name → row, built once per snapshot (C-speed dict(zip))."""
        if self._name_index is None:
            self._name_index = dict(zip(self.names, range(len(self.names))))
        return self._name_index


_INSTANCE_SEQ = itertools.count()


@guarded_by("_lock", "_node_slot", "_pod_slot", "_pod_resources", "_pod_foreign", "_pod_holds")
class TensorSnapshotCache:
    def __init__(self, node_informer, pod_informer, rr_cache, soft_store):
        self._lock = threading.RLock()
        self._exact = True
        # cache-instance id + structure revision (see TensorSnapshot.
        # structure_key); instance ids are process-unique so revisions
        # from different maintainers can never alias in consumer caches
        self._instance_id = next(_INSTANCE_SEQ)
        self._structure_rev = 0
        # snapshot()'s structure-derived parts, keyed by _structure_rev
        self._struct_cache = None
        # monotonic typed-delta feed: every mutation this mirror absorbs
        # publishes one delta (under the mirror lock, so a snapshot
        # taken under the same lock sees a consistent sequence); the
        # delta-solve engine keys its warm-path checks on the sequence
        self.feed = ChangeFeed()
        # equivalence-class index (ROADMAP 2): every node mutation below
        # renotes its one slot, keeping the class multiset, revision and
        # XOR content digest O(1)-current off the same deltas
        self.classes = ClassIndex()

        # node table
        self._node_slot: Dict[str, int] = {}
        self._node_names: List[Optional[str]] = []
        self._free_nodes: List[int] = []
        self._alloc = np.zeros((0, 3), dtype=np.int64)
        self._usage = np.zeros((0, 3), dtype=np.int64)
        self._res_count = np.zeros(0, dtype=np.int64)
        self._name_rank = np.zeros(0, dtype=np.int64)
        self._names_dirty = True
        self._node_overhead = np.zeros((0, 3), dtype=np.int64)
        self._zone_id = np.zeros(0, dtype=np.int32)
        self._ready = np.zeros(0, dtype=bool)
        self._unsched = np.zeros(0, dtype=bool)
        self._labels: List[Dict[str, str]] = []
        self._zone_names: List[str] = []
        self._zone_index: Dict[str, int] = {}
        # usage destined for nodes we don't (yet) know
        self._orphan_usage: Dict[str, np.ndarray] = {}
        self._orphan_res_count: Dict[str, int] = {}

        # pod table (for overhead)
        self._pod_slot: Dict[Tuple[str, str], int] = {}
        self._pod_requests = np.zeros((0, 3), dtype=np.int64)
        # node NAME per pod slot (resolved to a node slot at fold time:
        # slots are reused on node churn and pods can be observed before
        # their node, so a stored slot index would go stale)
        self._pod_node_name: List[str] = []
        self._pod_active = np.zeros(0, dtype=bool)
        # per pod slot: its exact requests (the Quantity answers sum
        # these, never the int rows), whether another scheduler placed
        # it, and its _holds keys
        self._pod_resources: List[Optional[Resources]] = []
        self._pod_foreign = np.zeros(0, dtype=bool)
        self._pod_holds: List[Tuple[Optional[tuple], Optional[tuple]]] = []
        self._free_pods: List[int] = []
        # what each pod slot adds to _node_overhead now: the node slot
        # (-1: nothing) and the row; a node delete drops the records on
        # its slot at once, so a record never outlives its node
        self._pod_counted_on = np.zeros(0, dtype=np.int64)
        self._pod_counted_row = np.zeros((0, 3), dtype=np.int64)
        # pod slots whose counted state may have changed since the last
        # fold, and the indexes that find them: node name → bound slots
        # (a node add; the Quantity answers read it too), bare pod name →
        # slots (a soft reservation)
        self._dirty_pods: Set[int] = set()
        self._pods_on_node: Dict[str, Set[int]] = {}
        self._pods_named: Dict[str, Set[int]] = {}
        # what the reservations hold, in _holds' keys: (namespace, app
        # id, pod name) from each ResourceReservation's status.pods, and
        # a count per (app id, pod name) of soft reservations
        self._reserved_pods: Set[Tuple[str, str, str]] = set()
        self._soft_reserved: Dict[Tuple[str, str], int] = {}

        node_informer.add_event_handler(
            on_add=self._on_node, on_update=lambda o, n: self._on_node(n),
            on_delete=self._on_node_delete,
        )
        pod_informer.add_event_handler(
            on_add=self._on_pod, on_update=lambda o, n: self._on_pod(n),
            on_delete=self._on_pod_delete,
        )
        rr_cache.add_change_observer(self._on_rr_change)
        soft_store.add_change_observer(self._on_soft_change)

    # -- node events ---------------------------------------------------------

    def _zone_of(self, labels: Dict[str, str]) -> int:
        zone = labels.get(ZONE_LABEL, ZONE_LABEL_PLACEHOLDER)
        idx = self._zone_index.get(zone)
        if idx is None:
            idx = len(self._zone_names)
            self._zone_index[zone] = idx
            self._zone_names.append(zone)
        return idx

    def _grow_nodes(self) -> int:
        n = len(self._node_names)
        extra = _GROW
        self._alloc = np.vstack([self._alloc, np.zeros((extra, 3), np.int64)])
        self._usage = np.vstack([self._usage, np.zeros((extra, 3), np.int64)])
        self._res_count = np.concatenate([self._res_count, np.zeros(extra, np.int64)])
        self._name_rank = np.concatenate([self._name_rank, np.zeros(extra, np.int64)])
        self._node_overhead = np.vstack(
            [self._node_overhead, np.zeros((extra, 3), np.int64)]
        )
        self._zone_id = np.concatenate([self._zone_id, np.zeros(extra, np.int32)])
        self._ready = np.concatenate([self._ready, np.zeros(extra, bool)])
        self._unsched = np.concatenate([self._unsched, np.zeros(extra, bool)])
        self._node_names.extend([None] * extra)
        self._labels.extend([{} for _ in range(extra)])
        self._free_nodes.extend(range(n + extra - 1, n - 1, -1))
        return self._free_nodes.pop()

    def _on_node(self, node: Node) -> None:
        with self._lock:
            racecheck.note_access(self, "_node_slot")
            slot = self._node_slot.get(node.name)
            new_zone = self._zone_of(node.labels)
            if slot is None or (
                self._labels[slot] != node.labels
                or self._zone_id[slot] != new_zone
                or bool(self._ready[slot]) != node.ready
                or bool(self._unsched[slot]) != node.unschedulable
            ):
                # structural change only: allocatable/status heartbeats
                # must not invalidate structure-keyed consumer caches
                self._structure_rev += 1
                self.feed.publish(DELTA_NODE_STRUCTURE, node.name)
            else:
                self.feed.publish(DELTA_NODE, node.name)
            if slot is None:
                slot = self._free_nodes.pop() if self._free_nodes else self._grow_nodes()
                self._node_slot[node.name] = slot
                self._node_names[slot] = node.name
                self._names_dirty = True
                pending = self._orphan_usage.pop(node.name, None)
                self._usage[slot] = pending if pending is not None else 0
                self._res_count[slot] = self._orphan_res_count.pop(node.name, 0)
                self._dirty_pods.update(self._pods_on_node.get(node.name, ()))
            row, exact = _resources_to_base(node.allocatable)
            if not exact:
                self._exact = False
            self._alloc[slot] = row
            self._zone_id[slot] = new_zone
            self._ready[slot] = node.ready
            self._unsched[slot] = node.unschedulable
            self._labels[slot] = dict(node.labels)
            self._note_class(slot, labels=node.labels)

    def _on_node_delete(self, node: Node) -> None:
        with self._lock:
            racecheck.note_access(self, "_node_slot")
            self._structure_rev += 1
            self.feed.publish(DELTA_NODE_STRUCTURE, node.name)
            slot = self._node_slot.pop(node.name, None)
            if slot is None:
                return
            # park any remaining usage so a node re-add restores it
            if self._usage[slot].any():
                self._orphan_usage[node.name] = self._usage[slot].copy()
            if self._res_count[slot]:
                self._orphan_res_count[node.name] = int(self._res_count[slot])
            self._node_names[slot] = None
            self._names_dirty = True
            self._alloc[slot] = 0
            self._usage[slot] = 0
            self._res_count[slot] = 0
            # the slot may go to another node before the next fold: the
            # records on it leave with its overhead, and each such pod
            # now counts nowhere, as its node is unknown (a pod that
            # moved off it since its last fold is dirty already)
            self._node_overhead[slot] = 0
            dropped = np.flatnonzero(self._pod_counted_on == slot)
            self._pod_counted_on[dropped] = -1
            self._pod_counted_row[dropped] = 0
            self._ready[slot] = False
            self._labels[slot] = {}
            self._free_nodes.append(slot)
            self.classes.drop_node(slot)

    def _note_class(self, slot: int,
                    labels: Optional[Dict[str, str]] = None) -> None:
        """Mirror one slot's full row into the equivalence-class index
        (O(1); callers hold ``self._lock``).  Overhead is folded lazily
        at snapshot() — until then the index sees the previous overhead
        row, and _fold_overhead re-notes whatever changed,
        so by the time snapshot() stamps class_digest the index is
        consistent with the rows it hands out."""
        name = self._node_names[slot]
        if name is None:
            return
        self.classes.note_node(
            slot,
            name,
            self._alloc[slot],
            self._usage[slot],
            self._node_overhead[slot],
            int(self._zone_id[slot]),
            bool(self._ready[slot]),
            bool(self._unsched[slot]),
            res_count=int(self._res_count[slot]),
            labels=labels,
        )

    # -- reservation usage ---------------------------------------------------

    def _apply_usage(self, node: str, row: np.ndarray, sign: int) -> None:
        # each call is one reservation contribution: the entry count
        # tracks whether the node would appear in GetReservedResources'
        # usage map at all (even with zero-valued rows)
        # like the usage row, the count is NOT clamped: a transient
        # minus-before-plus imbalance must cancel exactly when the
        # matching event arrives, or entry-ness would desync from the
        # reserved-resources map permanently
        slot = self._node_slot.get(node)
        if slot is not None:
            self._usage[slot] += sign * row
            self._res_count[slot] += sign
            self._note_class(slot)
        else:
            current = self._orphan_usage.get(node)
            if current is None:
                current = np.zeros(3, np.int64)
            self._orphan_usage[node] = current + sign * row
            self._orphan_res_count[node] = self._orphan_res_count.get(node, 0) + sign

    @staticmethod
    def _rr_rows(rr) -> Dict[str, np.ndarray]:
        """node → summed base-unit rows for one reservation object."""
        rows: Dict[str, np.ndarray] = {}
        for reservation in rr.spec.reservations.values():
            row, _ = _resources_to_base(reservation.resources_value())
            arr = rows.get(reservation.node)
            if arr is None:
                rows[reservation.node] = np.array(row, np.int64)
            else:
                rows[reservation.node] = arr + np.array(row, np.int64)
        return rows

    def _on_rr_change(self, old, new) -> None:
        with self._lock:
            if old is not None:
                for node, row in self._rr_rows(old).items():
                    self._apply_usage(node, row, -1)
                for pod_name in old.status.pods.values():
                    self._reserved_pods.discard((old.namespace, old.name, pod_name))
                    self._mark_pod((old.namespace, pod_name))
            if new is not None:
                for reservation in new.spec.reservations.values():
                    _, e = _resources_to_base(reservation.resources_value())
                    if not e:
                        self._exact = False
                for node, row in self._rr_rows(new).items():
                    self._apply_usage(node, row, +1)
                for pod_name in new.status.pods.values():
                    self._reserved_pods.add((new.namespace, new.name, pod_name))
                    self._mark_pod((new.namespace, pod_name))
            ref = new if new is not None else old
            self.feed.publish(
                DELTA_RESERVATION, ref.name if ref is not None else None
            )

    def _on_soft_change(self, app_id: str, node: str, resources, sign: int, pod_name: str) -> None:
        with self._lock:
            row, exact = _resources_to_base(resources)
            if not exact:
                self._exact = False
            self._apply_usage(node, np.array(row, np.int64), sign)
            key = (app_id, pod_name)
            count = self._soft_reserved.get(key, 0) + sign
            if count <= 0:
                self._soft_reserved.pop(key, None)
            else:
                self._soft_reserved[key] = count
            self._dirty_pods.update(self._pods_named.get(pod_name, ()))
            self.feed.publish(DELTA_SOFT_RESERVATION, pod_name)

    # -- pod table (overhead) ------------------------------------------------

    def _grow_pods(self) -> int:
        with self._lock:  # reentrant: _on_pod holds it already
            n = len(self._pod_active)
            extra = _GROW
            self._pod_requests = np.vstack([self._pod_requests, np.zeros((extra, 3), np.int64)])
            self._pod_node_name.extend([""] * extra)
            self._pod_active = np.concatenate([self._pod_active, np.zeros(extra, bool)])
            self._pod_resources.extend([None] * extra)
            self._pod_foreign = np.concatenate([self._pod_foreign, np.zeros(extra, bool)])
            self._pod_holds.extend([(None, None)] * extra)
            self._pod_counted_on = np.concatenate(
                [self._pod_counted_on, np.full(extra, -1, np.int64)]
            )
            self._pod_counted_row = np.vstack(
                [self._pod_counted_row, np.zeros((extra, 3), np.int64)]
            )
            self._free_pods.extend(range(n + extra - 1, n - 1, -1))
            return self._free_pods.pop()

    def _mark_pod(self, key: Tuple[str, str]) -> None:
        slot = self._pod_slot.get(key)
        if slot is not None:
            self._dirty_pods.add(slot)

    def _bind_pod(self, slot: int, node_name: str) -> None:
        """Point one pod slot at a node name, keeping _pods_on_node."""
        old = self._pod_node_name[slot]
        if old == node_name:
            return
        if old:
            on_old = self._pods_on_node[old]
            on_old.discard(slot)
            if not on_old:
                del self._pods_on_node[old]
        if node_name:
            self._pods_on_node.setdefault(node_name, set()).add(slot)
        self._pod_node_name[slot] = node_name

    def _on_pod(self, pod: Pod) -> None:
        with self._lock:
            racecheck.note_access(self, "_pod_slot")
            key = (pod.namespace, pod.name)
            slot = self._pod_slot.get(key)
            if pod.node_name == "":
                if slot is not None:
                    self._pod_active[slot] = False
                    self._dirty_pods.add(slot)
                    self.feed.publish(DELTA_POD, pod.name)
                # a nodeless pod the mirror never tracked changes no
                # state: queued-driver heartbeats must not churn the
                # content sequence (they arrive on every Filter cycle)
                return
            if slot is None:
                slot = self._free_pods.pop() if self._free_pods else self._grow_pods()
                self._pod_slot[key] = slot
                self._pods_named.setdefault(pod.name, set()).add(slot)
            requests = pod_to_resources(pod)
            row, exact = _resources_to_base(requests)
            if not exact:
                self._exact = False
            self._pod_requests[slot] = row
            self._pod_resources[slot] = requests
            self._pod_foreign[slot] = pod.scheduler_name != L.SPARK_SCHEDULER_NAME
            self._pod_holds[slot] = _holds(pod)
            self._bind_pod(slot, pod.node_name)
            self._pod_active[slot] = True
            self.feed.publish(DELTA_POD, pod.name)
            self._dirty_pods.add(slot)

    def _on_pod_delete(self, pod: Pod) -> None:
        with self._lock:
            racecheck.note_access(self, "_pod_slot")
            slot = self._pod_slot.pop((pod.namespace, pod.name), None)
            if slot is None:
                return
            self._pod_active[slot] = False
            self._pod_resources[slot] = None
            self._bind_pod(slot, "")
            named = self._pods_named[pod.name]
            named.discard(slot)
            if not named:
                del self._pods_named[pod.name]
            self._free_pods.append(slot)
            # its record leaves at the next fold, before any reuse of
            # the slot can count a new pod there
            self._dirty_pods.add(slot)
            self.feed.publish(DELTA_POD, pod.name)

    # -- snapshot ------------------------------------------------------------

    def _counted_on(self, slot: int) -> int:
        """The node slot one pod slot counts on, or -1: an active pod no
        reservation of its own application holds (overhead.go:139-141,
        ``_holds``) on a node the mirror knows."""
        if not self._pod_active[slot]:
            return -1
        hard, soft = self._pod_holds[slot]
        if hard in self._reserved_pods or soft in self._soft_reserved:
            return -1
        return self._node_slot.get(self._pod_node_name[slot], -1)

    def _fold_overhead(self) -> None:
        """Each dirty pod slot's recorded row taken off its node and its
        current one added: one ``mirror.overhead`` span under whatever
        took the snapshot (a Filter's ``fast_path.snapshot`` or
        ``executor.snapshot``, the capacity sampler's ``capacity.sample``,
        the reconcile) or asked a Quantity answer."""
        dirty = np.fromiter(self._dirty_pods, np.int64, len(self._dirty_pods))
        self._dirty_pods = set()
        with tracing.child_span(OVERHEAD_SPAN, {"rows": len(dirty)}):
            was_on = self._pod_counted_on[dirty]
            now_on = np.fromiter(
                (self._counted_on(int(slot)) for slot in dirty),
                dtype=np.int64, count=len(dirty),
            )
            touched = np.unique(np.concatenate([was_on, now_on]))
            touched = touched[touched >= 0]
            before = self._node_overhead[touched]
            was = was_on >= 0
            np.subtract.at(
                self._node_overhead, was_on[was], self._pod_counted_row[dirty[was]]
            )
            now = now_on >= 0
            rows = np.where(now[:, None], self._pod_requests[dirty], 0)
            np.add.at(self._node_overhead, now_on[now], rows[now])
            self._pod_counted_on[dirty] = now_on
            self._pod_counted_row[dirty] = rows
            changed = touched[(self._node_overhead[touched] != before).any(axis=1)]
            # overhead shifted under some nodes: bring their class-index rows
            # up to date (class KEY never depends on overhead, so this only
            # refreshes content hashes — class_rev is untouched)
            for slot in changed:
                self._note_class(int(slot))

    # -- Quantity answers ----------------------------------------------------

    def overhead(self, names: Iterable[str]) -> NodeGroupResources:
        """Each named node's overhead in exact Quantities: the requests
        of the pods counted on it (overhead.go:91-96)."""
        return self._answer(names, foreign_only=False)

    def non_schedulable_overhead(self, names: Iterable[str]) -> NodeGroupResources:
        """The part of ``overhead`` that pods of other schedulers
        (daemonsets and the like) take (overhead.go:98-103, read by the
        unschedulable-pod marker, unschedulablepods.go:149-151)."""
        return self._answer(names, foreign_only=True)

    def _answer(self, names: Iterable[str], foreign_only: bool) -> NodeGroupResources:
        """Folds, then gathers under the lock only which counted slots
        lie on which asked node and their Resources; the exact sums run
        outside it."""
        names = list(names)
        out: NodeGroupResources = {}
        for start in range(0, len(names), _ANSWER_CHUNK):
            chunk = names[start : start + _ANSWER_CHUNK]
            with self._lock:
                if self._dirty_pods:
                    self._fold_overhead()
                on = [self._pods_on_node.get(name, ()) for name in chunk]
                slots = np.fromiter(itertools.chain.from_iterable(on), np.int64)
                owner = np.repeat(np.arange(len(chunk)), [len(s) for s in on])
                kept = self._pod_counted_on[slots] >= 0
                if foreign_only:
                    kept &= self._pod_foreign[slots]
                requests = [self._pod_resources[s] for s in slots[kept].tolist()]
            totals = [Resources.zero()] * len(chunk)
            for i, r in zip(owner[kept].tolist(), requests):
                totals[i] = totals[i].add(r)
            out.update(zip(chunk, totals))
        return out

    def _recompute_name_ranks(self) -> None:
        live = [i for i, name in enumerate(self._node_names) if name is not None]
        order = sorted(live, key=lambda i: self._node_names[i])
        for rank, slot in enumerate(order):
            self._name_rank[slot] = rank
        self._names_dirty = False

    def snapshot(self) -> TensorSnapshot:
        with self._lock:
            if self._dirty_pods:
                self._fold_overhead()
            if self._names_dirty:
                self._recompute_name_ranks()
            # structure-derived parts (the Python-loop costs: live-slot
            # scan + 10k-element name/label lists) are cached per
            # structure revision — every mutation of names, labels,
            # zones, ready or unschedulable bumps _structure_rev
            # (_on_node/_on_node_delete), so a cache hit can only serve
            # identical structure.  The cached numpy rows are .copy()s,
            # never views, so later in-place maintainer writes (which
            # bump the rev) cannot reach snapshots already handed out.
            sc = self._struct_cache
            if sc is None or sc[0] != self._structure_rev:
                live = [
                    i for i, name in enumerate(self._node_names) if name is not None
                ]
                idx = np.array(live, dtype=np.int64)
                if len(idx) == 0:
                    idx = np.zeros(0, dtype=np.int64)
                sc = (
                    self._structure_rev,
                    idx,
                    [self._node_names[i] for i in live],
                    # label dicts are replaced (never mutated) on node
                    # events, so sharing the references is safe
                    [self._labels[i] for i in live],
                    list(self._zone_names),
                    self._zone_id[idx].copy(),
                    self._ready[idx].copy(),
                    self._unsched[idx].copy(),
                    self._name_rank[idx].copy(),
                )
                self._struct_cache = sc
            _, idx, names, labels, zone_names, zone_id, ready, unsched, ranks = sc
            return TensorSnapshot(
                names=names,
                allocatable=self._alloc[idx].copy(),
                usage=self._usage[idx].copy(),
                overhead=self._node_overhead[idx].copy(),
                zone_names=zone_names,
                zone_id=zone_id,
                ready=ready,
                unschedulable=unsched,
                labels=labels,
                exact=self._exact,
                res_entries=self._res_count[idx] > 0,  # comparison allocates fresh
                name_rank=ranks,
                structure_key=(self._instance_id, self._structure_rev),
                # feed.seq is stable here: every publisher holds this
                # mirror's lock, which snapshot() also holds
                content_key=(self._instance_id, self.feed.seq),
                # all class-index mutators run under this lock too, so
                # the digest/rev pair is consistent with the rows above
                class_digest=(self._instance_id, self.classes.digest),
                class_rev=self.classes.class_rev,
            )
