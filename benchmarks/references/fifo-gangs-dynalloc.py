"""``fifo-gangs`` where every application runs with dynamic allocation
(palantir/k8s-spark-scheduler README "Dynamic allocation";
``resource.go:383-435, 594-673``, ``resourcereservations.go:199-336``,
``softreservations.go``).

A driver is admitted as a gang of its *min* executors, and the FIFO pass
packs every earlier driver at its min too.  A grant writes min hard
slots.  What the cluster has given away is the hard slots (each at its
reservation's node, bound or not) plus the *soft* reservations: one per
executor granted beyond min, kept in memory, seen by every later
decision.  An executor's Filter answers, in this order: the node it is
already bound to (hard or soft); the first candidate, in ``NodeNames``
order, that holds an unbound hard slot of its gang; else, while the gang
holds fewer than max − min soft reservations, the first node that fits
it in executor priority order (zones by free memory, cpu, name; nodes by
free memory, cpu, name), recorded as a soft reservation; else a refusal.
When an executor dies, the next Filter *compacts* its application: each
soft-reserved executor, in the order they were granted, takes over an
unbound hard slot and gives up its soft reservation.

The cross-node case of the compaction is the port's reading of
``resourcereservations.go:326-335`` (the configuration lists it under
``assumed``): a slot on another node than the executor's keeps its node,
so it still reads as unbound (its executor runs elsewhere) and the next
soft-reserved executor is compacted into the same slot, and the next,
each losing its soft reservation, until one runs on the slot's node or
none is left.  An executor that lost its reservation this way holds no
reservation at all: its pod's requests are then *overhead* on its node,
taken off what is free, and off once more, for an extra executor's fit,
on nodes that hold any reservation (``resource.go:638-643``, the
reference's double count, which the program keeps under
``strict-reference-parity``, its default).  No other overhead exists in
these cells (no pod is foreign to the scheduler), so that is the one
place the quirk acts.

It imports nothing of the program; its inputs are the generator's
``Cluster`` and ``DynGang`` (``generators/dynamic-allocation.py``), its
arithmetic exact integers.  Executors are plain numbers: 1..max in the
order they ask, max + 1 the replacement.  A bind is taken to follow each
grant at once, as the traffic's verbs do it.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import plugins
from blocks import GI

_base = plugins.load("references", "fifo-gangs")
Grant = _base.Grant


@dataclass
class _Slot:
    node: str
    executor: Optional[int] = None  # the executor the slot is bound to


@dataclass
class _App:
    """One granted application: its hard slots, its soft reservations in
    the order they were made, and where its live executors run."""

    gang: object
    driver_node: str
    slots: List[_Slot]
    soft: Dict[int, str] = field(default_factory=dict)
    seen: Set[int] = field(default_factory=set)  # ever soft-reserved or died: never soft-reserved again
    running: Dict[int, str] = field(default_factory=dict)  # live, bound executors -> node
    dead: Set[int] = field(default_factory=set)
    asked: int = 0  # executors that have asked so far
    compact: bool = False  # an executor died since the last Filter

    @property
    def has_soft_store(self) -> bool:
        return self.gang.executors > self.gang.min_executors

    def unbound(self) -> List[_Slot]:
        """Slots that are free to take: never bound, bound to an executor
        that died, or to one that runs on another node
        (``resourcereservations.go:413-432``)."""
        return [
            s for s in self.slots
            if s.executor is None or s.executor in self.dead
            or self.running.get(s.executor, s.node) != s.node
        ]

    def unreserved(self) -> List[int]:
        held = {s.executor for s in self.slots} | set(self.soft)
        return [e for e in self.running if e not in held]


class Reference(_base.Reference):
    """``fifo-gangs`` with min/max executors, soft reservations and the
    compaction."""

    def __init__(self, cluster, policy: str, fifo: bool = True):
        super().__init__(cluster, policy, fifo)
        # the queue ahead is packed at min, as every driver is
        self.pending = [self._at_min(g) for g in self.pending]
        self._apps: Dict[str, _App] = {}
        self._memo_beside: Optional[tuple] = None
        self._zones = sorted(set(self.zone))
        self._zone_id = np.array([self._zones.index(z) for z in self.zone], dtype=np.int64)
        self._name_rank = np.argsort(np.argsort(np.array(self.names)))
        self._rows = np.zeros(0, dtype=np.int64)  # the candidates' rows, kept with ``_rank``

    @staticmethod
    def _at_min(gang):
        return replace(gang, executors=gang.min_executors)

    # -- state ---------------------------------------------------------------

    def _overhead(self) -> Tuple[np.ndarray, np.ndarray]:
        cpu, mem = np.zeros_like(self.alloc_cpu), np.zeros_like(self.alloc_mem)
        for app in self._apps.values():
            for executor in app.unreserved():
                i = self._index[app.running[executor]]
                cpu[i] += app.gang.executor_cpu * 1000
                mem[i] += app.gang.executor_mem_gi * GI
        return cpu, mem

    def _free(self, overhead: Optional[Tuple[np.ndarray, np.ndarray]] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Allocatable less hard slots, soft reservations and overhead."""
        cpu, mem = self.alloc_cpu.copy(), self.alloc_mem.copy()
        for app in self._apps.values():
            self._subtract(
                cpu, mem, app.gang, app.driver_node,
                [s.node for s in app.slots] + list(app.soft.values()), self._index,
            )
        over_cpu, over_mem = overhead or self._overhead()
        return cpu - over_cpu, mem - over_mem

    def _reserved_nodes(self) -> np.ndarray:
        """Nodes that hold any reservation, hard or soft."""
        held = np.zeros(len(self.names), dtype=bool)
        for app in self._apps.values():
            for node in [app.driver_node, *(s.node for s in app.slots), *app.soft.values()]:
                held[self._index[node]] = True
        return held

    # -- the operations the traffic drives -------------------------------------

    def filter_driver(self, gang) -> Optional[Grant]:
        """The gang at its min behind the queue at its min.  The base
        keeps its queue pass by who is granted; here what is free moves
        with the soft reservations and the slots too, so the pass is
        kept only while they stand as they stood."""
        self._compact()
        beside = tuple(
            (a, tuple(s.node for s in app.slots), tuple(app.soft.items()), tuple(sorted(app.unreserved())))
            for a, app in sorted(self._apps.items())
        )
        if beside != self._memo_beside:
            self._memo, self._memo_beside = {}, beside
        grant = super().filter_driver(self._at_min(gang))
        if grant is not None and gang.app_id not in self._apps:
            self._apps[gang.app_id] = _App(
                gang, grant.driver_node, [_Slot(n) for n in grant.executor_nodes]
            )
        return grant

    def filter_executor(self, gang, candidates: Sequence[str], executor: Optional[int] = None) -> Optional[str]:
        """The next executor of the gang asks (or ``executor``, by number)."""
        app = self._apps.get(gang.app_id)
        if app is None:
            return None
        if executor is None:
            app.asked += 1
            executor = app.asked
        self._compact()
        node = self._select(app, executor, candidates)
        if node is not None:
            app.running[executor] = node  # kube-scheduler binds it
        return node

    def _select(self, app: _App, executor: int, candidates: Sequence[str]) -> Optional[str]:
        if self._rank_of is not candidates:
            self._rank_of, self._rank = candidates, {n: i for i, n in enumerate(candidates)}
            self._rows = np.array([self._index[n] for n in candidates if n in self._index], dtype=np.int64)
        bound = next((s.node for s in app.slots if s.executor == executor), app.soft.get(executor))
        if bound is not None and bound in self._rank:
            return bound
        unbound = app.unbound()
        ranked = [s.node for s in unbound if s.node in self._rank]
        if ranked:
            node = min(ranked, key=self._rank.__getitem__)
            next(s for s in unbound if s.node == node).executor = executor
            return node
        spots = max(app.gang.executors - app.gang.min_executors - len(app.soft), 0) if app.has_soft_store else 0
        if len(unbound) + spots == 0:
            return None  # failure-unbound: the application is at its max
        node = self._first_fit(app.gang, candidates)
        if node is None:
            return None
        if unbound:
            unbound[0].node, unbound[0].executor = node, executor  # the slot moves with its executor
        elif executor not in app.seen:
            app.soft[executor] = node
            app.seen.add(executor)
        return node

    def _first_fit(self, gang, candidates: Sequence[str]) -> Optional[str]:
        """An executor beyond min, or one whose slot's node is no
        candidate: the first node, in executor priority order over the
        candidates, with room after every hard and soft reservation."""
        over_cpu, over_mem = self._overhead()
        cpu, mem = self._free((over_cpu, over_mem))
        twice = self._reserved_nodes()
        fit_cpu = cpu - np.where(twice, over_cpu, 0)
        fit_mem = mem - np.where(twice, over_mem, 0)
        rows = self._rows
        order = rows[self._executor_priority(cpu[rows], mem[rows], rows)]
        need_cpu, need_mem = gang.executor_cpu * 1000, gang.executor_mem_gi * GI
        for i in order:
            if fit_cpu[i] >= need_cpu and fit_mem[i] >= need_mem:
                return self.names[i]
        return None

    def _executor_priority(self, cpu: np.ndarray, mem: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """``_priority`` over the candidate rows alone, in whole-number
        sorts: zones ascending by the candidates' free (memory, cpu), then
        name; nodes by (zone, memory, cpu, name)."""
        zone_id = self._zone_id[rows]
        totals = [
            (int(mem[zone_id == z].sum()), int(cpu[zone_id == z].sum()), name)
            for z, name in enumerate(self._zones)
        ]
        zone_rank = np.empty(len(totals), dtype=np.int64)
        zone_rank[sorted(range(len(totals)), key=totals.__getitem__)] = np.arange(len(totals))
        return np.lexsort((self._name_rank[rows], cpu, mem, zone_rank[zone_id]))

    def lose_executor(self, gang, candidates: Sequence[str], slot: int) -> Tuple[Optional[int], Optional[str]]:
        """The executor bound to hard slot ``slot`` (1-based) dies, and its
        replacement (max + 1) asks: (who died, the replacement's node)."""
        app = self._apps[gang.app_id]
        lost = app.slots[slot - 1].executor
        if lost is None:
            return None, None
        app.running.pop(lost, None)
        app.dead.add(lost)
        app.soft.pop(lost, None)
        app.seen.add(lost)
        app.compact = app.has_soft_store
        return lost, self.filter_executor(gang, candidates, executor=gang.executors + 1)

    def _compact(self) -> None:
        """What every Filter begins with (``resourcereservations.go:268-336``)."""
        for app in self._apps.values():
            if not app.compact:
                continue
            app.compact = False
            for executor in list(app.soft):
                unbound = app.unbound()
                if not unbound:
                    continue
                at = app.running.get(executor, "")
                same = next((s for s in unbound if s.node == at), None)
                # no slot on its own node: the first unbound slot, which keeps its node
                (same or unbound[0]).executor = executor
                del app.soft[executor]

    def soft_reservations(self, gang) -> Optional[Dict[int, str]]:
        app = self._apps.get(gang.app_id)
        return dict(app.soft) if app is not None and app.has_soft_store else None

    def soft_applications(self) -> int:
        return sum(1 for app in self._apps.values() if app.has_soft_store)

    def retire(self, gang) -> None:
        """Hard and soft alike are gone with the application."""
        super().retire(gang)
        self._apps.pop(gang.app_id, None)
