"""Plain reference of the scheduler's admission semantics for static
Spark gangs behind a FIFO.

An independent, straightforward implementation of what the configuration
states (palantir/k8s-spark-scheduler, ``resource.go`` + ``binpack/``):
node priority order, FIFO over earlier pending drivers, all-or-nothing
gang packing under the configuration's policy (``policies/<name>.py``),
the reservation a grant writes, and which reserved node an executor of
the gang is then given.  It imports nothing of the program and takes
nothing the program made: its inputs are the generator's plain data
(``blocks.Cluster`` / ``blocks.Gang``), its arithmetic is exact integers
(milli-cpu, bytes).

The same operations on the same data give the same answers; ``check.py``
compares the program's answers with these, one by one.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import plugins
from blocks import GI, Cluster, Gang


@dataclass(frozen=True)
class Grant:
    """A granted gang: where its driver and each executor slot go."""

    driver_node: str
    executor_nodes: Tuple[str, ...]


class Reference:
    """The cluster as the reference scheduler sees it, and its answers."""

    def __init__(self, cluster: Cluster, policy: str, fifo: bool = True):
        self.policy = policy
        # pack(cpu, mem, zones, gang): free capacity and zones in priority order ->
        # (driver position, one executor position per executor) or None
        self._pack = plugins.load("policies", policy).pack
        self.fifo = fifo
        self.names = list(cluster.names)
        self._index = {n: i for i, n in enumerate(self.names)}
        self.zone = list(cluster.zone)
        self.alloc_cpu = cluster.cpu.astype(np.int64) * 1000
        self.alloc_mem = cluster.mem_gi.astype(np.int64) * GI
        self.pending: List[Gang] = sorted(cluster.backlog, key=lambda g: g.created)
        self.granted: Dict[str, Tuple[Gang, Grant]] = {}
        self._unbound: Dict[str, List[str]] = {}
        self._memo: Dict[tuple, tuple] = {}
        self._rank_of: Optional[Sequence[str]] = None
        self._rank: Dict[str, int] = {}

    # -- state ---------------------------------------------------------------

    def _free(self) -> Tuple[np.ndarray, np.ndarray]:
        cpu, mem = self.alloc_cpu.copy(), self.alloc_mem.copy()
        for gang, grant in self.granted.values():
            self._subtract(cpu, mem, gang, grant.driver_node, grant.executor_nodes, self._index)
        return cpu, mem

    @staticmethod
    def _subtract(cpu, mem, gang: Gang, driver, executors, index) -> None:
        d = index[driver]
        cpu[d] -= gang.driver_cpu * 1000
        mem[d] -= gang.driver_mem_gi * GI
        for e in executors:
            i = index[e]
            cpu[i] -= gang.executor_cpu * 1000
            mem[i] -= gang.executor_mem_gi * GI

    @staticmethod
    def _subtract_fifo_usage(cpu, mem, gang: Gang, driver: int, executors: List[int]) -> None:
        """What the FIFO pass takes off the cluster for an earlier driver
        (sparkpods.go:139-146, resource.go:254): usage is *assigned* per
        node, not added up: a node that hosts executors of the gang gives
        up one executor's resources, however many it hosts, and the
        driver's node gives up the driver's only if no executor shares
        it.  The reference scheduler's own behaviour, kept because
        decisions depend on it."""
        used = {driver: (gang.driver_cpu * 1000, gang.driver_mem_gi * GI)}
        for p in executors:
            used[p] = (gang.executor_cpu * 1000, gang.executor_mem_gi * GI)
        for p, (c, m) in used.items():
            cpu[p] -= c
            mem[p] -= m

    def _priority(self, cpu: np.ndarray, mem: np.ndarray) -> np.ndarray:
        """Zones ascending by free (memory, cpu), then name; nodes in a
        zone ascending by (memory, cpu, name) (nodesorting.go:95-122)."""
        totals: Dict[str, List[int]] = {}
        for i, z in enumerate(self.zone):
            t = totals.setdefault(z, [0, 0])
            t[0] += int(mem[i])
            t[1] += int(cpu[i])
        zone_rank = {
            z: r for r, z in enumerate(sorted(totals, key=lambda z: (totals[z][0], totals[z][1], z)))
        }
        return np.array(
            sorted(
                range(len(self.names)),
                key=lambda i: (zone_rank[self.zone[i]], int(mem[i]), int(cpu[i]), self.names[i]),
            ),
            dtype=np.int64,
        )

    # -- packing -------------------------------------------------------------

    def _after_earlier(self, gang: Gang):
        """Priority order and what is free once every earlier pending
        driver has been packed in FIFO order (resource.go:224-262); None
        for ``free`` when one of them does not fit.  Memoised on the state
        it is a function of: in steady traffic every request sees the same
        reservations and the same backlog ahead of it."""
        earlier = [g for g in self.pending if g.created < gang.created] if self.fifo else []
        key = (tuple(sorted(self.granted)), len(earlier), earlier[-1].app_id if earlier else "")
        if key not in self._memo:
            cpu, mem = self._free()
            order = self._priority(cpu, mem)
            cpu, mem = cpu[order], mem[order]
            names = [self.names[i] for i in order]
            zones = [self.zone[i] for i in order]
            ok = True
            for g in earlier:
                packed = self._pack(cpu, mem, zones, g)
                if packed is None:
                    ok = False  # enforce-after age is 0: no earlier driver is skipped
                    break
                self._subtract_fifo_usage(cpu, mem, g, *packed)
            self._memo = {key: (names, cpu, mem, zones, ok)}
        return self._memo[key]

    # -- the operations the traffic drives -------------------------------------

    def filter_driver(self, gang: Gang) -> Optional[Grant]:
        """A driver's Filter: the grant, or None where the gang (or an
        earlier driver) does not fit.  A grant reserves its nodes."""
        if gang.app_id in self.granted:
            return self.granted[gang.app_id][1]
        names, cpu, mem, zones, ok = self._after_earlier(gang)
        if not ok:
            return None
        packed = self._pack(cpu, mem, zones, gang)
        if packed is None:
            return None
        d, ex = packed
        grant = Grant(names[d], tuple(names[p] for p in ex))
        self.granted[gang.app_id] = (gang, grant)
        self._unbound[gang.app_id] = list(grant.executor_nodes)
        return grant

    def filter_executor(self, gang: Gang, candidates: Sequence[str]) -> Optional[str]:
        """An executor's Filter: the first candidate that holds an
        unbound reservation of its gang (resource.go:383-447)."""
        unbound = self._unbound.get(gang.app_id) or []
        if self._rank_of is not candidates:
            self._rank_of, self._rank = candidates, {n: i for i, n in enumerate(candidates)}
        ranked = [n for n in unbound if n in self._rank]
        if not ranked:
            return None
        name = min(ranked, key=self._rank.__getitem__)
        unbound.remove(name)
        return name

    def retire(self, gang: Gang) -> None:
        """The application finished: its pods and reservation are gone."""
        self.granted.pop(gang.app_id, None)
        self._unbound.pop(gang.app_id, None)
