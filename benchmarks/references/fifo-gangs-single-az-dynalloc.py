"""``fifo-gangs-dynalloc`` under the single-AZ policies with the install key
``should-schedule-dynamically-allocated-executors-in-same-az`` on
(palantir/k8s-spark-scheduler ``config/config.go``; ``resource.go:493-515,
594-703``).

Everything is ``references/fifo-gangs-dynalloc.py``'s: drivers and the
queue packed at min, hard slots + soft reservations + overhead, the
executor's order of answers, the loss and the compaction.  What differs:

* the policy is handed each node's schedulable cpu and memory beside what
  is free, as ``fifo-gangs-single-az.py`` does; here schedulable is
  allocatable less the overhead, the requests of executors that hold no
  reservation (``resources.go``: ``schedulable = allocatable -
  overhead``), which the compaction's cross-node case can leave behind;
* an executor beyond min is placed in the zone of its application's
  running pods (the driver and every bound executor), where those pods
  share one zone (``resource.go:493-515``; where they do not, anywhere);
* among the nodes there where it fits, the choice is
  ``rescheduleExecutorWithMinimalFragmentation`` (``resource.go:652-656,
  675-703``): a node that already holds a reservation of the application,
  hard or soft, first; then the least capacity for one more executor;
  then executor priority order.  The capacity is the reference's
  ``GetNodeCapacities`` with the overhead passed as the reserved map:
  what is free less the overhead once more, on every node, an executor's
  worth or more being a fit.  A quirk of the source, kept because the
  node it picks depends on it.

It imports nothing of the program; its arithmetic is exact integers but
for the zone score, float64 as ``efficiency.go``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import plugins
from blocks import GI

_dynalloc = plugins.load("references", "fifo-gangs-dynalloc")
Grant = _dynalloc.Grant


class Reference(_dynalloc.Reference):
    def __init__(self, cluster, policy: str, fifo: bool = True, same_az: bool = True):
        super().__init__(cluster, policy, fifo)
        self.same_az = same_az
        pack = self._pack
        # every pack of the base class is over the arrays of the last priority
        # order it computed (one memo entry, replaced with the order)
        self._pack = lambda cpu, mem, zones, gang: pack(cpu, mem, zones, gang, *self._schedulable)
        self._schedulable = (self.alloc_cpu, self.alloc_mem)

    def _priority(self, cpu: np.ndarray, mem: np.ndarray) -> np.ndarray:
        order = super()._priority(cpu, mem)
        over_cpu, over_mem = self._overhead()
        self._schedulable = ((self.alloc_cpu - over_cpu)[order], (self.alloc_mem - over_mem)[order])
        return order

    def _common_zone(self, app) -> Optional[int]:
        """The one zone of the application's running pods, or None where
        they span more than one (``resource.go:493-515``)."""
        running = [app.driver_node, *app.running.values()]
        zones = {int(self._zone_id[self._index[node]]) for node in running}
        return zones.pop() if len(zones) == 1 else None

    def _first_fit(self, gang, candidates: Sequence[str]) -> Optional[str]:
        """An executor beyond min: the lexicographic minimum of (holds no
        reservation of the application, capacity, executor priority
        position) over the candidates in the application's zone with
        capacity for one executor."""
        app = self._apps[gang.app_id]
        over_cpu, over_mem = self._overhead()
        cpu, mem = self._free((over_cpu, over_mem))
        rows = self._rows
        need_cpu, need_mem = gang.executor_cpu * 1000, gang.executor_mem_gi * GI
        capacity = np.maximum(
            np.minimum((cpu - over_cpu)[rows] // need_cpu, (mem - over_mem)[rows] // need_mem), 0
        )
        fits = capacity >= 1
        zone = self._common_zone(app) if self.same_az else None
        if zone is not None:
            fits &= self._zone_id[rows] == zone
        if not fits.any():
            return None
        position = np.empty(len(rows), dtype=np.int64)
        position[self._executor_priority(cpu[rows], mem[rows], rows)] = np.arange(len(rows))
        keys = [key[fits] for key in (*self._leading_keys(app, rows, capacity), position)]
        best = np.lexsort(keys[::-1])[0]  # np.lexsort sorts by its last key first
        return self.names[rows[np.flatnonzero(fits)[best]]]

    def _leading_keys(self, app, rows: np.ndarray, capacity: np.ndarray) -> tuple:
        """What ranks the candidate rows before executor priority order
        (``resource.go:675-703``): holds no reservation of the application
        (its hard slots' nodes and its soft reservations'), then capacity."""
        held = {self._index[s.node] for s in app.slots} | {self._index[n] for n in app.soft.values()}
        not_held = ~np.isin(rows, np.fromiter(held, dtype=np.int64, count=len(held)))
        return not_held, capacity
