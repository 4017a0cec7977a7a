"""``fifo-gangs`` for the policies that score a packing by what each node
can schedule (the single-AZ family, ``binpack/single_az.go``).

Everything is ``references/fifo-gangs.py``'s: the priority order, the
FIFO pass with the reference's *assigned* usage, reservations, executor
node choice.  The one thing that differs: the policy is handed, beside
what is free, each node's schedulable cpu and memory in the same priority
order (``pack(cpu, mem, zones, gang, sched_cpu, sched_mem)``), because a
packing efficiency is reserved over schedulable.  On these clusters
nothing but Spark runs, so what a node can schedule is what it can
allocate.
"""

from __future__ import annotations

import numpy as np

import plugins

_base = plugins.load("references", "fifo-gangs")
Grant = _base.Grant


class Reference(_base.Reference):
    def __init__(self, cluster, policy: str, fifo: bool = True):
        super().__init__(cluster, policy, fifo)
        pack = self._pack
        # every pack of the base class is over the arrays of the last priority
        # order it computed (one memo entry, replaced with the order)
        self._pack = lambda cpu, mem, zones, gang: pack(cpu, mem, zones, gang, *self._schedulable)
        self._schedulable = (self.alloc_cpu, self.alloc_mem)

    def _priority(self, cpu: np.ndarray, mem: np.ndarray) -> np.ndarray:
        order = super()._priority(cpu, mem)
        self._schedulable = (self.alloc_cpu[order], self.alloc_mem[order])
        return order
