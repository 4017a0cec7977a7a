"""``fifo-gangs`` on a cluster that is already busy (the generator's
``OccupiedCluster``).

What the reference scheduler subtracts from a node's allocatable before
it packs anything: every ResourceReservation's slots, summed per node
(``GetReservedResources``, reservation usage in ``resource.go``), and the
requests of every pod bound to the node that no reservation holds
(``overhead.go``: here the daemonsets').  The running applications' are
that once, at the start, so they stay out of ``granted``: the memo key
and ``_free()`` are ``fifo-gangs``' own.  Everything else is
``fifo-gangs``' semantics: priority order on free capacity, the FIFO
pass with its assigned-usage quirk, exact integers.  It imports nothing
of the program.
"""

from __future__ import annotations

import plugins

_base = plugins.load("references", "fifo-gangs")
Grant = _base.Grant
MI = 1 << 20


class Reference(_base.Reference):
    def __init__(self, cluster, policy: str, fifo: bool = True):
        super().__init__(cluster, policy, fifo)
        cpu, mem = self.alloc_cpu, self.alloc_mem  # the base's own arrays: free capacity at the start
        cpu -= sum(d.cpu_m for d in cluster.daemons)
        mem -= sum(d.mem_mi for d in cluster.daemons) * MI
        for gang, driver_node, executor_nodes in cluster.running:
            self._subtract(cpu, mem, gang, driver_node, executor_nodes, self._index)
