"""``fifo-gangs`` where the cluster is divided into instance groups.

The reference scheduler lists the nodes that match the pod's required
node affinity before it sorts or packs anything (``resource.go:292-295``)
and keeps its FIFO per instance group (``sparkpods.go``: the earlier
drivers are those of the pod's own group).  So a gang sees the nodes of
its group alone: the zone order is reckoned over their free capacity, the
queue ahead of it is its group's, and what the queue and the reservations
take is taken from them.  No group waits for another or takes from it:
each is ``references/fifo-gangs.py``'s cluster of its own, and this file
is only the division.  It imports nothing of the program; its inputs are
the generator's ``GroupCluster`` and ``GroupGang``
(``generators/instance-groups.py``).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

import plugins
from blocks import Cluster

_base = plugins.load("references", "fifo-gangs")
Grant = _base.Grant


class Reference:
    """One ``fifo-gangs`` reference per instance group, over that group's
    nodes and that group's backlog."""

    def __init__(self, cluster, policy: str, fifo: bool = True):
        group_of_node = np.array(cluster.group)
        self._groups: Dict[str, object] = {}
        for name in dict.fromkeys(cluster.group):
            rows = np.flatnonzero(group_of_node == name)
            part = Cluster(
                [cluster.names[i] for i in rows], cluster.cpu[rows], cluster.mem_gi[rows],
                [cluster.zone[i] for i in rows],
                [g for g in cluster.backlog if g.group == name], cluster.base_ts,
            )
            self._groups[name] = _base.Reference(part, policy, fifo)

    def _of(self, gang):
        """The gang's group; a gang that names a group without nodes fits nowhere."""
        return self._groups.get(gang.group)

    def filter_driver(self, gang) -> Optional[Grant]:
        group = self._of(gang)
        return None if group is None else group.filter_driver(gang)

    def filter_executor(self, gang, candidates: Sequence[str]) -> Optional[str]:
        group = self._of(gang)
        return None if group is None else group.filter_executor(gang, candidates)

    def retire(self, gang) -> None:
        group = self._of(gang)
        if group is not None:
            group.retire(gang)
