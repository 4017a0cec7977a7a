"""Node overhead read from scratch (reference ``internal/extender/overhead.go``).

Overhead = requests of pods that have a node but no reservation of ours;
non-schedulable overhead = the subset not managed by this scheduler at
all (daemonsets etc.).  The served paths read both from the tensor
mirror (``state/tensor_snapshot.py``), which keeps them by delta; this
walk is the reference the invariant checker and the tests hold the
mirror to."""

from __future__ import annotations

from typing import Iterable, Tuple

from ..types.objects import Pod
from ..types.resources import NodeGroupResources, Resources, pod_to_resources
from . import labels as L


def node_overhead(
    pods: Iterable[Pod], resource_reservation_manager, node_names: Iterable[str]
) -> Tuple[NodeGroupResources, NodeGroupResources]:
    """(overhead, non-schedulable overhead) of each named node: every
    bound pod on it that ``pod_has_reservation`` does not hold
    (overhead.go:120-153, resourcereservations.go:115-132)."""
    overhead = {name: Resources.zero() for name in node_names}
    non_schedulable = dict(overhead)
    for pod in pods:
        if pod.node_name not in overhead or resource_reservation_manager.pod_has_reservation(pod):
            continue
        requests = pod_to_resources(pod)
        overhead[pod.node_name] = overhead[pod.node_name].add(requests)
        if pod.scheduler_name != L.SPARK_SCHEDULER_NAME:
            non_schedulable[pod.node_name] = non_schedulable[pod.node_name].add(requests)
    return overhead, non_schedulable
