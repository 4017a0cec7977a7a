"""``static-allocation``'s nodes and pods on a cluster that is already
busy (the generator's ``OccupiedCluster``).

``nodes(cluster)`` yields more than nodes: ``stack.start_stack`` creates
whatever it yields in the API server before the program starts, so the
server finds a running cluster at start-up, as an extender restarted in
a busy cluster does.  In this order:

- the nodes (``static-allocation``'s);
- one Running pod of each daemonset on every node, bound by the default
  scheduler, in ``kube-system``, held by no reservation;
- each running application's driver and executor pods, Running and
  bound to their nodes (``node_name``);
- each running application's ResourceReservation, built by the
  program's own ``new_resource_reservation`` with every slot's pod in
  ``status.pods``, owned by its driver.

``pods(gang)`` is ``static-allocation``'s.  A program whose informers
prune their resourceVersion memory on every event once they hold more
than 16,384 objects (no ``Informer.TOMBSTONES_KEPT``) takes a quarter of
an hour to start over ~98,000 bound pods and then ~20 ms more for every
pod event: no measurement of this configuration.  This adapter says so at
once, before anything is started: it exits non-zero while it is loaded
where the program lacks that bound.
"""

from __future__ import annotations

from typing import Iterator

import plugins

_static = plugins.load("objects", "static-allocation")
pods = _static.pods
DAEMON_NAMESPACE = "kube-system"
DEFAULT_SCHEDULER = "default-scheduler"


def nodes(cluster) -> Iterator[object]:
    from k8s_spark_scheduler_tpu.scheduler.reservations_manager import (
        executor_reservation_name,
        new_resource_reservation,
    )
    from k8s_spark_scheduler_tpu.types.objects import Container, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import Resources

    yield from _static.nodes(cluster)
    for daemon in cluster.daemons:
        requests = Resources.of(f"{daemon.cpu_m}m", f"{daemon.mem_mi}Mi")
        for node in cluster.names:
            yield Pod(
                meta=ObjectMeta(
                    name=f"{daemon.name}-{node}",
                    namespace=DAEMON_NAMESPACE,
                    labels={"app": daemon.name},
                    creation_timestamp=cluster.base_ts,
                ),
                scheduler_name=DEFAULT_SCHEDULER,
                node_name=node,
                containers=[Container(daemon.name, requests)],
                phase=PodPhase.RUNNING,
            )
    for gang, driver_node, executor_nodes in cluster.running:
        driver, *executors = pods(gang)
        driver.meta.uid = f"uid-{driver.name}"  # the reservation's owner
        for pod, node in zip((driver, *executors), (driver_node, *executor_nodes)):
            pod.node_name = node
            pod.phase = PodPhase.RUNNING
        reservation = new_resource_reservation(
            driver_node,
            list(executor_nodes),
            driver,
            driver.containers[0].requests,
            executors[0].containers[0].requests,
        )
        for i, executor in enumerate(executors):
            reservation.status.pods[executor_reservation_name(i)] = executor.name
        yield driver
        yield from executors
        yield reservation


def _require_bounded_tombstones() -> None:
    from k8s_spark_scheduler_tpu.kube.informer import Informer

    if not hasattr(Informer, "TOMBSTONES_KEPT"):
        raise SystemExit(
            "objects/static-allocation-occupied: this program's informers prune "
            "their resourceVersion memory on every event above 16,384 objects: "
            "minutes to start over ~98,000 bound pods (no measurement of this "
            "configuration)"
        )


_require_bounded_tombstones()
