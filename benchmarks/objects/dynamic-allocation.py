"""``dynamic-allocation``'s nodes and pods, and what the mix's verbs need
to read from the program, as plain data.

Nodes are ``static-allocation``'s.  A gang is a driver with the three
dynamic-allocation annotations (``spark-dynamic-allocation-enabled``,
``-min-executor-count``, ``-max-executor-count``; no
``spark-executor-count``) and *max* executor pods, the shape the
reference's own test utilities build
(``extender_test_utils.go:342-423``): the first *min* to ask find a
reserved slot, the rest are placed one by one and held as soft
reservations.  With ``stack.py``, the only importer of the program.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import plugins

_static = plugins.load("objects", "static-allocation")
nodes = _static.nodes
NAMESPACE = "default"
SEEN_WITHIN_S = 10.0


def pods(gang) -> list:
    """[driver, executor-1..max] of the generator's ``DynGang``."""
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    return Harness.dynamic_allocation_spark_pods(
        gang.app_id,
        gang.min_executors,
        gang.executors,
        driver_cpu=str(gang.driver_cpu),
        driver_mem=f"{gang.driver_mem_gi}Gi",
        executor_cpu=str(gang.executor_cpu),
        executor_mem=f"{gang.executor_mem_gi}Gi",
        creation_timestamp=gang.created,
    )


def executor_index(gang, pod_name: str) -> int:
    """3 for ``<app>-exec-3``: executors as plain numbers, 1..max in the
    order they ask, max + 1 the replacement."""
    return int(pod_name[len(gang.app_id) + len("-exec-"):])


def replacement(gang):
    """The executor Spark starts for one it lost: a sibling of the
    gang's executors under the next name, max + 1."""
    pod = pods(gang)[-1]
    pod.meta.name = f"{gang.app_id}-exec-{gang.executors + 1}"
    return pod


def _scheduler(client):
    return client._stack.scheduler  # the lean client keeps the stack; the verbs' reads go past it


def slot_executor(client, gang, slot: int) -> Optional[int]:
    """The executor the scheduler holds bound to hard slot ``slot`` (1-based)."""
    rr = _scheduler(client).resource_reservation_cache.get(NAMESPACE, gang.app_id)
    name = None if rr is None else rr.status.pods.get(f"executor-{slot}")
    return None if name is None else executor_index(gang, name)


def soft_reservations(client, gang) -> Optional[Dict[int, str]]:
    """{executor: node} of the application's soft reservations as the
    scheduler holds them; None where the store has no entry for it."""
    held, found = _scheduler(client).soft_reservation_store.get_soft_reservation(gang.app_id)
    if not found:
        return None
    return {executor_index(gang, name): r.node for name, r in held.reservations.items()}


def soft_applications(client) -> int:
    """Applications the soft store holds an entry for."""
    return _scheduler(client).soft_reservation_store.get_application_count()


def delete_and_wait(client, pod, compaction_due: bool) -> None:
    """The pod dies; return once the scheduler's pod informer has let go
    of it and, where the application has soft reservations to compact
    (``compaction_due``), its compaction queue holds the application:
    the next Filter then sees the death, as it would a moment later in
    a deployment."""
    from k8s_spark_scheduler_tpu.scheduler.labels import SPARK_APP_ID_LABEL

    scheduler = _scheduler(client)
    manager = scheduler.resource_reservation_manager
    app_id = pod.labels[SPARK_APP_ID_LABEL]
    client._stack.api.delete("Pod", pod.namespace, pod.name)

    def seen() -> bool:
        if scheduler.pod_informer.get(pod.namespace, pod.name) is not None:
            return False
        if not compaction_due:
            return True
        with manager._da_compaction_lock:
            return app_id in manager._da_compaction_apps

    deadline = time.monotonic() + SEEN_WITHIN_S
    while not seen():
        if time.monotonic() > deadline:
            raise RuntimeError(f"the scheduler has not seen {pod.name} die within {SEEN_WITHIN_S:.0f} s")
        time.sleep(0.0002)
