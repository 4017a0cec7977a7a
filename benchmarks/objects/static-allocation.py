"""The generator's plain data as the program's objects: nodes of one
instance group, and Spark applications with static allocation (a driver
and a fixed number of identical executors), as ``bench.py:_config5_e2e``
and ``chip_smoke.py`` build them.  With ``stack.py``, the only importer
of the program."""

from __future__ import annotations

from blocks import Cluster, Gang

INSTANCE_GROUP = {"resource_channel": "batch-medium-priority"}


def nodes(cluster: Cluster) -> list:
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources

    return [
        Node(
            meta=ObjectMeta(name=name, labels={ZONE_LABEL: cluster.zone[i], **INSTANCE_GROUP}),
            allocatable=Resources.of(str(int(cluster.cpu[i])), f"{int(cluster.mem_gi[i])}Gi"),
        )
        for i, name in enumerate(cluster.names)
    ]


def pods(gang: Gang) -> list:
    """[driver, executor-1..n]."""
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    return Harness.static_allocation_spark_pods(
        gang.app_id,
        gang.executors,
        driver_cpu=str(gang.driver_cpu),
        driver_mem=f"{gang.driver_mem_gi}Gi",
        executor_cpu=str(gang.executor_cpu),
        executor_mem=f"{gang.executor_mem_gi}Gi",
        creation_timestamp=gang.created,
    )
