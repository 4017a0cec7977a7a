"""``instance-groups``' nodes and pods: every node labelled with its
instance group (the install default label, ``resource_channel``), every
driver and executor with a required node affinity ``resource_channel In
[its group]``, the shape the reference's own test utilities build
(``static_allocation_spark_pods(..., instance_group=...)``).

The configuration has to be served from the tensor mirror
(``expect_lane``): a program whose queue solver has no tensor entry
(``solve_tensor``) would serve every driver Filter of this size through
its Quantity path, seconds to tens of seconds each.  As
``static-allocation-tensor-path`` does, this adapter says so at once: it
exits non-zero while it is loaded where the program lacks that entry.
"""

from __future__ import annotations

INSTANCE_GROUP_LABEL = "resource_channel"


def nodes(cluster) -> list:
    """``cluster``: the generator's ``GroupCluster``."""
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources

    return [
        Node(
            meta=ObjectMeta(
                name=name,
                labels={ZONE_LABEL: cluster.zone[i], INSTANCE_GROUP_LABEL: cluster.group[i]},
            ),
            allocatable=Resources.of(str(int(cluster.cpu[i])), f"{int(cluster.mem_gi[i])}Gi"),
        )
        for i, name in enumerate(cluster.names)
    ]


def pods(gang) -> list:
    """[driver, executor-1..n] of the generator's ``GroupGang``."""
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    return Harness.static_allocation_spark_pods(
        gang.app_id,
        gang.executors,
        driver_cpu=str(gang.driver_cpu),
        driver_mem=f"{gang.driver_mem_gi}Gi",
        executor_cpu=str(gang.executor_cpu),
        executor_mem=f"{gang.executor_mem_gi}Gi",
        instance_group=gang.group,
        instance_group_label=INSTANCE_GROUP_LABEL,
        creation_timestamp=gang.created,
    )


def _require_tensor_path() -> None:
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuFifoSolver

    if not hasattr(TpuFifoSolver, "solve_tensor"):
        raise SystemExit(
            "objects/instance-groups: this program's queue solver has no "
            "solve_tensor: it cannot serve this configuration from the tensor "
            "mirror (no measurement of this system)"
        )


_require_tensor_path()
