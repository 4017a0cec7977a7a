"""Compile the queue-solver kernels a policy dispatches, before traffic.

The warm-up drives a private instance of the configured policy's queue
solver over synthetic problems of each wanted shape, through the same
``solve_tensor`` entry the extender calls and the ``feasible_batch``
entry the unschedulable-pod marker calls.  Whatever that policy dispatches on
this platform — Pallas queue kernel on a TPU, XLA zone solves on a CPU
host, the native C++ lane — is therefore what gets compiled; no second
list of "the kernels policy X uses" exists to drift from the solver.

Anything the compile raises propagates: a kernel the platform refuses is
a start-up failure (server/wiring.py keeps readiness false), not a
request-time fallback.
"""

from __future__ import annotations

from collections import Counter
from typing import Callable, Iterable, List, Optional, Tuple

from ..tracing.profiling import default_profiler
from ..types.resources import NodeSchedulingMetadata, Resources
from .registry import select_binpacker
from .sparkapp import AppDemand
from .tensorize import APP_BUCKETS, NODE_BUCKETS, bucket_size, tensorize_cluster

WARM_ZONES = 3  # zone count is a compile shape; 3 AZs is typical

# the shapes real clusters hit first; a server that starts against an
# already-populated cluster adds each instance group's own (warm_shapes)
_BASE_SHAPES = tuple((nb, APP_BUCKETS[0]) for nb in NODE_BUCKETS[:3])


def observed_groups(
    node_groups: Iterable[Optional[str]], driver_groups: Iterable[str]
) -> List[Tuple[int, int]]:
    """(nodes, pending drivers) of each instance group that has nodes,
    from the group of every node (None: the node carries no
    instance-group label and is in no group) and of every pending driver,
    as the informers hold them at start.  A driver Filter is served at
    its group's shape (the rows its node affinity admits, behind its
    group's queue), never at the whole cluster's."""
    nodes = Counter(node_groups)
    nodes.pop(None, None)
    pending = Counter(driver_groups)
    return [(n, pending[group]) for group, n in nodes.items()]


def warm_shapes(groups: Iterable[Tuple[int, int]]) -> Tuple[Tuple[int, int], ...]:
    """(node bucket, app bucket) pairs to compile: the small buckets plus,
    for each ``(nodes, pending drivers)`` of ``observed_groups``, the
    bucket its next driver Filter is served at.  The bucket above is not
    warmed, however near the queue stands to it: a queue that grows past
    16 / 64 / 256 / 1,024 compiles on a request thread, and the profiler
    counts it (``KERNEL_COMPILES``, ``phase=request``)."""
    shapes = list(_BASE_SHAPES)
    for n_nodes, n_pending_drivers in groups:
        if n_nodes <= 0:
            continue
        observed = (
            bucket_size(n_nodes),
            bucket_size(n_pending_drivers + 1, buckets=APP_BUCKETS),
        )
        if observed not in shapes:
            shapes.append(observed)
    return tuple(shapes)


@default_profiler.warming()  # what compiles inside counts as phase=warmup
def warm_queue_solver(
    binpack_algo: str,
    strict_reference_parity: bool,
    shapes: Iterable[Tuple[int, int]],
    should_stop: Callable[[], bool] = lambda: False,
) -> None:
    """Compile every kernel ``binpack_algo`` dispatches on this platform
    at each (nodes, apps) shape.  No-op for policies without a device
    queue solver, and for the plain policies on hosts where the native
    C++ lane serves them (nothing to compile; loading the library is the
    whole warm-up).  Raises on any build or compile failure."""
    binpacker = select_binpacker(
        binpack_algo, strict_reference_parity=strict_reference_parity
    )
    solver = binpacker.queue_solver
    if solver is None:
        return
    from .fifo_solver import _native_selected, _pallas_selected

    native_lane = not _pallas_selected(solver.backend) and _native_selected(
        solver.backend
    )
    if native_lane and not binpacker.is_single_az:
        return
    one = Resources.of("1", "1Gi")
    # a node size per zone: on identical zones every app of the queue
    # ties, and the single-AZ pass would stop at each of them to have the
    # host decide (a launch per app instead of one)
    sizes = [Resources.of(str(8 << z), f"{8 << z}Gi") for z in range(WARM_ZONES)]
    for n_nodes, n_apps in shapes:
        if should_stop():
            return
        metadata = {
            f"warm-{i:06d}": NodeSchedulingMetadata(
                available=sizes[i % WARM_ZONES],
                schedulable=sizes[i % WARM_ZONES],
                zone_label=f"warm-z{i % WARM_ZONES}",
            )
            for i in range(n_nodes)
        }
        cluster = tensorize_cluster(metadata, list(metadata), list(metadata))
        earlier = [AppDemand(one, one, 1) for _ in range(n_apps - 1)]
        outcome = solver.solve_tensor(
            cluster, earlier, [True] * len(earlier), AppDemand(one, one, 1)
        )
        if not outcome.supported or solver.last_queue_lane is None:
            raise RuntimeError(
                f"solver warm-up for {binpack_algo} at {n_nodes}x{n_apps} did "
                "not reach the queue solve (synthetic problem refused)"
            )
        # the unschedulable-pod marker's verdicts are a program of their
        # own (batch_solver.feasible_apps: one app shape per node bucket,
        # so a batch of one compiles what a scan of any backlog runs) at
        # the cluster's shape: its first scan begins a minute after
        # start, among the requests
        if solver.feasible_batch(cluster, [AppDemand(one, one, 1)])[0] is None:
            raise RuntimeError(
                f"solver warm-up for {binpack_algo} at {n_nodes} nodes did not "
                "reach the feasibility solve (synthetic problem refused)"
            )
