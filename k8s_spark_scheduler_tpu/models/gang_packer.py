"""GangPacker — the flagship compiled program of this framework.

Bundles the batch gang-packing solver into a configured, reusable,
optionally mesh-sharded program: snapshot tensors in, whole-FIFO-queue
placement decisions out.  This is the ``binpack: tpu-batch`` data plane
(BASELINE.json north star): the control plane marshals cluster state
into `ClusterTensor`/`AppTensor` and reads back per-app decisions,
while everything inside `solve` is a single XLA program with the node
axis sharded over the device mesh.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.batch_solver import QueueSolve, solve_queue
from ..ops.tensorize import (
    AppTensor,
    ClusterTensor,
    ScaledProblem,
    scale_problem,
)
from ..parallel import mesh as meshlib


@dataclass(frozen=True)
class GangPackerConfig:
    assignment_policy: str = "tightly-pack"  # or "distribute-evenly"
    node_bucket: Optional[int] = None
    app_bucket: Optional[int] = None
    use_mesh: bool = False
    # "pallas": single-kernel VMEM-resident queue solve (fastest on one
    # chip); "xla": lax.scan program (mesh-shardable, CPU-testable)
    backend: str = "pallas"


class GangPacker:
    """Compiled whole-queue gang packer."""

    def __init__(self, config: GangPackerConfig = GangPackerConfig(), devices=None):
        self.config = config
        self._mesh = meshlib.make_mesh(devices) if config.use_mesh else None
        if self._mesh is not None:
            node_mat = meshlib.node_matrix_sharding(self._mesh)
            node_vec = meshlib.node_sharding(self._mesh)
            rep = meshlib.replicated(self._mesh)
            self._solve = jax.jit(
                functools.partial(
                    solve_queue, evenly=config.assignment_policy == "distribute-evenly"
                ),
                in_shardings=(node_mat, node_vec, node_vec, rep, rep, rep, rep),
                out_shardings=QueueSolve(
                    feasible=rep,
                    driver_idx=rep,
                    exec_counts=jax.sharding.NamedSharding(
                        self._mesh, jax.sharding.PartitionSpec(None, meshlib.NODE_AXIS)
                    ),
                    exec_capacity=jax.sharding.NamedSharding(
                        self._mesh, jax.sharding.PartitionSpec(None, meshlib.NODE_AXIS)
                    ),
                    avail_after=node_mat,
                ),
            )
        elif config.backend == "pallas":
            if jax.default_backend() != "tpu":
                raise RuntimeError(
                    "GangPackerConfig(backend='pallas') needs a TPU; the default "
                    f"backend is {jax.default_backend()!r} — ask for backend='xla'"
                )
            from ..ops.pallas_queue import pallas_solve_queue

            evenly = config.assignment_policy == "distribute-evenly"

            def pallas_wrapped(*args):
                # decision-latency contract: per-app (feasible, driver)
                # plus the final availability.  Any single app's executor
                # placements are recovered with one O(N) solve_app on
                # the carried availability — exactly how TpuFifoSolver's
                # program decodes the current driver, and what the
                # bench measures as part of the headline op.  exec_counts
                # is therefore intentionally empty here (an [A, N]
                # placement matrix would be dead output for the FIFO
                # pass).
                feasible, driver_idx, avail_after = pallas_solve_queue(
                    *args, evenly=evenly
                )
                return QueueSolve(
                    feasible=feasible,
                    driver_idx=driver_idx,
                    exec_counts=jnp.zeros((0,), jnp.int32),
                    exec_capacity=jnp.zeros((0,), jnp.int32),
                    avail_after=avail_after,
                )

            self._solve = pallas_wrapped
        else:
            self._solve = functools.partial(
                solve_queue, evenly=config.assignment_policy == "distribute-evenly"
            )

    @property
    def mesh(self):
        return self._mesh

    def scale(self, cluster: ClusterTensor, apps: AppTensor) -> ScaledProblem:
        node_bucket = self.config.node_bucket
        if self._mesh is not None:
            from ..ops.tensorize import bucket_size

            n_devices = len(self._mesh.devices.reshape(-1))
            base = node_bucket or bucket_size(cluster.avail.shape[0])
            node_bucket = meshlib.pad_to_multiple(base, n_devices)
        return scale_problem(
            cluster, apps, node_bucket=node_bucket, app_bucket=self.config.app_bucket
        )

    def device_args(self, problem: ScaledProblem):
        args = (
            jnp.asarray(problem.avail),
            jnp.asarray(problem.driver_rank),
            jnp.asarray(problem.exec_ok),
            jnp.asarray(problem.driver),
            jnp.asarray(problem.executor),
            jnp.asarray(problem.count),
            jnp.asarray(problem.app_valid),
        )
        if self._mesh is not None:
            node_mat = meshlib.node_matrix_sharding(self._mesh)
            node_vec = meshlib.node_sharding(self._mesh)
            rep = meshlib.replicated(self._mesh)
            shardings = (node_mat, node_vec, node_vec, rep, rep, rep, rep)
            args = tuple(jax.device_put(a, s) for a, s in zip(args, shardings))
        return args

    def solve(self, problem: ScaledProblem) -> QueueSolve:
        """Run the compiled program.  problem.ok must be True.

        Profiled: compile vs execute time and cache hit/miss land in
        the kernel metrics (tracing/profiling.py) tagged with the
        configured backend lane."""
        if not problem.ok:
            raise ValueError("problem is not exactly tensorizable; use the host oracle")
        from ..tracing.profiling import default_profiler

        lane = "mesh" if self._mesh is not None else self.config.backend
        with default_profiler.profile(
            "gang_packer.solve_queue",
            lane=lane,
            fn=self._solve if hasattr(self._solve, "_cache_size") else None,
            shape_key=(problem.avail.shape, problem.driver.shape),
        ) as rec:
            out = self._solve(*self.device_args(problem))
            rec.sync(out.avail_after)
        return out

    def solve_fn(self):
        """(fn, sharding-prepared) — the raw jittable callable for
        compile checks and AOT tooling."""
        return self._solve
