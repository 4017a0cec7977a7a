"""``static-allocation``'s nodes and pods, for a configuration whose
policy has to be served from the tensor mirror (``expect_lane``): a
program whose queue solver for the single-AZ policies has no tensor entry
(``solve_tensor``) would serve every driver Filter of this size through
its Quantity path, seconds to tens of seconds each, 128 of them before
the window, and ``run.py`` would refuse the run afterwards or never see
its end.  This adapter says so at once, before anything is started: it
exits non-zero while it is loaded where the program lacks that entry.
"""

from __future__ import annotations

import plugins

_plain = plugins.load("objects", "static-allocation")
INSTANCE_GROUP = _plain.INSTANCE_GROUP
nodes = _plain.nodes
pods = _plain.pods


def _require_tensor_path() -> None:
    from k8s_spark_scheduler_tpu.ops.fifo_solver import TpuSingleAzFifoSolver

    if not hasattr(TpuSingleAzFifoSolver, "solve_tensor"):
        raise SystemExit(
            "objects/static-allocation-tensor-path: this program's single-AZ queue "
            "solver has no solve_tensor: it cannot serve this configuration from "
            "the tensor mirror (no measurement of this system)"
        )


_require_tensor_path()
