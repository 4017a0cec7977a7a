"""``dynamic-allocation``'s nodes and pods, for a configuration served by
the single-AZ min-frag queue pass at 10,000 nodes: a program whose valve
reads each snapshot of the pass whole (it has no
``batch_solver.compact_snapshots``) relaunches the kernel for every 32
flagged queue apps and decides each app on its own, so that a driver
Filter takes 0.1-0.9 s by the seed's cluster and the cell's rate follows
the seed threefold: no steady measurement of this configuration.  This
adapter says so at once, before anything is started: it exits non-zero
while it is loaded where the program lacks that view.
"""

from __future__ import annotations

import plugins

_plain = plugins.load("objects", "dynamic-allocation")


def __getattr__(name: str):
    """Everything else is ``dynamic-allocation``'s."""
    return getattr(_plain, name)


def _require_compacted_valve() -> None:
    from k8s_spark_scheduler_tpu.ops import batch_solver

    if not hasattr(batch_solver, "compact_snapshots"):
        raise SystemExit(
            "objects/dynamic-allocation-compacted-valve: this program's single-AZ "
            "min-frag valve reads every snapshot whole, a launch per 32 flagged "
            "apps: its rate follows the seed's cluster (no steady measurement of "
            "this configuration)"
        )


_require_compacted_valve()
