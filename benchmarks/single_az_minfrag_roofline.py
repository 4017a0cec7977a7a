"""Operations and bytes of the single-AZ minimal-fragmentation admission
rule, from the cell's shapes only, in ``single_az_roofline.py``'s style:
the count is of the rule, one pass over the queue, whatever implements it
and however often it is launched.

For each of the ``apps`` queued gangs in FIFO order the rule packs the gang
by minimal fragmentation inside every zone, scores each zone's packing by
its average packing efficiency and takes the best zone's usage off the
cluster.  The zones partition the nodes, so the drain looks at every node
once per gang, as in ``roofline.py``; the score then looks at every node
once more, as in ``single_az_roofline.py``.  Operations per (app, node),
by the parts of the rule:

=============================================  ===
part                                           ops
=============================================  ===
minimal-fragmentation inside the node's zone    23
  (``roofline.OPS_PER_APP_NODE``)
the zone score and the chosen zone's usage      20
  (``single_az_roofline.SCORE_OPS_PER_APP_NODE``)
=============================================  ===
total                                           43
=============================================  ===

The score reads the same per-node inputs whatever the inner policy, so the
bytes are ``single_az_roofline.queue_pass_bytes``.
"""

from __future__ import annotations

from typing import Dict

import roofline
import single_az_roofline

DRAIN_OPS_PER_APP_NODE = roofline.OPS_PER_APP_NODE["minimal-fragmentation"]
SCORE_OPS_PER_APP_NODE = sum(single_az_roofline.SCORE_OPS_PER_APP_NODE.values())
OPS_PER_APP_NODE = DRAIN_OPS_PER_APP_NODE + SCORE_OPS_PER_APP_NODE
queue_pass_bytes = single_az_roofline.queue_pass_bytes


def queue_pass_ops(nodes: int, apps: int) -> int:
    return OPS_PER_APP_NODE * nodes * apps


def least_seconds(nodes: int, apps: int, device_kind: str) -> Dict[str, object]:
    """The least time the chip could take for one pass over the queue, and
    which peak bounds it (against the bf16 peak, as ``roofline.least_seconds``)."""
    peaks = roofline.peaks_for(device_kind)
    compute = queue_pass_ops(nodes, apps) / peaks["bf16_flops_per_s"]
    memory = queue_pass_bytes(nodes, apps) / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute (bf16 peak)" if compute >= memory else "memory (HBM)",
        "compute_s": compute,
        "memory_s": memory,
    }
