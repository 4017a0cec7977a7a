"""Operations and bytes of the single-AZ admission rule, from the cell's
shapes only, in ``roofline.py``'s style: the count is of the rule, one
pass over the queue, whatever implements it and however often it is
launched.

For each of the ``apps`` queued gangs in FIFO order the rule packs the gang
tightly inside every zone, scores each zone's packing by its average
packing efficiency and takes the best zone's usage off the cluster.  The
zones partition the nodes, so the tightly-pack rule looks at every node
once per gang, as in ``roofline.py``; the score then looks at every node
once more.  Operations per (app, node), by the parts of the rule:

=============================================  ===
part                                           ops
=============================================  ===
tightly-pack inside the node's zone             15
  (``roofline.OPS_PER_APP_NODE``)
newly reserved, cpu and memory (mul, add) x 2    4
reserved = schedulable - free + new              4
  (sub, add) x 2
cpu up to whole cores (add, div)                 2
the two ratios (2 div)                           2
the larger of them (max)                         1
quantisation (mul, add, floor)                   3
weighted sum over the gang's pods (weight        3
  add, mul, add)
the chosen zone's usage (select)                 1
=============================================  ===
total                                           35
=============================================  ===

Bytes are ``roofline.queue_pass_bytes`` with what the score reads besides:
four more int32 per node (its zone, its schedulable cpu, its schedulable
memory in bytes, which takes two) and one more written per app (the zone
it was given).
"""

from __future__ import annotations

from typing import Dict

import roofline

SCORE_OPS_PER_APP_NODE = {
    "newly reserved": 4,
    "reserved": 4,
    "cpu to whole cores": 2,
    "ratios": 2,
    "larger ratio": 1,
    "quantisation": 3,
    "weighted sum": 3,
    "chosen zone's usage": 1,
}
OPS_PER_APP_NODE = roofline.OPS_PER_APP_NODE["tightly-pack"] + sum(SCORE_OPS_PER_APP_NODE.values())
MORE_READ_PER_NODE = 4
MORE_WRITTEN_PER_APP = 1


def queue_pass_ops(nodes: int, apps: int) -> int:
    return OPS_PER_APP_NODE * nodes * apps


def queue_pass_bytes(nodes: int, apps: int) -> int:
    more = roofline.INT32 * (MORE_READ_PER_NODE * nodes + MORE_WRITTEN_PER_APP * apps)
    return roofline.queue_pass_bytes(nodes, apps) + more


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
