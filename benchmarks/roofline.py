"""Operations and bytes of the FIFO admission rule, from the cell's shapes only.

The count is of the rule, whatever implements it: for each of the ``apps``
queued gangs in FIFO order, one look at every one of ``nodes`` nodes to
decide where the gang goes and what it takes off the cluster.  Integer
operations per (app, node), by the parts of the rule:

=========================  ============  =====================
part                       tightly-pack  minimal-fragmentation
=========================  ============  =====================
driver fits (2 cmp, and)              3                      3
capacity (2 div, min, max)            4                      4
first fit (cumsum add,                4                      -
  sub, 2 clip)
largest capacity (max)                -                      1
one node takes all (cmp,              -                      6
  select, min) x 2 tries
drain the fullest (cmp,               -                      5
  cumsum add, sub, 2 clip)
FIFO usage (2 select, 2 sub)          4                      4
=========================  ============  =====================
total                                15                     23
=========================  ============  =====================

Bytes are the least the rule must move when the cluster's state stays on
the chip through the whole queue: the inputs read once (three int32 per
node: free cpu, free memory, eligibility; five per app: driver cpu and
memory, executor cpu and memory, executor count) and the results written
once (two per node: what is free afterwards; one per app: admitted or
not).
"""

from __future__ import annotations

import json
import os
from typing import Dict

OPS_PER_APP_NODE = {"tightly-pack": 15, "minimal-fragmentation": 23}
INT32 = 4


def queue_pass_ops(policy: str, nodes: int, apps: int) -> int:
    return OPS_PER_APP_NODE[policy] * nodes * apps


def queue_pass_bytes(nodes: int, apps: int) -> int:
    read = INT32 * (3 * nodes + 5 * apps)
    written = INT32 * (2 * nodes + apps)
    return read + written


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a chip that is not in the
    table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in peaks.json")
    return table[device_kind]


def least_seconds(policy: str, nodes: int, apps: int, device_kind: str) -> Dict[str, object]:
    """The least time the chip could take for one queue pass, and which
    peak bounds it.  No int32 vector peak is published for the v5e, so
    the compute bound is taken against the bf16 peak: the share it gives
    is a floor of the share of the integer units."""
    peaks = peaks_for(device_kind)
    compute = queue_pass_ops(policy, nodes, apps) / peaks["bf16_flops_per_s"]
    memory = queue_pass_bytes(nodes, apps) / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute (bf16 peak)" if compute >= memory else "memory (HBM)",
        "compute_s": compute,
        "memory_s": memory,
    }
