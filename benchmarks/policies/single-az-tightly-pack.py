"""single-az-tightly-pack (binpack/single_az.go:23-97 over
single_az_pack_tightly.go:21, scored by efficiency.go:53-156): the whole
gang inside one zone.  Every zone that takes the gang under tightly-pack
is a candidate; the candidate with the highest average packing efficiency
wins, and an earlier zone keeps its place unless a later one is strictly
better.

The packing efficiency of a node is what is reserved on it once the gang
is placed over what it can schedule, per resource, the larger of the two
(efficiency.go:80-105):

    reserved = schedulable - free + newly reserved
    cpu      = ceil(reserved milli-cpu / 1000) / ceil(schedulable milli-cpu / 1000)
    memory   = reserved bytes / schedulable bytes

and a packing's average is the sum, in float64 and in this order, of the
driver node's efficiency and then each executor's node's, one term per
pod, over the number of pods (single_az.go:75-97: a node that hosts three
executors counts three times).  A candidate replaces the best so far only
on a strictly higher average, starting from 0.0.

Departures from the Go source, each without effect on these cells:
no GPU dimension (no node of these clusters has one, and a node without
GPUs scores 0.0 there, which the max of two non-negative ratios never is
below); zones are taken from one priority order, because every node of
these clusters is a candidate for driver and executor alike, so the
driver's and the executors' orders are the same list.

It needs what ``pack(cpu, mem, zones, gang)`` does not carry, each node's
schedulable cpu and memory in the same order: ``references/
fifo-gangs-single-az.py`` hands them over as two more arguments.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import plugins
from blocks import GI, Gang

Packed = Tuple[int, List[int]]


def zones_in_order(zones: Sequence[str]) -> List[str]:
    """The zones as they first appear in the priority order (single_az.go:57-72)."""
    seen: List[str] = []
    for z in zones:
        if z not in seen:
            seen.append(z)
    return seen


def node_efficiency(free_cpu: int, free_mem: int, new_cpu: int, new_mem: int,
                    sched_cpu: int, sched_mem: int) -> float:
    """One node's packing efficiency with ``new`` reserved on it on top of
    what already is (milli-cpu and bytes, whole numbers)."""
    reserved_cpu = sched_cpu - free_cpu + new_cpu
    reserved_mem = sched_mem - free_mem + new_mem
    cpu = float(-(-reserved_cpu // 1000)) / float(max(-(-sched_cpu // 1000), 1))
    mem = float(reserved_mem) / float(max(sched_mem, 1))
    return max(cpu, mem)


def average_efficiency(packed: Packed, cpu, mem, sched_cpu, sched_mem, gang: Gang) -> float:
    """The average over the gang's pods, driver first, of their nodes'
    efficiencies once the whole gang is placed."""
    driver, executors = packed
    new_cpu = {driver: gang.driver_cpu * 1000}
    new_mem = {driver: gang.driver_mem_gi * GI}
    for p in executors:
        new_cpu[p] = new_cpu.get(p, 0) + gang.executor_cpu * 1000
        new_mem[p] = new_mem.get(p, 0) + gang.executor_mem_gi * GI
    total = 0.0
    pods = [driver] + list(executors)
    for p in pods:
        total += node_efficiency(
            int(cpu[p]), int(mem[p]), new_cpu[p], new_mem[p], int(sched_cpu[p]), int(sched_mem[p])
        )
    return total / float(len(pods))


def best_zone(candidates: List[Tuple[float, Packed]]) -> Optional[Packed]:
    """The candidate (in zone order) with the highest average; a later
    one has to be strictly better (single_az.go:85-94)."""
    best, best_avg = None, 0.0
    for avg, packed in candidates:
        if best_avg < avg:
            best, best_avg = packed, avg
    return best


def pack(cpu, mem, zones, gang, sched_cpu, sched_mem) -> Optional[Packed]:
    """One gang against free (cpu, mem) in priority order with the nodes'
    zones and schedulable (cpu, mem): (driver position, executor
    positions) inside the best zone, or None where no zone takes it."""
    tightly = plugins.load("policies", "tightly-pack")
    zone_of = np.array(zones, dtype=object)
    candidates: List[Tuple[float, Packed]] = []
    for zone in zones_in_order(zones):
        inside = np.flatnonzero(zone_of == zone)
        packed = tightly.pack(cpu[inside], mem[inside], None, gang)
        if packed is None:
            continue
        placed = (int(inside[packed[0]]), [int(inside[p]) for p in packed[1]])
        candidates.append(
            (average_efficiency(placed, cpu, mem, sched_cpu, sched_mem, gang), placed)
        )
    return best_zone(candidates)
