"""single-az-minimal-fragmentation (binpack/single_az.go:23-97 over
minimal_fragmentation.go, scored by efficiency.go:53-156): the whole gang
inside one zone, packed there on as few nodes as possible.  Every zone
that takes the gang under minimal-fragmentation is a candidate; the
candidate with the highest average packing efficiency wins, and an
earlier zone keeps its place unless a later one is strictly better
(``single-az-tightly-pack.py``'s combinator and score, unchanged).

A quirk of the source, kept because the zone it picks depends on it:
minimal_fragmentation.go never writes the executors it places back into
the reserved map that single_az.go scores (every other distribution
does), so the score sees the driver's reservation alone.  Each pod still
counts one term of the average, an executor's node with what it held
before the gang came; the driver's node with the driver on top.

It needs each node's schedulable cpu and memory beside what is free, as
the single-AZ policies do: ``pack(cpu, mem, zones, gang, sched_cpu,
sched_mem)``.
"""

from __future__ import annotations

from dataclasses import replace
from typing import List, Optional, Tuple

import numpy as np

import plugins

_single_az = plugins.load("policies", "single-az-tightly-pack")
_min_frag = plugins.load("policies", "minimal-fragmentation")
Packed = _single_az.Packed


def pack(cpu, mem, zones, gang, sched_cpu, sched_mem) -> Optional[Packed]:
    """One gang against free (cpu, mem) in priority order with the nodes'
    zones and schedulable (cpu, mem): (driver position, executor
    positions) inside the best zone, or None where no zone takes it."""
    zone_of = np.array(zones, dtype=object)
    # the no-write-back quirk: what the score adds for an executor is nothing
    scored = replace(gang, executor_cpu=0, executor_mem_gi=0)
    candidates: List[Tuple[float, Packed]] = []
    for zone in _single_az.zones_in_order(zones):
        inside = np.flatnonzero(zone_of == zone)
        packed = _min_frag.pack(cpu[inside], mem[inside], None, gang)
        if packed is None:
            continue
        placed = (int(inside[packed[0]]), [int(inside[p]) for p in packed[1]])
        candidates.append(
            (_single_az.average_efficiency(placed, cpu, mem, sched_cpu, sched_mem, scored), placed)
        )
    return _single_az.best_zone(candidates)
