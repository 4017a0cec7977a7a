"""What the packing policies share: how many executors fit a node, and the
reference's search for the driver's node (binpack.go:60-87: the first node
that takes the driver and after which the executors can be distributed).
A policy (``policies/<name>.py``) is a file of its own with
``pack(cpu, mem, zones, gang)``; most are ``first_driver_that_fits`` with
their own way of distributing the executors."""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from blocks import GI, Gang

Distribute = Callable[[np.ndarray, int], Optional[List[int]]]


def caps_of(cpu: np.ndarray, mem: np.ndarray, ecpu: int, emem: int) -> np.ndarray:
    """Executors of (ecpu, emem) that fit in each node's free (cpu, mem)."""
    return np.maximum(np.minimum(cpu // ecpu, mem // emem), 0)


def first_driver_that_fits(
    cpu: np.ndarray, mem: np.ndarray, gang: Gang, distribute: Distribute
) -> Optional[Tuple[int, List[int]]]:
    """One gang against free (cpu, mem) given in priority order: (driver
    position, executor positions, one per executor) or None.
    ``distribute(caps, count)`` places ``count`` executors over nodes of
    capacities ``caps`` or returns None."""
    dcpu, dmem = gang.driver_cpu * 1000, gang.driver_mem_gi * GI
    ecpu, emem = gang.executor_cpu * 1000, gang.executor_mem_gi * GI
    base = caps_of(cpu, mem, ecpu, emem)
    total = int(base.sum())
    for d in np.flatnonzero((cpu >= dcpu) & (mem >= dmem)):
        with_driver = int(caps_of(cpu[d : d + 1] - dcpu, mem[d : d + 1] - dmem, ecpu, emem)[0])
        if total - int(base[d]) + with_driver < gang.executors:
            continue  # no distribution exists unless the capacities sum to the gang
        caps = base.copy()
        caps[d] = with_driver
        placed = distribute(caps, gang.executors)
        if placed is not None:
            return int(d), placed
    return None
