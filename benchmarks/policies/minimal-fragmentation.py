"""minimal-fragmentation (binpack/minimal_fragmentation.go): fewest hosts,
sparing the emptiest nodes where a fuller subset does."""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

import numpy as np

from packing import first_driver_that_fits


def _min_frag_select(count: int, entries: List[Tuple[int, int]]) -> Optional[List[int]]:
    """minimal_fragmentation.go:96-137 over (capacity, position) entries
    sorted ascending by capacity (stable)."""
    remaining = list(entries)
    out: List[int] = []
    while remaining:
        keys = [c for c, _ in remaining]
        pos = bisect.bisect_left(keys, count)
        if pos != len(remaining):  # one node takes everything left
            out.extend([remaining[pos][1]] * count)
            return out
        top = remaining[-1][0]
        first_top = bisect.bisect_left(keys, top)
        cur = first_top
        while count >= top and cur < len(remaining):
            out.extend([remaining[cur][1]] * top)
            count -= top
            cur += 1
        if count == 0:
            return out
        remaining = remaining[:first_top] + remaining[cur:]
    return None


def distribute(caps: np.ndarray, count: int) -> Optional[List[int]]:
    """Fewest hosts, sparing the emptiest nodes where a fuller subset
    does (minimal_fragmentation.go:59-94).  Returns node positions, one
    per executor, in the order the reference emits them."""
    pos = np.flatnonzero(caps > 0)
    if pos.size == 0:
        return None
    order = pos[np.argsort(caps[pos], kind="stable")]
    entries = [(int(caps[p]), int(p)) for p in order]
    top = entries[-1][0]
    if count < top:
        target = (count + top) // 2
        cut = bisect.bisect_left([c for c, _ in entries], target)
        got = _min_frag_select(count, entries[:cut])
        if got is not None:
            return got
    return _min_frag_select(count, entries)


def pack(cpu, mem, zones, gang):
    return first_driver_that_fits(cpu, mem, gang, distribute)
