"""tightly-pack (binpack/tightly_pack.go): first fit in priority order,
fill a node before moving on."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from packing import first_driver_that_fits


def distribute(caps: np.ndarray, count: int) -> Optional[List[int]]:
    before = np.cumsum(caps) - caps
    per_node = np.clip(count - before, 0, caps)
    if int(per_node.sum()) < count:
        return None
    return np.repeat(np.arange(caps.size), per_node).tolist()


def pack(cpu, mem, zones, gang):
    return first_driver_that_fits(cpu, mem, gang, distribute)
