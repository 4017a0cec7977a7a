"""The plain data a cell is made of (``Cluster``, ``Gang``) and the
stratified draws that generators share: every seed gives the same amount
of work, differently arranged.  Nothing here imports the program: a
generator (``generators/<name>.py``) makes this data from ``--seed``, an
object adapter (``objects/<name>.py``) turns it into the program's
objects, and a reference (``references/<name>.py``) answers from it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

GI = 1 << 30


@dataclass(frozen=True)
class Gang:
    """One Spark application: a driver and ``executors`` identical executors."""

    app_id: str
    executors: int
    executor_cpu: int  # whole cpus
    executor_mem_gi: int
    driver_cpu: int
    driver_mem_gi: int
    created: float  # creation timestamp; FIFO order


@dataclass(frozen=True)
class Cluster:
    names: List[str]
    cpu: np.ndarray  # whole cpus per node
    mem_gi: np.ndarray
    zone: List[str]
    backlog: List[Gang]
    base_ts: float


def rng_of(seed: int, stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative whole number, however large
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` integers covering lo..hi (inclusive) evenly, ascending: the
    uniform range without the luck of the draw."""
    return lo + (np.arange(n, dtype=np.int64) * (hi - lo + 1)) // n


def strata_draw(lo: int, hi: int, strata: int, rng: np.random.Generator) -> np.ndarray:
    """One integer from each of ``strata`` equal-width strata of [lo, hi+1)."""
    width = (hi - lo + 1) / strata
    return np.floor(lo + (np.arange(strata) + rng.random(strata)) * width).astype(np.int64)


def executor_counts(lo: int, hi: int, strata: int, rng: np.random.Generator) -> np.ndarray:
    """One executor count from each stratum, with the same total in every
    block: stratum k starts at ``lo + k*w`` (``w`` the whole-number width)
    and takes an offset from the fixed multiset {0,0,1,1,...,w-1,w-1};
    which stratum takes which offset comes from the seed.  A last stratum
    cut short by ``hi`` only takes offsets that stay inside the range."""
    w = -(-(hi - lo + 1) // strata)  # ceil
    offsets = np.repeat(np.arange(w), -(-strata // w))[:strata]
    last_room = hi - (lo + (strata - 1) * w)  # largest offset the last stratum may take
    if not 0 <= last_room < w:
        raise ValueError(f"range {lo}..{hi} does not split into {strata} strata")
    for _ in range(64):
        perm = rng.permutation(offsets)
        if perm[-1] <= last_room:
            return lo + np.arange(strata) * w + perm
    raise RuntimeError("no admissible offset permutation drawn")  # p < 1e-30
