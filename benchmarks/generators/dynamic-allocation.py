"""``stratified``'s deployment where every application runs with dynamic
allocation: the same nodes, backlog, ages and blocks, each gang with a
*range* of executors.  ``executors`` is the most it may hold
(``spark-dynamic-allocation-max-executor-count``), ``min_executors`` what
it is admitted with (``...-min-executor-count``): the configuration's
``gang.min_executors`` rule, a quarter of the max, rounded up.

With the block rule's strata of width 4 from 1 a gang of stratum *k* has
max 4k+1..4k+4 and min k+1, so every block of every seed holds 132 max
and 36 min executors: 96 executors beyond min, whichever gang takes
which offset.
"""

from __future__ import annotations

from dataclasses import astuple, dataclass
from typing import Dict, Iterator, List, Sequence

import plugins
from blocks import Cluster, Gang

_stratified = plugins.load("generators", "stratified")
BACKLOG_AGE_S = _stratified.BACKLOG_AGE_S
MIN_RULES = {"ceil(max / 4)": lambda most: -(-most // 4)}


@dataclass(frozen=True)
class DynGang(Gang):
    min_executors: int  # the gang's hard reservation slots; ``executors`` is the max


def _with_min(gangs: Sequence[Gang], config: Dict) -> List[DynGang]:
    rule = config["gang"]["min_executors"]
    if rule not in MIN_RULES:
        raise ValueError(f"gang.min_executors {rule!r} is no rule of this generator ({sorted(MIN_RULES)})")
    return [DynGang(*astuple(g), MIN_RULES[rule](g.executors)) for g in gangs]


def make_cluster(config: Dict, seed: int, now: float) -> Cluster:
    """``stratified``'s nodes and backlog; every pending driver carries
    its min beside its max."""
    base = _stratified.make_cluster(config, seed, now)
    return Cluster(base.names, base.cpu, base.mem_gi, base.zone, _with_min(base.backlog, config), base.base_ts)


def blocks(config: Dict, traffic: Dict, seed: int, base_ts: float) -> Iterator[List[DynGang]]:
    for block in _stratified.blocks(config, traffic, seed, base_ts):
        yield _with_min(block, config)
