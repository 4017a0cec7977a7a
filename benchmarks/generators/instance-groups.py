"""``stratified``'s deployment divided into instance groups (node pools):
every node carries one group, every gang asks for one, and the FIFO is
kept per group (the reference's ``instance-group-label``).

The configuration states the groups (``cluster.instance_groups``: name,
nodes, backlog, gangs per block).  The node capacities are ``stratified``'s
multisets, dealt so that every group holds an even share of the whole cpu
range, of the whole memory range and of every zone; which node of a group
takes which capacity comes from the seed.  The backlog and the stream are
``stratified``'s own gangs (shapes, ages, the same executor total in every
block); which of them asks for which group comes from the seed, and the
counts per group are exact: so many of the backlog, so many of every block.
"""

from __future__ import annotations

from dataclasses import astuple, dataclass
from typing import Dict, Iterator, List, Sequence

import numpy as np

import plugins
from blocks import Cluster, Gang, spread

_stratified = plugins.load("generators", "stratified")
BACKLOG_AGE_S = _stratified.BACKLOG_AGE_S


@dataclass(frozen=True)
class GroupGang(Gang):
    group: str  # the instance group its driver and executors require


@dataclass(frozen=True)
class GroupCluster(Cluster):
    group: List[str]  # per node, as ``zone``


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # three words and more: no stream of blocks.rng_of (two words) is met
    return np.random.default_rng(np.random.SeedSequence([int(seed), 34, *stream]))


def deal(n: int, sizes: Sequence[int]) -> np.ndarray:
    """``n`` positions dealt to ``len(sizes)`` hands, ``sizes[h]`` to hand
    ``h``, each hand's positions spread evenly over 0..n-1: position ``k``
    goes to the hand furthest behind its share of the first ``k + 1``."""
    sizes = np.asarray(sizes, dtype=np.int64)
    if int(sizes.sum()) != n:
        raise ValueError(f"group sizes {sizes.tolist()} do not add up to {n}")
    given = np.zeros(len(sizes), dtype=np.int64)
    hands = np.empty(n, dtype=np.int64)
    for k in range(n):
        # exact integers: share of k + 1 less what the hand holds, times n
        behind = sizes * (k + 1) - given * n
        behind[given >= sizes] = np.iinfo(np.int64).min
        hands[k] = h = int(np.argmax(behind))
        given[h] += 1
    return hands


def _with_group(gangs: Sequence[Gang], names: Sequence[str], hands: np.ndarray) -> List[GroupGang]:
    return [GroupGang(*astuple(g), names[h]) for g, h in zip(gangs, hands)]


def make_cluster(config: Dict, seed: int, now: float) -> GroupCluster:
    """The deployment's nodes, each in one instance group, and its pending
    backlog, each driver asking for one, from the seed."""
    c = config["cluster"]
    groups = c["instance_groups"]
    names = [g["name"] for g in groups]
    n = int(c["nodes"])
    base = _stratified.make_cluster(config, seed, now)
    hands = deal(n, [int(g["nodes"]) for g in groups])
    # the node's group: dealt along the nodes taken zone by zone, so that a
    # group's nodes are an even share of every zone
    by_zone = np.argsort(np.arange(n) % int(c["zones"]), kind="stable")
    node_group = np.empty(n, dtype=np.int64)
    node_group[by_zone] = hands
    # capacities: the ascending multiset dealt the same way, so that a group's
    # values are an even share of the whole range; then permuted inside the group
    cpu = np.empty(n, dtype=np.int64)
    mem = np.empty(n, dtype=np.int64)
    rng = _rng(seed, 1)
    for values, (lo, hi) in ((cpu, c["node_cpu"]), (mem, c["node_mem_gi"])):
        ascending = spread(lo, hi, n)
        for h in range(len(groups)):
            values[node_group == h] = rng.permutation(ascending[hands == h])
    backlog_group = _rng(seed, 2).permutation(
        np.repeat(np.arange(len(groups)), [int(g["backlog"]) for g in groups])
    )
    if len(backlog_group) != len(base.backlog):
        raise ValueError("the groups' backlogs do not add up to cluster.backlog")
    return GroupCluster(
        base.names, cpu, mem, base.zone, _with_group(base.backlog, names, backlog_group),
        base.base_ts, [names[h] for h in node_group],
    )


def blocks(config: Dict, traffic: Dict, seed: int, base_ts: float) -> Iterator[List[GroupGang]]:
    """``stratified``'s blocks; in every block each group asks for its
    stated number of gangs (``block_gangs``), which gang from the seed."""
    groups = config["cluster"]["instance_groups"]
    names = [g["name"] for g in groups]
    per_block = np.repeat(np.arange(len(groups)), [int(g["block_gangs"]) for g in groups])
    if len(per_block) != int(traffic["block_gangs"]):
        raise ValueError("the groups' block_gangs do not add up to the mix's block_gangs")
    for b, block in enumerate(_stratified.blocks(config, traffic, seed, base_ts)):
        yield _with_group(block, names, _rng(seed, 3, b).permutation(per_block))
