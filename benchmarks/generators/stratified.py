"""The source's deployment, stratified: ``bench.py:_config5_e2e``'s ranges
sampled without the luck of the draw.

Node capacities and the backlog's gang shapes are fixed multisets over the
configuration's ranges, permuted by the seed; the stream is a sequence of
*blocks* of ``block_gangs`` gangs, one from each equal-width stratum of
the executor-count range, with the same executor total in every block of
every seed.  Ages are the source's: the oldest pending driver was created
10,000 s before the run, each next one a second later, and every new
driver behind them all (``base = time.time() - 10_000``,
``creation_timestamp=base + i``).
"""

from __future__ import annotations

from typing import Dict, Iterator, List

from blocks import Cluster, Gang, executor_counts, rng_of, spread, strata_draw

BACKLOG_AGE_S = 10_000.0
CREATION_SPACING_S = 1.0


def make_cluster(config: Dict, seed: int, now: float) -> Cluster:
    """The deployment's nodes and its pending backlog, from the seed;
    ``now`` is the wall clock the ages are counted back from."""
    base_ts = now - BACKLOG_AGE_S
    c = config["cluster"]
    n = int(c["nodes"])
    rng = rng_of(seed, 1)
    cpu = rng.permutation(spread(c["node_cpu"][0], c["node_cpu"][1], n))
    mem = rng.permutation(spread(c["node_mem_gi"][0], c["node_mem_gi"][1], n))
    names = [f"n{i:05d}" for i in range(n)]
    zone = [f"z{i % int(c['zones'])}" for i in range(n)]
    g = config["gang"]
    m = int(c["backlog"])
    rng = rng_of(seed, 2)
    counts = rng.permutation(spread(g["executors"][0], g["executors"][1], m))
    cpus = rng.permutation(spread(g["executor_cpu"][0], g["executor_cpu"][1], m))
    mems = rng.permutation(spread(g["executor_mem_gi"][0], g["executor_mem_gi"][1], m))
    backlog = [
        Gang(
            f"queue-{i:05d}", int(counts[i]), int(cpus[i]), int(mems[i]),
            int(g["driver_cpu"]), int(g["driver_mem_gi"]), base_ts + i * CREATION_SPACING_S,
        )
        for i in range(m)
    ]
    return Cluster(names, cpu, mem, zone, backlog, base_ts)


def blocks(config: Dict, traffic: Dict, seed: int, base_ts: float) -> Iterator[List[Gang]]:
    """The endless stream of blocks behind the backlog.  Block ``b`` is the
    same for a seed whatever was drawn before it."""
    g = config["gang"]
    k = int(traffic["block_gangs"])
    first = int(config["cluster"]["backlog"])
    b = 0
    while True:
        rng = rng_of(seed, 1000 + b)
        counts = executor_counts(g["executors"][0], g["executors"][1], k, rng)
        cpus = rng.permutation(strata_draw(g["executor_cpu"][0], g["executor_cpu"][1], k, rng))
        mems = rng.permutation(
            strata_draw(g["executor_mem_gi"][0], g["executor_mem_gi"][1], k, rng)
        )
        order = rng.permutation(k)
        block = []
        for j, s in enumerate(order):
            i = b * k + j
            block.append(
                Gang(
                    f"b{b:05d}g{j}", int(counts[s]), int(cpus[s]), int(mems[s]),
                    int(g["driver_cpu"]), int(g["driver_mem_gi"]),
                    base_ts + (first + i) * CREATION_SPACING_S,
                )
            )
        yield block
        b += 1
