"""``stratified``'s deployment on a cluster that is already busy: the
same nodes, backlog, ages and blocks for a seed, and besides them what a
cluster with a standing Spark backlog holds while it runs.

- Every node carries one pod of each daemonset the configuration names
  (``occupancy.daemonsets``: a name and the requests of its pod), bound
  by the default scheduler and held by no reservation: what the
  reference counts as overhead (``overhead.go``).
- Running applications, drawn from the configuration's own gang ranges
  (uniform, a stream of the seed's own), are placed until the cpu their
  reservations hold reaches ``occupancy.running_cpu_share`` of the
  cluster's: the driver and then each executor on the first node, in a
  permutation of the nodes drawn for the application, that has room
  after the daemonsets and the applications placed before it; an
  application whose gang does not fit is drawn again.  No policy's order
  is assumed: the state a cluster reaches after long churn.
- Running applications are older than the whole backlog: the oldest was
  created ``occupancy.running_started_s_before_backlog`` seconds before
  the oldest pending driver, each next one a tenth of a second later.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

import plugins
from blocks import GI, Cluster, Gang, rng_of

_stratified = plugins.load("generators", "stratified")
BACKLOG_AGE_S = _stratified.BACKLOG_AGE_S
blocks = _stratified.blocks  # the stream behind the backlog is stratified's
MI = 1 << 20
RUNNING_SPACING_S = 0.1
DRAWS_PER_APPLICATION = 64  # draws of one application before the share is called unreachable


@dataclass(frozen=True)
class DaemonSet:
    name: str
    cpu_m: int  # milli-cpu requested by its pod on every node
    mem_mi: int  # MiB requested by its pod on every node


@dataclass(frozen=True)
class OccupiedCluster(Cluster):
    daemons: List[DaemonSet]  # one pod of each on every node
    # (gang, driver node, executor nodes in slot order) of each running application
    running: List[Tuple[Gang, str, Tuple[str, ...]]]


def make_cluster(config: Dict, seed: int, now: float) -> OccupiedCluster:
    """``stratified``'s nodes and backlog, the daemonsets on every node
    and the running applications, from the seed."""
    base = _stratified.make_cluster(config, seed, now)
    occupancy = config["occupancy"]
    daemons = [DaemonSet(d["name"], int(d["cpu_m"]), int(d["mem_mi"])) for d in occupancy["daemonsets"]]
    cpu = base.cpu.astype(np.int64) * 1000 - sum(d.cpu_m for d in daemons)
    mem = base.mem_gi.astype(np.int64) * GI - sum(d.mem_mi for d in daemons) * MI
    if (cpu < 0).any() or (mem < 0).any():
        raise ValueError("the daemonsets ask more than a node holds")
    running = _place_running(config, seed, base, cpu, mem)
    return OccupiedCluster(
        base.names, base.cpu, base.mem_gi, base.zone, base.backlog, base.base_ts, daemons, running,
    )


def _first_fit(cpu: np.ndarray, mem: np.ndarray, gang: Gang) -> Optional[Tuple[int, np.ndarray]]:
    """(driver position, executor positions) of ``gang`` on free (cpu,
    mem) given in the order to try, each pod on the first position with
    room, or None where the gang does not fit.  Identical executors taken
    one at a time, each on the first node with room, fill the nodes in
    order: room a node lacked for one never comes back."""
    dcpu, dmem = gang.driver_cpu * 1000, gang.driver_mem_gi * GI
    ecpu, emem = gang.executor_cpu * 1000, gang.executor_mem_gi * GI
    fits = np.flatnonzero((cpu >= dcpu) & (mem >= dmem))
    if not fits.size:
        return None
    d = int(fits[0])
    caps = np.minimum(cpu // ecpu, mem // emem)
    caps[d] = min((cpu[d] - dcpu) // ecpu, (mem[d] - dmem) // emem)
    per_node = np.clip(gang.executors - (np.cumsum(caps) - caps), 0, caps)
    if int(per_node.sum()) < gang.executors:
        return None
    return d, np.repeat(np.arange(caps.size), per_node)


def _place_running(config: Dict, seed: int, base: Cluster, cpu: np.ndarray, mem: np.ndarray):
    """Running applications placed on (and taken off) free ``cpu`` /
    ``mem`` until their reserved cpu reaches the configuration's share."""
    g, occupancy = config["gang"], config["occupancy"]
    target = float(occupancy["running_cpu_share"]) * int(base.cpu.sum()) * 1000
    started = base.base_ts - float(occupancy["running_started_s_before_backlog"])
    rng = rng_of(seed, 3)
    names = base.names
    running: List[Tuple[Gang, str, Tuple[str, ...]]] = []
    reserved = 0
    while reserved < target:
        for _ in range(DRAWS_PER_APPLICATION):
            i = len(running)
            gang = Gang(
                f"run-{i:05d}",
                int(rng.integers(g["executors"][0], g["executors"][1] + 1)),
                int(rng.integers(g["executor_cpu"][0], g["executor_cpu"][1] + 1)),
                int(rng.integers(g["executor_mem_gi"][0], g["executor_mem_gi"][1] + 1)),
                int(g["driver_cpu"]), int(g["driver_mem_gi"]),
                started + i * RUNNING_SPACING_S,
            )
            order = rng.permutation(len(names))
            placed = _first_fit(cpu[order], mem[order], gang)
            if placed is not None:
                break
        else:
            raise ValueError(f"no application fits after {len(running)}: the share is out of reach")
        d, executors = order[placed[0]], order[placed[1]]
        cpu[d] -= gang.driver_cpu * 1000
        mem[d] -= gang.driver_mem_gi * GI
        np.subtract.at(cpu, executors, gang.executor_cpu * 1000)
        np.subtract.at(mem, executors, gang.executor_mem_gi * GI)
        running.append((gang, names[d], tuple(names[e] for e in executors)))
        reserved += gang.driver_cpu * 1000 + gang.executors * gang.executor_cpu * 1000
    return running
