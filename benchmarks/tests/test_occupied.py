"""``fifo10k-tightly-occupied``: the generator's busy cluster, the objects
it becomes, the reference's free capacity counted by hand, the rehearsal,
the control and a planted fault in the program's mirror.  Run this file
alone (``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_occupied.py -q``):
its rehearsals start clients whose source ports collide with other
files' in one process."""

import json

import numpy as np
import pytest

import plugins
import run as run_mod
from blocks import GI, Gang

CELL = "fifo10k-tightly-occupied.drivers"
SEED = 4_000_000_019
MI = 1 << 20
generator = plugins.load("generators", "occupied")


def rehearsal_config():
    return run_mod.rehearsal_size(run_mod.find_cell(CELL)["config"])


def rehearse(capsys, *extra, seed=SEED):
    code = run_mod.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", "0", "--rehearse", *extra]
    )
    return code, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def per_node(cluster):
    """Requested (milli-cpu, bytes) on each node: daemonsets and running pods."""
    index = {n: i for i, n in enumerate(cluster.names)}
    cpu = np.full(len(cluster.names), sum(d.cpu_m for d in cluster.daemons), np.int64)
    mem = np.full(len(cluster.names), sum(d.mem_mi for d in cluster.daemons) * MI, np.int64)
    for gang, driver, executors in cluster.running:
        cpu[index[driver]] += gang.driver_cpu * 1000
        mem[index[driver]] += gang.driver_mem_gi * GI
        for node in executors:
            cpu[index[node]] += gang.executor_cpu * 1000
            mem[index[node]] += gang.executor_mem_gi * GI
    return cpu, mem


# -- the generator ---------------------------------------------------------------


def test_the_same_seed_gives_the_same_cluster_and_stratifieds_nodes_and_backlog():
    config = rehearsal_config()
    a = generator.make_cluster(config, SEED, 1_000_000.0)
    b = generator.make_cluster(config, SEED, 1_000_000.0)
    assert (a.daemons, a.running) == (b.daemons, b.running)
    plain = plugins.load("generators", "stratified").make_cluster(config, SEED, 1_000_000.0)
    assert a.names == plain.names and a.backlog == plain.backlog
    assert (a.cpu == plain.cpu).all() and (a.mem_gi == plain.mem_gi).all()
    other = generator.make_cluster(config, SEED + 1, 1_000_000.0)
    assert other.running != a.running


def test_running_applications_hold_the_share_within_one_gang_and_no_node_is_overfull():
    config = rehearsal_config()
    cluster = generator.make_cluster(config, SEED, 1_000_000.0)
    g = config["gang"]
    target = config["occupancy"]["running_cpu_share"] * int(cluster.cpu.sum()) * 1000
    reserved = sum(gang.driver_cpu * 1000 + gang.executors * gang.executor_cpu * 1000
                   for gang, _, _ in cluster.running)
    largest = (g["driver_cpu"] + g["executors"][1] * g["executor_cpu"][1]) * 1000
    assert target <= reserved < target + largest
    cpu, mem = per_node(cluster)
    assert (cpu <= cluster.cpu * 1000).all() and (mem <= cluster.mem_gi.astype(np.int64) * GI).all()
    for gang, driver, executors in cluster.running:
        assert len(executors) == gang.executors and gang.created < cluster.base_ts
        assert g["executors"][0] <= gang.executors <= g["executors"][1]


def test_the_objects_put_each_daemonset_on_every_node_and_reserve_every_running_pod():
    config = rehearsal_config()
    cluster = generator.make_cluster(config, SEED, 1_000_000.0)
    objects = list(plugins.load("objects", "static-allocation-occupied").nodes(cluster))
    kinds = [o.KIND for o in objects]
    assert kinds[: len(cluster.names)] == ["Node"] * len(cluster.names)
    daemons = [o for o in objects if o.KIND == "Pod" and o.namespace == "kube-system"]
    assert len(daemons) == len(cluster.daemons) * len(cluster.names)
    for d in cluster.daemons:
        assert sorted(p.node_name for p in daemons if p.labels["app"] == d.name) == sorted(cluster.names)
    assert all(p.scheduler_name == "default-scheduler" and p.phase == "Running" for p in daemons)
    reservations = [o for o in objects if o.KIND == "ResourceReservation"]
    spark = {o.name: o for o in objects if o.KIND == "Pod" and o.namespace != "kube-system"}
    assert len(reservations) == len(cluster.running)
    assert len(spark) == sum(1 + gang.executors for gang, _, _ in cluster.running)
    for rr, (gang, driver, executors) in zip(reservations, cluster.running):
        assert rr.name == gang.app_id and len(rr.status.pods) == len(rr.spec.reservations) == 1 + gang.executors
        for slot, pod_name in rr.status.pods.items():
            assert spark[pod_name].node_name == rr.spec.reservations[slot].node
        assert rr.spec.reservations["driver"].node == driver
        assert rr.meta.owner_references[0].uid == spark[f"{gang.app_id}-driver"].meta.uid != ""


# -- the reference ---------------------------------------------------------------


def test_the_references_free_capacity_is_allocatable_less_reservations_and_daemonsets():
    """Four nodes counted by hand: 8 cpu / 16 Gi each; daemonsets 100m /
    128 Mi and 200m / 256 Mi; one application with its driver (1 cpu,
    1 Gi) on n1 and executors (2 cpu, 3 Gi) on n1, n1, n2; another
    with its driver on n3 and one executor (4 cpu, 5 Gi) on n3."""
    names = ["n0", "n1", "n2", "n3"]
    cluster = generator.OccupiedCluster(
        names, np.full(4, 8), np.full(4, 16), ["z0"] * 4, [], 0.0,
        [generator.DaemonSet("a", 100, 128), generator.DaemonSet("b", 200, 256)],
        [
            (Gang("r1", 3, 2, 3, 1, 1, -1.0), "n1", ("n1", "n1", "n2")),
            (Gang("r2", 1, 4, 5, 1, 1, -0.9), "n3", ("n3",)),
        ],
    )
    ref = plugins.load("references", "fifo-gangs-occupied").Reference(cluster, "tightly-pack")
    cpu, mem = ref._free()
    assert cpu.tolist() == [7700, 7700 - 1000 - 4000, 7700 - 2000, 7700 - 1000 - 4000]
    assert mem.tolist() == [16 * GI - 384 * MI, 16 * GI - 384 * MI - 7 * GI, 16 * GI - 384 * MI - 3 * GI,
                            16 * GI - 384 * MI - 6 * GI]
    assert ref.granted == {}


# -- the run ---------------------------------------------------------------------


def test_the_rehearsal_is_correct_and_every_answer_a_grant(capsys):
    code, line = rehearse(capsys)
    assert code == run_mod.EXIT_REHEARSAL
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values() if c["limit"] == 0)


def test_the_control_without_fifo_reads_incorrect(capsys):
    _, line = rehearse(capsys, "--control", "fifo-off")
    assert line["correct"] is False and line["checks"]["driver_answers_wrong"]["value"] > 0


def test_a_mirror_that_drops_the_daemonsets_overhead_reads_incorrect(capsys, monkeypatch):
    """The program's tensor mirror never sees a pod the default scheduler
    bound: each node's daemonset requests go missing from its overhead,
    so a node whose free cpu or memory is a whole number of executors
    short by them takes one more in the program than in the reference."""
    from k8s_spark_scheduler_tpu.scheduler import labels as L
    from k8s_spark_scheduler_tpu.state.tensor_snapshot import TensorSnapshotCache

    real = TensorSnapshotCache._on_pod

    def blind(self, pod):
        if pod.scheduler_name == L.SPARK_SCHEDULER_NAME:
            real(self, pod)

    monkeypatch.setattr(TensorSnapshotCache, "_on_pod", blind)
    _, line = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["reservations_wrong"]["value"] > 0
