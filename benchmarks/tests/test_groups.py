"""The instance-group configuration's part of the yardstick: what its
generator deals, its plain reference against the program's host oracle,
the fault the comparison has to catch (a reference that does not know
the groups), its rehearsal and its control."""

import json
import os
import time
from collections import Counter
from itertools import islice

import numpy as np
import pytest

import plugins
import run as run_mod
import stack as stack_mod
import traffic as traffic_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fifo10k-groups.drivers"
SEEDS = (0, 7, 2**31 + 11, 3_000_000_017)

groups_gen = plugins.load("generators", "instance-groups")
stratified = plugins.load("generators", "stratified")
NOW = groups_gen.BACKLOG_AGE_S


def config_of(rehearsal=False):
    with open(os.path.join(BENCH, "configs", "fifo10k-groups.json")) as f:
        config = json.load(f)
    return run_mod.rehearsal_size(config) if rehearsal else config


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "drivers.json")) as f:
        return json.load(f)


def test_deal_gives_every_hand_its_size_evenly_spread():
    hands = groups_gen.deal(100, [44, 22, 15, 11, 8])
    assert Counter(hands.tolist()) == {0: 44, 1: 22, 2: 15, 3: 11, 4: 8}
    for h, size in enumerate([44, 22, 15, 11, 8]):
        at = np.flatnonzero(hands == h)
        # never further from an even spacing than one whole step
        assert np.abs(at - (np.arange(size) + 0.5) * 100 / size).max() <= 100 / size
    with pytest.raises(ValueError, match="do not add up"):
        groups_gen.deal(10, [4, 4])


@pytest.mark.parametrize("rehearsal", [False, True], ids=["full", "rehearsal"])
def test_group_sizes_and_backlog_shares_are_exact_and_each_group_spans_ranges_and_zones(rehearsal):
    config = config_of(rehearsal)
    stated = config["cluster"]["instance_groups"]
    lo_cpu, hi_cpu = config["cluster"]["node_cpu"]
    lo_mem, hi_mem = config["cluster"]["node_mem_gi"]
    arrangements = set()
    for seed in SEEDS[:3]:
        c = groups_gen.make_cluster(config, seed, NOW)
        plain = stratified.make_cluster(config, seed, NOW)
        # the deployment is stratified's: names, zones, the capacity multisets, the backlog's gangs and ages
        assert c.names == plain.names and c.zone == plain.zone and c.base_ts == plain.base_ts
        assert Counter(c.cpu.tolist()) == Counter(plain.cpu.tolist())
        assert Counter(c.mem_gi.tolist()) == Counter(plain.mem_gi.tolist())
        assert [(g.app_id, g.executors, g.executor_cpu, g.executor_mem_gi, g.created) for g in c.backlog] == [
            (g.app_id, g.executors, g.executor_cpu, g.executor_mem_gi, g.created) for g in plain.backlog
        ]
        assert Counter(c.group) == {g["name"]: g["nodes"] for g in stated}
        assert Counter(g.group for g in c.backlog) == {g["name"]: g["backlog"] for g in stated}
        group = np.array(c.group)
        zone = np.array(c.zone)
        for g in stated:
            rows = group == g["name"]
            # the whole range, to within one step of the group's even spacing over it
            # (at full size less than one value: the ends themselves)
            for values, lo, hi in ((c.cpu[rows], lo_cpu, hi_cpu), (c.mem_gi[rows], lo_mem, hi_mem)):
                step = (hi - lo + 1) / g["nodes"]
                assert values.min() <= lo + step and values.max() >= hi - step
                assert rehearsal or (values.min(), values.max()) == (lo, hi)
            per_zone = Counter(zone[rows].tolist())
            assert len(per_zone) == config["cluster"]["zones"]
            assert max(per_zone.values()) - min(per_zone.values()) <= 2  # an even share of every zone
            # an even share of the range: each quarter of it holds about a quarter of the group
            quarters = np.histogram(c.cpu[rows], bins=4, range=(lo_cpu, hi_cpu + 1))[0]
            assert quarters.max() - quarters.min() <= 0.1 * g["nodes"] + 2
        # interleaved in age: no group's pending drivers are all at one end of the queue
        first_half = Counter(g.group for g in c.backlog[: len(c.backlog) // 2])
        assert all(0 < first_half[g["name"]] < g["backlog"] for g in stated)
        arrangements.add((tuple(c.cpu.tolist()), tuple(g.group for g in c.backlog)))
    assert len(arrangements) == 3  # the seed arranges, it does not change the amounts
    again = groups_gen.make_cluster(config, SEEDS[2], NOW)
    assert (tuple(again.cpu.tolist()), tuple(g.group for g in again.backlog)) in arrangements


def test_every_block_of_every_seed_is_3_2_1_1_1_and_132_executors(mix):
    config = config_of()
    want = {g["name"]: g["block_gangs"] for g in config["cluster"]["instance_groups"]}
    assert list(want.values()) == [3, 2, 1, 1, 1]
    orders = set()
    strata_of_ig0 = set()
    for seed in SEEDS:
        plain = stratified.blocks(config, mix, seed, 0.0)
        for block in islice(groups_gen.blocks(config, mix, seed, 0.0), 12):
            assert Counter(g.group for g in block) == want
            assert (len(block), sum(g.executors for g in block)) == (8, 132)
            # stratified's own gangs, with a group each
            assert [(g.app_id, g.executors, g.created) for g in block] == [
                (g.app_id, g.executors, g.created) for g in next(plain)
            ]
            orders.add(tuple(g.group for g in block))
            strata_of_ig0.update((g.executors - 1) // 4 for g in block if g.group == "ig-0")
    assert len(orders) > 12 and strata_of_ig0 == set(range(8))  # which gang asks for which group: the seed's
    first = next(groups_gen.blocks(config, mix, 7, 0.0))
    assert first == next(groups_gen.blocks(config, mix, 7, 0.0))
    with pytest.raises(ValueError, match="block_gangs"):
        next(groups_gen.blocks(config, {**mix, "block_gangs": 4}, 7, 0.0))


def test_the_configuration_states_the_shapes_the_program_serves_its_groups_at():
    from k8s_spark_scheduler_tpu.ops.tensorize import APP_BUCKETS, bucket_size

    for config in (config_of(), config_of(rehearsal=True)):
        served = [
            {"group": g["name"], "nodes": bucket_size(g["nodes"]),
             "apps": bucket_size(g["backlog"] + 1, buckets=APP_BUCKETS)}
            for g in config["cluster"]["instance_groups"]
        ]
        assert config["shapes_served"] == served
        assert "shape_bucket" not in config  # the roofline readers' key: one shape, which this cell has not


def one_block(cluster, config, mix, seed, install, objects=None):
    objects = objects or plugins.load("objects", config["objects"])
    stream = groups_gen.blocks(config, mix, seed, cluster.base_ts)
    served = stack_mod.start_stack(cluster, objects, install)
    try:
        client = stack_mod.Client(served, cluster.names)
        return traffic_mod.run_block(client, objects, next(stream), mix["steps"])
    finally:
        served.stop()


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_answers_as_the_host_oracle_does_at_rehearsal_size(seed, mix):
    """``references/fifo-gangs-groups.py`` with ``policies/tightly-pack.py``
    against ``ops/packers.py``'s tightly-pack behind the extender's host
    FIFO loop, served over HTTP: one block, every group in it."""
    config = config_of(rehearsal=True)
    cluster = groups_gen.make_cluster(config, seed, time.time())
    reference = plugins.load("references", config["reference"]["model"]).Reference(
        cluster, config["reference"]["policy"]
    )
    rec = one_block(cluster, config, mix, seed, {"binpack_algo": "tightly-pack", "fifo": True})
    group_of = dict(zip(cluster.names, cluster.group))
    for g in rec.gangs:
        grant = reference.filter_driver(g.gang)
        assert grant is not None
        want = (grant.driver_node, grant.executor_nodes)
        assert g.read["reservation"] == want, g.gang
        assert g.read["api_reservation"] == want, g.gang
        assert {group_of[n] for n in (grant.driver_node, *grant.executor_nodes)} == {g.gang.group}
        reference.retire(g.gang)
    assert Counter(g.gang.group for g in rec.gangs) == {"ig-0": 3, "ig-1": 2, "ig-2": 1, "ig-3": 1, "ig-4": 1}


def test_a_gang_of_a_group_without_nodes_is_refused_by_the_reference():
    config = config_of(rehearsal=True)
    cluster = groups_gen.make_cluster(config, 3, NOW)
    reference = plugins.load("references", "fifo-gangs-groups").Reference(cluster, "tightly-pack")
    stranger = groups_gen.GroupGang("stranger", 1, 1, 2, 1, 1, NOW + 1e6, "ig-none")
    assert reference.filter_driver(stranger) is None
    assert reference.filter_executor(stranger, cluster.names) is None
    reference.retire(stranger)


def rehearse(capsys, *extra, seed=4_000_000_019, trace="0"):
    code = run_mod.main(
        [*extra, "--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", trace, "--rehearse"]
    )
    captured = capsys.readouterr()
    return code, json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_rehearsal_is_correct_names_the_cpu_and_ends_with_exit_2(capsys):
    code, line, err = rehearse(capsys)
    assert code == run_mod.EXIT_REHEARSAL == 2
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values() if c["limit"] == 0)
    assert set(line["metrics"]) >= {"driver_filter_p50_ms", "driver_filter_p95_ms", "pods_per_s", "setup_s"}


def test_a_traced_rehearsal_reports_the_queue_ahead_of_the_block_mix(capsys):
    code, line, _ = rehearse(capsys, seed=9, trace="1")
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    # (3 x 26 + 2 x 13 + 9 + 7 + 5) / 8 pending drivers of its own group ahead of a driver
    assert line["metrics"]["queue_ahead_apps"]["value"] == pytest.approx(15.625)
    # the CPU's warm session lane builds no tensor and compiles nothing: left out, not 0;
    # the roofline's reader is not asked (one configured shape, which this cell has not)
    for absent in ("queue_kernel_roofline", "queue_kernel_device_ms", "device_idle_share"):
        assert absent not in line["metrics"], absent


def test_the_control_without_fifo_reads_incorrect(capsys):
    _, line, _ = rehearse(capsys, "--control", "fifo-off")
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] > 0


def test_a_reference_that_does_not_know_the_groups_reads_incorrect(capsys, monkeypatch):
    """The planted fault: the program is right, the reference packs every
    gang over all the nodes behind one queue (``fifo-gangs`` itself, which
    takes the same cluster), and the comparison has to see the difference."""
    real = run_mod.find_cell

    def group_blind(workload):
        found = real(workload)
        found["config"]["reference"]["model"] = "fifo-gangs"
        return found

    monkeypatch.setattr(run_mod, "find_cell", group_blind)
    _, line, _ = rehearse(capsys)
    assert line["correct"] is False
    wrong = line["checks"]["driver_answers_wrong"]["value"]
    assert wrong > 0.5 * line["attempted"]
    assert line["checks"]["reservations_wrong"]["value"] >= wrong
    monkeypatch.setattr(run_mod, "find_cell", real)
    _, line, _ = rehearse(capsys)
    assert line["correct"] is True


def test_the_gate_tag_max_reader_takes_the_running_totals_last_value():
    reader = plugins.load("readers", "gate_tag_max")
    context = {
        "requests": {
            "a": {"fifo_gate": {"requestCompiles": 0}},
            "b": {"fifo_gate": {"requestCompiles": 3}},
            "c": {"fifo_gate": {"requestCompiles": 9}},  # an executor's: not counted
            "d": {"fifo_gate": {}},  # a driver of a program without the tag
            "e": {"fifo_gate": {"requestCompiles": True}},  # no number
        },
        "kinds": {"a": "driver", "b": "driver", "c": "executor", "d": "driver", "e": "driver"},
    }
    assert reader.read(context, tag="requestCompiles") == 3.0
    assert reader.read(context, tag="absent") is None  # the parent: the metric is left out


def test_a_program_without_the_tensor_entry_is_refused_at_once(monkeypatch):
    from k8s_spark_scheduler_tpu.ops import fifo_solver

    adapter = plugins.load("objects", "instance-groups")
    monkeypatch.delattr(fifo_solver.TpuFifoSolver, "solve_tensor")
    with pytest.raises(SystemExit, match="no solve_tensor"):
        adapter._require_tensor_path()
