"""The single-AZ configuration's part of the yardstick: its plain
reference against the program's host oracle, its roofline count against
a hand count, the faults the comparison has to catch, and its rehearsal."""

import json
import os
import time

import pytest

import planted_fault
import plugins
import run as run_mod
import single_az_roofline
import stack as stack_mod
import traffic as traffic_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fifo10k-single-az.drivers"


def config_of():
    with open(os.path.join(BENCH, "configs", "fifo10k-single-az.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_answers_as_the_host_oracle_does_at_1024_by_60(seed):
    """``references/fifo-gangs-single-az.py`` with
    ``policies/single-az-tightly-pack.py`` against
    ``ops/packers.single_az_tightly_pack`` behind the extender's host FIFO
    loop, served over HTTP: one block of the ``drivers`` mix."""
    config = config_of()
    config["cluster"].update(nodes=1024, backlog=60)
    with open(os.path.join(BENCH, "traffic", "drivers.json")) as f:
        mix = json.load(f)
    generator = plugins.load("generators", config["generator"])
    objects = plugins.load("objects", config["objects"])
    cluster = generator.make_cluster(config, seed, time.time())
    stream = generator.blocks(config, mix, seed, cluster.base_ts)
    reference = plugins.load("references", config["reference"]["model"]).Reference(
        cluster, config["reference"]["policy"]
    )
    oracle = stack_mod.start_stack(
        cluster, objects, {"binpack_algo": "single-az-tightly-pack", "fifo": True}
    )
    try:
        client = stack_mod.Client(oracle, cluster.names)
        rec = traffic_mod.run_block(client, objects, next(stream), mix["steps"])
    finally:
        oracle.stop()
    zone_of = dict(zip(cluster.names, cluster.zone))
    zones_taken = set()
    for g in rec.gangs:
        grant = reference.filter_driver(g.gang)
        want = (grant.driver_node, grant.executor_nodes) if grant else None
        assert g.read["reservation"] == want, g.gang
        assert g.read["api_reservation"] == want, g.gang
        assert grant is not None
        zones = {zone_of[n] for n in (grant.driver_node, *grant.executor_nodes)}
        assert len(zones) == 1  # every pod of the gang in one zone
        zones_taken |= zones
        reference.retire(g.gang)
    assert len(rec.gangs) == 8


def test_single_az_ops_and_bytes_at_1024_by_64():
    # tightly-pack's 15 per (app, node), once per node (the zones partition them), plus the
    # score: newly reserved 4, reserved 4, whole cores 2, ratios 2, max 1, quantisation 3,
    # weighted sum 3, the chosen zone's usage 1 = 20
    assert single_az_roofline.OPS_PER_APP_NODE == 15 + 20 == 35
    assert single_az_roofline.queue_pass_ops(1024, 64) == 35 * 65_536 == 2_293_760
    # read 3 + 4 int32 per node and 5 per app, write 2 per node and 1 + 1 per app
    assert single_az_roofline.queue_pass_bytes(1024, 64) == 4 * (7 * 1024 + 5 * 64) + 4 * (2 * 1024 + 2 * 64) == 38_656
    least = single_az_roofline.least_seconds(10_240, 1_024, "TPU v5 lite")
    assert least["compute_s"] == pytest.approx(35 * 10_240 * 1_024 / 197e12)
    assert least["bound"].startswith("compute") and least["seconds"] == least["compute_s"]
    with pytest.raises(KeyError, match="no published peaks"):
        single_az_roofline.least_seconds(1024, 64, "cpu")


def rehearse(capsys, *extra, seed=4_000_000_019, main=run_mod.main):
    code = main(
        [*extra, "--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", "0", "--rehearse"]
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


def test_a_traced_rehearsal_reports_the_new_span_metrics(capsys):
    code = run_mod.main(
        ["--workload", CELL, "--seed", "9", "--seconds", "1", "--trace", "1", "--rehearse"]
    )
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    # the native lane serves the CPU: the current driver's exact choice is there, the device
    # pass's valve and the trace's readers find nothing and are left out, not 0
    assert "zone_choice_ms" in line["metrics"] and "binpack_ms" in line["metrics"]
    for absent in ("zone_resolve_ms", "zone_resolved_apps", "queue_kernel_launches",
                   "single_az_kernel_roofline", "queue_kernel_roofline", "queue_kernel_device_ms"):
        assert absent not in line["metrics"], absent


def test_the_control_without_fifo_reads_incorrect(capsys):
    _, line, _ = rehearse(capsys, "--control", "fifo-off")
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] > 0


def test_a_reference_that_takes_the_first_feasible_zone_reads_incorrect(capsys):
    """The planted fault: the program is right, the reference is given
    the wrong rule, and the comparison has to see the difference."""
    unplant = planted_fault.plant("first-feasible-zone")
    try:
        _, line, _ = rehearse(capsys)
    finally:
        unplant()
    assert line["correct"] is False
    wrong = line["checks"]["driver_answers_wrong"]["value"]
    assert 0 < wrong  # wherever the best zone is not the first
    assert line["checks"]["reservations_wrong"]["value"] >= wrong
    # and with the rule put back the same run is correct again
    _, line, _ = rehearse(capsys)
    assert line["correct"] is True


def test_the_gate_tag_reader_takes_the_mean_over_the_windows_drivers():
    reader = plugins.load("readers", "gate_tag_mean")
    context = {
        "requests": {
            "a": {"fifo_gate": {"zoneResolved": 3, "launches": 1}},
            "b": {"fifo_gate": {"zoneResolved": 0, "launches": 2}},
            "c": {"fifo_gate": {"zoneResolved": 9}},  # an executor's: not counted
            "d": {"fifo_gate": {}},  # a driver on a lane without the tag
        },
        "kinds": {"a": "driver", "b": "driver", "c": "executor", "d": "driver"},
    }
    assert reader.read(context, tag="zoneResolved") == 1.5
    assert reader.read(context, tag="launches") == 1.5
    assert reader.read(context, tag="absent") is None


def test_a_program_without_the_tensor_entry_is_refused_at_once(monkeypatch):
    from k8s_spark_scheduler_tpu.ops import fifo_solver

    monkeypatch.delattr(fifo_solver.TpuSingleAzFifoSolver, "solve_tensor")
    adapter = plugins.load("objects", "static-allocation-tensor-path")
    with pytest.raises(SystemExit, match="no solve_tensor"):
        adapter._require_tensor_path()
