"""The single-AZ minimal-fragmentation configuration with dynamic
allocation kept in one zone: its plain reference against the program's
host oracle, its rehearsal and control, the metrics it brings, its
roofline count, and two references that the comparison has to call
wrong."""

import http.client
import json
import os
import time

import pytest

import planted_single_az_dynalloc
import plugins
import run as run_mod
import single_az_minfrag_roofline
import stack as stack_mod
import traffic as traffic_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "fifo10k-single-az-minfrag-dynalloc"
CELL = CONFIG + ".dynalloc-mix"
NEW_METRICS = ("common_zone_ms", "app_attraction_ms", "zone_unmemoised_apps", "single_az_minfrag_kernel_roofline")


def config_of(rehearsal=False):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    return run_mod.rehearsal_size(config) if rehearsal else config


@pytest.fixture(autouse=True)
def ports_the_system_picks(monkeypatch):
    """Many clients in one process: the system picks the source ports, as
    in tier 1's tests (PERF.md 7.1 (e))."""

    def connect(self):
        conn = http.client.HTTPConnection("127.0.0.1", self._port, timeout=120)
        conn.connect()
        return conn

    monkeypatch.setattr(stack_mod.Client, "_connect", connect)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "dynalloc-mix.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_answers_as_the_host_oracle_does_at_rehearsal_size(seed, mix):
    """``references/fifo-gangs-single-az-dynalloc.py`` with
    ``policies/single-az-minimal-fragmentation.py`` against
    ``ops/packers.py``'s single-AZ minimal fragmentation behind the
    extender's host FIFO loop, and the Quantity path of
    ``_reschedule_executor`` (the zone filter, then
    ``_reschedule_executor_with_minimal_fragmentation``) with the tensor
    mirror switched off, served over HTTP: one block through every verb."""
    import check

    config = config_of(rehearsal=True)
    objects = plugins.load("objects", config["objects"])
    generator = plugins.load("generators", config["generator"])
    cluster = generator.make_cluster(config, seed, time.time())
    stream = generator.blocks(config, mix, seed, cluster.base_ts)
    install = {**config["install"], "binpack_algo": "single-az-minimal-fragmentation"}
    served = stack_mod.start_stack(cluster, objects, install)
    try:
        served.scheduler.extender._fast_path_ok = False  # Quantity arithmetic for drivers and executors alike
        client = stack_mod.Client(served, cluster.names)
        rec = traffic_mod.run_block(client, objects, next(stream), mix["steps"])
        from k8s_spark_scheduler_tpu.metrics import names as mnames

        lanes = {
            lane: served.scheduler.metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": lane})
            for lane in ("fast", "slow")
        }
    finally:
        served.stop()
    assert lanes["fast"] == 0 and lanes["slow"] >= 96
    reference = plugins.load("references", config["reference"]["model"]).Reference(
        cluster, config["reference"]["policy"]
    )
    checks = check.compare([rec], reference, cluster.names, mix["steps"])
    assert check.is_correct(checks), checks
    assert checks["answers_compared"]["value"] == 8 * 3 + 132 + 8 * 4


def rehearse(capsys, *extra, seed=4_000_000_019, trace="0", main=run_mod.main):
    code = main(
        [*extra, "--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", trace, "--rehearse"]
    )
    captured = capsys.readouterr()
    return code, json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_rehearsal_is_correct_names_the_cpu_and_ends_with_exit_2(capsys):
    code, line, _ = rehearse(capsys)
    assert code == run_mod.EXIT_REHEARSAL == 2
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == line["window"]["blocks"] * (8 + 132 + 8)
    assert all(c["value"] == 0 for c in line["checks"].values() if c["limit"] == 0)
    assert set(line["metrics"]) == {"executor_filter_p50_ms", "pods_per_s", "setup_s"}


def test_a_traced_rehearsal_reports_the_executor_metrics_it_brings(capsys):
    code, line, _ = rehearse(capsys, seed=9, trace="1")
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    assert metrics["extra_executor_order_ms"] >= metrics["app_attraction_ms"] > 0
    assert metrics["common_zone_ms"] > 0
    assert metrics["extra_executor_share"] == pytest.approx(100 * 96 / 132)
    assert metrics["quantity_reschedule_share"] == 0.0
    # the native lane serves the CPU's queue pass: the valve's tag and the trace are not there
    assert "zone_unmemoised_apps" not in metrics and "single_az_minfrag_kernel_roofline" not in metrics
    assert "driver_filter_p50_ms" not in metrics and "zone_resolve_ms" not in metrics
    assert "mix_zone_resolve_ms" not in metrics and "mix_queue_kernel_launches" not in metrics


def test_the_control_without_fifo_reads_incorrect(capsys):
    _, line, _ = rehearse(capsys, "--control", "fifo-off")
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] > 0


@pytest.mark.parametrize("fault", planted_single_az_dynalloc.FAULTS)
def test_a_reference_with_a_planted_fault_reads_incorrect(capsys, fault):
    """The program is right; the reference forgets where an extra executor
    may go, or how it is chosen there, and the comparison sees it in the
    executors' answers and in the soft store, never in a driver's."""
    unplant = planted_single_az_dynalloc.plant(fault)
    try:
        _, line, _ = rehearse(capsys)
    finally:
        unplant()
    assert line["correct"] is False
    assert line["checks"]["executor_answers_wrong"]["value"] > 0
    assert line["checks"]["soft_reservations_wrong"]["value"] > 0
    assert line["checks"]["driver_answers_wrong"]["value"] == 0
    _, line, _ = rehearse(capsys)
    assert line["correct"] is True
    with pytest.raises(SystemExit, match="no fault"):
        planted_single_az_dynalloc.plant("none")


def test_the_new_metrics_are_left_out_where_the_program_has_no_span_or_tag():
    """A program without ``executor.common_zone``, ``executor.app_attraction``
    and the ``zoneUnmemoised`` tag (the parent's) gives each reader nothing."""
    import metrics as metrics_mod

    context = {
        "requests": {
            "d": {"total": {"predicate": 20.0, "fifo_gate": 9.0}, "self": {}, "fifo_gate": {"zoneResolved": 3}},
            "e": {"total": {"predicate": 2.0, "executor.order": 0.5}, "self": {}, "fifo_gate": {}},
        },
        "kinds": {"d": "driver", "e": "executor"},
        "trace": {"op_seconds": {}, "client_calls": {"client.filter_driver": 1}},
        "config": config_of(),
        "device": {"kind": "TPU v5 lite"},
    }
    assert {name: metrics_mod.read(name, context) for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


def test_single_az_minfrag_ops_and_bytes():
    # min-frag's drain 23 per (app, node) (roofline.py), plus the single-AZ score and the
    # chosen zone's usage 20 (single_az_roofline.py)
    assert single_az_minfrag_roofline.DRAIN_OPS_PER_APP_NODE == 23
    assert single_az_minfrag_roofline.SCORE_OPS_PER_APP_NODE == 20
    assert single_az_minfrag_roofline.OPS_PER_APP_NODE == 43
    assert single_az_minfrag_roofline.queue_pass_ops(1024, 64) == 43 * 65_536
    # the score reads the same per-node inputs whatever packs inside the zone
    assert single_az_minfrag_roofline.queue_pass_bytes(1024, 64) == 38_656
    least = single_az_minfrag_roofline.least_seconds(10_240, 1_024, "TPU v5 lite")
    assert least["compute_s"] == pytest.approx(43 * 10_240 * 1_024 / 197e12)
    assert least["bound"].startswith("compute") and least["seconds"] == least["compute_s"]
    with pytest.raises(KeyError, match="no published peaks"):
        single_az_minfrag_roofline.least_seconds(1024, 64, "cpu")


def test_the_roofline_reader_divides_the_least_time_by_the_kernels_per_filter():
    reader = plugins.load("readers", "single_az_minfrag_kernel_roofline")
    config = config_of()
    context = {
        "trace": {
            "op_seconds": {"jit_solve/pallas_solve_queue_single_az.1": 0.2, "jit_solve/fusion.3": 1.0},
            "client_calls": {"client.filter_driver": 10},
        },
        "config": config,
        "device": {"kind": "TPU v5 lite"},
    }
    least = single_az_minfrag_roofline.least_seconds(10_240, 1_024, "TPU v5 lite")["seconds"]
    assert reader.read(context) == pytest.approx(100 * least / 0.02)
    context["trace"]["op_seconds"] = {"jit_solve/fusion.3": 1.0}
    assert reader.read(context) is None


def test_the_valves_mix_twins_read_the_drivers_of_a_mix():
    """``mix_zone_resolve_ms`` and ``mix_queue_kernel_launches`` read what
    ``zone_resolve_ms`` and ``queue_kernel_launches`` read, over the
    drivers of a cell that reports ``pods_per_s``; executors do not count."""
    import metrics as metrics_mod

    def driver(resolve_ms, launches):
        return {
            "total": {"predicate": 30.0, "fifo_gate.zone_resolve": resolve_ms}, "self": {},
            "fifo_gate": {"launches": launches, "zoneResolved": 4},
        }

    context = {
        "requests": {
            "d1": driver(2.0, 1), "d2": driver(6.0, 3), "d3": driver(5.0, 2),
            "e": {"total": {"predicate": 2.0, "fifo_gate.zone_resolve": 90.0}, "self": {},
                  "fifo_gate": {"launches": 9}},
        },
        "kinds": {"d1": "driver", "d2": "driver", "d3": "driver", "e": "executor"},
        "trace": {"op_seconds": {}, "client_calls": {"client.filter_driver": 3}},
        "config": config_of(),
        "device": {"kind": "TPU v5 lite"},
    }
    assert metrics_mod.read("mix_zone_resolve_ms", context) == 5.0
    assert metrics_mod.read("mix_queue_kernel_launches", context) == 2.0
    assert metrics_mod.read("mix_zone_resolve_ms", context) == metrics_mod.read(
        "zone_resolve_ms", context
    )
