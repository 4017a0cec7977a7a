"""The dynamic-allocation configuration's part of the yardstick: what its
generator deals, its plain reference against the program's host oracle,
its rehearsal, its control, the reader it brings, and three references
that the comparison has to call wrong."""

import http.client
import json
import os
import time
from itertools import islice

import pytest

import planted_dynalloc
import plugins
import run as run_mod
import stack as stack_mod
import traffic as traffic_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fifo10k-dynalloc.dynalloc-mix"

dyn_gen = plugins.load("generators", "dynamic-allocation")
stratified = plugins.load("generators", "stratified")


def config_of(rehearsal=False):
    with open(os.path.join(BENCH, "configs", "fifo10k-dynalloc.json")) as f:
        config = json.load(f)
    return run_mod.rehearsal_size(config) if rehearsal else config


@pytest.fixture(autouse=True)
def ports_the_system_picks(monkeypatch):
    """A process of tests makes many clients, and each starts its private
    sequence of source ports where the last one started: after a few
    hundred requests the next client finds its first 64 ports in TIME_WAIT
    (PERF.md 7.1 (e)).  Here the system picks, as in tier 1's tests."""

    def connect(self):
        conn = http.client.HTTPConnection("127.0.0.1", self._port, timeout=120)
        conn.connect()
        return conn

    monkeypatch.setattr(stack_mod.Client, "_connect", connect)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "dynalloc-mix.json")) as f:
        return json.load(f)


def test_every_block_of_every_seed_holds_132_max_36_min_and_96_extras(mix):
    config = config_of()
    mins_seen = set()
    for seed in [*range(17), 2**31 + 11, 3_000_000_017, 2**32 + 5]:  # 20 seeds
        plain = stratified.blocks(config, mix, seed, 0.0)
        for block in islice(dyn_gen.blocks(config, mix, seed, 0.0), 6):
            most = sum(g.executors for g in block)
            least = sum(g.min_executors for g in block)
            assert (len(block), most, least, most - least) == (8, 132, 36, 96)
            assert all(g.min_executors == -(-g.executors // 4) for g in block)
            assert sorted(g.min_executors for g in block) == list(range(1, 9))  # stratum k: min k + 1
            # stratified's own gangs, with a min each
            assert [(g.app_id, g.executors, g.executor_cpu, g.created) for g in block] == [
                (g.app_id, g.executors, g.executor_cpu, g.created) for g in next(plain)
            ]
            mins_seen.update((g.executors, g.min_executors) for g in block)
    assert {m for m, _ in mins_seen} == set(range(1, 32))  # every max of the range, over the seeds
    cluster = dyn_gen.make_cluster(config, 7, dyn_gen.BACKLOG_AGE_S)
    plain = stratified.make_cluster(config, 7, dyn_gen.BACKLOG_AGE_S)
    assert cluster.names == plain.names and (cluster.cpu == plain.cpu).all()
    assert [(g.app_id, g.executors) for g in cluster.backlog] == [(g.app_id, g.executors) for g in plain.backlog]
    assert all(g.min_executors == -(-g.executors // 4) for g in cluster.backlog)  # the queue carries the annotations too
    with pytest.raises(ValueError, match="no rule"):
        dyn_gen.make_cluster({**config, "gang": {**config["gang"], "min_executors": "max"}}, 7, 0.0)


def test_the_pods_carry_the_three_annotations_and_no_executor_count():
    from k8s_spark_scheduler_tpu.scheduler import labels as L
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import spark_resources

    objects = plugins.load("objects", "dynamic-allocation")
    gang = dyn_gen.DynGang("app", 13, 2, 4, 1, 1, 0.0, 4)
    pods = objects.pods(gang)
    assert len(pods) == 1 + 13  # create_gang creates the driver and max executors
    annotations = pods[0].meta.annotations
    assert annotations[L.DYNAMIC_ALLOCATION_ENABLED] == "true" and L.EXECUTOR_COUNT not in annotations
    resources = spark_resources(pods[0])
    assert (resources.min_executor_count, resources.max_executor_count) == (4, 13)
    replacement = objects.replacement(gang)
    assert replacement.name == "app-exec-14" and objects.executor_index(gang, replacement.name) == 14
    assert replacement.labels == pods[1].labels and replacement.meta.annotations == pods[1].meta.annotations


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_answers_as_the_host_oracle_does_at_rehearsal_size(seed, mix):
    """``references/fifo-gangs-dynalloc.py`` with ``policies/tightly-pack.py``
    against ``ops/packers.py``'s tightly-pack behind the extender's host
    FIFO loop and the Quantity path of ``_reschedule_executor`` (the
    mirror's lanes switched off), served over HTTP: one block through
    every verb of the mix."""
    import check

    config = config_of(rehearsal=True)
    objects = plugins.load("objects", config["objects"])
    cluster = dyn_gen.make_cluster(config, seed, time.time())
    stream = dyn_gen.blocks(config, mix, seed, cluster.base_ts)
    served = stack_mod.start_stack(cluster, objects, {"binpack_algo": "tightly-pack", "fifo": True})
    try:
        extender = served.scheduler.extender
        extender._fast_path_ok = False  # no tensor mirror: Quantity arithmetic for drivers and executors alike
        client = stack_mod.Client(served, cluster.names)
        rec = traffic_mod.run_block(client, objects, next(stream), mix["steps"])
        from k8s_spark_scheduler_tpu.metrics import names as mnames

        slow = served.scheduler.metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "slow"})
        fast = served.scheduler.metrics.get_counter(mnames.TPU_FASTPATH, {"path": "executor", "lane": "fast"})
    finally:
        served.stop()
    assert (fast, slow >= 96) == (0, True)
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
    code, line, err = rehearse(capsys)
    assert code == run_mod.EXIT_REHEARSAL == 2
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert line["correct"] is True and line["failed"] == 0
    blocks = line["window"]["blocks"]
    assert line["attempted"] == blocks * (8 + 132 + 8)  # drivers, executors, replacements
    assert set(line["checks"]) == {
        "answers_missing", "driver_answers_wrong", "reservations_wrong", "executor_answers_wrong",
        "soft_reservations_wrong", "replacement_answers_wrong", "api_reservations_wrong",
        "soft_reservations_left", "answers_compared",
    }
    assert all(c["value"] == 0 for c in line["checks"].values() if c["limit"] == 0)
    assert "check soft_reservations_wrong: 0 (limit 0)" in err
    assert set(line["metrics"]) == {"executor_filter_p50_ms", "pods_per_s", "setup_s"}


def test_a_traced_rehearsal_reports_the_new_metrics_and_the_share_is_96_of_132(capsys):
    code, line, _ = rehearse(capsys, seed=9, trace="1")
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    new = {
        "extra_executor_ms", "extra_executor_order_ms", "extra_executor_snapshot_ms", "executor_select_ms",
        "reservation_lookup_ms", "soft_bind_ms", "extra_executor_share", "quantity_reschedule_share",
        "compaction_ms",
    }
    assert new <= set(metrics)
    assert metrics["extra_executor_share"] == pytest.approx(100 * 96 / 132)
    assert metrics["quantity_reschedule_share"] == 0.0 and metrics["compiles_in_window"] == 0
    assert metrics["extra_executor_ms"] >= metrics["extra_executor_order_ms"] > 0
    assert metrics["executor_select_ms"] >= metrics["reservation_lookup_ms"] > 0
    # spark-mix's per-layer metrics are the cell's too; a drivers-cell metric is not
    assert {"executor_serde_ms", "lock_hold_ms", "mix_driver_filter_p50_ms", "executor_handler_ms"} <= set(metrics)
    assert "serde_ms" not in metrics and "device_idle_share" not in metrics


def test_the_control_without_fifo_reads_incorrect(capsys):
    _, line, _ = rehearse(capsys, "--control", "fifo-off")
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] > 0


@pytest.mark.parametrize(
    "fault,wrong",
    [
        ("soft-blind", "executor_answers_wrong"),
        ("drivers-at-max", "reservations_wrong"),
        ("never-compacts", "soft_reservations_wrong"),
    ],
)
def test_a_reference_with_a_planted_fault_reads_incorrect(capsys, fault, wrong):
    """The program is right; the reference forgets one rule of dynamic
    allocation, and the comparison has to see the difference, under the
    check that names it."""
    unplant = planted_dynalloc.plant(fault)
    try:
        _, line, _ = rehearse(capsys)
    finally:
        unplant()
    assert line["correct"] is False
    assert line["checks"][wrong]["value"] > 0
    if fault == "never-compacts":  # every other answer stands: only what the store holds after the loss differs
        assert line["checks"]["driver_answers_wrong"]["value"] == 0
        assert line["checks"]["executor_answers_wrong"]["value"] == 0
    _, line, _ = rehearse(capsys)
    assert line["correct"] is True
    with pytest.raises(SystemExit, match="no fault"):
        planted_dynalloc.plant("none")


def test_span_share_counts_requests_that_hold_the_span_and_is_silent_on_a_program_without_it():
    reader = plugins.load("readers", "span_share")
    spans = lambda *names: {"total": dict.fromkeys(names, 1.0)}  # noqa: E731
    context = {
        "requests": {
            "a": spans("predicate", "executor.select"),
            "b": spans("predicate", "executor.select", "executor.fast_reschedule"),
            "c": spans("predicate", "executor.select", "executor.fast_reschedule"),
            "d": spans("predicate", "executor.fast_reschedule"),  # a driver's: not counted
            "e": spans("predicate", "executor.fast_reschedule"),  # outside the window: no kind
        },
        "kinds": {"a": "executor", "b": "executor", "c": "executor", "d": "driver"},
    }
    assert reader.read(context, span="executor.fast_reschedule", kind="executor") == pytest.approx(200 / 3)
    assert reader.read(context, span="executor.quantity_reschedule", kind="executor", beside="executor.select") == 0.0
    assert reader.read(context, span="executor.fast_reschedule", kind="replacement_executor") is None
    # the parent commit: ``executor.fast_reschedule`` is there, ``executor.select`` and the Quantity path's span are not
    parent = {
        "requests": {"a": spans("predicate"), "b": spans("predicate", "executor.fast_reschedule")},
        "kinds": {"a": "executor", "b": "executor"},
    }
    assert reader.read(parent, span="executor.fast_reschedule", kind="executor", beside="executor.select") == 50.0
    assert reader.read(parent, span="executor.quantity_reschedule", kind="executor", beside="executor.select") is None
