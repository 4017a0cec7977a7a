"""run.py end to end on the CPU: what it refuses, what its rehearsal
prints, the control that has to read incorrect, and the timed path broken
underneath a run."""

import json
import os
import subprocess
import sys

import pytest

import run as run_mod
import stack as stack_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = ["fifo10k-tightly.drivers", "fifo10k-minfrag.drivers", "fifo10k-tightly.spark-mix"]


def rehearse(capsys, workload, *extra, seed=4_000_000_019):
    code = run_mod.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "1", "--trace", "0", "--rehearse", *extra]
    )
    captured = capsys.readouterr()
    return code, json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_a_cpu_backend_is_refused_with_no_result(capsys):
    with pytest.raises(SystemExit) as exit_info:
        run_mod.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert exit_info.value.code == 1
    assert "no TPU" in captured.err and '"correct"' not in captured.out


def test_alone_with_the_benchmark_files_it_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120, stdin=subprocess.DEVNULL,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert out.returncode != 0
    assert "not importable" in out.stderr and '"correct"' not in out.stdout


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_names_the_cpu_and_is_no_result(capsys, workload):
    code, line, err = rehearse(capsys, workload)
    assert code == run_mod.EXIT_REHEARSAL != 0
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] == 0 for c in line["checks"].values() if c["limit"] == 0)
    assert "check driver_answers_wrong: 0 (limit 0)" in err
    assert set(line["metrics"]) >= {"pods_per_s", "setup_s"}


def test_a_traced_rehearsal_reports_the_cells_per_layer_metrics(capsys):
    code = run_mod.main(
        ["--workload", CELLS[2], "--seed", "9", "--seconds", "1", "--trace", "1", "--rehearse"]
    )
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    # the spans, the client and the counters are read; no device ran, so the
    # trace's readers found nothing and their metrics are left out, not 0
    assert {"executor_serde_ms", "lock_hold_ms", "mix_driver_filter_p50_ms",
            "client_outside_filter_share", "compiles_in_window"} <= set(line["metrics"])
    assert "device_idle_share" not in line["metrics"]
    assert "serde_ms" not in line["metrics"]  # a drivers-cell metric
    assert line["device"]["window_s"] > 0 and "breakdown" in line


@pytest.mark.parametrize("workload", CELLS[:2])
def test_the_control_without_fifo_reads_incorrect(capsys, workload):
    """The program with its own fifo=false path in the program's place:
    the new driver is packed as if no earlier driver were pending."""
    _, line, _ = rehearse(capsys, workload, "--control", "fifo-off")
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] > 0


def _break(monkeypatch, wrap):
    real = stack_mod.start_stack

    def started(*args, **kwargs):
        stack = real(*args, **kwargs)
        wrap(stack)
        return stack

    monkeypatch.setattr(stack_mod, "start_stack", started)


def test_an_answer_altered_where_it_is_produced_reads_incorrect(capsys, monkeypatch):
    """The driver's node swapped for another just before it is answered."""

    def wrap(stack):
        extender = stack.scheduler.extender
        real = extender.predicate

        def altered(args):
            result = real(args)
            if result.node_names and args.pod.name.endswith("-driver"):
                other = next(n for n in args.node_names if n != result.node_names[0])
                result.node_names = [other]
            return result

        extender.predicate = altered

    _break(monkeypatch, wrap)
    _, line, _ = rehearse(capsys, CELLS[0])
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] == line["window"]["gangs"]
    assert line["checks"]["reservations_wrong"]["value"] == 0  # only the answer was altered


def test_a_reservation_altered_where_it_is_written_reads_incorrect(capsys, monkeypatch):
    """Every executor slot written onto the driver's node: the answer is
    right, what was acknowledged is not, and the executors land wrong."""

    def wrap(stack):
        manager = stack.scheduler.extender._rrm
        real = manager.create_reservations

        def altered(driver, resources, driver_node, executor_nodes):
            return real(driver, resources, driver_node, [driver_node] * len(executor_nodes))

        manager.create_reservations = altered

    _break(monkeypatch, wrap)
    _, line, _ = rehearse(capsys, CELLS[2])
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] == 0
    assert line["checks"]["reservations_wrong"]["value"] > 0
    assert line["checks"]["api_reservations_wrong"]["value"] > 0
    assert line["checks"]["executor_answers_wrong"]["value"] > 0


def test_a_write_back_that_stores_something_else_reads_incorrect(capsys, monkeypatch):
    """The scheduler answers right; what its write-back stores in the API
    server, the durable copy, has every executor slot on the driver's node."""

    def wrap(stack):
        real = stack.api.create

        def altered(obj):
            if obj.KIND == "ResourceReservation":
                obj = obj.deepcopy()
                for slot in obj.spec.reservations.values():
                    slot.node = obj.spec.reservations["driver"].node
            return real(obj)

        stack.api.create = altered

    _break(monkeypatch, wrap)
    _, line, _ = rehearse(capsys, CELLS[0])
    assert line["correct"] is False
    assert line["checks"]["driver_answers_wrong"]["value"] == 0
    assert line["checks"]["api_reservations_wrong"]["value"] > 0


def test_an_unknown_verb_names_the_file_to_add(monkeypatch):
    real = run_mod.find_cell

    def found(workload):
        cell = real(workload)
        cell["traffic"]["steps"] = ["create_driver", "cordon_node"]
        return cell

    monkeypatch.setattr(run_mod, "find_cell", found)
    with pytest.raises(ValueError, match=r"no traffic/steps/cordon_node\.py .* add it as a new file"):
        run_mod.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0", "--rehearse"])


def test_pods_are_counted_from_the_answers_not_from_what_was_meant():
    import traffic as traffic_mod
    from blocks import Gang

    refused = traffic_mod.GangRecord(Gang("a", 5, 1, 2, 1, 1, 0.0), {"driver": [(0.01, "t1", b'{"NodeNames": null}')]})
    granted = traffic_mod.GangRecord(
        Gang("b", 2, 1, 2, 1, 1, 1.0),
        {"driver": [(0.01, "t2", b'{"NodeNames": ["n1"]}')], "executor": [(0.005, "t3", b"{}"), (0.005, "t4", b"{}")]},
    )
    block = traffic_mod.BlockRecord(0.0, 1.0, [refused, granted])
    assert block.pods == 4  # 2 drivers + the 2 executors that were asked about, not 2 + 7
    import plugins
    rate = plugins.load("readers", "pods_per_s").read({"window": [block], "blocks_s": 1.0})
    assert rate == 4.0


def test_a_queue_pass_off_the_stated_lane_is_no_measurement(capsys, monkeypatch):
    real = run_mod.find_cell

    def found(workload):
        cell = real(workload)
        cell["config"]["rehearsal"]["expect_lane"] = "pallas"  # the CPU serves from the native lane
        return cell

    monkeypatch.setattr(run_mod, "find_cell", found)
    code = run_mod.main(
        ["--workload", CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0", "--rehearse"]
    )
    captured = capsys.readouterr()
    assert code == 1 and '"correct"' not in captured.out
    assert "not a measurement of this system" in captured.err
