"""The per-layer metrics that read the program's spans: the reader of
background traces on a made-up context, every metric file against its
reader, and a traced rehearsal of a ``drivers`` cell with one scan of the
marker inside its window."""

import glob
import json
import os

import pytest

import plugins
import run as run_mod
import stack as stack_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPAN_METRICS = {
    "lock_unnamed_ms", "queue_assemble_ms", "scale_problem_ms", "upload_ms", "device_wait_ms",
    "readback_ms", "driver_finish_ms", "handler_ms", "executor_handler_ms", "response_write_ms",
    "marker_scan_s", "marker_solve_s", "marker_host_s",
}


def trace(**total_ms):
    return {"total": total_ms, "self": dict(total_ms), "fifo_gate": {}}


def scan(seconds, solve, metadata, mark):
    return trace(**{"unschedulable.scan": seconds * 1e3, "scan.solve": solve * 1e3,
                    "scan.metadata": metadata * 1e3, "scan.mark": mark * 1e3})


def test_background_span_s_reads_the_traces_that_are_no_request():
    reader = plugins.load("readers", "background_span_s")
    context = {
        "kinds": {"req-1": "driver", "req-2": "executor"},
        "requests": {
            "req-1": trace(**{"http.request": 30.0, "predicate": 28.0}),
            # a request's trace is ignored even if it held the span
            "req-2": trace(**{"http.request": 7.0, "unschedulable.scan": 99_000.0}),
            "warm-up": trace(**{"http.request": 40.0}),  # no request of the window, no scan either
            "scan-1": scan(12.0, 9.0, 0.5, 1.5),
            "scan-2": scan(14.0, 10.0, 0.7, 1.7),
            "scan-3": scan(19.0, 15.0, 0.6, 1.6),
        },
    }
    root = "unschedulable.scan"
    assert reader.read(context, root=root, spans=[root]) == pytest.approx(14.0)
    assert reader.read(context, root=root, spans=["scan.solve"]) == pytest.approx(10.0)
    assert reader.read(context, root=root, spans=["scan.metadata", "scan.mark"]) == pytest.approx(2.2)
    assert reader.read(context, root=root, spans=["scan.sleep"]) is None  # a span the program lacks
    for key in ("scan-1", "scan-2", "scan-3"):
        del context["requests"][key]
    assert reader.read(context, root=root, spans=[root]) is None  # no scan in the window


def test_every_metric_file_resolves_to_a_reader_and_is_listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    files = sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json")))
    assert {os.path.basename(p)[:-5] for p in files} == set(listed)
    for path in files:
        with open(path) as f:
            spec = json.load(f)
        assert spec["name"] == os.path.basename(path)[:-5]
        assert callable(plugins.load("readers", spec["reader"]).read), spec["name"]
    cells = {w["name"] for w in bench["workloads"]}
    for name in SPAN_METRICS:
        entry = listed[name]
        assert entry["source"] == "program_span" and set(entry["workloads"]) <= cells
        assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}


def test_a_traced_rehearsal_of_a_drivers_cell_reports_the_span_metrics_its_lane_can_have(
    capsys, monkeypatch
):
    """The CPU's lane is the warm native session: no device, so the
    ``device.*`` metrics and ``scale_problem_ms`` (the cold path's spans)
    are left out, not 0.  The marker's first scan comes a minute after
    start-up; here one is run inside the window."""
    started = {}
    start_stack = stack_mod.start_stack
    run_window = run_mod.traffic_mod.run_window

    def keep_the_stack(*args, **kwargs):
        started["stack"] = start_stack(*args, **kwargs)
        return started["stack"]

    def window_with_one_scan(*args, **kwargs):
        window = run_window(*args, **kwargs)
        started["stack"].scheduler.unschedulable_marker.scan_for_unschedulable_pods()
        return window

    monkeypatch.setattr(stack_mod, "start_stack", keep_the_stack)
    monkeypatch.setattr(run_mod.traffic_mod, "run_window", window_with_one_scan)
    code = run_mod.main(
        ["--workload", "fifo10k-tightly.drivers", "--seed", "11", "--seconds", "1", "--trace", "1", "--rehearse"]
    )
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == run_mod.EXIT_REHEARSAL and line["correct"] is True
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    assert SPAN_METRICS & set(metrics) == {
        "lock_unnamed_ms", "queue_assemble_ms", "driver_finish_ms", "handler_ms", "response_write_ms",
        "marker_scan_s", "marker_solve_s", "marker_host_s",
    }
    assert all(metrics[name] > 0 for name in SPAN_METRICS & set(metrics))
    assert metrics["lock_unnamed_ms"] < metrics["lock_hold_ms"] <= metrics["handler_ms"]
    assert metrics["marker_solve_s"] + metrics["marker_host_s"] <= metrics["marker_scan_s"]
    assert metrics["marker_scan_s"] * 1e3 > metrics["handler_ms"]  # seconds, not ms
