"""The metrics of the two modes of a driver's Filter
(``readers/driver_mode_share.py``, ``readers/driver_mode_ms.py``) on small
hand-made contexts: both modes present, no slow request, and a program
whose spans do not record the runtime."""

import json
import os

import pytest

import metrics as metrics_mod
from traffic import BlockRecord, GangRecord

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = [
    "fifo10k-tightly.drivers", "fifo10k-minfrag.drivers", "fifo10k-single-az.drivers", "fifo10k-groups.drivers",
]
SCAN = "unschedulable.scan"


def driver(client_ms, gate_ms, cpu_ms, wait_ms, lock_ms, bg=None, gc_ms=None):
    tags = {"lane": "pallas", "cpuMs": cpu_ms}
    if bg:
        tags["bg"] = bg
    if gc_ms is not None:
        tags.update(gcMs=gc_ms, gcRuns=1)
    total = {"http.request": lock_ms + 2.0, "predicate": lock_ms, "fifo_gate": gate_ms}
    if wait_ms is not None:
        total["device.wait"] = wait_ms
    return client_ms, {"total": total, "self": dict(total), "fifo_gate": tags}


def context_of(drivers, executors=()):
    """One block; ``drivers`` and ``executors`` as ``driver(...)`` gives them."""
    requests, kinds, answers = {}, {}, {"driver": [], "executor": []}
    for kind, found in (("driver", drivers), ("executor", executors)):
        for i, (client_ms, req) in enumerate(found):
            trace_id = f"{kind}-{i}"
            requests[trace_id] = req
            kinds[trace_id] = kind
            answers[kind].append((client_ms / 1e3, trace_id, b"{}"))
    requests["scan-1"] = {"total": {SCAN: 3000.0}, "self": {SCAN: 3000.0}, "fifo_gate": {}}
    return {"window": [BlockRecord(0.0, 1.0, [GangRecord(None, answers)])], "requests": requests, "kinds": kinds}


# ten fast drivers around 20 ms, one of them beside a write-back; two slow ones, one in the scan and
# one beside a write-back and a collection; an executor that is slower than any of them and counts nowhere
FAST = [driver(20.0 + i, 8.0, 2.5, 5.0, 15.0 + i, bg="lifecycle.drain" if i < 3 else None) for i in range(9)] + [
    driver(24.0, 8.2, 2.6, 5.0, 18.0, bg="writeback")
]
SLOW = [
    driver(70.0, 50.0, 2.7, 6.0, 64.0, bg=f"capacity.sample,lifecycle.drain,{SCAN}"),
    driver(90.0, 60.0, 13.0, 7.0, 80.0, bg="lifecycle.drain,writeback", gc_ms=11.0),
]
BOTH = context_of(FAST + SLOW, executors=[driver(500.0, 1.0, 1.0, None, 400.0, bg=SCAN)])

EXPECTED_BOTH = {
    "slow_mode_share": 100.0 * 2 / 12,
    "slow_mode_filter_ms": 80.0,
    "scan_phase_share": 100.0 * 1 / 12,
    "slow_mode_in_scan_share": 50.0,
    "slow_mode_in_writeback_share": 50.0,
    "writeback_overlap_share": 100.0 * 2 / 12,
    # means: a thread clock that ticks makes one span's cpuMs a sample
    "gate_offcpu_ms": (9 * 0.5 + (8.2 - 2.6 - 5.0) + (50.0 - 2.7 - 6.0) + (60.0 - 13.0 - 7.0)) / 12,
    "slow_mode_gate_offcpu_ms": ((50.0 - 2.7 - 6.0) + (60.0 - 13.0 - 7.0)) / 2,
    "gate_cpu_ms": (9 * 2.5 + 2.6 + 2.7 + 13.0) / 12,
    "slow_mode_gate_cpu_ms": (2.7 + 13.0) / 2,
    "slow_mode_in_capacity_sample_share": 50.0,
    "capacity_sample_overlap_share": 100.0 * 1 / 12,
    "slow_mode_in_lifecycle_drain_share": 100.0,
    "lifecycle_drain_overlap_share": 100.0 * 5 / 12,
    "slow_mode_device_wait_ms": 6.5,
    "slow_mode_lock_hold_ms": 72.0,
    "gate_gc_ms": 11.0 / 12,
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED_BOTH))
def test_a_metric_of_the_two_modes_on_a_window_that_holds_both(name):
    assert metrics_mod.read(name, BOTH) == pytest.approx(EXPECTED_BOTH[name])


@pytest.mark.parametrize("name", sorted(EXPECTED_BOTH))
def test_a_window_with_no_slow_driver_reads_shares_of_zero_and_leaves_the_slow_modes_times_out(name):
    value = metrics_mod.read(name, context_of(FAST))
    if name in ("slow_mode_share", "scan_phase_share", "capacity_sample_overlap_share"):
        assert value == 0.0  # a reading: the program could have said otherwise
    elif name == "lifecycle_drain_overlap_share":
        assert value == pytest.approx(30.0)  # the three fast ones beside a drain
    elif name.startswith("slow_mode_"):
        assert value is None  # nothing to take a median or a share over
    else:
        assert value is not None and value >= 0.0


@pytest.mark.parametrize("name", sorted(EXPECTED_BOTH))
def test_a_program_whose_spans_do_not_record_the_runtime_leaves_every_metric_out(name):
    """The parent of the PR that added the tags: no ``cpuMs`` on any gate."""
    before = [
        (ms, {**req, "fifo_gate": {"lane": "pallas"}}) for ms, req in FAST + SLOW
    ]
    assert metrics_mod.read(name, context_of(before)) is None
    assert metrics_mod.read(name, context_of([])) is None  # no driver was traced


def test_a_lane_with_no_device_leaves_the_device_wait_out_and_reads_the_gate_without_it():
    native = context_of(
        [driver(20.0 + i, 8.0, 7.5, None, 15.0) for i in range(10)] + [driver(90.0, 60.0, 9.0, None, 80.0)]
    )
    assert metrics_mod.read("slow_mode_device_wait_ms", native) is None
    assert metrics_mod.read("gate_offcpu_ms", native) == pytest.approx((10 * 0.5 + 51.0) / 11)
    assert metrics_mod.read("slow_mode_gate_offcpu_ms", native) == pytest.approx(51.0)


def test_the_new_metrics_are_listed_for_the_four_drivers_cells_and_reported_there_only():
    listed = {m["name"]: m for m in bench()["per_layer"]}
    for name in EXPECTED_BOTH:
        entry = listed[name]
        assert entry["workloads"] == CELLS and entry["moves"] == "driver_filter_p95_ms"
        assert entry["layer"] == "host runtime" and entry["source"] == "program_span"
    reported = metrics_mod.per_layer(
        {**bench(), "per_layer": [listed[name] for name in EXPECTED_BOTH]}, "fifo10k-tightly.drivers", BOTH
    )
    assert set(reported) == set(EXPECTED_BOTH)
    assert not metrics_mod.per_layer(
        {**bench(), "per_layer": [listed[name] for name in EXPECTED_BOTH]}, "fifo10k-tightly.spark-mix", BOTH
    )
