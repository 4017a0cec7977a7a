"""The reduction from trace events to device metrics: a hand-made trace
whose answers can be read off, then the small trace recorded on the chip."""

import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"


def ev(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name, "start_ns": start_us * 1000, "dur_ns": dur_us * 1000}


HAND = [
    # the client: two driver Filters with a create before and a retire after each
    ev("/host:CPU", "client", "client.create", 0, 100),
    ev("/host:CPU", "client", "client.filter_driver", 100, 1000),
    ev("/host:CPU", "client", "client.retire", 1100, 100),
    ev("/host:CPU", "client", "client.create", 1300, 100),
    ev("/host:CPU", "client", "client.filter_driver", 1400, 1000),
    ev("/host:CPU", "client", "client.retire", 2400, 100),
    # the device: per Filter a module holding a kernel and an overlapping small op
    ev(DEV, "XLA Modules", "jit_pallas_solve_queue(123)", 400, 320),
    ev(DEV, "XLA Ops", "pallas_solve_queue.1", 400, 300),
    ev(DEV, "XLA Ops", "fusion.1", 650, 70),  # overlaps the kernel's last 50 us
    ev(DEV, "XLA Modules", "jit_pallas_solve_queue(123)", 1700, 320),
    ev(DEV, "XLA Ops", "pallas_solve_queue.1", 1700, 300),
    ev(DEV, "XLA Ops", "fusion.1", 2000, 20),
    ev(DEV, "XLA Ops", "stray.op", 5000, 50),  # after the window: not counted
    ev(DEV, "Steps", "0", 0, 2500),  # another line of the device plane: not an operation
]


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_hand_made_trace():
    r = trace_reduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(2500e-6)
    # busy: [400,720] and [1700,2020] -> 640 us; the overlap is counted once
    assert r["busy_s"] == pytest.approx(640e-6)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.744)
    assert r["op_seconds"] == pytest.approx(
        {"jit_pallas_solve_queue/pallas_solve_queue.1": 600e-6, "jit_pallas_solve_queue/fusion.1": 90e-6}
    )
    assert r["client_calls"] == {"client.create": 2, "client.filter_driver": 2, "client.retire": 2}
    # idle gaps by the client step under way; 200 us between retire and create are nobody's
    assert r["idle_gaps"] == pytest.approx({
        "client.create": 200e-6, "client.filter_driver": (300 + 380 + 300 + 380) * 1e-6,
        "client.retire": 200e-6, "client.other": 100e-6,
    })
    assert sum(r["idle_gaps"].values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert trace_reduce.top(r["op_seconds"], 1) == [["jit_pallas_solve_queue/pallas_solve_queue.1", 600e-6]]


def test_a_trace_without_client_steps_has_no_window():
    with pytest.raises(ValueError, match="no client"):
        trace_reduce.reduce([e for e in HAND if e["plane"] == DEV])


def test_recorded_chip_trace():
    """Two driver Filters of fifo10k-tightly.drivers cut from a trace
    recorded on a TPU v5e (see data/README in PERF.md's findings)."""
    path = os.path.join(DATA, "small_trace.json")
    with open(path) as f:
        recorded = json.load(f)
    r = trace_reduce.reduce(recorded["events"])
    want = recorded["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["client_calls"]["client.filter_driver"] == want["filter_driver_calls"]
    kernel = sum(s for n, s in r["op_seconds"].items() if "pallas_solve_queue" in n.split("/")[-1])
    assert kernel == pytest.approx(want["queue_kernel_s"])
    assert sum(r["idle_gaps"].values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
