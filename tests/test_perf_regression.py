"""tools/perf_regression.py — the continuous perf-baseline gate.

The harness must (a) pass the committed trajectory as-is, (b) fail a
synthetically slowed headline or contention-lane metric, and (c)
tolerate the sparse early history (``parsed: null`` rounds, rounds
with no lanes).  These tests pin all three so the CI gate can be
trusted to mean "regressed", not "flaky".
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import perf_regression as pr  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HEADLINE_METRIC = "p99_filter_latency_10k_nodes_x_1k_apps_batched_repack"


def _write_round(tmp_path, n, parsed):
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(
        json.dumps({"n": n, "cmd": "python bench.py", "rc": 0, "tail": "", "parsed": parsed})
    )
    return path


def _artifact(headline_value=24.0, lanes=None):
    return {
        "headline": {"metric": HEADLINE_METRIC, "value": headline_value, "unit": "ms"},
        "lanes": lanes or {},
    }


# -- band fitting --------------------------------------------------------------


def test_fit_band_median_and_floor():
    band = pr.fit_band([20.0, 22.0, 21.0], floor=0.35, window=4)
    assert band["baseline"] == 21.0
    assert band["tolerance"] == 0.35  # spread/2 < floor
    assert band["threshold"] == pytest.approx(21.0 * 1.35)


def test_fit_band_widens_with_noisy_history():
    # relative spread 1.0 -> tolerance 0.5 beats the floor
    band = pr.fit_band([10.0, 30.0, 20.0], floor=0.35, window=4)
    assert band["tolerance"] == 0.5
    assert band["threshold"] == pytest.approx(20.0 * 1.5)


def test_fit_band_ignores_nulls_and_empty():
    assert pr.fit_band([], floor=0.35, window=4) is None
    assert pr.fit_band([None, 0, -3], floor=0.35, window=4) is None
    band = pr.fit_band([None, 12.0], floor=0.35, window=4)
    assert band["baseline"] == 12.0 and band["points"] == 1


def test_fit_band_windows_recent_history():
    # old 100s fall outside the window of 2; only [10, 12] count
    band = pr.fit_band([100.0, 100.0, 10.0, 12.0], floor=0.35, window=2)
    assert band["baseline"] == 12.0


# -- history loading -----------------------------------------------------------


def test_load_history_tolerates_sparse_rounds(tmp_path):
    # r01: flat headline dict (pre-lane format); r02: parsed null
    # (crashed tail parse); r03: full artifact with lanes
    _write_round(tmp_path, 1, {"metric": HEADLINE_METRIC, "value": 30.0, "unit": "ms"})
    _write_round(tmp_path, 2, None)
    _write_round(
        tmp_path,
        3,
        _artifact(25.0, lanes={"native-cpp cpu": {"p99_ms": 18.0}}),
    )
    (tmp_path / "BENCH_RESULT.json").write_text("{}")  # must not be picked up

    history = pr.load_history(str(tmp_path))
    assert [e["round"] for e in history] == [1, 3]
    assert history[0]["value"] == 30.0 and history[0]["lanes"] is None
    assert history[1]["lanes"]["native-cpp cpu"]["p99_ms"] == 18.0


def test_committed_trajectory_loads():
    history = pr.load_history(REPO)
    # the CPU rounds still committed (r03, r04, r06, r07) minus the
    # parsed-null one; rounds r01, r02, r05 left with PR 21
    assert len(history) >= 3
    # at least the latest committed round must carry the current metric
    assert any(e["metric"] == HEADLINE_METRIC for e in history)


# -- regression checks ---------------------------------------------------------


def _lane_history(tmp_path):
    lanes = {
        "native-cpp cpu": {"p99_ms": 18.0},
        "contention http": {
            "total_p99_ms": 24.0,
            "solve_p99_ms": 12.0,
            "serde_p99_ms": 4.0,
            "write_back_p99_ms": 2.0,
            "lock_hold_ms_p99": 1.0,
        },
    }
    _write_round(tmp_path, 6, _artifact(24.0, lanes=lanes))
    _write_round(tmp_path, 7, _artifact(25.0, lanes=lanes))
    return lanes


def test_run_checks_passes_unchanged_artifact(tmp_path):
    lanes = _lane_history(tmp_path)
    report = pr.run_checks(
        pr.load_history(str(tmp_path)),
        {"path": "x", "metric": HEADLINE_METRIC, "value": 24.5, "lanes": lanes},
    )
    assert report["pass"], report
    assert report["failures"] == 0
    statuses = {c["check"]: c["status"] for c in report["checks"]}
    assert statuses[f"headline:{HEADLINE_METRIC}"] == "pass"
    assert statuses["lane:contention http:solve_p99_ms"] == "pass"


def test_run_checks_fails_slowed_headline(tmp_path):
    lanes = _lane_history(tmp_path)
    report = pr.run_checks(
        pr.load_history(str(tmp_path)),
        {"path": "x", "metric": HEADLINE_METRIC, "value": 24.0 * 2.0, "lanes": lanes},
    )
    assert not report["pass"]
    failed = {c["check"] for c in report["checks"] if c["status"] == "fail"}
    assert f"headline:{HEADLINE_METRIC}" in failed


def test_run_checks_fails_slowed_contention_lane(tmp_path):
    lanes = _lane_history(tmp_path)
    slowed = json.loads(json.dumps(lanes))
    slowed["contention http"]["solve_p99_ms"] *= 3.0
    slowed["contention http"]["lock_hold_ms_p99"] *= 3.0
    report = pr.run_checks(
        pr.load_history(str(tmp_path)),
        {"path": "x", "metric": HEADLINE_METRIC, "value": 24.0, "lanes": slowed},
    )
    assert not report["pass"]
    failed = {c["check"] for c in report["checks"] if c["status"] == "fail"}
    assert "lane:contention http:solve_p99_ms" in failed
    assert "lane:contention http:lock_hold_ms_p99" in failed
    # the headline itself still passes — the lane gate is what caught it
    statuses = {c["check"]: c["status"] for c in report["checks"]}
    assert statuses[f"headline:{HEADLINE_METRIC}"] == "pass"


def test_run_checks_skips_without_history(tmp_path):
    report = pr.run_checks(
        [], {"path": "x", "metric": HEADLINE_METRIC, "value": 24.0, "lanes": {}}
    )
    assert report["pass"]  # nothing to regress against yet
    assert all(c["status"] == "skipped" for c in report["checks"])


def test_run_checks_tolerates_new_lane_first_appearance(tmp_path):
    """A lane the lane-bearing trajectory has never recorded (the round
    it first lands, e.g. "class-compressed cold") must be reported
    "new" — it passes the gate and becomes next round's baseline —
    while known lanes keep their bands and a no-lane-history round
    keeps plain "skipped"."""
    lanes = _lane_history(tmp_path)
    current = json.loads(json.dumps(lanes))
    current["class-compressed cold"] = {"p99_ms": 70.0}
    report = pr.run_checks(
        pr.load_history(str(tmp_path)),
        {"path": "x", "metric": HEADLINE_METRIC, "value": 24.0, "lanes": current},
    )
    assert report["pass"], report
    statuses = {c["check"]: c["status"] for c in report["checks"]}
    assert statuses["lane:class-compressed cold:p99_ms"] == "new"
    assert statuses["lane:native-cpp cpu:p99_ms"] == "pass"
    # a slowed KNOWN lane still fails in the same report shape
    current["native-cpp cpu"] = {"p99_ms": 18.0 * 3.0}
    report = pr.run_checks(
        pr.load_history(str(tmp_path)),
        {"path": "x", "metric": HEADLINE_METRIC, "value": 24.0, "lanes": current},
    )
    assert not report["pass"]
    statuses = {c["check"]: c["status"] for c in report["checks"]}
    assert statuses["lane:class-compressed cold:p99_ms"] == "new"
    assert statuses["lane:native-cpp cpu:p99_ms"] == "fail"


# -- CLI / committed repo state ------------------------------------------------


def test_cli_passes_on_committed_repo(tmp_path):
    out = tmp_path / "report.json"
    rc = pr.main(["--repo", REPO, "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["pass"] and report["checks"]


def test_cli_fails_on_synthetic_regression(tmp_path):
    # history: two healthy rounds; current: headline doubled
    _lane_history(tmp_path)
    current = tmp_path / "BENCH_RESULT.json"
    current.write_text(json.dumps(_artifact(24.0 * 2.0)))
    rc = pr.main(["--repo", str(tmp_path), "--json", str(tmp_path / "r.json")])
    assert rc == 1


def test_cli_missing_artifact(tmp_path):
    rc = pr.main(["--repo", str(tmp_path)])
    assert rc == 2
