"""The driver-facing bench contract, pinned in CI: ``python bench.py``
must end its stdout with exactly one parseable headline JSON line — even
with stderr discarded entirely — and must write the durable all-lane
artifact to disk.  Smoke shapes must never touch the canonical
BENCH_RESULT.json.  This is the CPU run (``JAX_PLATFORMS=cpu``): every
result it prints has to say so."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_final_line_is_the_headline(tmp_path):
    env = dict(os.environ)
    env.update(
        BENCH_NODES="120", BENCH_APPS="12", BENCH_CHAIN="2",
        BENCH_ROUNDS="2", BENCH_E2E_PROBES="2",
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    smoke = os.path.join(REPO, "BENCH_RESULT_smoke.json")
    if os.path.exists(smoke):
        os.unlink(smoke)
    canonical_mtime = (
        os.path.getmtime(os.path.join(REPO, "BENCH_RESULT.json"))
        if os.path.exists(os.path.join(REPO, "BENCH_RESULT.json"))
        else None
    )
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540,
        stdin=subprocess.DEVNULL,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert lines, "bench printed nothing to stdout"
    headline = json.loads(lines[-1])  # the FINAL line is the headline
    assert headline["unit"] == "ms"
    assert headline["value"] > 0
    # vs_baseline is the ratio to the 50ms north-star target (computed
    # from the unrounded p99, so compare with a relative tolerance that
    # absorbs the 3-decimal rounding of `value` at smoke-shape latencies)
    expected = 50.0 / max(headline["value"], 1e-3)
    assert abs(headline["vs_baseline"] - expected) / expected < 0.05
    assert headline["backend"] in ("native-cpp", "xla-scan")
    assert isinstance(headline["load_ok"], bool)
    # a CPU run says so on every result: the headline, the artifact
    assert headline["platform"] == "cpu"
    assert headline["device_kind"] and headline["device_count"] >= 1

    # the cache went where the standard variable put it, nowhere else
    assert os.listdir(tmp_path / "cache")

    # durable artifact on disk, at the SMOKE path for a smoke shape
    with open(smoke) as f:
        artifact = json.load(f)
    assert artifact["headline"] == headline
    assert artifact["device"]["platform"] == "cpu"
    assert artifact["lanes"], "no lanes recorded"
    assert "fingerprint" in artifact["host"]
    assert artifact["shape"] == {"nodes": 120, "apps": 12, "chain": 2, "rounds": 2}

    # preemption what-if contract (ISSUE 14): the policy engine's victim
    # validation is the solver's admission rule on avail + freed; it is
    # pure numpy (the no-warm-session fallback), so the lane is
    # unconditional and its per-call p50 is pinned in the artifact
    pw = artifact["lanes"].get("preemption-whatif cpu")
    assert pw is not None, "no preemption-whatif lane"
    assert pw["gangs"] == 16
    assert pw["whatif_p50_ms"] > 0
    assert pw["rounds"] >= 16  # per-call samples: gangs x reps

    # class-compressed contract (ISSUE 20): when the native class solver
    # exists, the bench must pin the class lane at 10× the main shape
    # (100k × 10k at canonical), prove byte-identity to the row-level
    # solve every run, and carry the compression evidence the speedup
    # claim rests on.  tools/perf_regression.py band-gates the lane.
    from k8s_spark_scheduler_tpu.native.fifo import (
        native_classes_available,
    )

    if native_classes_available():
        cc = artifact["lanes"].get("class-compressed cold")
        assert cc is not None, "no class-compressed lane"
        assert cc["nodes"] == 1200 and cc["apps"] == 120  # 10x smoke shape
        assert cc["parity"] == "byte-identical"
        assert cc["p50_ms"] > 0 and cc["row_p50_ms"] > 0
        assert cc["classes_initial"] >= 1
        assert cc["compression_ratio"] >= 1.0
        assert cc["speedup_p50"] > 0
        warm = artifact["lanes"].get("class-compressed warm")
        assert warm is not None and warm["p50_ms"] >= 0

    # a metric named p99_filter_latency… must be the
    # request-level number measured at the HTTP boundary — pinned to the
    # config5-e2e lane's own stats, with its sample count carried in the
    # headline.  A solver microbench falls back to the distinct
    # p99_queue_solve… name, so the two can never be confused.
    lane = artifact["lanes"].get("config5-e2e http")
    if headline["metric"].startswith("p99_filter_latency"):
        assert headline["measured_at"] == "http"
        assert lane is not None
        assert headline["value"] == lane["p99_ms"]
        assert headline["samples"] == lane["rounds"] >= 2
        assert headline["backend"] == lane["backend"]
        assert headline["fallbacks"] == lane["fallbacks"] == 0
        assert "solver_p99_ms" in headline
        # delta-solve annotations (PR 5): when the native session lane
        # exists, the headline must carry the steady-state warm-hit rate
        # and resume depth from the e2e phase plus the session lane's
        # warm/cold solver p50s — dashboards and the acceptance bound
        # (warm p50 ≥ 3x below cold p50) key on these exact names
        from k8s_spark_scheduler_tpu.native.fifo import (
            native_session_available,
        )

        if native_session_available():
            assert 0.0 <= headline["warm_hit_rate"] <= 1.0
            assert headline["warm_hit_rate"] == lane["warm_hit_rate"]
            assert "resume_depth_p50" in headline
            ds = artifact["lanes"].get("deltasolve-session cpu")
            assert ds is not None
            assert headline["warm_solve_p50_ms"] == ds["warm_p50_ms"] > 0
            assert headline["cold_solve_p50_ms"] == ds["cold_p50_ms"] > 0
            assert ds["warm_speedup_p50"] > 0

        # provenance overhead contract (PR 6): when the native explainer
        # exists the bench must pin explain + flight-recorder costs as
        # their own lane — explain is an on-demand diagnostic budgeted at
        # "about a cold solve", the recorder note at sub-millisecond, and
        # the persisted bundle file is bounded
        from k8s_spark_scheduler_tpu.native.fifo import (
            native_explain_available,
        )

        if native_explain_available():
            prov = artifact["lanes"].get("provenance-explain cpu")
            assert prov is not None
            assert prov["explain_p50_ms"] > 0
            assert prov["recorder_note_p50_ms"] >= 0
            assert prov["bundle_file_bytes"] > 0

        # capacity-probe contract (PR 7): when the native probe exists
        # the bench pins its latency at the bench node shape × 16 gang
        # shapes, and the bisection depth stays a handful of
        # feasibility solves per shape
        from k8s_spark_scheduler_tpu.native.fifo import (
            native_probe_available,
        )

        if native_probe_available():
            capl = artifact["lanes"].get("capacity-probe cpu")
            assert capl is not None
            assert capl["probe_p50_ms"] > 0
            assert capl["shapes"] == 16
            # ≤ 2 + ceil(log2(k_max)) + 1 evaluations per shape
            assert 0 < capl["solves_per_probe"] <= 16 * 23
            assert capl["solves_per_shape_p50"] <= 23

        # contention-lane contract (PR 11): the e2e phase scrapes the
        # live server's /debug/criticalpath + /debug/contention and pins
        # the latency decomposition and predicate-lock stats as their
        # own lane; the headline carries the coverage + dominant-segment
        # annotations.  tools/perf_regression.py gates on these exact
        # key names, so they are part of the durable artifact contract.
        con = artifact["lanes"].get("contention http")
        assert con is not None, "e2e phase ran but no contention lane"
        for key in (
            "total_p99_ms", "solve_p99_ms", "serde_p99_ms",
            "write_back_p99_ms", "gate_queue_p99_ms", "lock_wait_p99_ms",
            "other_p99_ms", "lock_hold_ms_p99",
        ):
            assert isinstance(con[key], (int, float)), key
        assert con["window"] >= headline["samples"]
        assert 0.0 < con["coverage_p50"] <= 1.0
        assert con["lock_acquisitions"] > 0
        # the named segments reconstruct the end-to-end p99 within the
        # acceptance bound (sum of per-segment p99s upper-bounds the
        # total p99, and coverage keeps "other" small)
        assert headline["criticalpath_coverage_p50"] == con["coverage_p50"]
        assert headline["criticalpath_dominant"] in (
            "solve", "serde", "write-back", "gate-queue", "lock-wait",
            "other",
        )
    else:
        assert headline["metric"].startswith("p99_queue_solve")
        assert lane is None

    # the canonical artifact was not touched by the smoke run
    if canonical_mtime is not None:
        assert (
            os.path.getmtime(os.path.join(REPO, "BENCH_RESULT.json"))
            == canonical_mtime
        )


def test_bench_headline_falls_back_to_queue_solve_name(tmp_path):
    """With the request-level phase switched off (BENCH_E2E_PROBES=0),
    the headline keeps the solver lane under its own p99_queue_solve…
    name — never the Filter name."""
    env = dict(os.environ)
    env.update(
        BENCH_NODES="120", BENCH_APPS="12", BENCH_CHAIN="2",
        BENCH_ROUNDS="2", BENCH_E2E_PROBES="0",
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540,
        stdin=subprocess.DEVNULL,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    headline = json.loads(lines[-1])
    assert headline["metric"].startswith("p99_queue_solve")
    assert headline["backend"] in ("native-cpp", "xla-scan")
    assert headline["platform"] == "cpu"
