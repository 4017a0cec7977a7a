"""Bring-up guards (ISSUE 21): nothing may let a CPU answer pass for a
chip answer.  Compile cache placement, native libraries built from the
committed source on the running host, warm-up failure fatal to
readiness, host fallbacks counted, and chip_smoke.py's drive rehearsed
at a tiny size on the CPU (the script itself still refuses to pass
without a chip)."""

import ctypes
import importlib
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache -----------------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    process's own cache placement must not move."""
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_honours_the_standard_variable(monkeypatch, config_updates, tmp_path):
    from k8s_spark_scheduler_tpu.utils import compilecache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compilecache.configure_compile_cache() == str(tmp_path)
    # placed from outside: the code sets no directory of its own
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch, config_updates):
    from k8s_spark_scheduler_tpu.utils import compilecache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert compilecache.configure_compile_cache() == expected
    assert config_updates["jax_compilation_cache_dir"] == expected
    # the directory is part of the cache key: same answer every time,
    # no fingerprint / pid / time component
    assert compilecache.configure_compile_cache() == expected


# -- native loader -----------------------------------------------------------

_TOY = 'extern "C" int toy_answer() { return %d; }\n'


def test_native_loader_ignores_foreign_and_stale_libraries(monkeypatch, tmp_path):
    from k8s_spark_scheduler_tpu import native

    build = tmp_path / "_build"
    build.mkdir()
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    src = tmp_path / "toy.cpp"
    src.write_text(_TOY % 1)

    # a library carried over from another machine / source: same stem,
    # different content hash — and not even loadable
    foreign = build / "libtoy-0123456789abcdef.so"
    foreign.write_bytes(b"not an ELF file")
    lib = native.build_native_lib(str(src), "toy", ["-O1"])
    assert lib.toy_answer() == 1
    built = [p.name for p in build.iterdir() if p.name != foreign.name]
    assert len(built) == 1 and built[0].startswith("libtoy-")
    assert not foreign.exists(), "dead libraries are cleared when a build lands"

    # new source bytes → new name, rebuilt; the old build is not reused
    src.write_text(_TOY % 2)
    assert native.build_native_lib(str(src), "toy", ["-O1"]).toy_answer() == 2
    # other flags → other name too
    first = {p.name for p in build.iterdir()}
    native.build_native_lib(str(src), "toy", ["-O2"])
    assert {p.name for p in build.iterdir()} != first

    # no "prebuilt .so with no source" branch: a missing source is an error
    src.unlink()
    with pytest.raises(OSError):
        native.build_native_lib(str(src), "toy", ["-O2"])


def test_loaded_native_libraries_are_named_by_this_hosts_hash():
    from k8s_spark_scheduler_tpu import native
    from k8s_spark_scheduler_tpu.native import fifo

    assert native.native_available() and fifo.native_fifo_available()
    names = os.listdir(native._BUILD_DIR)
    for stem in ("libsnapshot-", "libfifosolver-"):
        assert sum(n.startswith(stem) and n.endswith(".so") for n in names) == 1


# -- warm-up -----------------------------------------------------------------


def _refuse_zone_kernel(monkeypatch):
    """What the single-AZ policy's queue pass runs on this platform (the
    native lane on a CPU host) refuses, as a kernel Mosaic rejects would."""
    from k8s_spark_scheduler_tpu.native import fifo

    def refuse(*args, **kwargs):
        raise RuntimeError("injected: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fifo, "solve_queue_single_az_native", refuse)


def test_warmup_compile_error_fails_readiness(monkeypatch):
    """A kernel of the configured policy that does not compile keeps the
    server unready for good; wait_ready raises instead of waiting."""
    from k8s_spark_scheduler_tpu.config import Install
    from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
    from k8s_spark_scheduler_tpu.server.wiring import (
        SolverWarmupError,
        init_server_with_clients,
    )

    _refuse_zone_kernel(monkeypatch)
    server = init_server_with_clients(
        APIServer(), Install(binpack_algo="tpu-batch-single-az", fifo=True)
    )
    try:
        with pytest.raises(SolverWarmupError):
            server.wait_ready(timeout=60.0)
        assert not server.warmup_complete()
        assert "injected" in str(server.warmup_error)
    finally:
        server.stop()


def test_warmup_compiles_through_the_configured_solver():
    """The warm-up drives the policy's own solver: afterwards the kernels
    that policy dispatches on this platform are in the jit cache."""
    from k8s_spark_scheduler_tpu.ops import warmup
    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_single, solve_zones_jit

    zones0, single0 = solve_zones_jit._cache_size(), solve_single._cache_size()
    warmup.warm_queue_solver("tpu-batch-az-aware", True, [(64, 16)])
    assert solve_zones_jit._cache_size() >= zones0
    assert solve_single._cache_size() >= single0
    # host policies have nothing to warm; unknown shapes are added once
    warmup.warm_queue_solver("tightly-pack", True, [(64, 16)])
    assert warmup.warm_shapes([]) == warmup.warm_shapes([(0, 0)]) == warmup._BASE_SHAPES
    assert warmup.warm_shapes([(10_000, 1_000)]) == warmup._BASE_SHAPES + ((10240, 1024),)
    assert warmup.warm_shapes([(60, 3)]) == warmup._BASE_SHAPES


def test_after_warmup_a_filter_and_a_marker_scan_compile_nothing(monkeypatch):
    """The marker's first scan begins a minute after start, among the
    requests: its verdict program (``feasible_apps``, one app shape per
    node bucket whatever the backlog) has to be warm like the Filter's
    own (``solve_filter``)."""
    import random

    from test_batch_parity import orders_for, random_app, random_cluster

    from k8s_spark_scheduler_tpu.metrics import names as mnames
    from k8s_spark_scheduler_tpu.ops import batch_solver, fifo_solver, warmup
    from k8s_spark_scheduler_tpu.ops.registry import select_binpacker
    from k8s_spark_scheduler_tpu.ops.tensorize import tensorize_cluster
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    # the XLA lane, as on a host with neither a TPU nor the C++ library
    monkeypatch.setattr(fifo_solver, "_native_selected", lambda backend: False)
    warmup.warm_queue_solver("tpu-batch", True, [(256, 64)])
    h = Harness(binpack_algo="tpu-batch")
    try:
        h.server.wait_ready(timeout=300.0)  # the server's own warm-up: the small buckets
        stats = batch_solver.compilation_cache_stats()
        assert stats["solve_filter"] >= 1 and stats["feasible_apps"] >= 1

        rng = random.Random(30)
        metadata = random_cluster(rng, 200)
        cluster = tensorize_cluster(metadata, *orders_for(metadata, rng))
        earlier = [random_app(rng) for _ in range(40)]
        solver = select_binpacker("tpu-batch").queue_solver
        outcome = solver.solve_tensor(cluster, earlier, [True] * 40, random_app(rng))
        assert outcome.supported and solver.last_queue_lane == "xla"
        # scans over clusters of two warmed node buckets: a backlog of five
        # on six nodes, then of thirteen on a hundred and six
        for n_nodes, n_apps in ((6, 5), (100, 8)):
            for i in range(n_nodes):
                h.new_node(f"n{n_nodes}-{i}")
            for i in range(n_apps):
                pod = h.static_allocation_spark_pods(f"app-aged-{n_nodes}-{i}", 1 + i)[0]
                pod.meta.creation_timestamp = time.time() - 3600
                h.create_pod(pod)
            h.unschedulable_marker.scan_for_unschedulable_pods()
        # distinct gangs: five in the first scan, eight in the second
        assert h.server.metrics.get_counter(
            mnames.UNSCHEDULABLE_SOLVE_COUNT, {"lane": "tensor"}
        ) == 5 + 8
    finally:
        h.close()
    assert batch_solver.compilation_cache_stats() == stats


def test_server_process_exits_nonzero_when_warmup_fails(tmp_path):
    cfg = tmp_path / "install.json"
    cfg.write_text('{"binpack": "tpu-batch-single-az", "fifo": true}')
    script = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from k8s_spark_scheduler_tpu.native import fifo
        def refuse(*a, **k):
            raise RuntimeError("injected: Mosaic failed to compile TPU kernel")
        fifo.solve_queue_single_az_native = refuse
        from k8s_spark_scheduler_tpu.server.__main__ import main
        sys.exit(main(["--port", "0", "--config", {str(cfg)!r}]))
        """
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=180, stdin=subprocess.DEVNULL,
    )
    assert out.returncode == 1, out.stderr[-2000:]
    assert "solver warmup failed" in out.stderr
    assert f"compile-cache={tmp_path / 'cc'}" in out.stdout


def test_bench_request_phase_raises_on_warmup_failure(monkeypatch):
    """bench.py no longer turns a failed phase into a log line: the
    exception leaves main() and the process exits non-zero."""
    from k8s_spark_scheduler_tpu.server.wiring import SolverWarmupError

    monkeypatch.setenv("BENCH_NODES", "60")
    monkeypatch.setenv("BENCH_APPS", "6")
    monkeypatch.setenv("BENCH_E2E_PROBES", "1")
    sys.modules.pop("bench", None)
    bench = importlib.import_module("bench")
    try:
        from k8s_spark_scheduler_tpu.ops import warmup

        def refuse(*args, **kwargs):
            raise RuntimeError("injected: Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(warmup, "warm_queue_solver", refuse)
        with pytest.raises(SolverWarmupError):
            bench._config5_e2e()
    finally:
        sys.modules.pop("bench", None)


# -- fallback accounting -----------------------------------------------------


def test_device_fault_is_counted_as_a_fallback():
    from k8s_spark_scheduler_tpu.metrics import names as mnames
    from k8s_spark_scheduler_tpu.ops import registry
    from k8s_spark_scheduler_tpu.testing.harness import Harness

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        for i in range(4):
            h.new_node(f"n{i}", cpu="16", memory="32Gi")
        nodes = [f"n{i}" for i in range(4)]
        metrics = h.server.metrics

        def fallbacks(path):
            return metrics.get_counter(
                mnames.TPU_FASTPATH, {"path": path, "lane": "fallback"}
            )

        ok = Harness.static_allocation_spark_pods("clean", 2)
        assert h.schedule(ok[0], nodes).node_names
        assert fallbacks("driver") == fallbacks("driver-fifo") == 0
        assert h.server.resilience.lanes.failure_totals() == {}

        registry.set_kernel_fault_hook(
            lambda lane: RuntimeError("injected device fault")
            if lane in ("tensor_driver", "device_fifo")
            else None
        )
        try:
            faulted = Harness.static_allocation_spark_pods("faulted", 2)
            # still answered — from the host path — but no longer silently
            assert h.schedule(faulted[0], nodes).node_names
        finally:
            registry.set_kernel_fault_hook(None)
        assert fallbacks("driver") == 1
        assert fallbacks("driver-fifo") == 1
        assert h.server.resilience.lanes.failure_totals() == {
            "tensor_driver": 1, "device_fifo": 1,
        }
    finally:
        h.close()


def test_first_compile_is_not_scored_against_the_latency_budget():
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.tracing.profiling import default_profiler

    h = Harness(binpack_algo="tpu-batch", is_fifo=True)
    try:
        t0 = time.perf_counter() - 12.0  # a 12 s request …
        compile0 = default_profiler.compile_seconds()
        default_profiler._add_compile(10.0, "request")  # … 10 s of it compiling
        elapsed = h.extender._lane_elapsed(t0, compile0)
        assert 2.0 <= elapsed < 3.0
    finally:
        h.close()


def test_forced_pallas_gang_packer_raises_off_tpu():
    from k8s_spark_scheduler_tpu.models.gang_packer import GangPacker, GangPackerConfig

    with pytest.raises(RuntimeError, match="needs a TPU"):
        GangPacker(GangPackerConfig(backend="pallas"))
    GangPacker(GangPackerConfig(backend="xla"))  # the CPU-testable lane


# -- chip_smoke.py -----------------------------------------------------------


@pytest.fixture(scope="module")
def chip_smoke():
    return importlib.import_module("chip_smoke")


def test_chip_smoke_refuses_to_pass_without_a_chip(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err and "'cpu'" in captured.err
    assert '"ok"' not in captured.out  # no result line


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, stdin=subprocess.DEVNULL,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert out.returncode != 0
    assert "not importable" in out.stderr and '"ok"' not in out.stdout


@pytest.mark.parametrize(
    "policy", ["tpu-batch", "tpu-batch-single-az-minimal-fragmentation"]
)
def test_chip_smoke_drive_rehearsed_on_cpu(chip_smoke, policy):
    """The drive-and-compare functions at a tiny size, against the host
    oracle twin, with the lane this platform serves from (native C++)."""
    report = chip_smoke.run_phase(
        policy, chip_smoke.DEVICE_POLICIES[policy], 48, 6, seed=7,
        expect_lane="native",
    )
    assert report.granted_drivers >= 1 and report.executors == 4
    assert report.refused == 1
    assert report.requests == chip_smoke.NEW_DRIVERS + 4 + 1


def test_chip_smoke_fails_when_another_lane_served(chip_smoke):
    """What the script asserts on the chip: here the native lane serves,
    so demanding the Pallas kernel must fail the phase."""
    with pytest.raises(chip_smoke.SmokeFailure, match="expected 'pallas'"):
        chip_smoke.run_phase(
            "tpu-batch", "tightly-pack", 48, 6, seed=7, expect_lane="pallas"
        )


def test_chip_smoke_fails_when_a_device_lane_is_not_traced(chip_smoke, monkeypatch):
    """On the chip a served driver request has to carry device.upload,
    device.wait and device.readback; rehearsed here on the jnp lane the
    CPU can serve, then with one of the spans taken away."""
    from k8s_spark_scheduler_tpu.ops import fifo_solver

    start_stack = chip_smoke.start_stack

    def on_the_xla_lane(policy, name, **kwargs):
        stack = start_stack(policy, name, **kwargs)
        if name == "device":
            stack.scheduler.extender.delta_engine = None
            stack.solver.backend = "xla"
        return stack

    monkeypatch.setattr(chip_smoke, "start_stack", on_the_xla_lane)
    report = chip_smoke.run_phase("tpu-batch", "tightly-pack", 48, 6, seed=7, expect_lane="xla")
    assert report.granted_drivers >= 1
    monkeypatch.setattr(fifo_solver, "_readback", np.asarray)  # the transfer without its span
    with pytest.raises(chip_smoke.SmokeFailure, match="device.readback"):
        chip_smoke.run_phase("tpu-batch", "tightly-pack", 48, 6, seed=7, expect_lane="xla")


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_chip_smoke_reports_the_single_az_valve(chip_smoke, monkeypatch, lane):
    """The single-AZ drive on a lane with the valve (the jnp twin, and
    the kernel in interpret mode): launches and zoneResolved are read
    from the fifo_gate span, and a drive that was to meet uncertified
    apps and met none fails."""
    start_stack = chip_smoke.start_stack

    def on_a_device_lane(policy, name, **kwargs):
        stack = start_stack(policy, name, **kwargs)
        if name == "device":
            stack.scheduler.extender.delta_engine = None
            stack.solver.backend = lane
            stack.solver.interpret = True
        return stack

    monkeypatch.setattr(chip_smoke, "start_stack", on_a_device_lane)
    args = ("tpu-batch-single-az", "single-az-tightly-pack", 48, 6)
    report = chip_smoke.run_phase(*args, seed=7, expect_lane=lane)
    drivers = chip_smoke.NEW_DRIVERS + 1
    assert report.granted_drivers >= 1 and report.launches >= drivers
    if report.zone_resolved == 0:
        with pytest.raises(chip_smoke.SmokeFailure, match="take another --seed"):
            chip_smoke.run_phase(*args, seed=7, expect_lane=lane, expect_resolved=True)
    else:
        chip_smoke.run_phase(*args, seed=7, expect_lane=lane, expect_resolved=True)


def test_chip_smoke_catches_a_counted_fallback(chip_smoke):
    from k8s_spark_scheduler_tpu.ops import registry

    registry.set_kernel_fault_hook(
        lambda lane: RuntimeError("injected") if lane == "tensor_driver" else None
    )
    try:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.run_phase(
                "tpu-batch", "tightly-pack", 48, 6, seed=7, expect_lane="native"
            )
    finally:
        registry.set_kernel_fault_hook(None)
