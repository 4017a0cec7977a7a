#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N --seconds S --trace 0|1

run from the root of a checkout, on a machine with one TPU.  The cell's
configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``) and the per-layer metrics that list the cell
(``metrics/*.json``) are found by name; nothing here knows a cell.

One process, which owns the chip.  Set-up: make the cluster and backlog
from ``--seed``, start the served stack as a deployment does, run the
traffic's warm-up blocks.  Window: whole blocks of the closed-loop
traffic from a block boundary to the first boundary at or after
``--seconds``; in a ``--trace 0`` run nothing of the harness's runs in it
but the client loop.  Afterwards: every answer of the window is compared
with the plain reference, and the last line of stdout is the result.

It exits non-zero, and prints no result, without a TPU, with fewer chips
than the cell asks for, after any host fallback or lane demotion, or when
a driver's queue pass was not served by the lane the configuration states.

``--rehearse`` drives the same code on the CPU at the configuration's
rehearsal size, on the lane that platform serves from.  It is not a
measurement: the line it prints names the CPU and it exits 2.
``--control fifo-off`` runs the program with its FIFO guarantee switched
off, which the comparison has to call incorrect.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from the process's first line

import argparse
import gc
import json
import logging
import os
import sys
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import check
import metrics as metrics_mod
import plugins
import traffic as traffic_mod

CONTROLS = ("fifo-off",)
EXIT_REHEARSAL = 2


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(workload: str) -> dict:
    """The cell, its configuration and traffic files, by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json ({sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": load_json(ROOT, config_entry["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "bench": bench,
    }


def device_or_exit(chips: int, rehearse: bool) -> Dict[str, object]:
    """The accelerator as JAX reports it; exit 1 where it is not the one a
    measurement needs."""
    import jax

    backend = jax.default_backend()
    if rehearse:
        if backend != "cpu":
            raise SystemExit("run.py: --rehearse is for the CPU (JAX_PLATFORMS=cpu)")
    elif backend != "tpu":
        print(
            f"run.py: no TPU: jax.default_backend() is {backend!r}; a measurement "
            "needs the chip (--rehearse drives the paths on the CPU)",
            file=sys.stderr,
        )
        raise SystemExit(1)
    devices = jax.devices()
    if not rehearse and len(devices) < chips:
        print(f"run.py: the cell asks for {chips} chips, JAX reports {len(devices)}", file=sys.stderr)
        raise SystemExit(1)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


class CompileCounter:
    """Counts programs JAX compiled or fetched from its persistent cache:
    every new program that entered the process, through JAX's own
    monitoring events."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.count += 1


def rehearsal_size(config: dict) -> dict:
    """The configuration cut to its ``rehearsal`` size and the lane the CPU
    serves from: the group's keys stand in for the configuration's own,
    ``cluster`` key by key."""
    small = dict(config["rehearsal"])
    cluster = {**config["cluster"], **small.pop("cluster")}
    return {**config, **small, "cluster": cluster}


def write_series(out_dir: str, cell: str, seed: int, setup_s: float, e2e: dict, window, events) -> None:
    """The run's per-block series (and a traced run's plain events) for
    whoever looks for an outlier's cause."""
    os.makedirs(out_dir, exist_ok=True)
    series = {
        "workload": cell, "seed": seed, "setup_s": setup_s, "end_to_end": e2e,
        "blocks": [
            {"since_start_s": b.start - _T0, "seconds": b.end - b.start, "pods": b.pods,
             "filter_seconds": b.filter_seconds,
             "slowest_ms": max(a[0] for a in traffic_mod.answers([b])) * 1e3}
            for b in window
        ],
    }
    tag = f"{cell}.seed{seed}.{int(time.time() * 1000) % 10**9}.json"
    if events is not None:
        with open(os.path.join(out_dir, "events." + tag), "w") as f:
            json.dump(events, f)
    with open(os.path.join(out_dir, tag), "w") as f:
        json.dump(series, f)


def run(args) -> int:
    found = find_cell(args.workload)
    cell, config, mix = found["cell"], found["config"], found["traffic"]
    traffic_mod.step_modules(mix["steps"])  # an unknown verb stops the run here
    generator = plugins.load("generators", config["generator"])
    objects = plugins.load("objects", config["objects"])
    if args.rehearse:
        config = rehearsal_size(config)
    # the backlog is old on purpose (FIFO); its slow-schedule warnings would bury an error
    logging.getLogger("k8s_spark_scheduler_tpu").setLevel(logging.ERROR)

    try:
        import stack as stack_mod
        cache_dir = stack_mod.configure_compile_cache()
    except ImportError as err:
        print(f"run.py: the program is not importable from {ROOT}: {err}", file=sys.stderr)
        return 1
    device = device_or_exit(int(cell["chips"]), args.rehearse)
    compiles = CompileCounter()

    cluster = generator.make_cluster(config, args.seed, time.time())
    stream = generator.blocks(config, mix, args.seed, cluster.base_ts)
    install = dict(config["install"])
    if args.control == "fifo-off":
        install["fifo"] = False
    stack = stack_mod.start_stack(cluster, objects, install)
    try:
        client = stack_mod.Client(stack, cluster.names)
        tracing = None
        if args.trace:
            import tracing as tracing_mod

            tracing = tracing_mod.Tracing(stack, float(mix["trace_seconds"]))
        annotate = tracing.annotate if tracing else traffic_mod.no_annotation

        def run_one(block):
            return traffic_mod.run_block(client, objects, block, mix["steps"], annotate)

        t_ready = time.perf_counter() - _T0
        warm = [run_one(next(stream)) for _ in range(int(mix["warmup_blocks"]))]
        # The window opens at a stated phase of the deployment's periodic work
        # (configuration: ``window.opens_s_after_stack_start``), so that the same
        # share of it falls inside every window; until then the traffic goes on,
        # block by block.  That wait is no work of set-up's and is not in setup_s.
        opens_at = stack.started + float(config.get("window", {}).get("opens_s_after_stack_start", 0.0))
        fill_from = time.perf_counter()

        def fill(until: float, one=run_one) -> float:
            while time.perf_counter() < until:
                warm.append(one(next(stream)))
            return time.perf_counter()

        if tracing:
            # the profiler's start is slow and falls on blocks that are thrown away
            filled = fill(opens_at - tracing.START_COSTS_S)
            tracing.start_profiler()
            plain = lambda block: traffic_mod.run_block(client, objects, block, mix["steps"])  # noqa: E731
            warm.append(plain(next(stream)))
            profiling = time.perf_counter()
            fill_s = (filled - fill_from) + (fill(opens_at, plain) - profiling)
        else:
            fill_s = fill(opens_at) - fill_from
        failures_at_open = stack_mod.lane_failures(stack)
        gc.collect()  # every window starts from the same heap state
        if tracing:
            tracing.open_window()
        compiles_before = compiles.count
        opened_s = time.perf_counter() - _T0
        setup_s = opened_s - fill_s
        window = traffic_mod.run_window(
            stream, args.seconds, run_one, tracing.on_block if tracing else None
        )
        compiles_in_window = compiles.count - compiles_before
        if tracing:
            tracing.close_window()
        peak = memory_peak_bytes()
        if args.control is None:
            stack_mod.assert_served_by_device(stack, failures_at_open)
            lanes = [g.read.get("lane") for b in warm + window for g in b.gangs]
            off = [l for l in lanes if l is None or l.split("-")[0] != config["expect_lane"]]
            if off:
                raise stack_mod.NotThisSystem(
                    f"{len(off)} of {len(lanes)} driver queue passes were served by "
                    f"{sorted(set(map(str, off)))}, not {config['expect_lane']!r}"
                )
    finally:
        stack.stop()

    # the program's state is gone; now the plain reference answers the same stream
    t_ref = time.perf_counter()
    model = plugins.load("references", config["reference"]["model"])
    reference = model.Reference(cluster, config["reference"]["policy"], fifo=bool(config["install"]["fifo"]))
    checks = check.compare(window, reference, cluster.names, mix["steps"])
    reference_s = time.perf_counter() - t_ref
    correct = check.is_correct(checks)

    requests = traffic_mod.answers(window)
    refused = sum(1 for r in requests if traffic_mod.granted(r[2]) is None)
    bench = found["bench"]
    context = metrics_mod.window_context(window, setup_s, compiles_in_window, config, device)
    e2e = metrics_mod.end_to_end(bench, cell["name"], context)
    result: Dict[str, object] = {"correct": correct, "attempted": len(requests), "failed": refused}
    if args.trace:
        context.update(tracing.context(window))
        result["metrics"] = metrics_mod.per_layer(bench, cell["name"], context)
        device = {**device, "busy_s": context["busy_s"], "window_s": context["traced_window_s"]}
        result["breakdown"] = context["breakdown"]
    else:
        result["metrics"] = e2e
    result["device"] = {**device, "memory_peak_bytes": peak}
    result["window"] = {
        "blocks": len(window), "seconds": window[-1].end - window[0].start,
        "gangs": sum(len(b.gangs) for b in window), "reference_s": reference_s,
        "compiles_in_window": compiles_in_window,
        "compile_cache": cache_dir, "seed": args.seed,
        "setup": {"ready_s": t_ready, "warmup_blocks_s": setup_s - t_ready,
                  "stack_started_s": stack.started - _T0, "phase_fill_s": fill_s, "opened_s": opened_s,
                  "first_request_ms": traffic_mod.answers(warm)[0][0] * 1e3 if warm else None,
                  "over_budget_in_setup": failures_at_open},
    }
    if args.rehearse:
        result["rehearsal"] = "not a measurement: CPU, the lane that platform serves from, rehearsal size"
    if args.control:
        result["control"] = args.control
    result["checks"] = checks  # each number compared beside its limit, last
    if args.out:
        write_series(args.out, cell["name"], args.seed, setup_s,
                     {n: m["value"] for n, m in e2e.items()}, window,
                     tracing.events if tracing else None)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return EXIT_REHEARSAL if args.rehearse else 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true", help="CPU walk-through; not a measurement")
    parser.add_argument("--control", choices=CONTROLS, help="break a stated guarantee; must read incorrect")
    parser.add_argument("--out", help="directory for the per-block series of this run")
    args = parser.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import stack as stack_mod

    try:
        return run(args)
    except stack_mod.NotThisSystem as err:
        print(f"run.py: not a measurement of this system: {err}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
