#!/usr/bin/env python3
"""chip_smoke.py — does the Filter path still serve from the chip?

Run from the root of a checkout, on a machine with one TPU:

    python3 chip_smoke.py [--seed N]

One process, which owns the chip.  It builds the served stack the way a
deployment does (``init_server_with_clients`` + ``ExtenderHTTPServer``),
loads a cluster made from ``--seed`` and POSTs real ``/predicates``
requests over HTTP:

1. **Full size** — 10,000 nodes and a 1,000-deep pending driver backlog
   (BASELINE config 5; shapes as ``bench.py:_config5_e2e``: 3 zones,
   4-96 cpu / 8-256 Gi nodes, gangs of 1-32 executors) under
   ``tpu-batch`` and under ``tpu-batch-single-az``: three new drivers
   behind the backlog, every executor of the first granted gang, and
   one driver too large to fit.  The single-AZ drive has to meet queue
   apps whose zone the device's score cannot certify (``zoneResolved``,
   printed with the lane): the exact decision for them is part of what
   is compared.
2. **Every device policy** — the same drive at the 1,024 × 64 shape
   bucket under each of the six ``tpu-batch*`` names, so every Pallas
   kernel variant the registry can dispatch is compiled by Mosaic, run,
   and checked through the served path.

Every response (``NodeNames``, ``FailedNodes``, ``Error``) is compared
with a twin stack in the same process that answers from the plain host
path, fed the same objects.  For every driver request the script asserts
that the queue pass was served by the device (``last_queue_lane`` and
the ``fifo_gate`` span), that no lane recorded a failure or demotion,
and that the extender counted zero host fallbacks.  Any failed phase
fails the run: nothing is caught and skipped.

It exits non-zero, and prints no result line, unless
``jax.default_backend() == "tpu"`` — there is no flag that lets it pass
without a chip.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Times printed on the way (first-request compile seconds per shape, wall
seconds per phase) are set-up, not metrics.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
import urllib.request
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

try:
    import numpy as np

    from k8s_spark_scheduler_tpu.config import Install, ResilienceConfig
    from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
    from k8s_spark_scheduler_tpu.kube.crd import DEMAND_CRD_NAME, demand_crd_spec
    from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
    from k8s_spark_scheduler_tpu.server.wiring import Server, init_server_with_clients
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.tracing.profiling import default_profiler
    from k8s_spark_scheduler_tpu.types import serde
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta, Pod, PodPhase
    from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources
except ImportError as err:  # chip_smoke.py alone, without the program
    sys.exit(f"chip_smoke: the repository is not importable from here: {err}")

# device policy name → the reference policy whose host oracle
# (ops/packers.py) defines the right answer
DEVICE_POLICIES = {
    "tpu-batch": "tightly-pack",
    "tpu-batch-distribute-evenly": "distribute-evenly",
    "tpu-batch-minimal-fragmentation": "minimal-fragmentation",
    "tpu-batch-single-az": "single-az-tightly-pack",
    "tpu-batch-az-aware": "az-aware-tightly-pack",
    "tpu-batch-single-az-minimal-fragmentation": "single-az-minimal-fragmentation",
}

FULL_NODES, FULL_BACKLOG = 10_000, 1_000
# 1,024 nodes and a backlog that keeps backlog + current driver inside
# the 64-app bucket for every new driver of the drive
POLICY_NODES, POLICY_BACKLOG = 1_024, 60
NEW_DRIVERS = 3


class SmokeFailure(AssertionError):
    """A phase of the smoke did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclass
class Stack:
    """One served extender: embedded API server + wiring + HTTP front."""

    name: str
    api: APIServer
    scheduler: Server
    http: ExtenderHTTPServer

    @property
    def solver(self):
        return self.scheduler.extender.binpacker.queue_solver

    def stop(self) -> None:
        self.http.stop()
        self.scheduler.stop()


def start_stack(
    binpack_algo: str, name: str, twin: bool = False, native_queue_lane: bool = False
) -> Stack:
    """init_server_with_clients + ExtenderHTTPServer(port=0) + wait_ready,
    as a deployment starts.  ``native_queue_lane`` pins a tpu-batch twin's
    queue solver to the C++ host lane (the independent implementation used
    where the host oracle loop is too slow to answer)."""
    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    install = Install(binpack_algo=binpack_algo, fifo=True)
    if twin:
        # the host oracle's earlier-drivers loop can outlast the 30 s
        # request deadline (kube-scheduler's extender timeout); the
        # deadline does not enter any decision
        install.resilience = ResilienceConfig(request_deadline_seconds=3600.0)
    scheduler = init_server_with_clients(api, install, demand_poll_interval=0.5)
    if native_queue_lane:
        scheduler.extender.binpacker.queue_solver.backend = "native"
    http = ExtenderHTTPServer(scheduler, port=0)
    http.start()
    stack = Stack(name, api, scheduler, http)
    try:
        check(scheduler.wait_ready(timeout=600.0), f"{name}: server not ready")
    except BaseException:
        stack.stop()
        raise
    return stack


@dataclass
class Cluster:
    node_names: List[str]
    base_ts: float
    rng: np.random.RandomState
    created: int = 0  # drivers made so far (backlog + new), for timestamps


def load_cluster(stacks: List[Stack], n_nodes: int, n_backlog: int, seed: int) -> Cluster:
    """The same nodes and pending driver backlog into every stack's API
    server, in bulk.  Shapes follow bench.py:_config5_e2e."""
    rng = np.random.RandomState(seed)
    names = [f"n{i:05d}" for i in range(n_nodes)]
    cpus = rng.randint(4, 96, size=n_nodes)
    mems = rng.randint(8, 256, size=n_nodes)
    for stack in stacks:
        for i, name in enumerate(names):
            stack.api.create(
                Node(
                    meta=ObjectMeta(
                        name=name,
                        labels={
                            ZONE_LABEL: f"z{i % 3}",
                            "resource_channel": "batch-medium-priority",
                        },
                    ),
                    allocatable=Resources.of(str(int(cpus[i])), f"{int(mems[i])}Gi"),
                )
            )
    cluster = Cluster(names, time.time() - 100_000.0, rng)
    for _ in range(n_backlog):
        for stack, pods in zip(stacks, new_gang(cluster, len(stacks), "queue")):
            stack.api.create(pods[0])
    return cluster


def new_gang(
    cluster: Cluster, copies: int, prefix: str,
    executors: Optional[int] = None, executor_cpu: Optional[str] = None,
) -> List[List[Pod]]:
    """``copies`` identical [driver, executor…] pod lists (one per stack —
    an API server keeps the object it is given), drawn from the cluster's
    seeded stream and created later than every driver before it."""
    rng = cluster.rng
    k = int(rng.randint(1, 32))
    cpu = str(int(rng.randint(1, 8)))
    mem = f"{int(rng.randint(2, 16))}Gi"
    i = cluster.created
    cluster.created += 1
    return [
        Harness.static_allocation_spark_pods(
            f"{prefix}-{i:05d}",
            executors if executors is not None else k,
            executor_cpu=executor_cpu or cpu,
            executor_mem=mem,
            creation_timestamp=cluster.base_ts + i,
        )
        for _ in range(copies)
    ]


def post_filter(stack: Stack, pod: Pod, node_names: List[str]) -> Tuple[dict, str, float]:
    """One real POST /predicates.  Returns (body, trace id, seconds)."""
    payload = {"Pod": serde.pod_to_dict(pod), "NodeNames": node_names}
    req = urllib.request.Request(
        f"http://127.0.0.1:{stack.http.port}/predicates",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=3600) as resp:
        body = json.loads(resp.read())
        trace_id = resp.headers.get("X-Trace-Id", "")
    return body, trace_id, time.perf_counter() - t0


def same_answer(body: dict, twin_body: dict) -> bool:
    """NodeNames, Error and the FailedNodes node set must be equal.  A
    refusal's message must be equal too, except that a stack whose
    policy has a queue solver appends the provenance explainer's detail
    ("<reason>: short 8 executors … in cpu; …", diagnostic only) which a
    host-policy twin has no solve artifacts to produce: there the twin's
    message must be the device message's reason."""
    if (body.get("NodeNames") or []) != (twin_body.get("NodeNames") or []):
        return False
    if (body.get("Error") or "") != (twin_body.get("Error") or ""):
        return False
    failed, twin_failed = body.get("FailedNodes") or {}, twin_body.get("FailedNodes") or {}
    if failed.keys() != twin_failed.keys():
        return False
    return all(
        msg == twin_failed[node] or msg.startswith(twin_failed[node] + ": ")
        for node, msg in failed.items()
    )


def bind(stack: Stack, pod: Pod, node: str) -> None:
    """What kube-scheduler does after a successful Filter."""
    bound = stack.api.get(Pod.KIND, pod.namespace, pod.name)
    bound.node_name = node
    bound.phase = PodPhase.RUNNING
    stack.api.update(bound)


# what a driver request served by a device lane of the tensor-snapshot
# queue solver carries in its trace (docs/observability.md): the
# benchmark's upload_ms, device_wait_ms and readback_ms read these
DEVICE_SPANS = ("device.upload", "device.wait", "device.readback")


def trace_of(stack: Stack, trace_id: str) -> dict:
    """The request's completed trace."""
    deadline = time.monotonic() + 5.0
    trace = None
    while trace is None and time.monotonic() < deadline:
        trace = stack.scheduler.tracer.find_by_trace_id(trace_id)
        if trace is None:
            time.sleep(0.01)  # the root span closes just after the reply
    check(trace is not None, f"{stack.name}: no trace {trace_id!r} recorded")
    return trace


def spans_of(span: dict):
    yield span
    for child in span.get("children", ()):
        yield from spans_of(child)


def gate_tags_of(trace: dict) -> dict:
    """The tags of the request's fifo_gate span."""
    for span in spans_of(trace["root"]):
        if span["name"] == "fifo_gate":
            return span.get("tags", {})
    return {}


def assert_device_served(stack: Stack, trace_id: str, expect_lane: str) -> dict:
    """After one driver Filter on the device stack: the queue pass ran on
    the expected lane, nothing failed, nothing fell back.  Lane names
    carry a policy suffix ("pallas-minfrag", "native-session"): the part
    before the dash is the lane.  Returns the fifo_gate span's tags."""
    solver = stack.solver
    lane = solver.last_queue_lane
    check(
        lane is not None and lane.split("-")[0] == expect_lane,
        f"{stack.name}: queue pass served by {lane!r}, expected {expect_lane!r}",
    )
    if hasattr(solver, "last_path"):
        check(
            solver.last_path in ("fused", "native"),
            f"{stack.name}: single-AZ queue pass took the {solver.last_path!r} lane",
        )
    trace = trace_of(stack, trace_id)
    gate = gate_tags_of(trace).get("lane")
    check(
        gate is not None and gate.split("-")[0] == expect_lane,
        f"{stack.name}: fifo_gate span says lane {gate!r}, expected {expect_lane!r}",
    )
    if expect_lane != "native":
        # a device lane that stops being traced fails bring-up here, not
        # a benchmark metric's None later
        missing = set(DEVICE_SPANS) - {span["name"] for span in spans_of(trace["root"])}
        check(not missing, f"{stack.name}: a driver request on lane {gate!r} carries no {sorted(missing)} span")
    lanes = stack.scheduler.resilience.lanes
    check(not lanes.failure_totals(), f"{stack.name}: lane failures {lanes.failure_totals()}")
    check(not lanes.demoted_lanes(), f"{stack.name}: demoted lanes {lanes.demoted_lanes()}")
    fallbacks = stack.scheduler.extender.host_fallbacks()
    check(fallbacks == 0, f"{stack.name}: {fallbacks} host fallbacks counted")
    return gate_tags_of(trace)


@dataclass
class DriveReport:
    requests: int = 0
    granted_drivers: int = 0
    executors: int = 0
    refused: int = 0
    first_request_compile_s: float = 0.0
    # single-AZ: queue apps decided exactly on the host, and kernel
    # launches, summed over the drive's driver requests
    zone_resolved: int = 0
    launches: int = 0
    device_s: List[float] = field(default_factory=list)
    twin_s: List[float] = field(default_factory=list)


def drive_and_compare(
    device: Stack, twin: Stack, cluster: Cluster, expect_lane: str
) -> DriveReport:
    """The smoke's request mix against both stacks, each answer compared:
    NEW_DRIVERS new drivers behind the backlog, every executor of the
    first granted gang, one driver too large to fit."""
    report = DriveReport()
    names = cluster.node_names

    def both(pods: Tuple[Pod, Pod], what: str, is_driver: bool) -> dict:
        created = [s.api.create(p) for s, p in zip((device, twin), pods)]
        compile0 = default_profiler.compile_seconds()
        body, trace_id, secs = post_filter(device, created[0], names)
        if report.requests == 0:
            report.first_request_compile_s = (
                default_profiler.compile_seconds() - compile0
            )
        twin_body, _, twin_secs = post_filter(twin, created[1], names)
        report.requests += 1
        report.device_s.append(secs)
        report.twin_s.append(twin_secs)
        check(
            same_answer(body, twin_body),
            f"{what}: device stack answered {_brief(body)}, twin {_brief(twin_body)}",
        )
        if is_driver:
            gate = assert_device_served(device, trace_id, expect_lane)
            report.zone_resolved += int(gate.get("zoneResolved", 0))
            report.launches += int(gate.get("launches", 0))
        for stack, pod in zip((device, twin), created):
            if body.get("NodeNames"):
                bind(stack, pod, body["NodeNames"][0])
        return body

    first_gang: Optional[List[List[Pod]]] = None
    for i in range(NEW_DRIVERS):
        # a small first gang keeps the executor leg short
        gangs = new_gang(cluster, 2, "new", executors=4 if i == 0 else None)
        body = both((gangs[0][0], gangs[1][0]), f"new driver {i}", True)
        if body.get("NodeNames"):
            report.granted_drivers += 1
            if first_gang is None:
                first_gang = gangs
    check(first_gang is not None, "no new driver was granted; nothing proves the grant path")
    for j in range(1, len(first_gang[0])):
        body = both((first_gang[0][j], first_gang[1][j]), f"executor {j - 1}", False)
        check(bool(body.get("NodeNames")), f"executor {j - 1} of a granted gang was refused: {_brief(body)}")
        report.executors += 1
    huge = new_gang(cluster, 2, "huge", executors=8, executor_cpu="512")
    body = both((huge[0][0], huge[1][0]), "oversized driver", True)
    check(
        not body.get("NodeNames") and len(body.get("FailedNodes") or {}) == len(names),
        f"oversized driver was not refused on every node: {_brief(body)}",
    )
    report.refused += 1
    return report


def _brief(body: dict) -> str:
    failed = body.get("FailedNodes") or {}
    sample = next(iter(failed.values()), "")
    return (
        f"NodeNames={body.get('NodeNames')} FailedNodes[{len(failed)}]"
        f"{'=' + repr(sample) if sample else ''} Error={body.get('Error')!r}"
    )


def run_phase(
    device_policy: str, twin_policy: str, n_nodes: int, n_backlog: int,
    seed: int, expect_lane: str, twin_native: bool = False, expect_resolved: bool = False,
) -> DriveReport:
    """One policy at one size: start both stacks, load, drive, stop.
    ``expect_resolved``: the drive has to meet queue apps that the
    single-AZ valve decides on the host."""
    t0 = time.perf_counter()
    # twin first: the kernel profiler binds to the last server wired, and
    # the device stack's compile seconds are the ones to report
    twin = start_stack(twin_policy, "twin", twin=True, native_queue_lane=twin_native)
    try:
        device = start_stack(device_policy, "device")
        try:
            t_load = time.perf_counter()
            cluster = load_cluster([device, twin], n_nodes, n_backlog, seed)
            load_s = time.perf_counter() - t_load
            report = drive_and_compare(device, twin, cluster, expect_lane)
        finally:
            device.stop()
    finally:
        twin.stop()
    valve = ""
    if report.launches:
        valve = f" zoneResolved={report.zone_resolved} launches={report.launches};"
    check(
        report.zone_resolved > 0 or not expect_resolved,
        f"{device_policy}: no queue app needed the exact zone decision; take another --seed",
    )
    print(
        f"  {device_policy} vs {twin_policy}"
        f"{' (native C++ queue lane)' if twin_native else ' (host oracle)'} "
        f"at {n_nodes} nodes x {n_backlog} backlog: {report.requests} requests "
        f"({report.granted_drivers} drivers granted, {report.executors} executors, "
        f"{report.refused} refused) all equal; lane={expect_lane};{valve} "
        f"first-request compile {report.first_request_compile_s:.2f}s; "
        f"device stack median {np.median(report.device_s) * 1e3:.1f}ms/request "
        f"(sum {sum(report.device_s):.1f}s), twin median "
        f"{np.median(report.twin_s) * 1e3:.1f}ms/request (sum {sum(report.twin_s):.1f}s) "
        f"— host clock, set-up only; load {load_s:.1f}s, phase {time.perf_counter() - t0:.1f}s",
        flush=True,
    )
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=5)
    args = parser.parse_args(argv)
    t_start = time.perf_counter()
    # the backlog is made old on purpose (FIFO order); its "older than
    # the slow log threshold" warnings would bury a real error
    logging.getLogger("k8s_spark_scheduler_tpu").setLevel(logging.ERROR)

    from k8s_spark_scheduler_tpu.utils.compilecache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this script "
            "only passes on the chip",
            file=sys.stderr,
        )
        return 1
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # printed only; the run does not depend on it
        libtpu = "unknown"
    print(
        f"chip_smoke: platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} (serving from {devices[0]}) "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"python={sys.version.split()[0]} compile_cache={cache_dir} seed={args.seed}",
        flush=True,
    )

    print(f"phase 1: full size, {FULL_NODES} nodes x {FULL_BACKLOG} pending drivers", flush=True)
    # the reference policy's host loop (1,000 earlier drivers x 10,000
    # nodes in exact Quantity arithmetic) takes minutes per driver
    # request at this size — four of them do not fit the run's limit, nor
    # one kube-scheduler's 30 s timeout — so the full-size twin is the
    # same policy on the independent native C++ queue lane; phase 2
    # compares every policy with the host oracle itself, at the largest
    # size this script drives it
    run_phase(
        "tpu-batch", "tpu-batch", FULL_NODES, FULL_BACKLOG, args.seed,
        expect_lane="pallas", twin_native=True,
    )
    # the native lane chooses every zone in float64, so the twin is exact
    # where the device's score is not: the apps the valve resolves
    run_phase(
        "tpu-batch-single-az", "tpu-batch-single-az", FULL_NODES, FULL_BACKLOG, args.seed,
        expect_lane="pallas", twin_native=True, expect_resolved=True,
    )

    print(
        f"phase 2: every device policy at {POLICY_NODES} nodes x "
        f"{POLICY_BACKLOG} pending drivers (the 1024 x 64 shape bucket) "
        "against its host oracle",
        flush=True,
    )
    for device_policy, oracle_policy in DEVICE_POLICIES.items():
        run_phase(
            device_policy, oracle_policy, POLICY_NODES, POLICY_BACKLOG,
            args.seed + 1, expect_lane="pallas",
        )

    print(
        f"chip_smoke: all phases passed in {time.perf_counter() - t_start:.1f}s "
        "(compilation and set-up included)",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
