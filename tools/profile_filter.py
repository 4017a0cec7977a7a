"""Profile the Filter request path at the north-star shape.

Builds the same 10k-node x 1k-pending-driver snapshot as bench.py's
config5-e2e lane, then measures two nested layers so the overhead
between them is attributable:

  1. ``predicate``— extender.predicate(args) called in-process with
                    pre-parsed ExtenderArgs (everything server-side
                    except HTTP + JSON serde)
  2. ``http``     — the real POST /predicates round trip

plus the FIFO demand-lookup cost, and optionally cProfiles the
predicate layer (--cprofile).  For a per-phase wall-clock attribution
(solve / tensor build / serde / reservation create), monkeypatch-wrap
the phase functions — cProfile mixes in background-thread time on a
single-core host.

Usage:  python tools/profile_filter.py [--nodes 10000 --apps 1000
        --probes 30] [--cprofile]
"""

from __future__ import annotations

import argparse
import cProfile
import io
import json
import os
import pstats
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# this tool profiles the CPU lane (cProfile of the host path); force,
# not setdefault, so a TPU host's environment cannot redirect it
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def build(n_nodes: int, n_apps: int, probes: int):
    import logging

    from k8s_spark_scheduler_tpu.config import Install
    from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
    from k8s_spark_scheduler_tpu.kube.crd import DEMAND_CRD_NAME, demand_crd_spec
    from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
    from k8s_spark_scheduler_tpu.server.wiring import init_server_with_clients
    from k8s_spark_scheduler_tpu.testing.harness import Harness
    from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
    from k8s_spark_scheduler_tpu.types.resources import ZONE_LABEL, Resources

    logging.disable(logging.WARNING)
    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    scheduler = init_server_with_clients(
        api, Install(binpack_algo="tpu-batch", fifo=True), demand_poll_interval=0.5
    )
    rng = np.random.RandomState(5)
    names = []
    for i in range(n_nodes):
        name = f"n{i:05d}"
        names.append(name)
        api.create(
            Node(
                meta=ObjectMeta(
                    name=name,
                    labels={
                        ZONE_LABEL: f"z{i % 3}",
                        "resource_channel": "batch-medium-priority",
                    },
                ),
                allocatable=Resources.of(
                    str(int(rng.randint(4, 96))), f"{int(rng.randint(8, 256))}Gi"
                ),
            )
        )
    base = time.time() - 10_000.0
    for i in range(n_apps):
        d = Harness.static_allocation_spark_pods(
            f"queue-{i:04d}",
            int(rng.randint(1, 32)),
            executor_cpu=str(int(rng.randint(1, 8))),
            executor_mem=f"{int(rng.randint(2, 16))}Gi",
            creation_timestamp=base + i,
        )[0]
        api.create(d)
    probe_pods = []
    for i in range(probes):
        d = Harness.static_allocation_spark_pods(
            f"probe-{i:03d}",
            int(rng.randint(1, 32)),
            executor_cpu=str(int(rng.randint(1, 8))),
            executor_mem=f"{int(rng.randint(2, 16))}Gi",
            creation_timestamp=base + n_apps + i,
        )[0]
        probe_pods.append(api.create(d))
    http = ExtenderHTTPServer(scheduler, port=0)
    http.start()
    # the readiness condition a real deployment gates traffic on: caches
    # synced AND solver warmup finished (warmup compiler threads would
    # otherwise contend with the timed probes on a small host)
    scheduler.wait_ready(timeout=600.0)
    return api, scheduler, http, names, probe_pods


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=10000)
    ap.add_argument("--apps", type=int, default=1000)
    ap.add_argument("--probes", type=int, default=30)
    ap.add_argument("--cprofile", action="store_true")
    args = ap.parse_args()

    from k8s_spark_scheduler_tpu.types import serde

    t0 = time.perf_counter()
    api, scheduler, http, names, probe_pods = build(
        args.nodes, args.apps, args.probes
    )
    print(f"setup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    ext = scheduler.extender

    def post_filter(pod):
        payload = {"Pod": serde.pod_to_dict(pod), "NodeNames": names}
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/predicates",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        t = time.perf_counter()
        with urllib.request.urlopen(req, timeout=120) as resp:
            body = json.loads(resp.read())
        return (time.perf_counter() - t) * 1000.0, body

    def direct_predicate(pod):
        from k8s_spark_scheduler_tpu.types.extenderapi import ExtenderArgs

        a = ExtenderArgs(pod=pod, node_names=list(names))
        t = time.perf_counter()
        ext.predicate(a)
        return (time.perf_counter() - t) * 1000.0

    # warmup through HTTP (compile + mirror + caches)
    wm, _ = post_filter(probe_pods[0])
    print(f"warmup: {wm:.1f}ms", file=sys.stderr)

    half = len(probe_pods) // 2
    http_lat, pred_lat = [], []
    prof = cProfile.Profile() if args.cprofile else None
    for pod in probe_pods[1:half]:
        ms, _ = post_filter(pod)
        http_lat.append(ms)
    if prof:
        prof.enable()
    for pod in probe_pods[half:]:
        pred_lat.append(direct_predicate(pod))
    if prof:
        prof.disable()

    def stats(tag, lat):
        if not lat:
            return
        a = np.array(lat)
        print(
            f"{tag}: p50={np.percentile(a, 50):.1f}ms "
            f"p90={np.percentile(a, 90):.1f}ms max={a.max():.1f}ms "
            f"mean={a.mean():.1f}ms n={len(a)}",
            file=sys.stderr,
        )

    stats("http    ", http_lat)
    stats("predicate", pred_lat)

    # solver-only: prebuilt problem through the same native lane
    from k8s_spark_scheduler_tpu.scheduler.sparkpods import (
        spark_app_demand_cached,
    )

    pod = probe_pods[-1]
    queued = ext._pod_lister.list_earlier_drivers(pod)
    t = time.perf_counter()
    demands = [spark_app_demand_cached(q)[1] for q in queued]
    demand_ms = (time.perf_counter() - t) * 1000.0
    print(f"demand-lookup x{len(queued)}: {demand_ms:.1f}ms", file=sys.stderr)

    if prof:
        s = io.StringIO()
        ps = pstats.Stats(prof, stream=s).sort_stats("cumulative")
        ps.print_stats(40)
        print(s.getvalue())

    http.stop()
    scheduler.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
