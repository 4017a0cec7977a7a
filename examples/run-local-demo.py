#!/usr/bin/env python
"""Self-contained local demo: extender server + fake autoscaler + a
simulated kube-scheduler submitting Spark apps over HTTP.

    python examples/run-local-demo.py

Shows the full loop from SURVEY §1's diagram: Filter calls, gang
admission, reservation objects, a demand when capacity runs out, the
autoscaler fulfilling it, and the retried app landing on scaled nodes.
"""

import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# default to CPU so the demo runs anywhere; set DEMO_TPU=1 to run the
# solver on the chip
if os.environ.get("DEMO_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")

import logging

logging.basicConfig(level=logging.WARNING)

from k8s_spark_scheduler_tpu.config import Install
from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
from k8s_spark_scheduler_tpu.kube.crd import DEMAND_CRD_NAME, demand_crd_spec
from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
from k8s_spark_scheduler_tpu.server.wiring import init_server_with_clients
from k8s_spark_scheduler_tpu.testing.fake_autoscaler import FakeAutoscaler
from k8s_spark_scheduler_tpu.testing.harness import Harness
from k8s_spark_scheduler_tpu.types import serde
from k8s_spark_scheduler_tpu.types.objects import Node, ObjectMeta
from k8s_spark_scheduler_tpu.types.resources import Resources, ZONE_LABEL


def post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predicates",
        data=json.dumps(payload).encode(),
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def main():
    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    scheduler = init_server_with_clients(
        api, Install(fifo=True, binpack_algo="tpu-batch"), demand_poll_interval=0.05
    )
    scheduler.lazy_demand_informer.wait_ready(10)
    http = ExtenderHTTPServer(scheduler, port=0)
    http.start()
    print(f"extender on :{http.port} (binpack=tpu-batch, fifo=on)")

    for i in range(3):
        api.create(
            Node(
                meta=ObjectMeta(
                    name=f"node-{i}",
                    labels={ZONE_LABEL: "zone1", "resource_channel": "batch-medium-priority"},
                ),
                allocatable=Resources.of("8", "16Gi"),
            )
        )
    print("cluster: 3 nodes x 8cpu/16Gi")

    autoscaler = FakeAutoscaler(api, scheduler.lazy_demand_informer.informer())

    def submit(app_id, executors, driver_exists=False):
        pods = Harness.static_allocation_spark_pods(app_id, executors)
        if not driver_exists:
            api.create(pods[0])
        node_names = [n.name for n in api.list("Node")]
        result = post(http.port, {"Pod": serde.pod_to_dict(pods[0]), "NodeNames": node_names})
        if result.get("NodeNames"):
            driver_node = result["NodeNames"][0]
            bound = api.get("Pod", "default", pods[0].name)
            bound.node_name = driver_node
            bound.phase = "Running"
            api.update(bound)
            placed = [driver_node]
            for p in pods[1:]:
                api.create(p)
                r = post(http.port, {"Pod": serde.pod_to_dict(p), "NodeNames": node_names})
                if r.get("NodeNames"):
                    b = api.get("Pod", "default", p.name)
                    b.node_name = r["NodeNames"][0]
                    b.phase = "Running"
                    api.update(b)
                    placed.append(r["NodeNames"][0])
            print(f"  {app_id}: GANG ADMITTED driver@{driver_node}, executors@{placed[1:]}")
            return True
        reason = next(iter(result.get("FailedNodes", {"?": "?"}).values()))
        print(f"  {app_id}: rejected — {reason}")
        return False

    print("\n[1] small app (1 driver + 3 executors):")
    submit("etl-small", 3)

    print("\n[2] big app that does NOT fit (1 + 40):")
    ok = submit("ml-big", 40)
    if not ok:
        demands = api.list("Demand")
        print(f"  demand created: {demands[0].name if demands else 'none'} "
              f"(units: {[(u.count, u.resources.cpu.serialize()) for u in demands[0].spec.units] if demands else []})")

    deadline = time.time() + 10
    while time.time() < deadline and not autoscaler.fulfilled:
        time.sleep(0.05)
    scaled = [n.name for n in api.list("Node") if n.name.startswith("scaled-")]
    print(f"\n[3] fake autoscaler fulfilled the demand: +{len(scaled)} nodes")

    print("\n[4] kube-scheduler retries the big app (driver + all executors):")
    submit("ml-big", 40, driver_exists=True)
    scaled_used = {
        r.node
        for rr in api.list("ResourceReservation")
        if rr.name == "ml-big"
        for r in rr.spec.reservations.values()
        if r.node.startswith("scaled-")
    }
    print(f"  reservations on scaled nodes: {sorted(scaled_used) or 'none'}")

    rrs = api.list("ResourceReservation")
    print(f"\nreservation objects at the API server: {[rr.name for rr in rrs]}")
    snap = scheduler.metrics.snapshot()
    requests = {k: v for k, v in snap["counters"].items() if k.startswith("foundry.spark.scheduler.requests")}
    print(f"request counters: {json.dumps(requests, indent=2)[:400]}")

    http.stop()
    scheduler.stop()
    print("\ndemo complete")


if __name__ == "__main__":
    main()
