"""The system under test, started as a deployment starts it, and a lean client.

Copied in shape from ``chip_smoke.py`` (``start_stack``, ``load_cluster``,
``new_gang``, ``post_filter``, ``bind``, ``assert_device_served``) so that
a later PR may change the program and its smoke, not the yardstick.  This
and the object adapters (``objects/<name>.py``) are the only modules of
the benchmark that import the program.
"""

from __future__ import annotations

import errno
import http.client
import os
import json
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from blocks import Cluster

NAMESPACE = "default"
SOURCE_PORTS = (20_000, 60_000)  # the client's own; 40,000 connections before one comes back


class NotThisSystem(RuntimeError):
    """The run was not served the way the cell states (a host fallback,
    a demoted lane, a queue pass off the Pallas kernel): no measurement."""


@dataclass
class Stack:
    api: object
    scheduler: object
    http: object
    started: float = 0.0  # perf_counter when the server's background loops started

    @property
    def solver(self):
        return self.scheduler.extender.binpacker.queue_solver

    def stop(self) -> None:
        self.http.stop()
        self.scheduler.stop()


def start_stack(cluster: Cluster, objects, install: Dict[str, object]) -> Stack:
    """``objects`` is the configuration's adapter (``objects/<name>.py``),
    ``install`` its ``install`` group, handed to the program's ``Install`` whole.
    An API server that already holds the cluster and its backlog, then
    ``init_server_with_clients`` + ``ExtenderHTTPServer`` + ``wait_ready``:
    the server finds its cluster at start-up, as a deployment's does, and
    warms that cluster's own shape bucket."""
    from k8s_spark_scheduler_tpu.config import Install
    from k8s_spark_scheduler_tpu.kube.apiserver import APIServer
    from k8s_spark_scheduler_tpu.kube.crd import DEMAND_CRD_NAME, demand_crd_spec
    from k8s_spark_scheduler_tpu.server.http import ExtenderHTTPServer
    from k8s_spark_scheduler_tpu.server.wiring import init_server_with_clients

    api = APIServer()
    api.create_crd(DEMAND_CRD_NAME, demand_crd_spec())
    for node in objects.nodes(cluster):
        api.create(node)
    for gang in cluster.backlog:
        api.create(objects.pods(gang)[0])
    scheduler = init_server_with_clients(
        api, Install(**install), demand_poll_interval=0.5
    )
    started = time.perf_counter()  # init_server_with_clients ends by starting the periodic loops
    http_server = ExtenderHTTPServer(scheduler, port=0)
    http_server.start()
    stack = Stack(api, scheduler, http_server, started)
    try:
        if not scheduler.wait_ready(timeout=900.0):
            raise RuntimeError("server not ready after 900 s")
    except BaseException:
        stack.stop()
        raise
    return stack


class Client:
    """What kube-scheduler and the API machinery do around one Filter,
    with as little of its own work as it can: the 10,000-name
    ``NodeNames`` fragment is encoded once, a request keeps only
    (latency, trace id, response bytes)."""

    def __init__(self, stack: Stack, node_names: List[str]):
        from k8s_spark_scheduler_tpu.types import serde

        self._stack = stack
        self._pod_to_dict = serde.pod_to_dict
        self._names = b',"NodeNames":' + json.dumps(node_names).encode() + b"}"
        self._rr_cache = stack.scheduler.resource_reservation_cache
        self._port = stack.http.port
        # successive processes start a thousand ports apart
        self._source_port = SOURCE_PORTS[0] + (os.getpid() % 40) * 1000

    def create(self, pod):
        return self._stack.api.create(pod)

    def _connect(self) -> http.client.HTTPConnection:
        """A new connection (the server speaks HTTP/1.0 and closes after
        each answer) from the next source port of a private sequence, so
        that no address pair comes back within a run: the server's side of
        a closed connection stays in TIME_WAIT for a minute, and a SYN that
        meets one is dropped until it ends (a 63 s Filter, seen once in 15
        spark-mix runs when the system picked the ports)."""
        for _ in range(64):
            self._source_port += 1
            if self._source_port >= SOURCE_PORTS[1]:
                self._source_port = SOURCE_PORTS[0]
            if self._source_port == self._port:
                continue
            conn = http.client.HTTPConnection(
                "127.0.0.1", self._port, timeout=120,
                source_address=("127.0.0.1", self._source_port),
            )
            try:
                conn.connect()
                return conn
            except OSError as err:
                conn.close()
                if err.errno != errno.EADDRINUSE:
                    raise
        raise RuntimeError("no free source port among 64 tried")

    def filter(self, pod) -> Tuple[float, str, bytes]:
        """One real POST /predicates: (seconds, trace id, response body).
        The time includes the connection, as kube-scheduler's does."""
        body = b'{"Pod":' + json.dumps(self._pod_to_dict(pod)).encode() + self._names
        t0 = time.perf_counter()
        conn = self._connect()
        try:
            conn.request("POST", "/predicates", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            seconds = time.perf_counter() - t0
            if resp.status != 200:
                raise RuntimeError(f"/predicates answered {resp.status}: {data[:200]!r}")
            return seconds, resp.getheader("X-Trace-Id", ""), data
        finally:
            conn.close()

    def queue_lane(self) -> Optional[str]:
        """The lane that served the last driver's queue pass."""
        return getattr(self._stack.solver, "last_queue_lane", None)

    @staticmethod
    def _slots(rr) -> Tuple[str, Tuple[str, ...]]:
        """(driver node, executor slot nodes in slot order) of a reservation object."""
        slots: Dict[str, str] = {name: r.node for name, r in rr.spec.reservations.items()}
        driver = slots.pop("driver")
        ordered = sorted(slots, key=lambda s: int(s.rsplit("-", 1)[1]))
        return driver, tuple(slots[s] for s in ordered)

    def reservation(self, app_id: str) -> Optional[Tuple[str, Tuple[str, ...]]]:
        """The acknowledged reservation as the scheduler holds it."""
        rr = self._rr_cache.get(NAMESPACE, app_id)
        return None if rr is None else self._slots(rr)

    api_wait_s = 60.0  # a minute past the answer, at the most, for the durable copy

    def api_reservation(self, app_id: str, due: bool) -> Optional[Tuple[str, Tuple[str, ...]]]:
        """The durable copy: the ResourceReservation object in the API
        server, which the scheduler's write-back creates after it has
        answered.  Where one is ``due`` (the gang was granted), a copy that
        comes late is late, not wrong: wait for it; one that never comes
        reads None."""
        from k8s_spark_scheduler_tpu.kube.errors import NotFoundError

        deadline = time.monotonic() + (self.api_wait_s if due else 0.0)
        while True:
            try:
                return self._slots(self._stack.api.get("ResourceReservation", NAMESPACE, app_id))
            except NotFoundError:
                if time.monotonic() > deadline:
                    return None
                time.sleep(0.0002)

    def bind(self, pod, node: str) -> None:
        """What kube-scheduler does after a successful Filter."""
        from k8s_spark_scheduler_tpu.types.objects import Pod, PodPhase

        bound = self._stack.api.get(Pod.KIND, pod.namespace, pod.name)
        bound.node_name = node
        bound.phase = PodPhase.RUNNING
        self._stack.api.update(bound)

    def retire(self, pods: list, app_id: str) -> None:
        """The application finished: its pods are deleted and the owner
        collection drops its reservation; return once the scheduler's
        reservation cache has let go of it, so that the next request sees
        the steady cluster again."""
        for pod in reversed(pods):
            self._stack.api.delete("Pod", pod.namespace, pod.name)
        deadline = time.monotonic() + 10.0
        while self._rr_cache.get(NAMESPACE, app_id) is not None:
            if time.monotonic() > deadline:
                raise RuntimeError(f"reservation of {app_id} still held 10 s after its pods went")
            time.sleep(0.0002)


def lane_failures(stack: Stack) -> Dict[str, int]:
    """Lifetime failures per lane: errors, and answers that came over the
    lane's latency budget (the first request of a process that compiles
    is one: set-up, where it falls before the window)."""
    return dict(stack.scheduler.resilience.lanes.failure_totals())


def assert_served_by_device(stack: Stack, failures_at_open: Dict[str, int]) -> None:
    """Nothing fell back to the host, ever; nothing was demoted, ever;
    no lane failed or ran over its budget inside the window."""
    fallbacks = stack.scheduler.extender.host_fallbacks()
    if fallbacks:
        raise NotThisSystem(f"{fallbacks} host fallbacks counted")
    lanes = stack.scheduler.resilience.lanes
    if lanes.demoted_lanes():
        raise NotThisSystem(f"demoted lanes {lanes.demoted_lanes()}")
    if lane_failures(stack) != failures_at_open:
        raise NotThisSystem(
            f"lane failures inside the window: {lane_failures(stack)} (at open: {failures_at_open})"
        )


def configure_compile_cache() -> str:
    from k8s_spark_scheduler_tpu.utils.compilecache import configure_compile_cache as configure

    return configure()
