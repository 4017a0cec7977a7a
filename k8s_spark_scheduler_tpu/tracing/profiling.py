"""Kernel-level profiling hooks for the JAX solvers.

The predicate hot path dispatches compiled programs (XLA scans, pallas
kernels, the native C++ lane).  A flat request timer can't tell an
operator whether a slow Filter paid jit *compilation* (new shape bucket
→ seconds) or *execution* (steady state → sub-millisecond), so the
profiler splits every profiled dispatch into:

- **compile time** — wall time of the traced Python call when the jit
  cache grew (trace + lower + compile; ``KERNEL_COMPILE_TIME``),
- **execute time** — host-observed dispatch-to-ready: the host's clock
  from the call until ``block_until_ready`` returns
  (``KERNEL_EXECUTE_TIME``).  It holds the launch and the sync besides
  the device's own time; device time comes from a JAX profile only,
- **cache hits/misses** — ``KERNEL_CACHE_HITS`` / ``KERNEL_CACHE_MISSES``,
- **compiles by where they happened** — ``KERNEL_COMPILES`` with
  ``phase=warmup`` (under ``KernelProfiler.warming()``), ``request`` (a
  request's trace is active on the compiling thread: the caller waits
  for the compiler, under the predicate lock) or ``background`` (the
  unschedulable-pod marker's scan, a library caller),

all tagged with the kernel name and the lane ("xla", "pallas",
"native", …), and mirrored onto the active trace span so a span tree
shows exactly which kernel compiled mid-request.  Under a request's
trace a jitted dispatch's ``kernel:<name>`` span has two children:
``device.dispatch`` (the jitted call returning) and ``device.wait``
(``sync()`` blocking until the outputs are ready).

Cache-miss detection prefers the jitted function's own cache
(``fn._cache_size()``); lanes that can't expose one (pallas wrappers)
fall back to a seen-(kernel, shape-key) set.  The native C++ lane has
no compile phase: profiled with ``jit=False``, it records execute time
only.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, Optional, Set, Tuple

from ..metrics import names as mnames
from .spans import (
    NOOP_SPAN,
    REQUEST_ROOTS,
    Tracer,
    child_span,
    current_span,
    default_tracer,
)
from ..analysis.guarded import guarded_by


PHASE_WARMUP, PHASE_REQUEST, PHASE_BACKGROUND = "warmup", "request", "background"
# the phase a caller states for its thread (KernelProfiler.warming)
_STATED_PHASE: ContextVar[Optional[str]] = ContextVar(
    "k8s_spark_scheduler_tpu_compile_phase", default=None
)


def compile_phase() -> str:
    """Where a compile on this thread happens: what the caller stated,
    else ``request`` under a request's trace, else ``background``."""
    stated = _STATED_PHASE.get()
    if stated is not None:
        return stated
    span = current_span()
    while span is not None and span.parent is not None:
        span = span.parent
    if span is not None and span.name in REQUEST_ROOTS:
        return PHASE_REQUEST
    return PHASE_BACKGROUND


def jit_cache_size(fn) -> Optional[int]:
    """Entry count of a jitted function's compilation cache, or None
    when the callable doesn't expose one (plain wrappers, native)."""
    try:
        return fn._cache_size()
    except Exception:
        return None


class _KernelRecord:
    """Per-dispatch timing marks.  ``sync(*arrays)`` must be called
    right after the traced call returns, with the outputs — it stamps
    the dispatch end, then blocks until the arrays are device-ready."""

    __slots__ = ("t0", "t_dispatch", "t_end", "_phase")

    def __init__(self, phase=NOOP_SPAN) -> None:
        # the open ``device.dispatch`` span of a traced jitted dispatch
        self._phase = phase
        self._phase.__enter__()
        self.t0 = time.perf_counter()
        self.t_dispatch: Optional[float] = None
        self.t_end: Optional[float] = None

    def _end_phase(self) -> None:
        self._phase.__exit__(None, None, None)
        self._phase = NOOP_SPAN

    def sync(self, *arrays: Any) -> None:
        self.t_dispatch = time.perf_counter()
        traced = self._phase is not NOOP_SPAN
        self._end_phase()
        with child_span("device.wait") if traced else NOOP_SPAN:
            for a in arrays:
                block = getattr(a, "block_until_ready", None)
                if block is not None:
                    block()
        self.t_end = time.perf_counter()


class _Profile:
    __slots__ = ("_profiler", "_kernel", "_lane", "_fn", "_shape_key", "_jit",
                 "_rec", "_span", "_cache_before")

    def __init__(self, profiler, kernel, lane, fn, shape_key, jit):
        self._profiler = profiler
        self._kernel = kernel
        self._lane = lane
        self._fn = fn
        self._shape_key = shape_key
        self._jit = jit
        self._rec: Optional[_KernelRecord] = None
        self._span = NOOP_SPAN
        self._cache_before: Optional[int] = None

    def __enter__(self) -> _KernelRecord:
        # kernel spans are always sub-phases: attach only when a request
        # span is active, so background solves (warmup, the
        # unschedulable scan) don't litter the ring with root traces
        if current_span() is not None:
            self._span = self._profiler.tracer.span(
                f"kernel:{self._kernel}", {mnames.TAG_LANE: self._lane}
            )
        self._span.__enter__()
        if self._jit and self._fn is not None:
            self._cache_before = jit_cache_size(self._fn)
        traced = self._jit and self._span is not NOOP_SPAN
        self._rec = _KernelRecord(child_span("device.dispatch") if traced else NOOP_SPAN)
        return self._rec

    def __exit__(self, exc_type, exc, tb) -> bool:
        rec = self._rec
        rec._end_phase()  # a dispatch that raised, or whose caller never synced
        now = time.perf_counter()
        t_end = rec.t_end if rec.t_end is not None else now
        t_dispatch = rec.t_dispatch if rec.t_dispatch is not None else t_end
        try:
            if exc is None:
                self._record(rec.t0, t_dispatch, t_end)
        finally:
            self._span.__exit__(exc_type, exc, tb)
        return False

    def _record(self, t0: float, t_dispatch: float, t_end: float) -> None:
        prof = self._profiler
        metrics = prof.metrics
        tags = {mnames.TAG_KERNEL: self._kernel, mnames.TAG_LANE: self._lane}
        if not self._jit:
            execute = t_end - t0
            metrics.histogram(mnames.KERNEL_EXECUTE_TIME, execute, tags)
            self._span.tag("executeMs", round(execute * 1000.0, 4))
            return

        miss = prof._classify_miss(
            self._kernel, self._fn, self._shape_key, self._cache_before
        )
        if miss:
            compile_s = t_dispatch - t0
            execute = t_end - t_dispatch
            phase = compile_phase()
            metrics.counter(mnames.KERNEL_CACHE_MISSES, tags)
            metrics.counter(mnames.KERNEL_COMPILES, {**tags, mnames.TAG_PHASE: phase})
            metrics.histogram(mnames.KERNEL_COMPILE_TIME, compile_s, tags)
            prof._add_compile(compile_s, phase)
            self._span.tag("compileMs", round(compile_s * 1000.0, 4))
        else:
            # steady state: dispatch is µs-level, fold it into execute
            execute = t_end - t0
            metrics.counter(mnames.KERNEL_CACHE_HITS, tags)
        metrics.histogram(mnames.KERNEL_EXECUTE_TIME, execute, tags)
        self._span.tag("executeMs", round(execute * 1000.0, 4))
        self._span.tag("cacheHit", not miss)


@guarded_by("_seen_lock", "_seen", "_compile_s", "_compiles")
class KernelProfiler:
    """Profiling sink: records into a metrics registry and the active
    trace.  One module-level instance (``default_profiler``) is rebound
    to the server's registry/tracer by the wiring."""

    def __init__(self, metrics=None, tracer: Optional[Tracer] = None):
        from ..metrics.registry import default_registry

        self.metrics = metrics if metrics is not None else default_registry
        self.tracer = tracer if tracer is not None else default_tracer
        self._seen: Set[Tuple[str, Any]] = set()
        self._compile_s = 0.0
        self._compiles: Dict[str, int] = {}
        self._seen_lock = threading.Lock()

    def configure(self, metrics=None, tracer: Optional[Tracer] = None) -> None:
        if metrics is not None:
            self.metrics = metrics
        if tracer is not None:
            self.tracer = tracer

    def profile(
        self,
        kernel: str,
        lane: str = "",
        fn=None,
        shape_key: Any = None,
        jit: bool = True,
    ) -> _Profile:
        """Context manager around one kernel dispatch.  The managed
        value is a record whose ``sync(*outputs)`` the caller invokes
        immediately after the dispatch returns."""
        return _Profile(self, kernel, lane, fn, shape_key, jit)

    def compile_seconds(self) -> float:
        """Total first-call (trace + lower + compile) seconds recorded
        so far.  Callers difference two readings around a region: the
        extender keeps a request's compile time out of the lane-latency
        score, chip_smoke.py reports it per shape as set-up."""
        with self._seen_lock:
            return self._compile_s

    def compiles(self, phase: str) -> int:
        """Programs compiled so far in ``phase`` (``compile_phase``).
        ``request`` counts those a request waited for: 0 on a server
        whose warm-up covered the shapes its requests are served at."""
        with self._seen_lock:
            return self._compiles.get(phase, 0)

    @contextmanager
    def warming(self):
        """What this thread compiles inside is the warm-up's."""
        token = _STATED_PHASE.set(PHASE_WARMUP)
        try:
            yield
        finally:
            _STATED_PHASE.reset(token)

    def _add_compile(self, seconds: float, phase: str) -> None:
        with self._seen_lock:
            self._compile_s += seconds
            self._compiles[phase] = self._compiles.get(phase, 0) + 1

    def _classify_miss(self, kernel, fn, shape_key, cache_before) -> bool:
        if fn is not None and cache_before is not None:
            after = jit_cache_size(fn)
            return after is not None and after > cache_before
        key = (kernel, shape_key)
        with self._seen_lock:
            if key in self._seen:
                return False
            self._seen.add(key)
            return True


default_profiler = KernelProfiler()
