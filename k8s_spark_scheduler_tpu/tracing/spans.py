"""Spans, trace assembly, and the bounded completed-trace ring.

One trace per scheduling request: the HTTP layer opens the root span
(``http.request``), the extender and the solvers open children, and
when the root closes the finished tree is serialized into the tracer's
ring where ``GET /traces`` and ``GET /debug/schedule/<pod>`` read it.

The active span is a module-level ``ContextVar`` — per-thread in the
threaded HTTP server (each request handler thread has its own context),
and shared across tracer instances so ``events.events`` and log lines
can stamp the current ``trace_id`` without any plumbing.

Two additions serve the traces that are no request's and the profiler:

- an *aggregate child* (``Span.aggregate(name)``): one child per name
  that stands for many sequential phases of its parent, its duration
  their sum and its ``count`` tag their number — the unschedulable-pod
  marker's scan runs a thousand solves and must not put a thousand
  spans into ``/traces``;
- the *profiler bridge*: while a JAX profiler session is active in the
  process, every span of a trace also opens a
  ``jax.profiler.TraceAnnotation("sched.<name>")`` for its lifetime, so
  the program's phases lie on the profiler's timeline beside the
  device's operations.  The tracer asks once per root span
  (``TraceMe.is_enabled()``, ~135 ns); the trace's spans inherit the
  answer, and with no session nothing is built.

Beside its wall time a span records, as tags written at its exit, what
the wall alone cannot say of a slow request:

- ``cpuMs``: the CPU time of the span's own thread between enter and
  exit (``time.thread_time_ns``), on the spans whose call site asks for
  it (``cpu=True``: ``fifo_gate``).  Wall less CPU is the time the
  thread did not run: waiting for the interpreter's lock, blocked on
  the device or a socket, descheduled by the host.  Not on every span:
  where the thread clock is a system call (20 µs a read on the
  benchmark's host) fifty reads a request cost 5 % end to end.  Left
  out under a virtual clock;
- ``gcMs`` / ``gcRuns``: the collector's pauses that fell inside the
  span, from one ``gc.callbacks`` hook (``install_gc_hook``); a
  collection runs on the thread that tripped it, so the hook books the
  pause to that thread's active span and each exit hands a child's sum
  to its parent.  Written only where a collection ran;
- ``bg``: the names of the background work under way at the span's
  enter or exit or begun in between (``BackgroundTable``): the
  ``background(name)`` markers the server's loops put around one unit
  of their work.  Written only where there was any, and never on a span
  of the marked work itself.
"""

from __future__ import annotations

import gc
import itertools
import sys
import threading
import time
import uuid
from collections import deque
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Tuple

from .. import timesource
from ..analysis import racecheck
from ..analysis.guarded import guarded_by

# the single active-span slot shared by every Tracer (see module doc)
_CURRENT: ContextVar[Optional["Span"]] = ContextVar(
    "k8s_spark_scheduler_tpu_current_span", default=None
)

_SPAN_SEQ = itertools.count(1)

# roots that are one scheduling request; any other root (the marker's
# ``unschedulable.scan``) is background work that request-shaped
# consumers (critical path, lifecycle ledger, SLO latency) must skip
REQUEST_ROOTS = ("http.request", "predicate")

PROFILER_PREFIX = "sched."
_annotations_built = 0

_thread_ns = time.thread_time_ns

# the marker this thread's work runs inside, if any: its own spans take no ``bg``
_MARKED: ContextVar[Optional[str]] = ContextVar(
    "k8s_spark_scheduler_tpu_background_marker", default=None
)

# the aggregate child whose phase runs on this thread: no span is active
# inside a phase, and the collector's hook still has to find whom to charge
_PHASE: ContextVar[Optional["AggregateSpan"]] = ContextVar(
    "k8s_spark_scheduler_tpu_aggregate_phase", default=None
)


@guarded_by("_lock", "_active", "_left", "_ticks")
class BackgroundTable:
    """What runs in the process beside the requests: a name per unit of
    background work under way, and for each name the tick at which one
    last left.  A span reads ``mark`` once at its enter and once at its
    exit, unlocked; only where the two differ, or work is under way,
    does it ask ``since`` for names.  Names are a few constants of the
    code (docs/observability.md lists them), so the table stays small."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active: Dict[str, int] = {}
        self._left: Dict[str, int] = {}
        self._ticks = 0  # enters and leaves so far
        # ``_ticks`` while work is under way, ``-_ticks`` while none is;
        # written under the lock, read without it
        self.mark = 0

    def enter(self, name: str) -> None:
        with self._lock:
            racecheck.note_access(self, "_active")
            self._ticks += 1
            self._active[name] = self._active.get(name, 0) + 1
            self.mark = self._ticks

    def leave(self, name: str) -> None:
        with self._lock:
            racecheck.note_access(self, "_active")
            self._ticks += 1
            left = self._active[name] - 1
            if left:
                self._active[name] = left
            else:
                del self._active[name]
            self._left[name] = self._ticks
            self.mark = self._ticks if self._active else -self._ticks

    def since(self, mark: int) -> set:
        """Names under way now, or that left after ``mark`` was read."""
        tick = abs(mark)
        with self._lock:
            names = set(self._active)
            names.update(name for name, left in self._left.items() if left > tick)
        return names


_BACKGROUND = BackgroundTable()


class background:
    """``with tracing.background("writeback"): ...`` around one unit of a
    loop's work: a name in the process-wide table for its duration, so
    that the spans it overlaps say so in their ``bg`` tag.  No span and
    no trace: the ring's places are the requests'."""

    __slots__ = ("name", "_token")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "background":
        _BACKGROUND.enter(self.name)
        self._token = _MARKED.set(self.name)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        _MARKED.reset(self._token)
        _BACKGROUND.leave(self.name)
        return False


# -- the collector's pauses -----------------------------------------------------

_gc_started = 0.0
# (generation, pause seconds) not yet in a registry: the hook may run
# inside the registry's own lock (any allocation can trip a collection),
# so it never publishes; the reporters' tick drains (as for lock telemetry)
_GC_PENDING: deque = deque(maxlen=4096)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
        return
    if not _gc_started:
        return
    pause = time.perf_counter() - _gc_started
    _gc_started = 0.0
    _GC_PENDING.append((info["generation"], pause))
    if timesource.is_virtual():
        return  # a simulator's trace is virtual end to end
    span = _CURRENT.get() or _PHASE.get()
    if span is not None:
        if span._gc is None:
            span._gc = [pause, 1]
        else:
            span._gc[0] += pause
            span._gc[1] += 1


def install_gc_hook() -> None:
    """Once per process, at wiring time."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def publish_gc_pauses(metrics) -> None:
    """Drain the pauses seen so far into ``metrics``' histogram."""
    from ..metrics import names as mnames

    while _GC_PENDING:
        try:
            generation, pause = _GC_PENDING.popleft()
        except IndexError:  # another drain took it
            return
        metrics.histogram(
            mnames.RUNTIME_GC_PAUSE_TIME, pause, {mnames.TAG_GENERATION: str(generation)}
        )


def annotations_built() -> int:
    """Profiler annotations built so far by the bridge (0 for a process
    that never had a profiler session while it traced)."""
    return _annotations_built


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    active in this process, else None.  Never imports jax: a process
    that has not imported it cannot hold a session."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation
    return annotation if annotation.is_enabled() else None


def current_span() -> Optional["Span"]:
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    span = _CURRENT.get()
    return span.trace_id if span is not None else None


def add_tag(key: str, value: Any) -> None:
    """Tag the active span, if any — safe to call from untraced code."""
    span = _CURRENT.get()
    if span is not None:
        span.tags[key] = value


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def child_span(name: str, tags: Optional[Dict[str, Any]] = None, cpu: bool = False):
    """Span attached to the active trace, or the shared no-op when none
    is active — for library layers (state caches, solvers) that must
    observe request traces but never start root traces of their own.
    ``cpu``: as ``Tracer.span``'s."""
    parent = _CURRENT.get()
    if parent is None:
        return NOOP_SPAN
    span = Span(name, parent.trace_id, parent, cpu)
    parent.children.append(span)
    if tags:
        span.tags.update(tags)
    return span


def aggregate_span(name: str):
    """One phase of the aggregate child ``name`` of the active span (see
    ``Span.aggregate``), or the shared no-op when none is active."""
    parent = _CURRENT.get()
    if parent is None:
        return NOOP_SPAN
    return parent.aggregate(name)


def trace_tag_total(name: str, tag: str):
    """The sum of the numeric tag ``tag`` over the spans named ``name``
    in the active trace so far, 0 outside a trace: what a request's
    earlier phases did, for a later span of the same request to say
    (``fifo_gate``'s ``overheadRows``: the pod slots the request's
    ``mirror.overhead`` refreshes folded)."""
    span = _CURRENT.get()
    if span is None:
        return 0
    while span.parent is not None:
        span = span.parent
    total, todo = 0, [span]
    while todo:
        span = todo.pop()
        if span.name == name:
            total += span.tags.get(tag, 0)
        todo.extend(span.children)
    return total


class Span:
    """One timed phase.  Children attach at creation; duration lands at
    context-manager exit.  Not a dataclass: __slots__ + plain attribute
    writes keep per-span cost to a few hundred ns."""

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent",
        "start_time",
        "duration",
        "tags",
        "children",
        "_t0",
        "_cpu",
        "_c0",
        "_bg0",
        "_gc",
        "_token",
        "_tracer",
        "_bridge",
        "_annotation",
    )

    def __init__(self, name: str, trace_id: str, parent: Optional["Span"], cpu: bool = False):
        self.name = name
        self.trace_id = trace_id
        self.span_id = format(next(_SPAN_SEQ), "x")
        self.parent = parent
        self.start_time = 0.0
        self.duration: Optional[float] = None
        self.tags: Dict[str, Any] = {}
        self.children: List[Span] = []
        self._t0 = 0.0
        # the thread's CPU clock is read only where the call site asks
        self._cpu = cpu
        self._c0: Optional[int] = None
        self._token = None
        self._tracer: Optional["Tracer"] = None
        # the profiler bridge: the annotation class while this trace is
        # bridged (decided once, at the root), else None
        self._bridge = parent._bridge if parent is not None else None
        self._annotation = None
        # [seconds, runs] of the collections that ran inside this span
        # (the hook's and the children's exits write it), else None
        self._gc: Optional[list] = None

    def aggregate(self, name: str) -> "AggregateSpan":
        """The aggregate child ``name`` of this span, made on first use:
        ``with span.aggregate("scan.solve"): ...`` once per phase.  The
        phases must be sequential in the span's own thread."""
        for child in self.children:
            if child.name == name and type(child) is AggregateSpan:
                return child
        child = AggregateSpan(name, self.trace_id, self)
        self.children.append(child)
        return child

    def tag(self, key: str, value: Any) -> "Span":
        self.tags[key] = value
        return self

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "spanId": self.span_id,
            "parentId": self.parent.span_id if self.parent is not None else None,
            "startTime": self.start_time,
            "durationMs": round((self.duration or 0.0) * 1000.0, 4),
            "tags": dict(self.tags),
        }
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    # -- context manager ------------------------------------------------------

    def __enter__(self) -> "Span":
        # semantic instant, not latency: sim traces carry virtual time
        self.start_time = timesource.now()
        self._token = _CURRENT.set(self)
        if self._bridge is not None:
            global _annotations_built
            _annotations_built += 1
            self._annotation = self._bridge(PROFILER_PREFIX + self.name)
            self._annotation.__enter__()
        self._bg0 = _BACKGROUND.mark
        # duration through the same pluggable source family: a sim
        # trace must not mix virtual timestamps with wall durations
        self._t0 = timesource.perf()
        # the CPU clock is read inside the wall's interval, so that a span
        # never reads more CPU than wall
        if self._cpu and not timesource.is_virtual():
            self._c0 = _thread_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._c0 is not None:
            self.tags["cpuMs"] = (_thread_ns() - self._c0) / 1e6
        self.duration = timesource.perf() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if exc is not None and "error" not in self.tags:
            self.tags["error"] = f"{type(exc).__name__}: {exc}"
        mark = _BACKGROUND.mark
        if mark > 0 or mark != self._bg0:
            self._book_background()
        if self._token is not None:
            _CURRENT.reset(self._token)
        # after the reset: a collection from here on is the parent's
        if self._gc is not None:
            self._book_gc()
        if self.parent is None and self._tracer is not None:
            self._tracer._finish_trace(self)
        return False

    def _book_gc(self) -> None:
        """The collections since the last booking into ``gcMs`` /
        ``gcRuns``, and up to the parent."""
        pause, runs = self._gc
        self._gc = None
        tags, parent = self.tags, self.parent
        tags["gcMs"] = round(tags.get("gcMs", 0.0) + pause * 1000.0, 4)
        tags["gcRuns"] = tags.get("gcRuns", 0) + runs
        if parent is not None:
            if parent._gc is None:
                parent._gc = [pause, runs]
            else:
                parent._gc[0] += pause
                parent._gc[1] += runs

    def _book_background(self) -> None:
        """The background work since ``_bg0`` was read into ``bg``; the
        marked work's own spans (the scan's) take none."""
        if _MARKED.get() is not None:
            return
        names = _BACKGROUND.since(self._bg0)
        if "bg" in self.tags:  # an aggregate child's earlier phases
            names.update(self.tags["bg"].split(","))
        if names:
            self.tags["bg"] = ",".join(sorted(names))


class AggregateSpan(Span):
    """One child that stands for every phase of its name under one
    parent: each ``with`` adds the phase's time to ``duration`` and one
    to the ``count`` tag, so the parent's self time stays right and the
    tree stays small.  While a phase runs no span is active — whatever
    the phase calls opens no-op children instead of a thousand real
    ones — and nothing goes to the profiler, and no phase reads the
    thread's CPU clock."""

    __slots__ = ("_phase",)

    def __enter__(self) -> "AggregateSpan":
        if self.duration is None:
            self.start_time = timesource.now()
            self.duration = 0.0
            self.tags["count"] = 0
        self._token = _CURRENT.set(None)
        self._phase = _PHASE.set(self)
        self._bg0 = _BACKGROUND.mark
        self._t0 = timesource.perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.duration += timesource.perf() - self._t0
        self.tags["count"] += 1
        mark = _BACKGROUND.mark
        if mark > 0 or mark != self._bg0:
            self._book_background()
        _PHASE.reset(self._phase)
        _CURRENT.reset(self._token)
        if self._gc is not None:
            self._book_gc()
        return False


class _NoopSpan:
    """Shared do-nothing span: returned by disabled tracers so call
    sites never branch.  tag()/attribute writes are swallowed."""

    __slots__ = ()
    trace_id = None
    span_id = None
    tags: Dict[str, Any] = {}

    def tag(self, key: str, value: Any) -> "_NoopSpan":
        return self

    def aggregate(self, name: str) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


@guarded_by("_lock", "_ring", "_finished")
class Tracer:
    """Span factory + bounded ring of completed traces.

    ``span(name)`` opens a child of the active span, or a new root (and
    therefore a new trace) when none is active.  When a root span exits,
    the whole tree is serialized and appended to the ring; with a
    registry every span's duration is recorded as a tagged histogram so
    /metrics carries per-phase latency distributions without reading traces.
    """

    def __init__(self, capacity: int = 256, enabled: bool = True, metrics=None):
        self.enabled = enabled
        self._ring: deque = deque(maxlen=capacity)
        # total completed traces ever — cursor for completed_since();
        # the ring holds the most recent len(_ring) of them
        self._finished = 0
        self._lock = threading.Lock()
        self._metrics = metrics
        # trace-completion observers (e.g. the critical-path analyzer):
        # called with the live root Span after the tree lands in the
        # ring, outside the ring lock.  Wiring-time append only.
        self._observers: list = []

    # -- span creation --------------------------------------------------------

    def span(
        self,
        name: str,
        tags: Optional[Dict[str, Any]] = None,
        trace_id: Optional[str] = None,
        cpu: bool = False,
    ):
        """Context manager for one phase.  ``trace_id`` is honored only
        when this span starts a new trace (no active parent).  ``cpu``:
        the span also reads its thread's CPU clock and tags ``cpuMs``;
        for the few spans something reads it on (the clock can be a
        system call)."""
        if not self.enabled:
            return NOOP_SPAN
        parent = _CURRENT.get()
        if parent is not None:
            span = Span(name, parent.trace_id, parent, cpu)
            parent.children.append(span)
        else:
            span = Span(name, trace_id or new_trace_id(), None, cpu)
            span._tracer = self
            span._bridge = _profiler_annotation()
        if tags:
            span.tags.update(tags)
        return span

    # -- completed traces -----------------------------------------------------

    def _finish_trace(self, root: Span) -> None:
        trace = {
            "traceId": root.trace_id,
            "startTime": root.start_time,
            "durationMs": round((root.duration or 0.0) * 1000.0, 4),
            "root": root.to_dict(),
        }
        with self._lock:
            self._ring.append(trace)
            self._finished += 1
        if self._metrics is not None:
            from ..metrics import names as mnames

            stack = [root]
            while stack:
                span = stack.pop()
                self._metrics.histogram(
                    mnames.TRACE_SPAN_TIME,
                    span.duration or 0.0,
                    {mnames.TAG_SPAN: span.name},
                )
                stack.extend(span.children)
        for observer in self._observers:
            try:
                observer(root)
            except Exception:  # an observer must never break a request
                pass

    def add_observer(self, fn) -> None:
        """Register a trace-completion callback ``fn(root_span)``.
        Call at wiring time only — the list is read unlocked."""
        self._observers.append(fn)

    @property
    def completed_total(self) -> int:
        """Total traces ever completed (monotonic drain cursor)."""
        with self._lock:
            return self._finished

    def completed_since(self, cursor: int) -> Tuple[List[dict], int]:
        """Traces completed after ``cursor`` (oldest first, truncated
        to the ring's reach) and the new cursor value.  Pull-based
        alternative to add_observer for consumers that must never run
        inside a request — the lifecycle ledger drains here off-thread
        because for direct predicate calls the root span closes (and
        observers fire) while the predicate lock is still held."""
        with self._lock:
            total = self._finished
            fresh = total - cursor
            if fresh <= 0:
                return [], total
            n = min(fresh, len(self._ring))
            if n == 0:
                return [], total
            out = list(self._ring)[-n:]
        return out, total

    def traces(self, limit: Optional[int] = None) -> List[dict]:
        """Completed traces, newest first."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        if limit is not None:
            out = out[: max(limit, 0)]
        return out

    def find_by_tag(self, key: str, value: Any) -> Optional[dict]:
        """Newest completed trace with ``tags[key] == value`` on any
        span in the tree."""
        for trace in self.traces():
            if _tree_has_tag(trace["root"], key, value):
                return trace
        return None

    def find_by_trace_id(self, trace_id: str) -> Optional[dict]:
        for trace in self.traces():
            if trace["traceId"] == trace_id:
                return trace
        return None

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def _tree_has_tag(span_dict: dict, key: str, value: Any) -> bool:
    if span_dict.get("tags", {}).get(key) == value:
        return True
    return any(
        _tree_has_tag(c, key, value) for c in span_dict.get("children", ())
    )


def render_trace_text(trace: dict, events: Optional[List[Tuple[str, dict]]] = None) -> str:
    """Human-readable span tree (the /debug/schedule payload): one line
    per span with duration, indented by depth, tags inline; correlated
    events appended."""
    lines = [
        f"trace {trace['traceId']}  start={time.strftime('%Y-%m-%dT%H:%M:%S', time.gmtime(trace['startTime']))}Z"
        f"  total={trace['durationMs']:.3f}ms"
    ]

    def walk(span: dict, depth: int) -> None:
        tags = span.get("tags", {})
        tag_str = " ".join(f"{k}={v}" for k, v in sorted(tags.items(), key=lambda kv: kv[0]))
        lines.append(
            f"{'  ' * depth}- {span['name']}  {span['durationMs']:.3f}ms"
            + (f"  [{tag_str}]" if tag_str else "")
        )
        for child in span.get("children", ()):
            walk(child, depth + 1)

    walk(trace["root"], 1)
    if events:
        lines.append("events:")
        for name, values in events:
            lines.append(f"  - {name} {values}")
    return "\n".join(lines) + "\n"


# module-level default (swappable for tests; the server wires its own)
default_tracer = Tracer()
