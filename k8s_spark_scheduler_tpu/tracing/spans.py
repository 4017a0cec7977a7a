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
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
import uuid
from collections import deque
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Tuple

from .. import timesource
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


def child_span(name: str, tags: Optional[Dict[str, Any]] = None):
    """Span attached to the active trace, or the shared no-op when none
    is active — for library layers (state caches, solvers) that must
    observe request traces but never start root traces of their own."""
    parent = _CURRENT.get()
    if parent is None:
        return NOOP_SPAN
    span = Span(name, parent.trace_id, parent)
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
        "_token",
        "_tracer",
        "_bridge",
        "_annotation",
    )

    def __init__(self, name: str, trace_id: str, parent: Optional["Span"]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = format(next(_SPAN_SEQ), "x")
        self.parent = parent
        self.start_time = 0.0
        self.duration: Optional[float] = None
        self.tags: Dict[str, Any] = {}
        self.children: List[Span] = []
        self._t0 = 0.0
        self._token = None
        self._tracer: Optional["Tracer"] = None
        # the profiler bridge: the annotation class while this trace is
        # bridged (decided once, at the root), else None
        self._bridge = parent._bridge if parent is not None else None
        self._annotation = None

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
        # duration through the same pluggable source family: a sim
        # trace must not mix virtual timestamps with wall durations
        self._t0 = timesource.perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.duration = timesource.perf() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if exc is not None and "error" not in self.tags:
            self.tags["error"] = f"{type(exc).__name__}: {exc}"
        if self._token is not None:
            _CURRENT.reset(self._token)
        if self.parent is None and self._tracer is not None:
            self._tracer._finish_trace(self)
        return False


class AggregateSpan(Span):
    """One child that stands for every phase of its name under one
    parent: each ``with`` adds the phase's time to ``duration`` and one
    to the ``count`` tag, so the parent's self time stays right and the
    tree stays small.  While a phase runs no span is active — whatever
    the phase calls opens no-op children instead of a thousand real
    ones — and nothing goes to the profiler."""

    __slots__ = ()

    def __enter__(self) -> "AggregateSpan":
        if self.duration is None:
            self.start_time = timesource.now()
            self.duration = 0.0
            self.tags["count"] = 0
        self._token = _CURRENT.set(None)
        self._t0 = timesource.perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.duration += timesource.perf() - self._t0
        self.tags["count"] += 1
        _CURRENT.reset(self._token)
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
    the whole tree is serialized and appended to the ring; optionally
    every span's duration is recorded as a tagged histogram so /metrics
    carries per-phase latency distributions without reading traces.
    """

    def __init__(
        self,
        capacity: int = 256,
        enabled: bool = True,
        metrics=None,
        record_span_metrics: bool = True,
    ):
        self.enabled = enabled
        self._ring: deque = deque(maxlen=capacity)
        # total completed traces ever — cursor for completed_since();
        # the ring holds the most recent len(_ring) of them
        self._finished = 0
        self._lock = threading.Lock()
        self._metrics = metrics
        self._record_span_metrics = record_span_metrics
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
    ):
        """Context manager for one phase.  ``trace_id`` is honored only
        when this span starts a new trace (no active parent)."""
        if not self.enabled:
            return NOOP_SPAN
        parent = _CURRENT.get()
        if parent is not None:
            span = Span(name, parent.trace_id, parent)
            parent.children.append(span)
        else:
            span = Span(name, trace_id or new_trace_id(), None)
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
        if self._metrics is not None and self._record_span_metrics:
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
