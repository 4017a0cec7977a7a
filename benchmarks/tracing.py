"""What a ``--trace 1`` run adds to the process: the profiler over a
sub-window of whole blocks, ``client.*`` annotations around the client's
steps, an observer that keeps each request's span times, and the
collector's pauses.  None of it exists in a ``--trace 0`` run.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence

import trace_reduce
from traffic import answers
from traffic import kinds as traffic_kinds


def span_times(root) -> Dict[str, object]:
    """One finished request as plain numbers: per span name the summed
    duration and self time (duration less the children's) in ms, and the
    tags of its ``fifo_gate`` span."""
    total: Dict[str, float] = {}
    self_ms: Dict[str, float] = {}
    tags: Dict[str, object] = {}
    todo = [root]
    while todo:
        span = todo.pop()
        ms = (span.duration or 0.0) * 1e3
        kids = sum((c.duration or 0.0) for c in span.children) * 1e3
        total[span.name] = total.get(span.name, 0.0) + ms
        self_ms[span.name] = self_ms.get(span.name, 0.0) + max(ms - kids, 0.0)
        if span.name == "fifo_gate":
            tags = dict(span.tags)
        todo.extend(span.children)
    return {"total": total, "self": self_ms, "fifo_gate": tags}


class Tracing:
    START_COSTS_S = 6.0  # the profiler's start and the block thrown away after it, about

    def __init__(self, stack, trace_seconds: float):
        import jax

        self._jax = jax
        self._trace_seconds = trace_seconds
        self._dir = tempfile.mkdtemp(prefix="trace-")  # under TMPDIR; removed after the reduction
        self._profiling = False
        self._recording = False
        self.requests: Dict[str, Dict[str, object]] = {}
        self.events: List[dict] = []
        self._gc_started: Optional[float] = None
        self.gc_pause_s = 0.0
        self.window_open: Optional[float] = None
        self.window_close: Optional[float] = None
        self.traced_until: Optional[float] = None
        stack.scheduler.tracer.add_observer(self._on_trace)
        gc.callbacks.append(self._on_gc)

    def annotate(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)

    def _on_trace(self, root) -> None:
        if self._recording:
            self.requests[root.trace_id] = span_times(root)

    def _on_gc(self, phase: str, info: dict) -> None:
        if not self._recording:
            return
        if phase == "start":
            self._gc_started = time.perf_counter()
        elif self._gc_started is not None:
            self.gc_pause_s += time.perf_counter() - self._gc_started
            self._gc_started = None

    def start_profiler(self) -> None:
        """Before the window: the profiler's own start-up is slow and falls
        on a block that the caller runs next and throws away."""
        options = self._jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the client's steps are annotated; Python frames are not wanted
        self._jax.profiler.start_trace(self._dir, profiler_options=options)
        self._profiling = True

    def open_window(self) -> None:
        self._recording = True
        self.window_open = time.perf_counter()

    def on_block(self, rec, since_open: float) -> None:
        if self._profiling and since_open >= self._trace_seconds:
            self._stop_profiler(rec.end)

    def _stop_profiler(self, at: float) -> None:
        self._jax.profiler.stop_trace()
        self._profiling = False
        self.traced_until = at

    def close_window(self) -> None:
        self.window_close = time.perf_counter()
        if self._profiling:
            self._stop_profiler(self.window_close)
        self._recording = False
        gc.callbacks.remove(self._on_gc)

    def context(self, window: Sequence) -> Dict[str, object]:
        """What a traced run adds to ``metrics.window_context`` for the
        per-layer readers, as plain data."""
        try:
            self.events = trace_reduce.load_events(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        reduced = trace_reduce.reduce(self.events)
        kinds = {
            answer[1]: kind for kind in traffic_kinds(window) for answer in answers(window, kind)
        }
        return {
            "requests": self.requests,
            "kinds": kinds,
            "window_s": self.window_close - self.window_open,
            "gc_pause_s": self.gc_pause_s,
            "trace": reduced,
            "busy_s": reduced["busy_s"],
            "traced_window_s": reduced["window_s"],
            "breakdown": {
                "device_ops": trace_reduce.top(reduced["op_seconds"]),
                "idle_gaps": trace_reduce.top(reduced["idle_gaps"]),
            },
        }
