"""Span-based tracing for the scheduling hot path.

The reference scheduler runs inside the witchcraft runtime, which gives
every request a zipkin-style trace (trc1 log lines, span ids on every
log statement).  This package is that runtime's analog for the
reproduction: lightweight in-process spans with parent/child links and
tags, a bounded ring of completed traces served over ``GET /traces``,
and kernel-level profiling hooks that split JAX solver time into
trace/compile vs execute (``tracing.profiling``).

Design constraints (a served Filter is 7-35 ms end to end at 10,000
nodes and opens 12-30 spans):

- a span is a handful of attribute writes and one ``perf_counter`` pair
  (about 2 µs with its place in the ring; tests/test_perf_guard.py
  holds a 24-span tree under 500 µs).  At its exit it tags itself with
  what the wall alone cannot say, where there is any: ``gcMs`` /
  ``gcRuns`` and ``bg``; and ``cpuMs``, one ``thread_time_ns`` pair,
  only where the call site asks (``cpu=True``: ``fifo_gate``), because
  that clock is a 20 µs system call on some hosts
  (docs/observability.md, "What a span says of the runtime");
- context propagation uses one ``contextvars.ContextVar`` shared by all
  tracers, so events/logs can stamp ``trace_id`` without knowing which
  tracer opened the trace;
- a disabled tracer returns a shared no-op context manager (zero
  allocation), so tracing can never regress an untraced deployment —
  enforced by tests/test_perf_guard.py.
"""

from .spans import (  # noqa: F401
    NOOP_SPAN,
    REQUEST_ROOTS,
    AggregateSpan,
    Span,
    Tracer,
    add_tag,
    aggregate_span,
    annotations_built,
    background,
    child_span,
    current_span,
    current_trace_id,
    default_tracer,
    install_gc_hook,
    publish_gc_pauses,
    trace_tag_total,
)
