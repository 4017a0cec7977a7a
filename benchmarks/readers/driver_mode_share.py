"""Share, in percent, of the window's drivers that are in a mode, among
all of them or among the slow ones.

A driver is **slow** where its client-side time is over twice the median
of the window's drivers (p95 is 2.7-4.1 times the median in every
``drivers`` cell, so the line falls between the two modes of the
latency), and **beside** a named piece of background work where the
``bg`` tag of its ``fifo_gate`` span holds the name (``unschedulable.scan``,
``writeback``, ...: the program's tracer writes, comma-joined, what ran in
the process while the span was open).

The tags are read from ``fifo_gate`` because ``tracing.span_times`` keeps
that span's tags and no other's.  A program whose gates carry no ``cpuMs``
(the tag the gate has since the tracer records the runtime) cannot say
what ran beside a request: nothing is read, and every metric of the two
modes is left out, not 0.  A share among the slow ones is an overlap,
not a cause: a request three times longer runs beside any periodic work
more often by its length alone, so each is read against the same share
among all drivers."""

from statistics import median

from traffic import answers

SLOW_OVER_MEDIAN = 2.0


def drivers(context):
    """``[(client-side ms, the request's span times)]`` of the window's
    traced drivers, or None on a program whose spans do not record the
    runtime."""
    found = [
        (answer[0] * 1e3, context["requests"][answer[1]])
        for answer in answers(context["window"], "driver")
        if answer[1] in context["requests"]
    ]
    if not any("cpuMs" in req["fifo_gate"] for _, req in found):
        return None
    return found


def slow(found):
    """Those of ``found`` over twice its median client-side time."""
    if not found:
        return []
    line = SLOW_OVER_MEDIAN * median(ms for ms, _ in found)
    return [(ms, req) for ms, req in found if ms > line]


def beside(req, name):
    return name in str(req["fifo_gate"].get("bg", "")).split(",")


def population(context, among):
    """The drivers a metric is taken over (``all`` or ``slow``), or None."""
    found = drivers(context)
    if found is None:
        return None
    if among == "all":
        return found
    if among == "slow":
        return slow(found)
    raise ValueError(f"among {among!r}: 'all' or 'slow'")


def read(context, mode=None, beside_work=None, among="all"):
    """``mode``: ``slow``; or ``beside_work``: a name of background work."""
    of = population(context, among)
    if not of:
        return None
    if mode == "slow":
        holding = len(slow(of))
    elif mode is None and beside_work is not None:
        holding = sum(1 for _, req in of if beside(req, beside_work))
    else:
        raise ValueError("mode 'slow' or beside_work=<name>")
    return 100.0 * holding / len(of)
