"""Median over the window's background traces (those that are no request
of the window's) of the seconds in named spans: the program's periodic
work, such as the unschedulable-pod marker's scan."""

from statistics import median


def read(context, root, spans):
    """``root``: the span a trace has to hold to count (the background
    work's root span).  ``spans``: span names whose total durations are
    summed per trace."""
    values = []
    for trace_id, req in context["requests"].items():
        if trace_id in context["kinds"] or root not in req["total"]:
            continue
        found = [req["total"][s] for s in spans if s in req["total"]]
        if found:
            values.append(sum(found) / 1e3)
    return median(values) if values else None
