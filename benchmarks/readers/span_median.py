"""Median over the window's requests of a kind of the time in named spans."""

from statistics import median


def read(context, spans, kind="all", measure="total", lane_prefix=None):
    """``spans``: span names summed per request.  ``measure``: ``total``
    duration or ``self`` time (less the children's).  ``lane_prefix``:
    keep only requests whose ``fifo_gate`` span has a ``lane`` tag that
    starts so."""
    values = []
    for trace_id, req in context["requests"].items():
        if kind != "all" and context["kinds"].get(trace_id) != kind:
            continue
        if kind == "all" and trace_id not in context["kinds"]:
            continue
        if lane_prefix is not None and not str(req["fifo_gate"].get("lane", "")).startswith(lane_prefix):
            continue
        found = [req[measure][s] for s in spans if s in req[measure]]
        if found:
            values.append(sum(found))
    return median(values) if values else None
