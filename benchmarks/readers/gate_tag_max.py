"""Largest value over the window's requests of a kind of a numeric tag of
their ``fifo_gate`` span: the reading of a running total the program
keeps (``requestCompiles``), as the window's last request saw it."""


def read(context, tag, kind="driver"):
    values = [
        req["fifo_gate"].get(tag)
        for trace_id, req in context["requests"].items()
        if context["kinds"].get(trace_id) == kind
    ]
    values = [v for v in values if isinstance(v, (int, float)) and not isinstance(v, bool)]
    return float(max(values)) if values else None
