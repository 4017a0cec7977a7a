"""Mean over the window's requests of a kind of a numeric tag of their
``fifo_gate`` span (which ``tracing.span_times`` keeps per request)."""


def read(context, tag, kind="driver"):
    values = []
    for trace_id, req in context["requests"].items():
        if context["kinds"].get(trace_id) != kind:
            continue
        value = req["fifo_gate"].get(tag)
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            values.append(float(value))
    return sum(values) / len(values) if values else None
