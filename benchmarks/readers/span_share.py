"""Share, in percent, of the window's requests of a kind whose trace holds
a named span.  None where no request of the kind was traced.  A share of
0 is a reading only of a program that could have opened the span:
``beside`` names a span that the same program opens on every request of
the kind, and where no request holds that one either, the program knows
neither and the metric is left out."""


def read(context, span, kind, beside=None):
    of_kind = [
        req["total"] for trace_id, req in context["requests"].items()
        if context["kinds"].get(trace_id) == kind
    ]
    if not of_kind:
        return None
    holding = sum(1 for spans in of_kind if span in spans)
    if not holding and beside is not None and not any(beside in spans for spans in of_kind):
        return None
    return 100.0 * holding / len(of_kind)
