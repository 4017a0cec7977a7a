"""A percentile of the client-side times of all the window's requests of a
kind (``driver``, ``executor``), in ms."""

from traffic import answers, percentile


def read(context, kind, q):
    values = [a[0] * 1e3 for a in answers(context["window"], kind)]
    return percentile(values, q) if values else None
