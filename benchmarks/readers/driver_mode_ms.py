"""A time in ms over the window's drivers, all of them or the slow ones
(``driver_mode_share``: over twice the median client-side time): the
client-side time itself, the time in named spans, or one of three
readings of the ``fifo_gate`` span's runtime tags:

``gate_cpu``     its ``cpuMs``: the CPU time of the request's thread inside it
``gate_offcpu``  its duration less its ``cpuMs`` less ``device.wait``'s
                 duration: what the thread neither ran nor waited on the
                 device for (the interpreter's lock, the host)
``gate_gc``      its ``gcMs``, 0 where no collection ran inside it
"""

from statistics import mean, median

import plugins

population = plugins.load("readers", "driver_mode_share").population

STATISTICS = {"median": median, "mean": mean}


def gate_value(req, quantity):
    gate = req["fifo_gate"]
    if quantity == "gate_gc":
        return float(gate.get("gcMs", 0.0))
    if "cpuMs" not in gate or "fifo_gate" not in req["total"]:
        return None
    if quantity == "gate_cpu":
        return float(gate["cpuMs"])
    if quantity == "gate_offcpu":
        return req["total"]["fifo_gate"] - float(gate["cpuMs"]) - req["total"].get("device.wait", 0.0)
    raise ValueError(f"quantity {quantity!r}")


def read(context, quantity=None, spans=None, among="all", statistic="median"):
    """``quantity``: ``client``, ``gate_cpu``, ``gate_offcpu`` or
    ``gate_gc``; or ``spans``: span names whose total durations are
    summed per request (a request with none of them is left out)."""
    of = population(context, among)
    if not of:
        return None
    if spans is not None:
        values = [
            sum(req["total"][s] for s in spans if s in req["total"])
            for _, req in of if any(s in req["total"] for s in spans)
        ]
    elif quantity == "client":
        values = [ms for ms, _ in of]
    else:
        values = [v for v in (gate_value(req, quantity) for _, req in of) if v is not None]
    return STATISTICS[statistic](values) if values else None
