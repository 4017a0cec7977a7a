"""Pods the scheduler answered for in the window's whole blocks (counted
from the answers, not from what was meant to be asked) over all of the
blocks' time."""


def read(context):
    pods = sum(b.pods for b in context["window"])
    return pods / context["blocks_s"] if pods and context["blocks_s"] > 0 else None
