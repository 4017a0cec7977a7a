"""Share of the time inside the window's whole blocks that the client spent
outside its Filter calls: creating, reading back, binding, retiring,
building pods and request bodies: the harness's own.  (What falls between
blocks, a traced run's profiler stop, is in neither.)"""


def read(context):
    if context["in_blocks_s"] <= 0:
        return None
    return 100.0 * (context["in_blocks_s"] - context["filter_s"]) / context["in_blocks_s"]
