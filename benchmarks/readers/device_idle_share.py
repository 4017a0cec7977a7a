"""1 - (union of the device's operation intervals) / traced window, from
the profiler trace."""


def read(context):
    trace = context["trace"]
    if not trace["device_planes"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
