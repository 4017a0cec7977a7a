"""Device time of the operations whose name contains the configuration's
``queue_kernel``, per driver Filter of the traced window, from the
profiler trace."""


def seconds_and_calls(context):
    trace = context["trace"]
    kernel = context["config"]["queue_kernel"]
    seconds = sum(s for name, s in trace["op_seconds"].items() if kernel in name.split("/")[-1])
    calls = trace["client_calls"].get("client.filter_driver", 0)
    return seconds, calls


def read(context):
    seconds, calls = seconds_and_calls(context)
    if seconds <= 0 or calls <= 0:
        return None
    return 1e3 * seconds / calls
