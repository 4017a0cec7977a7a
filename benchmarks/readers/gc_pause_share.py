"""Share of the window the serving process spent in the collector
(``gc.callbacks`` start to stop)."""


def read(context):
    if context["window_s"] <= 0:
        return None
    return 100.0 * context["gc_pause_s"] / context["window_s"]
