"""Programs that JAX compiled, or fetched from its persistent cache,
between window open and close.  0 is the only sound reading."""


def read(context):
    return context["compiles_in_window"]
