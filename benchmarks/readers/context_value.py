"""A number the harness took itself (``setup_s``)."""


def read(context, key):
    return context.get(key)
