"""The least time the chip could take for one queue pass of the cell's
shape (``roofline.least_seconds``: the larger of operations over the
compute peak and bytes over the HBM peak) over the kernel's measured
device time per pass."""

import roofline
import plugins

seconds_and_calls = plugins.load("readers", "device_op_ms").seconds_and_calls


def read(context):
    seconds, calls = seconds_and_calls(context)
    if seconds <= 0 or calls <= 0:
        return None
    config = context["config"]
    least = roofline.least_seconds(
        config["reference"]["policy"], config["shape_bucket"]["nodes"],
        config["shape_bucket"]["apps"], context["device"]["kind"],
    )
    return 100.0 * least["seconds"] / (seconds / calls)
