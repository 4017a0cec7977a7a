"""The least time the chip could take for one pass of the single-AZ
minimal-fragmentation rule over the cell's queue
(``single_az_minfrag_roofline.least_seconds``) over the kernel's measured
device time per driver Filter, all launches of the kernel in a Filter
together."""

import plugins
import single_az_minfrag_roofline

seconds_and_calls = plugins.load("readers", "device_op_ms").seconds_and_calls


def read(context):
    seconds, calls = seconds_and_calls(context)
    if seconds <= 0 or calls <= 0:
        return None
    shape = context["config"]["shape_bucket"]
    least = single_az_minfrag_roofline.least_seconds(shape["nodes"], shape["apps"], context["device"]["kind"])
    return 100.0 * least["seconds"] / (seconds / calls)
