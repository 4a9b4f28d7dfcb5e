"""Seconds in which an operation ran on the device in the traced span, for each row that
a kernel tier (dense, mask, sort) decided in that span."""

EXAMPLE = {"trace": {"busy_s": 0.5, "window_s": 8.0, "kernel_rows": 250,
                     "device_ops": [["while.17", 0.4]]},
           "want": 2.0}


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["kernel_rows"] or not trace["busy_s"]:
        return None
    return 1e3 * trace["busy_s"] / trace["kernel_rows"]
