"""Seconds in which an operation ran on the device in the traced span, for each row that
a kernel tier (dense, mask, sort) decided in that span."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["kernel_rows"] or not trace["busy_s"]:
        return None
    return 1e3 * trace["busy_s"] / trace["kernel_rows"]
