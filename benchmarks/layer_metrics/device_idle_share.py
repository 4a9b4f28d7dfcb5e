"""Share of the traced span in which no operation ran on the device."""

EXAMPLE = {"trace": {"busy_s": 0.5, "window_s": 8.0, "kernel_rows": 250,
                     "device_ops": [["while.17", 0.4]]},
           "want": 93.75}


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
