"""Share of the window's wall time inside the chunked wavefront
(`run_chunked`, whole): every device operation of a launch runs in it.
Span `launch.device`."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"launch.device": {"n": 2, "s": 0.8}},
           "spans_after": {"launch.device": {"n": 8, "s": 3.2}},
           "want": 6.0}


def read(ctx):
    return share(ctx, "launch.device")
