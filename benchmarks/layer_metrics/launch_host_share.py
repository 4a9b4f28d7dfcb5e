"""Share of the window's wall time spent in a launch's check outside
the wavefront: grouping, packing, building the launches, the result
dicts, any host escalation. Span `launch.host`."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"launch.host": {"n": 2, "s": 0.2}},
           "spans_after": {"launch.host": {"n": 8, "s": 1.0}},
           "want": 2.0}


def read(ctx):
    return share(ctx, "launch.host")
