"""Milliseconds of the dispatcher thread a launch spent inferring its
rows' transaction graphs (span `launch.graph`: nodes, the rt / ww / wr /
rw edges and the non-cycle anomalies of all the launch's rows in one
pass over their concatenated micro-op rows), for each row: the span is
nested in the `launch.host` tile and counts the rows. A program that
serves no such span reads nothing."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"launch.graph": {"n": 64, "s": 0.2}},
           "spans_after": {"launch.graph": {"n": 704, "s": 2.12}},
           "want": 3.0}


def read(ctx):
    return mean_ms(ctx, "launch.graph")
