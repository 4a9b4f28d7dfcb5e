"""Milliseconds from the end of a request's own scan on the dispatcher
thread to the start of the launch it rides: the other requests' scans
and the linger. Span `request.formation_wait`, from the request's own
stamps."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"request.formation_wait": {"n": 8, "s": 20.0}},
           "spans_after": {"request.formation_wait": {"n": 48, "s": 160.0}},
           "want": 3500.0}


def read(ctx):
    return mean_ms(ctx, "request.formation_wait")
