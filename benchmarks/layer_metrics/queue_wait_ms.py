"""Milliseconds from a request's admission to the moment the
dispatcher's `take` returned it: span `request.queue_wait`, from the
request's own stamps."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"request.queue_wait": {"n": 8, "s": 4.0}},
           "spans_after": {"request.queue_wait": {"n": 48, "s": 34.0}},
           "want": 750.0}


def read(ctx):
    return mean_ms(ctx, "request.queue_wait")
