"""Share of the window's wall time the dispatcher thread was blocked in
`queue.take` with nothing to form a batch from: span `dispatch.take`."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"dispatch.take": {"n": 5, "s": 1.0}},
           "spans_after": {"dispatch.take": {"n": 25, "s": 1.2}},
           "want": 0.5}


def read(ctx):
    return share(ctx, "dispatch.take")
