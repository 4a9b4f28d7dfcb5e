"""Milliseconds from the dispatch of a launch's closure program to its
flags on the host (span `launch.closure`, nested in `launch.device`:
the edges' transfer, the scatter into the planes, the three closures,
the flags back), for each row closed; on a backend without an
accelerator the span is the host search that decides the same flags.
A program that serves no such span reads nothing."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"launch.closure": {"n": 64, "s": 1.0}},
           "spans_after": {"launch.closure": {"n": 704, "s": 9.0}},
           "want": 12.5}


def read(ctx):
    return mean_ms(ctx, "launch.closure")
