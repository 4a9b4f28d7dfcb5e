"""Milliseconds of the dispatcher thread a launch spent scanning its
rows' domains and grouping them by window (span `launch.group`) and
packing the groups' macro tensors (span `launch.pack`), for each row
packed: the two are nested in the `launch.host` tile, and `launch.pack`
counts the rows it packed. A program that serves neither span reads
nothing."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"launch.pack": {"n": 400, "s": 0.05},
                            "launch.group": {"n": 400, "s": 0.03}},
           "spans_after": {"launch.pack": {"n": 2400, "s": 0.17},
                           "launch.group": {"n": 2400, "s": 0.07}},
           "want": 0.08}


def read(ctx):
    return mean_ms(ctx, "launch.pack", "launch.group")
