"""Share of the requests completed in the window that the fast lane
answered: certified whole on the dispatcher thread, never in a batch."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"completed": 8, "fastpath_requests": 1},
           "stats_after": {"completed": 48, "fastpath_requests": 11},
           "want": 25.0}


def read(ctx):
    n = delta(ctx, "stats", "completed")
    return 100.0 * delta(ctx, "stats", "fastpath_requests") / n if n else None
