"""Share of the requests completed in the window that the fast lane
answered: certified whole on the dispatcher thread, never in a batch."""

from benchmarks.layer_metrics import delta


def read(ctx):
    n = delta(ctx, "stats", "completed")
    return 100.0 * delta(ctx, "stats", "fastpath_requests") / n if n else None
