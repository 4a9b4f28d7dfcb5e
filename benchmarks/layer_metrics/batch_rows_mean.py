"""Rows in a coalesced launch, averaged over the window's launches."""

from benchmarks.layer_metrics import delta


def read(ctx):
    n = delta(ctx, "stats", "batches")
    return delta(ctx, "stats", "batch_rows") / n if n else None
