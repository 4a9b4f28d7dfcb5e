"""Rows in a coalesced launch, averaged over the window's launches."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"batches": 2, "batch_rows": 100},
           "stats_after": {"batches": 6, "batch_rows": 1100},
           "want": 250.0}


def read(ctx):
    n = delta(ctx, "stats", "batches")
    return delta(ctx, "stats", "batch_rows") / n if n else None
