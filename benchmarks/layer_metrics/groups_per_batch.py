"""Window groups a coalesced launch ran (`/stats` `groups_run`, what
`run_chunked` counts), averaged over the window's launches: each group
is a scan of its own on the chip, one after another."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"batches": 2, "groups_run": 6},
           "stats_after": {"batches": 6, "groups_run": 11},
           "want": 1.25}


def read(ctx):
    if "groups_run" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    n = delta(ctx, "stats", "batches")
    return delta(ctx, "stats", "groups_run") / n if n else None
