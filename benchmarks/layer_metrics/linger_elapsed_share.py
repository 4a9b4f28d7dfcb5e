"""Of the window's launches, the part whose linger window had already
passed when the dispatcher took them (`/stats` `lingers_elapsed` over
`batches`): two requests or more that queued longer than the window
behind a busy dispatcher, so it did not wait blind for company again
(only for a submission it could see arriving). A request taken alone
is never counted: its window opens at the take."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"batches": 2, "lingers_elapsed": 1},
           "stats_after": {"batches": 10, "lingers_elapsed": 7},
           "want": 0.75}


def read(ctx):
    if "lingers_elapsed" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    n = delta(ctx, "stats", "batches")
    return delta(ctx, "stats", "lingers_elapsed") / n if n else None
