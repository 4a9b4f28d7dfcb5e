"""Per cent of the transaction rows decided in the window that a flag of
the closure or a non-cycle anomaly refuted: Δ`txn_rows_flagged` /
Δ`txn_rows` of `/stats`. Each of them is looked at again on the host
at demux for its anomaly's name and witness, so this is the share of
rows that pay that; healthy near the mix's share of invalid histories.
Nothing from a program that does not serve the two counters, nor where
no transaction row came; 0.0 where rows came and none was refuted."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"txn_rows": 64, "txn_rows_flagged": 8},
           "stats_after": {"txn_rows": 704, "txn_rows_flagged": 72},
           "want": 10.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if "txn_rows" not in stats or "txn_rows_flagged" not in stats:
        return None  # a program that does not serve the counters
    n = delta(ctx, "stats", "txn_rows")
    return 100.0 * delta(ctx, "stats", "txn_rows_flagged") / n if n else None
