"""Nodes of the transaction graphs built in the window for each row
they were built for: Δ`txn_nodes` / Δ`txn_rows` of `/stats`. A node is
a transaction that took effect (`ok`, or `info` with an element
somebody observed), so this is the closure's real size under its node
bucket (1,024 for the cell's 1,000-transaction histories). Nothing
from a program that does not serve the two counters, nor where no
transaction row came."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"txn_rows": 64, "txn_nodes": 63_808},
           "stats_after": {"txn_rows": 192, "txn_nodes": 191_424},
           "want": 997.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if "txn_rows" not in stats or "txn_nodes" not in stats:
        return None  # a program that does not serve the counters
    n = delta(ctx, "stats", "txn_rows")
    return delta(ctx, "stats", "txn_nodes") / n if n else None
