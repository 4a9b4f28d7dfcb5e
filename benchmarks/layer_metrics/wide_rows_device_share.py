"""Of the rows that entered the kernel ladder with a WIDE window (past
`SEGMENT_MAX_SLOTS` 10 of `ops/kernel_ir.py`: wider than the reference's
crash-free workloads reach, held by the plain dense family alone), the
share that the device decided: 100 x (1 - Δ`wide_rows_host` /
Δ`wide_rows`) of `/stats`. A row a host engine decided (`auto`'s DFS
budget before the device pass, `_check_dfs` / `_check_cpu` after it)
ran on the dispatcher thread while the batch waited. Nothing where no
wide row came."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"wide_rows": 10, "wide_rows_host": 2},
           "stats_after": {"wide_rows": 210, "wide_rows_host": 12},
           "want": 95.0}


def read(ctx):
    if "wide_rows" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counters
    wide = delta(ctx, "stats", "wide_rows")
    if not wide:
        return None
    return 100.0 * (1.0 - delta(ctx, "stats", "wide_rows_host") / wide)
