"""Of the rows offered to the host certifier in the window (scanned, or
routed kernel-first by its cost gate), the share whose host verdict the
caller used: Δ`rows_delivered` over Δ(`rows_scanned` + `rows_gated`) of
the program's `lin_fastpath` counters. 0 where rows were offered and
the lane delivered none (a closed gate, or the all-or-nothing rule
discarding every partly certified request); nothing where none were
offered."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"fastpath_before": {"rows_scanned": 64, "rows_gated": 0,
                               "rows_delivered": 8},
           "fastpath_after": {"rows_scanned": 192, "rows_gated": 672,
                              "rows_delivered": 48},
           "want": 5.0}


def read(ctx):
    if "rows_delivered" not in ctx["after"]["fastpath"]:
        return None  # a program that does not serve the counters
    offered = delta(ctx, "fastpath", "rows_scanned") \
        + delta(ctx, "fastpath", "rows_gated")
    if not offered:
        return None
    return 100.0 * delta(ctx, "fastpath", "rows_delivered") / offered
