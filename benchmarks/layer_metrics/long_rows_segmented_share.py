"""Of the LONG rows that entered `check_encoded` in the window (at
least `LONG_HISTORY_MIN_EVENTS` 8,192 events of `ops/segment_scan.py`),
the share the segment route decided (`decided-tier` `dense-seg`): 100 x
Δ`long_rows_segmented` / Δ`long_rows` of `/stats`. What is left went to
the chunked wavefront as a LONG launch (or, past the dense caps, to the
ladder). Nothing where no long row came."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"long_rows": 4, "long_rows_segmented": 4},
           "stats_after": {"long_rows": 14, "long_rows_segmented": 12},
           "want": 80.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if "long_rows" not in stats or "long_rows_segmented" not in stats:
        return None  # a program that does not serve the counters
    rows = delta(ctx, "stats", "long_rows")
    if not rows:
        return None
    return 100.0 * delta(ctx, "stats", "long_rows_segmented") / rows
