"""fsyncs the WAL issued in the window for each request admitted in
it."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"submitted": 10, "journal_group_commits": 20},
           "stats_after": {"submitted": 50, "journal_group_commits": 80},
           "want": 1.5}


def read(ctx):
    n = delta(ctx, "stats", "submitted")
    return delta(ctx, "stats", "journal_group_commits") / n if n else None
