"""fsyncs the WAL issued in the window for each request admitted in
it."""

from benchmarks.layer_metrics import delta


def read(ctx):
    n = delta(ctx, "stats", "submitted")
    return delta(ctx, "stats", "journal_group_commits") / n if n else None
