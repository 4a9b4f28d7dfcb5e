"""Milliseconds an admitted request waited for its WAL record: span
`journal.append`, from building the submit record to the return of the
group commit that covers it, on the handler thread."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"journal.append": {"n": 10, "s": 2.0}},
           "spans_after": {"journal.append": {"n": 50, "s": 26.0}},
           "want": 600.0}


def read(ctx):
    return mean_ms(ctx, "journal.append")
