"""Milliseconds of `fsync` the WAL paid for each submit record: span
`journal.fsync` (in the commit leader, whatever records the group held)
over the count of `journal.append`. What is left of `journal_append_ms`
is the record's own building, its write and the wait for a leader."""

from benchmarks.layer_metrics._spans import N, S

EXAMPLE = {"spans_before": {"journal.append": {"n": 10, "s": 2.0},
                            "journal.fsync": {"n": 18, "s": 1.0}},
           "spans_after": {"journal.append": {"n": 50, "s": 26.0},
                           "journal.fsync": {"n": 90, "s": 9.0}},
           "want": 200.0}


def read(ctx):
    n, s = N(ctx, "journal.append"), S(ctx, "journal.fsync")
    return None if n is None or s is None else 1e3 * s / n
