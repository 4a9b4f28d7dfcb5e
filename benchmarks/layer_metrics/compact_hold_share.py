"""Share of the window's wall time a compaction of the WAL held the
journal's lock, so that no handler could append and no request be
acknowledged: span `journal.compact_hold`, a compaction's snapshot of
its index and its last step (the rest of the tail, the fsync, the
replace). The copy between the two holds no lock and is not in it. 0
where no compaction ran in the window (a cell finishes 2,049 requests
before its first); nothing from a program that does not serve the
counters that came with the span."""

from benchmarks.layer_metrics._spans import share

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"journal_compactions": 1},
           "stats_after": {"journal_compactions": 2},
           "spans_before": {"journal.compact_hold": {"n": 2, "s": 0.01}},
           "spans_after": {"journal.compact_hold": {"n": 4, "s": 0.05}},
           "want": 0.1}


def read(ctx):
    stats = ctx["after"]["stats"]
    if stats.get("spans") is None or "journal_compactions" not in stats:
        return None  # a program without the span
    return share(ctx, "journal.compact_hold") or 0.0
