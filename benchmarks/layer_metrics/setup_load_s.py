"""Thread-seconds the set-up's builds spent in the backend for programs
the persistent compile cache GAVE: span `build.load` at the window's
start. 0.0 on a run that keeps no cache (a rehearsal) and on a
checkout's first. `setup_build_wait_s` says what a set-up reader reads
and where it gives nothing."""

from benchmarks.layer_metrics.setup_build_wait_s import seconds_at_start

EXAMPLE = {"stats_before": {"batches": 40}, "stats_after": {"batches": 240},
           "spans_before": {"build.trace": {"n": 260, "s": 18.0},
                            "build.load": {"n": 258, "s": 26.25}},
           "spans_after": {"build.trace": {"n": 260, "s": 18.0},
                           "build.load": {"n": 258, "s": 26.25}},
           "want": 26.25}


def read(ctx):
    return seconds_at_start(ctx, "build.load")
