"""Thread-seconds the set-up's builds spent while XLA compiled programs
FROM SOURCE: span `build.compile` at the window's start. Healthy 0.0 on
every run but a checkout's first; anything else is a cold cache, a tree
whose programs changed, or a cache read lost to the cache's file lock
(JAX warns `Error reading persistent compilation cache entry` and
compiles). `setup_build_wait_s` says what a set-up reader reads and
where it gives nothing."""

from benchmarks.layer_metrics.setup_build_wait_s import seconds_at_start

EXAMPLE = {"stats_before": {"batches": 40}, "stats_after": {"batches": 240},
           "spans_before": {"build.trace": {"n": 260, "s": 18.0},
                            "build.compile": {"n": 2, "s": 3.75}},
           "spans_after": {"build.trace": {"n": 260, "s": 18.0},
                           "build.compile": {"n": 2, "s": 3.75}},
           "want": 3.75}


def read(ctx):
    return seconds_at_start(ctx, "build.compile")
