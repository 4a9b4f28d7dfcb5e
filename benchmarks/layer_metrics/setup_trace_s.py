"""Thread-seconds of Python in the set-up's builds: spans `build.trace`
(a program traced to a jaxpr) and `build.lower` (the jaxpr to an MLIR
module) at the window's start. The cache spares neither: a program that
loads in 0.1 s is traced and lowered first, one build thread at a time
under the GIL. `setup_build_wait_s` says what a set-up reader reads and
where it gives nothing."""

from benchmarks.layer_metrics.setup_build_wait_s import seconds_at_start

EXAMPLE = {"stats_before": {"batches": 40}, "stats_after": {"batches": 240},
           "spans_before": {"build.trace": {"n": 260, "s": 18.0},
                            "build.lower": {"n": 260, "s": 4.5}},
           "spans_after": {"build.trace": {"n": 260, "s": 18.0},
                           "build.lower": {"n": 260, "s": 4.5}},
           "want": 22.5}


def read(ctx):
    return seconds_at_start(ctx, "build.trace", "build.lower")
