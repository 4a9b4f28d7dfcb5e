"""Build threads busy a second the set-up waited for programs: the four
build stages' thread-seconds (`build.trace`, `build.lower`, `build.load`,
`build.compile`) over span `build.ahead`'s wall seconds, at the window's
start. Of `BUILD_THREADS` 8; near 1 says a key's builds run one at a
time (the GIL: tracing and lowering are Python). The wait also holds
each program's first execution and its operands' transfer, so it can
read under 1. Nothing where nothing was waited for.
`setup_build_wait_s` says what a set-up reader reads and where it gives
nothing."""

from benchmarks.layer_metrics.setup_build_wait_s import seconds_at_start

EXAMPLE = {"stats_before": {"batches": 40}, "stats_after": {"batches": 240},
           "spans_before": {"build.trace": {"n": 260, "s": 18.0},
                            "build.lower": {"n": 260, "s": 4.5},
                            "build.load": {"n": 258, "s": 26.25},
                            "build.compile": {"n": 2, "s": 3.75},
                            "build.ahead": {"n": 260, "s": 30.0}},
           "spans_after": {"build.trace": {"n": 260, "s": 18.0},
                           "build.lower": {"n": 260, "s": 4.5},
                           "build.load": {"n": 258, "s": 26.25},
                           "build.compile": {"n": 2, "s": 3.75},
                           "build.ahead": {"n": 260, "s": 30.0}},
           "want": 1.75}


def read(ctx):
    waited = seconds_at_start(ctx, "build.ahead")
    if not waited:
        return None
    return seconds_at_start(ctx, "build.trace", "build.lower", "build.load",
                            "build.compile") / waited
