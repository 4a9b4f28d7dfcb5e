"""Wall seconds the set-up waited for programs: span `build.ahead`'s
seconds at the window's START (graftd's start, which builds the keys of
the host's record, and the warm-up's launches that met a key first).

The set-up readers (`setup_*`) read the program's totals where the
window opens, `ctx["before"]["stats"]`: `/stats` is cumulative, and
everything before the window is set-up. They give nothing for a run
whose window served no batch (such a run is not a run of the cell, and
its `setup_s` is not compared either), nothing from a program that does
not serve the build stages (no span `build.trace`: a parent commit), and
0.0 for a stage that never ran where the program serves the stages."""

EXAMPLE = {"stats_before": {"batches": 40}, "stats_after": {"batches": 240},
           "spans_before": {"build.trace": {"n": 260, "s": 21.0},
                            "build.ahead": {"n": 260, "s": 31.5}},
           "spans_after": {"build.trace": {"n": 260, "s": 21.0},
                           "build.ahead": {"n": 260, "s": 31.5}},
           "want": 31.5}


def stats_at_start(ctx):
    """The program's `/stats` at the window's start; None for a run
    whose window served no batch."""
    before, after = ctx["before"]["stats"], ctx["after"]["stats"]
    if after.get("batches", 0) == before.get("batches", 0):
        return None
    return before


def spans_at_start(ctx):
    """The program's span totals at the window's start; None where a
    set-up reader has nothing to read (module docstring)."""
    spans = (stats_at_start(ctx) or {}).get("spans")
    if not spans or "build.trace" not in spans:
        return None
    return spans


def seconds_at_start(ctx, *names):
    """Seconds of the spans `names` at the window's start, summed."""
    spans = spans_at_start(ctx)
    if spans is None:
        return None
    return sum(spans.get(n, {"s": 0.0})["s"] for n in names)


def read(ctx):
    return seconds_at_start(ctx, "build.ahead")
