"""Share of the window's wall time the dispatcher thread spent deciding
rows on the HOST: span `launch.escalate` (the host engines of
`algorithm="auto"`: the DFS budget it spends on a window past 12 before
the device pass, `_check_dfs` / `_check_cpu` on what that pass left),
nested inside the `launch.device` tile. 0 where no row met a host
engine; nothing from a program that does not serve the counters that
came with the span."""

from benchmarks.layer_metrics._spans import share

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"wide_rows_host": 0},
           "stats_after": {"wide_rows_host": 3},
           "spans_before": {"launch.escalate": {"n": 1, "s": 0.5}},
           "spans_after": {"launch.escalate": {"n": 4, "s": 2.5}},
           "want": 5.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if stats.get("spans") is None or "wide_rows_host" not in stats:
        return None  # a program without the span
    return share(ctx, "launch.escalate") or 0.0
