"""Check units graftd admitted for each history it admitted in the
window: Δ`units_admitted` / Δ`histories_admitted` of `/stats`. 1.0
where every history is checked whole; the number of keys where the
workload is split by key (`multi-register`: one unit a key, labels
`h{i}/key={k}`). Nothing from a program that does not serve the two
counters, nor where no history was admitted."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"histories_admitted": 24, "units_admitted": 2400},
           "stats_after": {"histories_admitted": 64, "units_admitted": 6400},
           "want": 100.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if "histories_admitted" not in stats or "units_admitted" not in stats:
        return None  # a program that does not serve the counters
    n = delta(ctx, "stats", "histories_admitted")
    return delta(ctx, "stats", "units_admitted") / n if n else None
