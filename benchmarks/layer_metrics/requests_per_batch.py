"""Requests that rode one coalesced launch, averaged over the window's
launches: Δ`batched_requests` / Δ`batches` of `/stats`. A request of a
split workload is as many rows as its history has keys, so the row cap
of a launch (`DEFAULT_MAX_BATCH_ROWS` 256) fits two 100-key requests
and never a third: healthy near 2 under such a mix, 1.0 where every
request rides alone."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"stats_before": {"batches": 10, "batched_requests": 14},
           "stats_after": {"batches": 50, "batched_requests": 84},
           "want": 1.75}


def read(ctx):
    if "batched_requests" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    n = delta(ctx, "stats", "batches")
    return delta(ctx, "stats", "batched_requests") / n if n else None
