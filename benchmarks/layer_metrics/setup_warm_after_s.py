"""Seconds from graftd's construction to `/stats` `warm`: `warm_after_s`
(journal replay, the first touch of the devices, the tuner's plans, the
host's record of keys and the wait for their programs: spans `start.*`
and `build.ahead`). What a restart pays before its first launch. Nothing
from a program that does not serve it, and nothing for a run whose
window served no batch (`setup_build_wait_s` says why)."""

from benchmarks.layer_metrics.setup_build_wait_s import stats_at_start

EXAMPLE = {"stats_before": {"batches": 40, "warm_after_s": 12.5},
           "stats_after": {"batches": 240, "warm_after_s": 12.5},
           "want": 12.5}


def read(ctx):
    return (stats_at_start(ctx) or {}).get("warm_after_s")
