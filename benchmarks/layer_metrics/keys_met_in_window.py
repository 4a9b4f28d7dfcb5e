"""Keys a launch met first INSIDE the window: Δ`keys_met_by_launch` of
`/stats`. Such a launch waits for its key whole (26 programs: seconds of
a stalled dispatcher, `launch.build` in a traced run's idle gaps, and
requests stranded at the drain cap where the cycle is short); `/stats`
`build_keys` names the key (`met: "launch"`) with its seconds. 0 while
the start's record and the warm-up met every key the window's batches
meet: every run of a checkout but its first few."""

from benchmarks.layer_metrics import delta

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"keys_met_by_launch": 9},
           "stats_after": {"keys_met_by_launch": 10},
           "want": 1}


def read(ctx):
    if "keys_met_by_launch" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    return delta(ctx, "stats", "keys_met_by_launch")
