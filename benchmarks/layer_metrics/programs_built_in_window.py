"""Programs the backend built or loaded INSIDE the window:
Δ`programs_built` of `/stats` (every `backend_compile_duration` event
of the process, whichever thread and span it interrupted;
`recent_compiles` names the last sixteen). A launch whose shapes are
its own (a long history's exact length, before the LONG keys) builds
its programs when it comes, on the dispatcher thread, and neither
`shape_misses` nor `keys_met_in_window` counts them: this does. 0 once
every program the window's launches ask for was built before it."""

from benchmarks.layer_metrics import delta

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"programs_built": 31},
           "stats_after": {"programs_built": 34},
           "want": 3}


def read(ctx):
    if "programs_built" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    return delta(ctx, "stats", "programs_built")
