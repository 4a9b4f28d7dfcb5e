"""Programs a launch built in the window after its key's launch-shape
set had been built: Δ`shape_misses` of `/stats`. 0 while the set
(`checker/schedule.launch_shapes`) names every program a launch asks
for; `recent_shape_misses` names the program, key, rows and width of
one that it did not."""

from benchmarks.layer_metrics import delta

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"shape_misses": 1},
           "stats_after": {"shape_misses": 3},
           "want": 2}


def read(ctx):
    if "shape_misses" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    return delta(ctx, "stats", "shape_misses")
