"""Share of the window's wall time the wavefront was blocked on the
device, reading a round's flags back: span `launch.sync`, nested in
`launch.device`. `launch_share` less this is the host's work inside the
wavefront (slices, puts, recompaction gathers)."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"launch.sync": {"n": 20, "s": 0.4}},
           "spans_after": {"launch.sync": {"n": 80, "s": 2.0}},
           "want": 4.0}


def read(ctx):
    return share(ctx, "launch.sync")
