"""Share of the window's wall time the dispatcher thread spent in the
SEGMENT route: span `launch.segment` (`ops/segment_scan.py`
`check_segmented_batch`: the plan's cuts on the host, one kernel over
all the segments of a launch's long rows, the host's composition of
their tables), nested inside the `launch.device` tile. 0 where no long
row took the route (a CPU rehearsal's rows are short, and off the TPU
`auto` keeps a long one on the chunked route); nothing from a program
that does not serve the counters that came with the span."""

from benchmarks.layer_metrics._spans import share

ZERO_IS_A_READING = True

EXAMPLE = {"stats_before": {"long_rows": 3, "long_rows_segmented": 3},
           "stats_after": {"long_rows": 11, "long_rows_segmented": 11},
           "spans_before": {"launch.segment": {"n": 3, "s": 9.0}},
           "spans_after": {"launch.segment": {"n": 11, "s": 33.0}},
           "want": 60.0}


def read(ctx):
    stats = ctx["after"]["stats"]
    if stats.get("spans") is None or "long_rows" not in stats \
            or "long_rows_segmented" not in stats:
        return None  # a program without the span
    return share(ctx, "launch.segment") or 0.0
