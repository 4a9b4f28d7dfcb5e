"""Share of the window's wall time the dispatcher thread spent in the
host certifier's lane before a launch: span `dispatch.scan`, one of the
nine that tile its loop (`certify_busy_share` reads the same work by a
clock pair inside `lin_fastpath_pass`). 0 where the cost gate kept
every request out of the lane."""

from benchmarks.layer_metrics._spans import share

ZERO_IS_A_READING = True

EXAMPLE = {"spans_before": {"dispatch.scan": {"n": 10, "s": 0.5}},
           "spans_after": {"dispatch.scan": {"n": 110, "s": 2.5}},
           "want": 5.0}


def read(ctx):
    if ctx["after"]["stats"].get("spans") is None:
        return None  # a program that serves no spans
    return share(ctx, "dispatch.scan") or 0.0
