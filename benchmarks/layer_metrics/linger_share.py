"""Share of the window's wall time the dispatcher thread spent waiting
for company for a batch it had already taken: span `dispatch.linger`,
entered for every batch the linger applies to. 0 where every batch's
window had passed in the queue and no submission was arriving (or the
window's length is 0)."""

from benchmarks.layer_metrics._spans import share

ZERO_IS_A_READING = True

EXAMPLE = {"spans_before": {"dispatch.linger": {"n": 4, "s": 0.2}},
           "spans_after": {"dispatch.linger": {"n": 24, "s": 1.2}},
           "want": 2.5}


def read(ctx):
    if ctx["after"]["stats"].get("spans") is None:
        return None  # a program that serves no spans
    return share(ctx, "dispatch.linger") or 0.0
