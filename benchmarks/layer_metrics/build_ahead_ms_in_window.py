"""Milliseconds launches waited in the window for their key's programs
to be built: the seconds span `build.ahead` added. 0 where the warm-up
met every key the window's batches meet; a key first met inside the
window is built there, whole (26 programs), once a host."""

from benchmarks.layer_metrics._spans import S

ZERO_IS_A_READING = True

EXAMPLE = {"spans_before": {"build.ahead": {"n": 52, "s": 3.0}},
           "spans_after": {"build.ahead": {"n": 78, "s": 4.25}},
           "want": 1250.0}


def read(ctx):
    if ctx["after"]["stats"].get("spans") is None:
        return None  # a program that serves no spans
    return 1e3 * (S(ctx, "build.ahead") or 0.0)
