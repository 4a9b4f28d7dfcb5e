"""What the span readers share: deltas of the program's own span totals
over the window.

graftd's `/stats` serves `spans`, `{name: {"n": count, "s": seconds}}`,
process-wide totals of the named durations on its served path (PERF.md,
section 3, lists every name with its thread). `run.py` snapshots the
stats at the window's start and end, so a reader sees both under
`ctx["before"|"after"]["stats"]["spans"]`. A program that has no spans
(a parent commit) serves no such key, and every reader here then gives
None: the result line leaves the metric out.
"""

#: the spans that tile the dispatcher thread's loop when graftd runs one
#: worker: none of them is nested in another
TILING = ("dispatch.take", "dispatch.scan", "dispatch.linger",
          "launch.host", "launch.device", "demux.results",
          "demux.counterexample", "demux.account", "demux.trace_write")


def _delta(ctx, name: str, field: str):
    after = ctx["after"]["stats"].get("spans")
    if after is None:
        return None
    before = ctx["before"]["stats"].get("spans") or {}
    zero = {"n": 0, "s": 0.0}
    return after.get(name, zero)[field] - before.get(name, zero)[field]


def N(ctx, name: str):
    """How often span `name` ended in the window; None where the program
    serves no spans or this one did not move."""
    return _delta(ctx, name, "n") or None


def S(ctx, *names):
    """Seconds the spans `names` added in the window, summed; None where
    the program serves no spans or none of them moved."""
    if ctx["after"]["stats"].get("spans") is None:
        return None
    if not any(_delta(ctx, n, "n") or _delta(ctx, n, "s") for n in names):
        return None
    return sum(_delta(ctx, n, "s") for n in names)


def mean_ms(ctx, name: str, *more):
    """Milliseconds per `name` of the spans `name` + `more`."""
    n, s = N(ctx, name), S(ctx, name, *more)
    return None if n is None or s is None else 1e3 * s / n


def share(ctx, *names):
    """Per cent of the window's wall seconds that the spans took."""
    s = S(ctx, *names)
    return None if s is None else 100.0 * s / ctx["window_s"]
