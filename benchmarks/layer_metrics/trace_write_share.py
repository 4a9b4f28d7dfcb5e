"""Share of the window's wall time the dispatcher thread spent writing
each request's `results.json` and `history.jsonl` into the store, before
its next `take`: span `demux.trace_write`."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"demux.trace_write": {"n": 16, "s": 0.3}},
           "spans_after": {"demux.trace_write": {"n": 64, "s": 1.5}},
           "want": 3.0}


def read(ctx):
    return share(ctx, "demux.trace_write")
