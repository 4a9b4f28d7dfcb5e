"""Milliseconds the dispatcher thread spent minimising a witness for
each invalid row it explained: span `demux.counterexample`, which
counts those rows."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"demux.counterexample": {"n": 20, "s": 0.5}},
           "spans_after": {"demux.counterexample": {"n": 120, "s": 3.0}},
           "want": 25.0}


def read(ctx):
    return mean_ms(ctx, "demux.counterexample")
