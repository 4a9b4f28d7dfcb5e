"""Share of the window's wall time spent handing a launch's verdicts
back: slicing, tier counts and request stats (span `demux.results`),
then the cache, the retention window, the terminal WAL marker and the
followers (span `demux.account`)."""

from benchmarks.layer_metrics._spans import share

EXAMPLE = {"spans_before": {"demux.results": {"n": 16, "s": 0.01},
                            "demux.account": {"n": 2, "s": 0.09}},
           "spans_after": {"demux.results": {"n": 64, "s": 0.05},
                           "demux.account": {"n": 8, "s": 0.45}},
           "want": 1.0}


def read(ctx):
    return share(ctx, "demux.results", "demux.account")
