"""Milliseconds a client spent encoding a history before its frame
left: span `client.encode` (the seconds each binary frame's header says
its encoder took, from `submit` to the fingerprint: the rows' columns,
the split by key, an `encode_history` a unit; graftd adds them to the
span as it admits the frame, as evidence and nothing more) over
Δ`histories_admitted` of `/stats`. It lies inside the acknowledgement
and inside every verdict. Nothing from a program without the span or
the counter, nor where no frame said its seconds."""

from benchmarks.layer_metrics import delta
from benchmarks.layer_metrics._spans import S

EXAMPLE = {"stats_before": {"histories_admitted": 24},
           "stats_after": {"histories_admitted": 64},
           "spans_before": {"client.encode": {"n": 2400, "s": 1.2}},
           "spans_after": {"client.encode": {"n": 6400, "s": 3.2}},
           "want": 50.0}


def read(ctx):
    if "histories_admitted" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    s, n = S(ctx, "client.encode"), delta(ctx, "stats", "histories_admitted")
    return None if s is None or not n else 1e3 * s / n
