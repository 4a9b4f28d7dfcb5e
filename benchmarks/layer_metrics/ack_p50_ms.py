"""Median, over the requests completed in the window, of the client's
time from calling `submit` to holding the acknowledgement with the
request's id: decode, encode, fingerprint, admission and the WAL's
fsync."""

import statistics

EXAMPLE = {"acks_ms": [5.0, 7.0, 100.0], "want": 7.0}


def read(ctx):
    return statistics.median(ctx["acks_ms"]) if ctx["acks_ms"] else None
