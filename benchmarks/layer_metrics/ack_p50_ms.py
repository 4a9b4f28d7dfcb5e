"""Median, over the requests completed in the window, of the client's
time from calling `submit` to holding the acknowledgement with the
request's id: decode, encode, fingerprint, admission and the WAL's
fsync."""

import statistics


def read(ctx):
    return statistics.median(ctx["acks_ms"]) if ctx["acks_ms"] else None
