"""Milliseconds a request spent being decoded and fingerprinted at
admission, on its handler thread: spans `ingest.decode` (the frame's
decode, or the JSON parse and `encode_history`) and `ingest.fingerprint`
for each `ingest.decode`."""

from benchmarks.layer_metrics._spans import mean_ms

EXAMPLE = {"spans_before": {"ingest.decode": {"n": 10, "s": 1.0},
                            "ingest.fingerprint": {"n": 10, "s": 0.5}},
           "spans_after": {"ingest.decode": {"n": 50, "s": 5.0},
                           "ingest.fingerprint": {"n": 50, "s": 1.5}},
           "want": 125.0}


def read(ctx):
    return mean_ms(ctx, "ingest.decode", "ingest.fingerprint")
