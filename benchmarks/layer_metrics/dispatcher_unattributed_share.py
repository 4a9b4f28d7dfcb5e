"""Share of the window's wall time that none of the nine spans tiling
the dispatcher's loop accounts for. A span is credited when it ends, so
over a 51 s window this reads true to about 2 points either way and may
come out a little negative. Nothing where graftd runs more than one
worker: the loop is then a router and the spans overlap. The nine
shares go to standard error on one line."""

import json
import sys

from benchmarks.layer_metrics._spans import S, TILING

EXAMPLE = {"spans_before": {name: {"n": 1, "s": 1.0} for name in TILING},
           "spans_after": {name: {"n": 5, "s": 5.0} for name in TILING},
           "want": 10.0}


def read(ctx):
    if ctx["after"]["stats"].get("workers", 1) > 1:
        return None
    total = S(ctx, *TILING)
    if total is None:
        return None
    w = ctx["window_s"]
    sys.stderr.write("dispatcher shares: " + json.dumps(
        {n: round(100.0 * (S(ctx, n) or 0.0) / w, 3) for n in TILING})
        + "\n")
    return 100.0 * (w - total) / w
