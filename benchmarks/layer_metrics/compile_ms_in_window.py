"""Milliseconds the backend spent building programs, or loading them
from the compile cache, between the window's start and its end: the
program's own `compile_s` counter (JAX's
`backend_compile_duration` events). 0 on a run whose warm-up reached
every shape."""

EXAMPLE = {"spans_before": {}, "spans_after": {},
           "stats_before": {"compile_s": 14.25},
           "stats_after": {"compile_s": 14.5},
           "want": 250.0}
#: a time of faults: a warm-up that reached every shape reads 0
ZERO_IS_A_READING = True


def read(ctx):
    after = ctx["after"]["stats"]
    if "compile_s" not in after:
        return None
    return 1e3 * (after["compile_s"]
                  - ctx["before"]["stats"].get("compile_s", 0.0))
