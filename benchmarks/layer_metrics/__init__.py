"""One reader to a per-layer metric: `read(ctx)` gives the number, or
None where this run holds nothing to read it from.

`ctx` holds `window_s`; `before` and `after`, the program's counters at
the window's start and end (`stats`, `fastpath`, `tiers`, as `run.py`'s
`read_counters` takes them); `requests`, the client-side records of the
requests completed in the window; `acks_ms`; `compiles_in_window`; and
`trace`, the reduced device trace of a `--trace 1` run or None.
"""


def delta(ctx, group: str, key: str):
    return ctx["after"][group].get(key, 0) - ctx["before"][group].get(key, 0)


def tier_rows(ctx) -> dict:
    """Rows decided in the window, by the tier that decided them."""
    out = {}
    for tier, row in ctx["after"]["tiers"].items():
        base = ctx["before"]["tiers"].get(tier, {"rows": 0})["rows"]
        if row["rows"] - base:
            out[tier] = row["rows"] - base
    return out
