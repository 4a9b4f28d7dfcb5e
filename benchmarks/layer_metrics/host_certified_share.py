"""Share of the rows decided in the window that the host certifier
decided (tiers ending in `@lin`)."""

from benchmarks.layer_metrics import tier_rows

EXAMPLE = {"tiers_before": {"backtrack@lin": {"rows": 10, "wall_s": 0.0},
                            "mask": {"rows": 90, "wall_s": 0.0}},
           "tiers_after": {"backtrack@lin": {"rows": 110, "wall_s": 0.0},
                           "mask": {"rows": 990, "wall_s": 0.0}},
           "want": 10.0}


def read(ctx):
    rows = tier_rows(ctx)
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * sum(n for t, n in rows.items()
                       if t.endswith("@lin")) / total
