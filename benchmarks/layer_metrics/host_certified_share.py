"""Share of the rows decided in the window that the host certifier
decided (tiers ending in `@lin`)."""

from benchmarks.layer_metrics import tier_rows


def read(ctx):
    rows = tier_rows(ctx)
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * sum(n for t, n in rows.items()
                       if t.endswith("@lin")) / total
