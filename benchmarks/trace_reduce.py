"""From the profiler's trace to busy seconds, the operations that took
most time, and the idle gaps.

`device_events` reads an `.xplane.pb` with JAX alone and keeps, for each
device plane, the events of its operations line. `reduce` is plain
arithmetic over `(device, name, start_ns, duration_ns)` tuples, so it
can be checked on a small recorded list. Busy time is the union of the
intervals in which an operation ran on a device, averaged over the
devices seen; idle is the rest of the traced span.
"""

from __future__ import annotations

#: line of a device plane that holds one event for each operation run
OPS_LINES = ("XLA Ops",)


def device_events(data) -> tuple:
    """([(device, name, start_ns, duration_ns)], first_ns) of a trace
    (a `jax.profiler.ProfileData`): the operations of every device
    plane, and the earliest timestamp on any plane, which is where the
    trace began on the events' own clock."""
    events, first = [], None
    for plane in data.planes:
        device = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name
        for line in plane.lines:
            if device and line.name in OPS_LINES:
                for ev in line.events:
                    events.append((plane.name, ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)))
            elif not device:
                for ev in line.events:
                    if first is None or ev.start_ns < first:
                        first = int(ev.start_ns)
                    break
    if events:
        lo = min(e[2] for e in events)
        first = lo if first is None else min(first, lo)
    return events, first


def union_ns(intervals: list) -> tuple:
    """(covered ns, [(gap start, gap ns)]) of [(start, end)] intervals;
    the gaps are those between the first start and the last end."""
    covered, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def reduce(events: list, window_s: float, span_ns=None) -> dict:
    """`window_s`: the traced span by the host's clock. `span_ns`:
    (start, end) of that span on the events' clock, where it is known;
    events are clipped to it, and the time before the first and after
    the last operation then counts as a gap."""
    by_dev: dict = {}
    by_name: dict = {}
    for dev, name, start, dur in events:
        end = start + dur
        if span_ns is not None:
            start, end = max(start, span_ns[0]), min(end, span_ns[1])
        if end <= start:
            continue
        by_dev.setdefault(dev, []).append((start, end))
        # the trace names an operation by its whole HLO line
        short = name.split(" = ")[0].lstrip("%")[:60]
        by_name[short] = by_name.get(short, 0) + (end - start)
    busy, gaps = [], []
    for dev, ivs in sorted(by_dev.items()):
        covered, g = union_ns(ivs)
        busy.append(covered)
        if span_ns is not None:
            first, last = min(s for s, _ in ivs), max(e for _, e in ivs)
            g = [(span_ns[0], first - span_ns[0])] + g + \
                [(last, span_ns[1] - last)]
        gaps += [(s, d) for s, d in g if d > 0]
    n_dev = max(1, len(by_dev))
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    if by_dev:
        extent = max(e for ivs in by_dev.values() for _, e in ivs) \
            - min(s for ivs in by_dev.values() for s, _ in ivs)
        window_s = max(window_s, extent / 1e9)
    return {"busy_s": sum(busy) / n_dev / 1e9, "window_s": window_s,
            "devices": len(by_dev), "n_events": len(events),
            "device_ops": [[n, ns / n_dev / 1e9] for n, ns in ops],
            "gaps": sorted(gaps, key=lambda g: -g[1])}


def name_gaps(gaps: list, samples: list, period_s: float) -> list:
    """Idle seconds by what the host's counters did in each gap.
    `samples`: [(epoch ns, {counter: value})] taken while the trace ran.
    A gap shorter than two sampling periods cannot be told apart and is
    summed under one name."""
    by_label: dict = {}
    for start, dur in gaps:
        if dur < 2 * period_s * 1e9 or len(samples) < 2:
            label = f"gaps_under_{2 * period_s:g}s"
        else:
            inside = [row for t, row in samples
                      if start <= t <= start + dur]
            if len(inside) < 2:
                label = "no_sample"
            else:
                moved = sorted(k for k in inside[0]
                               if inside[-1][k] != inside[0][k])
                label = "advanced:" + ("+".join(moved) or "nothing")
        by_label[label] = by_label.get(label, 0) + dur
    return [[k, v / 1e9] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])]
