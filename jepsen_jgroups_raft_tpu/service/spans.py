"""Idle-by-span report: where the device's idle time went, by the span
open on graftd's dispatcher thread.

    python -m jepsen_jgroups_raft_tpu.service.spans <xplane.pb | profile dir>

Reads one `jax.profiler` trace taken while graftd served (a
`JGRAFT_PROFILE_DIR` trace, or any `jax.profiler.start_trace` session in
the serving process). While a session is active every span of the
served path (`checker/schedule.py`: `span`, `annotate`) is a
`TraceAnnotation` on the host plane, on the same clock as the device
planes. The report takes the device-busy intervals from each device
plane's `XLA Modules` line (one event a program run, a handful a launch,
where `XLA Ops` holds millions), the spans from the host-plane line of
the dispatcher thread, and prints the busy seconds and the idle seconds
by the innermost span open in each idle gap. Gaps shorter than 10 ms are
summed under one name. With several device planes "busy" means any of
them was.

`idle_by_span` is plain interval arithmetic over lists, so it can be
checked by hand (tests/test_spans.py).
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import List, Optional, Tuple

#: what a span of the served path is called (PERF.md, section 3)
SPAN_PREFIXES = ("dispatch.", "launch.", "demux.", "journal.", "ingest.")
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 10_000_000
SHORT = "gaps_under_10ms"
NO_SPAN = "no_span"


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint cover of [(start, end)] intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def idle_by_span(busy: List[Tuple[int, int]],
                 spans: List[Tuple[str, int, int]],
                 lo: int, hi: int, min_gap_ns: int = MIN_GAP_NS) -> dict:
    """Busy and idle nanoseconds of the window [lo, hi), the idle ones
    by the innermost span open on the dispatcher thread.

    busy: (start, end) intervals in which the device ran something.
    spans: (name, start, end) of one thread, so properly nested; the
        innermost at a moment is the one open there that began last.
    Returns ``{"busy_ns", "idle_ns", "by_span": [[name, ns], ...]}``,
    the names by falling share; their nanoseconds sum to `idle_ns`."""
    cover = [(max(s, lo), min(e, hi)) for s, e in union(busy)]
    cover = [(s, e) for s, e in cover if e > s]
    gaps, at = [], lo
    for s, e in cover:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    by: dict = {}
    for g0, g1 in gaps:
        if g1 - g0 < min_gap_ns:
            by[SHORT] = by.get(SHORT, 0) + (g1 - g0)
            continue
        inside = [(n, max(s, g0), min(e, g1), s) for n, s, e in spans
                  if s < g1 and e > g0]
        cuts = sorted({g0, g1} | {t for _, s, e, _ in inside
                                  for t in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            # the spans open over the whole piece; the one that began
            # last is the innermost
            open_here = [(began, n) for n, s, e, began in inside
                         if s <= a and e >= b]
            name = max(open_here)[1] if open_here else NO_SPAN
            by[name] = by.get(name, 0) + (b - a)
    busy_ns = sum(e - s for s, e in cover)
    return {"busy_ns": busy_ns, "idle_ns": (hi - lo) - busy_ns,
            "by_span": [[n, ns] for n, ns in
                        sorted(by.items(), key=lambda kv: -kv[1])]}


def covered_ns(busy: List[Tuple[int, int]],
               spans: List[Tuple[str, int, int]], name: str) -> int:
    """Busy nanoseconds that fall inside spans called `name`."""
    inside = union([(s, e) for n, s, e in spans if n == name])
    total = 0
    for b0, b1 in union(busy):
        for s, e in inside:
            total += max(0, min(b1, e) - max(b0, s))
    return total


def find_trace(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def read_trace(data) -> dict:
    """Out of a `jax.profiler.ProfileData`: the device-busy intervals
    (every device plane's `XLA Modules` line), and the spans of the
    dispatcher thread: the host-plane line that holds most `dispatch.*`
    events."""
    busy: List[Tuple[int, int]] = []
    devices, best, best_n = 0, [], -1
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    devices += 1
                    busy += [(int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                             for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(ev.name, int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns))
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES)]
                n = sum(1 for s in spans if s[0].startswith("dispatch."))
                if spans and n > best_n:
                    best, best_n = spans, n
    return {"busy": busy, "spans": best, "devices": devices}


def report(trace: dict, lo: Optional[int] = None,
           hi: Optional[int] = None) -> dict:
    """The window defaults to the extent of everything read."""
    edges = [t for s, e in trace["busy"] for t in (s, e)] + \
            [t for _, s, e in trace["spans"] for t in (s, e)]
    if not edges:
        raise ValueError("the trace holds neither an XLA Modules line "
                         "nor a span of the served path")
    lo = min(edges) if lo is None else lo
    hi = max(edges) if hi is None else hi
    out = idle_by_span(trace["busy"], trace["spans"], lo, hi)
    out["window_ns"] = hi - lo
    out["devices"] = trace["devices"]
    out["span_events"] = len(trace["spans"])
    out["busy_in_launch_device_ns"] = covered_ns(
        [(max(s, lo), min(e, hi)) for s, e in trace["busy"]],
        trace["spans"], "launch.device")
    return out


def render(rep: dict) -> str:
    s = 1e-9
    lines = [
        f"window {rep['window_ns'] * s:.3f} s, {rep['devices']} device "
        f"plane(s), {rep['span_events']} span events on the dispatcher "
        f"thread",
        f"device busy {rep['busy_ns'] * s:.3f} s "
        f"({100.0 * rep['busy_ns'] / max(rep['window_ns'], 1):.2f} %), of "
        f"which inside launch.device "
        f"{rep['busy_in_launch_device_ns'] * s:.3f} s",
        f"device idle {rep['idle_ns'] * s:.3f} s, by the innermost span "
        f"open on the dispatcher thread:"]
    for name, ns in rep["by_span"]:
        lines.append(f"  {name:<24}{ns * s:>10.3f} s"
                     f"{100.0 * ns / max(rep['idle_ns'], 1):>8.2f} %")
    return "\n".join(lines)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        sys.stderr.write(__doc__.split("\n\n")[1] + "\n")
        return 2
    from jax.profiler import ProfileData

    path = find_trace(Path(args[0]))
    rep = report(read_trace(ProfileData.from_file(str(path))))
    print(f"{path}\n{render(rep)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
