"""Per cent of the chip's bf16 matrix peak that the closure launches
reached WHILE THE DEVICE RAN: 2 x Δ`closure_macs` floating-point
operations of the window, over the window's device-busy seconds times
`bf16_flops_per_s` of `benchmarks/peaks.json`.

The operations: `closure_macs` counts, for every squaring that RAN in a
closure program (the kernel returns its iteration counts), the rows of
the launch's bucket times N^3 multiply-adds of one [N, N] x [N, N]
product a row; `flops` below turns them into operations, two a
multiply-add. The yardstick is the bf16 MXU peak whatever dtype the
program multiplies in: the planes are 0/1, which bf16 holds exactly,
and an f32 accumulator counts to 2^24 > N, so that is what a closure
CAN be run at.

The time is the device's own: the share of the traced span in which an
operation ran on the device (`trace.busy_s / trace.window_s`, what
`device_idle_share` reads), laid over the window's seconds. In this
cell every device operation belongs to a closure program (its scatter,
its squarings, its flags), so the busy seconds are the program's, and
the share cannot pass 100 % unless the count is wrong. The counter is
the whole window's and the busy share the traced span's: the reading
is as good as the span is typical of the window. The host's seconds
round a launch (transfer, dispatch, the blocking read) are
`closure_ms_per_row`'s.

Nothing without a device trace, from a program that does not serve the
counter, nor where no squaring ran on a device."""

import json
from pathlib import Path

from benchmarks.layer_metrics import delta

#: a 40 s window whose traced span ran the device a sixteenth of the
#: time: 2.5 busy seconds, a tenth of 197e12 x 2.5 operations
EXAMPLE = {"stats_before": {"closure_macs": 0},
           "stats_after": {"closure_macs": 24625 * 10 ** 9},
           "trace": {"busy_s": 0.5, "window_s": 8.0, "kernel_rows": 0,
                     "device_ops": [["while.3", 0.4]]},
           "want": 10.0}


def flops(macs: int) -> float:
    """Operations of `macs` multiply-adds."""
    return 2.0 * macs


def peak_flops_per_s():
    """The bf16 peak of the run's device kind: of the one kind
    `peaks.json` holds or, where it holds several, of the kind JAX
    reports (None where that is not among them)."""
    with open(Path(__file__).resolve().parents[1] / "peaks.json") as fh:
        peaks = {k: v for k, v in json.load(fh).items()
                 if isinstance(v, dict)}
    if len(peaks) == 1:
        [row] = peaks.values()
    else:
        import jax

        row = peaks.get(jax.devices()[0].device_kind)
    return row["bf16_flops_per_s"] if row else None


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"] or not trace["window_s"]:
        return None
    if "closure_macs" not in ctx["after"]["stats"]:
        return None  # a program that does not serve the counter
    macs = delta(ctx, "stats", "closure_macs")
    peak = peak_flops_per_s()
    if not macs or not peak:
        return None
    busy_s = ctx["window_s"] * trace["busy_s"] / trace["window_s"]
    return 100.0 * flops(macs) / (busy_s * peak)
