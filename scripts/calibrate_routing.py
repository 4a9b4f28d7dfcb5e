#!/usr/bin/env python
"""One-shot calibration for the per-shape platform-routing gate.

`auto` routes a dense window group to the host mesh when its scanned-cell
count B×E is under PLATFORM_ROUTE_MIN_CELLS (checker/linearizable.py) —
a constant measured on one host+chip pair (doc/running.md "Measured
routing gates"). This script DERIVES the crossover on the current
hardware: it times the identical dense kernel launch on the default
backend and on the host CPU backend across a grid of batch shapes, finds
the largest shape where the host still wins, and prints the
JGRAFT_ROUTE_MIN_CELLS value to export.

Run it on a TPU-attached session (on a CPU-only host both "platforms"
are the same backend and the script says so). The shapes mirror the
suite's real spread: config-3-like tiny keys up through config-4-like
long histories.

Usage:
  python scripts/calibrate_routing.py            # full grid
  python scripts/calibrate_routing.py --quick    # 4 shapes, smoke test
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="4 shapes only (CI smoke)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repetitions per shape (min is kept)")
    ap.add_argument("--unroll", action="store_true",
                    help="also sweep JGRAFT_SCAN_UNROLL in {1,2,4} per "
                         "shape on the default backend (round-5: unroll=2 "
                         "measured 1.49x on the CPU mesh at the config-4 "
                         "shape; the TPU default stays 1 until this sweep "
                         "runs on-chip)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                         pack_batch,
                                                         pad_batch_bucketed)
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models.register import CasRegister
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (
        dense_plan, make_dense_batch_checker)

    default = jax.default_backend()
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        print("cpu backend unavailable (JAX_PLATFORMS pinned exclusively); "
              "cannot calibrate", file=sys.stderr)
        return 2
    same = default == "cpu"
    if same:
        print("# default backend IS the host cpu — crossover is "
              "degenerate on this session; run on a TPU-attached host "
              "for a real gate", file=sys.stderr)

    # (histories, ops/history): config-3-like → config-4-like.
    shapes = [(600, 16), (600, 64), (128, 64), (128, 256),
              (64, 1000), (16, 1000), (16, 10_000)]
    if args.quick:
        shapes = [(64, 16), (64, 64), (8, 256), (4, 1000)]

    rng = random.Random(5)
    rows = []
    for n_hist, n_ops in shapes:
        encs = [encode_history(
            random_valid_history(rng, "register", n_ops=n_ops, n_procs=5,
                                 crash_p=0.05, max_crashes=3), CasRegister())
            for _ in range(n_hist)]
        plan = dense_plan(CasRegister(), encs)
        if plan is None:
            continue
        ev, (val_of,), B = pad_batch_bucketed(
            pack_batch(encs)["events"], (plan.val_of,))
        kernel = make_dense_batch_checker(CasRegister(), plan.kind,
                                          plan.n_slots, plan.n_states)
        cells = int(ev.shape[0]) * int(ev.shape[1])

        def timed(dev):
            e, v = ((jax.device_put(ev, dev), jax.device_put(val_of, dev))
                    if dev is not None else (ev, val_of))
            np.asarray(kernel(e, v)[0])  # warm (compile for this placement)
            best = float("inf")
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                np.asarray(kernel(e, v)[0])
                best = min(best, time.perf_counter() - t0)
            return best

        t_default = timed(None)
        t_host = t_default if same else timed(host)
        rows.append({"histories": n_hist, "ops": n_ops, "cells": cells,
                     "default_s": round(t_default, 4),
                     "host_s": round(t_host, 4),
                     "host_wins": bool(t_host < t_default)})
        if args.unroll:
            import os
            sweep = {}
            prior = os.environ.get("JGRAFT_SCAN_UNROLL")
            try:
                for u in (1, 2, 4):
                    os.environ["JGRAFT_SCAN_UNROLL"] = str(u)
                    # The kernel cache keys on scan_unroll(), so this
                    # builds (and compiles) a distinct kernel per value.
                    k_u = make_dense_batch_checker(
                        CasRegister(), plan.kind, plan.n_slots,
                        plan.n_states)
                    np.asarray(k_u(ev, val_of)[0])
                    best = float("inf")
                    for _ in range(args.repeats):
                        t0 = time.perf_counter()
                        np.asarray(k_u(ev, val_of)[0])
                        best = min(best, time.perf_counter() - t0)
                    sweep[f"unroll{u}"] = round(best, 4)
            finally:
                # Restore (not pop) so neither a mid-sweep failure nor
                # an operator-set value leaks a DIFFERENT unroll into
                # later shapes' default timings (mislabeled rows would
                # poison the derived gate).
                if prior is None:
                    os.environ.pop("JGRAFT_SCAN_UNROLL", None)
                else:
                    os.environ["JGRAFT_SCAN_UNROLL"] = prior
            rows[-1]["unroll_sweep"] = sweep
        print(json.dumps(rows[-1]), flush=True)

    # Derive the gate from the FIRST crossover in cell order, not the
    # largest host win: one noisy/stalled chip timing at a big shape
    # must not inflate the gate past every chip-winning shape below it
    # (a stall during calibration would otherwise print a
    # gate that routes chip-winning work to the host forever).
    by_cells = sorted(rows, key=lambda r: r["cells"])
    first_chip_win = next((r["cells"] for r in by_cells
                           if not r["host_wins"]), None)
    stray = [r["cells"] for r in by_cells
             if r["host_wins"] and first_chip_win is not None
             and r["cells"] > first_chip_win]
    if same:
        print("# no recommendation (single-backend session)")
    elif first_chip_win is None:
        print("# recommendation: the host won EVERY shape — the chip "
              "path looks unhealthy (a stall?); re-run before "
              "trusting any gate")
    else:
        gate = first_chip_win
        print(f"# recommendation: export JGRAFT_ROUTE_MIN_CELLS={gate}")
        print("# (smallest chip-winning shape; update "
              "PLATFORM_ROUTE_MIN_CELLS + doc/running.md if this moves "
              "a headline row)")
        if stray:
            print(f"# WARNING: host also won at {stray} cells — "
                  "non-monotonic crossover, likely timing noise or a "
                  "stall; re-run before trusting the gate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
