"""Single-process A/B of JGRAFT_SCAN_UNROLL on the north-star batch.

The first on-chip session showed ~2x inter-process variance
(identical dense benches: 475 / 400 / 249 hist/s), so cross-process
comparisons cannot resolve a 1.2-1.5x knob.  This script builds the
kernels for several unroll values in ONE process (the kernel caches key
on the unroll, so they coexist), then interleaves timed reps A/B/A/B...
and reports per-setting min and median — the only sound way to compare
on this deployment.

Usage: python scripts/ab_unroll.py [--unrolls 1,2,4] [--reps 5]
"""
import argparse
import os
import random
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--unrolls", default="1,2,4")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--n-histories", type=int, default=1000)
    ap.add_argument("--n-ops", type=int, default=1000)
    args = ap.parse_args()
    unrolls = [int(u) for u in args.unrolls.split(",")]

    from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                         pack_batch)
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models.register import CasRegister
    from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plans_grouped
    from jepsen_jgroups_raft_tpu.parallel.mesh import (check_batch_sharded,
                                                       make_mesh)

    rng = random.Random(20260729)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=args.n_ops,
                                  n_procs=5, crash_p=0.05, max_crashes=3)
             for _ in range(args.n_histories)]
    encs = [encode_history(h, model) for h in hists]
    mesh = make_mesh()
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest, "north-star batch should be fully dense-plannable"
    batch = pack_batch(encs)

    def timed(unroll: int) -> float:
        os.environ["JGRAFT_SCAN_UNROLL"] = str(unroll)
        t0 = time.perf_counter()
        fins = [check_batch_sharded(model, batch["events"][idxs], mesh,
                                    dense=plan, defer=True)
                for idxs, plan in grouped]
        for fin in fins:
            fin()
        return time.perf_counter() - t0

    for u in unrolls:          # warm-up: compile every cache entry
        timed(u)
    times: dict[int, list[float]] = {u: [] for u in unrolls}
    for _ in range(args.reps):  # interleaved: variance hits all settings
        for u in unrolls:
            times[u].append(timed(u))
    for u in unrolls:
        ts = times[u]
        print({"unroll": u, "min_s": round(min(ts), 3),
               "median_s": round(statistics.median(ts), 3),
               "hist_per_s_at_min": round(args.n_histories / min(ts), 1),
               "reps": [round(t, 3) for t in ts]})


if __name__ == "__main__":
    main()
