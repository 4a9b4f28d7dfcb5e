"""Single-process A/B: Pallas tile kernel vs vmapped XLA dense kernel
on the north-star batch (the compete-or-retire measurement, VERDICT r4
#2). Cross-process comparison is meaningless (identical dense benches
spanned 249-475 hist/s across processes), so both engines run
interleaved in ONE process and the per-engine min/median decide.

Usage: python scripts/ab_pallas.py [--reps 5]
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--n-histories", type=int, default=1000)
    ap.add_argument("--n-ops", type=int, default=1000)
    args = ap.parse_args()

    import random

    import numpy as np

    from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                         pack_batch,
                                                         pad_batch_bucketed)
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models.register import CasRegister
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (
        dense_plans_grouped, make_dense_batch_checker)
    from jepsen_jgroups_raft_tpu.ops.pallas_scan import (
        make_pallas_batch_checker)

    rng = random.Random(20260729)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=args.n_ops,
                                  n_procs=5, crash_p=0.05, max_crashes=3)
             for _ in range(args.n_histories)]
    encs = [encode_history(h, model) for h in hists]
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest
    batch = pack_batch(encs)
    # Pre-pad once: both engines consume identical [B, E, 5] groups.
    padded = []
    for idxs, plan in grouped:
        ev, (val_of,), B = pad_batch_bucketed(batch["events"][idxs],
                                              (plan.val_of,))
        padded.append((plan, np.asarray(ev), np.asarray(val_of), B))

    def run_dense():
        t0 = time.perf_counter()
        outs = [(make_dense_batch_checker(model, p.kind, p.n_slots,
                                          p.n_states)(ev, vf), B)
                for p, ev, vf, B in padded]
        n = sum(int(np.asarray(ok)[:B].sum()) for (ok, _), B in outs)
        return time.perf_counter() - t0, n

    import jax

    interpret = jax.default_backend() != "tpu"  # smoke-testable off-chip

    def run_pallas():
        t0 = time.perf_counter()
        outs = [(make_pallas_batch_checker(model, p.n_slots, p.n_states,
                                           ev.shape[1],
                                           interpret=interpret)(ev, vf), B)
                for p, ev, vf, B in padded]
        n = sum(int(np.asarray(ok)[:B].sum()) for (ok, _), B in outs)
        return time.perf_counter() - t0, n

    engines = {"dense": run_dense, "pallas": run_pallas}
    valid = {}
    for name, fn in engines.items():        # warm-up: compile
        _, valid[name] = fn()
    assert valid["dense"] == valid["pallas"] == args.n_histories, valid
    times = {n: [] for n in engines}
    for _ in range(args.reps):              # interleaved
        for name, fn in engines.items():
            times[name].append(fn()[0])
    for name, ts in times.items():
        print({"engine": name, "min_s": round(min(ts), 3),
               "median_s": round(statistics.median(ts), 3),
               "hist_per_s_at_min": round(args.n_histories / min(ts), 1),
               "hist_per_s_at_median":
                   round(args.n_histories / statistics.median(ts), 1),
               "reps": [round(t, 3) for t in ts]})


if __name__ == "__main__":
    main()
