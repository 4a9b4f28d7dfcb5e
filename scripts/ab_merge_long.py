"""Single-process A/B: merged vs per-window launches for LONG histories
(config-#4 shape, VERDICT r4 #3). Launches serialize on one TPU core,
so N per-window groups pay the SUM of their scan depths; one merged
launch at the widest window pays max-E once at a higher per-step width.
Which side wins is an empirical question about whether the per-step
wall is op-latency-bound (merge wins) or width-bound (per-window wins)
at config-4 frontier sizes — and the round-3 number that set the
per-window policy predates the interleaved-A/B methodology this repo
now requires for on-chip comparisons (cross-process dense reps have
spanned 249-677 hist/s).

Runs the PRODUCTION path (check_histories, auto routing) with
JGRAFT_MERGE_LONG flipped per rep, interleaved in one process.

SHORT histories have no such knob: which of their windows share a
launch is the backend's measured cost's answer (ops/dense_scan.py
`best_partition`; scripts/sweep_group_cost.py measures it).

Usage: python scripts/ab_merge_long.py [--reps 5]
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
    ap.add_argument("--n-histories", type=int, default=16)
    ap.add_argument("--n-ops", type=int, default=10_000)
    args = ap.parse_args()

    import random

    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models.register import CasRegister

    rng = random.Random(3)
    model = CasRegister()
    knob = "JGRAFT_MERGE_LONG"
    hists = [random_valid_history(rng, "register", n_ops=args.n_ops,
                                  n_procs=5, crash_p=0.02, max_crashes=4)
             for _ in range(args.n_histories)]

    def run(merged: bool):
        os.environ[knob] = "1" if merged else "0"
        t0 = time.perf_counter()
        rs = check_histories(hists, model, algorithm="jax")
        dt = time.perf_counter() - t0
        n_valid = sum(1 for r in rs if r["valid?"] is True)
        return dt, n_valid

    variants = {"per-window": False, "merged": True}
    valid = {}
    for name, m in variants.items():        # warm-up: compile
        _, valid[name] = run(m)
    assert valid["per-window"] == valid["merged"] == args.n_histories, valid
    times = {n: [] for n in variants}
    for _ in range(args.reps):              # interleaved
        for name, m in variants.items():
            times[name].append(run(m)[0])
    os.environ.pop(knob, None)
    for name, ts in times.items():
        print({"variant": name, "min_s": round(min(ts), 3),
               "median_s": round(statistics.median(ts), 3),
               "hist_per_s_at_min": round(args.n_histories / min(ts), 2),
               "hist_per_s_at_median":
                   round(args.n_histories / statistics.median(ts), 2),
               "reps": [round(t, 3) for t in ts]})


if __name__ == "__main__":
    main()
