"""Single-process interleaved A/B: weaker-consistency rung vs full
linearizability (ISSUE-10 acceptance measurement).

Runs the PRODUCTION path (check_histories, auto routing) with the
``consistency=`` knob flipped per rep, interleaved in one process — the
methodology this repo requires for perf claims (cross-process
comparisons measure the host's mood). The rung-ordering
invariant is asserted before anything is timed: every history the
linearizable pass accepts must be accepted by the weaker rung.

The acceptance bar (ISSUE 10): ``consistency=sequential`` beats full
linearizability on at least one north-star-sized shape. The mechanism
is the greedy witness certifier (checker/consistency.py): a weaker rung
admits more witnesses, so the O(events · window) host scan certifies
most valid rows without any kernel launch; ``--no-greedy`` measures the
kernel-only rung as the ablation arm.

Usage: python scripts/ab_consistency.py [--reps 3] [--n-histories 1000]
       [--n-ops 1000] [--rung sequential] [--model register|set|queue]
       [--no-greedy]
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--n-histories", type=int, default=1000)
    ap.add_argument("--n-ops", type=int, default=1000)
    ap.add_argument("--rung", default="sequential",
                    choices=["sequential", "session"])
    ap.add_argument("--model", default="register",
                    choices=["register", "counter", "set", "queue"])
    ap.add_argument("--no-greedy", action="store_true",
                    help="disable the greedy certifier (kernel-only rung)")
    args = ap.parse_args()

    import random

    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models import (CasRegister, Counter, GSet,
                                                TicketQueue)

    model = {"register": CasRegister, "counter": Counter, "set": GSet,
             "queue": TicketQueue}[args.model]()
    rng = random.Random(3)
    hists = [random_valid_history(rng, args.model, n_ops=args.n_ops,
                                  n_procs=5, crash_p=0.05, max_crashes=3)
             for _ in range(args.n_histories)]
    if args.no_greedy:
        os.environ["JGRAFT_GREEDY_CERTIFY"] = "0"

    def run(consistency: str):
        t0 = time.perf_counter()
        rs = check_histories(hists, model, algorithm="jax",
                             consistency=consistency)
        dt = time.perf_counter() - t0
        return dt, [r["valid?"] for r in rs], rs

    variants = ("linearizable", args.rung)
    verdicts = {}
    rs = []
    for name in variants:                     # warm-up: compile
        _, verdicts[name], rs = run(name)
    # Rung-ordering invariant: lin-pass ⇒ rung-pass, per history.
    bad = [i for i, (a, b) in enumerate(zip(verdicts["linearizable"],
                                            verdicts[args.rung]))
           if a is True and b is not True]
    assert not bad, f"rung ordering violated at rows {bad[:5]}"
    greedy_rows = sum(1 for r in rs if r.get("algorithm") == "greedy-witness")
    print({"rung": args.rung, "greedy_certified_rows": greedy_rows,
           "rows": len(hists),
           "greedy_enabled": not args.no_greedy})

    times = {n: [] for n in variants}
    for _ in range(args.reps):                # interleaved
        for name in variants:
            times[name].append(run(name)[0])
    os.environ.pop("JGRAFT_GREEDY_CERTIFY", None)
    for name, ts in times.items():
        print({"variant": name, "min_s": round(min(ts), 3),
               "median_s": round(statistics.median(ts), 3),
               "hist_per_s_at_min": round(args.n_histories / min(ts), 2),
               "reps": [round(t, 3) for t in ts]})
    speedup = min(times["linearizable"]) / min(times[args.rung])
    print({"speedup_at_min": round(speedup, 3),
           "acceptance_rung_cheaper": speedup > 1.0})


if __name__ == "__main__":
    main()
