"""Single-process interleaved A/B: cheap-decision tier on vs off
(ISSUE-13 acceptance measurement).

Measures the production weak-rung path (`check_histories`,
``consistency=sequential``) with the cheap tiers (value-guided
bounded-backtrack certifier + exact cycle tier) enabled vs disabled
(``JGRAFT_GREEDY_CERTIFY=0 JGRAFT_CYCLE_TIER=0``), interleaved with
candidate rotation in ONE process — the methodology this repo requires
for perf claims (cross-process comparisons measure the host's
mood). Verdict identity between the arms is asserted before anything
is timed (the tier-soundness gate), and the per-family decided
fractions are reported from the cheap arm's verdicts.

Acceptance bars (ISSUE 13): register/cas ≥ 1.2× with the cheap tier on
(reversing PR-9's measured ≈0.77×, where mutator ambiguity defeated the
no-backtrack greedy), queue greedy decided-fraction ≥ 0.9 (crashed-op
landmines placed lazily). ``--with-lin`` additionally measures the rung
against full linearizability — the PR-9 regression's original axis.

Usage: python scripts/ab_cheap_tier.py [--reps 3] [--n-histories 400]
       [--n-ops 1000] [--rung sequential] [--families register,queue,set]
       [--with-lin]
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
    ap.add_argument("--n-histories", type=int, default=400)
    ap.add_argument("--n-ops", type=int, default=1000)
    ap.add_argument("--rung", default="sequential",
                    choices=["sequential", "session"])
    ap.add_argument("--families", default="register,queue,set")
    ap.add_argument("--with-lin", action="store_true",
                    help="also time the linearizable rung (the PR-9 axis)")
    args = ap.parse_args()

    import random

    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models import (CasRegister, Counter, GSet,
                                                TicketQueue)

    factories = {"register": CasRegister, "counter": Counter, "set": GSet,
                 "queue": TicketQueue}
    overall_ok = True
    for family in args.families.split(","):
        family = family.strip()
        model = factories[family]()
        rng = random.Random(13)
        hists = [random_valid_history(rng, family, n_ops=args.n_ops,
                                      n_procs=5, crash_p=0.05,
                                      max_crashes=3)
                 for _ in range(args.n_histories)]

        def set_cheap(on: bool) -> None:
            os.environ["JGRAFT_GREEDY_CERTIFY"] = "1" if on else "0"
            os.environ["JGRAFT_CYCLE_TIER"] = "1" if on else "0"

        def run(cheap: bool, consistency: str = args.rung):
            set_cheap(cheap)
            t0 = time.perf_counter()
            rs = check_histories(hists, model, algorithm="jax",
                                 consistency=consistency)
            return time.perf_counter() - t0, rs

        # Warm-up (compile) + verdict-identity gate BEFORE timing.
        _, rs_on = run(True)
        _, rs_off = run(False)
        bad = [i for i, (a, b) in enumerate(zip(rs_on, rs_off))
               if a["valid?"] is not b["valid?"]]
        assert not bad, f"{family}: cheap-tier verdicts diverge at {bad[:5]}"

        tiers: dict = {}
        for r in rs_on:
            t = r.get("decided-tier", "?")
            tiers[t] = tiers.get(t, 0) + 1
        cheap_rows = sum(v for k, v in tiers.items()
                         if k in ("greedy", "backtrack", "cycle", "trivial"))
        decided_fraction = cheap_rows / len(rs_on)
        print({"family": family, "rung": args.rung, "rows": len(hists),
               "decided_by_tier": tiers,
               "cheap_decided_fraction": round(decided_fraction, 4)})

        variants = [("cheap-on", True), ("cheap-off", False)]
        times = {name: [] for name, _ in variants}
        for rep in range(args.reps):          # interleaved, order rotated
            order = variants if rep % 2 == 0 else variants[::-1]
            for name, cheap in order:
                times[name].append(run(cheap)[0])
        for name, ts in times.items():
            print({"family": family, "variant": name,
                   "min_s": round(min(ts), 3),
                   "median_s": round(statistics.median(ts), 3),
                   "hist_per_s_at_min": round(len(hists) / min(ts), 2),
                   "reps": [round(t, 3) for t in ts]})
        speedup = min(times["cheap-off"]) / min(times["cheap-on"])
        row = {"family": family,
               "speedup_at_min": round(speedup, 3)}
        if family == "register":
            row["acceptance_register_1_2x"] = speedup >= 1.2
            overall_ok &= speedup >= 1.2
        if family == "queue":
            row["acceptance_queue_decided_0_9"] = decided_fraction >= 0.9
            overall_ok &= decided_fraction >= 0.9
        print(row)

        if args.with_lin:
            # PR-9's original axis: the weak rung vs full linearizability
            # (cheap tier on) — the ≈0.77× register regression's A/B.
            set_cheap(True)
            lin_ts, rung_ts = [], []
            run(True, "linearizable")  # warm-up
            for rep in range(args.reps):
                pair = (("lin", "linearizable"), ("rung", args.rung))
                for name, c in pair if rep % 2 == 0 else pair[::-1]:
                    dt, _ = run(True, c)
                    (lin_ts if name == "lin" else rung_ts).append(dt)
            print({"family": family,
                   "rung_vs_lin_speedup_at_min":
                   round(min(lin_ts) / min(rung_ts), 3),
                   "lin_min_s": round(min(lin_ts), 3),
                   "rung_min_s": round(min(rung_ts), 3)})

    for k in ("JGRAFT_GREEDY_CERTIFY", "JGRAFT_CYCLE_TIER"):
        os.environ.pop(k, None)
    print({"acceptance_all": overall_ok})


if __name__ == "__main__":
    main()
