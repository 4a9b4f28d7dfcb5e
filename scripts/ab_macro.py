"""Single-process interleaved A/B: macro-event compaction vs the legacy
one-event-per-step stream (ISSUE-4 acceptance measurement).

Runs the PRODUCTION path (check_histories, auto routing, default
JGRAFT_SCAN_CHUNK) with JGRAFT_MACRO_EVENTS flipped per rep, interleaved
in one process — the methodology this repo requires for perf claims
(cross-process comparisons measure the host's mood; identical
benches have spanned 249-677 hist/s across processes). Verdicts are
asserted identical between the two variants before anything is timed.

The acceptance bar (ISSUE 4): macro ≥ 1.25× legacy histories/sec on
host CPU at the north-star shape, with the scan length dropped to
#FORCEs + spill (reported here via pack_macro_batch row counts and in
the bench JSON's scan_steps field).

Usage: python scripts/ab_macro.py [--reps 3] [--n-histories 1000]
       [--n-ops 1000]
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
    args = ap.parse_args()

    import random

    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                         pack_macro_batch)
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.models.register import CasRegister

    rng = random.Random(3)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=args.n_ops,
                                  n_procs=5, crash_p=0.05, max_crashes=3)
             for _ in range(args.n_histories)]

    # Scan-length evidence: macro rows vs legacy events (the bench JSON
    # reports the same split as scan_steps / scan_steps_legacy).
    encs = [encode_history(h, model) for h in hists]
    legacy_steps = sum(e.n_events for e in encs)
    macro_steps = int(pack_macro_batch(encs)["n_events"].sum())
    print({"legacy_steps": legacy_steps, "macro_steps": macro_steps,
           "compaction": round(legacy_steps / max(macro_steps, 1), 3)})

    def run(macro: bool):
        os.environ["JGRAFT_MACRO_EVENTS"] = "1" if macro else "0"
        t0 = time.perf_counter()
        rs = check_histories(hists, model, algorithm="jax")
        dt = time.perf_counter() - t0
        return dt, [r["valid?"] for r in rs]

    variants = {"legacy": False, "macro": True}
    verdicts = {}
    for name, m in variants.items():        # warm-up: compile
        _, verdicts[name] = run(m)
    assert verdicts["legacy"] == verdicts["macro"], \
        "verdict mismatch between macro and legacy streams"
    times = {n: [] for n in variants}
    for _ in range(args.reps):              # interleaved
        for name, m in variants.items():
            times[name].append(run(m)[0])
    os.environ.pop("JGRAFT_MACRO_EVENTS", None)
    for name, ts in times.items():
        print({"variant": name, "min_s": round(min(ts), 3),
               "median_s": round(statistics.median(ts), 3),
               "hist_per_s_at_min": round(args.n_histories / min(ts), 2),
               "hist_per_s_at_median":
                   round(args.n_histories / statistics.median(ts), 2),
               "reps": [round(t, 3) for t in ts]})
    speedup = min(times["legacy"]) / min(times["macro"])
    print({"speedup_at_min": round(speedup, 3),
           "acceptance_1_25x": speedup >= 1.25})


if __name__ == "__main__":
    main()
