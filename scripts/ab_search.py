"""Single-process interleaved A/B: guided vs random scenario search
(ISSUE-20 acceptance measurement).

Plants K proven-reachable violations (each plant carries an (operator,
edit-seed) pair verified INVALID at plant time — misses are search
failures, not planting failures), then runs the coverage-guided arm
against the `JGRAFT_SEARCH_GUIDED=0` random-ablation arm over the SAME
plant bases, operators, admission path and per-generation candidate
budget, in ONE process — the methodology this repo requires for perf
claims (cross-process comparisons measure the host's mood).

Discipline, in order:

  1. corpus DETERMINISM is asserted before anything is timed: each
     arm's warm-up run and every timed rep must produce identical
     corpus fingerprints (same seed ⇒ same corpus, the tentpole's
     reproducibility contract);
  2. every archived entry must have re-verified INVALID after
     minimization (unconfirmed == 0), re-checked here from disk;
  3. one warm-up per arm absorbs XLA compiles — batch formation is
     linger-timing-dependent, so coalesced shapes (hence compile
     cache hits) vary run-to-run; medians over interleaved reps with
     order rotation absorb the residual recompile spikes;
  4. CPU time is `time.process_time` (the driver's own accounting),
     charging the in-process graftd workers to the run.

Acceptance bars (ISSUE 20): guided recall ≥ 0.9 over K ≥ 20 plants
spanning ≥ 3 families, and guided recall-per-CPU-minute ≥ 1.5× random
(medians). The defaults reproduce the tuned operating point: seed 0,
population 32, generations 4, survivors 8, edit space 16 → measured
guided recall 1.0 at ≈1.9× random.

Usage: python scripts/ab_search.py [--plants 20] [--reps 3] [--seed 0]
       [--population 32] [--generations 4] [--survivors 8]
       [--edit-space 16] [--n-ops 16] [--families a,b,...]
"""
import argparse
import os
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plants", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--population", type=int, default=32)
    ap.add_argument("--generations", type=int, default=4)
    ap.add_argument("--survivors", type=int, default=8)
    ap.add_argument("--edit-space", type=int, default=16)
    ap.add_argument("--n-ops", type=int, default=16)
    ap.add_argument("--families",
                    default="register,set,queue,list-append")
    args = ap.parse_args()

    from jepsen_jgroups_raft_tpu.platform import pin_cpu

    pin_cpu(8)

    from jepsen_jgroups_raft_tpu.search import (Corpus, SearchConfig,
                                                plant_violations, run_recall)
    from jepsen_jgroups_raft_tpu.search.corpus import reverify_entry
    from jepsen_jgroups_raft_tpu.service.daemon import CheckingService

    families = tuple(f.strip() for f in args.families.split(",") if f.strip())
    assert len(families) >= 3, "acceptance needs plants across ≥3 families"
    assert args.plants >= 20, "acceptance needs K ≥ 20 plants"

    def config(guided: bool, corpus_dir: str) -> SearchConfig:
        return SearchConfig(
            families=families, population=args.population,
            generations=args.generations, survivors=args.survivors,
            edit_space=args.edit_space, seed=args.seed, guided=guided,
            corpus_dir=corpus_dir, n_ops=args.n_ops)

    scratch = tempfile.mkdtemp(prefix="ab-search-")
    print(f"planting {args.plants} violations across {families} "
          f"(seed {args.seed}) ...")
    plants = plant_violations(config(True, os.path.join(scratch, "plant")),
                              args.plants)
    fam_counts = {}
    for p in plants:
        fam_counts[p.base.family] = fam_counts.get(p.base.family, 0) + 1
    assert len(fam_counts) >= 3, fam_counts
    print(f"  planted: {fam_counts}")

    arms = {"guided": True, "random": False}
    fingerprints = {}  # arm -> corpus fingerprints of the FIRST run
    timed = {"guided": [], "random": []}

    def one_run(arm: str, tag: str):
        # a FRESH service per run: graftd dedupes byte-identical
        # resubmissions (ISSUE 8), so a shared service would hand later
        # reps cached verdicts and the timing would measure cache
        # lookups instead of checking CPU. The XLA compile cache is
        # process-global, so the warm-up still pays the compiles once.
        cdir = os.path.join(scratch, f"{arm}-{tag}")
        svc = CheckingService(store_root=None, batch_wait=0.02)
        try:
            rep = run_recall(config(arms[arm], cdir), plants=plants,
                             service=svc)
        finally:
            svc.shutdown(wait=True)
        fps = tuple(rep.report["corpus-fingerprints"])
        if arm in fingerprints:
            assert fps == fingerprints[arm], (
                f"{arm} corpus NOT deterministic across reps: "
                f"{len(fps)} vs {len(fingerprints[arm])} entries")
        else:
            fingerprints[arm] = fps
        assert rep.report["unconfirmed"] == 0, rep.report
        corpus = Corpus(cdir)
        for entry in corpus.entries():
            assert reverify_entry(entry), \
                f"{arm} archived a non-witness: {entry['fingerprint']}"
        shutil.rmtree(cdir, ignore_errors=True)
        return rep

    try:
        # warm-up (absorbs XLA compiles; also seeds the determinism ref)
        for arm in arms:
            r = one_run(arm, "warmup")
            print(f"  warmup {arm:6s}: recall {r.recall:.2f} "
                  f"cpu {r.cpu_s:.1f}s")
        # timed reps, interleaved, order rotated so neither arm always
        # rides the warmer cache
        orders = [("guided", "random"), ("random", "guided")]
        for i in range(args.reps):
            for arm in orders[i % len(orders)]:
                r = one_run(arm, f"rep{i}")
                timed[arm].append(r)
                print(f"  rep{i} {arm:6s}: recall {r.recall:.2f} "
                      f"cpu {r.cpu_s:.1f}s "
                      f"rpm {r.recall_per_cpu_min:.2f}")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    rows = {}
    for arm in arms:
        reps = timed[arm]
        rows[arm] = {
            "arm": arm,
            "recall": reps[0].recall,  # deterministic across reps
            "found": len(reps[0].found),
            "planted": reps[0].planted,
            "corpus": len(fingerprints[arm]),
            "cpu_s_median": round(statistics.median(
                r.cpu_s for r in reps), 3),
            "recall_per_cpu_min_median": round(statistics.median(
                r.recall_per_cpu_min for r in reps), 4),
        }
        print(rows[arm])

    g, r = rows["guided"], rows["random"]
    ratio = g["recall_per_cpu_min_median"] / \
        max(1e-9, r["recall_per_cpu_min_median"])
    print({"metric": "guided_vs_random_recall_per_cpu_min",
           "ratio": round(ratio, 3),
           "plants": args.plants, "families": list(fam_counts),
           "seed": args.seed})

    ok = True
    if g["recall"] < 0.9:
        print(f"FAIL: guided recall {g['recall']:.2f} < 0.9")
        ok = False
    if ratio < 1.5:
        print(f"FAIL: guided/random recall-per-CPU-min {ratio:.2f} < 1.5")
        ok = False
    print("AB-SEARCH " + ("PASS" if ok else "FAIL"))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
