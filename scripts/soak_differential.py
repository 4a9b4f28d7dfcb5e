#!/usr/bin/env python
"""Randomized cross-engine differential soak for the linearizability
checkers — the repeatable form of the round-3 soundness campaign (74,688
histories, 0 mismatches, 0 unknowns; BASELINE.md cites the exact command).

Every generated history (linearizable-by-construction, with a configurable
fraction randomly corrupted — the oracle decides whether a corruption
actually breaks linearizability) is verified by three INDEPENDENT engines
and the verdicts must agree:

  * the product path  — `check_histories(algorithm="auto")`: on-device
    kernels + the sound escalation ladder (checker/linearizable.py),
  * the CPU oracle    — unbounded frontier search on the UNPRUNED
    encoding (checker/wgl_cpu.py), immune to routing/prune bugs,
  * the DFS engine    — knossos/porcupine-style DFS-with-undo
    (checker/dfs_cpu.py), a structurally different search.

Any verdict mismatch is a soundness bug: the soak prints the seed and the
history and exits 1. `unknown` from the product path is reported (it is a
routing-coverage gap, not unsoundness — round-3's one finding became the
DFS escalation rung) and fails the soak only with --strict-unknown.

Reference test-philosophy anchor: evidence must be re-runnable
(/root/reference/test/jepsen/jgroups/raft_test.clj drives the production
checker on pinned histories; this scales that idea to randomized volume).

Usage (the round-3-scale campaign ≈ ~40 min on an idle 8-core host):
  python scripts/soak_differential.py --count 16000
Quick CI-sized pass (also exposed as `pytest -m soak`):
  python scripts/soak_differential.py --count 300
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from jepsen_jgroups_raft_tpu.platform import pin_cpu  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0,
                   help="base seed; history i uses seed+i (default 0)")
    p.add_argument("--count", type=int, default=2000,
                   help="number of histories (default 2000)")
    p.add_argument("--workloads", default="register,counter",
                   help="comma list of register,counter (default both)")
    p.add_argument("--max-ops", type=int, default=60,
                   help="ops per history drawn from [4, max-ops]")
    p.add_argument("--max-procs", type=int, default=6,
                   help="concurrency drawn from [1, max-procs]")
    p.add_argument("--max-crash-p", type=float, default=0.35,
                   help="per-history crash prob drawn from [0, max]")
    p.add_argument("--corrupt-frac", type=float, default=0.5,
                   help="fraction of histories perturbed (default 0.5)")
    p.add_argument("--batch", type=int, default=64,
                   help="histories per product-path batch (default 64; "
                        "batching exercises the shared-window packing)")
    p.add_argument("--strict-unknown", action="store_true",
                   help="treat product-path unknown verdicts as failures")
    p.add_argument("--product-algorithm", default="auto",
                   choices=["auto", "jax", "race", "dfs"],
                   help="algorithm for the product path — soaks every "
                        "engine behind the same oracle (default auto)")
    p.add_argument("--pin-capacity", type=int, default=None,
                   help="pin the sort-frontier kernel's capacity ladder "
                        "(n_configs) — routes kernel-checked histories "
                        "through the general sort kernel instead of the "
                        "dense planner (auto's wide-window DFS rung still "
                        "applies; incompatible with dfs, which would "
                        "silently ignore the pin)")
    p.add_argument("--platform", default="cpu", choices=["cpu", "default"],
                   help="cpu (default; pinned 8-device host mesh, "
                        "reproducible anywhere) or default backend (TPU "
                        "when attached)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.pin_capacity is not None and \
            args.product_algorithm == "dfs":
        # dfs ignores the pin entirely — refuse rather than produce
        # mislabeled evidence (round-4 review finding).
        print("--pin-capacity is incompatible with "
              f"--product-algorithm {args.product_algorithm}",
              file=sys.stderr)
        return 2
    if args.platform == "cpu":
        pin_cpu(8)

    from jepsen_jgroups_raft_tpu.checker.dfs_cpu import (
        SearchBudgetExceeded, check_encoded_dfs)
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.checker.wgl_cpu import (FrontierOverflow,
                                                         check_encoded_cpu)
    from jepsen_jgroups_raft_tpu.history.packing import encode_history
    from jepsen_jgroups_raft_tpu.history.synth import (corrupt,
                                                       random_valid_history)
    from jepsen_jgroups_raft_tpu.models.counter import Counter
    from jepsen_jgroups_raft_tpu.models.register import CasRegister

    models = {"register": CasRegister, "counter": Counter}
    workloads = [w.strip() for w in args.workloads.split(",") if w.strip()]
    for w in workloads:
        if w not in models:
            print(f"unknown workload {w!r}", file=sys.stderr)
            return 2

    t0 = time.perf_counter()
    n_done = n_corrupted = n_invalid = 0
    unknowns: list[int] = []
    mismatches: list[dict] = []

    def oracle_verdict(enc, model, seed):
        """Unpruned-unbounded frontier; None when genuinely infeasible
        (astronomically wide window — the generator's max_crashes cap
        makes this rare at soak shapes). Valid verdicts also have their
        WITNESS replayed through the sequential model — a witness that
        does not replay legally (or linearizes fewer ops than the
        history forces) is a soundness bug in the witness machinery
        even when the verdict itself is right."""
        try:
            r = check_encoded_cpu(enc, model, witness=True)
        except FrontierOverflow:
            return None
        if r.valid:
            from jepsen_jgroups_raft_tpu.history.packing import (EV_FORCE,
                                                                 EV_OPEN)

            fab = {}
            n_force = 0
            for row, oi in zip(enc.events, enc.op_index):
                if row[0] == EV_OPEN:
                    fab[int(oi)] = (int(row[2]), int(row[3]), int(row[4]))
                elif row[0] == EV_FORCE:
                    n_force += 1
            state = model.init_state()
            for oi in r.witness:
                if oi not in fab:
                    # An op index with no OPEN row is itself the
                    # witness-machinery breakage this check hunts —
                    # record it, don't crash the campaign on KeyError.
                    mismatches.append({
                        "seed": seed, "kind": "witness-unknown-op",
                        "witness": r.witness, "at": oi})
                    break
                f, a, b = fab[oi]
                state, legal = model.step(state, f, a, b)
                if not legal:
                    mismatches.append({
                        "seed": seed, "kind": "witness-replay-illegal",
                        "witness": r.witness, "at": oi})
                    break
            if len(r.witness) < n_force:
                mismatches.append({
                    "seed": seed, "kind": "witness-too-short",
                    "witness_len": len(r.witness), "n_force": n_force})
        return r.valid

    def dfs_verdict(enc, model):
        try:
            return check_encoded_dfs(enc, model, max_steps=5_000_000).valid
        except SearchBudgetExceeded:
            return None

    for start in range(0, args.count, args.batch):
        idxs = range(start, min(start + args.batch, args.count))
        batch = []  # (i, workload, history)
        for i in idxs:
            rng = random.Random(args.seed + i)
            wl = rng.choice(workloads)
            h = random_valid_history(
                rng, wl,
                n_ops=rng.randint(4, args.max_ops),
                n_procs=rng.randint(1, args.max_procs),
                crash_p=rng.uniform(0.0, args.max_crash_p),
                max_crashes=rng.randint(0, 5))
            was_corrupted = rng.random() < args.corrupt_frac
            if was_corrupted:
                h = corrupt(rng, h)
            batch.append((i, wl, h, was_corrupted))

        # Product path runs per-workload (one model per batch).
        for wl in workloads:
            rows = [(i, h, c) for i, w, h, c in batch if w == wl]
            if not rows:
                continue
            model = models[wl]()
            results = check_histories([h for _, h, _ in rows], model,
                                      algorithm=args.product_algorithm,
                                      n_configs=args.pin_capacity)
            for (i, h, was_corrupted), res in zip(rows, results):
                n_done += 1
                n_corrupted += was_corrupted
                auto = res["valid?"]
                enc_unpruned = encode_history(h, model, prune=False)
                oracle = oracle_verdict(enc_unpruned, model, args.seed + i)
                dfs = dfs_verdict(enc_unpruned, model)
                n_invalid += oracle is False
                if not was_corrupted and oracle is False:
                    mismatches.append({
                        "seed": args.seed + i, "workload": wl,
                        "kind": "generator-unsound",
                        "detail": "valid-by-construction history judged "
                                  "invalid by the oracle"})
                # The product path signals unknown with the UNKNOWN
                # sentinel ("unknown"), never None — compare on
                # bool-ness, not identity with None. Oracle overflow
                # (None) also lands here: with no ground truth the
                # comparison is a coverage gap, not a verdict.
                if not isinstance(auto, bool) or oracle is None:
                    unknowns.append(args.seed + i)
                    if args.strict_unknown:
                        mismatches.append({
                            "seed": args.seed + i, "workload": wl,
                            "kind": "unknown", "auto": repr(auto),
                            "oracle": oracle, "dfs": dfs})
                    continue
                disagree = [
                    name for name, v in
                    (("auto", auto), ("dfs", dfs))
                    if isinstance(v, bool) and v is not oracle
                ]
                if disagree:
                    mismatches.append({
                        "seed": args.seed + i, "workload": wl,
                        "kind": "verdict-mismatch", "auto": auto,
                        "oracle": oracle, "dfs": dfs,
                        "history": [(o.process, o.type, o.f, o.value)
                                    for o in h]})
        done = min(start + args.batch, args.count)
        if done % max(args.batch * 4, 256) < args.batch or done == args.count:
            dt = time.perf_counter() - t0
            print(f"  {done}/{args.count} histories  "
                  f"({done / dt:.0f}/s, {len(mismatches)} mismatches, "
                  f"{len(unknowns)} unknown)", flush=True)

    dt = time.perf_counter() - t0
    summary = {
        "histories": n_done,
        "corrupted": n_corrupted,
        "oracle_invalid": n_invalid,
        "mismatches": len(mismatches),
        "unknowns": len(unknowns),
        "time_s": round(dt, 1),
        "seed": args.seed,
        "count": args.count,
    }
    print(json.dumps(summary))
    for m in mismatches[:20]:
        print("MISMATCH:", json.dumps(m), file=sys.stderr)
    if unknowns:
        print(f"unknown seeds (routing-coverage gaps): {unknowns[:50]}",
              file=sys.stderr)
    return 1 if mismatches else 0


if __name__ == "__main__":
    sys.exit(main())
