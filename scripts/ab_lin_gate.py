"""Verdict identity across the host certifier's routing arms (ISSUE 28),
on the benchmark's own traffic, through graftd's lane and launch.

The measured gate (checker/autotune.py `lin_fastpath_route`) only ever
routes: a gated row, an undecided row and a row checked with the
certifier forced off reach the same kernel ladder. This script shows it
on `counter-1k.campaign`'s traffic (`benchmarks/generators/synth.py`,
the cell's configuration and traffic files): each seed's requests go
through a real `BatchScheduler` (`fastlane`, then `execute` with its
demux and counterexamples) and every row's `valid?`, failing op and
counterexample are written out, one file an arm:

    python scripts/ab_lin_gate.py run --arm default --out OUT/default.json
    python scripts/ab_lin_gate.py run --arm off     --out OUT/off.json
    python scripts/ab_lin_gate.py run --arm always  --out OUT/always.json
    python scripts/ab_lin_gate.py compare OUT/default.json OUT/off.json ...

`default` sets nothing (the measured gate over a throwaway store: it
closes within the first seed and the file says how many rows it gated),
`off` is `JGRAFT_LIN_FASTPATH=0`, `always` is `JGRAFT_AUTOTUNE=0` (every
row scanned, as before ISSUE 28). One process an arm: a chip belongs to
one process at a time. `run` uses nothing this PR added to the program,
so the same file drives an older checkout (`--root`) for the
comparison with its defaults. `compare` exits 1 on the first file that
differs from the first in any row.
"""
import argparse
import hashlib
import json
import os
import random
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARMS = {"default": {}, "off": {"JGRAFT_LIN_FASTPATH": "0"},
        "always": {"JGRAFT_AUTOTUNE": "0"}}


def run(args) -> None:
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.environ.update(ARMS[args.arm])
    os.environ["JGRAFT_AUTOTUNE_STORE"] = tempfile.mkdtemp(
        prefix="ab-lin-gate-")

    from benchmarks.generators import synth as gen
    from jepsen_jgroups_raft_tpu.checker.linearizable import \
        fastpath_counters
    from jepsen_jgroups_raft_tpu.history.synth import build_history
    from jepsen_jgroups_raft_tpu.service.admission import AdmissionQueue
    from jepsen_jgroups_raft_tpu.service.request import admit
    from jepsen_jgroups_raft_tpu.service.scheduler import BatchScheduler

    with open(os.path.join(root, "benchmarks/configs/counter-1k.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/campaign.json")) as f:
        traffic = json.load(f)
    if args.ops:
        config["ops_per_history"] = args.ops
    if args.kind != config["history_kind"]:
        # the same deployment over the other kind of history (ISSUE 32:
        # the register's dense domain kernels beside the counter's mask)
        config.update(history_kind=args.kind, service_workload=args.kind)
    import jax

    sched = BatchScheduler(AdmissionQueue())
    out = {"arm": args.arm, "root": root, "kind": args.kind,
           "device": jax.devices()[0].device_kind, "seeds": {}}
    for seed in args.seeds:
        reqs = [admit([build_history(rows) for rows in req],
                      config["service_workload"])
                for req in gen.make_requests(
                    random.Random(seed), config, traffic, args.requests,
                    first_request=0)]
        t0 = time.perf_counter()
        decided, live = sched.fastlane(reqs)
        sched.execute(live)
        rows = []
        for r in reqs:
            for res in r.results:
                ce = json.dumps(res.get("counterexample"), sort_keys=True,
                                default=repr)
                rows.append([res["valid?"], res.get("failing-op-index"),
                             hashlib.sha256(ce.encode()).hexdigest()[:16]
                             if res.get("counterexample") else None])
        out["seeds"][str(seed)] = rows
        print(f"{args.arm} seed {seed}: {len(rows)} rows, "
              f"{sum(1 for v, _, _ in rows if v is False)} invalid, "
              f"{len(decided)} requests from the lane, "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    out["fastpath_counters"] = fastpath_counters()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({"arm": args.arm, "out": args.out,
                      "fastpath_counters": out["fastpath_counters"]}))


def compare(args) -> None:
    docs = []
    for path in args.files:
        with open(path) as f:
            docs.append(json.load(f))
    base, bad = docs[0], 0
    for path, doc in zip(args.files[1:], docs[1:]):
        n = diff = 0
        for seed, rows in base["seeds"].items():
            other = doc["seeds"].get(seed)
            if other is None or len(other) != len(rows):
                print(f"{path}: seed {seed} missing or of another size")
                diff += 1
                continue
            for i, (a, b) in enumerate(zip(rows, other)):
                n += 1
                if a != b:
                    diff += 1
                    print(f"{path}: seed {seed} row {i}: {a} != {b}")
        print(f"{args.files[0]} ({base['arm']}) vs {path} ({doc['arm']}): "
              f"{n} rows compared, {diff} differ")
        bad += diff
    sys.exit(1 if bad else 0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--arm", choices=sorted(ARMS), required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--root", default=ROOT,
                   help="the checkout whose program is driven")
    r.add_argument("--seeds", type=int, nargs="+",
                   default=[2801000001, 2801000013, 2801000027,
                            2801000039, 2801000043, 2801000057])
    r.add_argument("--requests", type=int, default=4,
                   help="requests a seed (32 histories each)")
    r.add_argument("--kind", choices=["counter", "register"],
                   default="counter",
                   help="kind of history (default: the configuration's)")
    r.add_argument("--ops", type=int, default=0,
                   help="ops a history (default: the configuration's)")
    r.set_defaults(fn=run)
    c = sub.add_parser("compare")
    c.add_argument("files", nargs="+")
    c.set_defaults(fn=compare)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
