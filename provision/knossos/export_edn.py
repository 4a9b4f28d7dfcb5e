"""Export histories to knossos-readable EDN.

The bridge between this framework's stores / synthetic batches and the
JVM knossos timing harness (core.clj): one `.edn` file per history,
each a vector of op maps in the shape knossos consumes — the same
shape the reference's golden histories use
(/root/reference/test/jepsen/jgroups/raft_test.clj:9-25):

    {:process 0 :type :invoke :f :write :value 1 :index 4 :time 123}

Modes:
  --north-star OUT   synthesize the BASELINE north-star batch (1000 ×
                     1k-op CAS-register histories, seed 20260729).
  --store RUN OUT    export a recorded run dir's history.jsonl,
                     splitting multi-register tuples per key the way
                     `independent/checker` does (register.clj:106).

Runs on the build host (no JVM needed): only the timing half needs
docker. Unit-tested by tests/test_knossos_export.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def edn_value(v):
    if v is None:
        return "nil"
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, str):
        return json.dumps(v)
    if isinstance(v, (list, tuple)):
        return "[" + " ".join(edn_value(x) for x in v) + "]"
    raise TypeError(f"no EDN encoding for {type(v)}: {v!r}")


def op_edn(op: dict) -> str:
    parts = [f":process {edn_value(op['process'])}",
             f":type :{op['type']}",
             f":f :{op['f']}",
             f":value {edn_value(op.get('value'))}"]
    if "index" in op:
        parts.append(f":index {op['index']}")
    if "time" in op:
        parts.append(f":time {op['time']}")
    return "{" + " " .join(parts) + "}"


def history_edn(ops) -> str:
    return "[" + "\n ".join(op_edn(o) for o in ops) + "]"


def write_histories(histories, out_dir: str) -> int:
    os.makedirs(out_dir, exist_ok=True)
    for i, ops in enumerate(histories):
        with open(os.path.join(out_dir, f"h{i:05d}.edn"), "w") as f:
            f.write(history_edn(ops))
    return len(histories)


def north_star_histories(n: int = 1000):
    """First `n` histories of BASELINE.md's north-star batch (one
    seed and shape — the comparison is only meaningful on identical
    inputs)."""
    import random

    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history

    rng = random.Random(20260729)
    out = []
    for _ in range(n):
        h = random_valid_history(rng, "register", n_ops=1000, n_procs=5,
                                 crash_p=0.05, max_crashes=3)
        out.append([{"process": o.process, "type": o.type, "f": o.f,
                     "value": list(o.value) if isinstance(o.value, tuple)
                     else o.value, "index": i, "time": o.time}
                    for i, o in enumerate(h)])
    return out


def store_histories(run_dir: str):
    """Load a recorded run and split it per key — through the SAME
    loader + client-op filter + independent split the product checker
    uses (core/store.load_history → History.client_ops →
    checker/independent.split_by_key), so the exported histories are
    exactly what `check` would verify: nemesis ops filtered, tuple
    values unwrapped."""
    from jepsen_jgroups_raft_tpu.checker.independent import split_by_key
    from jepsen_jgroups_raft_tpu.core.store import load_history

    hist = load_history(run_dir).client_ops()
    tupled = any(isinstance(o.value, (list, tuple)) and len(o.value) == 2
                 for o in hist if o.type == "invoke")
    per_key = split_by_key(hist) if tupled else {None: hist}
    out = []
    for k in sorted(per_key, key=str):
        ops = per_key[k]
        out.append([{"process": o.process, "type": o.type, "f": o.f,
                     "value": list(o.value) if isinstance(o.value, tuple)
                     else o.value,
                     "index": i, "time": o.time}
                    for i, o in enumerate(ops)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--north-star", metavar="OUT")
    ap.add_argument("--store", nargs=2, metavar=("RUN_DIR", "OUT"))
    args = ap.parse_args(argv)
    if args.north_star:
        n = write_histories(north_star_histories(), args.north_star)
    elif args.store:
        n = write_histories(store_histories(args.store[0]), args.store[1])
    else:
        ap.error("pick --north-star or --store")
    print(f"wrote {n} histories")
    return 0


if __name__ == "__main__":
    sys.exit(main())
