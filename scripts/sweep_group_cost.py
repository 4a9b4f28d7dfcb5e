"""What a window group costs on the chip, measured (ISSUE 33): the sweep
that `ops/dense_scan.py` `TPU_GROUP_COST` is, and the table that holds
the model's partitions to the fastest ones measured.

    python scripts/sweep_group_cost.py run --out chiprun_out/sweep33/sweep.json
    python scripts/sweep_group_cost.py table chiprun_out/sweep33/sweep.json
    python scripts/sweep_group_cost.py run --kinds register --windows 11 12 13 \
        --states 8 --rows 8 32 64 128 256 --half-windows --batches \
        --out chiprun_out/p41b/wide.json      # one family's wide rows
    python scripts/sweep_group_cost.py table chiprun_out/p41b/narrow.json \
        chiprun_out/p41b/wide.json            # several runs, one table

`run` (one process, it holds the chip) times `check_encoded` on batches
of the benchmark's own histories (`benchmarks/generators/synth.py`, the
counter cell's configuration and traffic files; `register` is the same
deployment over CAS-register histories) with the grouping FORCED: the
program's `dense_plans_grouped` is replaced, for this process, by one
that returns the partition under test, so pack, placement, the wavefront
and its blocking reads are the program's own. Every reading is the
median of `--reps` warm runs; a key is built whole before its first
timed run (`serve_rows`, as graftd's scheduler builds it).

  shapes      one group whose every row is as wide as its launch:
              crash-free histories (window 5) with W - 5 crashed writes
              open from the first event, so the closure runs as many
              sweeps a step as a launch at W can ask for (the batched
              fixpoint waits for its widest row); padded states S
              forced, rows 8 ... 1024, the cell's event length and one
              half of it.
  partitions  the cell's own batches (128, 256 and 1000 rows, both
              kinds): every contiguous partition of the batch's sorted
              windows, interleaved rep by rep.

`table` reads the file on any machine: the readings as a `GroupCost`
(`ops/dense_scan.py`: the table IS the model; a shape booked at its
launch's device phase plus the median host part of its row count,
`cost_from`; the part of a group that does not scale with its steps
from the two lengths), then, for each
batch of `partitions`, the partition `dense_scan.best_partition` picks
under the file's table and under the program's, beside the fastest
measured.
"""
import argparse
import functools
import itertools
import json
import os
import random
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KINDS = {"counter": "mask", "register": "domain"}


@functools.lru_cache(maxsize=None)
def _requests(kind: str, seed: int, n: int, ops: int, crashes: bool):
    """(model, raw histories): `n` histories of the cell's traffic (10 %
    perturbed, the planted reads), as rows."""
    from benchmarks.generators import synth as gen
    from jepsen_jgroups_raft_tpu.service.request import service_workloads

    with open(os.path.join(ROOT, "benchmarks/configs/counter-1k.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/campaign.json")) as f:
        traffic = json.load(f)
    config.update(history_kind=kind, service_workload=kind,
                  ops_per_history=ops)
    if not crashes:
        config.update(crash_probability=0.0, max_crashes=0)
    per = int(traffic["histories_per_request"])
    reqs = gen.make_requests(random.Random(seed), config, traffic,
                             -(-n // per), first_request=0)
    return (service_workloads()[kind][0](),
            [rows for req in reqs for rows in req][:n])


def _histories(kind: str, seed: int, n: int, ops: int, crashes: bool,
               widen: int = 0):
    """`_requests`, encoded as graftd's admission encodes them. `widen`:
    that many crashed writes (adds) open from the first event on, so
    that a crash-free history of window 5 has window 5 + `widen`
    throughout."""
    from benchmarks.generators.synth import INVOKE
    from jepsen_jgroups_raft_tpu.history.packing import encode_history
    from jepsen_jgroups_raft_tpu.history.synth import build_history

    model, raw = _requests(kind, seed, n, ops, crashes)
    crashed = [(20_000 + j, INVOKE,
                "add" if kind == "counter" else "write", 1)
               for j in range(widen)]
    return model, [encode_history(build_history(crashed + rows), model)
                   for rows in raw]


class Forced:
    """Stands in for `dense_plans_grouped`: returns `self.groups`, a
    list of (row indices, kind, W, S or None)."""

    def __init__(self):
        self.groups = []
        self.plans = []  # the DensePlans of the last call

    def __call__(self, model, encs):
        import numpy as np

        from jepsen_jgroups_raft_tpu.ops.dense_scan import (DensePlan,
                                                            _pad_domains)

        out = []
        for idxs, kind, w, s in self.groups:
            idxs = list(idxs)
            if kind == "mask":
                val_of, s_pad = np.zeros((len(idxs), 1), np.int32), 1
            else:
                domains = [np.asarray(model.dense_domain(encs[i].events),
                                      dtype=np.int32) for i in idxs]
                s_own, val_of = _pad_domains(domains, range(len(idxs)))
                s_pad = max(s or s_own, s_own)
                if s_pad > s_own:  # more states: more copies of id 0
                    val_of = np.concatenate(
                        [val_of, np.repeat(val_of[:, :1],
                                           s_pad - s_own, axis=1)], axis=1)
            out.append((idxs, DensePlan(kind, w, s_pad, val_of)))
        self.plans = [plan for _, plan in out]
        return out, []


def _once(forced, encs, model, groups, serve_rows):
    """One `check_encoded` over `encs` under `groups`: (seconds, the
    launch's own spans, verdicts)."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_encoded
    from jepsen_jgroups_raft_tpu.checker.schedule import snapshot_spans

    forced.groups = groups
    before = snapshot_spans()
    t0 = time.perf_counter()
    # past window 12 `auto` spends a host DFS budget before the device
    # pass and hands the kernels what it leaves: the sweep reads the
    # kernels, so such a group goes to them whole
    wide = any(w > 12 for _, _, w, _ in groups)
    res = check_encoded(encs, model, algorithm="jax" if wide else "auto",
                        lin_fastpath=False, serve_rows=serve_rows)
    wall = time.perf_counter() - t0
    after = snapshot_spans()
    spans = {k: after.get(k, {}).get("s", 0.0)
             - before.get(k, {}).get("s", 0.0)
             for k in ("launch.host", "launch.device", "launch.sync")}
    return wall, spans, [r["valid?"] for r in res]


def _reading(runs):
    walls = [w for w, _, _ in runs]
    return {"ms": round(statistics.median(walls) * 1e3, 2),
            "runs_ms": [round(w * 1e3, 2) for w in walls],
            "spans_ms": {k: round(statistics.median(
                s[k] for _, s, _ in runs) * 1e3, 2)
                for k in runs[0][1]}}


def _contiguous(windows):
    """Every partition of `windows` (sorted) into contiguous blocks."""
    for cuts in itertools.product((0, 1), repeat=len(windows) - 1):
        blocks, cur = [], [windows[0]]
        for w, cut in zip(windows[1:], cuts):
            if cut:
                blocks.append(cur)
                cur = []
            cur.append(w)
        blocks.append(cur)
        yield blocks


def run(args) -> None:
    os.environ["JGRAFT_AUTOTUNE"] = "0"  # no plan, nothing measured
    import jax

    from jepsen_jgroups_raft_tpu.checker import linearizable, schedule
    from jepsen_jgroups_raft_tpu.platform import enable_compile_cache

    enable_compile_cache()
    schedule.BUILD_THREADS = args.build_threads
    forced = linearizable.dense_plans_grouped = Forced()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"no chip: {dev}")
    ops = args.ops
    big = max(args.rows)
    out = {"device": dev.device_kind, "platform": dev.platform,
           "ops": ops, "reps": args.reps, "shapes": [], "partitions": []}

    def dump():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    t_start = time.perf_counter()
    kinds = {k: KINDS[k] for k in args.kinds}
    for kind, family in kinds.items():
        for w in args.windows:
            upto = big if w <= args.big_window else 256
            half_rows = [r for r in args.half_rows
                         if w in args.half_windows]
            model, flat = _histories(kind, args.seed, upto, ops,
                                     crashes=False, widen=w - 5)
            _, flat_half = _histories(
                kind, args.seed + 1, max(half_rows, default=0), ops // 2,
                crashes=False, widen=w - 5)
            assert max(e.n_slots for e in flat + flat_half) <= w
            for s in ([None] if family == "mask" else args.states):
                for encs_all, rows_list, cap in (
                        (flat, args.rows, upto),
                        (flat_half, half_rows, 128)):
                    for rows in sorted(rows_list, reverse=True):
                        if rows > cap or rows > len(encs_all):
                            continue
                        encs = encs_all[:rows]
                        reps = args.reps if rows <= 256 else \
                            min(args.reps, 3)
                        group = [(range(rows), family, w, s)]
                        # the first builds what is not built and loads
                        runs = [_once(forced, encs, model, group, cap)
                                for _ in range(reps + 1)][1:]
                        rec = {"kind": family, "W": w,
                               "S": forced.plans[0].n_states,
                               "rows": rows,
                               "rows_padded": schedule.launch_rows(rows),
                               "steps": max(e.n_events for e in encs),
                               **_reading(runs)}
                        out["shapes"].append(rec)
                        print(json.dumps(rec), file=sys.stderr, flush=True)
                dump()
    print(f"shapes done at {time.perf_counter() - t_start:.0f} s",
          file=sys.stderr, flush=True)

    for kind, family in kinds.items():
        model, cell = _histories(kind, args.seed + 2,
                                 max(args.batches, default=0), ops,
                                 crashes=True)
        for n in args.batches:
            encs = cell[:n]
            windows = sorted({max(e.n_slots, 1) for e in encs})
            by_w = {w: [i for i, e in enumerate(encs)
                        if max(e.n_slots, 1) == w] for w in windows}
            parts = list(_contiguous(windows))
            groups_of = [[([i for w in blk for i in by_w[w]], family,
                           blk[-1], None) for blk in blocks]
                         for blocks in parts]
            upto = max(256, n)
            runs = [[] for _ in parts]
            verdicts = None
            for rep in range(args.reps + 1):  # the first builds, loads
                for k, groups in enumerate(groups_of):
                    run_k = _once(forced, encs, model, groups, upto)
                    if verdicts is None:
                        verdicts = run_k[2]
                    assert run_k[2] == verdicts, (kind, n, parts[k])
                    if rep:
                        runs[k].append(run_k)
            rec = {"kind": family, "rows": n,
                   "invalid": verdicts.count(False),
                   "windows": {str(w): len(by_w[w]) for w in windows},
                   "steps": {str(w): max(encs[i].n_events for i in by_w[w])
                             for w in windows},
                   "states": {str(w): 1 if family == "mask" else max(
                       len(model.dense_domain(encs[i].events))
                       for i in by_w[w]) for w in windows},
                   "partitions": [{"blocks": blocks, **_reading(rs)}
                                  for blocks, rs in zip(parts, runs)]}
            out["partitions"].append(rec)
            print(json.dumps(rec), file=sys.stderr, flush=True)
            dump()
    out["seconds"] = round(time.perf_counter() - t_start, 1)
    dump()
    print(json.dumps({"out": args.out, "shapes": len(out["shapes"]),
                      "partitions": len(out["partitions"]),
                      "seconds": out["seconds"]}))


def cost_from(doc: dict):
    """The readings of a `run` file as a `GroupCost`."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    full = max(s["steps"] for s in doc["shapes"])
    rows = sorted({s["rows"] for s in doc["shapes"]})
    # What a shape is booked at (ISSUE 45): its launch's device phase
    # (span `launch.device`) plus the MEDIAN, over the shapes of its
    # kind, row count and length, of what the wall holds besides. That
    # rest is the host's (plan, pack, results) and follows the rows,
    # not the window; since ISSUE 45 it is half a 128-row group's wall
    # and reads in two modes 60-90 ms apart from shape to shape on one
    # tree, which the raw walls would book as a window's cost.
    host = {}
    for s in doc["shapes"]:
        host.setdefault((s["kind"], s["rows"], s["steps"] > 0.75 * full),
                        []).append(s["ms"] - s["spans_ms"]["launch.device"])
    shapes = [dict(s, ms=round(
        s["spans_ms"]["launch.device"] + statistics.median(
            host[s["kind"], s["rows"], s["steps"] > 0.75 * full]), 2))
        for s in doc["shapes"]]
    ms, steps, halves = {}, {}, []   # ms: kind -> S -> W -> rows -> [ms]
    for s in shapes:
        if max(s["runs_ms"]) > 3 * min(s["runs_ms"]):
            print(f"  disturbed, left out: {s}")
        elif s["steps"] > 0.75 * full:
            steps[s["kind"]] = min(steps.get(s["kind"], full), s["steps"])
            ms.setdefault(s["kind"], {}).setdefault(s["S"], {}).setdefault(
                s["W"], {}).setdefault(s["rows"], []).append(s["ms"])
        else:
            halves.append(s)
    fixed = {}
    for kind in ms:
        # two lengths of one shape: the part that does not scale
        est = []
        for h in halves:
            whole = ms[kind].get(h["S"], {}).get(h["W"], {}).get(h["rows"])
            if h["kind"] == kind and whole:
                slope = (statistics.mean(whole) - h["ms"]) / (
                    steps[kind] - h["steps"])
                est.append(statistics.mean(whole) - slope * steps[kind])
        # a file that read no second length keeps the program's
        fixed[kind] = round(statistics.median(est), 1) if est else \
            dense_scan.TPU_GROUP_COST.fixed_ms[kind]
        print(f"  {kind}: fixed part {[round(e, 1) for e in est]} ms")
    # kind -> S -> W -> {rows: ms}: means, then what a session's noise
    # must not teach the partition: a reading a smaller S lacks is the
    # next S's; a launch a window wider, or of more states, never costs
    # less (running maxima; a gap takes the maximum so far)
    tab = {k: {S: {w: {r: statistics.mean(v) for r, v in by.items()}
                   for w, by in t.items()} for S, t in by_s.items()}
           for k, by_s in ms.items()}
    for by_s in tab.values():
        sizes = sorted(by_s)
        for S, wider in zip(sizes, sizes[1:]):
            for w, by in by_s[S].items():
                for r in rows:
                    if r not in by and r <= max(max(b) for b in
                                                by_s[S].values()):
                        by[r] = by_s[wider][w][r]
        for k, S in enumerate(sizes):
            floor = {}
            for w in sorted(by_s[S]):
                by = by_s[S][w]
                for r in rows:
                    low = max(floor.get(r, 0.0),
                              by_s[sizes[k - 1]].get(w, {}).get(r, 0.0)
                              if k else 0.0)
                    if r in by or (low and any(q > r for q in by)):
                        by[r] = floor[r] = max(by.get(r, 0.0), low)
    cost = dense_scan.GroupCost(
        rows=tuple(rows), steps=steps, fixed_ms=fixed,
        ms={k: {S: {w: tuple(round(by[r], 1) for r in rows[:len(by)])
                    for w, by in sorted(t.items())}
                for S, t in sorted(by_s.items())}
            for k, by_s in tab.items()})
    return cost


def table(args) -> None:
    """The file's readings as a `GroupCost`, and what it and the
    program's own table pick for the file's batches."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    with open(args.file[0]) as f:
        doc = json.load(f)
    for more in args.file[1:]:  # one session's runs: their shapes as one
        with open(more) as f:
            doc["shapes"] += json.load(f)["shapes"]
    batches = doc["partitions"]
    if args.partitions:  # the batches of another run of the session
        with open(args.partitions) as f:
            batches = json.load(f)["partitions"]
    cost = cost_from(doc)
    print(f"{doc['device']}: {cost}")
    for name, use in (("the file's", cost),
                      ("the program's", dense_scan.TPU_GROUP_COST)):
        print(f"partitions under {name} table:")
        for b in batches:
            windows = sorted(int(w) for w in b["windows"])
            picked = dense_scan.best_partition(
                b["kind"], [(w, b["windows"][str(w)], b["states"][str(w)],
                             b["steps"][str(w)]) for w in windows], use)
            blocks = [[windows[i] for i in blk] for blk in picked]
            best = min(b["partitions"], key=lambda p: p["ms"])
            mine = next(p for p in b["partitions"]
                        if p["blocks"] == blocks)
            print(f"  {b['kind']} {b['rows']} rows {b['windows']}: picks "
                  f"{blocks} {mine['ms']} ms; fastest {best['blocks']} "
                  f"{best['ms']} ms; {mine['ms'] / best['ms'] - 1:+.1%}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--out", required=True)
    r.add_argument("--seed", type=int, default=3301000007)
    r.add_argument("--ops", type=int, default=1000)
    r.add_argument("--reps", type=int, default=5)
    r.add_argument("--rows", type=int, nargs="+",
                   default=[8, 32, 64, 128, 256, 512, 1024])
    r.add_argument("--windows", type=int, nargs="+",
                   default=[5, 6, 7, 8, 9, 10])
    r.add_argument("--kinds", nargs="+", choices=sorted(KINDS),
                   default=list(KINDS),
                   help="which families to read (a kernel change re-reads "
                        "its own family's rows)")
    r.add_argument("--big-window", type=int, default=8,
                   help="rows past 256 only up to this window")
    r.add_argument("--states", type=int, nargs="+", default=[4, 8])
    r.add_argument("--half-rows", type=int, nargs="+", default=[8, 128])
    r.add_argument("--half-windows", type=int, nargs="*", default=[6, 8])
    r.add_argument("--batches", type=int, nargs="*",
                   default=[128, 256, 1000])
    r.add_argument("--build-threads", type=int, default=12)
    r.add_argument("--rehearse", action="store_true",
                   help="allow a run without a chip (a walk-through: "
                        "its times say nothing)")
    r.set_defaults(fn=run)
    t = sub.add_parser("table")
    t.add_argument("file", nargs="+")
    t.add_argument("--partitions",
                   help="take the batches from this file's `partitions`")
    t.set_defaults(fn=table)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
