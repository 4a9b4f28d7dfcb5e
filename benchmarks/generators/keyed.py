"""Seeded generator of the multi-key register map: the reference's
`:multi-register` workload (`workload/workload.clj:7-15`,
`workload/register.clj:106-117`) as a suite's run records it. One group
of `processes` threads works through the keys `0, 1, 2, ...` in order
(`(range)`, `independent/concurrent-generator`), `ops_per_key` ops a key
(`raft.clj:24-27`, `--ops-per-key`), `ops_per_history` ops in all; an
op is a read, a write or a cas drawn uniformly, its values in
`[0, value_range)` (`register.clj:21-34`), its value wrapped `(key,
value)`. The group moves on to the next key when a key's ops are all
invoked, while that key's last ops may still be open: keys overlap at
their seams and nowhere else (an op that completes lasts from one key
into the next at most; one that hangs longer has crashed).

A history is a list of `(process, type, f, (key, value))` rows in
real-time order; a read's invocation is `(key, None)`, a cas
`(key, (from, to))`. It is linearizable by construction, as `synth`'s
are: every op takes effect atomically at a simulated point between its
invocation and its completion, on a map of registers that are unset
until written. A crashed op may have taken effect and never reports;
its thread comes back under a fresh process id, and at most
`max_crashes` ops of a HISTORY crash (not of a key: an op whose
completion is unknown stays a candidate to the history's end for a
checker that sees the map whole). The mix's `perturbed_share` of
histories have one completion of one key corrupted (`synth.corrupt`'s
operators on that key's rows); a planted history ends in an impossible
read of its last key by process 10,000. It imports nothing of the program.
"""

from __future__ import annotations

import random

from benchmarks.generators import synth

INVOKE, OK, FAIL, INFO = synth.INVOKE, synth.OK, synth.FAIL, synth.INFO


def random_valid_rows(rng: random.Random, n_ops: int, ops_per_key: int,
                      n_procs: int, value_range: int, crash_p: float,
                      max_crashes: int) -> list:
    """`synth.random_valid_rows`'s walk (register arm) over a map: the
    k-th op invoked goes to key `k // ops_per_key`."""
    state: dict = {}
    rows: list = []
    pending: dict = {}
    done_ops = crashes = 0
    free = list(range(n_procs))
    next_pid = n_procs
    while done_ops < n_ops or pending:
        choices = []
        key = done_ops // ops_per_key
        # a thread that is still at key k - 2 holds the group back: an
        # op that completes lasts from one key into the next at most
        if done_ops < n_ops and free and not any(
                d["key"] < key - 1 for d in pending.values()):
            choices.append("invoke")
        unlin = [p for p, d in pending.items() if not d["lin"]]
        lin = [p for p, d in pending.items() if d["lin"]]
        may_crash = crashes < max_crashes
        if unlin:
            choices.append("linearize")
            if may_crash and rng.random() < crash_p:
                choices.append("crash_unapplied")
        if lin:
            choices.append("complete")
            if may_crash and rng.random() < crash_p:
                choices.append("crash_applied")
        act = rng.choice(choices)
        if act == "invoke":
            p = free.pop(rng.randrange(len(free)))
            f = rng.choice(["read", "write", "cas"])
            if f == "read":
                value = None
            elif f == "write":
                value = rng.randrange(value_range)
            else:
                value = (rng.randrange(value_range),
                         rng.randrange(value_range))
            pending[p] = {"key": key, "f": f, "value": value, "lin": False,
                          "result": None}
            rows.append((p, INVOKE, f, (key, value)))
            done_ops += 1
        elif act == "linearize":
            d = pending[rng.choice(unlin)]
            key, f, v = d["key"], d["f"], d["value"]
            if f == "read":
                d["result"] = state.get(key)
            elif f == "write":
                state[key] = v
            else:
                d["result"] = state.get(key) == v[0]
                if d["result"]:
                    state[key] = v[1]
            d["lin"] = True
        elif act == "complete":
            p = rng.choice(lin)
            d = pending.pop(p)
            key, f = d["key"], d["f"]
            if f == "cas" and d["result"] is False:
                rows.append((p, FAIL, f, (key, d["value"])))
            elif f == "read":
                rows.append((p, OK, f, (key, d["result"])))
            else:
                rows.append((p, OK, f, (key, d["value"])))
            free.append(p)
        else:
            p = rng.choice(lin if act == "crash_applied" else unlin)
            d = pending.pop(p)
            crashes += 1
            free.append(next_pid)
            next_pid += 1
            if rng.random() < 0.5:
                rows.append((p, INFO, d["f"], (d["key"], d["value"])))
    return rows


def keys_of(rows: list) -> list:
    return sorted({r[3][0] for r in rows})


def corrupt_one_key(rng: random.Random, rows: list) -> list:
    """One completion of one seeded key perturbed: `synth.corrupt` on
    that key's rows (values unwrapped), written back in place."""
    key = rng.choice(keys_of(rows))
    at = [i for i, r in enumerate(rows) if r[3][0] == key]
    sub = synth.corrupt(rng, [rows[i][:3] + (rows[i][3][1],) for i in at],
                        "register")
    out = list(rows)
    for i, (p, typ, f, v) in zip(at, sub):
        out[i] = (p, typ, f, (key, v))
    return out


def plant_impossible_read(rows: list) -> list:
    """An acknowledged read of a value nobody wrote: certainly invalid.
    Of the key the threads ended at: the run is over, and a read of a
    key they left long ago is not this workload's."""
    key = keys_of(rows)[-1]
    return rows + [(10_000, INVOKE, "read", (key, None)),
                   (10_000, OK, "read", (key, 99))]


def make_requests(rng: random.Random, config: dict, traffic: dict,
                  n_requests: int, first_request: int) -> list:
    """`n_requests` requests of `histories_per_request` histories each,
    perturbed and planted as `synth.make_requests` does."""
    per = int(traffic["histories_per_request"])
    n = n_requests * per
    hs = [random_valid_rows(rng, int(config["ops_per_history"]),
                            int(config["ops_per_key"]),
                            int(config["processes"]),
                            int(config["value_range"]),
                            config["crash_probability"],
                            int(config["max_crashes"]))
          for _ in range(n)]
    for i in rng.sample(range(n), round(n * traffic["perturbed_share"])):
        hs[i] = corrupt_one_key(rng, hs[i])
    every = int(traffic.get("planted_every", 0))
    out = []
    for r in range(n_requests):
        req = hs[r * per:(r + 1) * per]
        if every and (first_request + r) % every == 0:
            k = rng.randrange(per)
            req[k] = plant_impossible_read(req[k])
        out.append(req)
    return out
