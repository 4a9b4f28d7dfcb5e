"""Seeded generator of Elle's list-append workload as Jepsen ships it
(`jepsen.tests.cycle.append` over `elle.txn/wr-txns`; Kingsbury &
Alvaro, VLDB 2020): an op is ONE TRANSACTION of `min_txn_length` to
`max_txn_length` micro-ops, each a read with chance `read_share` and
else an append, on a key drawn from a pool of `key_count` live keys
with weight `key_dist_base ** -i` for the pool's i-th place (`key_dist`
`exponential`, the only one there is); every append to a key
carries the next value of that key's own counter (1, 2, 3, ...), so an
element is unique on its key; a key that has been handed
`max_writes_per_key` appends retires, and the next fresh key (`0, 1, 2,
...`) takes its place in the pool.

A history is a list of `(process, type, "txn", micro-ops)` rows in
real-time order. The micro-ops are a tuple of `(f, key, value)`: `f` is
`"append"` or `"r"`, an invoked read is `("r", key, None)`, a completed
one carries the WHOLE list of its key as a tuple. It is strict
serializable by construction, as `synth`'s histories are linearizable:
`processes` threads share one store, and every transaction takes
effect, all its micro-ops in order and at once, at a simulated point
between its invocation and its completion. A crashed one may have taken
effect and never reports (an `info` row with its reads still `None`, or
no row at all); its thread comes back under a fresh process id; at most
`max_crashes` transactions of a history crash, and the one that does is
more often than not one that holds an append (the control,
`crashed_ops_dropped`, treats such a transaction as never having
happened, and is wrong wherever somebody read what it appended).

The mix's `perturbed_share` of histories have ONE completed read of one
transaction corrupted: its tail dropped, two neighbours swapped, or a
later element of its key spliced in. The plain reference says which of
them are invalid: many a stale read is legal. A planted history ends in
a transaction of process 10,000 that reads a key's list reversed.

One name of the program is read (`require_workload_served`), as
`partition`'s and `long`'s generators read one: a checkout whose graftd
does not know the configuration's `service_workload` answers every
submission 400, its clients run through their pool in seconds of
warm-up, and its run ends BY ITSELF WITH EXIT CODE 0 and an empty line
(`attempted` 0, no `hist_per_s`: PERF.md, section 6, PR 51), which reads
as a result and is none. So such a program gets no pool: the run ends
in its `pool` phase, soon, with a failing line and exit code 1.
"""

from __future__ import annotations

import random

from benchmarks.generators import synth

INVOKE, OK, FAIL, INFO = synth.INVOKE, synth.OK, synth.FAIL, synth.INFO


class WorkloadNotServed(RuntimeError):
    """The checkout's program cannot run this configuration."""


def require_workload_served(config: dict) -> None:
    """Refuse a program whose graftd does not serve the workload."""
    name = config["service_workload"]
    try:
        from jepsen_jgroups_raft_tpu.service.request import \
            service_workloads
        served = name in service_workloads()
    except ImportError:
        served = False
    if not served:
        raise WorkloadNotServed(
            f"{config.get('name', 'this configuration')}: this checkout's "
            f"graftd serves no workload {name!r} (service/request.py "
            f"service_workloads): every submission would be a 400")


class Keys:
    """The pool of live keys, their counters, and the draw."""

    def __init__(self, key_count: int, base: float, max_writes: int):
        self.pool = list(range(key_count))
        self.next_key = key_count
        self.weights = [float(base) ** -i for i in range(key_count)]
        self.max_writes = max_writes
        self.writes: dict = {}

    def draw(self, rng: random.Random) -> int:
        return rng.choices(self.pool, self.weights)[0]

    def next_value(self, key: int) -> int:
        """The value of the next append to `key`; the key retires once
        it has been handed its last."""
        n = self.writes.get(key, 0) + 1
        self.writes[key] = n
        if n >= self.max_writes:
            self.pool[self.pool.index(key)] = self.next_key
            self.next_key += 1
        return n


def random_txn(rng: random.Random, keys: Keys, min_len: int,
               max_len: int, read_share: float) -> tuple:
    mops = []
    for _ in range(rng.randint(min_len, max_len)):
        key = keys.draw(rng)
        if rng.random() < read_share:
            mops.append(("r", key, None))
        else:
            mops.append(("append", key, keys.next_value(key)))
    return tuple(mops)


def apply_txn(store: dict, mops: tuple) -> tuple:
    """The transaction takes effect on `store`; its micro-ops as
    completed."""
    out = []
    for f, key, v in mops:
        if f == "r":
            out.append(("r", key, tuple(store.get(key, ()))))
        else:
            store.setdefault(key, []).append(v)
            out.append((f, key, v))
    return tuple(out)


def random_valid_rows(rng: random.Random, n_txns: int, n_procs: int,
                      crash_p: float, max_crashes: int, key_count: int,
                      base: float, min_len: int, max_len: int,
                      max_writes: int, read_share: float = 0.5) -> list:
    """`synth.random_valid_rows`'s walk with a transaction as the op."""
    keys = Keys(key_count, base, max_writes)
    store: dict = {}
    rows: list = []
    pending: dict = {}
    done = crashes = 0
    free = list(range(n_procs))
    next_pid = n_procs
    while done < n_txns or pending:
        choices = []
        if done < n_txns and free:
            choices.append("invoke")
        unlin = [p for p, d in pending.items() if d["result"] is None]
        lin = [p for p, d in pending.items() if d["result"] is not None]
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
            mops = random_txn(rng, keys, min_len, max_len, read_share)
            pending[p] = {"mops": mops, "result": None}
            rows.append((p, INVOKE, "txn", mops))
            done += 1
        elif act == "linearize":
            d = pending[rng.choice(unlin)]
            d["result"] = apply_txn(store, d["mops"])
        elif act == "complete":
            p = rng.choice(lin)
            rows.append((p, OK, "txn", pending.pop(p)["result"]))
            free.append(p)
        else:
            among = lin if act == "crash_applied" else unlin
            # more often than not the one that crashes holds an append
            holding = [p for p in among if any(
                m[0] == "append" for m in pending[p]["mops"])]
            p = rng.choice(holding if holding and rng.random() < 0.75
                           else among)
            d = pending.pop(p)
            crashes += 1
            free.append(next_pid)
            next_pid += 1
            if rng.random() < 0.5:
                rows.append((p, INFO, "txn", d["mops"]))
    return rows


def _ok_reads(rows: list) -> list:
    """(row, micro-op) of every completed read that saw an element."""
    return [(i, j) for i, r in enumerate(rows) if r[1] == OK
            for j, m in enumerate(r[3]) if m[0] == "r" and m[2]]


def _final_lists(rows: list) -> dict:
    """The longest list anybody saw of each key."""
    final: dict = {}
    for r in rows:
        if r[1] == OK:
            for f, key, v in r[3]:
                if f == "r" and len(v) > len(final.get(key, ())):
                    final[key] = v
    return final


def corrupt(rng: random.Random, rows: list) -> list:
    """One completed read of one transaction perturbed; the checker
    decides whether that broke the history."""
    reads = _ok_reads(rows)
    if not reads:
        return rows
    i, j = rng.choice(reads)
    f, key, seen = rows[i][3][j]
    how = rng.choice(["drop_tail", "swap", "splice"])
    later = [e for e in _final_lists(rows).get(key, ()) if e not in seen]
    if how == "splice" and not later:
        how = "swap"
    if how == "swap" and len(seen) < 2:
        how = "drop_tail"
    if how == "drop_tail":
        seen = seen[:-rng.randint(1, len(seen))]
    elif how == "swap":
        k = rng.randrange(len(seen) - 1)
        seen = seen[:k] + (seen[k + 1], seen[k]) + seen[k + 2:]
    else:
        k = rng.randrange(len(seen) + 1)
        seen = seen[:k] + (rng.choice(later),) + seen[k:]
    mops = rows[i][3]
    out = list(rows)
    out[i] = rows[i][:3] + (mops[:j] + ((f, key, seen),) + mops[j + 1:],)
    return out


def plant_reversed_read(rows: list) -> list:
    """An acknowledged read of a key's list back to front, after
    everything else has completed: certainly invalid. Of the key with
    the longest list; a history where nobody saw two elements of any
    key gets a read of an element nobody appended."""
    final = _final_lists(rows)
    key = max(final, key=lambda k: (len(final[k]), -k)) if final else 0
    seen = tuple(reversed(final.get(key, ())))
    if len(seen) < 2:
        seen = (10_000, 10_001)
    return rows + [(10_000, INVOKE, "txn", (("r", key, None),)),
                   (10_000, OK, "txn", (("r", key, seen),))]


def make_requests(rng: random.Random, config: dict, traffic: dict,
                  n_requests: int, first_request: int) -> list:
    """`n_requests` requests of `histories_per_request` histories each,
    perturbed and planted as `synth.make_requests` does."""
    require_workload_served(config)
    if config["key_dist"] != "exponential":
        raise ValueError(f"elle_append: no key_dist "
                         f"{config['key_dist']!r}, only 'exponential'")
    per = int(traffic["histories_per_request"])
    n = n_requests * per
    hs = [random_valid_rows(rng, int(config["ops_per_history"]),
                            int(config["processes"]),
                            config["crash_probability"],
                            int(config["max_crashes"]),
                            int(config["key_count"]),
                            config["key_dist_base"],
                            int(config["min_txn_length"]),
                            int(config["max_txn_length"]),
                            int(config["max_writes_per_key"]),
                            config["read_share"])
          for _ in range(n)]
    for i in rng.sample(range(n), round(n * traffic["perturbed_share"])):
        hs[i] = corrupt(rng, hs[i])
    every = int(traffic.get("planted_every", 0))
    out = []
    for r in range(n_requests):
        req = hs[r * per:(r + 1) * per]
        if every and (first_request + r) % every == 0:
            k = rng.randrange(per)
            req[k] = plant_reversed_read(req[k])
        out.append(req)
    return out
