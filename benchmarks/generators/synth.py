"""Seeded history generator: a copy of the program's
`history/synth.py` (`random_valid_history`, register and counter arms),
the value-level operators `synth.corrupt` draws from
(`search/operators.py`: perturb-read / -write / -cas / -sum) and
`chip_smoke.make_batch`'s planted impossible read. Kept here so that
no later PR can change the traffic; it imports nothing of the program.

A history is a list of `(process, type, f, value)` rows in real-time
order. A generated history is linearizable by construction: every op
takes effect atomically at a simulated point between its invocation and
its completion. A crashed op may have taken effect and never reports;
its process comes back under a fresh id.
"""

from __future__ import annotations

import random

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"


def random_valid_rows(rng: random.Random, kind: str, n_ops: int,
                      n_procs: int, value_range: int, crash_p: float,
                      max_crashes: int) -> list:
    state = None if kind == "register" else 0
    rows: list = []
    pending: dict = {}
    done_ops = crashes = 0
    free = list(range(n_procs))
    next_pid = n_procs
    while done_ops < n_ops or pending:
        choices = []
        if done_ops < n_ops and free:
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
            if kind == "register":
                f = rng.choice(["read", "write", "cas"])
                if f == "read":
                    value = None
                elif f == "write":
                    value = rng.randrange(value_range)
                else:
                    value = (rng.randrange(value_range),
                             rng.randrange(value_range))
            elif kind == "counter":
                f = rng.choice(["read", "add", "add-and-get"])
                value = (None if f == "read"
                         else rng.randrange(1, value_range + 1))
            else:
                raise ValueError(f"unknown history kind {kind!r}")
            pending[p] = {"f": f, "value": value, "lin": False,
                          "result": None}
            rows.append((p, INVOKE, f, value))
            done_ops += 1
        elif act == "linearize":
            d = pending[rng.choice(unlin)]
            f, v = d["f"], d["value"]
            if kind == "register":
                if f == "read":
                    d["result"] = state
                elif f == "write":
                    state = v
                else:
                    frm, to = v
                    d["result"] = state == frm
                    if d["result"]:
                        state = to
            else:
                if f == "read":
                    d["result"] = state
                else:
                    state += v
                    d["result"] = (v, state)
            d["lin"] = True
        elif act == "complete":
            p = rng.choice(lin)
            d = pending.pop(p)
            f, r = d["f"], d["result"]
            if f == "cas" and r is False:
                rows.append((p, FAIL, f, d["value"]))
            elif f in ("read", "add-and-get"):
                rows.append((p, OK, f, r))
            else:
                rows.append((p, OK, f, d["value"]))
            free.append(p)
        else:
            p = rng.choice(lin if act == "crash_applied" else unlin)
            d = pending.pop(p)
            crashes += 1
            free.append(next_pid)
            next_pid += 1
            if rng.random() < 0.5:
                rows.append((p, INFO, d["f"], d["value"]))
    return rows


def _invoke_of(rows, i):
    p = rows[i][0]
    for j in range(i - 1, -1, -1):
        if rows[j][0] == p:
            return j if rows[j][1] == INVOKE else None
    return None


def _perturb_read(rng, rows):
    idxs = [i for i, r in enumerate(rows) if r[1] == OK and r[2] == "read"]
    if not idxs:
        return None
    i = rng.choice(idxs)
    v = rows[i][3]
    rows[i][3] = (v if isinstance(v, int) else 0) + rng.choice([1, -1])
    return rows


def _perturb_write(rng, rows):
    idxs = [i for i, r in enumerate(rows) if r[1] == OK and r[2] == "write"]
    if not idxs:
        return None
    i = rng.choice(idxs)
    j = _invoke_of(rows, i)
    if j is None:
        return None
    nv = rows[i][3] + rng.choice([1, -1, 2])
    rows[i][3] = rows[j][3] = nv
    return rows


def _perturb_cas(rng, rows):
    idxs = [i for i, r in enumerate(rows)
            if r[1] in (OK, FAIL) and r[2] == "cas"]
    if not idxs:
        return None
    i = rng.choice(idxs)
    rows[i][1] = FAIL if rows[i][1] == OK else OK
    return rows


def _perturb_sum(rng, rows):
    idxs = [i for i, r in enumerate(rows)
            if r[1] == OK and r[2] == "add-and-get"]
    if not idxs:
        return None
    i = rng.choice(idxs)
    v0, s = rows[i][3]
    rows[i][3] = (v0, s + rng.choice([1, -1]))
    return rows


_OPERATORS = {"register": (_perturb_read, _perturb_write, _perturb_cas),
              "counter": (_perturb_read, _perturb_sum)}


def corrupt(rng: random.Random, rows: list, kind: str) -> list:
    """Perturb one completion; the checker decides whether that broke
    the history."""
    ops = list(_OPERATORS[kind])
    rng.shuffle(ops)
    for op in ops:
        out = op(rng, [list(r) for r in rows])
        if out is not None:
            return [tuple(r) for r in out]
    return rows


def plant_impossible_read(rows: list, kind: str) -> list:
    """An acknowledged read of a value nobody wrote: certainly
    invalid."""
    never = 99 if kind == "register" else -5
    return rows + [(10_000, INVOKE, "read", None),
                   (10_000, OK, "read", never)]


def make_requests(rng: random.Random, config: dict, traffic: dict,
                  n_requests: int, first_request: int) -> list:
    """`n_requests` requests of `histories_per_request` histories each.
    A seeded `perturbed_share` of all histories is corrupted; where the
    mix says so, every `planted_every`-th request (counted over the
    whole run, hence `first_request`) also gets one planted read."""
    per = int(traffic["histories_per_request"])
    kind = config["history_kind"]
    n = n_requests * per
    hs = [random_valid_rows(rng, kind, config["ops_per_history"],
                            config["processes"], config["value_range"],
                            config["crash_probability"],
                            config["max_crashes"])
          for _ in range(n)]
    for i in rng.sample(range(n), round(n * traffic["perturbed_share"])):
        hs[i] = corrupt(rng, hs[i], kind)
    every = int(traffic.get("planted_every", 0))
    out = []
    for r in range(n_requests):
        req = hs[r * per:(r + 1) * per]
        if every and (first_request + r) % every == 0:
            k = rng.randrange(per)
            req[k] = plant_impossible_read(req[k], kind)
        out.append(req)
    return out
