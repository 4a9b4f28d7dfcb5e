"""Seeded generator of partition-nemesis register histories: a simulated
run of the reference's documented experiment 3 (`doc/intro.md:39-41`:
five nodes, one client thread bound to each, a partition that outlasts
the request timeout) with the reference's CLI defaults (`raft.clj:14-51`:
`--rate` 10 Hz a thread, `--interval` 5 s between nemesis operations,
`--workload single-register`: reads, writes and compare-and-sets over
values in `[0, value_range)`, `register.clj:21-34`).

A simulated clock. Each thread invokes at its rate. The nemesis cuts a
seeded minority of the nodes off at every odd multiple of
`nemesis_interval_s` and heals at every even one. On a node that can
reach the majority an op takes effect at a point between its invocation
and its completion and completes `ok`, or `fail` for a `cas` that found
another value, after its latency. An op sent to a node of the minority
during a cut hangs; at `operation_timeout_s` it has CRASHED: an `info`
row (for half of them no completion row at all, as `synth` does), the
thread coming back under a fresh process id. Whether a crashed op took
effect, and at which point between its invocation and the history's
end, is a seeded coin (Jepsen's meaning of `:info`), applied in the
simulation: every history made here is linearizable by construction.
An op that the heal finds hanging goes through after it.

The configuration's two `reduced` keys cap the timeouts: `max_crashes`
a history and `max_crashes_per_cut` a cut. Past either, an op sent to a
minority node is refused at once: a `fail` row, the reference's definite
`:connect` (`client.clj:6-44`), an op that certainly did not happen.

Perturbation and planting are the mix's and `synth`'s, as in every
cell. Of the program one constant is read, before any history is made
(`require_device_window`): the deployment is a graftd whose device
families hold the widest window these histories reach, and a checkout
that would hand such rows to `auto`'s host engines gets no pool.

A history is a list of `(process, type, f, value)` rows in real-time
order, as `synth`'s.
"""

from __future__ import annotations

import heapq
import random

from benchmarks.generators import synth
from benchmarks.generators.synth import FAIL, INFO, INVOKE, OK

#: seconds past which a crashed op's seeded point of effect lies after
#: every history's end, as a multiple of the run's expected length
_HORIZON = 1.25

_INVOKE, _EFFECT, _COMPLETE, _TIMEOUT = 0, 1, 2, 3


def cut_of(t: float, interval: float) -> int:
    """Index of the cut that holds second `t` (the first is 0), or -1
    while the network is whole: cuts last from every odd multiple of
    the nemesis interval to the next even one."""
    k = int(t // interval)
    return k // 2 if k % 2 else -1


def partition_rows(rng: random.Random, config: dict,
                   clock: list = None) -> list:
    """One history. `clock`, a list, takes the simulated second of every
    row (for the tests: the rows themselves carry no time)."""
    n_ops = int(config["ops_per_history"])
    n_threads = int(config["processes"])
    value_range = int(config["value_range"])
    gap = 1.0 / float(config["rate_hz_per_thread"])
    interval = float(config["nemesis_interval_s"])
    timeout = float(config["operation_timeout_s"])
    lat_lo, lat_hi = (ms / 1e3 for ms in config["op_latency_ms"])
    n_minority = int(config["partition"]["minority_nodes"])
    max_crashes = int(config["max_crashes"])
    per_cut = int(config["max_crashes_per_cut"])
    horizon = _HORIZON * n_ops * gap / n_threads + 2 * interval

    state = None
    rows = _Rows(clock)
    crashes = 0
    crashes_in_cut: dict = {}
    minority_of: dict = {}      # cut index -> the nodes cut off
    pid = list(range(n_threads))
    next_pid = n_threads
    invoked = 0
    # (time, seq, what, thread, op): an op is {"f", "value", "result"};
    # `seq` keeps the order of ties on the clock
    events = [(rng.uniform(0.0, gap), i, _INVOKE, i, None)
              for i in range(n_threads)]
    heapq.heapify(events)
    seq = n_threads

    def push(t, what, thread, op):
        nonlocal seq
        heapq.heappush(events, (t, seq, what, thread, op))
        seq += 1

    def apply(op):
        nonlocal state
        f, v = op["f"], op["value"]
        if f == "read":
            op["result"] = state
        elif f == "write":
            state = v
        else:
            op["result"] = state == v[0]
            if op["result"]:
                state = v[1]

    while events:
        t, _, what, thread, op = heapq.heappop(events)
        if what == _INVOKE:
            if invoked >= n_ops:
                continue
            invoked += 1
            f = rng.choice(["read", "write", "cas"])
            value = (None if f == "read" else rng.randrange(value_range)
                     if f == "write" else (rng.randrange(value_range),
                                           rng.randrange(value_range)))
            op = {"f": f, "value": value, "result": None}
            rows.add(t, (pid[thread], INVOKE, f, value))
            cut = cut_of(t, interval)
            if cut >= 0 and cut not in minority_of:
                minority_of[cut] = rng.sample(range(n_threads), n_minority)
            if cut < 0 or thread not in minority_of[cut]:
                lat = rng.uniform(lat_lo, lat_hi)
                push(t + rng.uniform(0.0, lat), _EFFECT, thread, op)
                push(t + lat, _COMPLETE, thread, op)
                continue
            heal = (2 * cut + 2) * interval
            if t + timeout >= heal:
                # the heal finds it hanging: it goes through after it
                lat = rng.uniform(lat_lo, lat_hi)
                push(heal + rng.uniform(0.0, lat), _EFFECT, thread, op)
                push(heal + lat, _COMPLETE, thread, op)
            elif crashes < max_crashes and \
                    crashes_in_cut.get(cut, 0) < per_cut:
                crashes += 1
                crashes_in_cut[cut] = crashes_in_cut.get(cut, 0) + 1
                if rng.random() < 0.5:   # it took effect, some time
                    push(rng.uniform(t, horizon), _EFFECT, thread, op)
                push(t + timeout, _TIMEOUT, thread, op)
            else:
                # refused at once: it certainly did not happen
                rows.add(t, (pid[thread], FAIL, f, value))
                push(t + gap, _INVOKE, thread, None)
        elif what == _EFFECT:
            apply(op)   # a crashed op's, past the last row: unobserved
        elif what == _COMPLETE:
            f, r = op["f"], op["result"]
            if f == "cas" and r is False:
                rows.add(t, (pid[thread], FAIL, f, op["value"]))
            elif f == "read":
                rows.add(t, (pid[thread], OK, f, r))
            else:
                rows.add(t, (pid[thread], OK, f, op["value"]))
            push(_next_invoke(t, gap, rng), _INVOKE, thread, None)
        else:  # _TIMEOUT
            if rng.random() < 0.5:
                rows.add(t, (pid[thread], INFO, op["f"], op["value"]))
            pid[thread] = next_pid
            next_pid += 1
            push(_next_invoke(t, gap, rng), _INVOKE, thread, None)
    return rows.rows


class _Rows:
    """The history's rows and, where asked for, their seconds."""

    def __init__(self, clock):
        self.rows: list = []
        self.clock = clock

    def add(self, t: float, row: tuple) -> None:
        self.rows.append(row)
        if self.clock is not None:
            self.clock.append(t)


def _next_invoke(t: float, gap: float, rng: random.Random) -> float:
    """A thread's next invocation: a staggered rate, uniform on
    (0, 2 / rate) from the completion, as Jepsen's `stagger`."""
    return t + rng.uniform(0.0, 2 * gap)


def widest_window(config: dict) -> int:
    """The widest window a history made here can reach: a timed-out op
    holds its slot to the history's end, and each thread can have one
    op pending beside them."""
    return int(config["max_crashes"]) + int(config["processes"])


class WindowNotServed(RuntimeError):
    """The checkout's program cannot run this configuration."""


def require_device_window(config: dict) -> None:
    """Refuse a program whose device families end under the
    configuration's widest window. Such a program still answers: `auto`
    sends the rows past its cap through the sort ladder, which they
    overflow, and decides them with Python on graftd's one dispatcher
    thread while 256 rows wait. The tree ISSUE 40 started from did so
    at 5-13 hist/s and 18-47 s a verdict, its warm-up at the mix's cap
    with no client warm and programs still building inside the window,
    its runs spreading 27 % and 16 % (PERF.md section 5): that is the
    fault the configuration was cut to keep off the device's path, not
    a deployment anybody measures against. So the run ends here, soon
    and with a failing line, before a pool is made."""
    from jepsen_jgroups_raft_tpu.ops.kernel_ir import DENSE_MAX_SLOTS

    need = widest_window(config)
    if DENSE_MAX_SLOTS < need:
        raise WindowNotServed(
            f"{config.get('name', 'this configuration')}: histories of {config['max_crashes']} "
            f"timeouts and {config['processes']} threads reach windows of "
            f"{need}; this checkout's device families end at "
            f"{DENSE_MAX_SLOTS} (ops/kernel_ir.py DENSE_MAX_SLOTS) and the "
            f"rest would be decided on the host: not this deployment")


def make_requests(rng: random.Random, config: dict, traffic: dict,
                  n_requests: int, first_request: int) -> list:
    """`n_requests` requests of `histories_per_request` histories each,
    perturbed and planted exactly as `synth.make_requests` does it (the
    same draws in the same order), so that the mix means in this cell
    what it means in the others."""
    if config["history_kind"] != "register":
        raise ValueError("the partition generator makes register "
                         f"histories, not {config['history_kind']!r}")
    require_device_window(config)
    per = int(traffic["histories_per_request"])
    n = n_requests * per
    hs = [partition_rows(rng, config) for _ in range(n)]
    for i in rng.sample(range(n), round(n * traffic["perturbed_share"])):
        hs[i] = synth.corrupt(rng, hs[i], "register")
    every = int(traffic.get("planted_every", 0))
    out = []
    for r in range(n_requests):
        req = hs[r * per:(r + 1) * per]
        if every and (first_request + r) % every == 0:
            k = rng.randrange(per)
            req[k] = synth.plant_impossible_read(req[k], "register")
        out.append(req)
    return out
