"""Seeded generator of the long-history cell: `synth`'s histories, the
mix's perturbation and planting, `synth.make_requests` word for word,
behind one look at the program (`require_long_rows_served`).

The deployment is a graftd that hands a 100k-op history to its kernels.
A checkout whose fast lane scans such a row host-first does not: the
certifier is Python by the event, it took 235-326 s for one of these
histories (six of six certified, tier `backtrack`; my host runs, PR 44),
on graftd's one dispatcher thread, and its gate closes a row class only
after 64 rows on each side. No verdict of such a program arrives inside
a window; its run would end at the warm-up's cap with nothing to
compare. So it gets no pool: the run ends in its `pool` phase, soon and
with a failing line, as `partition`'s does for a window the device does
not hold. One name of the program is read.

A history is a list of `(process, type, f, value)` rows in real-time
order, as `synth`'s.
"""

from __future__ import annotations

import random

from benchmarks.generators import synth

#: histories up to this many ops are the host certifier's business (the
#: other cells' are 1,000): a program may scan them first
SCANNED_OPS = 2_000


class LongRowNotServed(RuntimeError):
    """The checkout's program cannot run this configuration."""


def require_long_rows_served(config: dict) -> None:
    """Refuse a program that would scan this configuration's histories
    on the host before its kernels see them."""
    ops = int(config["ops_per_history"])
    if ops <= SCANNED_OPS:
        return  # a rehearsal's rows
    try:
        from jepsen_jgroups_raft_tpu.checker.linearizable import \
            LIN_FASTPATH_MAX_EVENTS as cap
    except ImportError:
        cap = None
    if cap is None or cap > ops:  # a history holds more events than ops
        raise LongRowNotServed(
            f"{config.get('name', 'this configuration')}: histories of "
            f"{ops} ops; this checkout's fast lane scans a row of any "
            f"length host-first (checker/linearizable.py "
            f"LIN_FASTPATH_MAX_EVENTS: {cap}), minutes a history on the "
            f"dispatcher thread: not this deployment")


def make_requests(rng: random.Random, config: dict, traffic: dict,
                  n_requests: int, first_request: int) -> list:
    require_long_rows_served(config)
    return synth.make_requests(rng, config, traffic, n_requests,
                               first_request)
