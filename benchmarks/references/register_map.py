"""A map of compare-and-set registers, each initially unset: read,
write, cas of `(key, value)`, the keys the integers `0, 1, 2, ...` that
the reference's `:multi-register` workload takes from `(range)` IN
ORDER (`workload/workload.clj:7-15`; checked there one key at a time, by
`independent/checker` of `knossos.model/cas-register`,
`workload/register.clj:106-117`). Here the model is the map:
`references/frontier.py` walks the history as it was recorded, every
key's ops in one real-time order, and knows nothing of a split by key.

A register's semantics are `cas_register.py`'s, departures and all: a
write or a cas that completed `ok` took effect; a cas that `fail`ed did
not happen; one whose completion is unknown may have and may not, to
the history's end; an acknowledged read of an unset register is legal
only while it is unset.

The state holds the keys the group of threads can still be at, and
forgets the ones behind it. A map that kept every key cannot be
searched: two writes that were both open when a key's ops ended leave
its last value either's for ever, so the frontier grows by that factor
a key (92,928 configurations 18 keys into one history, 64 s for 2,000
ops: PERF.md, section 6, PR 47). The source's threads take the keys in
order, and an op that completes lasts from one key into the next at
most, so once an op of key `k` has taken effect no acknowledged op of a
key under `k - 1` is still to come: the state is `(lo, value of lo,
value of lo + 1, ...)` with `lo` at least `k - 1`, a key past its end
unset, a key under `lo` forgotten. What a crashed op does to a
forgotten key nobody can see: its write is taken as done, its cas is
never taken. An ACKNOWLEDGED op of a forgotten key is not this
workload: `step` raises `KeysOutOfOrder` and the comparison ends
without a verdict, never with a wrong one.
"""

INIT = (0,)


class KeysOutOfOrder(ValueError):
    """An acknowledged op of a key the threads had left behind."""


def encode(f, value, ctype, cvalue):
    if ctype == "fail":
        return None
    forced = ctype == "ok"
    key, v = value
    if f == "read":
        return (("read", key, cvalue[1], None, True), True) if forced \
            else None
    if f == "write":
        return ("write", key, v, None, forced), forced
    if f == "cas":
        return ("cas", key, v[0], v[1], forced), forced
    raise ValueError(f"register_map: unknown f {f!r}")


def put(state, key, value):
    """`state` with `key` (at least `state[0]`) at `value`, the keys
    under `key - 1` forgotten."""
    lo = state[0]
    values = state[1:] + (None,) * (key - lo + 1 - (len(state) - 1))
    values = values[:key - lo] + (value,) + values[key - lo + 1:]
    if key - 1 > lo:
        values, lo = values[key - 1 - lo:], key - 1
    while values and values[-1] is None:
        values = values[:-1]
    return (lo,) + values


def step(state, op):
    f, key, a, b, forced = op
    lo = state[0]
    if key < lo:
        if forced:
            raise KeysOutOfOrder(
                f"an acknowledged {f} of key {key} after an op of key "
                f"{lo + 1} or later took effect: the keys are not taken "
                f"in order, one after another")
        return state, f == "write"
    at = key - lo + 1
    now = state[at] if at < len(state) else None
    if f == "read":
        return (put(state, key, now), True) if now == a else (state, False)
    if f == "write":
        return put(state, key, a), True
    return (put(state, key, b), True) if now == a else (state, False)
