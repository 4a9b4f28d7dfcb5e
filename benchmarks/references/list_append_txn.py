"""A map of append-only lists, each initially empty, with a TRANSACTION
as the atomic op: Elle's list-append workload (Kingsbury & Alvaro, VLDB
2020; Jepsen's `jepsen.tests.cycle.append`) held to strict
serializability, which is linearizability of the transactions over one
object, the map. `references/frontier.py` walks the history as it was
recorded and knows nothing of dependency graphs, keys or cycles.

A row's value is the tuple of its transaction's micro-ops, `("append",
key, element)` or `("r", key, list)`; an invoked read carries `None`, a
completed one the WHOLE list of its key as the client saw it. `step`
applies a transaction's micro-ops in order, at one point in time: an
append extends its key's list; a read is legal iff the list, the
transaction's own earlier appends included, equals what it saw.

A transaction that completed `ok` took effect, reads and all; one that
`fail`ed did not happen; one whose completion is unknown (`info`, or
none at all) may have taken effect and may not, to the history's end,
and what it read constrains nothing: its appends are kept, its reads
dropped, and one with no append at all is dropped whole.

The state is `(keys, entries, forgotten)`: the keys somebody can still
read, in order, beside each its `(list, readers left)`, and the set of
keys whose last reader has taken effect: hashable, and equal for equal
maps however they were reached. A key that nobody ever reads is in none
of the three; a forgotten key's list is gone, whatever is appended to
it: nobody can see it again. (Two tuples and `bisect`, so that a step
copies thirty entries in C and sorts nothing.) A map that kept every list
to the end cannot be searched: two appends to one key that were both
open when the last read of that key had gone leave its list in either
order for ever, and an `info` transaction's append that nobody observed
may sit anywhere behind that read, so the frontier multiplies a key (the
first full-size history did not end in 40 minutes; forgetting a key
only once every transaction that NAMES it had taken effect still left
one history in five over a second and one in forty over 15 s, two runs
of eight past the harness's 60 s: PERF.md, section 6, PR 51). That needs
the number of transactions that read each key, which `frontier.py`
cannot give (it hands `encode` one op at a time), so `encode` counts
them as it goes, in a context that the history's ops share:
`frontier.py` encodes every op of a history before its first `step`,
and the first `encode` after a `step` starts a new context. Only `ok`
transactions read (an `info` one's reads are dropped), and an `ok`
transaction takes effect before it completes, so every key is forgotten
in every configuration. The same context holds every prefix of every
list that was seen of a key, and an append to a key that still has a
reader to come is legal only if the list it makes is one of them: lists
only grow, so that reader saw a list that BEGINS with this one, or no
continuation of this configuration answers it. That ends a wrong order
of two concurrent appends where it is made and not at the key's next
read, which an unpopular key may not get for hundreds of transactions
(one history of 480 took 6.6 s and held 95,040 configurations at once,
and one of some 3,000 compared on the chip's host made a child's sixteen
rows take 53.4 s of the harness's 60; with it that history takes 0.075 s
and holds 80, and 1,200 read 0.058 s in the median and 0.33 s at the
worst where 480 read 0.21 and 6.6: my host runs, PR 51).

Used any other way (a history stepped before all of it is encoded) the
count of a key's readers comes out short, and `step` RAISES at the
first sign of it, a read of a forgotten key or a key its context never
counted: on a sound count neither can happen.
`tests/test_listappend_txn.py` holds the verdicts to those of the same
`step` with every key's readers counted as without end and every list
taken as seen (nothing is ever forgotten, no configuration ended
early), at sizes where that search ends.
"""

from bisect import bisect_left

INIT = ((), (), frozenset())

#: the context of the history being encoded: `readers`, {key:
#: transactions that read it}, and `shown`, {key: every prefix of every
#: list a transaction saw of it}; `step` marks it over, and the next
#: `encode` starts another
_HISTORY = {"readers": {}, "shown": {}, "over": False}


def encode(f, value, ctype, cvalue):
    global _HISTORY
    if f != "txn":
        raise ValueError(f"list_append_txn: unknown f {f!r}")
    if _HISTORY["over"]:
        _HISTORY = {"readers": {}, "shown": {}, "over": False}
    if ctype == "fail":
        return None
    if ctype == "ok":
        mops = tuple((m[0], m[1], tuple(m[2]) if m[0] == "r" else m[2])
                     for m in cvalue)
    else:
        mops = tuple((m[0], m[1], m[2]) for m in value if m[0] == "append")
        if not mops:
            return None
    readers, shown = _HISTORY["readers"], _HISTORY["shown"]
    reads = frozenset(m[1] for m in mops if m[0] == "r")
    for m in mops:
        readers.setdefault(m[1], 0)
    for key in reads:
        readers[key] += 1
    for m in mops:
        if m[0] == "r":     # a list's prefixes go in together
            have, n = shown.setdefault(m[1], set()), len(m[2])
            while n and m[2][:n] not in have:
                have.add(m[2][:n])
                n -= 1
    return (mops, reads, readers, shown), ctype == "ok"


def step(state, op):
    mops, reads, readers, shown = op
    _HISTORY["over"] = True
    keys, entries, forgotten = state
    for f, key, v in mops:
        at = bisect_left(keys, key)
        if at == len(keys) or keys[at] != key:
            if key not in readers or (f == "r" and key in forgotten):
                raise RuntimeError(
                    f"list_append_txn: key {key!r} has more readers than "
                    f"were counted: encode EVERY op of a history, then "
                    f"step it, one history at a time")
            if key in forgotten or not readers[key]:
                continue        # a key nobody reads (any more)
            keys = keys[:at] + (key,) + keys[at:]
            entries = entries[:at] + (((), readers[key]),) + entries[at:]
        seen, left = entries[at]
        if f == "r":
            if seen != v:
                return state, False
        elif f == "append":
            # another transaction's read of this key is still to come,
            # and a list only grows: what it saw begins with this list,
            # or this configuration is dead already (this transaction's
            # own later reads are held to the list below, exactly)
            if left > (key in reads) \
                    and seen + (v,) not in shown.get(key, ()):
                return state, False
            entries = entries[:at] + ((seen + (v,), left),) \
                + entries[at + 1:]
        else:
            raise ValueError(f"list_append_txn: unknown micro-op {f!r}")
    for key in reads:
        at = bisect_left(keys, key)
        seen, left = entries[at]
        if left > 1:
            entries = entries[:at] + ((seen, left - 1),) + entries[at + 1:]
        else:
            keys = keys[:at] + keys[at + 1:]
            entries = entries[:at] + entries[at + 1:]
            forgotten = forgotten | {key}
    return (keys, entries, forgotten), True
