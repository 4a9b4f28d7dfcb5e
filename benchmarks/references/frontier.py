"""Plain reference: is a history linearizable?

Wing & Gong's search with Lowe's memoisation, in its frontier form:
walk the history in real-time order; keep the set of reachable
configurations (ops linearized so far, model state); when an op
completes `ok`, extend every configuration by every pending op that is
legal in it until nothing new appears, then keep only those that have
linearized the completed op. An empty set means no linearization
exists. Python sets and integers, no cap: the answer is exact.

Completion semantics are the reference system's (Jepsen's taxonomy):
`fail` means the op did not happen and is dropped; `info`, or no
completion at all, means it may or may not have happened, so it stays
pending to the end; an `info` read constrains nothing and is dropped.

The model comes from `references/<model>.py`: `INIT`, `encode(f,
invoke_value, completion_type, completion_value) -> (op, forced) |
None`, `step(state, op) -> (state, legal)`. Nothing here imports the
program under test.
"""

from __future__ import annotations


def linearizable(rows, model) -> bool:
    """`rows`: (process, type, f, value) in real-time order."""
    ops: list = []          # [f, invoke value, completion type, value]
    at: list = []           # per row: ("open" | "force", op id)
    pending: dict = {}
    for p, typ, f, value in rows:
        if typ == "invoke":
            if p in pending:
                raise ValueError(f"process {p} invoked twice")
            pending[p] = len(ops)
            ops.append([f, value, "info", None])
            at.append(("open", pending[p]))
        else:
            k = pending.pop(p)
            ops[k][2], ops[k][3] = typ, value
            at.append(("force", k))
    enc = [model.encode(*o) for o in ops]
    step = model.step
    frontier = {(0, model.INIT)}
    open_ops: dict = {}     # op id -> bit
    free_bits: list = []
    n_bits = 0
    for what, k in at:
        if enc[k] is None:
            continue
        op, forced = enc[k]
        if what == "open":
            if free_bits:
                bit = free_bits.pop()
            else:
                bit = 1 << n_bits
                n_bits += 1
            open_ops[k] = (bit, op)
            continue
        if not forced:
            continue
        cands = list(open_ops.values())
        stack = list(frontier)
        while stack:
            mask, state = stack.pop()
            for bit, o in cands:
                if mask & bit:
                    continue
                state2, legal = step(state, o)
                if legal:
                    cfg = (mask | bit, state2)
                    if cfg not in frontier:
                        frontier.add(cfg)
                        stack.append(cfg)
        bit = open_ops.pop(k)[0]
        frontier = {(m & ~bit, s) for m, s in frontier if m & bit}
        if not frontier:
            return False
        free_bits.append(bit)
    return True
