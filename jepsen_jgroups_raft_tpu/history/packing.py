"""Pack an operation history into a fixed-shape int32 event tensor.

This is the "tensor-packing path" of the north star (BASELINE.json): the
bridge between jepsen-style histories and the on-device frontier search.

Key design decision (TPU-first): instead of shipping raw (invoke, complete)
interval pairs to the device, the host compiles the history into a compact
**event stream** the kernel can scan with fixed shapes:

  OPEN  slot f a b   — an op becomes available for linearization. The op is
                       assigned a *slot*: a position in a sliding window of
                       at most W concurrently-open ops. Slots of completed
                       (ok) ops are recycled; crashed (info) ops hold their
                       slot forever (they remain linearization candidates
                       until the end — reference doc/intro.md:35-41 names
                       exactly this as the checker-pressure problem).
  FORCE slot         — the op in `slot` completed ok: every surviving
                       search configuration must have linearized it by now.

A search configuration is then just (uint32 bitmask over W slots, int32
model state) — fixed width, dedupable by sort, vmappable. The algorithm is
the Wing&Gong/Lowe linear search reshaped for SIMD: closure-expansion of the
frontier needs to run only at FORCE events, because between two completions
every open op is mutually concurrent (no real-time edge can appear without a
completion), so deferring expansion to the next FORCE reaches the identical
configuration set.

`fail` completions are dropped before packing (the op never executed), and
idempotent info ops were dropped by the model encoding — mirroring the
reference's error taxonomy (workload/client.clj:52-63).

By default the kernels consume this stream MACRO-COMPACTED
(`macro_compact` / `pack_macro_batch`, ISSUE 4): each run of
consecutive OPENs coalesces into the FORCE step that ends it, so the
scan length drops to #FORCEs + spill. Since OPENs only latch registers
and closure was already deferred to FORCE events, the batched latch is
verdict-preserving bit for bit (doc/checker-design.md §1b);
JGRAFT_MACRO_EVENTS=0 restores the one-event-per-step stream.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import (Iterable, List, NamedTuple, Optional, Sequence,
                    Union)

import numpy as np

from ..platform import env_int
from .ops import (NIL, History, Op, OpPair,  # noqa: F401  (NIL re-exported)
                  pair_ops, pair_ops_indexed)

# Event types.
EV_PAD = 0
EV_OPEN = 1
EV_FORCE = 2


def encode_vector_on() -> bool:
    """Whether encoding takes the vectorized columnar path (ISSUE 15
    tentpole (a)): `encode_history` routes through the per-model
    columnar twins + `_encode_history_columnar`, and the
    `IncrementalEncoder` settles suffixes columnar-ly.
    ``JGRAFT_ENCODE_VECTOR=0`` forces the per-pair Python loop — the
    differential ORACLE arm (byte-identical output, pinned by
    tests/test_fast_encode.py). Parsed defensively via `env_int`:
    garbage warns and keeps the default (on)."""
    return env_int("JGRAFT_ENCODE_VECTOR", 1, minimum=0) != 0

#: Cap on opens carried by one macro-event row. Bounds the row width
#: (3 + 4·P int32 lanes) independently of the concurrency window — a
#: timeout-polluted sort-kernel history can hold ~100 slots open at
#: once, and an uncapped row would grow past 400 lanes for a run the
#: spill rule handles in ⌈run/16⌉ latch-only rows instead. Dense-kernel
#: runs (window ≤ 12) never spill at this cap.
MACRO_MAX_OPENS = 16


@dataclass
class EncodedHistory:
    """A packed history ready for the checker kernels.

    events:   [E, 5] int32 rows (etype, slot, f, a, b)
    op_index: [E]    int32 original history index of the op behind each
                     event (-1 for padding) — for counterexample reporting.
    n_slots:  width of the concurrency window actually used.
    n_ops:    number of encoded (non-dropped) ops.
    proc:     [E]    int32 dense process id of the op behind each event,
                     or None (hand-built encodings). Kernels never read
                     it — it exists for the weaker-consistency rung
                     relaxation (checker/consistency.py), which defers
                     FORCE events along per-process program order.
    """

    events: np.ndarray
    # counterexample attribution only; never read by a verdict path,
    # and derivable from the encode for identical event rows anyway
    op_index: np.ndarray  # lint: allow(fp-irrelevant)
    n_slots: int
    # recomputable from events (count of EV_OPEN rows): two histories
    # with identical hashed event bytes cannot differ in n_ops
    n_ops: int  # lint: allow(fp-irrelevant)
    proc: Optional[np.ndarray] = None

    @property
    def n_events(self) -> int:  # lint: allow(fp-irrelevant) derived: events.shape[0], and events is hashed
        return int(self.events.shape[0])


def encode_history(
    history: Union[History, Sequence[Op]],
    model,
    prune: bool = True,
) -> EncodedHistory:
    """Compile a history into the event-stream representation.

    The model provides per-pair encoding (opcode, args, forced?) via
    ``model.encode_pair``; this function owns slot assignment and event
    ordering. Real-time order is the order of ops in the history.
    `prune` enables the dead-crashed-op pre-pass (verdict-preserving;
    see `_prune_dead_crashed` — differential tests pin pruned vs
    unpruned encodings against the CPU oracle).

    Models exposing `encode_pairs_columnar` take the columnar fast path
    (`_encode_history_columnar`) — byte-identical output, ~7× less
    host time per op (the suite's end-to-end hist/s includes encode, so
    this is perf surface, not plumbing; round-4 work on VERDICT r3 #3).
    ``JGRAFT_ENCODE_VECTOR=0`` (`encode_vector_on`) pins the per-pair
    loop below instead — the differential oracle arm.
    """

    ops = list(history)
    if getattr(model, "txn_graph", False):
        # a transaction model owns its unit's event stream (micro-op
        # rows beside OPEN and FORCE; models/listappend_txn.py)
        return model.encode_ops(ops)
    pairs = pair_ops_indexed(ops)
    cols = (model.encode_pairs_columnar(pairs)
            if encode_vector_on() else None)
    if cols is not None:
        return _encode_history_columnar(ops, model, cols, prune)

    # Pair + encode in one pass over indexed pairs (no identity maps —
    # this is the batch-encode hot path; round-3 profile: ~85% of the
    # suite wall was host encode before this was flattened).
    opens: dict = {}  # invoke position -> (pair, encoded)
    forces: dict = {}  # completion position -> invoke position
    for ip, cp, inv, comp in pairs:
        pair = OpPair(inv, comp)
        enc = model.encode_pair(pair)
        if enc is None:
            continue
        opens[ip] = (pair, enc)
        if enc.forced:
            # A forced op must HAVE a completion (forced = "completed
            # ok, must linearize by then"); a model claiming forced for
            # a crashed pair is inconsistent and must fail loudly, not
            # silently drop the FORCE event (cp is -1 for crashed pairs
            # and would never be visited by the event loop).
            if cp < 0:
                raise ValueError(
                    f"model {type(model).__name__} encoded a pair with no "
                    f"completion as forced (invoke index {inv.index})")
            forces[cp] = ip
    if prune:
        _prune_dead_crashed(model, opens, forces)

    rows: List[tuple] = []
    op_idx: List[int] = []
    procs: List[int] = []
    pid_of: dict = {}
    free: List[int] = []  # min-heap of recyclable slots
    next_slot = 0
    slot_of: dict = {}  # invoke position -> slot
    for i, op in enumerate(ops):
        if i in opens:
            pair, enc = opens[i]
            if free:
                slot = heapq.heappop(free)
            else:
                slot = next_slot
                next_slot += 1
            slot_of[i] = slot
            rows.append((EV_OPEN, slot, enc.f, enc.a, enc.b))
            op_idx.append(op.index if op.index >= 0 else i)
            procs.append(pid_of.setdefault(op.process, len(pid_of)))
        elif i in forces:
            slot = slot_of[forces[i]]
            rows.append((EV_FORCE, slot, 0, 0, 0))
            op_idx.append(op.index if op.index >= 0 else i)
            procs.append(pid_of.setdefault(ops[forces[i]].process,
                                           len(pid_of)))
            heapq.heappush(free, slot)

    events = np.asarray(rows, dtype=np.int32).reshape(-1, 5)
    return EncodedHistory(
        events=events,
        op_index=np.asarray(op_idx, dtype=np.int32),
        n_slots=next_slot,
        n_ops=len(opens),
        proc=np.asarray(procs, dtype=np.int32),
    )


def _encode_history_columnar(ops, model, cols, prune: bool) -> EncodedHistory:
    """Columnar twin of the per-pair encode body: same prune fixpoint,
    same slot recycling, same event order — differential tests pin the
    output byte-identical. The per-op costs removed: OpPair + EncodedOp
    construction, per-field method calls, and the per-op observer list
    the prune used to build (now four numpy columns)."""
    fs, as_, bs, forced, ips, cps = cols
    n = len(fs)
    forced_a = np.asarray(forced, dtype=bool)
    cps_a = np.asarray(cps, dtype=np.int64) if n else \
        np.empty(0, dtype=np.int64)
    # Same contract as the per-pair path: forced ⇒ has a completion
    # (one vectorized check instead of a per-op loop).
    bad = forced_a & (cps_a < 0)
    if bad.any():
        k = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"model {type(model).__name__} encoded a pair with no "
            f"completion as forced (invoke index {ops[ips[k]].index})")
    if prune and not forced_a.all():
        keep = _prune_dead_crashed_columnar(model, fs, as_, bs, forced,
                                            ips, cps)
        if keep is not None and not keep.all():
            fs = np.asarray(fs, dtype=np.int64)[keep]
            as_ = np.asarray(as_, dtype=np.int64)[keep]
            bs = np.asarray(bs, dtype=np.int64)[keep]
            forced = forced_a[keep]
            ips = np.asarray(ips, dtype=np.int64)[keep]
            cps = cps_a[keep]
            n = len(fs)

    # Event stream = OPENs at invoke positions merged with FORCEs at the
    # completion positions of forced ops, ascending by history position
    # (positions are unique: one op per history row).
    forced_a = np.asarray(forced, dtype=bool)
    cps_a = np.asarray(cps, dtype=np.int64)
    force_ks = np.flatnonzero(forced_a)
    n_ev = n + len(force_ks)
    ev_pos = np.empty(n_ev, dtype=np.int64)
    ev_pos[:n] = ips
    ev_pos[n:] = cps_a[force_ks]
    ev_k = np.empty(n_ev, dtype=np.int64)
    ev_k[:n] = np.arange(n)
    ev_k[n:] = force_ks
    order = np.argsort(ev_pos, kind="stable")
    is_open = order < n
    which = ev_k[order]

    # Slot assignment must walk events in order (recycling is
    # history-order-dependent); lean int loop, arrays filled after.
    slot_of = [0] * n
    slots = [0] * n_ev
    free: List[int] = []
    next_slot = 0
    for j, (k, op_ev) in enumerate(zip(which.tolist(), is_open.tolist())):
        if op_ev:
            if free:
                s = heapq.heappop(free)
            else:
                s = next_slot
                next_slot += 1
            slot_of[k] = s
            slots[j] = s
        else:
            s = slot_of[k]
            slots[j] = s
            heapq.heappush(free, s)

    events = np.zeros((n_ev, 5), dtype=np.int32)
    events[:, 0] = np.where(is_open, EV_OPEN, EV_FORCE)
    events[:, 1] = slots
    fab = np.zeros((n, 3), dtype=np.int32)
    fab[:, 0] = fs
    fab[:, 1] = as_
    fab[:, 2] = bs
    events[is_open, 2:5] = fab[which[is_open]]

    # op_index: the op's history `index` field, or its position when unset.
    pos_l = ev_pos[order].tolist()
    op_idx = np.fromiter(
        ((ops[p].index if ops[p].index >= 0 else p) for p in pos_l),
        dtype=np.int32, count=n_ev)
    # Per-event dense process ids (a FORCE's completion op shares its
    # invoke's process, so indexing by history position is uniform).
    pid_of: dict = {}
    proc = np.fromiter(
        (pid_of.setdefault(ops[p].process, len(pid_of)) for p in pos_l),
        dtype=np.int32, count=n_ev)
    return EncodedHistory(events=events, op_index=op_idx,
                          n_slots=next_slot, n_ops=n, proc=proc)


def _prune_dead_crashed_columnar(model, fs, as_, bs, forced, ips, cps):
    """Vectorized twin of `_prune_dead_crashed` (same fixpoint, same
    verdict-preservation argument — see that docstring). Returns a keep
    mask over the kept-op columns, or None when the model's hooks
    disable pruning. Monotonicity makes the fixpoint order-independent:
    dropping an op only removes observers, which can only enable more
    drops, so iterating to stability reaches the same unique result as
    the per-op dict walk."""
    tabs = model.prune_observe_enable(fs, as_, bs)
    if tabs is None:
        return None
    enable_val, enable_has, observe_val, observe_has = tabs
    n = len(fs)
    forced_a = np.asarray(forced, dtype=bool)
    ip_a = np.asarray(ips, dtype=np.int64)
    # Force position per op; unforced ops never retire (+inf sentinel).
    fpos = np.full(n, np.iinfo(np.int64).max, dtype=np.int64)
    fpos[forced_a] = np.asarray(cps, dtype=np.int64)[forced_a]
    keep = np.ones(n, dtype=bool)
    candidates = np.flatnonzero(~forced_a)
    changed = True
    while changed:
        changed = False
        for c in candidates:
            if not keep[c]:
                continue
            if not enable_has[c]:
                # Empty enable set: the op provably never changes state,
                # and optional no-ops cannot constrain anything — drop.
                keep[c] = False
                changed = True
                continue
            observers = (keep & observe_has & (fpos > ip_a[c])
                         & (observe_val == enable_val[c]))
            observers[c] = False
            if not observers.any():
                keep[c] = False
                changed = True
    return keep


def _prune_dead_crashed(model, opens: dict, forces: dict) -> None:
    """Drop crashed (optional) ops that provably cannot change the
    verdict, BEFORE slot assignment — each drop frees a never-retiring
    slot, and kernel cost is exponential in the window (SURVEY §7.4.3;
    reference doc/intro.md:35-41 names crashed ops as the checker-
    pressure problem).

    Soundness: let c be an optional op and V = model.enable_values(c)
    the only state values linearizing c can newly expose. If no op that
    could linearize after c (= any op not FORCEd before c's invocation)
    observes any v ∈ V, then (⇐) a witness without c is a witness for
    both op sets, and (⇒) removing c from a witness keeps it legal: the
    op right after c cannot be one whose legality needs c's value (none
    observes it), so it is unconditionally legal (e.g. a register
    write) and the state trajectory re-converges — verdicts are equal.
    Iterated to fixpoint: each step preserves the verdict of the
    surviving set, so the composition does too. Models opt in via the
    enable/observe hooks; any None disables the pass (conservative)."""
    if all(enc.forced for _, enc in opens.values()):
        return  # no crashed candidates — skip building the observer list
    force_pos = {ip: cp for cp, ip in forces.items()}
    observers = []  # (invoke pos, force pos or None, frozenset(values))
    for ip, (pair, enc) in opens.items():
        ov = model.observe_values(enc)
        if ov is None:
            return
        observers.append((ip, force_pos.get(ip), frozenset(ov)))
    changed = True
    while changed:
        changed = False
        for ip, (pair, enc) in list(opens.items()):
            if enc.forced:
                continue
            ev = model.enable_values(enc)
            if ev is None or not set(ev):
                # No enable set known → keep; empty enable set → the op
                # exposes nothing, but optional no-ops cannot constrain
                # anything either, so drop it outright.
                if ev is not None:
                    del opens[ip]
                    observers = [o for o in observers if o[0] != ip]
                    changed = True
                continue
            observed = set()
            for oip, fpos, vals in observers:
                if oip == ip:
                    continue
                if fpos is None or fpos > ip:
                    observed |= vals
            if not (set(ev) & observed):
                del opens[ip]
                observers = [o for o in observers if o[0] != ip]
                changed = True


def pad_batch_bucketed(events: np.ndarray, tables=(), floor_b: int = 8,
                       floor_e: Optional[int] = 32, multiple_b: int = 1):
    """Pad a packed [B, E, 5] batch (and optional per-history [B, X]
    tables) to jit-cache-friendly shapes: B to the next bucket of the
    pow2+midpoint series ≥ floor_b (see `_bucket_pow2`; shapes like 12,
    48, 96 occur) then up to a multiple of multiple_b for mesh sharding;
    E likewise from floor_e (None keeps E exact). Pad rows are EV_PAD
    no-ops. Returns (events, tables_list, original_B) — the single home of
    the padding convention (checker and mesh both route through it)."""
    B, E = events.shape[0], events.shape[1]
    B2 = _bucket_pow2(B, floor_b)
    B2 = ((B2 + multiple_b - 1) // multiple_b) * multiple_b
    E2 = E if floor_e is None else _bucket_pow2(E, floor_e)
    if (B2, E2) != (B, E):
        padded = np.zeros((B2, E2) + events.shape[2:], dtype=events.dtype)
        padded[:B, :E] = events
        events = padded
    out_tables = []
    for t in tables:
        if t.shape[0] != B2:
            tp = np.zeros((B2,) + t.shape[1:], dtype=t.dtype)
            tp[:B] = t
            t = tp
        out_tables.append(t)
    return events, out_tables, B


def bucket_rows(n: int, floor: int = 8) -> int:
    """Public face of the pow2+midpoint bucket series for ROW counts —
    the chunked-scan scheduler (checker/schedule.py) recompacts a
    shrinking active set through these exact buckets so every
    recompaction hits a jit-cache entry the initial padding already
    compiled, instead of triggering a fresh XLA compile per eviction."""
    return _bucket_pow2(n, floor)


def _bucket_pow2(n: int, floor: int) -> int:
    """Next bucket ≥ n from the series floor·{1, 1.5, 2, 3, 4, 6, 8…}
    (powers of two plus their midpoints): padding waste is capped at
    ~33% instead of pow2's 2×, while the jit-cache shape count only
    doubles. The 1000-history north-star batch measured 1.34× padded
    rows under pure pow2 bucketing — real kernel time, not headroom."""
    b = floor
    while b < n:
        if b + b // 2 >= n:
            return b + b // 2
        b *= 2
    return b


def pack_batch(
    encoded: Iterable[EncodedHistory],
    n_events: Optional[int] = None,
) -> dict:
    """Pad a batch of encoded histories to a common event length.

    Returns numpy arrays: events [B, E, 5], op_index [B, E],
    n_events [B], n_slots [B]. Padding rows are EV_PAD (no-ops in the
    kernel scan), so histories of different lengths batch cleanly.
    """

    encs = list(encoded)
    if not encs:
        raise ValueError("empty batch")
    E = n_events or max(e.n_events for e in encs)
    if any(e.n_events > E for e in encs):
        raise ValueError("n_events smaller than longest history")
    B = len(encs)
    events = np.zeros((B, E, 5), dtype=np.int32)
    op_index = np.full((B, E), -1, dtype=np.int32)
    ne = np.zeros((B,), dtype=np.int32)
    ns = np.zeros((B,), dtype=np.int32)
    for i, e in enumerate(encs):
        events[i, : e.n_events] = e.events
        op_index[i, : e.n_events] = e.op_index
        ne[i] = e.n_events
        ns[i] = e.n_slots
    return {
        "events": events,
        "op_index": op_index,
        "n_events": ne,
        "n_slots": ns,
    }


def macro_events_on() -> bool:
    """Whether kernels consume the macro-compacted event stream (ISSUE-4
    tentpole; see `macro_compact`). ``JGRAFT_MACRO_EVENTS=0`` restores
    the legacy one-event-per-step stream — the differential/ablation
    path the macro≡legacy tests pin verdict-identical. Parsed
    defensively (`platform.env_int`): garbage warns and keeps the
    default (on)."""
    return env_int("JGRAFT_MACRO_EVENTS", 1, minimum=0) != 0


def bucket_opens(n: int, cap: int = MACRO_MAX_OPENS) -> int:
    """Macro payload width P for a group whose longest open run is `n`:
    the pow2+midpoint series (1, 2, 3, 4, 6, 8, 12, 16 — same shape
    discipline as rows/events) capped at MACRO_MAX_OPENS, so one
    compiled kernel serves a bucket of run lengths instead of a fresh
    XLA compile per batch. Runs longer than the cap spill into
    latch-only macro rows (`macro_compact`)."""
    return min(_bucket_pow2(max(int(n), 1), 1), cap)


class _MacroGroups(NamedTuple):
    """`_macro_groups`' answer: the rows of a group concatenated, and
    the macro group of every open and force in them."""

    cat: np.ndarray        # the concatenated events [N, 5] int32
    open_idx: np.ndarray   # positions of the OPENs in `cat`
    force_idx: np.ndarray  # positions of the FORCEs
    ogrp: np.ndarray       # macro group of each open
    orid: np.ndarray       # row id of each open
    frid: np.ndarray       # row id of each force
    counts: np.ndarray     # opens in each group
    gbase: np.ndarray      # [B + 1] each row's first group; last: all


def _macro_groups(rows: Sequence[np.ndarray]) -> _MacroGroups:
    """The one metadata pass behind the macro row math, over the rows of
    a GROUP at once: their packed [E_i, 5] event streams are concatenated
    and every per-row quantity becomes one pass over that array with a
    row id beside each event.

    A row with nF forces has nF + 1 macro groups (group i's opens
    precede its force i; the last is the trailing never-forced run) and
    the groups of a batch are numbered row after row, so an open's
    group is the forces before it in `cat` plus its row id — and force
    k's group is k plus ITS row id.

    `max_open_run`, `macro_row_count`, `macro_compact` and the batch
    packers all derive from these — one definition, so the counting
    passes can never drift from the compaction itself."""
    B = len(rows)
    cat = (np.concatenate(rows) if B else np.empty((0, 5))).astype(
        np.int32, copy=False).reshape(-1, 5)
    lens = np.fromiter((len(r) for r in rows), dtype=np.int32, count=B)
    rid = np.repeat(np.arange(B, dtype=np.int32), lens)
    et = np.ascontiguousarray(cat[:, 0])
    is_force = et == EV_FORCE
    open_idx = np.flatnonzero(et == EV_OPEN)
    force_idx = np.flatnonzero(is_force)
    # forces at or before each event; an open is no force, so for it
    # these are the forces strictly before it
    fcum = np.zeros(len(cat) + 1, dtype=np.int32)
    np.cumsum(is_force, dtype=np.int32, out=fcum[1:])
    orid, frid = rid[open_idx], rid[force_idx]
    ogrp = fcum[1:][open_idx] + orid
    starts = np.zeros(B + 1, dtype=np.int32)
    np.cumsum(lens, out=starts[1:])
    gbase = fcum[starts] + np.arange(B + 1, dtype=np.int32)
    counts = np.bincount(ogrp, minlength=int(gbase[-1])).astype(
        np.int32, copy=False)
    return _MacroGroups(cat, open_idx, force_idx, ogrp, orid, frid, counts,
                        gbase)


def _macro_rows(counts: np.ndarray, gbase: np.ndarray, macro_p: int):
    """Macro rows of every group at payload width P: ⌈opens/P⌉ latch
    rows, at least one for a FORCE (a force with no fresh open still
    needs its row), none forced for a row's trailing group. Returns
    (mbase: each group's first macro row in the batch's running count,
    with the total last; ne: macro rows a batch row)."""
    n_rows = -(-counts // np.int32(macro_p))
    trailing = gbase[1:] - 1
    tail = n_rows[trailing]
    np.maximum(n_rows, 1, out=n_rows)
    n_rows[trailing] = tail
    mbase = np.zeros(len(n_rows) + 1, dtype=np.int32)
    np.cumsum(n_rows, out=mbase[1:])
    at = mbase[gbase]
    return mbase, at[1:] - at[:-1]


def _macro_fill(rows: Sequence[np.ndarray], macro_p: Optional[int],
                n_events: Optional[int] = None, n_rows: Optional[int] = None,
                cap: int = MACRO_MAX_OPENS):
    """Compact the rows of a group WHOLE and write their tensor once:
    (events [B, E, 3 + 4·P] int32, ne [B] macro rows a row, P).

    `macro_p` None takes `bucket_opens` of the group's longest open run
    under `cap`. `n_events` pins E (default: the longest macro stream,
    at least 1); `n_rows` ≥ len(rows) appends EV_PAD rows (0 macro rows
    each). The layout of a row is `macro_compact`'s — that IS this pass
    at one row."""
    g = _macro_groups(rows)
    P = int(macro_p) if macro_p is not None else \
        bucket_opens(int(g.counts.max(initial=0)), cap)
    mbase, ne = _macro_rows(g.counts, g.gbase, P)
    B = len(rows) if n_rows is None else int(n_rows)
    if B > len(rows):
        ne = np.concatenate([ne, np.zeros(B - len(rows), np.int32)])
    longest = int(ne.max(initial=0))
    E = n_events or max(longest, 1)
    if longest > E:
        raise ValueError("n_events smaller than longest macro stream")
    L = 3 + 4 * P
    if B * E * L >= 2 ** 31:
        raise ValueError("macro tensor past int32 indexing")
    out = np.zeros(B * E * L, dtype=np.int32)
    # where a batch row's first macro row lands in `out`, less where it
    # sits in the batch's running count
    shift = np.arange(len(rows), dtype=np.int32) * np.int32(E) \
        - mbase[g.gbase[:-1]]
    if len(g.open_idx):
        # rank of each open within its group (opens come group by group)
        first = np.cumsum(g.counts, dtype=np.int32) - g.counts
        j = np.arange(len(g.open_idx), dtype=np.int32) - first[g.ogrp]
        mrow = mbase[g.ogrp] + shift[g.orid] + j // np.int32(P)
        base = mrow * np.int32(L)
        out[base] = EV_OPEN  # latch-only (spill / trailing) unless forced
        out.reshape(-1, L)[:, 2] = np.bincount(mrow, minlength=B * E)
        base += 3 + 4 * (j % np.int32(P))
        opens = g.cat.take(g.open_idx, axis=0)
        for k in range(4):
            out[base + k] = opens[:, 1 + k]
    if len(g.force_idx):
        # a group's FORCE rides its last row, the one before the next
        # group's first; force k's group is k + its row id
        nxt = np.arange(1, len(g.force_idx) + 1, dtype=np.int32) + g.frid
        base = (mbase[nxt] - 1 + shift[g.frid]) * np.int32(L)
        out[base] = EV_FORCE
        out[base + 1] = g.cat[:, 1].take(g.force_idx)
    return out.reshape(B, E, L), ne, P


def macro_row_count(events: np.ndarray, macro_p: int) -> int:
    """Macro rows `macro_compact(events, macro_p)` would produce,
    WITHOUT building them."""
    g = _macro_groups([np.reshape(events, (-1, 5))])
    return int(_macro_rows(g.counts, g.gbase, macro_p)[1][0])


def max_open_run(events: np.ndarray) -> int:
    """Longest run of consecutive OPEN events (the quantity P buckets):
    opens are grouped by the number of FORCEs preceding them — the
    trailing group (crashed never-forced opens) counts too."""
    return int(_macro_groups([np.reshape(events, (-1, 5))]).counts.max())


def macro_compact(events: np.ndarray, macro_p: int) -> np.ndarray:
    """Compact a packed [E, 5] event stream into macro-event rows
    [E_mac, 3 + 4·P] int32 — the ISSUE-4 tentpole encoding. The
    compaction is defined a GROUP at a time (`_macro_fill`, which
    `pack_macro_batch` writes its tensor with); this is its one-row
    case.

    Each run of consecutive OPENs coalesces into the FORCE step that
    ends it: row = [mtype, force_slot, n_opens, (slot, f, a, b)·P].
    mtype is EV_FORCE for a macro ending in a FORCE, EV_OPEN for a
    latch-only macro (spill of a run longer than P, or the trailing
    run of crashed never-forced opens), EV_PAD for batch padding. The
    kernels latch all n_opens payloads at once (slots within a run are
    distinct — a slot is only recycled by a FORCE) and then run the
    single existing closure+FORCE, so the scan length drops to
    #FORCEs + spill rows while reaching the identical pre-FORCE
    register state as the one-event-per-step stream (closure is a
    reachability fixpoint over those registers — the soundness argument
    in doc/checker-design.md; macro≡legacy pinned bitwise by
    tests/test_macro_events.py)."""
    out, ne, _ = _macro_fill([np.reshape(events, (-1, 5))], macro_p)
    return out[0, : int(ne[0])]


def pack_macro_batch(
    encoded: Iterable[EncodedHistory],
    n_events: Optional[int] = None,
    cap: int = MACRO_MAX_OPENS,
    window: Optional[int] = None,
) -> dict:
    """Macro-stream twin of `pack_batch`: compact every history of a
    batch at one shared payload width P (`bucket_opens` of the batch's
    longest open run — or, given the launch's `window`, of that: a run
    of opens holds distinct slots, so it never outgrows the window, and
    P then follows from the kernel's window instead of from the rows a
    batch happens to hold, which keeps it out of the launch-shape set,
    checker/schedule.py) and pad to a common macro-row count. The
    compaction is defined a group at a time: the rows' events are
    concatenated once and compacted in one pass with a row id beside
    each event, straight into the batch's tensor (`_macro_fill`; no
    per-row array, no loop over the rows) — `macro_compact` is its
    one-row case. Returns
    numpy arrays events [B, E_mac, 3+4·P], n_events [B] (MACRO row
    counts — the scheduler's exhaustion/span math runs on these),
    n_slots [B], plus the scalar "macro_p" the kernel builders key on
    and "legacy_events" (the batch's max one-event-per-step length):
    routing gates calibrated on legacy event counts — the host/TPU
    cell gate, the LONG-group exact-padding policy — must keep reading
    legacy lengths, or the ~2× compaction silently halves their
    thresholds. Padding rows are EV_PAD no-ops, exactly like
    `pack_batch`."""
    encs = list(encoded)
    if not encs:
        raise ValueError("empty batch")
    events, ne, P = _macro_fill(
        [e.events for e in encs],
        None if window is None else bucket_opens(window, cap),
        n_events=n_events, cap=cap)
    return {
        "events": events,
        "n_events": ne,
        "n_slots": np.fromiter((e.n_slots for e in encs), dtype=np.int32,
                               count=len(encs)),
        "macro_p": P,
        "legacy_events": max(e.n_events for e in encs),
    }


def _shard_slice(n_encs: int, process_index: int, process_count: int,
                 n_rows: Optional[int]) -> tuple:
    """(lo, hi, n_rows) for a per-host pack: the shard's row range over
    the GLOBAL row count (≥ the batch size when the caller pre-pads for
    a global mesh; the extra rows are EV_PAD no-op histories assigned
    to the trailing shards)."""
    from ..parallel.distributed import shard_bounds

    n_rows = n_encs if n_rows is None else int(n_rows)
    if n_rows < n_encs:
        raise ValueError(f"n_rows {n_rows} smaller than batch {n_encs}")
    lo, hi = shard_bounds(n_rows, process_count, process_index)
    return lo, hi, n_rows


def pack_batch_shard(
    encoded: Sequence[EncodedHistory],
    process_index: int,
    process_count: int,
    n_rows: Optional[int] = None,
    n_events: Optional[int] = None,
) -> dict:
    """Per-host twin of `pack_batch` (ISSUE 7): pad/fill ONLY the row
    shard process `process_index` of `process_count` owns, at the
    batch-GLOBAL event length — so the shard tensors of all processes,
    concatenated in process order, equal `pack_batch` of the whole
    batch row for row (shard-local pack ≡ global pack then shard;
    doc/checker-design.md §10, pinned by tests/test_distributed.py).
    Each host therefore pays only its shard's share of the fill work,
    and the tensor is born on its shard. `n_rows` (≥ batch size) adds
    global EV_PAD padding rows for mesh-divisible launches. Extra keys:
    ``shard`` = (lo, hi) and ``n_rows_global``."""
    encs = list(encoded)
    if not encs:
        raise ValueError("empty batch")
    E = n_events or max(e.n_events for e in encs)
    if any(e.n_events > E for e in encs):
        raise ValueError("n_events smaller than longest history")
    lo, hi, n_rows = _shard_slice(len(encs), process_index, process_count,
                                  n_rows)
    B_local = hi - lo
    events = np.zeros((B_local, E, 5), dtype=np.int32)
    op_index = np.full((B_local, E), -1, dtype=np.int32)
    ne = np.zeros((B_local,), dtype=np.int32)
    ns = np.zeros((B_local,), dtype=np.int32)
    for j, e in enumerate(encs[lo:min(hi, len(encs))]):
        events[j, : e.n_events] = e.events
        op_index[j, : e.n_events] = e.op_index
        ne[j] = e.n_events
        ns[j] = e.n_slots
    return {
        "events": events,
        "op_index": op_index,
        "n_events": ne,
        "n_slots": ns,
        "shard": (lo, hi),
        "n_rows_global": n_rows,
    }


def pack_macro_batch_shard(
    encoded: Sequence[EncodedHistory],
    process_index: int,
    process_count: int,
    n_rows: Optional[int] = None,
    n_events: Optional[int] = None,
    cap: int = MACRO_MAX_OPENS,
) -> dict:
    """Per-host twin of `pack_macro_batch` (ISSUE 7 tentpole (b)). The
    batch-GLOBAL shapes — payload width P (longest open run anywhere in
    the batch) and macro row count E — are computed from every
    history's metadata via the cheap counting pass (`_macro_groups` /
    `_macro_rows`, no row assembly), then ONLY this process's row shard
    is actually compacted and filled (`_macro_fill`, the one pass). The
    concatenation of every process's output equals `pack_macro_batch`
    of the whole batch, row for row, so the per-host tensors feed the
    same compiled kernels at the same shapes (soundness:
    doc/checker-design.md §10; identity pinned by
    tests/test_distributed.py). This parallelizes the dominant
    host-side pack cost — compaction + array fill — across host
    CPUs."""
    encs = list(encoded)
    if not encs:
        raise ValueError("empty batch")
    # ONE metadata pass over the whole batch feeds both the batch-global
    # payload width P (the longest open run anywhere) and, at that P,
    # every history's macro row count — the batch-global half of the
    # pack cost every host pays, so it must not scan the event arrays
    # twice.
    g = _macro_groups([e.events for e in encs])
    P = bucket_opens(int(g.counts.max()), cap)
    longest = int(_macro_rows(g.counts, g.gbase, P)[1].max())
    E = n_events or max(longest, 1)
    if longest > E:
        raise ValueError("n_events smaller than longest macro stream")
    lo, hi, n_rows = _shard_slice(len(encs), process_index, process_count,
                                  n_rows)
    mine = encs[lo:min(hi, len(encs))]
    events, ne, _ = _macro_fill([e.events for e in mine], P, n_events=E,
                                n_rows=hi - lo)
    ns = np.zeros((hi - lo,), dtype=np.int32)
    ns[:len(mine)] = [e.n_slots for e in mine]
    return {
        "events": events,
        "n_events": ne,
        "n_slots": ns,
        "macro_p": P,
        "legacy_events": max(e.n_events for e in encs),
        "shard": (lo, hi),
        "n_rows_global": n_rows,
    }


# ----------------------------------------------------- streaming encoder


class IncrementalEncoder:
    """Append-only twin of ``encode_history(..., prune=False)`` for
    streaming sessions (ISSUE 12): history rows arrive in real-time
    order across segment boundaries, and each ``feed`` emits the newly
    SETTLED suffix of the event stream — exactly the rows
    `encode_history` produces for the complete history, in the same
    order, so a kernel carry advanced on the suffixes reaches the same
    state as one uninterrupted scan (doc/checker-design.md §14).

    Settlement: an op's OPEN row content depends on its completion (an
    ok read encodes its observed value, a ``fail`` drops the op
    entirely), so the event at history position p can only be emitted
    once every invocation at position ≤ p has its completion RECORDED
    somewhere in the accumulated history. Jepsen's runner records an
    ``info`` row for crashed workers, so mid-run every invoke
    eventually settles; invokes still outstanding at ``feed(...,
    final=True)`` become crashed pairs — the same rule `pair_ops`
    applies to a finished history. Settled events are FINAL: appending
    rows appends events, never rewrites them (prefix stability — the
    differentials in tests/test_stream.py pin the emitted stream
    byte-identical to the one-shot encode at every cut).

    Pruning is off by design: `_prune_dead_crashed` keys on global
    observer structure that later appends can change. Pruning is
    verdict-preserving in both directions (its docstring), so streamed
    verdicts still match the pruned one-shot path.

    Memory: only the UNSETTLED tail of rows is retained (bounded by the
    live concurrency window in any real history); settled rows are
    dropped as their events are emitted.
    """

    def __init__(self, model):
        self.model = model
        #: columnar settle (ISSUE 15 tentpole (a)): the settled-suffix
        #: emit batch-encodes each settle's invokes through the model's
        #: columnar twin instead of per-op `encode_pair` calls. Fixed at
        #: construction (JGRAFT_ENCODE_VECTOR) and flipped off
        #: permanently if the model has no columnar hook — the two
        #: paths store different `_enc_of` payloads and must never mix
        #: mid-session. Emitted streams are byte-identical either way
        #: (tests/test_fast_encode.py pins random cuts).
        self._vector = encode_vector_on()
        self.consumed = 0   # history rows ingested
        self.cut = 0        # rows settled (events emitted)
        self.n_ops = 0      # encoded (kept) ops
        self.n_slots = 0    # window high-water (= reference next_slot)
        self.n_events = 0   # events emitted so far
        self._tail: list = []      # Op rows at positions [cut, consumed)
        self._pending: dict = {}   # process -> invoke position
        self._comp: dict = {}      # invoke position -> completion Op
        self._inv_of: dict = {}    # completion position -> invoke position
        self._enc_of: dict = {}    # invoke position -> EncodedOp | None
        self._free: list = []      # recyclable slots (min-heap)
        self._slot_of: dict = {}   # invoke position -> slot
        self._pid_of: dict = {}    # raw process -> dense id

    @property
    def unsettled(self) -> int:
        """Rows ingested but not yet settled (the resident tail)."""
        return self.consumed - self.cut

    def validate(self, ops) -> list:
        """Parse rows and check pairing against a scratch copy of the
        pending set WITHOUT mutating the encoder — the same errors
        `pair_ops_indexed` raises (double invoke, stray completion),
        raised atomically so a rejected segment leaves the session
        re-appendable. Returns the parsed Op rows."""
        ops = [op if isinstance(op, Op) else Op.from_dict(op)
               for op in ops]
        scratch = set(self._pending)
        for op in ops:
            t = op.type
            if t == "invoke":
                if op.process in scratch:
                    raise ValueError(
                        f"process {op.process} invoked twice without "
                        f"completing")
                scratch.add(op.process)
            elif op.is_completion():
                if op.process not in scratch:
                    raise ValueError(
                        f"completion without invocation: process "
                        f"{op.process}")
                scratch.discard(op.process)
            else:
                raise ValueError(f"unknown op type: {t!r}")
        return ops

    def feed(self, ops, final: bool = False):
        """Ingest history rows and emit the newly settled events.

        Returns (events [n,5] int32, op_index [n] int32, proc [n]
        int32) — empty arrays when nothing new settled. Raises
        ValueError on malformed rows (see `validate`) without mutating
        the encoder. ``final=True`` settles everything: outstanding
        invokes become crashed pairs (`pair_ops`' end-of-history
        rule)."""
        ops = self.validate(ops)
        for op in ops:
            pos = self.consumed
            self.consumed += 1
            self._tail.append(op)
            if op.type == "invoke":
                self._pending[op.process] = pos
            else:
                ipos = self._pending.pop(op.process)
                self._comp[ipos] = op
                self._inv_of[pos] = ipos
        if self._vector:
            return self._settle_vector(final)
        return self._settle(final)

    def _settle_vector(self, final: bool):
        """Columnar twin of `_settle` (ISSUE 15 tentpole (a)): the
        settled prefix's invoke rows batch-encode through the model's
        `encode_pairs_columnar` — one tight columnar pass instead of a
        per-op `encode_pair` call with OpPair/EncodedOp construction —
        then the slot/heap emission loop runs exactly like the scalar
        path, so the emitted stream is byte-identical (differential-
        pinned at random cuts). `_enc_of` stores the bare forced flag
        here (True/False, None for dropped ops) — the only field the
        completion branch reads — where the scalar path stores the
        EncodedOp; the per-session `_vector` latch keeps the two
        representations from ever mixing."""
        advance = 0
        for op in self._tail:
            pos = self.cut + advance
            if op.type == "invoke" and pos not in self._comp \
                    and not final:
                break  # completion not recorded yet: unsettled
            advance += 1
        empty = (np.empty((0, 5), dtype=np.int32),
                 np.empty(0, dtype=np.int32),
                 np.empty(0, dtype=np.int32))
        if advance == 0:
            return empty
        pairs = []
        # completion stream position per invoke position (the
        # encode_pairs_columnar contract wants the COMPLETION's
        # position in the pair tuple, like pair_ops_indexed emits —
        # _inv_of maps completion pos -> invoke pos, so invert it;
        # every recorded completion has an entry until the completion
        # row itself settles, which is after this pass)
        cpos_of = {ip: cp for cp, ip in self._inv_of.items()}
        for j in range(advance):
            op = self._tail[j]
            if op.type == "invoke":
                pos = self.cut + j
                comp = self._comp.get(pos)
                pairs.append((pos,
                              -1 if comp is None else cpos_of[pos],
                              op, comp))
        cols = self.model.encode_pairs_columnar(pairs)
        if cols is None:
            # model without a columnar twin: latch the scalar path for
            # the session's lifetime (nothing was stored vector-style
            # yet — the scalar settle re-walks the untouched tail)
            self._vector = False
            return self._settle(final)
        fs, as_, bs, forced, ips, _cps = cols
        kept = {ip: (int(f), int(a), int(b), bool(fo))
                for ip, f, a, b, fo in zip(ips, fs, as_, bs, forced)}

        rows: list = []
        op_idx: list = []
        procs: list = []
        for j in range(advance):
            op = self._tail[j]
            pos = self.cut + j
            if op.type == "invoke":
                ent = kept.get(pos)
                self._enc_of[pos] = ent if ent is None else ent[3]
                if ent is not None:
                    f, a, b, fo = ent
                    if fo and pos not in self._comp:
                        raise ValueError(
                            f"model {type(self.model).__name__} encoded "
                            f"a pair with no completion as forced "
                            f"(invoke index {op.index})")
                    if self._free:
                        slot = heapq.heappop(self._free)
                    else:
                        slot = self.n_slots
                        self.n_slots += 1
                    self._slot_of[pos] = slot
                    rows.append((EV_OPEN, slot, f, a, b))
                    op_idx.append(op.index if op.index >= 0 else pos)
                    procs.append(self._pid_of.setdefault(
                        op.process, len(self._pid_of)))
                    self.n_ops += 1
            else:
                ipos = self._inv_of.pop(pos)
                self._comp.pop(ipos, None)
                encF = self._enc_of.pop(ipos, None)
                if encF is True:
                    slot = self._slot_of.pop(ipos)
                    rows.append((EV_FORCE, slot, 0, 0, 0))
                    op_idx.append(op.index if op.index >= 0 else pos)
                    procs.append(self._pid_of.setdefault(
                        op.process, len(self._pid_of)))
                    heapq.heappush(self._free, slot)
                elif encF is False:
                    # optional (info) op: the slot never recycles
                    self._slot_of.pop(ipos, None)
        del self._tail[:advance]
        self.cut += advance
        self.n_events += len(rows)
        events = np.asarray(rows, dtype=np.int32).reshape(-1, 5)
        return (events,
                np.asarray(op_idx, dtype=np.int32),
                np.asarray(procs, dtype=np.int32))

    def _settle(self, final: bool):
        rows: list = []
        op_idx: list = []
        procs: list = []
        advanced = 0
        for op in self._tail:
            pos = self.cut + advanced
            if op.type == "invoke":
                if pos not in self._comp and not final:
                    break  # completion not recorded yet: unsettled
                comp = self._comp.get(pos)
                enc = self.model.encode_pair(OpPair(op, comp))
                self._enc_of[pos] = enc
                if enc is not None:
                    if enc.forced and comp is None:
                        raise ValueError(
                            f"model {type(self.model).__name__} encoded "
                            f"a pair with no completion as forced "
                            f"(invoke index {op.index})")
                    if self._free:
                        slot = heapq.heappop(self._free)
                    else:
                        slot = self.n_slots
                        self.n_slots += 1
                    self._slot_of[pos] = slot
                    rows.append((EV_OPEN, slot, enc.f, enc.a, enc.b))
                    op_idx.append(op.index if op.index >= 0 else pos)
                    procs.append(self._pid_of.setdefault(
                        op.process, len(self._pid_of)))
                    self.n_ops += 1
            else:
                ipos = self._inv_of.pop(pos)
                self._comp.pop(ipos, None)
                enc = self._enc_of.pop(ipos, None)
                if enc is not None and enc.forced:
                    slot = self._slot_of.pop(ipos)
                    rows.append((EV_FORCE, slot, 0, 0, 0))
                    op_idx.append(op.index if op.index >= 0 else pos)
                    procs.append(self._pid_of.setdefault(
                        op.process, len(self._pid_of)))
                    heapq.heappush(self._free, slot)
                elif enc is not None:
                    # optional (info) op: the slot never recycles — the
                    # op stays a linearization candidate forever.
                    self._slot_of.pop(ipos, None)
            advanced += 1
        del self._tail[:advanced]
        self.cut += advanced
        self.n_events += len(rows)
        events = np.asarray(rows, dtype=np.int32).reshape(-1, 5)
        return (events,
                np.asarray(op_idx, dtype=np.int32),
                np.asarray(procs, dtype=np.int32))
