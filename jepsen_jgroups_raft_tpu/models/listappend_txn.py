"""Elle's list-append workload with a TRANSACTION as the op (ISSUE 51).

An op of this workload is `{f: "txn", value: [[f, k, v], ...]}`: its
micro-ops are `["append", k, e]` and `["r", k, list]`, an invoked read
carries None and a completed one the WHOLE list of its key. Keys and
elements are any int32 and a list is as long as it is: there is no
packed state (`models/listappend.py` packs a list base-32 into one
int32, six elements of 1..31, and is the per-key linearizability face
of the single-op workload; neither bound applies here). A history of
transactions is not checked by a frontier search over a model state at
all: its verdict is a cycle search over the transactions' dependency
graph (checker/txn_graph.py), so this model has no `step`.

What it owns is the unit's ENCODING, which stays an `EncodedHistory`:
the int32 event stream that the frame, the fingerprint, the WAL, the
result cache, replay and adoption already carry. Beside a
transaction's `EV_OPEN` (its invocation; the slot as every model's: the
concurrency window) and `EV_FORCE` (it completed `ok`), the stream
holds micro-op rows, which follow their transaction's `EV_OPEN`
directly:

    EV_OPEN          slot  0    0     0
    EV_FORCE         slot  0    0     0
    EV_APPEND        mop   key  elem  0     one row an append
    EV_OBSERVE       mop   key  elem  pos   one row an OBSERVED ELEMENT
    EV_READ_EMPTY    mop   key  0     0     a read that saw ()
    EV_FAILED_APPEND 0     key  elem  0     an append of a `fail`ed txn

`mop` is the micro-op's place in its transaction. An `ok` transaction's
micro-ops are its completion's (reads filled); one whose completion is
unknown (`info`, or none) keeps its appends and loses its reads (they
constrain nothing), and is left out whole if it appends nothing; a
`fail`ed one did not happen, and leaves only `EV_FAILED_APPEND` rows,
so that a read of an element it tried to append is told apart from a
read of an element nobody wrote. Everything a verdict reads is in the
hashed bytes (`events`, `n_slots`).

Two ways to the same arrays: `encode_ops` reads `Op`s (or `OpRow`s)
one micro-op and one element at a time, the oracle; `encode_columns`
reads the rows' columns, pairs them in one pass and fills the element
rows with numpy (a 1,000-transaction history is ~1.2k appends and ~20k
observed elements). tests/test_listappend_txn.py holds them equal.
"""

from __future__ import annotations

import heapq
from itertools import chain
from typing import List, Sequence

import numpy as np

from ..history.ops import FAIL, INVOKE, OK, pair_ops_indexed
from ..history.packing import EV_FORCE, EV_OPEN, EncodedHistory
from .base import INT32_MAX, INT32_MIN, Model

EV_APPEND = 3
EV_OBSERVE = 4
EV_READ_EMPTY = 5
EV_FAILED_APPEND = 6

APPEND = "append"
READ = "r"


def _bad(what: str) -> ValueError:
    return ValueError(f"list-append-txn: {what}")


def _int32(x, what: str) -> int:
    if not isinstance(x, (int, np.integer)) or isinstance(x, bool) \
            or not INT32_MIN <= x <= INT32_MAX:
        raise _bad(f"{what} {x!r} is not an int32")
    return int(x)


def _micro_ops(value, completed: bool) -> List[tuple]:
    """A transaction's micro-ops as `(f, key, payload)`: an append's
    payload its element, a completed read's the tuple it saw, any other
    read's None."""
    if not isinstance(value, (list, tuple)):
        raise _bad(f"a transaction's value is a list of micro-ops, "
                   f"not {value!r}")
    out = []
    for m in value:
        if not isinstance(m, (list, tuple)) or len(m) != 3:
            raise _bad(f"a micro-op is [f, key, value], not {m!r}")
        f, key, v = m
        key = _int32(key, "key")
        if f == APPEND:
            out.append((APPEND, key, _int32(v, "element")))
        elif f == READ:
            if completed and not isinstance(v, (list, tuple)):
                raise _bad(f"a completed read of key {key} carries "
                           f"{v!r}, not a list")
            out.append((READ, key, tuple(v) if completed else None))
        else:
            raise _bad(f"unknown micro-op {f!r}")
    return out


class ListAppendTxn(Model):
    name = "list-append-txn"
    #: `check_encoded` routes a model that says so to the transaction
    #: graph (checker/txn_graph.py) and never to a frontier kernel
    txn_graph = True

    def init_state(self) -> int:
        return 0

    # ------------------------------------------------ the object path

    def encode_ops(self, ops: Sequence) -> EncodedHistory:
        """The oracle: `Op`s or `OpRow`s in, one Python step a micro-op
        and an observed element."""
        ops = list(ops)
        opens: dict = {}    # invoke position -> its rows after the OPEN
        failed: dict = {}   # invoke position -> EV_FAILED_APPEND rows
        forces: dict = {}   # completion position -> invoke position
        for ip, cp, inv, comp in pair_ops_indexed(ops):
            if inv.f != "txn":
                raise _bad(f"unknown f {inv.f!r}")
            ctype = comp.type if comp is not None else "info"
            done = ctype == OK
            mops = _micro_ops(comp.value if done else inv.value, done)
            if ctype == FAIL:
                failed[ip] = [(EV_FAILED_APPEND, 0, k, v, 0)
                              for f, k, v in mops if f == APPEND]
                continue
            rows = []
            for j, (f, k, v) in enumerate(mops):
                if f == APPEND:
                    rows.append((EV_APPEND, j, k, v, 0))
                elif done and not v:
                    rows.append((EV_READ_EMPTY, j, k, 0, 0))
                elif done:
                    rows.extend((EV_OBSERVE, j, k, _int32(e, "element"),
                                 pos) for pos, e in enumerate(v))
            if not done and not rows:
                continue
            opens[ip] = rows
            if done:
                forces[cp] = ip
        events: List[tuple] = []
        op_idx: List[int] = []
        procs: List[int] = []
        pid_of: dict = {}
        free: List[int] = []
        slot_of: dict = {}
        next_slot = 0
        for i, op in enumerate(ops):
            idx = op.index if op.index >= 0 else i
            if i in opens:
                slot = heapq.heappop(free) if free else next_slot
                next_slot += slot == next_slot
                slot_of[i] = slot
                rows = [(EV_OPEN, slot, 0, 0, 0)] + opens[i]
            elif i in failed:
                rows = failed[i]
            elif i in forces:
                slot = slot_of[forces[i]]
                heapq.heappush(free, slot)
                rows = [(EV_FORCE, slot, 0, 0, 0)]
            else:
                continue
            if rows:
                pid = pid_of.setdefault(op.process, len(pid_of))
                events.extend(rows)
                op_idx.extend([idx] * len(rows))
                procs.extend([pid] * len(rows))
        return EncodedHistory(
            events=np.asarray(events, dtype=np.int32).reshape(-1, 5),
            op_index=np.asarray(op_idx, dtype=np.int32),
            n_slots=next_slot, n_ops=len(opens),
            proc=np.asarray(procs, dtype=np.int32))

    # ------------------------------------------------ the column path

    def encode_columns(self, cols) -> EncodedHistory:
        """`encode_ops` of the rows whose columns these are
        (`service.request._wire_columns`, nemesis rows taken out): one
        pass that pairs the rows, one Python step a micro-op, and the
        element rows filled from their concatenation."""
        procs, types, fs, values, index = cols
        n = len(procs)
        # -- pairing, as `pair_ops_indexed` does it
        pending: dict = {}
        done_at = [-2] * n    # invoke position -> completion position
        ctype_of: dict = {}
        for i in range(n):
            t, p = types[i], procs[i]
            if t == INVOKE:
                if p in pending:
                    raise ValueError(
                        f"process {p} invoked twice without completing "
                        f"(indices {index[pending[p]]}, {index[i]})")
                pending[p] = i
                done_at[i] = -1
            elif t in (OK, FAIL, "info"):
                ip = pending.pop(p, None)
                if ip is None:
                    raise ValueError(
                        f"completion without invocation: process {p} "
                        f"index {index[i]}")
                done_at[ip] = i
                ctype_of[ip] = t
            else:
                raise ValueError(f"unknown op type: {t!r}")
        # -- a block of rows an invocation, one row an `ok` completion
        # (position in the history, rows, process, index): a block is
        # its OPEN and its micro-op rows, or a failed txn's appends
        seg_pos: List[int] = []
        seg_rows: List[int] = []
        heads: List[tuple] = []     # segment -> its first row, or None
        m_seg: List[int] = []       # micro-op -> segment
        m_type: List[int] = []
        m_mop: List[int] = []
        m_key: List[int] = []
        m_elem: List[int] = []      # an append's element
        m_rows: List[int] = []
        seen: List[tuple] = []      # a non-empty read's list
        free: List[int] = []
        slot_of: dict = {}
        next_slot = n_open = 0
        for i in range(n):
            cp = done_at[i]
            if cp == -2:            # a completion
                continue
            if fs[i] != "txn":
                raise _bad(f"unknown f {fs[i]!r}")
            ctype = ctype_of.get(i, "info")
            done = ctype == OK
            mops = _micro_ops(values[cp] if done else values[i], done)
            failed = ctype == FAIL
            seg = len(seg_pos)
            rows = 0
            for j, (f, k, v) in enumerate(mops):
                if f == APPEND:
                    typ, r = (EV_FAILED_APPEND if failed else EV_APPEND), 1
                    m_elem.append(v)
                elif failed or not done:
                    continue
                elif v:
                    typ, r = EV_OBSERVE, len(v)
                    seen.append(v)
                    m_elem.append(0)
                else:
                    typ, r = EV_READ_EMPTY, 1
                    m_elem.append(0)
                m_seg.append(seg)
                m_type.append(typ)
                m_mop.append(0 if failed else j)
                m_key.append(k)
                m_rows.append(r)
                rows += r
            if failed:
                if rows:
                    seg_pos.append(i)
                    seg_rows.append(rows)
                    heads.append(None)
                continue
            if not done and not rows:
                continue
            n_open += 1
            seg_pos.append(i)
            seg_rows.append(rows + 1)
            heads.append((EV_OPEN, i))
            if done:
                seg_pos.append(cp)
                seg_rows.append(1)
                heads.append((EV_FORCE, i))
        # the segments in the history's order; slots in that order too
        order = sorted(range(len(seg_pos)), key=seg_pos.__getitem__)
        place = [0] * len(order)
        slots = [0] * len(order)
        for at, s in enumerate(order):
            place[s] = at
            head = heads[s]
            if head is None:
                continue
            if head[0] == EV_OPEN:
                slot = heapq.heappop(free) if free else next_slot
                next_slot += slot == next_slot
                slot_of[head[1]] = slot
            else:
                slot = slot_of[head[1]]
                heapq.heappush(free, slot)
            slots[s] = slot
        rows_sorted = np.asarray([seg_rows[s] for s in order],
                                 dtype=np.int64)
        start_sorted = np.cumsum(rows_sorted) - rows_sorted
        n_ev = int(rows_sorted.sum()) if len(order) else 0
        seg_start = np.empty(len(order), dtype=np.int64)
        seg_start[np.asarray(order, dtype=np.int64)] = start_sorted
        events = np.zeros((n_ev, 5), dtype=np.int64)
        # heads
        has_head = np.asarray([h is not None for h in heads], dtype=bool)
        at = seg_start[has_head]
        events[at, 0] = [h[0] for h in heads if h is not None]
        events[at, 1] = np.asarray(slots, dtype=np.int64)[has_head]
        # micro-op rows: a segment's follow its head in order
        if m_seg:
            mseg = np.asarray(m_seg, dtype=np.int64)
            mrows = np.asarray(m_rows, dtype=np.int64)
            before = np.cumsum(mrows) - mrows
            first = np.flatnonzero(np.r_[True, mseg[1:] != mseg[:-1]])
            in_seg = before - np.repeat(before[first],
                                        np.diff(np.r_[first, len(mseg)]))
            mstart = seg_start[mseg] + has_head[mseg] + in_seg
            mtype = np.asarray(m_type, dtype=np.int64)
            head_cols = np.stack([mtype, np.asarray(m_mop, np.int64),
                                  np.asarray(m_key, np.int64),
                                  np.asarray(m_elem, np.int64)], axis=1)
            single = mtype != EV_OBSERVE
            events[mstart[single], :4] = head_cols[single]
            if seen:
                lens = mrows[~single]
                try:
                    elems = np.fromiter(chain.from_iterable(seen),
                                        dtype=np.int64,
                                        count=int(lens.sum()))
                except (TypeError, ValueError, OverflowError):
                    for e in chain.from_iterable(seen):
                        _int32(e, "element")
                    raise
                if len(elems) and (elems.min() < INT32_MIN
                                   or elems.max() > INT32_MAX):
                    bad = elems[(elems < INT32_MIN) | (elems > INT32_MAX)]
                    raise _bad(f"element {int(bad[0])!r} is not an int32")
                pos = np.arange(len(elems)) - np.repeat(
                    np.cumsum(lens) - lens, lens)
                rows_at = np.repeat(mstart[~single], lens) + pos
                events[rows_at, :3] = np.repeat(head_cols[~single, :3],
                                                lens, axis=0)
                events[rows_at, 3] = elems
                events[rows_at, 4] = pos
        # op_index and proc: a segment's rows all carry its own row's
        seg_sorted = np.asarray([seg_pos[s] for s in order], dtype=np.int64)
        pid_of: dict = {}
        pids = [pid_of.setdefault(procs[p], len(pid_of))
                for p in seg_sorted.tolist()]
        idx = np.asarray(index, dtype=np.int64)[seg_sorted] if n_ev \
            else np.empty(0, dtype=np.int64)
        return EncodedHistory(
            events=events.astype(np.int32),
            op_index=np.repeat(idx, rows_sorted).astype(np.int32),
            n_slots=next_slot, n_ops=n_open,
            proc=np.repeat(np.asarray(pids, dtype=np.int64),
                           rows_sorted).astype(np.int32))
