"""Strict serializability of transaction histories, Elle's way (ISSUE 51).

The rows of a transaction model (`models/listappend_txn.py`: one unit a
multi-key history, its op a transaction) are decided here, inside the
launch that `check_encoded` is, on both wires alike. A history is
strict-serializable iff it is linearizable with a transaction as the
atomic op over one object, the map of append-only lists. The plain
reference of the benchmark decides that by a frontier search; this
module decides the same question from the transactions' dependency
graph, which is complete for this workload because it is recoverable
and traceable: an element is appended to a key at most once, and a read
returns its key's WHOLE list, so every observed list names the
transactions that made it and the order they made it in.

Nodes: the `ok` transactions, and every `info` one an element of which
somebody observed (it took effect; one nobody observed constrains
nothing and is left out). Planes, each edge true of EVERY legal order:

  rt   T1 completed before T2 was invoked (its transitive reduction: of
       the transactions that completed before T2's invocation, those
       that completed after the latest invocation among them). A
       process's own order rides inside it.
  ww   consecutive elements of a key's longest observed list (the
       spine: every other observed list of the key is a prefix of it).
  wr   the appender of an observed list's last element -> the observer.
  rw   the observer -> the appender of the spine's next element past
       what it saw, and -> every node that appends to that key an
       element nobody observed. With the ww chain that reaches every
       node that appends an element the list lacks (anomaly.py's rule)
       through one rw edge.

**Valid iff the union is acyclic and no non-cycle anomaly holds**: an
observed element that nobody appended or that a `fail`ed transaction
appended (G1a), two observed lists of a key of which neither is a
prefix of the other, an element twice in a list, or a transaction at
odds with itself: an edge from a transaction to itself that runs
against the order of its own micro-ops (it read a key without its own
earlier append, or with its own later one: `internal`). A read that
shows part of another transaction's appends is a wr + rw 2-cycle. What
the inference cannot decide is `unknown`, never `valid`: an element
appended twice to a key (not recoverable), a stream it cannot parse, a
graph past the closure's node cap.

Per launch: the non-cycle checks and the edge lists of ALL its rows in
vectorised passes over their concatenated micro-op rows, a block of
rows that fits the cache a pass (`infer`; numpy sorts and searches, no
Python step a row, an element or an edge); the host sends EDGES,
one int32 a plane-source-target, and the device scatters them into the
planes, closes them batched (`ops/kernel_ir.make_txn_closure`) and
returns four flags a row: a cycle in rt u ww, in + wr, a single rw edge
closing such a path, any cycle. On a backend without an accelerator the
host decides the same flags from the same edges (`host_flags`;
`cycle._use_kernel`'s rule). Only a flagged row is looked at again, for
its anomaly's name and witness (`explain`: `anomaly.certify_planes`
over that row's planes, laid out from the edges the launch inferred;
graftd calls it at demux).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from ..history.packing import (EV_FORCE, EV_OPEN, EV_PAD, EncodedHistory,
                               bucket_rows)
from ..models.listappend_txn import (EV_APPEND, EV_FAILED_APPEND, EV_OBSERVE,
                                     EV_READ_EMPTY)
from ..ops.kernel_ir import CYCLE_MAX_NODES_TILED
from .base import INVALID, UNKNOWN, VALID
from .schedule import (ClosureLaunch, closure_rows_cap, closure_spec,
                       launch_span, note_cycle, note_tier, note_txn,
                       run_closure, span)

#: planes of a transaction graph, in the order of their codes
PLANES = ("rt", "ww", "wr", "rw")
RT, WW, WR, RW = range(4)
#: a host plane's place among the device program's three: rt and ww
#: are one class there (G0's), wr and rw one each
DEVICE_PLANE = np.asarray([0, 0, 1, 2], dtype=np.int64)
FLAGS = ("G0", "G1c", "G-single", "cycle")

_BIG = np.iinfo(np.int64).max // 4
#: spine cells (keys x longest list) a block of rows may hold (six
#: rows of this deployment are ~1e4); past it the block's rows are
#: `unknown`
_MAX_CELLS = 1 << 26


@dataclass
class Inferred:
    """What one pass over a launch's rows found."""

    n_nodes: np.ndarray                 # [B] nodes a row
    #: [E, 3] int64: plane, source node, target node (a node by its
    #: number in its row); no loops; a row's edges after the other's
    edges: np.ndarray
    edge_base: np.ndarray = None        # [B + 1] a row's first edge
    #: row -> the non-cycle anomalies it holds, by name
    anomalies: dict = field(default_factory=dict)
    #: row -> why the inference cannot decide it
    undecidable: dict = field(default_factory=dict)
    #: [T] the history index of each node's invocation, rows in order
    node_op: np.ndarray = None
    node_base: np.ndarray = None        # [B + 1] a row's first node

    def row_edges(self, row: int) -> np.ndarray:
        """One row's `[E, 3]` (plane, source, target) edges."""
        return self.edges[self.edge_base[row]:self.edge_base[row + 1]]

    def row_graph(self, row: int) -> dict:
        """What `explain` needs of one row, the launch's arrays let go."""
        a, b = self.node_base[row], self.node_base[row + 1]
        return {"n": int(self.n_nodes[row]),
                "edges": self.row_edges(row).copy(),
                "op_index": self.node_op[a:b].tolist(),
                "non-cycle": list(self.anomalies.get(row, []))}


def _pack(hi, lo):
    """Two int columns as one sortable int64 (`lo` any int32)."""
    return (np.asarray(hi, np.int64) << 32) | (np.asarray(lo, np.int64)
                                               & 0xFFFFFFFF)


def _lookup(sorted_ids, ids):
    """(found?, place in `sorted_ids`) of every id of `ids`."""
    if not len(sorted_ids):
        return np.zeros(len(ids), bool), np.zeros(len(ids), np.int64)
    at = np.minimum(np.searchsorted(sorted_ids, ids), len(sorted_ids) - 1)
    return sorted_ids[at] == ids, at


def _ranges(lo, hi):
    """(which range, index) of every index in the ranges [lo, hi)."""
    n = np.maximum(hi - lo, 0)
    which = np.repeat(np.arange(len(lo)), n)
    at = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
    return which, lo[which] + at


#: events one pass of the inference holds. A launch's rows are taken in
#: blocks of about this many events (six of this deployment's
#: histories), so that a pass's columns stay in the cache: 64 of the
#: cell's histories read 1.77 ms a row at 8 rows a pass against 3.38
#: in one pass of 1.4 M events (my host runs, PR 51)
INFER_BLOCK_EVENTS = 1 << 17


def infer(encs: Sequence[EncodedHistory]) -> Inferred:
    """Nodes, edges and non-cycle anomalies of every row of a launch:
    `_infer_block` over the launch's rows a block after the other, put
    together with the rows numbered as the launch has them."""
    starts, held = [0], 0
    for i, enc in enumerate(encs):
        if i > starts[-1] and held + enc.n_events > INFER_BLOCK_EVENTS:
            starts.append(i)
            held = 0
        held += enc.n_events
    parts = [_infer_block(encs[a:b])
             for a, b in zip(starts, starts[1:] + [len(encs)])]
    out = Inferred(
        n_nodes=np.concatenate([p.n_nodes for p in parts]),
        edges=np.concatenate([p.edges for p in parts]),
        node_op=np.concatenate([p.node_op for p in parts]))
    out.node_base = np.r_[0, np.cumsum(out.n_nodes)]
    out.edge_base = np.r_[0, np.cumsum(np.concatenate(
        [np.diff(p.edge_base) for p in parts]))]
    for a, p in zip(starts, parts):
        out.anomalies.update((a + r, v) for r, v in p.anomalies.items())
        out.undecidable.update((a + r, v) for r, v in p.undecidable.items())
    return out


def _infer_block(encs: Sequence[EncodedHistory]) -> Inferred:
    """One vectorised pass over the concatenated events of `encs`."""
    B = len(encs)
    lens = np.asarray([e.n_events for e in encs], dtype=np.int64)
    # The events' five fields as five contiguous int32 columns: a pass
    # over one of them then reads 4 bytes an event, not a 20-byte row
    # (every pass below is bound by the memory it reads). `arg` is the
    # slot of an OPEN or a FORCE and a micro-op's place in its
    # transaction.
    typ, arg, key, elem, pos = np.concatenate(
        [np.asarray(e.events, dtype=np.int32).reshape(-1, 5).T
         for e in encs] + [np.zeros((5, 0), np.int32)], axis=1)
    rid = np.repeat(np.arange(B, dtype=np.int32), lens)
    row_start = np.cumsum(lens) - lens
    out = Inferred(n_nodes=np.zeros(B, np.int64),
                   edges=np.zeros((0, 3), np.int64),
                   edge_base=np.zeros(B + 1, np.int64),
                   node_op=np.zeros(0, np.int64),
                   node_base=np.zeros(B + 1, np.int64))

    def refuse(rows, why: str) -> None:
        for r in np.unique(rows).tolist():
            out.undecidable.setdefault(int(r), why)

    def flag(rows, what: str) -> None:
        for r in np.unique(rows).tolist():
            out.anomalies.setdefault(int(r), []).append(what)

    # -- transactions: an OPEN each; the FORCE of its slot completes it
    is_open = typ == EV_OPEN
    open_pos = np.flatnonzero(is_open)
    T = len(open_pos)
    # event row -> latest OPEN
    txn_at = np.cumsum(is_open, dtype=np.int32) - np.int32(1)
    if not T:
        refuse(rid[(typ >= EV_APPEND) & (typ <= EV_READ_EMPTY)],
               "a micro-op before any invocation")
        return out
    t_rid = rid[open_pos].astype(np.int64)
    comp = np.full(T, _BIG, dtype=np.int64)
    of = np.flatnonzero(is_open | (typ == EV_FORCE))
    if len(of):
        slot_key = _pack(rid[of], arg[of])
        order = np.argsort(slot_key, kind="stable")
        sof, skey = of[order], slot_key[order]
        f_at = np.flatnonzero(typ[sof] == EV_FORCE)
        prev = np.maximum(f_at - 1, 0)
        ok_pair = (f_at > 0) & (typ[sof[prev]] == EV_OPEN) \
            & (skey[prev] == skey[f_at])
        refuse(rid[sof[f_at[~ok_pair]]], "a completion without its "
               "invocation in the event stream")
        comp[txn_at[sof[prev[ok_pair]]]] = sof[f_at[ok_pair]]
    ok = comp < _BIG
    # A micro-op before its row's first invocation belongs to no
    # transaction of that row (`txn_at` would hand it to the row
    # before): the row is refused and the micro-op struck out, so that
    # no row's stream can touch another row's graph. (A failed
    # transaction's append has no invocation by design.)
    t_first = np.minimum(np.searchsorted(t_rid, np.arange(B)), T - 1)
    which, at = _ranges(row_start, np.where(
        t_rid[t_first] == np.arange(B), open_pos[t_first],
        row_start + lens))
    stray = (typ[at] >= EV_APPEND) & (typ[at] <= EV_READ_EMPTY)
    refuse(which[stray], "a micro-op before any invocation")
    typ[at[stray]] = EV_PAD
    refuse(rid[(typ < EV_PAD) | (typ > EV_FAILED_APPEND)],
           "an event of an unknown type")     # the codes are 0 .. 6

    # -- appends, reads (one entry a micro-op), observed elements
    a_rows = np.flatnonzero(typ == EV_APPEND)
    a_txn, a_mop = txn_at[a_rows], arg[a_rows].astype(np.int64)
    a_rk_raw = _pack(rid[a_rows], key[a_rows])
    f_rows = np.flatnonzero(typ == EV_FAILED_APPEND)
    # of a transaction that completed `ok` (txn_at is -1 before any)
    read_ok = ok[np.maximum(txn_at, 0)] & (txn_at >= 0)
    o_rows = np.flatnonzero((typ == EV_OBSERVE) & read_ok)
    e_rows = np.flatnonzero((typ == EV_READ_EMPTY) & read_ok)
    o_pos = pos[o_rows]
    # a read's elements lie in a run: positions 0, 1, 2, ... of one
    # micro-op of one transaction on one key. Held row against the row
    # before it, which says the same of a run as holding each row
    # against its run's first.
    o_txn, o_mop, o_key = txn_at[o_rows], arg[o_rows], key[o_rows]
    bad = o_pos != 0
    bad[1:] &= ((o_pos[1:] != o_pos[:-1] + 1) | (o_txn[1:] != o_txn[:-1])
                | (o_mop[1:] != o_mop[:-1]) | (o_key[1:] != o_key[:-1]))
    if bad.any():       # those rows are refused and lose their reads
        rows = np.unique(rid[o_rows[bad]])
        refuse(rows, "observed elements out of their list's order")
        keep = ~np.isin(rid[o_rows], rows)
        o_rows, o_pos = o_rows[keep], o_pos[keep]
    first = np.flatnonzero(o_pos == 0)       # a read's first element
    r_len = np.diff(np.r_[first, len(o_rows)])
    o_read = np.repeat(np.arange(len(first)), r_len)
    # reads: the non-empty ones, then the empty ones
    r_row = np.r_[o_rows[first], e_rows]
    r_txn, r_mop = txn_at[r_row], arg[r_row].astype(np.int64)
    r_len = np.r_[r_len, np.zeros(len(e_rows), np.int64)]
    r_rk_raw = _pack(rid[r_row], key[r_row])
    f_rk_raw = _pack(rid[f_rows], key[f_rows])
    # (row, key) -> a dense id, over everything that names a key
    rk_all, inv = np.unique(np.r_[a_rk_raw, r_rk_raw, f_rk_raw],
                            return_inverse=True)
    a_rk = inv[:len(a_rows)]
    r_rk = inv[len(a_rows):len(a_rows) + len(r_row)]
    f_rk = inv[len(a_rows) + len(r_row):]
    K = len(rk_all)
    rk_rid = (rk_all >> 32).astype(np.int64)

    # -- the spine of each key: position -> element, every observed
    # list held to it
    slen = np.zeros(K, np.int64)
    np.maximum.at(slen, r_rk, r_len)
    lmax = int(slen.max()) if K else 0
    if K * max(lmax, 1) > _MAX_CELLS:
        refuse(np.arange(B), "lists too long for the spine table")
        return out
    cell_of = r_rk[:len(first)][o_read] * max(lmax, 1) + o_pos
    o_elem = elem[o_rows]
    spine = np.zeros(K * max(lmax, 1), np.int64)
    spine[cell_of] = o_elem
    flag(rid[o_rows[spine[cell_of] != o_elem]], "incompatible-order")
    # the spine's cells, a key after the other
    s_rk, s_pos = _ranges(np.zeros(K, np.int64), slen)
    s_elem = spine[s_rk * max(lmax, 1) + s_pos]
    s_id = _pack(s_rk, s_elem)
    srt = np.sort(s_id)
    flag(rk_rid[(srt[1:][srt[1:] == srt[:-1]] >> 32)], "duplicate-elements")
    # who appended each cell's element
    a_id = _pack(a_rk, elem[a_rows])
    a_order = np.argsort(a_id, kind="stable")
    a_sorted = a_id[a_order]
    twice = a_sorted[1:] == a_sorted[:-1]
    refuse(rk_rid[a_sorted[1:][twice] >> 32],
           "an element appended twice to one key: not recoverable")
    hit, at = _lookup(a_sorted, s_id)
    s_app = np.where(hit, a_order[at] if len(a_order) else 0, -1)
    if (~hit).any():
        miss = s_id[~hit]
        failed, _ = _lookup(np.sort(_pack(f_rk, elem[f_rows])), miss)
        flag(rk_rid[s_rk[~hit][failed]], "G1a-aborted-read")
        flag(rk_rid[s_rk[~hit][~failed]], "G1a-unwritten-read")
    s_base = np.cumsum(slen) - slen           # key -> its first cell
    a_seen = np.zeros(len(a_rows), bool)
    a_seen[s_app[hit]] = True

    # -- nodes: ok transactions, and info ones somebody observed
    node = ok.copy()
    node[a_txn[a_seen]] = True
    node_no = np.cumsum(node) - 1
    out.node_base = np.searchsorted(t_rid[node], np.arange(B + 1))
    out.n_nodes = np.diff(out.node_base)
    op_index = np.concatenate([np.asarray(e.op_index).reshape(-1)
                               for e in encs]) if lens.sum() else \
        np.zeros(0, np.int64)
    out.node_op = op_index[open_pos[node]].astype(np.int64)
    t_node = node_no - out.node_base[t_rid]   # txn -> its row's node no.

    # -- edges as (plane, source txn, target txn, source mop, target mop)
    parts = []

    def edges(plane, src, dst, src_mop, dst_mop):
        parts.append((np.full(len(src), plane, np.int64), src, dst,
                      src_mop, dst_mop))

    cell_ok = s_app >= 0
    nxt = np.flatnonzero((s_pos + 1 < slen[s_rk]) & cell_ok
                         & np.r_[cell_ok[1:], False])
    edges(WW, a_txn[s_app[nxt]], a_txn[s_app[nxt + 1]],
          a_mop[s_app[nxt]], a_mop[s_app[nxt + 1]])
    rd = np.flatnonzero(r_len > 0)
    last = s_app[s_base[r_rk[rd]] + r_len[rd] - 1]
    keep = last >= 0
    edges(WR, a_txn[last[keep]], r_txn[rd][keep], a_mop[last[keep]],
          r_mop[rd][keep])
    rd = np.flatnonzero(r_len < slen[r_rk])
    after = s_app[s_base[r_rk[rd]] + r_len[rd]]
    keep = after >= 0
    edges(RW, r_txn[rd][keep], a_txn[after[keep]], r_mop[rd][keep],
          a_mop[after[keep]])
    # every read of a key -> every node's append to it nobody observed
    un = np.flatnonzero(~a_seen & node[a_txn])
    if len(un) and len(r_row):
        un = un[np.argsort(a_rk[un], kind="stable")]
        lo = np.searchsorted(a_rk[un], r_rk, "left")
        hi = np.searchsorted(a_rk[un], r_rk, "right")
        which, at = _ranges(lo, hi)
        edges(RW, r_txn[which], a_txn[un[at]], r_mop[which], a_mop[un[at]])
    # real time: of the transactions that completed before T's
    # invocation, those that completed after the latest invocation
    # among them
    f_pos = np.sort(comp[ok])
    f_txn = np.flatnonzero(ok)[np.argsort(comp[ok])]
    if len(f_pos):
        inv_of = np.full(len(typ), -1, np.int64)
        inv_of[f_pos] = open_pos[f_txn]
        latest = np.maximum.accumulate(inv_of)
        nodes_t = np.flatnonzero(node)
        p = open_pos[nodes_t]
        m = np.maximum(np.where(p > 0, latest[np.maximum(p - 1, 0)], -1),
                       row_start[t_rid[nodes_t]] - 1)
        which, at = _ranges(np.searchsorted(f_pos, m, "right"),
                            np.searchsorted(f_pos, p, "left"))
        zero = np.zeros(len(which), np.int64)
        edges(RT, f_txn[at], nodes_t[which], zero, zero + 1)

    plane, src, dst, smop, dmop = (np.concatenate(c) for c in zip(*parts)) \
        if parts else (np.zeros(0, np.int64),) * 5
    loop = src == dst
    flag(t_rid[src[loop & (smop > dmop)]], "internal")
    keep = np.flatnonzero(~loop)
    e_rid = t_rid[src[keep]]
    order = np.argsort(e_rid, kind="stable")     # a row after the other
    keep, e_rid = keep[order], e_rid[order]
    out.edges = np.empty((len(keep), 3), np.int64)
    for col, values in enumerate((plane[keep], t_node[src[keep]],
                                  t_node[dst[keep]])):
        out.edges[:, col] = values
    out.edge_base = np.searchsorted(e_rid, np.arange(B + 1))
    return out


# ------------------------------------------------------------- host arm


def _dense(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    adj = np.zeros((n, n), dtype=np.uint8)
    adj[src, dst] = 1
    return adj


def host_flags(n: int, edges: np.ndarray) -> tuple:
    """The device program's four flags of one row, from its `[E, 3]`
    (plane, source, target) edges: a cycle in rt u ww, in + wr, an rw
    edge whose target reaches its source in rt u ww u wr, any cycle."""
    from .cycle import host_has_cycle

    plane, src, dst = edges[:, 0], edges[:, 1], edges[:, 2]
    if not host_has_cycle(_dense(n, src, dst)):
        return False, False, False, False
    anyc = True
    c0 = plane <= WW
    c1 = plane <= WR
    g0 = host_has_cycle(_dense(n, src[c0], dst[c0]))
    g1c = g0 or host_has_cycle(_dense(n, src[c1], dst[c1]))
    # one rw edge closing a path of the rest: some rw edge (u, v) with
    # v ~> u. Rare (a flagged row), so a search an rw target.
    gs = False
    adj: dict = {}
    for u, v in zip(src[c1].tolist(), dst[c1].tolist()):
        adj.setdefault(u, []).append(v)
    rw = edges[plane == RW]
    for v in np.unique(rw[:, 2]).tolist():
        want = set(rw[rw[:, 2] == v, 1].tolist())
        seen, stack = {v}, [v]
        while stack and not gs:
            for w in adj.get(stack.pop(), ()):
                if w in want:
                    gs = True
                    break
                if w not in seen:
                    seen.add(w)
                    stack.append(w)
        if gs:
            break
    return g0, g1c, gs, anyc


# ----------------------------------------------------------- device arm


def edge_width(n_bucket: int, n_edges: int) -> int:
    """Edges a row of a closure launch: sixteen a node, doubled until
    the launch's fattest row fits, so that the histories of one
    deployment meet one key."""
    w = 16 * n_bucket
    while w < n_edges:
        w *= 2
    return w


def _device_flags(model, n_bucket: int, rows: List[int], inf: Inferred,
                  serve_rows: Optional[int]) -> dict:
    """Flags of `rows` (all of node bucket `n_bucket`) by the closure
    program, a launch of at most `closure_rows_cap` rows at a time."""
    out: dict = {}
    cap = closure_rows_cap(n_bucket)
    by_row = {r: inf.row_edges(r) for r in rows}
    for at in range(0, len(rows), cap):
        part = rows[at:at + cap]
        width = edge_width(n_bucket, max(len(by_row[r]) for r in part))
        launch = ClosureLaunch(closure_spec(model, n_bucket), width,
                               len(part))
        with launch_span(len(part)), span("launch.closure", n=len(part)):
            codes, run = run_closure(launch, serve_rows)
            for j, r in enumerate(part):
                e = by_row[r]
                codes[j, :len(e)] = (DEVICE_PLANE[e[:, 0]] * n_bucket
                                     + e[:, 1]) * n_bucket + e[:, 2]
            flags, iters = run()
        note_txn(closure_launches=1, closure_macs=int(
            len(codes) * n_bucket ** 3 * int(iters.sum())))
        for j, r in enumerate(part):
            out[r] = tuple(bool(x) for x in flags[j])
    return out


# --------------------------------------------------------------- verdicts


def check_txn_rows(encs: Sequence[EncodedHistory], model,
                   kernel: Optional[bool] = None,
                   serve_rows: Optional[int] = None,
                   explain_flagged: bool = True) -> List[dict]:
    """One result a row of a transaction model's launch. `kernel`
    forces the arm (tests); None is `cycle._use_kernel`'s rule.
    `explain_flagged` false leaves a flagged row's `anomalies` to the
    caller: graftd's demux, which hands the row's result to `explain`
    with the row's graph still in it (`txn-graph`; `explain` takes it
    out again)."""
    from .cycle import _use_kernel

    t0 = time.perf_counter()
    with span("launch.graph", n=len(encs)):
        inf = infer(encs)
    use_kernel = _use_kernel() if kernel is None else kernel
    results: List[Optional[dict]] = [None] * len(encs)
    todo: dict = {}   # node bucket -> rows
    for i, enc in enumerate(encs):
        n = int(inf.n_nodes[i])
        base = {"algorithm": "txn-graph", "op-count": enc.n_ops,
                "concurrency-window": enc.n_slots,
                "decided-tier": "cycle", "nodes": n}
        if i in inf.undecidable:
            results[i] = dict(base, **{"valid?": UNKNOWN,
                                       "error": inf.undecidable[i]})
        elif n > CYCLE_MAX_NODES_TILED:
            note_cycle(cycle_size_skips=1)
            results[i] = dict(base, **{"valid?": UNKNOWN,
                                       "cycle-skipped-size": n})
        elif i in inf.anomalies:
            results[i] = dict(base, **{"valid?": INVALID,
                                       "flags": sorted(inf.anomalies[i])})
        elif n < 2:
            results[i] = dict(base, **{"valid?": VALID})
        else:
            results[i] = base
            todo.setdefault(bucket_rows(n, 4), []).append(i)
    note_txn(txn_rows=len(encs), txn_nodes=int(inf.n_nodes.sum()),
             txn_edges=len(inf.edges))
    for n_bucket, rows in sorted(todo.items()):
        # either arm: the same flags from the same edges
        # (tests/test_listappend_txn.py holds the two together), so
        # which arm ran is routing and never a verdict
        if use_kernel:
            flags = _device_flags(model, n_bucket, rows, inf, serve_rows)
            arm = "closure"
        else:
            with launch_span(len(rows)), span("launch.closure",
                                              n=len(rows)):
                flags = {r: host_flags(int(inf.n_nodes[r]),
                                       inf.row_edges(r)) for r in rows}
            arm = "host-scc"
        for r in rows:
            hit = [name for name, f in zip(FLAGS, flags[r]) if f]
            results[r]["valid?"] = INVALID if hit else VALID
            if hit:
                results[r]["flags"] = hit
            results[r]["kernel"] = arm
    flagged = [i for i, r in enumerate(results) if r["valid?"] is INVALID]
    note_txn(txn_rows_flagged=len(flagged))
    for i in flagged:
        results[i]["txn-graph"] = inf.row_graph(i)
        if explain_flagged:
            explain(results[i])
    wall = time.perf_counter() - t0
    note_tier("cycle", rows=len(encs), wall_s=wall)
    return results  # type: ignore[return-value]


def explain(result: dict) -> None:
    """Name a flagged row's anomalies and give each cycle's witness
    (history indices of the transactions' invocations): what the flags
    said, looked at again on the host, one row, from the edges its
    launch inferred (`Inferred.row_graph`, under `txn-graph` in the
    result until here)."""
    from .anomaly import certify_planes

    g = result.pop("txn-graph", None)
    if g is None:
        return
    n, edges = g["n"], g["edges"]
    planes = {name: _dense(n, *edges[edges[:, 0] == p, 1:].T)
              for p, name in enumerate(PLANES)}
    planes["po"] = np.zeros((n, n), dtype=np.uint8)
    graph = {"n": n, "planes": planes, "op_index": g["op_index"],
             "adj": functools.reduce(np.bitwise_or, planes.values())}
    found = {name: {} for name in g["non-cycle"]}
    found.update((k, v) for k, v in
                 certify_planes(graph, kernel=False).items()
                 if v is not None)
    result["anomalies"] = found
