"""Request records for the checking service.

A submission enters graftd as raw history material (op dicts over the
wire, `history.ops.History` objects in-process, or a recorded-run dir)
and is normalized at ADMISSION into a `CheckRequest`: per-unit encoded
event tensors (`history.packing.encode_history` — encoded exactly once,
here), a content fingerprint over those tensors (the result-cache key:
two tenants submitting byte-identical histories share one verdict), and
scheduling metadata (deadline, priority, submit time). Everything
downstream — bucketing, coalescing, demux — works on the encodings; the
raw ops are kept only for the per-request trace record.
"""

from __future__ import annotations

import hashlib
import threading
import time
import uuid
from dataclasses import dataclass, field
from itertools import repeat
from typing import List, Optional, Sequence

import numpy as np

from ..checker.base import merge_valid
from ..checker.schedule import span
from ..history.ops import INVOKE, NEMESIS, History, Op, OpRow
from ..history.packing import (EncodedHistory, encode_history,
                               encode_vector_on)

# Request lifecycle states.
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
CANCELLED = "cancelled"
FAILED = "failed"

#: Priority clamp at admission: each unit is one second of deadline
#: credit (scheduler.PRIORITY_CREDIT_S), so ±8 bounds the head start at
#: ±8 s — well under the 30 s aging cap, keeping the documented
#: starvation-free guarantee true against a client-supplied flood of
#: arbitrarily large priorities.
MAX_PRIORITY = 8

#: workload name → (model factory, values are (key, value) tuples?).
#: The tuple-valued workloads are split per key at admission (the same
#: independent decomposition checker/recorded.py applies to stored
#: runs), so one submitted multi-register history becomes one check
#: unit per key, labelled `h{i}/key={key}` in `str(key)` order
#: (`encode_units`: from the rows' columns where the histories arrive
#: as op dicts, through `split_by_key` otherwise; the same units either
#: way). "register"/"counter" accept plain single-key histories, one
#: unit `h{i}` each. "list-append-txn" (ISSUE 51) is Elle's list-append
#: with a TRANSACTION as the op: multi-key, and never split (a
#: transaction is atomic over its keys): one unit `h{i}` a history,
#: decided by the transaction graph inside the launch
#: (checker/txn_graph.py). "list-append" is the older single-op face of
#: that workload: split per key, with a cross-key overlay at admission
#: on the JSON wire only. The benchmark submits "register", "counter",
#: "multi-register" and "list-append-txn".
def service_workloads() -> dict:
    from ..models import (CasRegister, Counter, GSet, ListAppend,
                          ListAppendTxn, TicketQueue)

    return {
        "register": (CasRegister, False),
        "counter": (Counter, False),
        "single-register": (CasRegister, True),
        "multi-register": (CasRegister, True),
        "set": (GSet, False),
        "queue": (TicketQueue, False),
        "list-append": (ListAppend, True),
        "list-append-txn": (ListAppendTxn, False),
    }


def history_from_dicts(rows: Sequence[dict]) -> History:
    """Wire format → History: one op dict per row (`Op.to_dict` shape).
    JSON has no tuples, so list-valued ops (the independent workloads'
    (key, value) pairs) are retupled — same rule as `store.load_history`."""
    h = History()
    for d in rows:
        d = dict(d)
        if isinstance(d.get("value"), list):
            d["value"] = tuple(d["value"])
        h.append(Op.from_dict(d))
    return h


#: the keys of an op dict that `Op` holds as fields (`Op.from_dict`);
#: the rest ride in `extra`
_OP_KEYS = frozenset(
    ("process", "type", "f", "value", "time", "index", "error"))


def _wire_columns(dicts: Sequence[dict]):
    """The columns of a wire history that its encoding reads, under
    `history_from_dicts`'s rules: a missing `process` / `type` / `f` is
    a KeyError, list values are retupled, and `index` defaults to the
    row's position among ALL rows (what `History.append` gives an `Op`
    whose index is unset)."""
    procs = [d["process"] for d in dicts]
    types = [d["type"] for d in dicts]
    fs = [d["f"] for d in dicts]
    values = [d.get("value") for d in dicts]
    index = [d.get("index", -1) for d in dicts]
    if any(issubclass(t, list) for t in set(map(type, values))):
        values = [tuple(v) if isinstance(v, list) else v for v in values]
    if index and min(index) < 0:
        index = [i if x < 0 else x for i, x in enumerate(index)]
    return procs, types, fs, values, index


def _client_rows(cols) -> List[OpRow]:
    """`_wire_columns`' answer zipped into `OpRow`s, nemesis rows left
    out (`History.client_ops`). `tuple.__new__` is what `OpRow._make`
    calls, without a Python frame a row."""
    rows = list(map(tuple.__new__, repeat(OpRow), zip(*cols)))
    if NEMESIS in cols[0]:
        rows = [r for r in rows if r.process != NEMESIS]
    return rows


def _client_columns(cols) -> tuple:
    """`_wire_columns`' answer with the nemesis rows left out."""
    if NEMESIS not in cols[0]:
        return cols
    live = [j for j, p in enumerate(cols[0]) if p != NEMESIS]
    return tuple(list(map(col.__getitem__, live)) for col in cols)


def rows_from_dicts(dicts: Sequence[dict]) -> List[OpRow]:
    """Wire format → the client ops as `OpRow`s: what
    `history_from_dicts(dicts).client_ops()` holds, field for field of
    the five an encoding reads, with no `Op` built an event — the
    columns are read straight from the dicts and zipped."""
    return _client_rows(_wire_columns(dicts))


def _split_columns(dicts: Sequence[dict], cols) -> list:
    """`checker.independent.split_by_key` over a wire history's columns
    (`_wire_columns`' answer): `[(key, the key's dicts, the key's
    columns)]` in the order the keys first appear among the client
    rows, the key taken off every value and `index` left the row's
    place in the whole history. The same rules, so the same refusals:
    an invocation with no value at all is the ValueError `split_by_key`
    raises, with the `Op` it would have printed; a value that is not a
    pair fails to unpack as it does there; a row whose value or key is
    None belongs to no key."""
    procs, types, fs, values, index = cols
    live = range(len(procs))
    if NEMESIS in procs:
        live = [j for j in live if procs[j] != NEMESIS]
    inner = list(values)
    at: dict = {}  # key -> positions of its rows
    for j in live:
        v = values[j]
        if v is None:
            if types[j] == INVOKE:
                op = Op.from_dict({**dicts[j], "index": index[j]})
                raise ValueError(
                    f"independent history contains untupled op: {op}")
            continue
        key, inner[j] = v
        if key is None:
            continue
        rows = at.get(key)
        if rows is None:
            at[key] = [j]
        else:
            rows.append(j)
    return [(key, tuple(map(dicts.__getitem__, pos)), tuple(
        tuple(map(col.__getitem__, pos))
        for col in (procs, types, fs, inner, index)))
        for key, pos in at.items()]


class WireHistory(History):
    """`history_from_dicts(dicts).client_ops()` whose `Op`s are built
    when something first reads them: the unit of a submission that was
    encoded from its rows' columns (`encode_units`). Nothing on the way
    to a verdict reads them; a counterexample does (`scheduler.
    _attach_counterexamples`, invalid rows only), and the trace record
    reads `to_dicts`, which answers from the rows without them."""

    def __init__(self, dicts: Sequence[dict], cols=None):
        #: `cols`: the rows' columns where they are not `_wire_columns`
        #: of `dicts`: one key's rows of a split history, the key taken
        #: off the values and `index` the row's place in the WHOLE
        #: history (`_split_columns`; what `split_by_key` gives an `Op`)
        self._dicts = dicts
        self._cols = cols
        self._ops: Optional[List[Op]] = None

    @property
    def ops(self) -> List[Op]:
        if self._ops is None:
            self._ops = [
                Op(process=p, type=t, f=f, value=v,
                   time=d.get("time", -1), index=i, error=d.get("error"),
                   extra={k: x for k, x in d.items() if k not in _OP_KEYS})
                for d, p, t, f, v, i in zip(self._dicts, *self._columns())
                if p != NEMESIS]
        return self._ops

    def _columns(self):
        return self._cols if self._cols is not None \
            else _wire_columns(self._dicts)

    def to_dicts(self) -> List[dict]:
        """`[op.to_dict() for op in self]`, key for key and in
        `Op.to_dict`'s order."""
        if self._ops is not None:
            return super().to_dicts()
        out = []
        for d, p, t, f, v, i in zip(self._dicts, *self._columns()):
            if p == NEMESIS:
                continue
            row = {"process": p, "type": t, "f": f, "value": v,
                   "time": d.get("time", -1), "index": i}
            if d.get("error") is not None:
                row["error"] = d["error"]
            row.update((k, x) for k, x in d.items() if k not in _OP_KEYS)
            out.append(row)
        return out


def fingerprint_encodings(model, algorithm: str,
                          encs: Sequence[EncodedHistory],
                          consistency: str = "linearizable") -> str:
    """Content hash over the packed arrays of a submission — the result
    cache key. Hashing the ENCODING (not the op dicts) makes the cache
    insensitive to wire-level noise that cannot change the verdict
    (timestamps, op indices of dropped fail ops) while staying sound:
    the encoded event stream is exactly the checker's input. The
    consistency rung is part of the identity: the same bytes checked at
    a weaker rung are a DIFFERENT verdict — and at a weaker rung the
    per-event process ids are hashed too, because the relaxation defers
    FORCEs along per-process order, so two submissions with identical
    event rows but different proc arrays genuinely have different
    verdicts there (at the linearizable rung proc is inert and stays
    out of the hash, preserving wire-noise insensitivity).

    Zero-copy (ISSUE 15 tentpole (d)): the packed int32 buffers feed
    sha256 through memoryviews — `hashlib.update` consumes any
    C-contiguous buffer directly, so the per-submission `tobytes()`
    copies of the (often multi-MB) event tensors are gone. The BYTES
    hashed are identical, so every digest value is unchanged — the
    content-addressed store and the WAL replay key on these values
    (pinned by the golden-fingerprint test)."""
    h = hashlib.sha256()
    h.update(type(model).__name__.encode())
    h.update(b"\x00")
    h.update(algorithm.encode())
    weak = consistency != "linearizable"
    if weak:
        h.update(b"\x00")
        h.update(consistency.encode())
    for e in encs:
        h.update(memoryview(np.asarray(e.events.shape, dtype=np.int64)))
        h.update(memoryview(np.ascontiguousarray(e.events)))
        h.update(np.int64(e.n_slots).data)
        if weak:
            h.update(b"\x01" if e.proc is not None else b"\x00")
            if e.proc is not None:
                h.update(memoryview(np.ascontiguousarray(
                    np.asarray(e.proc, dtype=np.int32))))
    return h.hexdigest()


@dataclass
class CheckRequest:
    """One tenant submission, admitted and encoded.

    units: (label, History) pairs — one frontier-check unit each (a
        plain submission is one unit per history; independent workloads
        contribute one unit per key).
    encs: the per-unit encodings, parallel to `units`.
    deadline/submitted: monotonic seconds (scheduling only — a missed
        deadline reorders, it never drops).
    results: per-unit checker result dicts once DONE.
    stats: batch-attribution stamped at demux (batched_requests,
        batch_rows, batch_seq, the launch's labeled scan-scope counters).
    """

    id: str
    workload: str
    model: object
    algorithm: str
    units: List[tuple]
    encs: List[EncodedHistory]
    fingerprint: str
    deadline: float
    submitted: float
    priority: int = 0
    #: consistency ladder rung (checker/consistency.py): part of the
    #: bucket signature (same-rung requests coalesce) and the result
    #: fingerprint; the checker relaxes per batch, so admission keeps
    #: the canonical linearizable encoding.
    consistency: str = "linearizable"
    status: str = QUEUED
    results: Optional[List[dict]] = None
    error: Optional[str] = None
    cached: bool = False
    stats: dict = field(default_factory=dict)
    cancelled: threading.Event = field(default_factory=threading.Event)
    #: durability/resilience lifecycle (ISSUE 8): executor deaths while
    #: this request's batch was in flight (quarantined past the crash
    #: cap), solo = excluded from coalescing (a poison batch is SPLIT so
    #: innocent riders complete alone), force_host = the hung-batch
    #: watchdog's second strike (re-run via check_encoded_host, never
    #: the device path), watchdog_hits = strikes so far, replayed = came
    #: back from the admission journal, attached_to = idempotent-dup
    #: follower of the named primary request.
    crash_count: int = 0
    solo: bool = False
    force_host: bool = False
    watchdog_hits: int = 0
    #: monotonic time the CURRENT execution began (scheduler.execute
    #: stamps it beside the RUNNING flip). The watchdog strikes only
    #: when the EXECUTION has been running past the margin — a request
    #: that merely waited out its deadline in a backlogged queue is
    #: late, not hung, and demoting healthy workers for it would
    #: amplify the overload.
    run_started: float = 0.0
    #: the other stamps of a request's life (ISSUE 26; monotonic, 0.0 =
    #: not reached): `taken` when the dispatcher's `take` returned it,
    #: `scanned` when the fast lane's scan of THIS request ended,
    #: `finished` when it turned terminal. With `submitted` and
    #: `run_started` they give `stats["phases_ms"]`
    #: (scheduler._stamp_phases).
    taken: float = 0.0
    scanned: float = 0.0
    finished: float = 0.0
    replayed: bool = False
    attached_to: Optional[str] = None
    #: transactional-anomaly overlay (ISSUE 19): stamped at ADMISSION
    #: for txn_anomaly_capable models (`list-append` alone; a
    #: `list-append-txn` unit is certified inside its launch and its
    #: anomalies are in its result) from the UNDECOMPOSED
    #: multi-key histories — the per-key units cannot see cross-key
    #: cycles, and the fingerprint hashes only per-unit encodings, so
    #: this rides outside the result cache on purpose: a cached unit
    #: result-set stays reusable while the overlay is recomputed per
    #: submission (two submissions CAN share per-key encodings yet
    #: differ in cross-key session order). The binary lane
    #: (admit_encoded) ships encodings only, so it has no overlay: a
    #: `list-append` frame is answered from its per-key units alone.
    txn_anomalies: Optional[dict] = None
    #: `admit` encoded it from its rows' columns (`encode_units`); the
    #: daemon counts the two ways in `/stats`. Says how the encoding was
    #: made, never what it is: the arrays are equal either way.
    from_columns: bool = False
    _done: threading.Event = field(default_factory=threading.Event)
    _finish_lock: threading.Lock = field(default_factory=threading.Lock)

    @property
    def n_rows(self) -> int:
        return len(self.encs)

    @property
    def n_histories(self) -> int:
        """The histories this request's units came from: the distinct
        heads of their labels, `h{i}` of `h{i}` and of `h{i}/key=…`
        (a recorded run's `{workload}/u{j}` units are one history).
        Read from the labels, so a frame's and a replayed record's
        count as the JSON wire's does."""
        return len({str(label).split("/", 1)[0]
                    for label, _ in self.units})

    @property
    def terminal(self) -> bool:
        """True once a terminal state landed (first-wins `finish`)."""
        return self._done.is_set()

    def verdict(self):
        """Merged validity over the request's units (checker.base rule:
        any INVALID → INVALID, else any non-VALID → UNKNOWN), folded
        with the admission-time transactional-anomaly overlay — a
        cross-key G0/G1c/G-single refutes the submission even when
        every per-key unit passes its rung."""
        if self.results is None:
            return None
        base = merge_valid(r.get("valid?") for r in self.results)
        if self.txn_anomalies is not None:
            return merge_valid([base,
                                self.txn_anomalies.get("valid?", True)])
        return base

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request reaches a terminal state."""
        return self._done.wait(timeout)

    def finish(self, status: str, results: Optional[List[dict]] = None,
               error: Optional[str] = None) -> bool:
        # FIRST terminal state wins (returns False on a late loser): a
        # hung batch the watchdog requeued executes at-least-once, and
        # whichever execution finishes first owns the client-visible
        # result — the stale twin's finish must not overwrite it
        # (at-most-once client-visible result, doc/checker-design.md
        # §11). Results/error land BEFORE the terminal status: a
        # concurrent reader polling `status` (the HTTP surface's
        # to_dict without wait_s) must never observe a terminal state
        # whose results are still missing.
        with self._finish_lock:
            if self._done.is_set():
                return False
            self.results = results
            self.error = error
            self.finished = time.monotonic()
            self.status = status
            self._done.set()
            return True

    def to_dict(self, include_results: bool = True) -> dict:
        d = {
            "id": self.id,
            "status": self.status,
            "workload": self.workload,
            "algorithm": self.algorithm,
            "consistency": self.consistency,
            "units": [label for label, _ in self.units],
            "fingerprint": self.fingerprint,
            "priority": self.priority,
            "cached": self.cached,
        }
        if self.error is not None:
            d["error"] = self.error
        if self.replayed:
            d["replayed"] = True
        if self.attached_to is not None:
            d["attached_to"] = self.attached_to
        if self.stats:
            d["service-stats"] = dict(self.stats)
        if self.txn_anomalies is not None:
            d["txn-anomalies"] = self.txn_anomalies
        if include_results and self.results is not None:
            d["valid?"] = self.verdict()
            d["results"] = self.results
            if getattr(self.model, "txn_graph", False):
                # a transaction workload's units were certified inside
                # their launch: the same summary the admission overlay
                # gives `list-append`, read off the results (so on both
                # wires, and on a cached or replayed answer)
                d["txn-anomalies"] = {
                    "valid?": d["valid?"],
                    "histories": [{"valid?": r.get("valid?"),
                                   "anomalies": r.get("anomalies", {}),
                                   "nodes": r.get("nodes", 0)}
                                  for r in self.results]}
        return d


def _workload_model(workload: str):
    """(model instance, split per key?) of a service workload, or the
    ValueError every admission path answers an unknown one with."""
    workloads = service_workloads()
    if workload not in workloads:
        raise ValueError(f"unknown workload {workload!r} "
                         f"(have: {', '.join(sorted(workloads))})")
    model_factory, independent = workloads[workload]
    return model_factory(), independent


def build_units(histories: Sequence, workload: str):
    """Normalize raw submission material into (model, units): the
    workload's model instance plus (label, History) pairs — one
    frontier-check unit each: `h{i}` for history i checked whole, or,
    for a workload that is split per key, `h{i}/key={key}` a key in
    `str(key)` order (so `key=10` before `key=2`). The OBJECT way to
    the units: an `Op` a row, `split_by_key` with an `Op.replace` a
    row (span `ingest.split`). `encode_units`, below, is what both the
    server-side `admit` and the binary lane's CLIENT-side encoder call
    (ISSUE 18: both sides must derive identical unit lists from
    identical histories, or the server-derived fingerprint would
    diverge from the JSON path's); it owns the one other way to the
    same units and encodings, from the rows' columns, and holds that
    way to this one."""
    model, independent = _workload_model(workload)
    units: List[tuple] = []
    for i, h in enumerate(histories):
        if not isinstance(h, History):
            h = history_from_dicts(h)
        h = h.client_ops()
        if independent:
            from ..checker.independent import split_by_key

            with span("ingest.split"):
                subs = split_by_key(h)
            for key, sub in sorted(subs.items(),
                                   key=lambda kv: str(kv[0])):
                units.append((f"h{i}/key={key}", sub))
        else:
            units.append((f"h{i}", h))
    if not units:
        raise ValueError("empty submission: no checkable history units")
    return model, units


def encode_units(histories: Sequence, workload: str):
    """(model, units, encs, from_columns): the unit decomposition and an
    `encode_history` a unit, for both wires (`admit` for a JSON body,
    `ServiceClient._submit_binary` for a frame) so that they cannot
    drift. It adapts on what it can observe in its input, no knob
    (ISSUE 39): histories that all arrive as lists of op DICTS, for a
    model with a columnar twin (`encode_pairs_columnar`), are encoded
    from the rows' columns — `_wire_columns` once a history, then the
    same `pair_ops_indexed`, the same twin and the same `encode_history`
    body reading `OpRow`s, with no `Op`, `History` walk or `OpPair` an
    event; the units are `WireHistory`s. A workload that is split per
    key goes the same way (ISSUE 47): the history's rows grouped by key
    with the key taken off the value (`_split_columns`, span
    `ingest.split`), each key's rows encoded as above, the units
    labelled `h{i}/key=…` in `build_units`' order. A transaction model
    (`list-append-txn`, ISSUE 51) goes the same way by its own twin:
    `ListAppendTxn.encode_columns` fills a unit's micro-op rows from the
    columns, `encode_ops` (the object path) is its oracle. Anything else
    (`History` objects, a model without the twin, a model whose
    admission builds the `History` objects anyway for its cross-key
    overlay (`list-append`), ``JGRAFT_ENCODE_VECTOR=0``) takes the
    object path as it stood, `build_units`, which is the differential
    oracle (tests/test_submit_columns.py, tests/
    test_split_from_columns.py): equal labels, equal `EncodedHistory`
    arrays, so one fingerprint and one frame, byte for byte; and the
    same errors, since every rule but those of `_wire_columns`,
    `rows_from_dicts` and `_split_columns` is the same code."""
    from ..models.base import Model

    model, independent = _workload_model(workload)
    txn = getattr(model, "txn_graph", False)
    from_columns = (
        bool(histories) and encode_vector_on()
        and (txn or type(model).encode_pairs_columnar
             is not Model.encode_pairs_columnar)
        and not (independent
                 and getattr(model, "txn_anomaly_capable", False))
        and all(isinstance(h, (list, tuple))
                and set(map(type, h)) <= {dict} for h in histories))
    if not from_columns:
        model, units = build_units(histories, workload)
        encs = [encode_history(h, model) for _, h in units]
        return model, units, encs, False
    # every history's columns first, and its split (a malformed row
    # anywhere is refused before any pairing error, as `build_units`
    # does); a history's rows then live only while it is encoded, so
    # the collector never walks a submission's worth of them
    # (label, the unit's dicts, its columns, one key's?)
    parts: List[tuple] = []
    for i, h in enumerate(histories):
        cols = _wire_columns(h)
        if not independent:
            parts.append((f"h{i}", h, cols, False))
            continue
        with span("ingest.split"):
            split = _split_columns(h, cols)
        parts.extend((f"h{i}/key={key}", dicts, kcols, True)
                     for key, dicts, kcols in sorted(
                         split, key=lambda part: str(part[0])))
    if not parts:
        raise ValueError("empty submission: no checkable history units")
    if txn:
        # a transaction unit: its micro-op rows filled from the columns
        encs = [model.encode_columns(_client_columns(cols))
                for _, _, cols, _ in parts]
    else:
        encs = [encode_history(_client_rows(cols), model)
                for _, _, cols, _ in parts]
    # a whole history's `WireHistory` reads its columns again when asked
    # (rarely); a key's keeps them, they are not `_wire_columns` of its
    # dicts
    units = [(label, WireHistory(dicts, cols if keyed else None))
             for label, dicts, cols, keyed in parts]
    return model, units, encs, True


def admit(histories: Sequence, workload: str, algorithm: str = "auto",
          deadline_ms: Optional[float] = None, priority: int = 0,
          default_deadline_s: float = 3600.0,
          request_id: Optional[str] = None,
          consistency: str = "linearizable") -> CheckRequest:
    """Normalize a submission into a CheckRequest (encode once +
    fingerprint). `histories` items are History objects or op-dict
    lists. Raises ValueError on unknown workloads / malformed ops /
    unknown consistency rungs — the HTTP surface maps that to 400,
    never into the queue."""
    from ..checker.consistency import normalize_consistency

    consistency = normalize_consistency(consistency)
    with span("ingest.decode"):
        model, units, encs, from_columns = encode_units(histories, workload)
    txn = None
    if getattr(model, "txn_anomaly_capable", False):
        # host-only (kernel=False inside): Tarjan + numpy closure on
        # the admission thread, never a device launch
        from ..checker.anomaly import certify_submission

        txn = certify_submission([
            (h if isinstance(h, History) else
             history_from_dicts(h)).client_ops()
            for h in histories])
    with span("ingest.fingerprint"):
        fingerprint = fingerprint_encodings(model, algorithm, encs,
                                            consistency)
    now = time.monotonic()  # admission timestamp (txn overlay above)
    deadline = now + (deadline_ms / 1000.0 if deadline_ms is not None
                      else default_deadline_s)
    return CheckRequest(
        id=request_id or uuid.uuid4().hex[:12],
        workload=workload,
        model=model,
        algorithm=algorithm,
        units=units,
        encs=encs,
        fingerprint=fingerprint,
        deadline=deadline,
        submitted=now,
        priority=clamp_priority(priority),
        consistency=consistency,
        txn_anomalies=txn,
        from_columns=from_columns,
    )


def admit_encoded(workload: str, labels: Sequence[str],
                  encs: Sequence[EncodedHistory],
                  algorithm: str = "auto",
                  deadline_ms: Optional[float] = None, priority: int = 0,
                  default_deadline_s: float = 3600.0,
                  consistency: str = "linearizable",
                  claimed_fingerprint: Optional[str] = None) -> CheckRequest:
    """Admit a CLIENT-encoded submission (the binary frame lane, ISSUE
    18): the per-unit encodings arrive already packed, so admission
    skips the encode entirely — but NEVER the fingerprint. The digest
    is re-derived here over the received tensor bytes, exactly the
    computation the JSON path runs on its own encode output, so a
    client lying about its payload (or its claimed fingerprint) can
    only corrupt its own verdict: every cache/store/WAL key is the
    server-derived value (doc/checker-design.md §20). A claimed
    fingerprint that disagrees is recorded in the request's stats
    (operators can alarm on it) and otherwise ignored.

    Like journal replay (`journal.decode_request`), the units carry
    empty History placeholders — raw ops stay client-side by design,
    so the trace record has no history.jsonl and counterexample
    minimization is skipped for frame submissions."""
    from ..checker.consistency import normalize_consistency

    consistency = normalize_consistency(consistency)
    model, _ = _workload_model(workload)
    if not encs:
        raise ValueError("empty submission: no checkable history units")
    if len(labels) != len(encs):
        raise ValueError(f"{len(labels)} labels for {len(encs)} "
                         "encodings")
    with span("ingest.fingerprint"):
        fingerprint = fingerprint_encodings(model, algorithm, encs,
                                            consistency)
    now = time.monotonic()
    deadline = now + (deadline_ms / 1000.0 if deadline_ms is not None
                      else default_deadline_s)
    req = CheckRequest(
        id=uuid.uuid4().hex[:12],
        workload=workload,
        model=model,
        algorithm=algorithm,
        units=[(str(label), History()) for label in labels],
        encs=list(encs),
        fingerprint=fingerprint,
        deadline=deadline,
        submitted=now,
        priority=clamp_priority(priority),
        consistency=consistency,
    )
    if claimed_fingerprint is not None \
            and claimed_fingerprint != fingerprint:
        # keyed on the server's digest regardless; the mismatch is
        # evidence, not an error (a 400 would let a prober distinguish
        # digests it does not hold the preimage of)
        req.stats["fingerprint_mismatch"] = True
    return req


def clamp_priority(priority) -> int:
    return max(-MAX_PRIORITY, min(MAX_PRIORITY, int(priority)))


def admit_run_dir(run_dir, algorithm: str = "auto",
                  deadline_ms: Optional[float] = None, priority: int = 0,
                  workload: Optional[str] = None,
                  default_deadline_s: float = 3600.0,
                  consistency: str = "linearizable") -> CheckRequest:
    """Admit a recorded-run directory (store/<name>/<ts>/): load the
    stored history, split per key exactly like `checker/recorded.py`,
    and check it as one request. The service's re-verification surface
    for artifacts a live run already produced."""
    from ..checker.consistency import normalize_consistency
    from ..checker.recorded import load_run_histories
    from ..models.base import Model

    consistency = normalize_consistency(consistency)
    with span("ingest.decode"):
        model, subs, wl = load_run_histories(run_dir, workload)
        if not isinstance(model, Model):
            raise ValueError(
                f"{run_dir}: workload {wl!r} uses a non-frontier "
                "checker; re-verify it with "
                "`python -m jepsen_jgroups_raft_tpu check`")
        units = [(f"{wl}/u{i}", h) for i, h in enumerate(subs)]
        encs = [encode_history(h, model) for _, h in units]
    with span("ingest.fingerprint"):
        fingerprint = fingerprint_encodings(model, algorithm, encs,
                                            consistency)
    now = time.monotonic()
    deadline = now + (deadline_ms / 1000.0 if deadline_ms is not None
                      else default_deadline_s)
    return CheckRequest(
        id=uuid.uuid4().hex[:12],
        workload=wl,
        model=model,
        algorithm=algorithm,
        units=units,
        encs=encs,
        fingerprint=fingerprint,
        deadline=deadline,
        submitted=now,
        priority=clamp_priority(priority),
        consistency=consistency,
    )
