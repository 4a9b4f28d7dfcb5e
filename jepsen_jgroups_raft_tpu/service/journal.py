"""Write-ahead admission journal: graftd's durability tier (ISSUE 8).

The admission queue is the daemon's only record of accepted work, and it
is in-memory: before this module, a SIGKILL between ``/submit``'s 202
and the verdict silently dropped a request a client was promised a
result for. The journal closes that window with the classic WAL
contract: the ENCODED submission (the same encode-once output the
result-cache fingerprint hashes — service/request.py) is appended and
fsync'd *before* admission returns, a terminal marker is appended when
the request finishes, and on daemon start every submit record without a
terminal marker replays into the admission queue in original deadline
order.

Design points, each load-bearing:

* **Records are JSON lines with a CRC.** Crash mid-append is the NORMAL
  case for a WAL, not an error: the tail of the file may hold a
  truncated line or a torn write. Replay skips corrupt/truncated
  records LOUDLY (logged + counted in ``replayed["skipped"]``) and
  keeps going — one torn tail record must never strand the intact
  entries before it.
* **Terminal records carry clean results.** A DONE marker with a
  verdict free of any ``platform-degraded`` stamp doubles as a
  persisted cache entry: recovery repopulates the fingerprint LRU, so a
  replayed duplicate (or a client's post-restart resubmit) short-
  circuits at admission instead of re-executing — the at-most-once half
  of the exactly-once-verdict argument (doc/checker-design.md §11).
* **Compaction is bounded by ``JGRAFT_SERVICE_RETAIN``.** The WAL of an
  always-on daemon would otherwise grow per request forever. Once the
  finished-pair count exceeds twice the retention bound, the journal
  rewrites itself keeping every UNFINISHED entry (those are the
  durability payload) plus the newest ``retain`` finished pairs (those
  are the warm-cache payload), via write-temp + ``os.replace`` so a
  crash mid-compaction leaves either the old or the new file, never
  neither.
* **A compaction copies byte ranges; it parses nothing** (ISSUE 49).
  The journal keeps an INDEX of what it wrote: one ``(offset, length,
  kind, id-or-sid)`` a record, in file order, appended where the bytes
  are written and seeded by ``replay()``, which scans and CRC-checks
  the file anyway. The lines are written canonical and would be
  rewritten canonical, so the kept ones are copied verbatim, in three
  steps: (a) under the lock, a snapshot of the index and of the file's
  length; (b) with NO lock, the kept ranges below that length from a
  read handle of its own into ``wal.jsonl.tmp``, fsync'd every
  ``COPY_SYNC_BYTES`` so that an appender's fsync never queues behind
  the copy's dirty pages, and after them the bytes appended during the
  copy, until a chunk or less is left; (c) under the lock, that rest
  copied verbatim, fsync, ``os.replace``, the index rebuilt with the
  new offsets. Appenders wait for (a) and (c) alone: milliseconds where
  the parsing rewrite held them for as long as it ran (15.6 s at
  1.14 GB). Past the threshold `append_terminal` and `append_stream`
  wake a thread of the journal's own; `compact()` runs the same steps
  on its caller's. A crash during (b) or (c) leaves
  the old file whole beside a ``.tmp`` nobody reads; after the replace,
  the new one whole. The CRC of a line is checked where it is
  load-bearing, at ``replay()``: a line that rots on disk after it was
  indexed is copied as it is and skipped loudly at the next start, as
  it would have been had no compaction met it. A journal whose index
  does not cover its file from byte 0 (opened on an existing WAL and
  not replayed, an append that failed half-way) compacts once by the
  parsing scan, under the lock, and has an index afterwards
  (``journal_compact_scans``).
* **Journal IO failures degrade durability, not availability.** An
  append that raises OSError is logged and counted
  (``journal_errors``); the request is still admitted. A checking
  daemon that refuses work because its disk hiccuped converts a storage
  fault into an outage — the chaos harness (scripts/chaos_graftd.py)
  injects exactly this and asserts the queue never wedges.

``JGRAFT_SERVICE_JOURNAL=0`` disables the tier entirely — byte-for-byte
today's in-memory daemon (the chaos harness's ablation arm).
"""

from __future__ import annotations

import base64
import json
import logging
import os
import threading
import time
import zlib
from collections import deque
from pathlib import Path
from typing import List, Optional

import numpy as np

from ..history.ops import History
from ..checker.schedule import span
from ..history.packing import EncodedHistory
from ..platform import env_int
from .request import DONE, CheckRequest

LOG = logging.getLogger("jgraft.service")

#: Journal schema version; replay refuses records from a NEWER version
#: loudly (skip + count) instead of misparsing them.
JOURNAL_VERSION = 1

#: Stream-record family version (ISSUE 12). Stream sessions put MANY
#: records under one session id (open / per-segment / fin) — a distinct
#: record family from the submit/terminal pairs, versioned separately so
#: the streaming wire format can evolve without bumping the whole WAL
#: schema: replay skips NEWER stream records loudly while still
#: replaying every request record, and a pre-PR-12 WAL (no stream
#: records at all) replays byte-for-byte as before (the forward-compat
#: fixture test in tests/test_stream.py pins both directions).
#: v2 (ISSUE 18): adds the ``stream-bseg`` kind — binary-lane segments
#: journal the client-settled ARRAYS instead of raw op dicts. An old
#: daemon skips v2 records loudly (fail-safe: a WAL holding binary
#: segments is not replayable by a daemon that cannot decode them).
STREAM_VERSION = 2

#: The stream record kinds (`kind` field values).
STREAM_KINDS = ("stream-open", "stream-seg", "stream-bseg", "stream-fin")

#: Appends timed for `/stats` `journal_append_p50_ms`.
APPEND_WINDOW = 4096

#: Default group-commit linger (ms). See `journal_group_ms`.
DEFAULT_GROUP_MS = 2

#: Bytes a compaction's copy reads and writes at a time.
COPY_CHUNK = 4 << 20

#: How often at most a compaction's step (b) goes back for what was
#: appended while it copied.
CATCH_UP_ROUNDS = 8

#: Bytes a compaction's copy writes between two fsyncs of its temp file:
#: an appender's fsync shares the device's queue with the copy's dirty
#: pages, so the copy never lets more than this many pile up.
COPY_SYNC_BYTES = 8 << 20


def journal_enabled() -> bool:
    """JGRAFT_SERVICE_JOURNAL gate (default on; 0 restores the
    in-memory-only daemon — defensively parsed like every env gate)."""
    return env_int("JGRAFT_SERVICE_JOURNAL", 1, minimum=0) != 0


def journal_group_ms() -> int:
    """Group-commit linger window in ms (ISSUE 15 tentpole (c)).

    With N concurrent appenders, per-append fsync serializes into a
    lock convoy (measured: solo fsync ~0.15 ms on this host, but
    `journal_append_p50_ms` 6.5 ms under 8 clients).
    Group commit coalesces: one appender becomes the LEADER, writes
    every queued record, and issues ONE fsync covering the whole
    group; each member's append returns only after THAT fsync — the
    §11 durability point (no 2xx before the fsync covering *your*
    record) is preserved exactly, because membership in the group is
    decided before the write and completion is signalled after the
    fsync returns. The linger (up to this window, waiting for riders)
    is adaptive — it engages only while recent groups actually carried
    riders, so an uncontended appender pays no added latency
    (`_append_grouped`).

    ``JGRAFT_JOURNAL_GROUP_MS=0`` restores today's exact per-append
    write+fsync behavior (the same-process A/B arm). Resolved per
    append."""
    return env_int("JGRAFT_JOURNAL_GROUP_MS", DEFAULT_GROUP_MS,
                   minimum=0)


def _b64(arr: np.ndarray) -> str:
    return base64.b64encode(
        np.ascontiguousarray(arr, dtype=np.int32).tobytes()).decode("ascii")


def _unb64(s: str, shape) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s.encode("ascii")),
                         dtype=np.int32).reshape(shape).copy()


def _crc_line(rec: dict) -> str:
    """Canonical CRC32 over the record minus its own crc field."""
    body = {k: v for k, v in rec.items() if k != "crc"}
    canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return format(zlib.crc32(canon.encode()), "08x")


def _tag(rec: dict) -> tuple:
    """``(kind, key)`` of a record, all the compaction's keep rule reads
    of it: the session id for the stream family, the request id for
    every other kind."""
    kind = rec.get("kind")
    return kind, (str(rec.get("sid")) if kind in STREAM_KINDS
                  else rec.get("id"))


def plan_compaction(tags, retain: int):
    """The keep rule, over one ``(kind, key)`` a record in file order.
    Returns ``(keep, finished)``: the positions a compaction writes, in
    the order it writes them, and the finished pairs and sessions among
    them (what `_finished_since_compact` restarts from).

    First, in file order: every submit without a terminal marker, every
    record of a stream session without a fin, and the open + fin of the
    newest `retain` finished sessions (a finished session's segments are
    dead weight once its verdict exists). Then the newest `retain`
    finished ``(submit, terminal)`` pairs. Terminals without a kept
    submit, and kinds this version does not know, are dropped."""
    terminals = {}
    fins = {}
    for i, (kind, key) in enumerate(tags):
        if kind == "terminal":
            terminals[key] = i
        elif kind == "stream-fin":
            fins[key] = i
    # finished sessions, oldest first (their first fin's file order)
    fin_order = list(fins)
    drop_fins = set(fin_order[:-retain] if retain else fin_order)
    keep: List[int] = []
    pairs = []
    for i, (kind, key) in enumerate(tags):
        if kind in STREAM_KINDS:
            if key not in fins:
                keep.append(i)          # unfinished: keep whole
            elif (kind not in ("stream-seg", "stream-bseg")
                  and key not in drop_fins):
                keep.append(i)          # finished: open+fin only
        elif kind == "submit":
            term = terminals.get(key)
            if term is None:
                keep.append(i)
            else:
                pairs.append((i, term))
    for pair in pairs[-retain:]:
        keep.extend(pair)
    return keep, min(len(pairs), retain) + len(fins) - len(drop_fins)


def _runs(entries):
    """``(offset, length)`` of each maximal run of index entries that
    are neighbours in the file as they are in `entries`: what the copy
    reads in one piece."""
    runs: List[list] = []
    for off, n, _kind, _key in entries:
        if runs and runs[-1][0] + runs[-1][1] == off:
            runs[-1][1] += n
        else:
            runs.append([off, n])
    return runs


def encode_submit(req: CheckRequest) -> dict:
    """Submit record: everything replay needs to rebuild the request —
    the per-unit ENCODINGS (authoritative checker input; the raw op
    dicts are deliberately not journaled, so a replayed request's trace
    record has an empty history.jsonl), scheduling metadata converted
    to WALL time (monotonic clocks do not survive a restart), and the
    fingerprint (idempotency key)."""
    now_mono, now_wall = time.monotonic(), time.time()
    return {
        "kind": "submit",
        "v": JOURNAL_VERSION,
        "id": req.id,
        "workload": req.workload,
        "model": type(req.model).__name__,
        "algorithm": req.algorithm,
        "consistency": req.consistency,
        "fingerprint": req.fingerprint,
        "priority": req.priority,
        "deadline_wall": now_wall + (req.deadline - now_mono),
        "submitted_wall": now_wall - (now_mono - req.submitted),
        "units": [{
            "label": label,
            "n_slots": enc.n_slots,
            "n_ops": enc.n_ops,
            "events_shape": list(enc.events.shape),
            "events": _b64(enc.events),
            "op_index": _b64(enc.op_index),
            # proc rides along when present: the weaker-consistency
            # rungs relax along per-process order, and a replayed
            # request must reach the same relaxed stream (a missing
            # proc degrades the rung to the conservative identity
            # relaxation — sound, but stricter than promised).
            **({"proc": _b64(enc.proc)} if enc.proc is not None else {}),
        } for (label, _), enc in zip(req.units, req.encs)],
    }


def encode_terminal(req: CheckRequest) -> dict:
    """Terminal marker. Results ride along only for a clean DONE (the
    same never-persist-degraded rule the LRU cache applies): a degraded
    stamp describes the run that produced it, not a future replay."""
    rec = {
        "kind": "terminal",
        "v": JOURNAL_VERSION,
        "id": req.id,
        "fingerprint": req.fingerprint,
        "status": req.status,
    }
    if req.error is not None:
        rec["error"] = str(req.error)[:500]
    if req.status == DONE and req.results is not None and not any(
            "platform-degraded" in r for r in req.results):
        from ..core.store import _jsonable

        rec["results"] = _jsonable(req.results)
    return rec


def decode_request(rec: dict) -> CheckRequest:
    """Rebuild a CheckRequest from a submit record. Wall-clock deadline
    and submit time are mapped back onto THIS process's monotonic clock,
    preserving both the original deadline ORDER across replayed entries
    and the aging credit already accrued before the crash."""
    from .. import models as _models

    model_cls = getattr(_models, rec["model"], None)
    if model_cls is None:
        raise ValueError(f"journal record {rec['id']}: unknown model "
                         f"{rec['model']!r}")
    now_mono, now_wall = time.monotonic(), time.time()
    units, encs = [], []
    for u in rec["units"]:
        events = _unb64(u["events"], u["events_shape"])
        op_index = _unb64(u["op_index"], (u["events_shape"][0],))
        proc = (_unb64(u["proc"], (u["events_shape"][0],))
                if u.get("proc") is not None else None)
        units.append((u["label"], History()))
        encs.append(EncodedHistory(events=events, op_index=op_index,
                                   n_slots=int(u["n_slots"]),
                                   n_ops=int(u["n_ops"]), proc=proc))
    return CheckRequest(
        id=rec["id"],
        workload=rec["workload"],
        model=model_cls(),
        algorithm=rec["algorithm"],
        consistency=rec.get("consistency", "linearizable"),
        units=units,
        encs=encs,
        fingerprint=rec["fingerprint"],
        deadline=now_mono + (float(rec["deadline_wall"]) - now_wall),
        submitted=now_mono - max(0.0, now_wall
                                 - float(rec["submitted_wall"])),
        priority=int(rec["priority"]),
        replayed=True,
    )


def encode_stream_open(sid: str, workload: str, model_name: str,
                       algorithm: str, consistency: str,
                       n_units: int) -> dict:
    """Stream session-open record (ISSUE 12)."""
    return {
        "kind": "stream-open",
        "v": JOURNAL_VERSION,
        "stream_v": STREAM_VERSION,
        "sid": sid,
        "workload": workload,
        "model": model_name,
        "algorithm": algorithm,
        "consistency": consistency,
        "units": int(n_units),
        "opened_wall": time.time(),
    }


def encode_stream_segment(sid: str, seq: int, unit_ops, digest: str) -> dict:
    """One appended segment: the RAW op dict rows per unit (replay
    re-feeds them through the same incremental encoder the live path
    used, so the rebuilt carry is deterministic), plus the payload
    digest duplicate-detection keys on."""
    return {
        "kind": "stream-seg",
        "v": JOURNAL_VERSION,
        "stream_v": STREAM_VERSION,
        "sid": sid,
        "seq": int(seq),
        "digest": digest,
        "ops": unit_ops,
    }


def encode_stream_bseg(sid: str, seq: int, units, digest: str) -> dict:
    """One binary-lane segment (ISSUE 18): the client-settled suffix
    ARRAYS per unit plus the client encoder's cumulative counters —
    there are no raw op dicts to journal on this lane, and replay feeds
    the arrays straight back (`StreamSession.append_binary`) instead of
    re-encoding. Unit dicts are the `frame.SegmentFrame` payload shape:
    ``{"events", "op_index", "proc" (array|None), "n_slots", "n_ops",
    "consumed", "final"}``."""
    return {
        "kind": "stream-bseg",
        "v": JOURNAL_VERSION,
        "stream_v": STREAM_VERSION,
        "sid": sid,
        "seq": int(seq),
        "digest": digest,
        "units": [{
            "n_events": int(np.asarray(u["events"]).reshape(-1, 5).shape[0]),
            "n_slots": int(u["n_slots"]),
            "n_ops": int(u["n_ops"]),
            "consumed": int(u["consumed"]),
            "final": bool(u.get("final", False)),
            "events": _b64(np.asarray(u["events"]).reshape(-1, 5)),
            "op_index": _b64(u["op_index"]),
            **({"proc": _b64(u["proc"])}
               if u.get("proc") is not None else {}),
        } for u in units],
    }


def decode_stream_bseg_units(rec: dict) -> List[dict]:
    """Rebuild a ``stream-bseg`` record's per-unit payload dicts (the
    same shape `append_binary` consumes live). Malformed payloads raise
    ValueError/KeyError — the caller (session rebuild) skips loudly."""
    out: List[dict] = []
    for u in rec["units"]:
        n = int(u["n_events"])
        out.append({
            "events": _unb64(u["events"], (n, 5)),
            "op_index": _unb64(u["op_index"], (n,)),
            "proc": (_unb64(u["proc"], (n,))
                     if u.get("proc") is not None else None),
            "n_slots": int(u["n_slots"]),
            "n_ops": int(u["n_ops"]),
            "consumed": int(u["consumed"]),
            "final": bool(u.get("final", False)),
        })
    return out


def encode_stream_fin(sid: str, status: str, results=None,
                      error=None) -> dict:
    """Terminal marker for a stream session. Results ride along for a
    clean finish (same never-persist-degraded rule as request
    terminals) so `/stream/status` answers across a restart without a
    rebuild."""
    rec = {
        "kind": "stream-fin",
        "v": JOURNAL_VERSION,
        "stream_v": STREAM_VERSION,
        "sid": sid,
        "status": status,
    }
    if error is not None:
        rec["error"] = str(error)[:500]
    if results is not None and not any(
            isinstance(r, dict) and "platform-degraded" in r
            for r in results):
        from ..core.store import _jsonable

        rec["results"] = _jsonable(results)
    return rec


class AdmissionJournal:
    """Append-only WAL at ``<root>/wal.jsonl`` (root is
    ``store/<service>/journal/`` in the daemon's layout)."""

    def __init__(self, root, retain: Optional[int] = None):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.path = self.root / "wal.jsonl"
        self.retain = (retain if retain is not None
                       else env_int("JGRAFT_SERVICE_RETAIN", 1024,
                                    minimum=1))
        self._lock = threading.Lock()
        self._fh = None  # guarded_by(_lock)
        self._errors = 0  # guarded_by(_lock)
        self._appends = 0  # guarded_by(_lock)
        # group commit (ISSUE 15): pending entries + leader election.
        # _gcond guards _gqueue/_gleader; the IO itself runs under
        # _lock like every other write, so compaction/stats never
        # interleave with a group's write+fsync.
        self._gcond = threading.Condition(threading.Lock())
        # [line, done, ok, kind, key] per entry
        self._gqueue: List[list] = []  # guarded_by(_gcond)
        self._gleader = False  # guarded_by(_gcond)
        self._glast_multi = False   # previous group carried riders?
        self._group_commits = 0
        self._group_records = 0
        # Seeded lazily by replay() (which scans the file anyway — a
        # dedicated counting scan at open would read and CRC-check the
        # whole WAL a second time for nothing); a journal used without
        # a replay just starts the compaction amortization from zero.
        self._finished_since_compact = 0
        # What the file holds, for the compaction to copy from: one
        # (offset, length, kind, key) a record in file order, written
        # where the bytes are written (`_note_written`) and seeded by
        # replay(). None where it does not cover the file from byte 0;
        # _indexed_to is the length of the file it covers.
        self._index: Optional[List[tuple]] = (  # guarded_by(_lock)
            None if self.path.exists() and self.path.stat().st_size
            else [])
        self._indexed_to = 0  # guarded_by(_lock)
        # one compaction at a time: the journal's thread, a caller of
        # compact(), and replay() seeding the index; taken before _lock
        self._compact_mutex = threading.Lock()
        self._compact_pending = False  # guarded_by(_lock)
        self._compactions = 0  # guarded_by(_lock)
        self._compact_scans = 0  # guarded_by(_lock)
        self._compact_s = 0.0  # guarded_by(_lock)
        self._compact_hold_max = 0.0  # guarded_by(_lock)
        self._compact_bytes = 0  # guarded_by(_lock)
        #: test seam: called between a compaction's steps (b) and (c),
        #: with no lock of the journal's held but _compact_mutex
        self._after_copy = None
        self.append_ms: deque = deque(maxlen=APPEND_WINDOW)

    # ------------------------------------------------------------ write

    def _handle(self):  # requires(_lock)
        if self._fh is None or self._fh.closed:
            self._fh = open(self.path, "ab")
            size = self._fh.tell()
            if size and self._tail_is_torn(size):
                # A crash mid-append left a line without its newline:
                # end it, so that it costs its own record and not the
                # next one appended as well. The append this handle was
                # opened for brings the fsync that covers the byte.
                self._fh.write(b"\n")  # lint: allow(fsync)
                if self._indexed_to == size:
                    self._indexed_to += 1
        return self._fh

    def _close_handle(self) -> None:  # requires(_lock)
        if self._fh is not None and not self._fh.closed:
            self._fh.close()

    def _tail_is_torn(self, size: int) -> bool:
        with open(self.path, "rb") as fh:
            fh.seek(size - 1)
            return fh.read(1) != b"\n"

    def _note_written(self, at: int, lines) -> None:  # requires(_lock)
        """Index the records just written at offset `at`, one ``(length,
        kind, key)`` each. Bytes the index did not see before them (a
        handle opened on a WAL nobody replayed, another writer) leave
        the journal without an index until a compaction scans."""
        if self._index is None:
            return
        if at != self._indexed_to:
            self._index = None
            return
        for n, kind, key in lines:
            self._index.append((at, n, kind, key))
            at += n
        self._indexed_to = at

    def _append(self, build, fsync: bool,
                name: str = "journal.mark") -> bool:
        """Append a record, or the record `build()` returns. One span
        covers the whole of it (building and serialising the record, the write,
        the wait for the commit that covers it): `journal.append` for a
        submit record, on the handler thread inside the
        acknowledgement, `journal.mark` for every other kind (terminal
        markers, on the dispatcher thread; stream records). Its seconds
        are the `append_ms` sample too."""
        with span(name) as sp:
            rec = build() if callable(build) else build
            rec["crc"] = _crc_line(rec)
            line = (json.dumps(rec, sort_keys=True,
                               separators=(",", ":")) + "\n").encode()
            group = journal_group_ms() if fsync else 0
            if group > 0:
                ok = self._append_grouped(line, group, _tag(rec))
            else:
                ok = self._append_alone(line, rec, fsync)
        # under the lock: stats() iterates append_ms while holding it
        # (a bare deque.append is atomic, but sorted() mid-mutation is
        # not)
        with self._lock:
            self.append_ms.append(sp.s * 1000.0)
        return ok

    def _append_alone(self, line: bytes, rec: dict, fsync: bool) -> bool:
        try:
            with self._lock:
                fh = self._handle()
                at = fh.tell()
                fh.write(line)
                fh.flush()
                if fsync:
                    with span("journal.fsync"):
                        os.fsync(fh.fileno())
                self._appends += 1
                self._note_written(at, [(len(line),) + _tag(rec)])
        except OSError:
            # Durability degraded, availability kept: the daemon counts
            # and logs, the request is still served (module docstring).
            # What reached the file is unknown: no index until a scan.
            with self._lock:
                self._errors += 1
                self._index = None
            LOG.warning("journal append failed for %s record %s",
                        rec.get("kind"), rec.get("id"), exc_info=True)
            return False
        return True

    def _append_grouped(self, line: bytes, group_ms: int,
                        tag: tuple) -> bool:
        """Leader/follower group commit (`journal_group_ms`). The
        caller's entry joins the pending queue; the first appender with
        no leader in flight LEADS: it drains the queue, writes every
        line, and issues ONE fsync for the whole group. Every member
        (leader included) returns only after the fsync that covers ITS
        line — the §11 durability point, unchanged. A failed group
        write degrades durability for all members (counted per record,
        availability kept) exactly like the per-append path.

        The linger is ADAPTIVE: a solo leader sleeps up to ``group_ms``
        for riders only when the PREVIOUS group carried some (an
        in-flight-contention signal); an uncontended appender commits
        immediately, so solo-append latency is identical to the
        per-append path. Under real concurrency no sleep is needed at
        all — followers pile into the queue during the current group's
        write+fsync and the next leader finds them already waiting."""
        entry = [line, False, False, *tag]   # line, done, ok, kind, key
        with self._gcond:
            self._gqueue.append(entry)
            while not entry[1] and self._gleader:
                self._gcond.wait(0.05)
            lead = not entry[1]
            if lead:
                self._gleader = True
                linger = (len(self._gqueue) == 1 and self._glast_multi)
        if lead:
            batch: List[list] = []
            ok = False
            try:
                if group_ms and linger:
                    time.sleep(group_ms / 1000.0)   # linger for riders
                with self._gcond:
                    batch = self._gqueue
                    self._gqueue = []
                try:
                    with self._lock:
                        fh = self._handle()
                        at = fh.tell()
                        fh.write(b"".join(e[0] for e in batch))
                        fh.flush()
                        with span("journal.fsync"):
                            os.fsync(fh.fileno())
                        self._appends += len(batch)
                        self._group_commits += 1
                        self._group_records += len(batch)
                        self._note_written(
                            at, [(len(e[0]), e[3], e[4]) for e in batch])
                    ok = True
                except OSError:
                    with self._lock:
                        self._errors += len(batch)
                        self._index = None
                    LOG.warning("journal group append failed "
                                "(%d records)", len(batch),
                                exc_info=True)
            finally:
                with self._gcond:
                    for e in batch:
                        e[2] = ok
                        e[1] = True
                    self._gleader = False
                    self._glast_multi = len(batch) > 1
                    self._gcond.notify_all()
        return entry[2]

    def append_submit(self, req: CheckRequest) -> bool:
        """Durability point: returns only after the record is fsync'd
        (or after the failure was counted). Must be called BEFORE the
        202 is visible to the client."""
        return self._append(lambda: encode_submit(req), fsync=True,
                            name="journal.append")

    def append_terminal(self, req: CheckRequest) -> bool:
        """Mark a journaled request finished. fsync'd too — a lost
        terminal marker is only re-execution on replay (idempotent),
        but a persisted one is a warm cache entry worth the write."""
        ok = self._append(lambda: encode_terminal(req), fsync=True)
        self._finished_one()
        return ok

    def append_stream(self, rec: dict) -> bool:
        """Append one stream-family record (open/segment/fin), fsync'd —
        the append path's 2xx must not become visible before the segment
        is durable (ISSUE 12). Same degrade-not-refuse stance as every
        other append."""
        ok = self._append(rec, fsync=True)
        if rec.get("kind") == "stream-fin":
            self._finished_one()
        return ok

    def _finished_one(self) -> None:
        """Count a finished pair or session. Amortized: once the WAL
        holds ~2x the retention bound of them, wake the compaction
        thread (each compaction trims back to `retain`, so the file
        oscillates between retain and 2·retain pairs instead of being
        rewritten per append). The caller's thread (the dispatcher's,
        in `_retire`) never compacts; a trigger that finds a compaction
        pending is dropped, and the count keeps running."""
        with self._lock:
            self._finished_since_compact += 1
            wake = (self._finished_since_compact > 2 * self.retain
                    and not self._compact_pending)
            if wake:
                self._compact_pending = True
        if wake:
            threading.Thread(target=self._compact_in_background,
                             name="journal-compact", daemon=True).start()

    def _compact_in_background(self) -> None:
        try:
            self.compact()
        finally:
            with self._lock:
                self._compact_pending = False

    # ----------------------------------------------------------- replay

    def _scan(self):
        """(records, skipped, index, size): parsed records in file
        order, and for each its index entry (`_index`'s form), of a file
        of `size` bytes; corrupt or truncated lines are skipped LOUDLY
        and get no entry — a torn tail is the normal crash signature,
        and it must cost one record, not the file. `index` is None
        where a last line without its newline parsed (a crash between
        the two): its range is not a whole line yet."""
        records: List[dict] = []
        index: Optional[List[tuple]] = []
        skipped = 0
        try:
            raw = self.path.read_bytes()
        except FileNotFoundError:
            return records, skipped, index, 0
        at = 0
        for ln, line in enumerate(raw.split(b"\n"), 1):
            off, at = at, at + len(line) + 1
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
                if not isinstance(rec, dict):
                    raise ValueError("journal line is not an object")
                if int(rec.get("v", -1)) > JOURNAL_VERSION:
                    raise ValueError(
                        f"record version {rec.get('v')} is newer than "
                        f"this daemon ({JOURNAL_VERSION})")
                if rec.get("crc") != _crc_line(rec):
                    raise ValueError("crc mismatch (torn write)")
            except (ValueError, json.JSONDecodeError) as e:
                skipped += 1
                LOG.warning("journal %s line %d skipped: %s",
                            self.path, ln, e)
                continue
            records.append(rec)
            if at > len(raw):
                index = None
            elif index is not None:
                index.append((off, at - off) + _tag(rec))
        return records, skipped, index, len(raw)

    def replay(self) -> dict:
        """Join submits with their terminal markers. Returns::

            {"unfinished": [CheckRequest…]   # deadline order
             "finished":   [(submit_rec, terminal_rec)…],
             "streams":    {sid: {"open": rec, "segments": [rec…],
                                  "fin": rec | None}},
             "skipped":    int}              # corrupt/truncated lines

        Submit records that fail to DECODE (unknown model, mangled
        tensor payload) are skipped loudly like torn lines — replay
        must deliver every intact entry even when one is poison.
        Stream records (ISSUE 12) are their OWN record family — many
        records per session id, versioned by ``stream_v`` — grouped
        per session; a record from a NEWER stream version is skipped
        loudly without touching the request replay, and a WAL with no
        stream records (pre-PR-12) replays exactly as before."""
        with self._compact_mutex:
            records, skipped, index, size = self._scan()
            with self._lock:
                # replay doubles as the scan that seeds the compaction's
                # index; an append that lands before the next one shows
                # as bytes the index did not see (`_note_written`)
                self._index, self._indexed_to = index, size
        submits = {}
        terminals = {}
        streams: dict = {}
        for rec in records:
            kind = rec.get("kind")
            if kind == "submit":
                submits[rec["id"]] = rec
            elif kind == "terminal":
                terminals[rec["id"]] = rec
            elif kind in STREAM_KINDS:
                try:
                    if int(rec.get("stream_v", -1)) > STREAM_VERSION:
                        raise ValueError(
                            f"stream record version {rec.get('stream_v')} "
                            f"is newer than this daemon ({STREAM_VERSION})")
                    sid = str(rec["sid"])
                except (ValueError, KeyError, TypeError) as e:
                    skipped += 1
                    LOG.warning("journal stream record skipped: %s", e)
                    continue
                s = streams.setdefault(
                    sid, {"open": None, "segments": [], "fin": None})
                if kind == "stream-open":
                    s["open"] = rec
                elif kind == "stream-fin":
                    s["fin"] = rec
                else:
                    s["segments"].append(rec)
        # Orphaned segments (their open record was corrupt/compacted
        # away) cannot rebuild a session: skipped loudly, not silently.
        for sid in [k for k, s in streams.items() if s["open"] is None]:
            skipped += len(streams[sid]["segments"])
            LOG.warning("journal stream %s has segments but no open "
                        "record; session dropped", sid)
            del streams[sid]
        for s in streams.values():
            # duplicate seqs are first-wins (a retried append whose 2xx
            # was lost journals twice; the payloads are digest-equal)
            seen: dict = {}
            for rec in s["segments"]:
                seen.setdefault(int(rec.get("seq", -1)), rec)
            s["segments"] = [seen[k] for k in sorted(seen)]
        unfinished: List[CheckRequest] = []
        finished = []
        for rid, rec in submits.items():
            if rid in terminals:
                finished.append((rec, terminals[rid]))
                continue
            try:
                unfinished.append(decode_request(rec))
            except (ValueError, KeyError, TypeError) as e:
                skipped += 1
                LOG.warning("journal entry %s undecodable, skipped: %s",
                            rid, e)
        unfinished.sort(key=lambda r: (r.deadline, r.submitted))
        with self._lock:
            # replay doubles as the finished-pair census that seeds the
            # compaction trigger (no separate counting scan at open)
            self._finished_since_compact = len(finished) + sum(
                1 for s in streams.values() if s["fin"] is not None)
        return {"unfinished": unfinished, "finished": finished,
                "streams": streams, "skipped": skipped}

    def stream_records(self, sid: str) -> Optional[dict]:
        """Re-scan the WAL for ONE session's stream records (the revive
        path of a parked/restored session — parking drops the records
        from memory on purpose; a revive pays one file scan, and never
        the tensor decode `replay()` does for request records). Returns
        the same per-session dict `replay()["streams"]` holds, or None
        when the session has no (intact) open record."""
        sid = str(sid)
        records = self._scan()[0]
        out = {"open": None, "segments": [], "fin": None}
        seen: dict = {}
        for rec in records:
            kind = rec.get("kind")
            if kind not in STREAM_KINDS or str(rec.get("sid")) != sid:
                continue
            try:
                if int(rec.get("stream_v", -1)) > STREAM_VERSION:
                    continue  # replay() already logged these
            except (ValueError, TypeError):
                continue
            if kind == "stream-open":
                out["open"] = rec
            elif kind == "stream-fin":
                out["fin"] = rec
            else:
                seen.setdefault(int(rec.get("seq", -1)), rec)
        out["segments"] = [seen[k] for k in sorted(seen)]
        return out if out["open"] is not None else None

    # ------------------------------------------------------- compaction

    def compact(self) -> None:
        """Rewrite the WAL: every unfinished entry survives, only the
        newest `retain` finished pairs do (`plan_compaction` has the
        rule, the stream family's half included). Atomic via
        temp+replace — a crash mid-compaction leaves a valid journal
        either way (the temp file of one that failed or died is
        overwritten by the next and never read by replay()). Synchronous,
        on the caller's thread; one that finds another compaction
        running waits for it first."""
        with self._compact_mutex:
            with span("journal.compact") as sp:
                try:
                    copied = self._compact_indexed()
                    if copied is None:
                        copied = self._compact_scanned()
                except OSError:
                    copied = None
                    LOG.warning("journal compaction failed; keeping the "
                                "uncompacted WAL", exc_info=True)
            with self._lock:
                if copied is None:
                    self._errors += 1
                else:
                    self._compactions += 1
                    self._compact_s += sp.s
                    self._compact_bytes += copied

    def _note_hold(self, seconds: float) -> None:  # requires(_lock)
        self._compact_hold_max = max(self._compact_hold_max, seconds)

    def _copy_ranges(self, src, tmp_fh, ranges) -> int:
        """Copy the byte `ranges` of the file `src` to `tmp_fh`,
        `COPY_CHUNK` at a time, fsync'd every `COPY_SYNC_BYTES` and at
        the end. Returns the bytes copied."""
        copied = unsynced = 0
        src = src.fileno()
        for off, n in ranges:
            while n:
                buf = os.pread(src, min(n, COPY_CHUNK), off)
                if not buf:
                    raise OSError("journal shorter than its index says")
                tmp_fh.write(buf)
                off += len(buf)
                n -= len(buf)
                copied += len(buf)
                unsynced += len(buf)
                if unsynced >= COPY_SYNC_BYTES:
                    tmp_fh.flush()
                    os.fsync(tmp_fh.fileno())
                    unsynced = 0
        tmp_fh.flush()
        os.fsync(tmp_fh.fileno())
        return copied

    def _compact_indexed(self) -> Optional[int]:  # requires(_compact_mutex)
        """The compaction from the index (module docstring): bytes
        copied, or None where the index does not cover the file. No
        record is parsed, CRC'd or re-encoded."""
        with self._lock:
            with span("journal.compact_hold") as hold:   # step (a)
                try:
                    covered = (self._index is not None and self._indexed_to
                               == os.path.getsize(self.path))
                except FileNotFoundError:
                    covered = False
                if covered:
                    index = list(self._index)
                    end0 = self._indexed_to
                    finished0 = self._finished_since_compact
            self._note_hold(hold.s)
        if not covered:
            return None
        keep, finished = plan_compaction([e[2:] for e in index],
                                         self.retain)
        kept = [index[i] for i in keep]
        tmp = self.path.with_suffix(".jsonl.tmp")
        with open(self.path, "rb") as src, open(tmp, "wb") as tmp_fh:
            head = self._copy_ranges(src, tmp_fh, _runs(kept))   # (b)
            # ... and what was appended during the copy, for as long as
            # it is more than a chunk (the copy outruns the appenders;
            # the rounds are bounded for the day it does not): bytes,
            # whole records or not, so that step (c) finds a few records
            tail = 0
            for _ in range(CATCH_UP_ROUNDS):
                more = os.fstat(src.fileno()).st_size - end0 - tail
                if more <= COPY_CHUNK:
                    break
                tail += self._copy_ranges(src, tmp_fh,
                                          [(end0 + tail, more)])
            if self._after_copy is not None:
                self._after_copy()
            with self._lock:
                with span("journal.compact_hold") as hold:   # step (c)
                    end1 = os.fstat(src.fileno()).st_size
                    tail += self._copy_ranges(
                        src, tmp_fh, [(end0 + tail, end1 - end0 - tail)])
                    tmp_fh.close()
                    self._close_handle()
                    os.replace(tmp, self.path)
                    if self._index is not None \
                            and self._indexed_to == end1:
                        at, moved = 0, []
                        for _off, n, kind, key in kept:
                            moved.append((at, n, kind, key))
                            at += n
                        moved.extend(
                            (off - end0 + head, n, kind, key)
                            for off, n, kind, key
                            in self._index[len(index):])
                        self._index = moved
                        self._indexed_to = head + tail
                    else:
                        self._index = None
                    # what finished during (b) is kept either way
                    self._finished_since_compact += finished - finished0
                self._note_hold(hold.s)
        return head + tail

    def _compact_scanned(self) -> int:  # requires(_compact_mutex)
        """The compaction of a journal without an index: parse and CRC
        every line, write the kept records re-encoded, all of it under
        the lock. Leaves an index behind. Returns the bytes written."""
        with self._lock:
            with span("journal.compact_hold") as hold:
                records = self._scan()[0]
                tags = [_tag(rec) for rec in records]
                keep, finished = plan_compaction(tags, self.retain)
                tmp = self.path.with_suffix(".jsonl.tmp")
                index, at = [], 0
                with open(tmp, "wb") as fh:
                    for i in keep:
                        line = (json.dumps(
                            records[i], sort_keys=True,
                            separators=(",", ":")) + "\n").encode()
                        fh.write(line)
                        index.append((at, len(line)) + tags[i])
                        at += len(line)
                    fh.flush()
                    os.fsync(fh.fileno())
                self._close_handle()
                os.replace(tmp, self.path)
                self._index, self._indexed_to = index, at
                self._finished_since_compact = finished
                self._compact_scans += 1
            self._note_hold(hold.s)
        return at

    # ------------------------------------------------------------ stats

    def stats(self) -> dict:
        with self._lock:
            samples = sorted(self.append_ms)
            out = {
                "journal_appends": self._appends,
                "journal_errors": self._errors,
                # group-commit evidence (ISSUE 15): how many fsyncs the
                # WAL actually issued and how many records each covered
                "journal_group_ms": journal_group_ms(),
                "journal_group_commits": self._group_commits,
                "journal_group_occupancy_mean": round(
                    self._group_records / self._group_commits, 3)
                if self._group_commits else 0.0,
                # compactions (ISSUE 49): how many, their wall seconds
                # and bytes, the longest single hold of the lock by one,
                # and how many had to scan for want of an index
                "journal_compactions": self._compactions,
                "journal_compact_s": round(self._compact_s, 4),
                "journal_compact_hold_ms_max": round(
                    self._compact_hold_max * 1000.0, 3),
                "journal_compact_bytes": self._compact_bytes,
                "journal_compact_scans": self._compact_scans,
            }
        if samples:
            out["journal_append_p50_ms"] = round(
                samples[len(samples) // 2], 4)
        return out

    def close(self) -> None:
        """Close the handle, after a compaction that is running has
        finished (one the process dies under leaves the old file whole
        and a temp file nobody reads)."""
        with self._compact_mutex:
            with self._lock:
                self._close_handle()
