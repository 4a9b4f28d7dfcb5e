"""graftd durability tier (ISSUE 8): write-ahead admission journal,
crash recovery, idempotent resubmission, poison-batch quarantine,
hung-batch watchdog, and the client's retry/backoff discipline.

Tier-1 except the real-SIGKILL subprocess case (marked slow; the fast
in-process variant below simulates the kill by dropping a daemon whose
worker never ran — the journal sees exactly what a SIGKILL leaves on
disk, minus the torn tail, which has its own unit tests). Invariants
mirror the chaos harness (scripts/chaos_graftd.py): nothing accepted is
lost, recovered verdicts equal direct `check_histories`, resubmission
executes at most once, and queues never wedge.
"""

from __future__ import annotations

import os
import random
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from jepsen_jgroups_raft_tpu.checker.linearizable import (check_encoded,
                                                          check_histories)
from jepsen_jgroups_raft_tpu.checker.schedule import open_span, snapshot_spans
from jepsen_jgroups_raft_tpu.models import CasRegister
from jepsen_jgroups_raft_tpu.service import (CheckingService, ServiceClient,
                                             ServiceError, journal,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.client import backoff_delay
from jepsen_jgroups_raft_tpu.service.journal import (AdmissionJournal,
                                                     decode_request,
                                                     encode_submit)
from jepsen_jgroups_raft_tpu.service.request import admit

from util import H, free_port, random_valid_history

WAIT_S = 120.0  # bound, not a sleep (first XLA compile dominates)
REPO = Path(__file__).resolve().parents[1]


def valid_hist(n_ops=20, seed=7):
    return random_valid_history(random.Random(seed), "register",
                                n_ops=n_ops, crash_p=0.0)


def invalid_hist(n_ops=20, salt=0):
    rows = []
    for i in range(n_ops - 1):
        v = salt * 100_000 + i
        rows += [(0, "invoke", "write", v), (0, "ok", "write", v)]
    rows += [(1, "invoke", "read", None), (1, "ok", "read", -7)]
    return H(*rows)


def make_service(**kw):
    kw.setdefault("store_root", None)
    kw.setdefault("batch_wait", 0.0)
    return CheckingService(**kw)


class Boom(BaseException):
    """Escapes the per-batch `except Exception` — the executor-killing
    failure mode the crash cap exists for (a jax fatal / MemoryError
    shape, not an ordinary check error)."""


# ----------------------------------------------------------- journal unit


class TestJournalRecords:
    def test_submit_record_roundtrip(self, tmp_path):
        req = admit([valid_hist(seed=1), invalid_hist()], "register",
                    deadline_ms=30_000, priority=3)
        j = AdmissionJournal(tmp_path)
        assert j.append_submit(req)
        j.close()
        out = j.replay()
        assert out["skipped"] == 0 and not out["finished"]
        [got] = out["unfinished"]
        assert got.id == req.id
        assert got.fingerprint == req.fingerprint
        assert got.priority == 3 and got.replayed
        assert len(got.encs) == len(req.encs)
        for a, b in zip(got.encs, req.encs):
            assert (a.events == b.events).all()
            assert (a.op_index == b.op_index).all()
            assert a.n_slots == b.n_slots and a.n_ops == b.n_ops
        # wall→monotonic mapping keeps the deadline in the same ballpark
        assert abs((got.deadline - time.monotonic()) - 30.0) < 5.0
        # the rebuilt encoding checks to the same verdicts
        direct = [r["valid?"] for r in check_encoded(req.encs, req.model)]
        replayed = [r["valid?"] for r in check_encoded(got.encs, got.model)]
        assert replayed == direct == [True, False]

    def test_terminal_marker_completes_entry(self, tmp_path):
        req = admit([valid_hist(seed=2)], "register")
        j = AdmissionJournal(tmp_path)
        j.append_submit(req)
        req.finish("done", results=[{"valid?": True, "algorithm": "x"}])
        j.append_terminal(req)
        j.close()
        out = j.replay()
        assert not out["unfinished"]
        [(sub, term)] = out["finished"]
        assert sub["id"] == term["id"] == req.id
        assert term["status"] == "done"
        assert term["results"] == [{"valid?": True, "algorithm": "x"}]

    def test_degraded_results_not_persisted(self, tmp_path):
        req = admit([valid_hist(seed=3)], "register")
        j = AdmissionJournal(tmp_path)
        j.append_submit(req)
        req.finish("done", results=[{"valid?": True,
                                     "platform-degraded": "stamp"}])
        j.append_terminal(req)
        out = j.replay()
        [(_, term)] = out["finished"]
        assert "results" not in term  # never replay a degrade stamp

    def test_torn_tail_skipped_loudly(self, tmp_path, caplog):
        j = AdmissionJournal(tmp_path)
        j.append_submit(admit([valid_hist(seed=4)], "register"))
        j.append_submit(admit([valid_hist(seed=5)], "register"))
        j.close()
        # crash mid-append: a torn, non-JSON tail is the NORMAL case
        with open(j.path, "ab") as f:
            f.write(b'{"kind":"submit","id":"torn-entry","v":1,"uni')
        with caplog.at_level("WARNING", logger="jgraft.service"):
            out = j.replay()
        assert len(out["unfinished"]) == 2
        assert out["skipped"] == 1
        assert any("skipped" in r.message for r in caplog.records)

    def test_corrupt_crc_mid_file_skipped(self, tmp_path):
        j = AdmissionJournal(tmp_path)
        j.append_submit(admit([valid_hist(seed=6)], "register"))
        j.append_submit(admit([valid_hist(seed=7)], "register"))
        j.close()
        lines = j.path.read_bytes().splitlines(keepends=True)
        # flip a payload byte inside the FIRST record: crc catches it
        corrupted = lines[0].replace(b'"workload":"register"',
                                     b'"workload":"registerX"', 1)
        j.path.write_bytes(corrupted + b"".join(lines[1:]))
        out = j.replay()
        assert out["skipped"] == 1
        assert len(out["unfinished"]) == 1

    def test_compaction_bounded_by_retain(self, tmp_path):
        j = AdmissionJournal(tmp_path, retain=2)
        finished = []
        for i in range(5):
            r = admit([valid_hist(seed=20 + i)], "register")
            j.append_submit(r)
            r.finish("done", results=[{"valid?": True}])
            finished.append(r)
            j.append_terminal(r)  # auto-compacts past retain
        pending = admit([valid_hist(seed=30)], "register")
        j.append_submit(pending)
        j.compact()
        out = j.replay()
        # every unfinished entry survives, finished pairs are bounded
        assert [r.id for r in out["unfinished"]] == [pending.id]
        assert len(out["finished"]) <= 2
        kept_ids = {sub["id"] for sub, _ in out["finished"]}
        assert kept_ids <= {r.id for r in finished[-2:]}

    def test_append_failure_degrades_not_fails(self, tmp_path,
                                               monkeypatch):
        j = AdmissionJournal(tmp_path)
        req = admit([valid_hist(seed=8)], "register")

        def broken_fsync(fd):
            raise OSError("disk on fire")

        monkeypatch.setattr(os, "fsync", broken_fsync)
        assert j.append_submit(req) is False  # counted, not raised
        assert j.stats()["journal_errors"] == 1

    def test_unknown_model_record_skipped(self, tmp_path):
        req = admit([valid_hist(seed=9)], "register")
        rec = encode_submit(req)
        rec["model"] = "NoSuchModel"
        with pytest.raises(ValueError):
            decode_request(rec)
        j = AdmissionJournal(tmp_path)
        j._append(rec, fsync=False)
        j.append_submit(admit([valid_hist(seed=10)], "register"))
        out = j.replay()
        assert out["skipped"] == 1 and len(out["unfinished"]) == 1


# ------------------------------------- compaction from the index (ISSUE 49)


def sub_rec(rid, pad=0):
    """A submit record by hand: what the compaction reads of a record
    is its kind and id, and a hand-made one is cheap at any size."""
    return {"kind": "submit", "v": 1, "id": rid, "fingerprint": "f" + rid,
            "pad": "x" * pad}


def term_rec(rid):
    return {"kind": "terminal", "v": 1, "id": rid, "fingerprint": "f" + rid,
            "status": "done", "results": [{"valid?": True}]}


def s_open(sid):
    return journal.encode_stream_open(sid, "register", "CasRegister", "auto",
                                      "linearizable", 1)


def s_seg(sid, seq):
    return journal.encode_stream_segment(sid, seq, [[]], f"d{seq}")


def s_bseg(sid, seq):
    return {"kind": "stream-bseg", "v": 1,
            "stream_v": journal.STREAM_VERSION, "sid": sid, "seq": seq,
            "digest": f"b{seq}", "units": []}


def s_fin(sid):
    return journal.encode_stream_fin(sid, "done", results=[{"valid?": True}])


def _pairs(n, tag="p"):
    out = []
    for i in range(n):
        out += [sub_rec(f"{tag}{i}"), term_rec(f"{tag}{i}")]
    return out


def _stream(sid, fin=True):
    return [s_open(sid), s_seg(sid, 1), s_bseg(sid, 2)] + (
        [s_fin(sid)] if fin else [])


def _everything():
    """Every family, finished and not, past `retain` 2 in both."""
    return (_pairs(2, "a") + [sub_rec("open0")] + _stream("live0", fin=False)
            + _stream("s0") + _pairs(2, "b") + _stream("s1")
            + [sub_rec("late"), sub_rec("open1")] + _stream("s2")
            + [s_seg("live0", 3), term_rec("late")] + _pairs(1, "c")
            + [{"kind": "from-the-future", "v": 1, "id": "u0"}])


def _corrupt_a_middle_line(path):
    lines = path.read_bytes().splitlines(keepends=True)
    k = len(lines) // 2
    lines[k] = lines[k].replace(b'"v":1', b'"v":0', 1)
    path.write_bytes(b"".join(lines))


def _tear_the_tail(path):
    with open(path, "ab") as f:
        f.write(b'{"kind":"submit","id":"torn-entry","v":1,"uni')


#: name -> (records, retain, what a crash did to the file afterwards)
DIFFERENTIAL = {
    "pairs-past-retain": (_pairs(3) + [sub_rec("open")] + _pairs(4, "q"),
                          2, None),
    "pairs-under-retain": ([sub_rec("open")] + _pairs(2), 4, None),
    "streams-past-retain": (_stream("s0") + _stream("live", fin=False)
                            + _stream("s1") + _stream("s2")
                            + [s_seg("live", 3)], 2, None),
    "streams-under-retain": (_stream("s0") + _stream("live", fin=False),
                             4, None),
    "every-family": (_everything(), 2, None),
    "duplicate-records": (_pairs(2) + [term_rec("p0"), sub_rec("p1"),
                                       s_open("s0"), s_fin("s0"),
                                       s_fin("s0")] + _pairs(2, "q"),
                          2, None),
    "corrupt-line-in-the-middle": (_everything(), 2,
                                   _corrupt_a_middle_line),
    "torn-tail": (_everything(), 2, _tear_the_tail),
    "nothing-finished": ([sub_rec("a"), sub_rec("b")]
                         + _stream("live", fin=False), 2, None),
}


def write_wal(root, records, retain):
    """A journal that wrote `records` itself and has not compacted."""
    j = AdmissionJournal(root, retain=1 << 30)
    for rec in records:
        assert j._append(dict(rec), fsync=False)
    j.retain = retain
    return j


def reopened(src, root, retain, replay):
    root.mkdir()
    (root / "wal.jsonl").write_bytes(src.path.read_bytes())
    j = AdmissionJournal(root, retain=retain)
    if replay:
        j.replay()
    return j


def index_matches_file(j):
    """The index is what a scan of the file would seed."""
    with j._lock:
        return j._index == j._scan()[2] and \
            j._indexed_to == j.path.stat().st_size


class TestIndexedCompaction:
    @pytest.mark.parametrize("case", sorted(DIFFERENTIAL))
    def test_indexed_equals_scanned_byte_for_byte(self, tmp_path, case,
                                                  monkeypatch):
        records, retain, damage = DIFFERENTIAL[case]
        writer = write_wal(tmp_path / "w", records, retain)
        writer.close()
        if damage is not None:
            damage(writer.path)
        scanned = reopened(writer, tmp_path / "scan", retain, replay=False)
        scanned.compact()
        assert scanned.stats()["journal_compact_scans"] == 1
        indexed = reopened(writer, tmp_path / "index", retain, replay=True)
        # nothing of a record is parsed, CRC'd or re-encoded from here on
        calls = []
        for mod, name in ((journal, "_crc_line"), (journal.json, "loads"),
                          (journal.json, "dumps")):
            real = getattr(mod, name)
            monkeypatch.setattr(
                mod, name, lambda *a, _r=real, _n=name, **kw:
                (calls.append(_n), _r(*a, **kw))[1])
        indexed.compact()
        monkeypatch.undo()
        assert calls == []
        st = indexed.stats()
        assert (st["journal_compactions"], st["journal_compact_scans"]) \
            == (1, 0)
        want = scanned.path.read_bytes()
        assert indexed.path.read_bytes() == want
        assert st["journal_compact_bytes"] == len(want)
        assert indexed._finished_since_compact == \
            scanned._finished_since_compact
        assert index_matches_file(indexed) and index_matches_file(scanned)
        if damage is None:
            # the index a journal keeps of its own writes says the same
            writer.compact()
            assert writer.stats()["journal_compact_scans"] == 0
            assert writer.path.read_bytes() == want
            assert index_matches_file(writer)
        # and a second compaction of what is left changes nothing
        indexed.compact()
        assert indexed.path.read_bytes() == want

    def test_appends_during_a_compaction_are_kept_once(self, tmp_path):
        j = write_wal(tmp_path, _pairs(5) + [sub_rec("open")], retain=2)
        during = [sub_rec("new0"), term_rec("open"), sub_rec("new1"),
                  term_rec("new1"), s_open("s9")]
        seen = []

        def append_them():
            for rec in during:
                assert j._append(dict(rec), fsync=True)
                if rec["kind"] == "terminal":
                    j._finished_one()

        def append_from_another_thread():
            seen.append(j.path.with_suffix(".jsonl.tmp").stat().st_size)
            t = threading.Thread(target=append_them)
            t.start()
            t.join(WAIT_S)
            assert not t.is_alive()

        j._after_copy = append_from_another_thread
        j.compact()
        j._after_copy = None
        assert seen and seen[0] > 0          # step (b) had been written
        lines = j.path.read_bytes().splitlines()
        for rec in during:
            key = rec.get("id") or rec["sid"]
            assert sum(1 for ln in lines if f'"{key}"'.encode() in ln
                       and f'"kind":"{rec["kind"]}"'.encode() in ln) == 1
        # the two newest pairs of before, then the appends, verbatim
        assert [journal._tag(journal.json.loads(ln)) for ln in lines] == [
            ("submit", "open"), ("submit", "p3"), ("terminal", "p3"),
            ("submit", "p4"), ("terminal", "p4")] + [
                journal._tag(r) for r in during]
        # retain's 2, and the two that finished during the copy
        assert j._finished_since_compact == 4
        assert index_matches_file(j)
        out = AdmissionJournal(tmp_path, retain=2).replay()
        assert out["skipped"] == 1           # new0: no payload to decode
        assert sorted(sub["id"] for sub, _ in out["finished"]) == [
            "new1", "open", "p3", "p4"]
        assert list(out["streams"]) == ["s9"]
        # the next compaction copies from the rebuilt index
        j.compact()
        assert j.stats()["journal_compact_scans"] == 0
        assert index_matches_file(j)

    def test_an_append_does_not_wait_for_the_copy(self, tmp_path):
        """2,050 finished pairs of 128 KB: an append that starts while
        step (b) copies has returned before the step ends, and the lock
        was held for a small part of the compaction."""
        j = AdmissionJournal(tmp_path, retain=1 << 30)
        for i in range(2050):
            j._append(sub_rec(f"r{i}", pad=128 << 10), fsync=False)
            j._append(term_rec(f"r{i}"), fsync=False)
        j.retain = 1024
        req = admit([valid_hist(seed=11)], "register")
        acked = threading.Event()
        appender = threading.Thread(
            target=lambda: j.append_submit(req) and acked.set())
        real_copy = j._copy_ranges

        def copy(src, tmp_fh, ranges):
            if not appender.ident:
                appender.start()
            return real_copy(src, tmp_fh, ranges)

        acked_by_the_end_of_the_copy = []
        j._copy_ranges = copy
        j._after_copy = lambda: acked_by_the_end_of_the_copy.append(
            acked.wait(WAIT_S))
        j.compact()
        appender.join(WAIT_S)
        assert acked_by_the_end_of_the_copy == [True]
        st = j.stats()
        assert st["journal_compactions"] == 1
        assert st["journal_compact_bytes"] > 1024 * (128 << 10)
        assert st["journal_compact_hold_ms_max"] < \
            0.25 * 1000 * st["journal_compact_s"]
        [back] = AdmissionJournal(tmp_path).replay()["unfinished"]
        assert back.id == req.id
        assert index_matches_file(j)

    @pytest.mark.parametrize("crash", ["after-the-copy",
                                       "before-the-replace"])
    def test_a_crash_mid_compaction_leaves_the_old_file(
            self, tmp_path, crash, monkeypatch):
        j = write_wal(tmp_path, _everything(), retain=2)
        before = j.path.read_bytes()
        tmp = j.path.with_suffix(".jsonl.tmp")
        if crash == "after-the-copy":
            def die():
                raise Boom()

            j._after_copy = die
            with pytest.raises(Boom):
                j.compact()
            j._after_copy = None
        else:
            def no_replace(a, b):
                raise OSError("power cut")

            monkeypatch.setattr(journal.os, "replace", no_replace)
            j.compact()                      # counted, not raised
            monkeypatch.undo()
            assert j.stats()["journal_errors"] == 1
        assert j.stats()["journal_compactions"] == 0
        assert j.path.read_bytes() == before
        assert tmp.exists() and tmp.stat().st_size > 0
        # a restart reads the old file whole and never the leftover
        want = reopened(j, tmp_path / "clean", 2, replay=False).replay()
        got = AdmissionJournal(tmp_path, retain=2).replay()
        assert got["skipped"] == want["skipped"]
        assert got["finished"] == want["finished"]
        assert got["streams"] == want["streams"]
        # the journal that survived the failure appends and compacts on,
        # over the leftover
        assert j._append(sub_rec("after"), fsync=True)
        j.compact()
        assert j.stats()["journal_compactions"] == 1
        assert not tmp.exists()
        assert b'"id":"after"' in j.path.read_bytes()
        assert index_matches_file(j)

    def test_unindexed_journal_scans_once_then_copies(self, tmp_path):
        write_wal(tmp_path, _everything(), retain=2).close()
        j = AdmissionJournal(tmp_path, retain=2)    # never replayed
        assert j._append(sub_rec("mine"), fsync=True)
        j.compact()
        st = j.stats()
        assert (st["journal_compactions"], st["journal_compact_scans"]) \
            == (1, 1)
        assert index_matches_file(j)
        for rec in _pairs(3, "z"):
            assert j._append(rec, fsync=True)
        j.compact()
        st = j.stats()
        assert (st["journal_compactions"], st["journal_compact_scans"]) \
            == (2, 1)
        assert index_matches_file(j)
        ids = [journal.json.loads(ln).get("id")
               for ln in j.path.read_bytes().splitlines()]
        assert ids[-4:] == ["z1", "z1", "z2", "z2"] and "mine" in ids

    def test_a_failed_append_costs_the_index_not_the_journal(
            self, tmp_path, monkeypatch):
        j = write_wal(tmp_path, _pairs(3), retain=2)

        def broken_fsync(fd):
            raise OSError("disk on fire")

        monkeypatch.setattr(os, "fsync", broken_fsync)
        assert j.append_submit(admit([valid_hist(seed=12)],
                                     "register")) is False
        monkeypatch.undo()
        j.compact()                          # what was written? scan.
        assert j.stats()["journal_compact_scans"] == 1
        assert index_matches_file(j)
        assert len(AdmissionJournal(tmp_path).replay()["unfinished"]) == 1

    def test_the_threshold_wakes_a_thread_of_the_journals_own(
            self, tmp_path):
        j = AdmissionJournal(tmp_path, retain=2)
        where = []
        j._after_copy = lambda: where.append(
            (threading.current_thread().name, open_span()))
        before = snapshot_spans()
        for i in range(5):
            r = admit([valid_hist(seed=50 + i)], "register")
            j.append_submit(r)
            r.finish("done", results=[{"valid?": True}])
            assert j.append_terminal(r)
            # the caller's thread never compacts
            assert open_span() is None
        deadline = time.monotonic() + WAIT_S
        while not j.stats()["journal_compactions"]:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        j.close()
        assert where and set(where) == {("journal-compact",
                                         "journal.compact")}
        assert threading.current_thread().name != "journal-compact"
        after = snapshot_spans()
        n = j.stats()["journal_compactions"]
        assert after["journal.compact"]["n"] - before.get(
            "journal.compact", {"n": 0})["n"] == n
        assert after["journal.compact_hold"]["n"] - before.get(
            "journal.compact_hold", {"n": 0})["n"] == 2 * n
        out = j.replay()
        assert len(out["finished"]) <= 4 and not out["unfinished"]

    def test_compact_hold_share_reads_a_served_journal(self, tmp_path):
        """The benchmark's reader (`benchmarks/layer_metrics/
        compact_hold_share.py`) over two snapshots of what graftd serves
        under `/stats`: the holds' seconds of the window's, 0 where no
        compaction ran, nothing from a parent's stats."""
        from benchmarks.layer_metrics import compact_hold_share as reader

        j = write_wal(tmp_path, _pairs(5), retain=2)

        def stats():
            return {"stats": dict(j.stats(), spans=snapshot_spans())}

        first = stats()
        idle = {"window_s": 2.0, "before": first, "after": stats()}
        assert reader.read(idle) == 0.0
        j.compact()
        ctx = {"window_s": 2.0, "before": first, "after": stats()}
        held = ctx["after"]["stats"]["spans"]["journal.compact_hold"]["s"] \
            - first["stats"]["spans"].get("journal.compact_hold",
                                          {"s": 0.0})["s"]
        assert held > 0
        assert reader.read(ctx) == pytest.approx(100 * held / 2.0)
        assert held >= 1e-3 * j.stats()["journal_compact_hold_ms_max"]
        parent = {"stats": {k: v for k, v in ctx["after"]["stats"].items()
                            if not k.startswith("journal_compact")}}
        assert reader.read(dict(ctx, after=parent)) is None
        manifest = journal.json.loads(
            (REPO / "BENCHMARK.json").read_text())
        [entry] = [m for m in manifest["per_layer"]
                   if m["name"] == "compact_hold_share"]
        assert entry == {"name": "compact_hold_share", "unit": "%",
                         "better": "lower", "source": "program_span",
                         "layer": "journal", "moves": "hist_per_s",
                         "workloads": ["register-map-10k.campaign-keyed"]}

    def test_a_torn_tail_costs_its_own_record_only(self, tmp_path):
        """The first append after a crash mid-append starts a line of
        its own: the torn one is ended, not continued."""
        j = AdmissionJournal(tmp_path)
        j.append_submit(admit([valid_hist(seed=13)], "register"))
        j.close()
        _tear_the_tail(j.path)
        j2 = AdmissionJournal(tmp_path)
        assert len(j2.replay()["unfinished"]) == 1
        assert j2.append_submit(admit([valid_hist(seed=14)], "register"))
        assert index_matches_file(j2)
        j2.close()
        out = AdmissionJournal(tmp_path).replay()
        assert out["skipped"] == 1 and len(out["unfinished"]) == 2


# ------------------------------------------------------- crash recovery


class TestCrashRecovery:
    def test_inprocess_crash_recovery(self, tmp_path):
        """Fast tier-1 SIGKILL stand-in: the first daemon journals three
        admissions but its worker never runs (autostart=False) and it is
        DROPPED without shutdown — exactly a kill's on-disk state. The
        second daemon must replay all three and produce verdicts
        identical to a direct check."""
        hists = [[valid_hist(seed=40)], [invalid_hist(salt=1)],
                 [valid_hist(seed=41)]]
        svc1 = make_service(store_root=str(tmp_path), autostart=False)
        reqs = [svc1.submit(h, workload="register") for h in hists]
        ids = [r.id for r in reqs]
        del svc1  # no shutdown: simulated SIGKILL

        svc2 = make_service(store_root=str(tmp_path))
        try:
            recovered = [svc2.get(i) for i in ids]
            assert all(r is not None and r.replayed for r in recovered)
            for r in recovered:
                assert r.wait(WAIT_S), f"replayed {r.id} stuck {r.status}"
            direct = [r["valid?"] for r in
                      check_histories([h[0] for h in hists],
                                      CasRegister())]
            assert [r.verdict() for r in recovered] == direct
            assert direct == [True, False, True]
            assert svc2.stats()["recovered_requests"] == 3
        finally:
            svc2.shutdown(wait=True)

    def test_recovery_restores_terminal_results_and_cache(self, tmp_path):
        h = valid_hist(seed=42)
        svc1 = make_service(store_root=str(tmp_path))
        req = svc1.submit([h], workload="register")
        assert req.wait(WAIT_S) and req.status == "done"
        # The worker appends the WAL terminal marker AFTER finish() (the
        # client-visible wait), so a kill in that window legitimately
        # replays the request for re-execution (at-least-once, §11).
        # This test asserts the durable-marker half of the contract —
        # wait until the marker is on disk before the simulated kill.
        wal = svc1._journal.path
        needle = f'"id":"{req.id}"'
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            text = wal.read_text() if wal.exists() else ""
            if any(needle in ln and '"kind":"terminal"' in ln
                   for ln in text.splitlines()):
                break
            time.sleep(0.01)
        else:
            raise AssertionError("terminal marker never reached the WAL")
        del svc1  # SIGKILL after the marker landed, before any client read

        svc2 = make_service(store_root=str(tmp_path), autostart=False)
        try:
            back = svc2.get(req.id)
            assert back is not None and back.status == "done"
            assert [r["valid?"] for r in back.results] == \
                   [r["valid?"] for r in req.results]
            # the journal re-warmed the LRU: resubmission is a hit
            re = svc2.submit([h], workload="register")
            assert re.cached and re.status == "done"
            assert svc2.stats()["cache_hits"] == 1
        finally:
            svc2.shutdown(wait=True)

    def test_replayed_duplicates_coalesce_via_cache_or_attach(
            self, tmp_path):
        """Two byte-identical unfinished journal entries replay as ONE
        execution: the first becomes primary, the second attaches."""
        h = valid_hist(seed=43)
        svc1 = make_service(store_root=str(tmp_path), autostart=False)
        r1 = svc1.submit([h], workload="register")
        r2 = svc1.submit([h], workload="register")
        assert r2.attached_to == r1.id  # attach already at admission
        del svc1

        svc2 = make_service(store_root=str(tmp_path), autostart=False)
        try:
            b1, b2 = svc2.get(r1.id), svc2.get(r2.id)
            assert b1 is not None and b2 is not None
            assert b2.attached_to == b1.id
            assert svc2.queue.depth == 1  # one execution planned
            svc2.start()
            assert b1.wait(WAIT_S) and b2.wait(WAIT_S)
            assert b1.verdict() is True and b2.verdict() is True
            st = svc2.stats()
            assert st["attached_requests"] == 1
            assert st["batches"] == 1
        finally:
            svc2.shutdown(wait=True)

    def test_clean_shutdown_leaves_no_replay(self, tmp_path):
        svc1 = make_service(store_root=str(tmp_path), autostart=False)
        req = svc1.submit([valid_hist(seed=44)], workload="register")
        svc1.shutdown(wait=True)  # fails queued loudly + journals it
        assert req.status == "failed"
        svc2 = make_service(store_root=str(tmp_path), autostart=False)
        try:
            assert svc2.stats()["recovered_requests"] == 0
            assert svc2.queue.depth == 0
            # the terminal outcome is still queryable after restart
            back = svc2.get(req.id)
            assert back is not None and back.status == "failed"
        finally:
            svc2.shutdown(wait=True)

    def test_journal_env_gate_restores_in_memory_daemon(self, tmp_path,
                                                        monkeypatch):
        monkeypatch.setenv("JGRAFT_SERVICE_JOURNAL", "0")
        svc = make_service(store_root=str(tmp_path))
        try:
            req = svc.submit([valid_hist(seed=45)], workload="register")
            assert req.wait(WAIT_S) and req.verdict() is True
            st = svc.stats()
            assert st["journal_enabled"] is False
            assert "journal_appends" not in st
            assert not (tmp_path / "graftd" / "journal").exists()
        finally:
            svc.shutdown(wait=True)

    def test_recovery_preserves_deadline_order(self, tmp_path):
        svc1 = make_service(store_root=str(tmp_path), autostart=False)
        late = svc1.submit([valid_hist(n_ops=16, seed=1)],
                           workload="register", deadline_ms=60_000)
        soon = svc1.submit([valid_hist(n_ops=400, seed=2)],
                           workload="register", deadline_ms=1_000)
        del svc1
        svc2 = make_service(store_root=str(tmp_path), autostart=False)
        try:
            svc2.start()
            b_late, b_soon = svc2.get(late.id), svc2.get(soon.id)
            assert b_late.wait(WAIT_S) and b_soon.wait(WAIT_S)
            assert b_soon.stats["batch_seq"] < b_late.stats["batch_seq"]
        finally:
            svc2.shutdown(wait=True)


# ------------------------------------------------ idempotent resubmission


class TestIdempotentResubmission:
    def test_duplicate_attaches_and_executes_once(self):
        h = valid_hist(seed=50)
        calls = {"n": 0}

        def counting(encs, model, algorithm="auto", **kw):
            calls["n"] += 1
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        svc = make_service(check_fn=counting, autostart=False)
        r1 = svc.submit([h], workload="register")
        r2 = svc.submit([h], workload="register")
        assert r2.attached_to == r1.id
        assert svc.queue.depth == 1
        svc.start()
        assert r1.wait(WAIT_S) and r2.wait(WAIT_S)
        svc.shutdown(wait=True)
        assert calls["n"] == 1  # at-most-once execution
        assert r1.verdict() is True and r2.verdict() is True
        assert [x["valid?"] for x in r2.results] == \
               [x["valid?"] for x in r1.results]
        st = svc.stats()
        assert st["attached_requests"] == 1
        assert st["submitted"] == 2 and st["completed"] == 2

    def test_follower_cancel_leaves_primary_running(self):
        svc = make_service(autostart=False)
        h = valid_hist(seed=51)
        r1 = svc.submit([h], workload="register")
        r2 = svc.submit([h], workload="register")
        assert svc.cancel(r2.id) == "cancelled"
        assert r1.status == "queued"
        svc.start()
        assert r1.wait(WAIT_S)
        svc.shutdown(wait=True)
        assert r1.verdict() is True
        assert r2.status == "cancelled" and r2.results is None

    def test_primary_cancel_promotes_follower(self):
        svc = make_service(autostart=False)
        h = valid_hist(seed=52)
        r1 = svc.submit([h], workload="register")
        r2 = svc.submit([h], workload="register")
        assert svc.cancel(r1.id) == "cancelled"
        assert svc.queue.depth == 1  # the promoted follower requeued
        svc.start()
        assert r2.wait(WAIT_S)
        svc.shutdown(wait=True)
        assert r1.status == "cancelled"
        assert r2.status == "done" and r2.verdict() is True
        assert r2.attached_to is None  # promoted

    def test_attach_does_not_cross_completed_requests(self):
        """A fingerprint whose primary already finished does NOT attach
        (it cache-hits instead) — attach is only for live requests."""
        svc = make_service(autostart=False)
        h = valid_hist(seed=53)
        r1 = svc.submit([h], workload="register")
        svc.start()
        assert r1.wait(WAIT_S)
        r2 = svc.submit([h], workload="register")
        svc.shutdown(wait=True)
        assert r2.cached and r2.attached_to is None


# --------------------------------------- quarantine + watchdog resilience


class TestPoisonBatchQuarantine:
    def test_crash_cap_bounds_respawn_and_quarantines(self):
        def dying(encs, model, algorithm="auto", **kw):
            raise Boom("deterministic executor killer")

        svc = make_service(check_fn=dying, autostart=False, crash_cap=2)
        req = svc.submit([valid_hist(seed=60)], workload="register")
        svc.start()
        assert req.wait(WAIT_S), f"stuck in {req.status}"
        assert req.status == "failed"
        assert "quarantined" in req.error
        st = svc.stats()
        assert st["quarantined"] == 1
        assert st["worker_restarts"] == 2  # cap, not forever
        # the queue is NOT wedged: a healthy submission completes
        svc.scheduler.check_fn = check_encoded
        ok = svc.submit([valid_hist(seed=61)], workload="register")
        assert ok.wait(WAIT_S) and ok.verdict() is True
        svc.shutdown(wait=True)

    def test_split_spares_innocent_riders(self):
        """A poison request (2 units) and an innocent one (1 unit)
        coalesce; the batch kills the executor; the SPLIT re-runs each
        solo — the innocent completes, only the poison quarantines."""
        def selective(encs, model, algorithm="auto", **kw):
            if len(encs) != 1:
                raise Boom("dies whenever the poison rows are aboard")
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        svc = make_service(check_fn=selective, autostart=False,
                           crash_cap=2)
        innocent = svc.submit([valid_hist(seed=62)], workload="register")
        poison = svc.submit([valid_hist(seed=63), valid_hist(seed=64)],
                            workload="register")
        svc.start()
        assert innocent.wait(WAIT_S) and poison.wait(WAIT_S)
        svc.shutdown(wait=True)
        assert innocent.status == "done" and innocent.verdict() is True
        assert innocent.stats["batched_requests"] == 1  # ran solo
        assert poison.status == "failed"
        assert "quarantined" in poison.error
        assert svc.stats()["quarantined"] == 1


class TestHungBatchWatchdog:
    def test_watchdog_rescues_hung_batch_via_host_ladder(self):
        release = threading.Event()

        def hanging(encs, model, algorithm="auto", **kw):
            release.wait(30)  # wedged device launch stand-in
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        svc = make_service(check_fn=hanging, watchdog_margin_s=0.25)
        try:
            req = svc.submit([valid_hist(seed=65)], workload="register",
                             deadline_ms=200)
            assert req.wait(WAIT_S), f"stuck in {req.status}"
            assert req.status == "done"
            assert req.verdict() is True
            # strike two forced the bounded host ladder, stamped like
            # every degrade (and therefore never cached)
            for r in req.results:
                assert "platform-degraded" in r
                assert "watchdog" in r["platform-degraded"]
            st = svc.stats()
            assert st["watchdog_requeues"] == 2
            assert st["completed"] == 1
            # the daemon is NOT wedged: a fresh healthy submission
            # (served by the replacement worker) completes
            svc.scheduler.check_fn = check_encoded
            ok = svc.submit([valid_hist(seed=66)], workload="register")
            assert ok.wait(WAIT_S) and ok.verdict() is True
            assert all("platform-degraded" not in r for r in ok.results)
        finally:
            release.set()
            svc.shutdown(wait=True)

    def test_watchdog_disabled_by_default_margin_zero(self):
        svc = make_service(watchdog_margin_s=0.0, autostart=False)
        svc.start()
        assert svc._watchdog is None
        svc.shutdown(wait=True)


# ------------------------------------------------- client retry/backoff


class TestClientBackoff:
    def test_backoff_delay_schedule(self):
        rng = random.Random(0)
        # jittered exponential, capped
        for attempt in range(1, 8):
            d = backoff_delay(attempt, 0.1, 2.0, rng=rng)
            assert 0.0 <= d <= 2.0
        # Retry-After is a FLOOR: never earlier than the daemon asked
        for _ in range(20):
            d = backoff_delay(1, 0.1, 2.0, retry_after_s=1.5, rng=rng)
            assert 1.5 <= d <= 3.5

    def test_429_retry_succeeds_after_drain(self):
        svc = make_service(autostart=False, queue_capacity=1)
        httpd, port, _ = serve_in_thread(svc)
        client = ServiceClient(f"http://127.0.0.1:{port}",
                               max_attempts=6, backoff_base_s=0.05)
        try:
            first = client.submit([valid_hist(seed=70)],
                                  workload="register")
            timer = threading.Timer(0.3, svc.start)
            timer.start()
            # queue full now; the retry loop must honor Retry-After and
            # land once the started worker drains the queue
            second = client.submit([valid_hist(seed=71)],
                                   workload="register")
            assert second["status"] in ("queued", "running", "done")
            for rec in (first, second):
                out = client.result(rec["id"], wait_s=60.0)
                while out["status"] not in ("done", "failed", "cancelled"):
                    out = client.result(rec["id"], wait_s=60.0)
                assert out["status"] == "done"
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_429_fail_fast_without_retry(self):
        svc = make_service(autostart=False, queue_capacity=1)
        httpd, port, _ = serve_in_thread(svc)
        client = ServiceClient(f"http://127.0.0.1:{port}")
        try:
            client.submit([valid_hist(seed=72)], workload="register")
            with pytest.raises(ServiceError) as exc:
                client.submit([valid_hist(seed=73)], workload="register",
                              retry=False)
            assert exc.value.status == 429
            assert exc.value.retry_after_s >= 0.5
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_connection_refused_retries_until_daemon_up(self):
        port = free_port()
        svc = make_service(autostart=False)
        started = {}

        def bring_up():
            started["httpd"], _, _ = serve_in_thread(
                svc, port=port)

        timer = threading.Timer(0.4, bring_up)
        timer.start()
        client = ServiceClient(f"http://127.0.0.1:{port}",
                               max_attempts=8, backoff_base_s=0.15,
                               backoff_cap_s=0.5)
        try:
            rec = client.submit([valid_hist(seed=74)],
                                workload="register")
            assert rec["status"] == "queued"
        finally:
            timer.join()
            if "httpd" in started:
                started["httpd"].shutdown()
                started["httpd"].server_close()
            svc.shutdown(wait=True)

    def test_connection_refused_exhausts_attempts(self):
        client = ServiceClient(f"http://127.0.0.1:{free_port()}",
                               max_attempts=2, backoff_base_s=0.01,
                               backoff_cap_s=0.02)
        with pytest.raises(OSError):
            client.submit([valid_hist(seed=75)], workload="register")

    def test_503_surfaces_retry_after(self):
        svc = make_service(autostart=False)
        httpd, port, _ = serve_in_thread(svc)
        client = ServiceClient(f"http://127.0.0.1:{port}")
        try:
            svc.shutdown(wait=True)
            with pytest.raises(ServiceError) as exc:
                client.submit([valid_hist(seed=76)], workload="register",
                              retry=False)
            assert exc.value.status == 503
            assert exc.value.retry_after_s == 2.0
        finally:
            httpd.shutdown()
            httpd.server_close()


# ------------------------------------------------ real SIGKILL (slow)


@pytest.mark.slow
class TestRealSigkill:
    def test_sigkill_mid_batch_recovers_with_identical_verdicts(
            self, tmp_path):
        """The acceptance-criteria shape, against the REAL daemon
        process: submit over HTTP, SIGKILL before the (lingered) batch
        launches, restart on the same store, and require both recovered
        verdicts to equal a direct check."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JGRAFT_SERVICE_BATCH_WAIT_MS="8000")
        store = str(tmp_path / "store")
        hists = [valid_hist(seed=80), invalid_hist(salt=2)]

        def spawn():
            port = free_port()
            proc = subprocess.Popen(
                [sys.executable, "-m", "jepsen_jgroups_raft_tpu",
                 "serve-checker", "--store", store,
                 "--host", "127.0.0.1", "--port", str(port)],
                env=env, cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            client = ServiceClient(f"http://127.0.0.1:{port}",
                                   max_attempts=30, backoff_base_s=0.3,
                                   backoff_cap_s=1.0, timeout=120.0)
            deadline = time.monotonic() + 90
            while True:
                try:
                    client.healthz()
                    break
                except OSError:
                    assert proc.poll() is None, "daemon died on boot"
                    assert time.monotonic() < deadline, "daemon not up"
                    time.sleep(0.3)
            return proc, client

        proc, client = spawn()
        try:
            recs = [client.submit([h], workload="register")
                    for h in hists]
            assert all(r["status"] == "queued" for r in recs)
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(30)

            proc, client = spawn()
            for rec, want in zip(recs, (True, False)):
                out = client.result(rec["id"], wait_s=60.0)
                deadline = time.monotonic() + 180
                while out["status"] not in ("done", "failed",
                                            "cancelled"):
                    assert time.monotonic() < deadline
                    out = client.result(rec["id"], wait_s=60.0)
                assert out["status"] == "done", out
                assert out["replayed"] is True
                assert out["valid?"] is want
            stats = client.stats()
            assert stats["recovered_requests"] == 2
            direct = [r["valid?"] for r in
                      check_histories(hists, CasRegister())]
            assert direct == [True, False]
        finally:
            proc.kill()
            proc.wait(30)
