"""Streaming verdict sessions (ISSUE 12): incremental encoder
differentials, carried-scan identity, mid-run violation surfacing,
append idempotency/ordering, flow control, idle-park + resume,
in-process crash-resume bitwise identity, cluster claim of an open
session, and the journal stream-record family's forward-compat."""

import json
import random
import threading
import time

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker.base import INVALID, VALID
from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.schedule import CarriedScan
from jepsen_jgroups_raft_tpu.history.packing import (IncrementalEncoder,
                                                     encode_history)
from jepsen_jgroups_raft_tpu.history.synth import (build_history,
                                                   random_valid_history)
from jepsen_jgroups_raft_tpu.models import (CasRegister, Counter, GSet,
                                            TicketQueue)
from jepsen_jgroups_raft_tpu.service import (CheckingService, ServiceClient,
                                             StreamBusy, StreamConflict,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.journal import (AdmissionJournal,
                                                     STREAM_VERSION,
                                                     _crc_line,
                                                     encode_stream_open,
                                                     encode_stream_segment)

MODELS = {
    "register": CasRegister,
    "counter": Counter,
    "set": GSet,
    "queue": TicketQueue,
}


def _segments(history, n):
    ops = [op.to_dict() for op in history.client_ops()]
    k = max(1, -(-len(ops) // n))
    return [ops[i:i + k] for i in range(0, len(ops), k)]


def _impossible_register_history(n_writes=6, tail_writes=2):
    """Valid writes, then an impossible read, then more valid ops —
    the violation becomes decidable exactly when the read settles."""
    rows = []
    for j in range(n_writes):
        rows += [(0, "invoke", "write", j), (0, "ok", "write", j)]
    rows += [(1, "invoke", "read", None), (1, "ok", "read", -7)]
    for j in range(tail_writes):
        rows += [(2, "invoke", "write", 100 + j), (2, "ok", "write", 100 + j)]
    return build_history(rows)


def _service(tmp_path, **kw):
    return CheckingService(store_root=str(tmp_path / "store"), **kw)


def _stream_whole(svc, history, workload, n_segments, rng=None):
    """Open → append every segment → finish; returns (final, states)."""
    st = svc.streams.open(workload=workload)
    sid = st["session"]
    states = []
    for i, seg in enumerate(_segments(history, n_segments), start=1):
        states.append(svc.streams.append(sid, i, seg, n_bytes=64))
    return svc.streams.finish(sid), states


# --------------------------------------------------- incremental encoder


class TestIncrementalEncoder:
    @pytest.mark.parametrize("kind", sorted(MODELS))
    def test_prefix_stable_and_final_identity(self, kind):
        """At EVERY cut the emitted stream is a prefix of the one-shot
        encode; fed to the end it is byte-identical (events, op_index,
        proc, n_slots, n_ops) to encode_history(prune=False)."""
        rng = random.Random(hash(kind) & 0xffff)
        for trial in range(8):
            model = MODELS[kind]()
            h = random_valid_history(
                random.Random(rng.randrange(1 << 30)), kind,
                n_ops=rng.randrange(1, 50), n_procs=rng.randrange(1, 5),
                crash_p=rng.choice([0.0, 0.25]))
            ops = list(h.client_ops())
            ref = encode_history(ops, model, prune=False)
            enc = IncrementalEncoder(model)
            parts = []
            i = 0
            while i < len(ops):
                n = rng.randrange(1, 7)
                parts.append(enc.feed(ops[i:i + n]))
                got = np.concatenate([p[0] for p in parts])
                assert np.array_equal(got, ref.events[:got.shape[0]])
                i += n
            parts.append(enc.feed([], final=True))
            ev = np.concatenate([p[0] for p in parts])
            oi = np.concatenate([p[1] for p in parts])
            pr = np.concatenate([p[2] for p in parts])
            assert np.array_equal(ev, ref.events)
            assert np.array_equal(oi, ref.op_index)
            assert np.array_equal(pr, ref.proc)
            assert enc.n_slots == ref.n_slots
            assert enc.n_ops == ref.n_ops

    def test_settlement_waits_for_completion(self):
        """An invoke's OPEN is held until its completion is recorded —
        its event content depends on the outcome."""
        m = CasRegister()
        enc = IncrementalEncoder(m)
        ev, _, _ = enc.feed([{"process": 0, "type": "invoke",
                              "f": "write", "value": 1}])
        assert ev.shape[0] == 0 and enc.unsettled == 1
        ev, _, _ = enc.feed([{"process": 0, "type": "ok",
                              "f": "write", "value": 1}])
        assert ev.shape[0] == 2  # OPEN + FORCE settle together
        assert enc.unsettled == 0

    def test_malformed_segment_rejects_atomically(self):
        m = CasRegister()
        enc = IncrementalEncoder(m)
        enc.feed([{"process": 0, "type": "invoke", "f": "write",
                   "value": 1}])
        with pytest.raises(ValueError):
            enc.feed([{"process": 0, "type": "invoke", "f": "write",
                       "value": 2}])  # double invoke
        with pytest.raises(ValueError):
            enc.feed([{"process": 9, "type": "ok", "f": "write",
                       "value": 2}])  # stray completion
        # the rejection did not corrupt the encoder
        ev, _, _ = enc.feed([{"process": 0, "type": "ok", "f": "write",
                              "value": 1}])
        assert ev.shape[0] == 2


# -------------------------------------------------------- carried scan


class TestCarriedScan:
    def test_cross_append_identity_with_monolithic(self):
        """Chaining feeds over arbitrary suffixes reaches the identical
        (ok, overflow) pair as the one-launch monolithic sort scan."""
        from jepsen_jgroups_raft_tpu.history.packing import (
            pad_batch_bucketed)
        from jepsen_jgroups_raft_tpu.ops.linear_scan import (
            DEFAULT_N_CONFIGS, bucket_slots, make_batch_checker)

        rng = random.Random(11)
        m = CasRegister()
        for trial in range(6):
            if trial % 3 == 2:
                h = _impossible_register_history()
            else:
                h = random_valid_history(
                    random.Random(rng.randrange(1 << 30)), "register",
                    n_ops=40, n_procs=4, crash_p=0.1)
            enc = encode_history(h.client_ops(), m, prune=False)
            kern = make_batch_checker(
                m, DEFAULT_N_CONFIGS, bucket_slots(max(enc.n_slots, 1)))
            ev, _, _b = pad_batch_bucketed(np.asarray(enc.events)[None])
            ok_ref = bool(np.asarray(kern(ev)[0])[0])
            cs = CarriedScan(m, enc.n_slots)
            i = 0
            while i < enc.events.shape[0]:
                n = rng.randrange(1, 9)
                cs.feed(enc.events[i:i + n])
                i += n
            assert cs.ok == ok_ref

    def test_decided_is_frozen_and_evicts(self):
        m = CasRegister()
        enc = encode_history(_impossible_register_history().client_ops(),
                             m, prune=False)
        cs = CarriedScan(m, enc.n_slots)
        cs.feed(enc.events)
        assert cs.decided and not cs.ok and not cs.overflow
        launches = cs.launches
        cs.feed(enc.events[:4])  # decided row swallows suffixes
        assert cs.launches == launches


# --------------------------------------------- verdict identity matrix


class TestStreamVerdictIdentity:
    @pytest.mark.parametrize("kind", sorted(MODELS))
    @pytest.mark.parametrize("macro", ["0", "1"])
    def test_segmented_equals_one_shot(self, tmp_path, monkeypatch,
                                       kind, macro):
        """Segment-by-segment verdict ≡ whole-history check_histories,
        both polarities, macro on/off, across histories the one-shot
        path routes dense AND sort."""
        monkeypatch.setenv("JGRAFT_MACRO_EVENTS", macro)
        svc = _service(tmp_path)
        try:
            rng = random.Random(hash((kind, macro)) & 0xffff)
            hists = [random_valid_history(
                random.Random(rng.randrange(1 << 30)), kind,
                n_ops=30, n_procs=4,
                crash_p=0.2 if kind == "register" else 0.0)
                for _ in range(2)]
            if kind == "register":
                hists.append(_impossible_register_history())
            for h in hists:
                fin, _ = _stream_whole(svc, h, kind, n_segments=4,
                                       rng=rng)
                [ref] = check_histories([h.client_ops()],
                                        MODELS[kind]())
                assert fin["valid?"] is ref["valid?"], (kind, macro)
        finally:
            svc.shutdown(wait=True)

    def test_wide_window_escalates_to_full_ladder(self, tmp_path,
                                                  monkeypatch):
        """A window beyond the sort kernel's MAX_SLOTS cannot ride the
        carried scan: the unit escalates and finish runs the full
        ladder — verdict still equals the one-shot path. Greedy is
        pinned off so the kernel path (and its escalation) is what is
        under test."""
        monkeypatch.setenv("JGRAFT_STREAM_GREEDY_MAX_EVENTS", "0")
        rows = []
        for p in range(130):   # window 131 > MAX_SLOTS (127)
            rows.append((p, "invoke", "write", p))
        rows += [(200, "invoke", "read", None), (200, "ok", "read", 3)]
        h = build_history(rows)
        svc = _service(tmp_path)
        try:
            fin, _ = _stream_whole(svc, h, "register", n_segments=3)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
            assert fin["results"][0].get("escalated-from-stream")
        finally:
            svc.shutdown(wait=True)

    def test_greedy_carries_simple_valid_sessions(self, tmp_path):
        """A sequential (no-concurrency) valid stream never launches a
        kernel: the greedy witness certifies every segment."""
        svc = _service(tmp_path)
        try:
            rows = []
            for j in range(30):
                rows += [(0, "invoke", "write", j),
                         (0, "ok", "write", j)]
            h = build_history(rows)
            fin, _ = _stream_whole(svc, h, "register", n_segments=5)
            assert fin["valid?"] is VALID
            assert fin["results"][0]["algorithm"] == "greedy-witness"
            assert fin["results"][0]["decided-tier"] == "greedy@lin"
        finally:
            svc.shutdown(wait=True)

    def test_backtracking_certifier_carries_ambiguous_sessions(
            self, tmp_path, monkeypatch):
        """ISSUE-13 stream-tier regression: a register session whose
        mutator ambiguity defeats the PR-9 no-backtrack greedy
        (JGRAFT_GREEDY_BACKTRACK=0 demonstrably hands it to the
        carried kernel) now stays on the greedy fast path per segment
        and finishes greedy-witness, with the deciding tier stamped."""
        from jepsen_jgroups_raft_tpu.checker.consistency import \
            certify_encoded

        m = CasRegister()
        rng = random.Random(3)
        svc = _service(tmp_path)
        try:
            target = None
            for _ in range(80):
                h = random_valid_history(rng, "register", n_ops=60,
                                         n_procs=5, crash_p=0.05,
                                         max_crashes=3)
                # the finish-time certify runs on the UNPRUNED settled
                # stream; condition the search on that exact stream
                enc = encode_history(h.client_ops(), m, prune=False)
                if certify_encoded(enc, m, budget=0)[0]:
                    continue
                if not certify_encoded(enc, m)[0]:
                    continue
                fin, _ = _stream_whole(svc, h, "register", 4)
                if fin["results"][0]["algorithm"] == "greedy-witness":
                    target = h
                    break
            assert target is not None, "no ambiguous-but-certifiable seed"
            assert fin["valid?"] is VALID
            assert fin["results"][0]["decided-tier"] == "backtrack@lin"
            # PR-9 ablation arm: same session, backtracking off — the
            # greedy path drops it and the carried kernel answers, with
            # the SAME verdict (the wiring never changes verdicts).
            monkeypatch.setenv("JGRAFT_GREEDY_BACKTRACK", "0")
            fin2, _ = _stream_whole(svc, target, "register", 4)
            assert fin2["valid?"] is VALID
            assert fin2["results"][0]["algorithm"] != "greedy-witness"
        finally:
            svc.shutdown(wait=True)


class TestEarliestSegmentDetection:
    def test_violation_surfaces_at_deciding_segment(self, tmp_path):
        """A seeded violation is reported at the segment where it first
        becomes decidable — in that append's RESPONSE — not at finish,
        and carries a minimized counterexample."""
        svc = _service(tmp_path)
        try:
            h = _impossible_register_history(n_writes=6, tail_writes=3)
            ops = [op.to_dict() for op in h.client_ops()]
            # seg 1: the six valid writes; seg 2: the impossible read;
            # seg 3: the valid tail
            chunks = [ops[:12], ops[12:14], ops[14:]]
            st = svc.streams.open(workload="register")
            sid = st["session"]
            out1 = svc.streams.append(sid, 1, chunks[0], n_bytes=64)
            assert "violation" not in out1
            out2 = svc.streams.append(sid, 2, chunks[1], n_bytes=64)
            assert out2["violation"]["decided-at-segment"] == 2
            assert out2["valid?"] is INVALID
            res = out2["violation"]["result"]
            assert res["counterexample"]["minimal-op-count"] >= 1
            out3 = svc.streams.append(sid, 3, chunks[2], n_bytes=64)
            assert out3["violation"]["decided-at-segment"] == 2
            fin = svc.streams.finish(sid)
            assert fin["valid?"] is INVALID
            assert fin["results"][0]["decided-at-segment"] == 2
            assert svc.stats()["stream_violations"] == 1
        finally:
            svc.shutdown(wait=True)


# ------------------------------------------- ordering / idempotency


class TestAppendMatrix:
    def test_duplicate_and_out_of_order(self, tmp_path):
        svc = _service(tmp_path)
        try:
            h = random_valid_history(random.Random(9), "register",
                                     n_ops=20, crash_p=0.0)
            segs = _segments(h, 3)
            st = svc.streams.open(workload="register")
            sid = st["session"]
            svc.streams.append(sid, 1, segs[0], n_bytes=64)
            # duplicate, same payload: idempotent no-op
            dup = svc.streams.append(sid, 1, segs[0], n_bytes=64)
            assert dup.get("duplicate") is True
            assert dup["next_seq"] == 2
            # duplicate seq, DIFFERENT payload: loud conflict
            with pytest.raises(StreamConflict):
                svc.streams.append(sid, 1, segs[1], n_bytes=64)
            # gap: rejected with the expected seq
            with pytest.raises(StreamConflict) as ei:
                svc.streams.append(sid, 3, segs[2], n_bytes=64)
            assert ei.value.expected_seq == 2
            svc.streams.append(sid, 2, segs[1], n_bytes=64)
            for i, seg in enumerate(segs[2:], start=3):
                svc.streams.append(sid, i, seg, n_bytes=64)
            fin = svc.streams.finish(sid)
            # finish is idempotent; append-after-finish conflicts
            assert svc.streams.finish(sid) == fin
            with pytest.raises(StreamConflict):
                svc.streams.append(sid, 99, segs[0], n_bytes=64)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
        finally:
            svc.shutdown(wait=True)

    def test_malformed_segment_is_value_error_and_recoverable(
            self, tmp_path):
        svc = _service(tmp_path)
        try:
            st = svc.streams.open(workload="register")
            sid = st["session"]
            with pytest.raises(ValueError):
                svc.streams.append(sid, 1, [{"process": 0, "type": "ok",
                                             "f": "write", "value": 1}],
                                   n_bytes=16)
            out = svc.streams.append(
                sid, 1, [{"process": 0, "type": "invoke", "f": "write",
                          "value": 1},
                         {"process": 0, "type": "ok", "f": "write",
                          "value": 1}], n_bytes=16)
            assert out["next_seq"] == 2
        finally:
            svc.shutdown(wait=True)

    def test_weak_rung_and_independent_workloads_rejected(self, tmp_path):
        svc = _service(tmp_path)
        try:
            with pytest.raises(ValueError):
                svc.streams.open(workload="register",
                                 consistency="sequential")
            with pytest.raises(ValueError):
                svc.streams.open(workload="multi-register")
        finally:
            svc.shutdown(wait=True)


# ------------------------------------------------------- flow control


class TestFlowControl:
    def test_segment_rate_budget_429(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JGRAFT_STREAM_SEGS_PER_S", "1")
        svc = _service(tmp_path)
        try:
            st = svc.streams.open(workload="register")
            sid = st["session"]
            seg = [{"process": 0, "type": "invoke", "f": "write",
                    "value": 1},
                   {"process": 0, "type": "ok", "f": "write", "value": 1}]
            # burst = 2 s worth = 2 tokens; the third append rejects
            svc.streams.append(sid, 1, seg, n_bytes=8)
            svc.streams.append(sid, 2, seg, n_bytes=8)
            with pytest.raises(StreamBusy) as ei:
                svc.streams.append(sid, 3, seg, n_bytes=8)
            assert ei.value.retry_after_s > 0
            # the rejected segment was NOT consumed
            assert svc.streams.status(sid)["next_seq"] == 3
        finally:
            svc.shutdown(wait=True)

    def test_session_cap_429_at_open(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JGRAFT_STREAM_SESSIONS", "1")
        svc = _service(tmp_path)
        try:
            svc.streams.open(workload="register")
            with pytest.raises(StreamBusy):
                svc.streams.open(workload="register")
        finally:
            svc.shutdown(wait=True)

    def test_open_existing_conflicts_without_resume(self, tmp_path):
        svc = _service(tmp_path)
        try:
            st = svc.streams.open(workload="register")
            with pytest.raises(StreamConflict):
                svc.streams.open(workload="register",
                                 session_id=st["session"])
            # resume=True re-attaches instead
            again = svc.streams.open(session_id=st["session"],
                                     resume=True)
            assert again["session"] == st["session"]
        finally:
            svc.shutdown(wait=True)


# ------------------------------------------------ idle park + resume


class TestIdleAndResume:
    def test_idle_park_then_resume(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JGRAFT_STREAM_IDLE_S", "0.2")
        svc = _service(tmp_path)
        try:
            h = random_valid_history(random.Random(3), "register",
                                     n_ops=24, crash_p=0.0)
            segs = _segments(h, 3)
            st = svc.streams.open(workload="register")
            sid = st["session"]
            svc.streams.append(sid, 1, segs[0], n_bytes=64)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if svc.streams.status(sid).get("status") == "incomplete":
                    break
                time.sleep(0.05)
            else:
                pytest.fail("session was never idle-parked")
            assert svc.streams.status(sid)["resumable"] is True
            assert svc.stats()["stream_idle_parked"] == 1
            # the next append revives it from the WAL
            for i, seg in enumerate(segs[1:], start=2):
                svc.streams.append(sid, i, seg, n_bytes=64)
            fin = svc.streams.finish(sid)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
            assert fin["resumed"] is True
            assert svc.stats()["resumed_sessions"] == 1
        finally:
            svc.shutdown(wait=True)

    def test_idle_without_journal_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_STREAM_IDLE_S", "0.2")
        svc = CheckingService(store_root=None)   # no journal
        try:
            st = svc.streams.open(workload="register")
            sid = st["session"]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if svc.streams.status(sid).get("status") == "failed":
                    break
                time.sleep(0.05)
            else:
                pytest.fail("journal-less idle session never failed")
            assert "idle" in svc.streams.status(sid)["error"]
        finally:
            svc.shutdown(wait=True)


# -------------------------------------------------- crash resume identity


class TestResumableCertifier:
    """ISSUE 14: the per-append greedy no longer restarts from op 0 —
    the certifier's (state, done-set, pending, backtrack frame) carry
    persists between appends and is rebuilt deterministically on
    replay, exactly like `CarriedScan`'s {inner, left}."""

    def test_resumed_certifier_carry_equals_uninterrupted(self,
                                                          tmp_path):
        """Interrupt a session mid-stream; the revived session's
        certifier carry must equal the uninterrupted session's
        FIELD-FOR-FIELD after the same appends, and both must finish
        with the same certified verdict."""
        h = random_valid_history(random.Random(31), "register",
                                 n_ops=40, crash_p=0.1)
        segs = _segments(h, 4)

        svc_a = _service(tmp_path / "uninterrupted")
        svc_a.streams.open(workload="register", session_id="s")
        for i, seg in enumerate(segs, start=1):
            svc_a.streams.append("s", i, seg, n_bytes=64)
        unit_a = svc_a.streams._get("s").units[0]
        assert unit_a.certifier is not None and unit_a.certified
        carry_a = unit_a.certifier.carry_state()

        root_b = tmp_path / "interrupted"
        svc_b = _service(root_b)
        svc_b.streams.open(workload="register", session_id="s")
        for i, seg in enumerate(segs[:2], start=1):
            svc_b.streams.append("s", i, seg, n_bytes=64)
        svc_b.shutdown(wait=True)   # streams survive by design

        svc_c = _service(root_b)
        for i, seg in enumerate(segs[2:], start=3):
            svc_c.streams.append("s", i, seg, n_bytes=64)
        unit_c = svc_c.streams._get("s").units[0]
        assert unit_c.certifier is not None
        assert unit_c.certifier.carry_state() == carry_a
        fin_a = svc_a.streams.finish("s")
        fin_c = svc_c.streams.finish("s")
        assert fin_a["results"][0] == fin_c["results"][0]
        assert fin_c["results"][0]["decided-tier"] in (
            "greedy@lin", "backtrack@lin")
        svc_a.shutdown(wait=True)
        svc_c.shutdown(wait=True)

    def test_append_does_not_rescan_the_prefix(self, tmp_path):
        """The O(segment) claim at the session surface: the model's
        step() call count per append stays bounded by the segment, not
        the accumulated history."""
        rows = []
        for j in range(120):
            rows += [(0, "invoke", "write", j), (0, "ok", "write", j)]
        h = build_history(rows)
        svc = _service(tmp_path)
        try:
            st = svc.streams.open(workload="register")
            sid = st["session"]
            sess = svc.streams._get(sid)
            calls = [0]
            raw = sess.model.step

            def counting(state, f, a, b):
                calls[0] += 1
                return raw(state, f, a, b)

            sess.model.step = counting
            segs = _segments(h, 8)
            per_append = []
            for i, seg in enumerate(segs, start=1):
                calls[0] = 0
                svc.streams.append(sid, i, seg, n_bytes=64)
                per_append.append(calls[0])
            unit = sess.units[0]
            assert unit.certified
            seg_events = 2 * len(segs[0])
            # a restarting certifier's later appends would each pay
            # >= the whole accumulated stream (~240 events)
            assert max(per_append[1:]) <= 4 * seg_events
        finally:
            svc.shutdown(wait=True)

    def test_undecided_certifier_hands_to_kernel_once(self, tmp_path):
        """Once the certifier goes undecided it is dropped (dead
        certifiers never un-decide) and the carried kernel owns the
        unit — same verdict as the one-shot path."""
        h = _impossible_register_history()
        svc = _service(tmp_path)
        try:
            fin, _ = _stream_whole(svc, h, "register", 3)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"] is INVALID
        finally:
            svc.shutdown(wait=True)


class TestCrashResume:
    def test_resume_bitwise_identity(self, tmp_path):
        """The interrupted-and-resumed session's final record equals the
        uninterrupted session's, field for field (timing-free records,
        so full equality IS bitwise identity), for both polarities."""
        for make in (lambda: random_valid_history(
                         random.Random(21), "register", n_ops=36,
                         crash_p=0.1),
                     _impossible_register_history):
            h = make()
            segs = _segments(h, 4)

            svc_a = _service(tmp_path / f"uninterrupted-{make.__name__}"
                             if hasattr(make, "__name__")
                             else tmp_path / "u")
            st = svc_a.streams.open(workload="register",
                                    session_id="fixed-sid")
            for i, seg in enumerate(segs, start=1):
                svc_a.streams.append("fixed-sid", i, seg, n_bytes=64)
            fin_a = svc_a.streams.finish("fixed-sid")
            svc_a.shutdown(wait=True)

            root_b = tmp_path / f"interrupted-{id(make)}"
            svc_b = _service(root_b)
            svc_b.streams.open(workload="register",
                               session_id="fixed-sid")
            for i, seg in enumerate(segs[:2], start=1):
                svc_b.streams.append("fixed-sid", i, seg, n_bytes=64)
            svc_b.shutdown(wait=True)   # streams survive by design

            svc_c = _service(root_b)
            assert svc_c.streams.status("fixed-sid")["status"] \
                == "incomplete"
            for i, seg in enumerate(segs[2:], start=3):
                svc_c.streams.append("fixed-sid", i, seg, n_bytes=64)
            fin_b = svc_c.streams.finish("fixed-sid")
            svc_c.shutdown(wait=True)

            a = {k: v for k, v in fin_a.items() if k != "resumed"}
            b = {k: v for k, v in fin_b.items() if k != "resumed"}
            assert a == b
            assert fin_b["resumed"] is True

    def test_violation_segment_survives_restart(self, tmp_path):
        h = _impossible_register_history(n_writes=5, tail_writes=0)
        ops = [op.to_dict() for op in h.client_ops()]
        chunks = [ops[:10], ops[10:]]
        svc = _service(tmp_path)
        svc.streams.open(workload="register", session_id="v")
        svc.streams.append("v", 1, chunks[0], n_bytes=64)
        out = svc.streams.append("v", 2, chunks[1], n_bytes=64)
        assert out["violation"]["decided-at-segment"] == 2
        svc.shutdown(wait=True)
        svc2 = _service(tmp_path)
        fin = svc2.streams.finish("v")
        assert fin["valid?"] is INVALID
        assert fin["results"][0]["decided-at-segment"] == 2
        svc2.shutdown(wait=True)

    def test_spill_rebuilds_from_journal(self, tmp_path, monkeypatch):
        """A unit past the resident-event cap drops its host buffers;
        the carry continues and a finish still verdicts correctly (the
        WAL reconstructs whatever the ladder needs)."""
        monkeypatch.setenv("JGRAFT_STREAM_RESIDENT_EVENTS", "8")
        monkeypatch.setenv("JGRAFT_STREAM_GREEDY_MAX_EVENTS", "4")
        svc = _service(tmp_path)
        try:
            h = random_valid_history(random.Random(8), "register",
                                     n_ops=40, crash_p=0.0)
            fin, _ = _stream_whole(svc, h, "register", n_segments=6)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
        finally:
            svc.shutdown(wait=True)

    def test_violation_after_spill_still_detected(self, tmp_path,
                                                  monkeypatch):
        """Post-spill segments must keep advancing the carry: a
        violation arriving AFTER the buffers were dropped still
        surfaces mid-run and the finish verdict is INVALID (the
        review-found false-VALID regression)."""
        monkeypatch.setenv("JGRAFT_STREAM_RESIDENT_EVENTS", "8")
        monkeypatch.setenv("JGRAFT_STREAM_GREEDY_MAX_EVENTS", "4")
        svc = _service(tmp_path)
        try:
            h = _impossible_register_history(n_writes=10, tail_writes=0)
            ops = [op.to_dict() for op in h.client_ops()]
            sid = svc.streams.open(workload="register")["session"]
            svc.streams.append(sid, 1, ops[:20], n_bytes=64)  # spills
            out = svc.streams.append(sid, 2, ops[20:], n_bytes=64)
            assert out["violation"]["decided-at-segment"] == 2
            fin = svc.streams.finish(sid)
            assert fin["valid?"] is INVALID
        finally:
            svc.shutdown(wait=True)

    def test_spilled_crashed_invoke_valid_at_finish(self, tmp_path,
                                                    monkeypatch):
        """A spilled unit whose history ends with an outstanding
        (crashed) invoke must still certify VALID when the read needs
        that write: the finish-time WAL rebuild applies the same
        end-of-history settle the live encoder does (the review-found
        false-INVALID regression)."""
        monkeypatch.setenv("JGRAFT_STREAM_RESIDENT_EVENTS", "8")
        monkeypatch.setenv("JGRAFT_STREAM_GREEDY_MAX_EVENTS", "4")
        rows = [(0, "invoke", "write", 5)]      # never completes
        for j in range(8):
            rows += [(2, "invoke", "write", j), (2, "ok", "write", j)]
        rows += [(1, "invoke", "read", None), (1, "ok", "read", 5)]
        h = build_history(rows)
        svc = _service(tmp_path)
        try:
            fin, _ = _stream_whole(svc, h, "register", n_segments=2)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert ref["valid?"] is VALID   # the scenario's premise
            assert fin["valid?"] is VALID
        finally:
            svc.shutdown(wait=True)

    def test_spill_refused_without_journal(self, monkeypatch):
        """With journaling off there is no WAL to rebuild from:
        spilling would destroy the only copy of the stream, so the
        daemon keeps the buffers (memory grows — the documented
        journaling-off trade) and the verdict stays correct."""
        monkeypatch.setenv("JGRAFT_STREAM_RESIDENT_EVENTS", "8")
        monkeypatch.setenv("JGRAFT_STREAM_GREEDY_MAX_EVENTS", "4")
        monkeypatch.setenv("JGRAFT_STREAM_IDLE_S", "0")
        svc = CheckingService(store_root=None)   # no journal
        try:
            h = random_valid_history(random.Random(8), "register",
                                     n_ops=40, crash_p=0.0)
            st = svc.streams.open(workload="register")
            sid = st["session"]
            for i, seg in enumerate(_segments(h, 6), start=1):
                svc.streams.append(sid, i, seg, n_bytes=64)
            fin = svc.streams.finish(sid)
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
        finally:
            svc.shutdown(wait=True)

    def test_finish_idempotent_across_restart(self, tmp_path):
        """A finish retried after a daemon restart (the lost-2xx case)
        answers the fin-record stub's final state, not a 409."""
        h = random_valid_history(random.Random(6), "register",
                                 n_ops=20, crash_p=0.0)
        svc = _service(tmp_path)
        svc.streams.open(workload="register", session_id="fi")
        for i, seg in enumerate(_segments(h, 2), start=1):
            svc.streams.append("fi", i, seg, n_bytes=64)
        fin = svc.streams.finish("fi")
        svc.shutdown(wait=True)
        svc2 = _service(tmp_path)
        try:
            again = svc2.streams.finish("fi")
            assert again["status"] == "done"
            assert again["valid?"] == fin["valid?"]
        finally:
            svc2.shutdown(wait=True)

    def test_append_racing_park_revives(self, tmp_path):
        """An append that loses the race with the idle reaper's park()
        is retried against the revived session — never a 500/conflict
        (the review-found freed-unit race)."""
        h = random_valid_history(random.Random(7), "register",
                                 n_ops=24, crash_p=0.0)
        segs = _segments(h, 3)
        svc = _service(tmp_path)
        try:
            svc.streams.open(workload="register", session_id="race")
            svc.streams.append("race", 1, segs[0], n_bytes=64)
            # simulate the reaper winning: park the live object and
            # swap in the stub, exactly what _reaper_loop does
            from jepsen_jgroups_raft_tpu.service.stream import _Stub

            sess = svc.streams._get("race")
            sess.park()
            with svc.streams._lock:
                svc.streams._sessions["race"] = _Stub("race")
            out = svc.streams.append("race", 2, segs[1], n_bytes=64)
            assert out["next_seq"] == 3
            # the stale object's own append also reports parked, which
            # the manager converts into a revive
            for i, seg in enumerate(segs[2:], start=3):
                svc.streams.append("race", i, seg, n_bytes=64)
            fin = svc.streams.finish("race")
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
        finally:
            svc.shutdown(wait=True)


# ----------------------------------------------------- cluster claim


class TestClusterClaim:
    def test_survivor_claims_open_session(self, tmp_path):
        """A dead replica's OPEN stream session is adopted with its WAL
        (re-journaled under the claimant) and resumes to the correct
        verdict on the survivor."""
        cdir = tmp_path / "cluster"
        h = random_valid_history(random.Random(5), "register",
                                 n_ops=30, crash_p=0.0)
        segs = _segments(h, 3)
        victim = CheckingService(
            store_root=str(tmp_path / "s0"), cluster_dir=str(cdir),
            replica_id="r0", lease_ttl_s=0.5, autostart=False)
        victim.streams.open(workload="register", session_id="claimed")
        for i, seg in enumerate(segs[:2], start=1):
            victim.streams.append("claimed", i, seg, n_bytes=64)
        # SIGKILL stand-in: drop the replica without removing its lease
        # or journaling terminals; the lease simply expires.
        victim.cluster._stop.set()
        victim._journal.close()

        survivor = CheckingService(
            store_root=str(tmp_path / "s1"), cluster_dir=str(cdir),
            replica_id="r1", lease_ttl_s=0.5, autostart=True)
        try:
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if survivor.cluster.handoff_scan() \
                        or survivor.stats()["handoff_streams"]:
                    break
                time.sleep(0.2)
            assert survivor.stats()["handoff_streams"] >= 1
            st = survivor.streams.status("claimed")
            assert st["status"] == "incomplete"
            for i, seg in enumerate(segs[2:], start=3):
                survivor.streams.append("claimed", i, seg, n_bytes=64)
            fin = survivor.streams.finish("claimed")
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
        finally:
            survivor.shutdown(wait=True)


# ---------------------------------------------------- HTTP + client


class TestHttpSurface:
    def test_http_stream_lifecycle(self, tmp_path):
        svc = _service(tmp_path)
        httpd, port, _t = serve_in_thread(svc)
        try:
            cl = ServiceClient(f"http://127.0.0.1:{port}")
            h = random_valid_history(random.Random(13), "register",
                                     n_ops=24, crash_p=0.0)
            s = cl.stream(workload="register")
            for seg in _segments(h, 3):
                s.append(seg)
            # duplicate resend of the last seq is idempotent
            s.seq -= 1
            dup = s.append(_segments(h, 3)[-1])
            assert dup.get("duplicate") is True
            fin = s.finish()
            [ref] = check_histories([h.client_ops()], CasRegister())
            assert fin["valid?"] is ref["valid?"]
            # status endpoint + unknown-session 404
            assert cl._call(
                "GET", f"/stream/status?session={s.session_id}"
            )["status"] == "done"
            from jepsen_jgroups_raft_tpu.service import ServiceError

            with pytest.raises(ServiceError) as ei:
                cl._call("GET", "/stream/status?session=nope")
            assert ei.value.status == 404
            with pytest.raises(ServiceError) as ei:
                cl._call("POST", "/stream/append",
                         {"session": s.session_id, "seq": 99, "ops": []})
            assert ei.value.status == 409
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_concurrent_sessions_do_not_interfere(self, tmp_path):
        svc = _service(tmp_path)
        httpd, port, _t = serve_in_thread(svc)
        try:
            url = f"http://127.0.0.1:{port}"
            hists = [random_valid_history(random.Random(100 + k),
                                          "register", n_ops=20,
                                          crash_p=0.0)
                     for k in range(4)]
            outs = [None] * 4

            def run(k):
                cl = ServiceClient(url)
                s = cl.stream(workload="register")
                for seg in _segments(hists[k], 3):
                    s.append(seg)
                outs[k] = s.finish()

            threads = [threading.Thread(target=run, args=(k,))
                       for k in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            for k, fin in enumerate(outs):
                [ref] = check_histories([hists[k].client_ops()],
                                        CasRegister())
                assert fin["valid?"] is ref["valid?"]
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)


# -------------------------------------------- journal forward-compat


class TestJournalStreamRecords:
    def test_pre_pr12_wal_replays_cleanly(self, tmp_path):
        """A WAL holding only submit/terminal records (the PR 8 format)
        replays with zero skips and an empty streams map."""
        from jepsen_jgroups_raft_tpu.service.request import admit

        j = AdmissionJournal(tmp_path / "j", retain=8)
        h = random_valid_history(random.Random(2), "register", n_ops=10)
        req = admit([h.client_ops()], "register")
        j.append_submit(req)
        j.close()
        j2 = AdmissionJournal(tmp_path / "j", retain=8)
        out = j2.replay()
        assert out["skipped"] == 0
        assert out["streams"] == {}
        assert len(out["unfinished"]) == 1
        j2.close()

    def test_newer_stream_version_skipped_loudly(self, tmp_path):
        """Stream records from a FUTURE stream_v are skipped (counted)
        while request records in the same WAL still replay — the
        forward-compat contract of the versioned record family."""
        from jepsen_jgroups_raft_tpu.service.request import admit

        j = AdmissionJournal(tmp_path / "j", retain=8)
        h = random_valid_history(random.Random(2), "register", n_ops=10)
        j.append_submit(admit([h.client_ops()], "register"))
        future = encode_stream_open("s1", "register", "CasRegister",
                                    "auto", "linearizable", 1)
        future["stream_v"] = STREAM_VERSION + 7
        j.append_stream(future)
        j.close()
        out = AdmissionJournal(tmp_path / "j", retain=8).replay()
        assert out["skipped"] == 1
        assert out["streams"] == {}
        assert len(out["unfinished"]) == 1

    def test_orphaned_segments_dropped_loudly(self, tmp_path):
        j = AdmissionJournal(tmp_path / "j", retain=8)
        j.append_stream(encode_stream_segment("ghost", 1, [[]], "d"))
        j.close()
        out = AdmissionJournal(tmp_path / "j", retain=8).replay()
        assert out["streams"] == {}
        assert out["skipped"] == 1

    def test_torn_stream_record_costs_one_line(self, tmp_path):
        j = AdmissionJournal(tmp_path / "j", retain=8)
        j.append_stream(encode_stream_open("s1", "register",
                                           "CasRegister", "auto",
                                           "linearizable", 1))
        j.append_stream(encode_stream_segment("s1", 1, [[]], "d"))
        j.close()
        with open(j.path, "ab") as fh:
            fh.write(b'{"kind": "stream-seg", "sid": "s1", "se')  # torn
        out = AdmissionJournal(tmp_path / "j", retain=8).replay()
        assert out["skipped"] == 1
        assert len(out["streams"]["s1"]["segments"]) == 1

    def test_compaction_preserves_unfinished_streams(self, tmp_path):
        """Compaction keeps every record of unfinished sessions, trims
        finished ones to their open+fin pair, and still honors the
        request-pair retention."""
        from jepsen_jgroups_raft_tpu.service.journal import (
            encode_stream_fin)

        j = AdmissionJournal(tmp_path / "j", retain=2)
        j.append_stream(encode_stream_open("live", "register",
                                           "CasRegister", "auto",
                                           "linearizable", 1))
        for k in range(1, 4):
            j.append_stream(encode_stream_segment("live", k, [[]], "d"))
        j.append_stream(encode_stream_open("done", "register",
                                           "CasRegister", "auto",
                                           "linearizable", 1))
        j.append_stream(encode_stream_segment("done", 1, [[]], "d"))
        j.append_stream(encode_stream_fin(
            "done", "done", results=[{"valid?": True}]))
        j.compact()
        j.close()
        out = AdmissionJournal(tmp_path / "j", retain=2).replay()
        assert len(out["streams"]["live"]["segments"]) == 3
        assert out["streams"]["live"]["fin"] is None
        assert out["streams"]["done"]["fin"] is not None
        assert out["streams"]["done"]["segments"] == []

    def test_segments_appended_during_a_compaction_survive(self, tmp_path):
        """A compaction copies without the journal's lock (ISSUE 49): a
        session that appends, and one that finishes, while it copies
        are in the replaced file as they were acknowledged, and the
        finished one counts towards the next compaction."""
        import threading

        from jepsen_jgroups_raft_tpu.service.journal import (
            encode_stream_fin)

        j = AdmissionJournal(tmp_path / "j", retain=1)
        for sid in ("old", "older"):
            j.append_stream(encode_stream_open(sid, "register",
                                               "CasRegister", "auto",
                                               "linearizable", 1))
            j.append_stream(encode_stream_segment(sid, 1, [[]], "d"))
        j.append_stream(encode_stream_fin("older", "done"))
        j.append_stream(encode_stream_open("live", "register",
                                           "CasRegister", "auto",
                                           "linearizable", 1))
        j.close()                        # let the trigger's thread end
        assert j._finished_since_compact == 1

        def meanwhile():
            t = threading.Thread(target=lambda: (
                j.append_stream(encode_stream_segment("live", 1, [[]],
                                                      "d1")),
                j.append_stream(encode_stream_segment("old", 2, [[]],
                                                      "d2")),
                j.append_stream(encode_stream_fin(
                    "old", "done", results=[{"valid?": True}]))))
            t.start()
            t.join(60)
            assert not t.is_alive()

        j._after_copy = meanwhile
        j.compact()
        j._after_copy = None
        j.close()
        # the compaction kept `older`; `old` finished during its copy
        assert j._finished_since_compact == 2
        assert j.stats()["journal_compact_scans"] == 0
        out = AdmissionJournal(tmp_path / "j", retain=1).replay()
        assert out["skipped"] == 0
        assert [r["seq"] for r in out["streams"]["live"]["segments"]] == [1]
        # `old` was unfinished at the snapshot: kept whole, fin and all
        assert [r["seq"] for r in out["streams"]["old"]["segments"]] == [
            1, 2]
        assert out["streams"]["old"]["fin"]["results"] == [
            {"valid?": True}]
        assert out["streams"]["older"]["segments"] == []
        # the next compaction trims `old` to its pair and drops `older`
        j.compact()
        j.close()
        out = AdmissionJournal(tmp_path / "j", retain=1).replay()
        assert sorted(out["streams"]) == ["live", "old"]
        assert out["streams"]["old"]["segments"] == []
        assert out["streams"]["old"]["fin"] is not None

    def test_fixture_wal_crc_discipline(self, tmp_path):
        """Stream records ride the same CRC'd JSONL discipline: a
        hand-built record with a valid CRC replays; a rotted one is
        skipped."""
        rec = encode_stream_open("s9", "register", "CasRegister",
                                 "auto", "linearizable", 1)
        rec["crc"] = _crc_line(rec)
        good = json.dumps(rec, sort_keys=True, separators=(",", ":"))
        rotted = good.replace('"units":1', '"units":2')
        p = tmp_path / "j"
        p.mkdir()
        (p / "wal.jsonl").write_text(good + "\n" + rotted + "\n")
        out = AdmissionJournal(p, retain=8).replay()
        assert "s9" in out["streams"]
        assert out["skipped"] == 1


# ------------------------------------------------------ lint scopes


class TestLintScope:
    def test_stream_module_in_lint_scopes(self):
        """service/stream.py rides the taxonomy + resource-leak scan
        prefixes (shipped baselines stay empty: the module must be
        clean under both analyzers)."""
        from jepsen_jgroups_raft_tpu.lint import taxonomy
        from jepsen_jgroups_raft_tpu.lint.flow import resource

        assert taxonomy.applies_to(
            "jepsen_jgroups_raft_tpu/service/stream.py")
        assert resource.applies_to(
            "jepsen_jgroups_raft_tpu/service/stream.py")


# -------------------------------------------------------- runner hook


class TestRunnerLiveStream:
    def test_run_test_streams_live(self, tmp_path):
        from jepsen_jgroups_raft_tpu.core.runner import run_test
        from jepsen_jgroups_raft_tpu.generator.base import (Clients, Limit,
                                                            Repeat)

        svc = _service(tmp_path)
        httpd, port, _t = serve_in_thread(svc)
        try:
            test = run_test({
                "name": "live",
                "nodes": ["n1"],
                "concurrency": 2,
                "client": None,
                "generator": Clients(
                    Limit(30, Repeat({"f": "write", "value": 7}))),
                "store": False,
                "live_stream": {"url": f"http://127.0.0.1:{port}",
                                "workload": "register",
                                "flush_ops": 8},
            })
            ls = test["results"]["live-stream"]
            assert ls["status"] == "done" and ls["valid?"] is True
            assert ls["segments"] >= 2
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_dead_monitor_never_kills_the_run(self, tmp_path):
        from jepsen_jgroups_raft_tpu.core.runner import run_test
        from jepsen_jgroups_raft_tpu.generator.base import (Clients, Limit,
                                                            Repeat)

        test = run_test({
            "name": "live-dead",
            "nodes": ["n1"],
            "concurrency": 1,
            "client": None,
            "generator": Clients(
                Limit(5, Repeat({"f": "write", "value": 1}))),
            "store": False,
            # nothing listens here: open fails, the run proceeds
            "live_stream": {"url": "http://127.0.0.1:9",
                            "workload": "register"},
        })
        assert len(test["history"]) == 10
        assert "live-stream" not in test["results"]
