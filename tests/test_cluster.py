"""Clustered graftd tests — ISSUE 11 tentpole.

Tier-1, CPU-only, in-process: N CheckingService replicas share one
cluster dir (tmp_path), faults are injected surgically (journal handles
dropped, leases backdated) instead of via subprocess SIGKILL — the real
process-kill matrix lives in scripts/chaos_graftd.py --replicas and the
CI cluster smoke stage. The load-bearing assertions mirror the
acceptance criteria: a fingerprint first checked on replica A answers
on replica B without a kernel launch; a dead replica's journal is
claimed by EXACTLY one survivor (atomic rename) and every accepted
entry reaches the same verdict a direct check produces; corrupt store
entries / torn leases cost one entry, never a replica; and the
single-replica daemon is byte-for-byte unchanged when clustering is
not configured.
"""

from __future__ import annotations

import json
import random
import threading
import time

import pytest

from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.models import CasRegister
from jepsen_jgroups_raft_tpu.service import (CheckingService, QueueFull,
                                             ResultStore, ServiceClient,
                                             ServiceError, serve_in_thread)
from jepsen_jgroups_raft_tpu.service.cluster import (lease_expired,
                                                     live_replicas,
                                                     read_lease)
from jepsen_jgroups_raft_tpu.service.store import (detail_fingerprint,
                                                   is_degraded)

from util import H, random_valid_history

WAIT_S = 120.0  # upper bound, not a sleep: first XLA compile dominates


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


def make_replica(cluster_dir, rid, **kw):
    kw.setdefault("store_root", None)
    kw.setdefault("batch_wait", 0.0)
    kw.setdefault("lease_ttl_s", 5.0)
    return CheckingService(cluster_dir=str(cluster_dir), replica_id=rid,
                           **kw)


RESULTS = [{"valid?": True, "algorithm": "jax", "op-count": 4,
            "counterexample": {"minimal-op-count": 2,
                               "ops": [{"f": "write", "value": 1}]}}]


# ------------------------------------------------------------ ResultStore


class TestResultStore:
    def test_roundtrip_preserves_full_results(self, tmp_path):
        store = ResultStore(tmp_path)
        assert store.put("ab" * 32, RESULTS) is True
        got = store.get("ab" * 32)
        assert got == RESULTS
        assert got is not RESULTS and got[0] is not RESULTS[0]  # copies

    def test_miss_is_none(self, tmp_path):
        assert ResultStore(tmp_path).get("cd" * 32) is None

    def test_degraded_never_stored(self, tmp_path):
        store = ResultStore(tmp_path)
        bad = [dict(RESULTS[0], **{"platform-degraded": "device lost"})]
        assert is_degraded(bad)
        assert store.put("ab" * 32, bad) is False
        assert store.get("ab" * 32) is None
        assert store.put_detail("ab" * 32, bad[0]) is False
        assert store.get_detail("ab" * 32) is None

    def test_torn_tail_skipped_loudly_then_healed(self, tmp_path, caplog):
        """A truncated entry (crash mid-write would need a failed
        os.replace, but bit rot / manual tampering happens) costs one
        miss, never the store — and the next put heals it in place."""
        store = ResultStore(tmp_path)
        fp = "ab" * 32
        store.put(fp, RESULTS)
        path = store._entry_path("results", fp)
        raw = path.read_bytes()
        path.write_bytes(raw[:len(raw) // 2])  # torn tail
        with caplog.at_level("WARNING", logger="jgraft.service"):
            assert store.get(fp) is None
        assert any("corrupt entry" in r.message for r in caplog.records)
        assert store.stats()["store_corrupt_skipped"] == 1
        assert store.put(fp, RESULTS) is True  # heal via atomic replace
        assert store.get(fp) == RESULTS

    def test_crc_mismatch_skipped(self, tmp_path):
        store = ResultStore(tmp_path)
        fp = "ab" * 32
        store.put(fp, RESULTS)
        path = store._entry_path("results", fp)
        rec = json.loads(path.read_bytes())
        rec["results"][0]["valid?"] = False  # rot the payload, keep crc
        path.write_text(json.dumps(rec))
        assert store.get(fp) is None
        assert store.stats()["store_corrupt_skipped"] == 1

    def test_newer_version_skipped_not_misparsed(self, tmp_path):
        from jepsen_jgroups_raft_tpu.service.store import _crc_entry

        store = ResultStore(tmp_path)
        fp = "ab" * 32
        rec = {"v": 99, "fingerprint": fp, "results": RESULTS}
        rec["crc"] = _crc_entry(rec)
        path = store._entry_path("results", fp)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(rec))
        assert store.get(fp) is None
        assert store.stats()["store_corrupt_skipped"] == 1

    def test_first_wins_loser_discards(self, tmp_path):
        store = ResultStore(tmp_path)
        fp = "ab" * 32
        assert store.put(fp, RESULTS) is True
        other = [{"valid?": False, "algorithm": "jax"}]
        assert store.put(fp, other) is False  # discarded, not replaced
        assert store.get(fp) == RESULTS
        assert store.stats()["store_put_discards"] == 1

    def test_concurrent_writer_race_one_valid_entry(self, tmp_path):
        """Two writers racing the same fingerprint: whichever publish
        lands, the entry is WHOLE and valid (atomic temp+replace), and
        at least one writer observed the other and discarded."""
        fp = "ab" * 32
        payloads = [[{"valid?": True, "writer": k}] for k in range(2)]
        stores = [ResultStore(tmp_path) for _ in range(2)]
        barrier = threading.Barrier(2)
        outcomes = [None, None]

        def racer(k):
            barrier.wait()
            for _ in range(50):
                outcomes[k] = stores[k].put(fp, payloads[k])

        ts = [threading.Thread(target=racer, args=(k,)) for k in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        got = stores[0].get(fp)
        assert got in payloads  # one whole entry, never an interleaving
        counts = [s.stats() for s in stores]
        assert sum(c["store_put_discards"] for c in counts) >= 1
        assert all(c["store_corrupt_skipped"] == 0 for c in counts)

    def test_detail_records_roundtrip(self, tmp_path):
        store = ResultStore(tmp_path)
        model = CasRegister()
        enc = encode_history(valid_hist().client_ops(), model)
        key = detail_fingerprint(model, "auto", enc)
        assert key == detail_fingerprint(model, "auto", enc)  # stable
        enc2 = encode_history(valid_hist(seed=9).client_ops(), model)
        assert key != detail_fingerprint(model, "auto", enc2)
        assert store.put_detail(key, RESULTS[0]) is True
        assert store.get_detail(key) == RESULTS[0]


# ------------------------------------------------------- leases and skew


class TestLeases:
    def test_renew_and_read(self, tmp_path):
        svc = make_replica(tmp_path, "ra", autostart=False)
        lease = read_lease(tmp_path / "leases" / "ra.json")
        assert lease is not None and lease["replica"] == "ra"
        assert not lease_expired(lease, skew_s=0.0)
        assert [x["replica"] for x in live_replicas(tmp_path)] == ["ra"]
        svc.shutdown()
        # clean shutdown removes the lease — nothing advertises a ghost
        assert read_lease(tmp_path / "leases" / "ra.json") is None

    def test_expiry_is_one_sided_under_clock_skew(self):
        now = 1_000_000.0
        lease = {"renewed_wall": now - 10.0, "ttl_s": 5.0}
        # stale beyond ttl but inside the skew margin: still alive
        assert not lease_expired(lease, now=now, skew_s=6.0)
        assert lease_expired(lease, now=now, skew_s=4.0)
        # a FUTURE-dated stamp (fast writer clock) is alive, not an
        # error — expiry never triggers against a live fast clock
        future = {"renewed_wall": now + 30.0, "ttl_s": 5.0}
        assert not lease_expired(future, now=now, skew_s=0.0)

    def test_corrupt_lease_skipped_loudly(self, tmp_path, caplog):
        svc = make_replica(tmp_path, "ra", autostart=False)
        (tmp_path / "leases" / "rb.json").write_text("{torn", "utf-8")
        (tmp_path / "leases" / "rc.json").write_text(
            json.dumps({"v": 1, "replica": "rc", "renewed_wall": 1.0,
                        "ttl_s": 5.0, "crc": "00000000"}))  # bad crc
        with caplog.at_level("WARNING", logger="jgraft.service"):
            live = live_replicas(tmp_path)
        assert [x["replica"] for x in live] == ["ra"]
        assert sum("lease" in r.message for r in caplog.records) >= 2
        svc.shutdown()


# ------------------------------------------------- cross-replica caching


class TestSharedStore:
    def test_replica_b_answers_replica_a_fingerprint(self, tmp_path):
        """The acceptance bar: replica B completes a fingerprint first
        checked on replica A at ADMISSION — store hit, zero batches,
        full results (not a verdict-code stub) — and the verdicts are
        identical to a direct check_histories."""
        hists = [valid_hist(seed=3), invalid_hist(salt=3)]
        direct = [r["valid?"] for r in check_histories(
            [h.client_ops() for h in hists], CasRegister())]
        a = make_replica(tmp_path, "ra")
        try:
            reqs = [a.submit([h], workload="register") for h in hists]
            for r in reqs:
                assert r.wait(WAIT_S)
            deadline = time.monotonic() + WAIT_S
            while a.stats()["store_puts"] < 2:
                assert time.monotonic() < deadline, a.stats()
                time.sleep(0.02)
        finally:
            a.shutdown()
        b = make_replica(tmp_path, "rb")
        try:
            outs = [b.submit([h], workload="register") for h in hists]
            assert all(o.status == "done" and o.cached for o in outs)
            st = b.stats()
            assert st["store_hits"] == 2 and st["batches"] == 0, st
            assert [o.verdict() for o in outs] == direct
            assert all(o.results for o in outs)
        finally:
            b.shutdown()

    def test_degraded_verdicts_never_cross_replicas(self, tmp_path):
        """A batch that degraded to the host ladder completes locally
        (stamped) but must NOT become a fleet-wide cache entry."""
        from jepsen_jgroups_raft_tpu.checker.linearizable import (
            check_encoded)

        calls = {"n": 0}

        def flaky(encs, model, algorithm="auto", **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected device failure")
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        a = make_replica(tmp_path, "ra", check_fn=flaky)
        try:
            req = a.submit([valid_hist(seed=5)], workload="register")
            assert req.wait(WAIT_S) and req.status == "done"
            assert all("platform-degraded" in r for r in req.results)
        finally:
            a.shutdown()
        b = make_replica(tmp_path, "rb", check_fn=flaky)
        try:
            out = b.submit([valid_hist(seed=5)], workload="register")
            assert out.wait(WAIT_S) and out.status == "done"
            assert not out.cached  # re-checked, not served the stamp
            assert b.stats()["store_hits"] == 0
        finally:
            b.shutdown()

    def test_recovery_warms_from_store_without_rechecking(self, tmp_path):
        """A cold-restarted replica whose WAL holds unfinished entries
        short-circuits every fingerprint the fleet already verified —
        warm from the store, not from the wire (tentpole (a))."""
        h = valid_hist(seed=6)
        # replica rb accepts the payload FIRST and "crashes" before
        # executing it (worker never started, journal handle dropped);
        # its long lease keeps peers from adopting the WAL mid-test
        b = make_replica(tmp_path, "rb", autostart=False,
                         lease_ttl_s=300.0)
        queued = b.submit([h], workload="register")
        assert queued.status == "queued"
        b._journal.close()
        # meanwhile the fleet (replica ra) verifies the same payload
        a = make_replica(tmp_path, "ra")
        try:
            req = a.submit([h], workload="register")
            assert req.wait(WAIT_S)
            deadline = time.monotonic() + WAIT_S
            while a.stats()["store_puts"] < 1:
                assert time.monotonic() < deadline
                time.sleep(0.02)
        finally:
            a.shutdown()
        b2 = make_replica(tmp_path, "rb", autostart=False,
                          lease_ttl_s=300.0)
        try:
            st = b2.stats()
            assert st["recovered_requests"] == 0, st  # nothing requeued
            assert st["store_hits"] == 1 and st["batches"] == 0, st
            out = b2.get(queued.id)
            assert out is not None and out.status == "done"
            assert out.verdict() is True
        finally:
            b2.shutdown()


# --------------------------------------------------------------- handoff


class TestJournalHandoff:
    def _accept_and_die(self, tmp_path, rid, hists):
        """A replica that 202's `hists` and then dies with everything
        still pending: autostart=False (no worker), journal handle
        dropped, heartbeat never started — only its lease remains, and
        the test backdates or waits that out."""
        svc = make_replica(tmp_path, rid, autostart=False,
                           lease_ttl_s=0.1)
        reqs = [svc.submit([h], workload="register") for h in hists]
        assert all(r.status == "queued" for r in reqs)
        svc._journal.close()
        return svc, reqs

    def test_survivor_adopts_and_finishes(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JGRAFT_CLUSTER_SKEW_S", "0.05")
        hists = [valid_hist(seed=21), invalid_hist(salt=21),
                 valid_hist(seed=22)]
        direct = [r["valid?"] for r in check_histories(
            [h.client_ops() for h in hists], CasRegister())]
        _dead, reqs = self._accept_and_die(tmp_path, "ra", hists)
        time.sleep(0.2)  # ttl 0.1 + skew 0.05 — the lease expires
        b = make_replica(tmp_path, "rb")
        try:
            assert b.cluster.handoff_scan() == 1
            # original ids answer on the survivor (the client's 404
            # failover relies on this)
            adopted = [b.get(r.id) for r in reqs]
            assert all(x is not None for x in adopted)
            for x in adopted:
                assert x.wait(WAIT_S) and x.status == "done"
            assert [x.verdict() for x in adopted] == direct
            st = b.stats()
            assert st["handoff_claims"] == 1
            assert st["handoff_requests"] == len(hists)
            # invariant: nothing orphaned after the handoff
            assert sorted(p.name for p in
                          (tmp_path / "journal").iterdir()) == ["rb"]
            assert sorted(p.name for p in
                          (tmp_path / "leases").glob("*.json")) \
                == ["rb.json"]
        finally:
            b.shutdown()

    def test_claim_is_exclusive_under_race(self, tmp_path, monkeypatch):
        """No double-ownership: two survivors scanning concurrently —
        the atomic rename lets exactly one adopt the dead WAL."""
        monkeypatch.setenv("JGRAFT_CLUSTER_SKEW_S", "0.05")
        self._accept_and_die(tmp_path, "ra", [valid_hist(seed=31)])
        time.sleep(0.2)
        b = make_replica(tmp_path, "rb")
        c = make_replica(tmp_path, "rc")
        try:
            barrier = threading.Barrier(2)
            claims = [0, 0]

            def scan(k, svc):
                barrier.wait()
                claims[k] = svc.cluster.handoff_scan()

            ts = [threading.Thread(target=scan, args=(0, b)),
                  threading.Thread(target=scan, args=(1, c))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert sum(claims) == 1, claims
            assert (b.stats()["handoff_claims"]
                    + c.stats()["handoff_claims"]) == 1
        finally:
            b.shutdown()
            c.shutdown()

    def test_adopted_duplicate_attaches_not_reexecutes(self, tmp_path,
                                                       monkeypatch):
        """Resubmit-at-most-once holds through a handoff: the dead
        replica journaled a primary AND its attached duplicate; the
        survivor re-owns both as one execution."""
        monkeypatch.setenv("JGRAFT_CLUSTER_SKEW_S", "0.05")
        h = valid_hist(seed=41)
        svc = make_replica(tmp_path, "ra", autostart=False,
                           lease_ttl_s=0.1)
        first = svc.submit([h], workload="register")
        dup = svc.submit([h], workload="register")
        assert dup.attached_to == first.id
        svc._journal.close()
        time.sleep(0.2)
        b = make_replica(tmp_path, "rb")
        try:
            assert b.cluster.handoff_scan() == 1
            out_p, out_d = b.get(first.id), b.get(dup.id)
            assert out_p.wait(WAIT_S) and out_d.wait(WAIT_S)
            assert out_p.status == "done" and out_d.status == "done"
            assert out_p.verdict() is True and out_d.verdict() is True
            st = b.stats()
            assert st["handoff_requests"] == 2
            assert st["batches"] <= 1  # one execution for both
        finally:
            b.shutdown()

    def test_restart_republishes_lease_before_heartbeat(self, tmp_path):
        """Regression: shutdown() removes the lease and the heartbeat
        thread's first renewal is a whole beat away — start() must
        re-publish SYNCHRONOUSLY, or a peer scanning in that window
        finds no lease (no ttl+skew grace applies to a missing file)
        and claims a LIVE replica's WAL."""
        a = make_replica(tmp_path, "ra", autostart=False)
        a.shutdown()
        assert read_lease(tmp_path / "leases" / "ra.json") is None
        a.start()
        try:
            lease = read_lease(tmp_path / "leases" / "ra.json")
            assert lease is not None and not lease_expired(lease)
            b = make_replica(tmp_path, "rb")
            try:
                assert b.cluster.handoff_scan() == 0  # ra is LIVE
            finally:
                b.shutdown()
        finally:
            a.shutdown()

    def test_legacy_journal_migrates_when_clustering_enabled(
            self, tmp_path):
        """Regression: enabling --cluster-dir on a daemon that ran
        durable single-replica relocates the WAL root; the PR 8 WAL's
        unfinished entries must migrate and replay, not be silently
        abandoned at the legacy path."""
        store, cdir = tmp_path / "store", tmp_path / "clu"
        s1 = CheckingService(store_root=str(store), name="graftd",
                             batch_wait=0.0, autostart=False)
        req = s1.submit([valid_hist(seed=55)], workload="register")
        s1._journal.close()
        legacy = store / "graftd" / "journal" / "wal.jsonl"
        assert legacy.exists()
        s2 = CheckingService(store_root=str(store), name="graftd",
                             cluster_dir=str(cdir), replica_id="up",
                             batch_wait=0.0, lease_ttl_s=5.0)
        try:
            assert not legacy.exists()
            out = s2.get(req.id)
            assert out is not None and out.wait(WAIT_S)
            assert out.status == "done" and out.verdict() is True
            assert s2.stats()["recovered_requests"] == 1
        finally:
            s2.shutdown()

    def test_live_lease_is_never_claimed(self, tmp_path):
        """Default skew (2 s) + a fresh lease: a peer's scan must not
        touch a live replica's journal."""
        a = make_replica(tmp_path, "ra", autostart=False)
        a.submit([valid_hist(seed=51)], workload="register")
        b = make_replica(tmp_path, "rb")
        try:
            assert b.cluster.handoff_scan() == 0
            assert (tmp_path / "journal" / "ra").exists()
        finally:
            b.shutdown()
            a.shutdown()


# ----------------------------------------------------- shedding and 429s


class TestLoadShedding:
    def test_shed_answers_clusters_best_retry_after(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("JGRAFT_SERVICE_SHED_DEPTH", "1")
        idle = make_replica(tmp_path, "rb")  # advertises ~0.5 s
        loaded = make_replica(tmp_path, "ra", autostart=False)
        try:
            loaded.submit([valid_hist(seed=61)], workload="register")
            with pytest.raises(QueueFull) as ei:
                loaded.submit([invalid_hist(salt=61)],
                              workload="register")
            # own estimate would be depth·EWMA ≥ 1 s; the idle peer's
            # advertisement (0.5 s floor) must win
            assert ei.value.retry_after_s == pytest.approx(0.5, abs=0.2)
        finally:
            idle.shutdown()
            loaded.shutdown()

    def test_shed_disabled_by_default(self, tmp_path):
        svc = make_replica(tmp_path, "ra", autostart=False)
        try:
            assert svc.cluster.shed_depth == 0
            for i in range(5):
                svc.submit([invalid_hist(salt=100 + i)],
                           workload="register")
            assert svc.queue.depth == 5  # nothing shed below capacity
        finally:
            svc.shutdown()


# ------------------------------------------------------- client routing


class _ScriptedTransport:
    """Replaces ServiceClient._call_once: answers per-netloc from a
    script and records every (netloc, attempt) the client makes."""

    def __init__(self, client, script):
        self.calls = []
        self.script = script  # netloc -> callable() -> dict | raise

        def fake(method, path, body=None, netloc=None):
            self.calls.append(netloc)
            return self.script[netloc]()

        client._call_once = fake


class TestClientRouting:
    def _client(self, **kw):
        kw.setdefault("max_attempts", 3)
        kw.setdefault("backoff_base_s", 0.0)
        kw.setdefault("backoff_cap_s", 0.0)
        return ServiceClient("http://a:1", replicas=["http://b:2"], **kw)

    def test_attempt_cap_is_cluster_global_for_status_retries(
            self, monkeypatch):
        """The ISSUE-11 satellite regression: N replicas must not
        multiply max_attempts into N·max_attempts tries."""
        cl = self._client()
        tr = _ScriptedTransport(cl, {
            "a:1": lambda: (_ for _ in ()).throw(
                ServiceError(429, {"error": "full",
                                   "retry_after_s": 0.0})),
            "b:2": lambda: (_ for _ in ()).throw(
                ServiceError(429, {"error": "full",
                                   "retry_after_s": 0.0})),
        })
        monkeypatch.setattr(time, "sleep", lambda s: None)
        with pytest.raises(ServiceError):
            cl._call("POST", "/submit", {})
        assert len(tr.calls) == 3  # == max_attempts, NOT 3 per replica

    def test_attempt_cap_is_cluster_global_for_conn_failures(
            self, monkeypatch):
        cl = self._client()
        tr = _ScriptedTransport(cl, {
            "a:1": lambda: (_ for _ in ()).throw(ConnectionError("down")),
            "b:2": lambda: (_ for _ in ()).throw(ConnectionError("down")),
        })
        monkeypatch.setattr(time, "sleep", lambda s: None)
        with pytest.raises(ConnectionError):
            cl._call("POST", "/submit", {})
        assert len(tr.calls) == 3

    def test_retry_after_floors_the_next_replica_too(self, monkeypatch):
        """A 429's Retry-After is a CLUSTER floor: the retry that moves
        to the next replica still waits it out (the hint already names
        the cluster's best-case slot)."""
        sleeps = []
        monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
        cl = self._client()
        answers = iter([
            lambda: (_ for _ in ()).throw(
                ServiceError(429, {"error": "full",
                                   "retry_after_s": 5.0})),
        ])
        ok = {"id": "x", "status": "queued"}
        tr = _ScriptedTransport(cl, {})
        tr.script = {"a:1": lambda: next(answers)(),
                     "b:2": lambda: ok}
        assert cl._call("POST", "/submit", {}) == ok
        assert tr.calls[0] != tr.calls[1]  # moved to the other replica
        assert sleeps and sleeps[0] >= 5.0  # floor honored across it

    def test_conn_failover_is_immediate(self, monkeypatch):
        """A dead replica is a liveness event: the client rotates to
        the next replica with no backoff sleep."""
        sleeps = []
        monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
        cl = self._client()
        ok = {"id": "x", "status": "queued"}
        tr = _ScriptedTransport(cl, {
            "a:1": lambda: (_ for _ in ()).throw(ConnectionError("down")),
            "b:2": lambda: ok,
        })
        assert cl._call("POST", "/submit", {}) == ok
        assert len(tr.calls) == 2 and not sleeps
        assert cl.failovers == 1

    def test_affinity_routing_is_stable_and_spreads(self):
        cl = ServiceClient("http://a:1",
                           replicas=["http://b:2", "http://c:3"])
        r1 = cl._route("fingerprint-one")
        assert r1 == cl._route("fingerprint-one")  # deterministic
        heads = {cl._route(f"fp-{i}")[0] for i in range(64)}
        assert len(heads) == 3  # rendezvous spreads across the fleet

    def test_result_404_fails_over_to_the_adopting_replica(
            self, tmp_path):
        """After a handoff the request id lives on the survivor; a
        client pointed first at a replica that never saw the id must
        find it (sequential 404 probes, no attempt budget burned)."""
        a = make_replica(tmp_path, "ra")
        b = make_replica(tmp_path, "rb")
        ha, pa, _ = serve_in_thread(a)
        hb, pb, _ = serve_in_thread(b)
        try:
            direct = ServiceClient(f"http://127.0.0.1:{pa}")
            rec = direct.submit([valid_hist(seed=71)],
                                workload="register")
            fleet = ServiceClient(f"http://127.0.0.1:{pb}",
                                  replicas=[f"http://127.0.0.1:{pa}"])
            out = fleet.result(rec["id"], wait_s=60.0)
            assert out["status"] == "done"
            with pytest.raises(ServiceError) as ei:
                fleet.result("no-such-id")
            assert ei.value.status == 404  # all replicas probed, then
            # the 404 surfaces (not an infinite probe loop)
        finally:
            ha.shutdown(); ha.server_close()
            hb.shutdown(); hb.server_close()
            a.shutdown(); b.shutdown()

    def test_single_url_client_unchanged(self):
        cl = ServiceClient("http://a:1")
        assert cl.netlocs == ["a:1"] and cl.netloc == "a:1"
        assert cl._route("anything") == ["a:1"]


# ------------------------------------------- detail exchange (tentpole d)


class TestDetailExchange:
    def test_remote_rows_upgrade_from_store(self, tmp_path, monkeypatch):
        """run_sharded with a configured store: the owning shard
        publishes full per-row details before the verdict exchange and
        the reader merges them into what were PR 7's code-only stubs —
        witnesses/counterexamples follow the verdict across hosts."""
        from jepsen_jgroups_raft_tpu.parallel import distributed
        from jepsen_jgroups_raft_tpu.service.store import (
            ResultStore as RS, detail_fingerprint as dfp)

        monkeypatch.setenv("JGRAFT_RESULT_STORE", str(tmp_path))
        model = CasRegister()
        hists = [valid_hist(seed=81), invalid_hist(salt=81)]
        encs = [encode_history(h.client_ops(), model) for h in hists]
        direct = check_histories([h.client_ops() for h in hists], model)

        # fake a 2-process cluster: we are process 0 and own row 0; the
        # "peer" (process 1) has already published row 1's full detail
        peer_store = RS(tmp_path)
        peer_store.put_detail(dfp(model, "auto", encs[1]), direct[1])
        monkeypatch.setattr(distributed, "process_count", lambda: 2)
        monkeypatch.setattr(distributed, "process_index", lambda: 0)
        codes = {0: distributed._CODE_VALID,
                 1: distributed._CODE_INVALID}

        def fake_exchange(arr, tag=None):
            import numpy as np

            return [np.asarray(arr, dtype="<i8"),
                    np.asarray([codes[1]], dtype="<i8")]

        monkeypatch.setattr(distributed, "exchange_i64", fake_exchange)

        calls = []
        results = distributed.run_sharded(
            encs, lambda sub: (calls.append(len(sub)) or
                               [dict(direct[0])]),
            granularity=1, model=model, algorithm="auto")
        assert calls == [1]  # we checked only our shard
        assert len(results) == 2
        remote = results[1]
        assert remote["valid?"] is False
        assert remote["detail-source"] == "result-store"
        assert remote["process"] == 1
        # the full verdict rode the store — not a code-only stub
        assert remote.get("op-count") == direct[1].get("op-count")

    def test_stub_without_store(self, monkeypatch):
        """No store configured: remote rows stay PR 7 stubs (inert
        seam), and nothing raises."""
        from jepsen_jgroups_raft_tpu.parallel import distributed

        monkeypatch.delenv("JGRAFT_RESULT_STORE", raising=False)
        monkeypatch.delenv("JGRAFT_SERVICE_CLUSTER_DIR", raising=False)
        store, key = distributed._detail_exchange(CasRegister(), "auto")
        assert store is None and key is None

    def test_detail_exchange_inert_without_model(self, tmp_path,
                                                 monkeypatch):
        from jepsen_jgroups_raft_tpu.parallel import distributed

        monkeypatch.setenv("JGRAFT_RESULT_STORE", str(tmp_path))
        store, key = distributed._detail_exchange(None, "auto")
        assert store is None and key is None


# ------------------------------------------------------------- inertness


class TestSingleReplicaInert:
    def test_no_cluster_without_configuration(self, tmp_path,
                                              monkeypatch):
        monkeypatch.delenv("JGRAFT_SERVICE_CLUSTER_DIR", raising=False)
        svc = CheckingService(store_root=str(tmp_path), batch_wait=0.0)
        try:
            assert svc.cluster is None
            st = svc.stats()
            assert st["cluster_enabled"] is False
            assert st["store_hits"] == 0 and st["handoff_claims"] == 0
            # the journal stays in the PR 8 per-daemon layout
            assert (tmp_path / "graftd" / "journal" / "wal.jsonl"
                    ).exists() or svc._journal is not None
        finally:
            svc.shutdown()

    def test_env_seam_engages_cluster(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JGRAFT_SERVICE_CLUSTER_DIR", str(tmp_path))
        monkeypatch.setenv("JGRAFT_SERVICE_REPLICA_ID", "envd")
        svc = CheckingService(store_root=None, batch_wait=0.0)
        try:
            assert svc.cluster is not None
            assert svc.cluster.replica_id == "envd"
            # the WAL rides the shared cluster layout (file appears on
            # first append; the path is pinned here)
            assert svc._journal is not None
            assert svc._journal.path == \
                tmp_path / "journal" / "envd" / "wal.jsonl"
            assert svc.stats()["cluster_enabled"] is True
        finally:
            svc.shutdown()
