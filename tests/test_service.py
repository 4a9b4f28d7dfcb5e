"""graftd (service/) tests — ISSUE 5 tentpole.

Tier-1, CPU-only (conftest pins the 8-vdev host mesh), no unconditional
sleeps: every wait is an Event/poll with a timeout bound. The load-
bearing assertions mirror the acceptance criteria: cross-request
batching engages (one launch carries rows from ≥2 requests) with every
demuxed verdict identical to a direct `linearizable.check_histories` of
the same history; identical resubmission is a cache hit; an injected
mid-check device failure completes via the CPU fallback with
`platform-degraded` stamped instead of erroring the request; the
scheduler honors deadlines, cancellation (queued AND mid-chunk),
backpressure rejection, and worker-thread death.
"""

from __future__ import annotations

import json
import random
import threading

import pytest

from jepsen_jgroups_raft_tpu.checker.linearizable import (check_encoded,
                                                          check_histories)
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.models import CasRegister
from jepsen_jgroups_raft_tpu.service import (CheckingService, QueueFull,
                                             ServiceClient, ServiceError,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.request import (admit,
                                                     fingerprint_encodings)
from jepsen_jgroups_raft_tpu.service.scheduler import (PRIORITY_CREDIT_S,
                                                       bucket_signature,
                                                       effective_deadline)

from util import H, random_valid_history

WAIT_S = 120.0  # upper bound, not a sleep: first XLA compile dominates


def valid_hist(n_ops=20, seed=7):
    return random_valid_history(random.Random(seed), "register",
                                n_ops=n_ops, crash_p=0.0)


def invalid_hist(n_ops=20, salt=0):
    """Sequential writes ending in a read no write produced: no
    linearization exists. Sized like `valid_hist` (n_ops completed
    pairs) so valid and invalid submissions share one shape bucket —
    the coalescing tests rely on riding the same launch. `salt` makes
    the CONTENT distinct across calls: byte-identical submissions now
    attach idempotently (ISSUE 8) instead of executing separately, so
    tests that want N independent requests need N fingerprints."""
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


def wait_all(reqs):
    for r in reqs:
        assert r.wait(WAIT_S), f"request {r.id} stuck in {r.status}"


# -------------------------------------------------------------- batching


class TestBatching:
    def test_coalesces_with_bitwise_identical_verdicts(self):
        """≥8 pending requests in one shape bucket ride ONE launch
        batch, and every demuxed verdict equals the direct check of the
        same history in isolation (acceptance bar)."""
        hists = [valid_hist(seed=i) if i % 3 else invalid_hist(salt=i)
                 for i in range(8)]
        svc = make_service(autostart=False)
        reqs = [svc.submit([h], workload="register") for h in hists]
        assert svc.queue.depth == 8
        svc.start()
        wait_all(reqs)
        svc.shutdown(wait=True)

        direct = [r["valid?"] for r in check_histories(hists, CasRegister())]
        assert [r.verdict() for r in reqs] == direct
        assert True in direct and False in direct  # both verdicts exercised
        # Cross-request coalescing engaged: every request rode a launch
        # with ≥2 requests' rows (the synth histories straddle one
        # event-bucket boundary, so up to two bucket batches form —
        # never one launch per request).
        for r in reqs:
            assert r.stats["batched_requests"] >= 2
            assert r.stats["batch_rows"] == r.stats["batched_requests"]
            # request identity threaded through the scan scope label
            assert r.id in r.stats["scan"]["label"]
        st = svc.stats()
        assert st["batches"] <= 2
        assert st["batched_requests"] == 8
        assert st["batch_occupancy_mean"] >= 2.0

    def test_concurrent_submitters_coalesce(self):
        """The sustained-concurrency shape: 8 submitter threads against
        a LIVE daemon; the linger window coalesces at least one launch
        across requests, and all verdicts are correct."""
        hists = [valid_hist(seed=100 + i) for i in range(8)]
        svc = make_service(batch_wait=0.1)
        reqs = [None] * 8
        barrier = threading.Barrier(8)

        def submit(i):
            barrier.wait(timeout=10)
            reqs[i] = svc.submit([hists[i]], workload="register")

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        wait_all(reqs)
        svc.shutdown(wait=True)
        assert all(r.verdict() is True for r in reqs)
        assert max(r.stats["batched_requests"] for r in reqs) >= 2
        assert svc.stats()["batches"] < 8  # strictly fewer launches

    def test_decided_tier_counters_per_request_and_daemon(self):
        """ISSUE 13: every demuxed verdict attributes a decision-ladder
        tier — per-request stats (the trace record's capacity-model
        evidence) and the daemon-wide /stats decided_tier counters are
        both present and non-degenerate."""
        svc = make_service()
        try:
            r = svc.submit([valid_hist(seed=5)], workload="register")
            s = svc.submit([valid_hist(seed=6)], workload="register",
                           consistency="sequential")
            assert r.wait(60) and s.wait(60)
            assert sum(r.stats["decided_tier"].values()) == 1
            assert sum(s.stats["decided_tier"].values()) == 1
            # the weak-rung request decided on a cheap tier
            assert set(s.stats["decided_tier"]) & \
                {"greedy", "backtrack", "cycle"}
            st = svc.stats()
            assert sum(st["decided_tier"].values()) >= 2
            assert r.results[0]["decided-tier"] in \
                ("dense", "mask", "sort", "host", "trivial")
        finally:
            svc.shutdown(wait=True)

    def test_multi_history_requests_demux_by_row(self):
        a = [valid_hist(seed=1), invalid_hist(), valid_hist(seed=2)]
        b = [invalid_hist()]
        svc = make_service(autostart=False)
        ra = svc.submit(a, workload="register")
        rb = svc.submit(b, workload="register")
        svc.start()
        wait_all([ra, rb])
        svc.shutdown(wait=True)
        assert [r["valid?"] for r in ra.results] == [True, False, True]
        assert [r["valid?"] for r in rb.results] == [False]
        assert ra.verdict() is False and rb.verdict() is False

    def test_bucket_signature_separates_shapes(self):
        r_small = admit([valid_hist(n_ops=16)], "register")
        r_small2 = admit([valid_hist(n_ops=18, seed=9)], "register")
        r_big = admit([valid_hist(n_ops=400)], "register")
        assert bucket_signature(r_small) == bucket_signature(r_small2)
        assert bucket_signature(r_small) != bucket_signature(r_big)


# ------------------------------------------------------- cache + encode


class TestCacheAndEncoding:
    def test_identical_resubmission_is_cache_hit(self):
        h = valid_hist(seed=42)
        svc = make_service(autostart=False)
        r1 = svc.submit([h], workload="register")
        svc.start()
        wait_all([r1])
        r2 = svc.submit([h], workload="register")
        assert r2.cached and r2.status == "done"
        assert [x["valid?"] for x in r2.results] == \
               [x["valid?"] for x in r1.results]
        st = svc.stats()
        assert st["cache_hits"] == 1
        svc.shutdown(wait=True)

    def test_fingerprint_keys_on_content_and_algorithm(self):
        h = valid_hist(seed=3)
        m = CasRegister()
        e1 = [encode_history(h, m)]
        e2 = [encode_history(valid_hist(seed=3), m)]
        e3 = [encode_history(valid_hist(seed=4), m)]
        assert fingerprint_encodings(m, "auto", e1) == \
               fingerprint_encodings(m, "auto", e2)
        assert fingerprint_encodings(m, "auto", e1) != \
               fingerprint_encodings(m, "auto", e3)
        assert fingerprint_encodings(m, "auto", e1) != \
               fingerprint_encodings(m, "cpu", e1)

    def test_check_encoded_is_pack_once_check_many(self):
        """The refactored entry: encode once, check twice — verdicts
        stable and identical to the encode-inside wrapper."""
        hists = [valid_hist(seed=5), invalid_hist()]
        m = CasRegister()
        encs = [encode_history(h, m) for h in hists]
        v1 = [r["valid?"] for r in check_encoded(encs, m)]
        v2 = [r["valid?"] for r in check_encoded(encs, m)]
        v3 = [r["valid?"] for r in check_histories(hists, m)]
        assert v1 == v2 == v3 == [True, False]


# ------------------------------------------------- deadlines + ordering


class TestDeadlineScheduling:
    def test_deadline_order_across_buckets(self):
        """Three pending requests in three different shape buckets:
        execution order follows the deadline, not arrival."""
        svc = make_service(autostart=False)
        late = svc.submit([valid_hist(n_ops=16, seed=1)],
                          workload="register", deadline_ms=60_000)
        mid = svc.submit([valid_hist(n_ops=400, seed=2)],
                         workload="register", deadline_ms=20_000)
        soon = svc.submit(
            [random_valid_history(random.Random(3), "counter", n_ops=16,
                                  crash_p=0.0)],
            workload="counter", deadline_ms=1_000)
        wait = [soon, mid, late]
        svc.start()
        wait_all(wait)
        svc.shutdown(wait=True)
        seqs = [r.stats["batch_seq"] for r in (soon, mid, late)]
        assert seqs == sorted(seqs), seqs
        assert len(set(seqs)) == 3  # three buckets → three launches

    def test_priority_clamped_at_admission(self):
        # a client-supplied flood priority cannot buy more than ±8s of
        # deadline credit — the starvation-free guarantee's bound
        hot = admit([valid_hist(n_ops=8)], "register", priority=10**6)
        cold = admit([valid_hist(n_ops=8)], "register", priority=-(10**6))
        assert hot.priority == 8 and cold.priority == -8

    def test_effective_deadline_aging_and_priority(self):
        # a near deadline (10s) beats the 30s aging cap: key == deadline
        r = admit([valid_hist(n_ops=8)], "register", deadline_ms=10_000)
        assert effective_deadline(r) == pytest.approx(r.deadline)
        far = admit([valid_hist(n_ops=8)], "register",
                    deadline_ms=3_600_000)
        # far deadline is capped by aging: key stops receding at +30s
        assert effective_deadline(far) == pytest.approx(far.submitted + 30.0)
        hot = admit([valid_hist(n_ops=8)], "register",
                    deadline_ms=3_600_000, priority=5)
        assert effective_deadline(hot) == pytest.approx(
            hot.submitted + 30.0 - 5 * PRIORITY_CREDIT_S)


# ------------------------------------------------------- cancellation


class TestCancellation:
    def test_cancel_while_queued_never_executes(self):
        svc = make_service(autostart=False)
        req = svc.submit([valid_hist()], workload="register")
        assert svc.cancel(req.id) == "cancelled"
        assert req.status == "cancelled" and req.results is None
        svc.start()
        svc.shutdown(wait=True)
        st = svc.stats()
        assert st["cancelled"] == 1 and st["batches"] == 0

    def test_cancel_mid_chunk_discards_verdict(self):
        """Cancel landing while the request's launch is in flight: the
        row work completes but the verdict is not delivered and the
        request finalizes CANCELLED (demux-time honor)."""
        started, release = threading.Event(), threading.Event()

        def gated(encs, model, algorithm="auto", **kw):
            started.set()
            assert release.wait(30)
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        svc = make_service(check_fn=gated)
        req = svc.submit([valid_hist()], workload="register")
        assert started.wait(30)
        assert svc.cancel(req.id) in ("running", "cancelled")
        release.set()
        assert req.wait(WAIT_S)
        svc.shutdown(wait=True)
        assert req.status == "cancelled"
        assert req.results is None
        assert svc.stats()["cancelled"] == 1

    def test_cancel_unknown_id(self):
        svc = make_service(autostart=False)
        assert svc.cancel("nope") is None
        svc.shutdown(wait=True)


# ------------------------------------------------------- backpressure


class TestBackpressure:
    def test_queue_full_rejects_with_retry_after(self):
        svc = make_service(autostart=False, queue_capacity=2)
        svc.submit([valid_hist(seed=1)], workload="register")
        svc.submit([valid_hist(seed=2)], workload="register")
        with pytest.raises(QueueFull) as exc:
            svc.submit([valid_hist(seed=3)], workload="register")
        assert exc.value.retry_after_s >= 0.5
        assert svc.stats()["rejected"] == 1
        # the rejected request never entered the registry
        assert len(svc._requests) == 2
        svc.shutdown(wait=True)

    def test_rejection_never_oversubscribes_queue(self):
        svc = make_service(autostart=False, queue_capacity=3)
        for i in range(3):
            svc.submit([valid_hist(seed=i)], workload="register")
        for i in range(4):
            with pytest.raises(QueueFull):
                svc.submit([valid_hist(seed=10 + i)], workload="register")
        assert svc.queue.depth == 3
        svc.shutdown(wait=True)


# ------------------------------------------------------ degrade-to-CPU


class TestDegradeToCpu:
    def test_injected_device_failure_degrades_with_stamp(self, monkeypatch):
        import jepsen_jgroups_raft_tpu.platform as plat

        monkeypatch.setattr(plat, "_DEGRADED_NOTE", None)
        calls = {"n": 0}

        def dying(encs, model, algorithm="auto", **kw):
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: device lost mid-check")

        hists = [valid_hist(seed=1), invalid_hist()]
        svc = make_service(check_fn=dying, autostart=False)
        req = svc.submit(hists, workload="register")
        svc.start()
        assert req.wait(WAIT_S)
        assert req.status == "done", req.error
        # sound verdicts from the host ladder, degrade stamped per result
        assert [r["valid?"] for r in req.results] == [True, False]
        for r in req.results:
            assert "platform-degraded" in r
            assert "graftd degraded to host CPU" in r["platform-degraded"]
        assert req.stats["degraded"] is True
        assert svc.stats()["degraded_batches"] == 1
        assert plat.degraded_note() is not None  # note_degraded reused
        # degraded verdicts are NOT cached: a healthy resubmission
        # re-checks instead of replaying the stamp
        req2 = svc.submit(hists, workload="register")
        assert not req2.cached
        svc.cancel(req2.id)
        svc.shutdown(wait=True)
        assert calls["n"] >= 1

    def test_non_platform_degrade_does_not_poison_later_batches(self,
                                                                monkeypatch):
        """A one-off NON-platform failure degrades only its own batch:
        the process-wide first-note-wins registry stays unset, so a
        later healthy batch's results carry no platform-degraded stamp
        (the long-lived-daemon poisoning mode)."""
        import jepsen_jgroups_raft_tpu.platform as plat

        monkeypatch.setattr(plat, "_DEGRADED_NOTE", None)
        first = threading.Event()

        def flaky(encs, model, algorithm="auto", **kw):
            if not first.is_set():
                first.set()
                raise ValueError("one-off kernel bug, not the platform")
            return check_encoded(encs, model, algorithm=algorithm, **kw)

        svc = make_service(check_fn=flaky, autostart=False)
        r1 = svc.submit([valid_hist(seed=1)], workload="register")
        svc.start()
        assert r1.wait(WAIT_S) and r1.status == "done"
        assert all("platform-degraded" in res for res in r1.results)
        assert plat.degraded_note() is None  # registry NOT written
        r2 = svc.submit([valid_hist(seed=2)], workload="register")
        assert r2.wait(WAIT_S) and r2.status == "done"
        assert all("platform-degraded" not in res for res in r2.results)
        assert r2.stats["degraded"] is False
        svc.shutdown(wait=True)

    def test_host_fallback_failure_fails_request_not_daemon(self):
        def dying(encs, model, algorithm="auto", **kw):
            raise RuntimeError("device down")

        def broken_fallback(enc, model):
            raise ValueError("host ladder broken too")

        svc = make_service(check_fn=dying, host_fallback=broken_fallback,
                           autostart=False)
        req = svc.submit([valid_hist()], workload="register")
        svc.start()
        assert req.wait(WAIT_S)
        assert req.status == "failed" and req.error
        # daemon still serves: a later healthy submission completes
        svc.scheduler.check_fn = check_encoded
        req2 = svc.submit([valid_hist(seed=8)], workload="register")
        assert req2.wait(WAIT_S)
        assert req2.verdict() is True
        svc.shutdown(wait=True)


# --------------------------------------------------- worker resilience


class TestWorkerResilience:
    def test_worker_death_restarts_without_losing_queue(self):
        svc = make_service()
        orig = svc.scheduler.next_batch
        tripped = threading.Event()

        def bomb(timeout, **kw):
            if not tripped.is_set():
                tripped.set()
                raise RuntimeError("injected worker death")
            return orig(timeout, **kw)

        svc.scheduler.next_batch = bomb
        # Wait for the bomb to actually kill the worker BEFORE
        # submitting — the pre-existing worker's in-flight next_batch
        # call could otherwise serve the request first.
        assert tripped.wait(10)
        req = svc.submit([valid_hist(seed=11)], workload="register")
        assert req.wait(WAIT_S)
        assert req.verdict() is True
        st = svc.stats()
        assert st["worker_restarts"] == 1
        assert st["worker_alive"]
        svc.shutdown(wait=True)
        assert not svc.stats()["worker_alive"]

    def test_submit_after_shutdown_is_loud(self):
        from jepsen_jgroups_raft_tpu.service.daemon import ServiceStopped

        svc = make_service(autostart=False)
        svc.shutdown(wait=True)
        with pytest.raises(ServiceStopped):
            svc.submit([valid_hist()], workload="register")

    def test_terminal_requests_are_evicted_past_retention(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_SERVICE_RETAIN", "2")
        svc = make_service(autostart=False)
        assert svc._retain == 2
        reqs = [svc.submit([valid_hist(seed=50 + i)], workload="register")
                for i in range(3)]
        svc.start()
        wait_all(reqs)
        svc.shutdown(wait=True)
        # oldest terminal request evicted, newest two still queryable
        alive = [svc.get(r.id) is not None for r in reqs]
        assert alive.count(True) == 2
        assert svc.get(reqs[-1].id) is not None

    def test_shutdown_fails_queued_loudly_and_joins(self):
        svc = make_service(autostart=False)
        before = set(threading.enumerate())
        req = svc.submit([valid_hist()], workload="register")
        svc.shutdown(wait=True)
        assert req.status == "failed"
        assert "shut down" in req.error
        # no thread THIS daemon created survives (enumerate() is
        # process-global; earlier tests' threads may still be draining)
        assert not any(t.name.startswith("graftd")
                       for t in threading.enumerate()
                       if t not in before)


# ------------------------------------------------------ traces + store


class TestTraceRecords:
    def test_trace_lands_in_store_layout(self, tmp_path):
        svc = make_service(store_root=str(tmp_path), autostart=False)
        req = svc.submit([valid_hist(seed=6)], workload="register")
        svc.start()
        wait_all([req])
        svc.shutdown(wait=True)
        entries = list((tmp_path / "graftd").iterdir())
        # the admission journal (ISSUE 8) lives next to the trace dirs
        assert (tmp_path / "graftd" / "journal" / "wal.jsonl").exists()
        runs = [d for d in entries if d.name != "journal"]
        assert len(runs) == 1 and req.id in runs[0].name
        rec = json.loads((runs[0] / "results.json").read_text())
        assert rec["valid?"] is True
        assert rec["service-stats"]["batched_requests"] == 1
        assert (runs[0] / "history.jsonl").exists()
        # the results browser picks it up like a test run
        from jepsen_jgroups_raft_tpu.core.serve import _index_html, _verdict
        assert _verdict(runs[0]) is True
        assert req.id in _index_html(tmp_path)

    def test_run_dir_submission(self, tmp_path):
        from jepsen_jgroups_raft_tpu.core.store import save_test

        h = H((0, "invoke", "write", (1, 4)), (0, "ok", "write", (1, 4)),
              (1, "invoke", "read", (1, None)), (1, "ok", "read", (1, 4)))
        run_dir = save_test({"name": "svcrun", "workload": "single-register",
                             "store_root": str(tmp_path)}, h,
                            {"valid?": True})
        svc = make_service(autostart=False)
        req = svc.submit_run_dir(run_dir)
        svc.start()
        wait_all([req])
        svc.shutdown(wait=True)
        assert req.verdict() is True
        assert req.workload == "single-register"
        assert len(req.units) == 1  # one key


# --------------------------------------------------------------- HTTP


class TestHttpSurface:
    @pytest.fixture()
    def live(self):
        svc = make_service(batch_wait=0.05)
        httpd, port, _ = serve_in_thread(svc)
        try:
            yield svc, ServiceClient(f"http://127.0.0.1:{port}",
                                     timeout=WAIT_S)
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_submit_result_roundtrip(self, live):
        svc, client = live
        rec = client.check([valid_hist(seed=21), invalid_hist()],
                           workload="register", timeout_s=WAIT_S)
        assert rec["status"] == "done"
        assert rec["valid?"] is False
        assert [r["valid?"] for r in rec["results"]] == [True, False]
        stats = client.stats()
        assert stats["completed"] >= 1
        assert client.healthz()["ok"] is True

    def test_http_backpressure_is_429_with_retry_after(self):
        svc = make_service(autostart=False, queue_capacity=1)
        httpd, port, _ = serve_in_thread(svc)
        client = ServiceClient(f"http://127.0.0.1:{port}")
        try:
            client.submit([valid_hist(seed=1)], workload="register")
            with pytest.raises(ServiceError) as exc:
                client.submit([valid_hist(seed=2)], workload="register")
            assert exc.value.status == 429
            assert exc.value.retry_after_s >= 0.5
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)

    def test_http_client_errors(self, live):
        svc, client = live
        with pytest.raises(ServiceError) as exc:
            client.result("missing-id")
        assert exc.value.status == 404
        with pytest.raises(ServiceError) as exc:
            client.submit([valid_hist()], workload="no-such-workload")
        assert exc.value.status == 400
        with pytest.raises(ServiceError) as exc:
            client.cancel("missing-id")
        assert exc.value.status == 404
        # a non-numeric priority is a 400, not an aborted connection
        with pytest.raises(ServiceError) as exc:
            client.submit([valid_hist()], workload="register",
                          priority="high")
        assert exc.value.status == 400

    def test_http_cancel_queued(self):
        svc = make_service(autostart=False)
        httpd, port, _ = serve_in_thread(svc)
        client = ServiceClient(f"http://127.0.0.1:{port}")
        try:
            rec = client.submit([valid_hist()], workload="register")
            out = client.cancel(rec["id"])
            assert out["status"] == "cancelled"
            assert client.result(rec["id"])["status"] == "cancelled"
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)


# ------------------------------------------------------- admission API


class TestAdmission:
    def test_unknown_workload_rejected_before_queue(self):
        svc = make_service(autostart=False)
        with pytest.raises(ValueError):
            svc.submit([valid_hist()], workload="bogus")
        assert svc.queue.depth == 0
        svc.shutdown(wait=True)

    def test_empty_submission_rejected(self):
        with pytest.raises(ValueError):
            admit([], "register")

    def test_independent_workload_splits_per_key(self):
        h = H((0, "invoke", "write", (1, 4)), (0, "ok", "write", (1, 4)),
              (1, "invoke", "write", (2, 5)), (1, "ok", "write", (2, 5)))
        req = admit([h], "multi-register")
        assert len(req.units) == 2
        assert {label.split("key=")[1] for label, _ in req.units} == \
               {"1", "2"}
