"""Linearizable-rung pre-kernel fast path (ISSUE 14): verdict-identity
differential matrix, @lin tier attribution, the weak-rung double-scan
skip, measured per-bucket gating, the certify abort budget, and the
graftd dispatch fast lane.

The suite opts INTO the fast path per test (tests/conftest.py pins
``JGRAFT_LIN_FASTPATH=0`` so the kernel-path suites keep seeing
launches); ``JGRAFT_AUTOTUNE`` stays 0 except in the gating tests, so
no host-dependent gate state leaks between tests.
"""

from __future__ import annotations

import random

import pytest

from jepsen_jgroups_raft_tpu.checker import autotune
from jepsen_jgroups_raft_tpu.checker.base import INVALID, VALID
from jepsen_jgroups_raft_tpu.checker.consistency import (
    StreamingCertifier, certify_encoded)
from jepsen_jgroups_raft_tpu.checker.linearizable import (
    check_encoded, check_encoded_host, check_histories,
    consume_fastpath_counters, fastpath_counters)
from jepsen_jgroups_raft_tpu.checker.schedule import (consume_tiers,
                                                      snapshot_tiers)
from jepsen_jgroups_raft_tpu.history.ops import History, Op
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.models import (CasRegister, Counter, GSet,
                                            TicketQueue)

from util import H, corrupt, random_valid_history

MODELS = {
    "register": CasRegister,   # covers the register AND cas op mix
    "counter": Counter,
    "set": GSet,
    "queue": TicketQueue,
}


def poisoned(h: History) -> History:
    """Append write w1; write w2; read w1 — all sequential on one fresh
    process — making the history INVALID at every rung (program order
    alone refutes it) while the certifier still scans the whole stream
    before coming up undecided: the fast path's worst case."""
    ops = list(h)
    t = max((op.time for op in ops), default=0) + 1
    p = 9999
    for i, (f, v, typ) in enumerate((
            ("write", 777001, "invoke"), ("write", 777001, "ok"),
            ("write", 777002, "invoke"), ("write", 777002, "ok"),
            ("read", None, "invoke"), ("read", 777001, "ok"))):
        ops.append(Op(process=p, type=typ, f=f, value=v, time=t + i))
    return History(ops)


def mixed_batch(kind: str, n: int = 8, n_ops: int = 40) -> list:
    """Valid + corrupted histories for one family (both polarities)."""
    rng = random.Random(11)
    out = []
    for i in range(n):
        h = random_valid_history(rng, kind, n_ops=n_ops, n_procs=4,
                                 crash_p=0.05, max_crashes=2)
        out.append(corrupt(rng, h) if i % 3 == 0 else h)
    return out


# ------------------------------------------------- differential matrix


@pytest.mark.parametrize("kind", sorted(MODELS))
@pytest.mark.parametrize("macro", ["1", "0"])
@pytest.mark.parametrize("chunk", ["128", "0"])
def test_fastpath_verdict_identity_matrix(kind, macro, chunk,
                                          monkeypatch):
    """ISSUE-14 soundness gate: verdicts bitwise-identical fast path on
    vs force-disabled, across all model families x macro on/off x
    chunked/monolithic, with both polarities in the batch."""
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", macro)
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", chunk)
    model = MODELS[kind]()
    hists = mixed_batch(kind)
    verdicts = {}
    for fp in ("1", "0"):
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", fp)
        verdicts[fp] = [r["valid?"] for r in
                        check_histories(hists, model, algorithm="jax")]
    assert verdicts["1"] == verdicts["0"], verdicts
    assert True in verdicts["1"] and False in verdicts["1"]


def test_fastpath_results_carry_lin_namespaced_tier(monkeypatch):
    """Certified rows attribute ``greedy@lin``/``backtrack@lin`` —
    never the weak-rung certifier's bare greedy/backtrack — end to end
    through the result dicts and the note_tier counters."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    rng = random.Random(5)
    m = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=60, n_procs=4,
                                  crash_p=0.05, max_crashes=2)
             for _ in range(8)]
    consume_tiers()
    consume_fastpath_counters()
    rs = check_histories(hists, m, algorithm="jax")
    certified = [r for r in rs if r["algorithm"] == "greedy-witness"]
    assert certified, "fast path never engaged on a valid batch"
    for r in certified:
        assert r["decided-tier"] in ("greedy@lin", "backtrack@lin"), r
    tiers = snapshot_tiers()
    assert set(tiers) & {"greedy@lin", "backtrack@lin"}
    assert "greedy" not in tiers and "backtrack" not in tiers
    c = fastpath_counters()
    assert c["rows_certified"] == len(certified)
    assert c["rows_scanned"] == len(hists)


def test_trivial_rows_keep_trivial_tier(monkeypatch):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    m = CasRegister()
    [r] = check_encoded([encode_history(H(), m)], m, algorithm="jax")
    assert r["decided-tier"] == "trivial"


def test_explicit_cpu_algorithm_keeps_its_engine(monkeypatch):
    """"cpu"/"dfs" are oracle selectors — the fast path only fronts
    the kernel-launching algorithms."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    rng = random.Random(5)
    m = CasRegister()
    h = random_valid_history(rng, "register", n_ops=30, crash_p=0.0)
    [r] = check_histories([h], m, algorithm="cpu")
    assert r["algorithm"] == "cpu"
    [r] = check_histories([h], m, algorithm="dfs")
    assert r["algorithm"] == "dfs"


# --------------------------------------------- weak-rung double-scan


def test_weak_rung_reentry_skips_second_scan(monkeypatch):
    """ISSUE-14 satellite: rows the rung certifier already failed to
    certify re-enter check_encoded at the lin rung with the fast path
    suppressed — the counter proves the skip fires, and the redundant
    scan counter proves nothing was scanned twice."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    # cycle tier off: the poisoned history is cycle-refutable, which
    # would decide it BEFORE the kernel re-entry this test pins
    monkeypatch.setenv("JGRAFT_CYCLE_TIER", "0")
    m = CasRegister()
    # sequential-INVALID (program order alone refutes it), so the rung
    # certifier fails on both streams and the kernel re-entry happens
    bad = poisoned(random_valid_history(random.Random(2), "register",
                                        n_ops=20, crash_p=0.0))
    consume_fastpath_counters()
    rs = check_histories([bad], m, algorithm="jax",
                         consistency="sequential")
    assert rs[0]["valid?"] is INVALID
    c = consume_fastpath_counters()
    assert c["rows_rung_skipped"] == 1
    assert c["rows_scanned"] == 0  # the lin pass never re-scanned
    # with the fast path force-disabled there is no scan to save: the
    # counter must stay silent (a JGRAFT_LIN_FASTPATH=0 ablation run's
    # stored results must not claim fast-path engagement)
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    check_histories([bad], m, algorithm="jax",
                    consistency="sequential")
    assert consume_fastpath_counters()["rows_rung_skipped"] == 0


# ------------------------------------------------------- abort budget


def test_certify_abort_budget_returns_undecided_never_wrong():
    m = CasRegister()
    rng = random.Random(7)
    h = random_valid_history(rng, "register", n_ops=40, crash_p=0.05)
    enc = encode_history(h.client_ops(), m)
    assert certify_encoded(enc, m)[0] is True
    ok, tier, _ = certify_encoded(enc, m, max_steps=2)
    assert ok is False and tier is None


def test_tiny_abort_budget_keeps_verdicts_identical(monkeypatch):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH_ABORT", "1")
    m = CasRegister()
    hists = mixed_batch("register")
    rs = check_histories(hists, m, algorithm="jax")
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    ref = check_histories(hists, m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [r["valid?"] for r in ref]


# ------------------------------------------------------ gating (autotune)


def test_low_hit_bucket_routes_kernel_first(monkeypatch, tmp_path):
    """ISSUE-14 acceptance satellite: a seeded low-hit bucket (all
    rows uncertifiable) trains the measured gate; later batches route
    kernel-first (rows_gated fires, nothing scanned) with verdicts
    unchanged, and the record is persisted in the host-fingerprinted
    store."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path))
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH_MIN_OBS", "8")
    autotune.reset_for_tests()
    m = CasRegister()
    rng = random.Random(9)
    # one uncertifiable history, repeated: every row lands in ONE
    # gating bucket, so the 8-row batch crosses MIN_OBS in one run
    hists = [poisoned(random_valid_history(rng, "register", n_ops=20,
                                           crash_p=0.0))] * 8
    consume_fastpath_counters()
    rs1 = check_histories(hists, m, algorithm="jax")
    c1 = consume_fastpath_counters()
    assert c1["rows_scanned"] == 8 and c1["rows_certified"] == 0
    # the record landed in the fingerprint store
    files = list((tmp_path / autotune.host_fingerprint()).glob(
        "linfp-*.json"))
    assert files, "gating record was not persisted"
    sig = autotune.lin_fastpath_sig(
        "CasRegister",
        encode_history(hists[0].client_ops(), m).n_events)
    assert autotune.lin_fastpath_route(sig) is False
    rs2 = check_histories(hists, m, algorithm="jax")
    c2 = consume_fastpath_counters()
    assert c2["rows_gated"] == 8 and c2["rows_scanned"] == 0
    assert [r["valid?"] for r in rs1] == [r["valid?"] for r in rs2]
    assert all(r["valid?"] is INVALID for r in rs2)
    # a fresh in-memory state reloads the persisted record (the
    # cross-process half of the gate)
    autotune.reset_for_tests()
    assert autotune.lin_fastpath_route(sig) is False


def test_gating_off_without_autotune(monkeypatch, tmp_path):
    """JGRAFT_AUTOTUNE=0 (the deterministic-test arm): the fast path
    always tries and persists nothing."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "0")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path))
    m = CasRegister()
    sig = autotune.lin_fastpath_sig("CasRegister", 40)
    autotune.lin_fastpath_observe(sig, rows=100, hits=0, wall_s=0.1)
    assert autotune.lin_fastpath_route(sig) is True
    assert not list(tmp_path.glob("**/linfp-*.json"))


def _gate_env(monkeypatch, tmp_path, min_obs="64"):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path))
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH_MIN_OBS", min_obs)
    autotune.reset_for_tests()


#: (launch rows, rows scanned, verdicts used, certifier s a scanned row,
#:  kernel rows, kernel s a row) -> host-first?
COST_RULE_CASES = {
    # the campaign cell, had partial eviction counted: 58 ms a used
    # verdict against 3 ms a kernel row
    "half-used-29ms-vs-3ms": ((256, 256, 128, 0.029, 256, 0.003), False),
    # PR 22's 1000 register rows on the chip: 15.5 ms against 1.9 ms —
    # the old 5 % floor never closed a 0.97 hit rate
    "nearly-all-used-15ms-vs-1.9ms": ((1000, 1000, 970, 0.015,
                                       1000, 0.0019), False),
    # a one-history launch: 8.2 ms against 60 ms stays host-first
    "row-class-1-8ms-vs-60ms": ((1, 100, 97, 0.008, 100, 0.060), True),
    # nothing delivered is decisive alone: no kernel sample needed
    "nothing-used-no-kernel-sample": ((256, 64, 0, 0.029, 0, 0.0),
                                      False),
    # either side under min_obs with something used: still unknown
    "certifier-side-under-min-obs": ((256, 63, 1, 0.029, 256, 0.003),
                                     True),
    "kernel-side-under-min-obs": ((256, 256, 128, 0.029, 63, 0.003),
                                  True),
}


@pytest.mark.parametrize("case", sorted(COST_RULE_CASES))
def test_gate_routes_on_cost_per_used_verdict(case, monkeypatch,
                                              tmp_path):
    """ISSUE 28: host-first iff a side is still unknown or a verdict
    the caller used is cheaper from the certifier than a row is through
    the kernels at that row class — in memory and from the persisted
    record alike."""
    _gate_env(monkeypatch, tmp_path)
    (launch, rows, used, cert_s, k_rows, k_s), host_first = \
        COST_RULE_CASES[case]
    sig = autotune.lin_fastpath_sig("Counter", 2000, launch)
    autotune.lin_fastpath_observe(sig, rows=rows, hits=used,
                                  wall_s=rows * cert_s)
    autotune.lin_fastpath_observe_kernel(sig, rows=k_rows,
                                         wall_s=k_rows * k_s)
    assert autotune.lin_fastpath_route(sig) is host_first
    autotune.reset_for_tests()   # a later process reads the record
    assert autotune.lin_fastpath_route(sig) is host_first
    # another row class of the same family and event bucket has its
    # own record: nothing was learned for it
    other = autotune.lin_fastpath_sig("Counter", 2000, 4 * launch + 64)
    assert other != sig and autotune.lin_fastpath_route(other) is True


def test_host_ladder_keeps_its_own_row_class(monkeypatch, tmp_path):
    """`check_encoded_host`'s alternative is the host search: its
    bucket (`LINFP_NO_LAUNCH`) is never closed by a kernel launch's
    cost, only by having delivered nothing."""
    _gate_env(monkeypatch, tmp_path, min_obs="4")
    m = CasRegister()
    good = encode_history(random_valid_history(
        random.Random(1), "register", n_ops=20,
        crash_p=0.0).client_ops(), m)
    launch_sig = autotune.lin_fastpath_sig("CasRegister", good.n_events)
    autotune.lin_fastpath_observe(launch_sig, rows=8, hits=0,
                                  wall_s=1.0)
    assert autotune.lin_fastpath_route(launch_sig) is False
    consume_fastpath_counters()
    for _ in range(6):
        r = check_encoded_host(good, m)
        assert r["decided-tier"] in ("greedy@lin", "backtrack@lin")
    c = consume_fastpath_counters()
    assert c["rows_scanned"] == 6 and c["rows_delivered"] == 6
    assert c["rows_gated"] == 0
    host_sig = autotune.lin_fastpath_sig("CasRegister", good.n_events,
                                         autotune.LINFP_NO_LAUNCH)
    assert host_sig[3] == autotune.LINFP_NO_LAUNCH != launch_sig[3]
    assert autotune.lin_fastpath_route(host_sig) is True


def test_kernel_sample_skipped_when_a_program_was_built(monkeypatch,
                                                        tmp_path):
    """A launch that compiled is not a cost sample: the kernel side
    folds nothing when `programs_built` advanced during the call."""
    from jepsen_jgroups_raft_tpu.checker import linearizable, schedule

    _gate_env(monkeypatch, tmp_path)
    m = CasRegister()
    encs = [encode_history(random_valid_history(
        random.Random(i), "register", n_ops=10,
        crash_p=0.0).client_ops(), m) for i in range(4)]
    sig = autotune.lin_fastpath_sig("CasRegister", encs[0].n_events, 4)
    assert all(autotune.lin_fastpath_sig(
        "CasRegister", e.n_events, 4) == sig for e in encs)

    def compiling(rest):
        schedule.note_compile("init_one", 7.5)
        return ["r"] * len(rest)

    def warm(rest):
        return ["r"] * len(rest)

    out = linearizable._observe_kernel_cost(encs, m, 4, compiling)
    assert out == ["r"] * 4
    assert autotune._linfp_record(sig)["kernel_rows"] == 0
    linearizable._observe_kernel_cost(encs, m, 4, warm)
    rec = autotune._linfp_record(sig)
    assert rec["kernel_rows"] == 4 and rec["kernel_wall_s"] > 0.0
    # the certifier's side is untouched by kernel samples
    assert rec["rows"] == 0 and rec["hits"] == 0


def test_check_encoded_feeds_both_sides(monkeypatch, tmp_path):
    """Through the real entry: the pass commits the rows it evicted as
    used verdicts, and the launch of the rest lands on the kernel side
    of the same bucket (once no program is built during it)."""
    _gate_env(monkeypatch, tmp_path)
    m = CasRegister()
    rng = random.Random(21)
    hists = [random_valid_history(rng, "register", n_ops=12,
                                  crash_p=0.0) for _ in range(3)]
    hists.append(poisoned(random_valid_history(rng, "register",
                                               n_ops=8, crash_p=0.0)))
    encs = [encode_history(h.client_ops(), m) for h in hists]
    sigs = {autotune.lin_fastpath_sig("CasRegister", e.n_events, 4)
            for e in encs}
    assert len(sigs) == 1
    [sig] = sigs
    consume_fastpath_counters()
    for _ in range(2):   # the first call may compile
        rs = check_encoded(encs, m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [VALID] * 3 + [INVALID]
    c = consume_fastpath_counters()
    assert c["rows_scanned"] == 8
    assert c["rows_certified"] == c["rows_delivered"] == 6
    rec = autotune._linfp_record(sig)
    assert rec["rows"] == 8 and rec["hits"] == 6
    assert 1 <= rec["kernel_rows"] <= 2 and rec["kernel_wall_s"] > 0.0


def test_version_1_record_reobserves(monkeypatch, tmp_path):
    """A record written under the old meaning of `hits` (rows the scan
    certified) must not route: schema 1 reads as no record."""
    import json

    _gate_env(monkeypatch, tmp_path)
    sig = autotune.lin_fastpath_sig("Counter", 2000, 256)
    autotune.lin_fastpath_observe(sig, rows=64, hits=0, wall_s=1.9)
    assert autotune.lin_fastpath_route(sig) is False
    path = autotune._linfp_path(sig)
    raw = json.loads(path.read_text())
    assert raw["version"] == autotune.LINFP_VERSION == 2
    assert raw["host_first"] is False
    raw["version"] = 1
    path.write_text(json.dumps(raw))
    autotune.reset_for_tests()
    assert autotune.lin_fastpath_route(sig) is True
    assert autotune._linfp_record(sig)["rows"] == 0


def test_shared_gate_dir_replicates_across_replicas(monkeypatch,
                                                    tmp_path):
    """ISSUE-18 satellite: two replicas with DISTINCT autotune stores
    but a shared JGRAFT_LINFP_DIR. Replica A trains a low-hit bucket;
    replica B — zero observations of its own — inherits the published
    gate record and routes kernel-first immediately. Without the
    shared dir, B starts untrained (routes fastpath-first)."""
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH_MIN_OBS", "8")
    monkeypatch.setenv("JGRAFT_LINFP_DIR", str(tmp_path / "cluster"))
    sig = autotune.lin_fastpath_sig("CasRegister", 40)
    # replica A: private store, trains the bucket, publishes
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "a"))
    autotune.reset_for_tests()
    autotune.lin_fastpath_observe(sig, rows=32, hits=0, wall_s=0.05)
    assert autotune.lin_fastpath_route(sig) is False
    shared = list((tmp_path / "cluster" / "linfp").glob("linfp-*.json"))
    assert shared, "gate record was not published to the shared dir"
    # replica B: fresh memory + DIFFERENT private store, inherits
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "b"))
    autotune.reset_for_tests()
    assert autotune.lin_fastpath_route(sig) is False
    # control: without the shared dir, B would be untrained
    monkeypatch.delenv("JGRAFT_LINFP_DIR")
    autotune.reset_for_tests()
    assert autotune.lin_fastpath_route(sig) is True


def test_shared_gate_reenables_fastpath_in_wavefront(monkeypatch,
                                                     tmp_path):
    """ISSUE-18 satellite: inside an active distributed wavefront the
    fast path stays off (host-local gate state would desync SPMD
    eviction) — unless the shared gate dir is configured, in which
    case certifiable rows are evicted before sharding. All rows here
    certify, so the kernel path (and its collectives) is never
    reached."""
    from jepsen_jgroups_raft_tpu.parallel import distributed

    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "0")
    monkeypatch.setattr(distributed, "wavefront_active", lambda: True)
    seen = []
    monkeypatch.setattr(
        distributed, "run_sharded",
        lambda encs, check_local, **kw: seen.append(len(encs))
        or check_local(list(encs)))
    rng = random.Random(3)
    hists = [random_valid_history(rng, "register", n_ops=24,
                                  crash_p=0.0) for _ in range(4)]
    m = CasRegister()
    consume_fastpath_counters()
    rs1 = check_histories(hists, m, algorithm="jax")
    c1 = consume_fastpath_counters()
    # no shared dir: wavefront stays kernel-first (run_sharded saw all)
    assert c1["rows_scanned"] == 0 and seen == [4]
    seen.clear()
    monkeypatch.setenv("JGRAFT_LINFP_DIR", str(tmp_path / "cluster"))
    rs2 = check_histories(hists, m, algorithm="jax")
    c2 = consume_fastpath_counters()
    assert c2["rows_certified"] == 4 and seen == []
    assert [r["valid?"] for r in rs1] == [r["valid?"] for r in rs2]
    assert all(r["valid?"] is VALID for r in rs2)


# ------------------------------------------------------- host ladder


def test_check_encoded_host_fastpath(monkeypatch):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    m = CasRegister()
    good = encode_history(random_valid_history(
        random.Random(1), "register", n_ops=20,
        crash_p=0.0).client_ops(), m)
    r = check_encoded_host(good, m)
    assert r["valid?"] is VALID
    assert r["decided-tier"] in ("greedy@lin", "backtrack@lin")
    # suppressed: the graftd fast lane already tried at dispatch
    r2 = check_encoded_host(good, m, lin_fastpath=False)
    assert r2["valid?"] is VALID and r2["decided-tier"] == "host"
    bad = encode_history(H(
        (0, "invoke", "write", 1), (0, "ok", "write", 1),
        (0, "invoke", "write", 2), (0, "ok", "write", 2),
        (1, "invoke", "read", None), (1, "ok", "read", 1),
    ), m)
    rb = check_encoded_host(bad, m)
    assert rb["valid?"] is INVALID and rb["decided-tier"] == "host"


# ------------------------------------------- resumable certifier (unit)


class TestStreamingCertifier:
    def _feed_cuts(self, model, enc, cuts_rng):
        sc = StreamingCertifier(model)
        ev, lo = enc.events, 0
        while lo < ev.shape[0]:
            hi = min(ev.shape[0], lo + cuts_rng.randint(1, 16))
            sc.feed(ev[lo:hi])
            lo = hi
        return sc

    @pytest.mark.parametrize("kind", sorted(MODELS))
    def test_certifies_valid_streams_across_random_cuts(self, kind):
        rng = random.Random(17)
        model = MODELS[kind]()
        for _ in range(4):
            h = random_valid_history(rng, kind, n_ops=40, n_procs=4,
                                     crash_p=0.05, max_crashes=2)
            enc = encode_history(h.client_ops(), model, prune=False)
            one_shot = certify_encoded(enc, model)[0]
            sc = self._feed_cuts(model, enc, rng)
            if one_shot:
                # the incremental scan may spend flips the one-shot
                # does not (op_forced is learned late), but a
                # certified prefix must stay certified
                assert sc.certified, kind
                assert sc.tier in ("greedy", "backtrack")
                assert sc.carry_state()["pos"] == enc.n_events

    def test_incremental_cost_is_per_segment(self):
        """The resumable carry's point: a later append pays O(segment)
        step calls, not the per-append restart's O(history)."""
        m = CasRegister()
        calls = [0]
        raw = m.step

        def counting(state, f, a, b):
            calls[0] += 1
            return raw(state, f, a, b)

        m.step = counting
        rows = []
        for j in range(200):
            rows += [(0, "invoke", "write", j), (0, "ok", "write", j)]
        enc = encode_history(H(*rows), CasRegister(), prune=False)
        sc = StreamingCertifier(m)
        seg = enc.n_events // 10
        per_feed = []
        for lo in range(0, enc.n_events, seg):
            calls[0] = 0
            assert sc.feed(enc.events[lo:lo + seg])
            per_feed.append(calls[0])
        # every feed costs ~its own segment; a restarting certifier's
        # LAST feed alone would pay >= the whole stream's step count
        assert max(per_feed[1:]) <= 4 * seg
        assert sum(per_feed) < 2 * enc.n_events + 4 * seg

    def test_undecided_is_permanent(self):
        m = CasRegister()
        bad = poisoned(H((0, "invoke", "write", 1),
                         (0, "ok", "write", 1)))
        enc = encode_history(bad.client_ops(), m, prune=False)
        sc = StreamingCertifier(m, budget=0)
        certified = True
        for lo in range(0, enc.n_events, 4):
            certified = sc.feed(enc.events[lo:lo + 4])
        assert certified is False and sc.certified is False
        assert sc.tier is None
        # feeding more can never resurrect a dead certifier
        assert sc.feed(enc.events[:0]) is False


# --------------------------------------------------- graftd fast lane


class TestServiceFastLane:
    def _service(self, **kw):
        from jepsen_jgroups_raft_tpu.service import CheckingService

        return CheckingService(store_root=None, **kw)

    def test_certifiable_request_skips_the_batch_path(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        svc = self._service()
        try:
            h = random_valid_history(random.Random(3), "register",
                                     n_ops=24, crash_p=0.0)
            req = svc.submit([h], workload="register")
            assert req.wait(30)
            assert req.verdict() is True
            assert req.stats.get("fastlane") is True
            assert sum(req.stats["decided_tier"].values()) == 1
            assert set(req.stats["decided_tier"]) <= {
                "greedy@lin", "backtrack@lin"}
            st = svc.stats()
            assert st["fastpath_requests"] == 1
            assert st["batches"] == 0          # never a batch slot
            assert st["completed"] == 1
            assert set(st["decided_tier"]) <= {
                "greedy@lin", "backtrack@lin"}
            # clean fast-lane verdicts are cacheable: an identical
            # resubmission answers from the fingerprint cache
            req2 = svc.submit([h], workload="register")
            assert req2.wait(30) and req2.cached
        finally:
            svc.shutdown(wait=True)

    def test_undecidable_request_still_batches(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        svc = self._service()
        try:
            bad = poisoned(random_valid_history(random.Random(4),
                                                "register", n_ops=16,
                                                crash_p=0.0))
            req = svc.submit([bad], workload="register")
            assert req.wait(60)
            assert req.verdict() is False
            assert not req.stats.get("fastlane")
            st = svc.stats()
            assert st["fastpath_requests"] == 0
            assert st["batches"] >= 1
        finally:
            svc.shutdown(wait=True)

    def test_partial_certify_never_double_counts_tiers(self,
                                                       monkeypatch):
        """Review fix: a partially-certifiable request's discarded
        fast-lane results must not tier-attribute rows the kernel then
        attributes again — decided fractions would exceed 1.0."""
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        svc = self._service()
        try:
            good = random_valid_history(random.Random(7), "register",
                                        n_ops=16, crash_p=0.0)
            bad = poisoned(random_valid_history(random.Random(8),
                                                "register", n_ops=16,
                                                crash_p=0.0))
            consume_tiers()
            req = svc.submit([good, bad], workload="register")
            assert req.wait(60)
            assert req.verdict() is False
            assert not req.stats.get("fastlane")
            tiers = consume_tiers()
            decided = sum(v["rows"] for v in tiers.values())
            assert decided == 2, tiers  # one attribution per row
            assert not set(tiers) & {"greedy@lin", "backtrack@lin"}, \
                tiers
        finally:
            svc.shutdown(wait=True)

    def test_cancel_during_lane_scan_is_honored(self, monkeypatch):
        """Review fix: a cancel landing DURING the host certify scan
        must finalize CANCELLED, not DONE — matching the batch path's
        honor-cancel-at-demux contract."""
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        from jepsen_jgroups_raft_tpu.service.admission import \
            AdmissionQueue
        from jepsen_jgroups_raft_tpu.service.request import (CANCELLED,
                                                             admit)
        from jepsen_jgroups_raft_tpu.service.scheduler import \
            BatchScheduler

        req = admit([random_valid_history(random.Random(3), "register",
                                          n_ops=16, crash_p=0.0)],
                    "register")
        raw = req.model.step

        def cancelling(state, f, a, b):
            req.cancelled.set()   # the tenant cancels mid-scan
            return raw(state, f, a, b)

        req.model.step = cancelling
        sched = BatchScheduler(AdmissionQueue())
        decided, live = sched.fastlane([req])
        assert decided == [req] and not live
        assert req.status == CANCELLED
        assert req.results is None

    def test_trivial_rows_do_not_block_the_lane(self, monkeypatch):
        """Review fix: a request carrying an empty (0-event) history
        is still fast-laned — empty rows are host-decidable for free
        and must not push the request onto the batch path."""
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        svc = self._service()
        try:
            good = random_valid_history(random.Random(3), "register",
                                        n_ops=16, crash_p=0.0)
            req = svc.submit([H(), good], workload="register")
            assert req.wait(30)
            assert req.verdict() is True
            assert req.stats.get("fastlane") is True
            assert req.results[0]["decided-tier"] == "trivial"
            assert req.results[1]["decided-tier"] in ("greedy@lin",
                                                      "backtrack@lin")
            st = svc.stats()
            assert st["fastpath_requests"] == 1 and st["batches"] == 0
        finally:
            svc.shutdown(wait=True)

    def test_lane_skipped_requests_keep_host_ladder_fastpath(
            self, monkeypatch):
        """Review fix: execute() suppresses the in-checker fast path
        only for requests the lane actually SCANNED — a force_host
        watchdog retry (lane-skipped) still gets the host ladder's
        pre-frontier certify pass."""
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        from jepsen_jgroups_raft_tpu.service.admission import \
            AdmissionQueue
        from jepsen_jgroups_raft_tpu.service.request import admit
        from jepsen_jgroups_raft_tpu.service.scheduler import \
            BatchScheduler

        req = admit([random_valid_history(random.Random(3), "register",
                                          n_ops=16, crash_p=0.0)],
                    "register")
        req.force_host = True   # watchdog second strike
        sched = BatchScheduler(AdmissionQueue())
        decided, live = sched.fastlane([req])
        assert not decided and live == [req]   # lane skipped, no scan
        sched.execute(live)
        assert req.verdict() is True
        # the degrade arm's host ladder ran ITS fast path: the verdict
        # was certified, not frontier-searched
        assert req.results[0]["decided-tier"] in ("greedy@lin",
                                                  "backtrack@lin")
        assert req.results[0]["platform-degraded"]

    def test_lane_disabled_for_injected_check_fn(self, monkeypatch):
        """An injected check_fn is a seam that must observe every
        batch — the lane never short-circuits it."""
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        from jepsen_jgroups_raft_tpu.checker.linearizable import \
            check_encoded as real_check
        seen = []

        def spying(encs, model, algorithm="auto",
                   consistency="linearizable"):
            seen.append(len(encs))
            return real_check(encs, model, algorithm=algorithm,
                              consistency=consistency,
                              lin_fastpath=False)

        svc = self._service(check_fn=spying)
        try:
            h = random_valid_history(random.Random(5), "register",
                                     n_ops=24, crash_p=0.0)
            req = svc.submit([h], workload="register")
            assert req.wait(30)
            assert req.verdict() is True
            assert seen == [1]
            assert svc.stats()["fastpath_requests"] == 0
        finally:
            svc.shutdown(wait=True)

    def test_lane_off_with_env_disable(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
        svc = self._service()
        try:
            h = random_valid_history(random.Random(6), "register",
                                     n_ops=24, crash_p=0.0)
            req = svc.submit([h], workload="register")
            assert req.wait(30)
            assert req.verdict() is True
            st = svc.stats()
            assert st["fastpath_requests"] == 0
            assert st["batches"] >= 1
        finally:
            svc.shutdown(wait=True)

    # ---- ISSUE 28: the lane tells the gate what it DELIVERED

    @staticmethod
    def _campaign_request(seed, rows=12):
        """`rows` histories of one event bucket, the last undecidable:
        the lane can never deliver such a request whole."""
        from jepsen_jgroups_raft_tpu.service.request import admit

        rng = random.Random(seed)
        hists = [random_valid_history(rng, "register", n_ops=8,
                                      crash_p=0.0)
                 for _ in range(rows - 1)]
        hists.append(poisoned(random_valid_history(
            rng, "register", n_ops=4, crash_p=0.0)))
        req = admit(hists, "register")
        assert {autotune.lin_fastpath_sig(
            "CasRegister", e.n_events)[2] for e in req.encs} == {32}
        return req

    @staticmethod
    def _spied_scheduler():
        """A real BatchScheduler on the default check path whose calls
        into `check_encoded` are recorded (the lane stays enabled: the
        seam is wrapped after construction)."""
        from jepsen_jgroups_raft_tpu.service.admission import \
            AdmissionQueue
        from jepsen_jgroups_raft_tpu.service.scheduler import \
            BatchScheduler

        sched = BatchScheduler(AdmissionQueue())
        real, calls = sched.check_fn, []

        def spy(encs, model, **kw):
            calls.append((len(encs), dict(kw)))
            return real(encs, model, **kw)

        sched.check_fn = spy
        return sched, calls

    def test_campaign_shape_closes_its_row_class(self, monkeypatch,
                                                 tmp_path):
        """Requests of several rows, each holding one undecidable row:
        after `min_obs` scanned rows that delivered nothing the lane
        stops scanning that row class — `rows_gated` fires,
        `rows_scanned` and the `dispatch.scan` span stop growing,
        nothing was delivered, `execute` still suppresses the
        in-checker pass, and the verdicts are those of a
        JGRAFT_LIN_FASTPATH=0 run row for row."""
        from jepsen_jgroups_raft_tpu.checker.schedule import \
            snapshot_spans

        _gate_env(monkeypatch, tmp_path, min_obs="8")
        sched, calls = self._spied_scheduler()
        consume_fastpath_counters()
        verdicts = []

        def scan_span():
            return dict(snapshot_spans().get("dispatch.scan",
                                             {"n": 0, "s": 0.0}))

        def one_batch(seed0):
            batch = [self._campaign_request(seed0 + k)
                     for k in range(2)]       # two requests of class 12
            decided, live = sched.fastlane(batch)
            assert not decided and live == batch
            assert all(r._fp_tried for r in batch)
            sched.execute(live)
            for r in batch:
                assert not r.stats.get("fastlane")
                verdicts.extend(res["valid?"] for res in r.results)
            return batch

        span0 = scan_span()
        first = one_batch(100)
        c1 = fastpath_counters()
        # one request of twelve rows reaches min_obs; the next rides
        # the gate
        assert c1["rows_scanned"] == 12 and c1["rows_gated"] == 12
        assert c1["rows_certified"] == 11 and c1["rows_delivered"] == 0
        assert [bool(r.scanned) for r in first] == [True, False]
        span1 = scan_span()
        assert span1["n"] == span0["n"] + 1
        one_batch(200)
        c2 = fastpath_counters()
        assert c2["rows_scanned"] == 12 and c2["rows_gated"] == 36
        assert c2["rows_delivered"] == 0
        assert scan_span() == span1          # no span, not a short one
        assert calls == [(24, {"algorithm": "auto",
                               "lin_fastpath": False})] * 2
        sig = autotune.lin_fastpath_sig("CasRegister", 20, 12)
        assert autotune.lin_fastpath_route(sig) is False
        # the same requests with the certifier forced off
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
        off = []
        for seed0 in (100, 200):
            batch = [self._campaign_request(seed0 + k)
                     for k in range(2)]
            sched.execute(batch)
            for r in batch:
                off.extend(res["valid?"] for res in r.results)
        assert verdicts == off
        assert verdicts == ([True] * 11 + [False]) * 4

    def test_ci_shape_keeps_the_lane_after_campaign_closed(
            self, monkeypatch, tmp_path):
        """The bypass: one-history certifiable requests, interleaved
        with campaign-shaped ones in the same family and event bucket,
        are still delivered from the lane after the large row class has
        closed — the gate keeps its costs by the request's row class."""
        from jepsen_jgroups_raft_tpu.service.request import (CANCELLED,
                                                             admit)

        _gate_env(monkeypatch, tmp_path, min_obs="8")
        sched, calls = self._spied_scheduler()
        consume_fastpath_counters()
        ci_sig = autotune.lin_fastpath_sig("CasRegister", 20, 1)
        big_sig = autotune.lin_fastpath_sig("CasRegister", 20, 12)
        assert ci_sig[:3] == big_sig[:3] and ci_sig != big_sig
        for k in range(6):
            big = self._campaign_request(300 + k)
            ci = admit([random_valid_history(
                random.Random(900 + k), "register", n_ops=8,
                crash_p=0.0)], "register")
            # popped together, as one bucket's requests are
            decided, live = sched.fastlane([big, ci])
            assert decided == [ci] and live == [big]
            big.finish(CANCELLED)   # not executed: only routing matters
            assert ci.stats["fastlane"] is True
            assert ci.verdict() is True
        assert autotune.lin_fastpath_route(big_sig) is False
        assert autotune.lin_fastpath_route(ci_sig) is True
        c = fastpath_counters()
        assert c["rows_delivered"] == 6
        assert c["rows_scanned"] == 12 + 6
        assert c["rows_gated"] == 5 * 12
        assert not calls

    def test_execute_reports_the_launch_under_each_requests_class(
            self, monkeypatch, tmp_path):
        """The lane consulted the gate per request, so `execute` owes
        it the kernel side under the same key: a request that went live
        books the rows of the launch it rode at that launch's wall a
        row (never a launch that built a program)."""
        from jepsen_jgroups_raft_tpu.service.request import admit

        _gate_env(monkeypatch, tmp_path)
        sched, calls = self._spied_scheduler()
        sig = autotune.lin_fastpath_sig("CasRegister", 20, 2)
        for k in range(3):
            rng = random.Random(700 + k)
            req = admit([random_valid_history(rng, "register", n_ops=8,
                                              crash_p=0.0),
                         poisoned(random_valid_history(
                             rng, "register", n_ops=4, crash_p=0.0))],
                        "register")
            decided, live = sched.fastlane([req])
            assert live == [req] and not decided
            sched.execute(live)
            assert [r["valid?"] for r in req.results] == [True, False]
        rec = autotune._linfp_record(sig)
        assert rec["rows"] == 6 and rec["hits"] == 0
        # the first launch may have compiled; the later ones are samples
        assert rec["kernel_rows"] in (4, 6) and rec["kernel_wall_s"] > 0
        assert all(kw["lin_fastpath"] is False for _, kw in calls)

    def test_stats_serve_the_fastpath_counters(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
        svc = self._service()
        try:
            consume_fastpath_counters()
            h = random_valid_history(random.Random(3), "register",
                                     n_ops=24, crash_p=0.0)
            req = svc.submit([h], workload="register")
            assert req.wait(30) and req.stats.get("fastlane") is True
            fp = svc.stats()["lin_fastpath"]
            assert fp["rows_scanned"] == fp["rows_delivered"] == 1
            assert fp["rows_gated"] == 0
        finally:
            svc.shutdown(wait=True)


# ------------------- verdict identity on the benchmark's traffic shape


def _campaign_traffic(seed, n=16, n_ops=60):
    """`counter-1k.campaign` cut to what the CPU tier affords: counter
    histories from five processes (value range 3, crash probability
    0.05, at most three crashes), 10 % perturbed, and one planted
    acknowledged read of a value nobody wrote."""
    rng = random.Random(seed)
    hists = [random_valid_history(rng, "counter", n_ops=n_ops, n_procs=5,
                                  value_range=3, crash_p=0.05,
                                  max_crashes=3) for _ in range(n)]
    for i in rng.sample(range(n), max(1, round(0.1 * n))):
        hists[i] = corrupt(rng, hists[i])
    k = rng.randrange(n)
    ops = list(hists[k])
    t = max(op.time for op in ops) + 1
    ops += [Op(process=10_000, type="invoke", f="read", value=None,
               time=t),
            Op(process=10_000, type="ok", f="read", value=-5,
               time=t + 1)]
    hists[k] = History(ops)
    return hists, k


def test_campaign_traffic_verdicts_identical_open_closed_off(
        monkeypatch, tmp_path):
    """Routing only: the benchmark's traffic through the served path
    (lane, launch, demux with counterexamples) and through
    `check_encoded` gives the same `valid?` and the same counterexample
    for every row with the gate open (everything scanned), with the
    gate closed (everything kernel-first) and with the certifier forced
    off."""
    from jepsen_jgroups_raft_tpu.service.admission import AdmissionQueue
    from jepsen_jgroups_raft_tpu.service.request import admit
    from jepsen_jgroups_raft_tpu.service.scheduler import BatchScheduler

    hists, planted = _campaign_traffic(2803)
    m = Counter()
    encs = [encode_history(h.client_ops(), m) for h in hists]

    def served():
        reqs = [admit(hists[i:i + 4], "counter")
                for i in range(0, len(hists), 4)]
        sched = BatchScheduler(AdmissionQueue())
        decided, live = sched.fastlane(reqs)
        sched.execute(live)
        return [(res["valid?"], res.get("failing-op-index"),
                 res.get("counterexample"))
                for r in reqs for res in r.results]

    def library():
        return [(r["valid?"], r.get("failing-op-index"))
                for r in check_encoded(encs, m, algorithm="jax")]

    arms = {}
    _gate_env(monkeypatch, tmp_path / "open")
    consume_fastpath_counters()
    arms["open"] = (served(), library())
    c = consume_fastpath_counters()
    assert c["rows_gated"] == 0 and c["rows_certified"] > 0
    assert c["rows_scanned"] >= len(hists)

    _gate_env(monkeypatch, tmp_path / "closed", min_obs="1")
    for e in encs:   # every bucket of either surface has delivered 0
        for batch_rows in (4, len(encs)):   # a request; the library call
            autotune.lin_fastpath_observe(
                autotune.lin_fastpath_sig("Counter", e.n_events,
                                          batch_rows),
                rows=1, hits=0, wall_s=0.03)
    arms["closed"] = (served(), library())
    c = consume_fastpath_counters()
    assert c["rows_scanned"] == 0
    assert c["rows_gated"] == 2 * len(hists)

    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    arms["off"] = (served(), library())
    assert not any(consume_fastpath_counters().values())

    assert arms["open"] == arms["off"]
    assert arms["closed"] == arms["off"]
    served_off, library_off = arms["off"]
    assert [v for v, _, _ in served_off] == [v for v, _ in library_off]
    assert served_off[planted][0] is INVALID
    assert served_off[planted][2], "no counterexample on the planted row"
    assert sum(v is VALID for v, _, _ in served_off) >= len(hists) // 2
