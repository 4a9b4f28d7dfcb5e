"""Macro-event compaction tests (ISSUE 4): macro≡legacy bitwise
differentials across the dense/mask/sort kernels and the chunked
scheduler (incl. crashed-op trailing latches, P-bucket boundary shapes,
pad_batch_bucketed round-trips, the JGRAFT_MACRO_EVENTS env-gate
ablation), a Pallas interpret-mode differential, and the per-run
scan-stats scope."""

import random

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker import schedule
from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.schedule import (ChunkLaunch,
                                                      consume_stats,
                                                      run_chunked,
                                                      snapshot_stats,
                                                      stats_scope)
from jepsen_jgroups_raft_tpu.history.packing import (EV_FORCE, EV_OPEN,
                                                     EV_PAD,
                                                     MACRO_MAX_OPENS,
                                                     bucket_opens,
                                                     encode_history,
                                                     macro_compact,
                                                     macro_events_on,
                                                     max_open_run,
                                                     pack_batch,
                                                     pack_macro_batch,
                                                     pad_batch_bucketed)
from jepsen_jgroups_raft_tpu.models import CasRegister, Counter
from jepsen_jgroups_raft_tpu.ops.dense_scan import (dense_plans_grouped,
                                                    macro_row_ints,
                                                    make_dense_batch_checker,
                                                    make_dense_chunk_checker)
from jepsen_jgroups_raft_tpu.ops.linear_scan import make_batch_checker

from util import corrupt, random_valid_history


@pytest.fixture(autouse=True)
def _reset_scan_stats():
    consume_stats()
    yield
    consume_stats()


def _mixed(rng, kind, n=24, crash_p=0.1):
    hists = []
    for i in range(n):
        h = random_valid_history(rng, kind, n_ops=4 + (i * 7) % 40,
                                 crash_p=crash_p)
        if i % 3 == 0:
            h = corrupt(rng, h)
        hists.append(h)
    return hists


def _decode(rows):
    """Expand macro rows back into the one-event-per-step stream —
    the encoder's exact inverse (opens keep their order within a run;
    the run's FORCE follows it)."""
    out = []
    for r in rows:
        for j in range(r[2]):
            out.append([EV_OPEN] + list(r[3 + 4 * j:7 + 4 * j]))
        if r[0] == EV_FORCE:
            out.append([EV_FORCE, int(r[1]), 0, 0, 0])
    return np.asarray(out, dtype=np.int32).reshape(-1, 5)


# ----------------------------------------------------------- encoder unit


def test_macro_compact_roundtrip_all_widths():
    """Decoding the macro stream reproduces the legacy stream exactly,
    for every payload width incl. spill (runs longer than P split into
    latch-only rows) — on real encoded histories."""
    rng = random.Random(7)
    model = CasRegister()
    for h in _mixed(rng, "register", n=8, crash_p=0.3):
        enc = encode_history(h, model)
        for P in (1, 2, 3, bucket_opens(max_open_run(enc.events))):
            rows = macro_compact(enc.events, P)
            np.testing.assert_array_equal(_decode(rows), enc.events)
            assert int((rows[:, 0] == EV_FORCE).sum()) == \
                int((enc.events[:, 0] == EV_FORCE).sum())
            assert (rows[:, 2] <= P).all()
            assert not (rows[:, 0] == EV_PAD).any()


def test_macro_compact_shapes():
    """Row-count arithmetic: #FORCEs + spill; back-to-back forces get
    payload-free rows; trailing crashed opens become latch-only rows."""
    ev = np.array([
        [1, 0, 9, 0, 0], [1, 1, 9, 0, 0], [1, 2, 9, 0, 0],  # run of 3
        [2, 0, 0, 0, 0], [2, 1, 0, 0, 0],                    # 2 forces
        [1, 3, 9, 0, 0],                                     # crashed open
    ], np.int32)
    rows = macro_compact(ev, 2)
    # force0 row carries the spill remainder: run 3 at P=2 → 1 latch-only
    # + 1 force row; force1 payload-free; trailing latch-only.
    assert rows.shape == (4, 3 + 4 * 2)
    assert rows[0].tolist()[:3] == [EV_OPEN, 0, 2]
    assert rows[1].tolist()[:3] == [EV_FORCE, 0, 1]
    assert rows[2].tolist()[:3] == [EV_FORCE, 1, 0]
    assert rows[3].tolist()[:3] == [EV_OPEN, 0, 1]
    assert rows[3, 3] == 3  # the crashed op's slot, latched, never forced


def test_bucket_opens_series():
    assert [bucket_opens(n) for n in (0, 1, 2, 3, 4, 5, 6, 7, 8, 12, 13,
                                      16, 17, 100)] == \
        [1, 1, 2, 3, 4, 6, 6, 8, 8, 12, 16, 16, 16, 16]
    assert bucket_opens(100) == MACRO_MAX_OPENS
    assert macro_row_ints(MACRO_MAX_OPENS) == 67
    assert macro_row_ints() == 67  # default = the cap the lint gate pins


def test_pack_macro_batch_layout():
    rng = random.Random(11)
    model = CasRegister()
    encs = [encode_history(h, model) for h in _mixed(rng, "register", n=6)]
    batch = pack_macro_batch(encs)
    P = batch["macro_p"]
    assert batch["events"].shape[2] == 3 + 4 * P
    for i, e in enumerate(encs):
        n = int(batch["n_events"][i])
        np.testing.assert_array_equal(
            _decode(batch["events"][i, :n]), e.events)
        assert not batch["events"][i, n:].any()  # EV_PAD tail
    # macro stream strictly shorter than the legacy stream whenever a
    # force follows any open (always, on these histories)
    assert (batch["n_events"] < np.array([e.n_events for e in encs])).all()


def test_pad_batch_bucketed_macro_rows_roundtrip():
    """Macro batches ride the same padding home as legacy batches —
    row/event buckets apply, the payload width is preserved."""
    rng = random.Random(13)
    model = CasRegister()
    encs = [encode_history(h, model) for h in _mixed(rng, "register", n=5)]
    batch = pack_macro_batch(encs)
    padded, _, B = pad_batch_bucketed(batch["events"])
    assert B == len(encs)
    assert padded.shape[2] == batch["events"].shape[2]
    np.testing.assert_array_equal(
        padded[:B, :batch["events"].shape[1]], batch["events"])
    assert not padded[B:].any()


# ---------------------------------------------------------- differentials


def _verdicts(hists, model, monkeypatch, macro, chunk, **kw):
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", macro)
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", chunk)
    return [r["valid?"] for r in check_histories(hists, model, **kw)]


@pytest.mark.parametrize("kind,model", [
    ("register", CasRegister()), ("counter", Counter())])
def test_macro_matches_legacy_dense(kind, model, monkeypatch):
    """The acceptance property: macro and legacy streams produce
    identical verdicts across the domain (register) and mask (counter)
    kernels, chunked and monolithic."""
    rng = random.Random(17)
    hists = _mixed(rng, kind)
    ref = _verdicts(hists, model, monkeypatch, macro="0", chunk="0")
    for chunk in ("0", "8", "128"):
        assert _verdicts(hists, model, monkeypatch, macro="1",
                         chunk=chunk) == ref


def test_macro_matches_legacy_sort(monkeypatch):
    """Pinned n_configs/n_slots route through the sort ladder; the
    macro sort kernel must agree, including the capacity-starved rung
    whose overflow escalation must pick the same histories."""
    rng = random.Random(19)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=20, n_procs=5,
                                  crash_p=0.5) for _ in range(6)]
    for kw in (dict(algorithm="jax", n_configs=64, n_slots=8),
               dict(algorithm="jax", n_configs=4, n_slots=8)):
        ref = _verdicts(hists, model, monkeypatch, macro="0", chunk="0",
                        **kw)
        for chunk in ("0", "4"):
            assert _verdicts(hists, model, monkeypatch, macro="1",
                             chunk=chunk, **kw) == ref


def test_macro_crashed_trailing_latches(monkeypatch):
    """Crash-heavy histories compact their never-forced opens into
    trailing latch-only macros; verdicts still match the legacy stream
    bitwise (prune off so the crashed ops actually reach the kernel)."""
    rng = random.Random(23)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=12, n_procs=5,
                                  crash_p=0.5, max_crashes=4)
             for _ in range(8)]
    encs = [encode_history(h, model, prune=False) for h in hists]
    trailing = 0
    for e in encs:
        rows = macro_compact(e.events, bucket_opens(max_open_run(e.events)))
        if rows.shape[0] and rows[-1, 0] == EV_OPEN:
            trailing += 1
    assert trailing > 0  # the shape under test actually occurs
    ref = _verdicts(hists, model, monkeypatch, macro="0", chunk="0")
    assert _verdicts(hists, model, monkeypatch, macro="1", chunk="8") == ref


def test_macro_chunk_kernel_matches_legacy_monolithic():
    """Kernel-level wavefront differential: macro chunk launches (with
    eviction/recompaction at a tiny chunk) agree row-for-row with the
    legacy monolithic batch kernel."""
    rng = random.Random(29)
    model = CasRegister()
    encs = [encode_history(h, model)
            for h in _mixed(rng, "register", n=30)]
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest
    for idxs, plan in grouped:
        sub = [encs[i] for i in idxs]
        legacy = pack_batch(sub)
        mac = pack_macro_batch(sub)
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=mac["macro_p"])
        [out] = run_chunked([ChunkLaunch(
            events=mac["events"], n_events=mac["n_events"],
            init_fn=init_fn, step_fn=step_fn, val_of=plan.val_of,
            tag=plan.kernel_tag)], chunk=4)
        kernel = make_dense_batch_checker(model, plan.kind, plan.n_slots,
                                          plan.n_states)
        ref_ok, _ = kernel(legacy["events"], plan.val_of)
        np.testing.assert_array_equal(out.ok, np.asarray(ref_ok))


def test_macro_hoisted_style_matches(monkeypatch):
    """The carry-hoisted transition style (TPU default; JGRAFT_HOIST=1
    forces it) takes the batched-latch path too — differential against
    the legacy stream under the same hoist."""
    monkeypatch.setenv("JGRAFT_HOIST", "1")
    rng = random.Random(31)
    model = CasRegister()
    encs = [encode_history(h, model)
            for h in _mixed(rng, "register", n=12)]
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest
    for idxs, plan in grouped:
        sub = [encs[i] for i in idxs]
        legacy, mac = pack_batch(sub), pack_macro_batch(sub)
        ok1, _ = make_dense_batch_checker(
            model, plan.kind, plan.n_slots, plan.n_states)(
                legacy["events"], plan.val_of)
        ok2, _ = make_dense_batch_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=mac["macro_p"])(mac["events"], plan.val_of)
        np.testing.assert_array_equal(np.asarray(ok1), np.asarray(ok2))


def test_sort_kernel_overflow_flags_match():
    """The sort kernel's (ok, overflow) PAIR — not just verdicts — is
    identical macro vs legacy, at starving and ample capacities."""
    rng = random.Random(37)
    model = CasRegister()
    encs = [encode_history(random_valid_history(
        rng, "register", n_ops=20, crash_p=0.3), model) for _ in range(8)]
    legacy, mac = pack_batch(encs), pack_macro_batch(encs)
    for C in (4, 64):
        ok1, ov1 = make_batch_checker(model, C, 8)(legacy["events"])
        ok2, ov2 = make_batch_checker(
            model, C, 8, macro_p=mac["macro_p"])(mac["events"])
        np.testing.assert_array_equal(np.asarray(ok1), np.asarray(ok2))
        np.testing.assert_array_equal(np.asarray(ov1), np.asarray(ov2))


def test_pallas_interpret_macro_differential():
    """Tiny-shape Pallas differential in interpret mode: the macro tile
    kernel agrees with the legacy tile kernel and the XLA dense kernel."""
    from jepsen_jgroups_raft_tpu.ops.pallas_scan import (
        make_pallas_batch_checker)

    rng = random.Random(41)
    model = CasRegister()
    hists = [corrupt(rng, random_valid_history(rng, "register", n_ops=10))
             if i % 2 else random_valid_history(rng, "register", n_ops=10)
             for i in range(4)]
    encs = [encode_history(h, model) for h in hists]
    grouped, _ = dense_plans_grouped(model, encs)
    for idxs, plan in grouped:
        if plan.kind != "domain":
            continue
        sub = [encs[i] for i in idxs]
        legacy, mac = pack_batch(sub), pack_macro_batch(sub)
        ok_ref, _ = make_dense_batch_checker(
            model, plan.kind, plan.n_slots, plan.n_states)(
                legacy["events"], plan.val_of)
        ok_leg, _ = make_pallas_batch_checker(
            model, plan.n_slots, plan.n_states,
            legacy["events"].shape[1], interpret=True)(
                legacy["events"], plan.val_of)
        ok_mac, _ = make_pallas_batch_checker(
            model, plan.n_slots, plan.n_states, mac["events"].shape[1],
            interpret=True, macro_p=mac["macro_p"])(
                mac["events"], plan.val_of)
        np.testing.assert_array_equal(np.asarray(ok_ref),
                                      np.asarray(ok_leg))
        np.testing.assert_array_equal(np.asarray(ok_ref),
                                      np.asarray(ok_mac))


# --------------------------------------------------------------- env gate


def test_macro_env_gate(monkeypatch):
    monkeypatch.delenv("JGRAFT_MACRO_EVENTS", raising=False)
    assert macro_events_on()
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "0")
    assert not macro_events_on()
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "1")
    assert macro_events_on()
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "banana")
    assert macro_events_on()  # defensive parse: garbage keeps the default


def test_macro_ablation_restores_legacy_stream(monkeypatch):
    """JGRAFT_MACRO_EVENTS=0 runs genuinely legacy-shaped work: results
    are tagged chunked, and the chunk schedule covers the legacy event
    bucket (more chunk-units than the macro stream needs)."""
    rng = random.Random(43)
    model = CasRegister()
    hists = _mixed(rng, "register", n=16)
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "1")
    check_histories(hists, model)
    macro_chunks = consume_stats()["chunks_run"]
    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "0")
    check_histories(hists, model)
    legacy_chunks = consume_stats()["chunks_run"]
    assert macro_chunks > 0
    assert legacy_chunks >= macro_chunks  # macro scans fewer chunk-units


# ------------------------------------------------------- per-run stats scope


def _run_some_chunked_work(model, rng):
    encs = [encode_history(random_valid_history(rng, "register", n_ops=8),
                           model) for _ in range(4)]
    grouped, _ = dense_plans_grouped(model, encs)
    launches = []
    for idxs, plan in grouped:
        mac = pack_macro_batch([encs[i] for i in idxs])
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=mac["macro_p"])
        launches.append(ChunkLaunch(
            events=mac["events"], n_events=mac["n_events"],
            init_fn=init_fn, step_fn=step_fn, val_of=plan.val_of))
    run_chunked(launches, chunk=4)


def test_stats_scope_isolates_back_to_back_runs():
    """The ISSUE-4 regression: back-to-back checker invocations in one
    process must not accumulate counters in per-run reads — each scope
    sees only its own work while the process totals keep accumulating
    for consume_stats."""
    model = CasRegister()
    rng = random.Random(47)
    with stats_scope() as first:
        _run_some_chunked_work(model, rng)
    with stats_scope() as second:
        _run_some_chunked_work(model, rng)
    assert first["groups_run"] > 0
    assert second["groups_run"] == first["groups_run"]  # NOT 2× — no
    assert second["chunks_run"] <= first["chunks_run"] * 2  # accumulation
    totals = snapshot_stats()
    assert totals["groups_run"] == \
        first["groups_run"] + second["groups_run"]


def test_perf_scan_stats_summary_is_per_run():
    """checker/perf.py's scan-stats block reads the innermost scope —
    the second run's stored summary equals its own counters, not the
    process-lifetime sum (what run_test's scope wrap guarantees)."""
    from jepsen_jgroups_raft_tpu.checker.perf import scan_stats_summary

    model = CasRegister()
    rng = random.Random(53)
    with stats_scope():
        _run_some_chunked_work(model, rng)
        s1 = scan_stats_summary()
    with stats_scope():
        _run_some_chunked_work(model, rng)
        s2 = scan_stats_summary()
    assert s1 is not None and s2 is not None
    assert s2["groups-run"] == s1["groups-run"]
    # outside any scope the process totals (both runs) answer
    assert scan_stats_summary()["groups-run"] == \
        s1["groups-run"] + s2["groups-run"]


def test_runner_wraps_checking_in_scope():
    """run_test's checking phase runs inside a stats_scope (the per-run
    isolation home) — asserted by observing the scope stack from a stub
    checker, without standing up a cluster."""
    from jepsen_jgroups_raft_tpu.client.base import Client
    from jepsen_jgroups_raft_tpu.core.runner import run_test
    from jepsen_jgroups_raft_tpu.generator.base import (Clients, Limit,
                                                        Repeat)
    from jepsen_jgroups_raft_tpu.history.ops import OK

    seen = {}

    class OkClient(Client):
        def open(self, test, node):
            return self

        def invoke(self, test, op):
            return op.replace(type=OK)

    class StubChecker:
        def check(self, test, history, opts=None):
            seen["scopes_active"] = len(schedule._SCOPES)
            # Chunked work INSIDE the check: the runner must stamp this
            # run's counters into the results afterwards (the composed
            # checker runs perf before the workload checker, so only the
            # runner sees the full per-run counters).
            _run_some_chunked_work(CasRegister(), random.Random(59))
            return {"valid?": True}

    test = run_test({
        "name": "scope-probe", "nodes": ["n1"], "concurrency": 1,
        "client": OkClient(), "checker": StubChecker(), "store": False,
        "generator": Clients(Limit(2, Repeat({"f": "write", "value": 1}))),
    })
    assert seen["scopes_active"] >= 1
    scan = test["results"]["scan-stats"]
    assert scan["groups-run"] >= 1


def test_stats_scope_nested_zero_scopes_exit_cleanly():
    """Scope exit removes by identity: two nested still-zero scopes are
    EQUAL dicts, and an equality-based remove would pop the outer one
    and crash the outer exit with ValueError."""
    with stats_scope() as outer:
        with stats_scope() as inner:
            pass  # both dicts still all-zero (equal) at inner exit
        schedule._add_stats(chunks_run=3)
        assert inner["chunks_run"] == 0  # the closed scope stays closed
    assert outer["chunks_run"] == 3
    assert not schedule._SCOPES


def test_long_group_policy_keys_on_legacy_event_lengths():
    """The LONG-group exact-padding policy was calibrated on legacy
    event counts; macro batches must feed it their legacy_events, not
    the ~2×-shorter macro row count."""
    from jepsen_jgroups_raft_tpu.checker.schedule import build_dense_launches
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (DensePlan,
                                                        MERGE_MAX_EVENTS)

    model = CasRegister()
    plan = DensePlan("mask", 2, 1, np.zeros((2, 1), np.int32))
    # A "long" group: legacy length over the merge threshold, macro
    # rows well under it — exactness must see the former.
    legacy_e = MERGE_MAX_EVENTS + 100
    batch = {"events": np.zeros((2, legacy_e // 2, 11), np.int32),
             "n_events": np.full((2,), legacy_e // 2, np.int32),
             "n_slots": np.full((2,), 2, np.int32),
             "macro_p": 2, "legacy_events": legacy_e}
    launches, _ = build_dense_launches(model, [([0, 1], plan, batch)])
    assert launches[0].exact_rows  # long-ness keyed on legacy length
    assert launches[0].device is None  # exact rows, default placement
    # And pack_macro_batch actually stamps the key it depends on.
    rng = random.Random(61)
    encs = [encode_history(random_valid_history(rng, "register", n_ops=8),
                           model) for _ in range(3)]
    mb = pack_macro_batch(encs)
    assert mb["legacy_events"] == max(e.n_events for e in encs)
