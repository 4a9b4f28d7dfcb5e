"""Macro-event compaction tests (ISSUE 4): macro≡legacy bitwise
differentials across the dense/mask/sort kernels and the chunked
scheduler (incl. crashed-op trailing latches, P-bucket boundary shapes,
pad_batch_bucketed round-trips, the JGRAFT_MACRO_EVENTS env-gate
ablation), and the per-run scan-stats scope."""

import functools
import json
import random
from pathlib import Path

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
                                                     macro_row_count,
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

ROOT = Path(__file__).resolve().parents[1]


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


# --------------------------- the group pass against a row at a time (PR 48)
#
# `pack_macro_batch` compacts a group WHOLE (history/packing.py
# `_macro_fill`). The reference below is the pack as it stood before: a
# `macro_compact` a row (two `flatnonzero`, a `searchsorted`, two
# `bincount`...) and a copy of each row into the batch tensor. It lives
# here, not in the program, so that the two cannot drift together.


def _ref_group_counts(events):
    events = np.asarray(events, dtype=np.int32)
    et = events[:, 0] if events.size else np.empty((0,), np.int32)
    open_idx = np.flatnonzero(et == EV_OPEN)
    force_idx = np.flatnonzero(et == EV_FORCE)
    grp = np.searchsorted(force_idx, open_idx, side="left")
    counts = np.bincount(grp, minlength=len(force_idx) + 1)
    return counts, len(force_idx), open_idx, force_idx, grp


def _ref_macro_compact(events, P):
    events = np.asarray(events, dtype=np.int32)
    counts, nF, open_idx, force_idx, grp = _ref_group_counts(events)
    n_rows = -(-counts // P)
    n_rows[:nF] = np.maximum(n_rows[:nF], 1)
    row_base = np.concatenate([[0], np.cumsum(n_rows)])
    total = int(row_base[-1])
    rows = np.zeros((total, 3 + 4 * P), dtype=np.int32)
    if nF:
        frow = row_base[1:nF + 1] - 1
        rows[frow, 0] = EV_FORCE
        rows[frow, 1] = events[force_idx, 1]
    if len(open_idx):
        starts = np.concatenate([[0], np.cumsum(counts)])
        j = np.arange(len(open_idx)) - starts[grp]
        mrow = row_base[grp] + j // P
        col = 3 + 4 * (j % P)
        for k in range(4):
            rows[mrow, col + k] = events[open_idx, 1 + k]
        rows[:, 2] = np.bincount(mrow, minlength=total)
    rows[rows[:, 0] == EV_PAD, 0] = EV_OPEN
    return rows


def _ref_max_open_run(events):
    counts, _, open_idx, _, _ = _ref_group_counts(events)
    return int(counts.max()) if len(open_idx) else 0


def _ref_pack(encs, n_events=None, cap=MACRO_MAX_OPENS, window=None):
    P = bucket_opens(window if window is not None
                     else max(_ref_max_open_run(e.events) for e in encs),
                     cap)
    compacted = [_ref_macro_compact(e.events, P) for e in encs]
    E = n_events or max(max(c.shape[0] for c in compacted), 1)
    B = len(encs)
    events = np.zeros((B, E, 3 + 4 * P), dtype=np.int32)
    ne = np.zeros((B,), dtype=np.int32)
    ns = np.zeros((B,), dtype=np.int32)
    for i, (e, c) in enumerate(zip(encs, compacted)):
        events[i, : c.shape[0]] = c
        ne[i] = c.shape[0]
        ns[i] = e.n_slots
    return {"events": events, "n_events": ne, "n_slots": ns, "macro_p": P,
            "legacy_events": max(e.n_events for e in encs)}


def _same_bits(got, want):
    for k in ("events", "n_events", "n_slots"):
        assert got[k].dtype == want[k].dtype == np.int32, k
        assert got[k].shape == want[k].shape, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert type(got["macro_p"]) is int and got["macro_p"] == want["macro_p"]
    assert got["legacy_events"] == want["legacy_events"]
    assert set(got) == set(want)


@functools.lru_cache(maxsize=None)
def _cell_encs(source, ops):
    """Sixteen histories of a cell's own generator, a quarter of them
    perturbed and one with a planted read, encoded as admission does."""
    from benchmarks.generators import partition, synth

    from jepsen_jgroups_raft_tpu.history.synth import build_history

    mix = {"histories_per_request": 16, "perturbed_share": 0.25,
           "planted_every": 1}
    name = "register-partition-1k" if source == "partition" else "counter-1k"
    config = json.loads((ROOT / "benchmarks" / "configs"
                         / f"{name}.json").read_text())
    config["ops_per_history"] = ops
    if source == "partition":
        # two cuts inside the history, as tests/test_partition_cell.py
        config.update(nemesis_interval_s=ops / 250.0,
                      operation_timeout_s=0.15)
        gen, model = partition, CasRegister()
    else:
        config.update(history_kind=source, service_workload=source)
        gen, model = synth, {"register": CasRegister, "counter": Counter}[
            source]()
    [req] = gen.make_requests(random.Random(2**31 + 48), config, mix, 1, 0)
    return tuple(encode_history(build_history(rows), model) for rows in req)


def _ev(*rows):
    """A hand-built encoding: rows of (etype, slot, f, a, b)."""
    from jepsen_jgroups_raft_tpu.history.packing import EncodedHistory

    ev = np.asarray(rows, dtype=np.int32).reshape(-1, 5)
    opens = ev[ev[:, 0] == EV_OPEN]
    return EncodedHistory(
        events=ev, op_index=np.arange(len(ev), dtype=np.int32),
        n_slots=int(opens[:, 1].max()) + 1 if len(opens) else 0,
        n_ops=len(opens))


def _o(slot, a=7):
    return (EV_OPEN, slot, 1, a, 0)


def _f(slot):
    return (EV_FORCE, slot, 0, 0, 0)


#: hand-built rows, by the shape of the stream
_SHAPES = {
    # the last events are opens that nothing forces
    "trailing_opens": _ev(_o(0), _f(0), _o(0), _o(1), _o(2), _o(3)),
    # forces back to back: the second has no fresh open
    "force_without_open": _ev(_o(0), _o(1), _f(0), _f(1), _o(0), _f(0)),
    "forces_first": _ev(_f(0), _f(1), _o(2)),
    "opens_only": _ev(*[_o(s, a=-s) for s in range(20)]),
    "one_force": _ev(_f(3)),
    "no_events": _ev(),
    "long_run": _ev(*[_o(s, a=-(2 ** 31) + s) for s in range(40)], _f(39),
                    *[_f(s) for s in range(39)]),
}

_SOURCES = [(s, ops) for s in ("register", "counter", "partition")
            for ops in (100, 1000)]
_ARGS = ([("default", {})]
         + [(f"cap{c}", {"cap": c}) for c in (1, 2, 3, 8, 16)]
         + [(f"window{w}", {"window": w}) for w in range(5, 14)]
         + [("window8_cap3", {"window": 8, "cap": 3}),
            ("n_events", {"n_events": 1536, "window": 8})])


@pytest.mark.parametrize("args", [a for _, a in _ARGS],
                         ids=[n for n, _ in _ARGS])
@pytest.mark.parametrize("source,ops", _SOURCES,
                         ids=[f"{s}{o}" for s, o in _SOURCES])
def test_group_pack_is_the_row_at_a_time_pack(source, ops, args):
    """Bit for bit, on the cells' own histories: every payload cap
    (1, 2, 3 make spill rows), every launch window, `n_events=` given."""
    encs = list(_cell_encs(source, ops))
    _same_bits(pack_macro_batch(encs, **args), _ref_pack(encs, **args))


@pytest.mark.parametrize("source,ops", _SOURCES,
                         ids=[f"{s}{o}" for s, o in _SOURCES])
def test_group_pack_of_one_row_and_the_one_row_helpers(source, ops):
    """A one-row batch; `macro_compact`, `macro_row_count` and
    `max_open_run` are the group pass at one row."""
    for e in _cell_encs(source, ops)[:4]:
        _same_bits(pack_macro_batch([e], window=8),
                   _ref_pack([e], window=8))
        assert max_open_run(e.events) == _ref_max_open_run(e.events)
        for P in (1, 3, 8):
            want = _ref_macro_compact(e.events, P)
            got = macro_compact(e.events, P)
            assert got.dtype == np.int32 and got.shape == want.shape
            np.testing.assert_array_equal(got, want)
            assert macro_row_count(e.events, P) == want.shape[0]


@pytest.mark.parametrize("cap", [1, 2, 3, 8, 16])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_group_pack_of_hand_built_rows(shape, cap):
    """Each hand-built row alone, first, last and in the middle of a
    batch of generated rows of very unequal length (4 to 1,000 ops)."""
    row = _SHAPES[shape]
    rng = random.Random(48)
    short = [encode_history(random_valid_history(rng, "register", n_ops=n),
                            CasRegister()) for n in (4, 9)]
    long = list(_cell_encs("register", 1000)[:2])
    got = macro_compact(row.events, cap)
    np.testing.assert_array_equal(got, _ref_macro_compact(row.events, cap))
    assert got.shape == (macro_row_count(row.events, cap), 3 + 4 * cap)
    assert max_open_run(row.events) == _ref_max_open_run(row.events)
    for encs in ([row], [row] + short + long, long + short + [row],
                 long[:1] + [row] + short + [row] + long[1:],
                 [row, row, row]):
        _same_bits(pack_macro_batch(encs, cap=cap),
                   _ref_pack(encs, cap=cap))
        _same_bits(pack_macro_batch(encs, cap=cap, window=13),
                   _ref_pack(encs, cap=cap, window=13))


def test_group_pack_refuses_what_the_row_pack_refused():
    encs = list(_cell_encs("register", 100))
    longest = int(pack_macro_batch(encs, window=8)["n_events"].max())
    with pytest.raises(ValueError, match="n_events smaller"):
        pack_macro_batch(encs, window=8, n_events=longest - 1)
    _same_bits(pack_macro_batch(encs, window=8, n_events=longest),
               _ref_pack(encs, window=8, n_events=longest))
    with pytest.raises(ValueError, match="empty batch"):
        pack_macro_batch([])


def test_no_loop_over_rows_in_the_group_pack():
    """The rule this pack was rebuilt to: `pack_macro_batch` and the
    pass under it hold no statement-level loop, and nothing but the
    gathers of the rows' `events` / `n_slots` iterates the rows."""
    import ast
    import inspect

    from jepsen_jgroups_raft_tpu.history import packing

    for fn in (packing.pack_macro_batch, packing._macro_fill,
               packing._macro_groups, packing._macro_rows):
        tree = ast.parse(inspect.getsource(fn))
        loops = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.For, ast.While))]
        # the one `for`: the four payload fields of `_macro_fill`
        assert [ast.unparse(n.iter) for n in loops] == (
            ["range(4)"] if fn is packing._macro_fill else []), fn
        calls = {n.func.id for n in ast.walk(tree)
                 if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)}
        assert "macro_compact" not in calls, fn


# ---------------------------------------- a launch's domains in one call


def _domain_rows():
    """Register rows that each stress the one-pass answer's order."""
    W, C, R = 1, 2, 0   # models/register.py WRITE, CAS, READ
    lo = -(2 ** 31)
    return {
        "negative_values": _ev((EV_OPEN, 0, W, -5, 0), _f(0),
                               (EV_OPEN, 0, W, 3, 0),
                               (EV_OPEN, 1, C, 3, -9), _f(0), _f(1),
                               (EV_OPEN, 0, W, 2 ** 31 - 1, 0), _f(0),
                               (EV_OPEN, 0, W, lo + 1, 0), _f(0)),
        # a write of the initial (nil) and a cas to it: it stays first
        "value_equal_to_initial": _ev((EV_OPEN, 0, W, lo, 0), _f(0),
                                      (EV_OPEN, 0, C, 4, lo), _f(0),
                                      (EV_OPEN, 0, W, 4, 0), _f(0)),
        # reads only; a read's value and a cas' FROM value are no states
        "no_write": _ev((EV_OPEN, 0, R, 6, 0), _f(0),
                        (EV_OPEN, 0, R, lo, 0), _f(0)),
        "cas_from_is_no_state": _ev((EV_OPEN, 0, C, 11, 12), _f(0)),
        "no_events": _ev(),
        # a FORCE's lanes are not an open's
        "force_lanes": _ev((EV_FORCE, 0, W, 77, 78), (EV_OPEN, 0, W, 1, 0)),
    }


@pytest.mark.parametrize("initial", [None, 0, 3, -5])
@pytest.mark.parametrize("shape", sorted(_domain_rows()))
def test_register_domains_in_one_pass(shape, initial):
    """`dense_domains(encs)` is `dense_domain` a row, value for value
    and in its order (the kernel's state index), wherever the row sits."""
    model = CasRegister(initial)
    row = _domain_rows()[shape]
    others = list(_domain_rows().values())
    cell = list(_cell_encs("register", 100)[:3])
    for encs in ([row], others, [row] + cell, cell + [row],
                 cell[:1] + [row] + cell[1:] + others):
        want = [model.dense_domain(e.events) for e in encs]
        got = model.dense_domains(encs)
        assert got == want
        assert all(type(v) is int for d in got for v in d)
        assert all(d[0] == int(model.initial) and d[1:] == sorted(d[1:])
                   and int(model.initial) not in d[1:] for d in got)


@pytest.mark.parametrize("source,ops", [("register", 100),
                                        ("register", 1000),
                                        ("partition", 100),
                                        ("partition", 1000)])
def test_register_domains_of_a_cells_rows(source, ops):
    model = CasRegister()
    encs = list(_cell_encs(source, ops))
    assert model.dense_domains(encs) == [model.dense_domain(e.events)
                                         for e in encs]
    assert model.dense_domains([]) == []


@pytest.mark.parametrize("kind", ["set", "counter", "queue"])
def test_default_domains_are_the_loop(kind):
    """The set model enumerates (or gives up, None) a row at a time and
    the counter has no domain: the base default is that loop."""
    from jepsen_jgroups_raft_tpu.models import GSet, TicketQueue
    from jepsen_jgroups_raft_tpu.models.base import Model

    model = {"set": GSet, "counter": Counter, "queue": TicketQueue}[kind]()
    assert type(model).dense_domains is Model.dense_domains
    rng = random.Random(48)
    encs = [encode_history(random_valid_history(rng, kind, n_ops=n,
                                                value_range=r), model)
            for n, r in ((2, 3), (5, 3), (12, 3), (40, 12))]
    want = [model.dense_domain(e.events) for e in encs]
    assert model.dense_domains(encs) == want
    if kind == "set":
        assert any(d is None for d in want) and \
            any(d is not None for d in want)
    if kind == "counter":
        assert want == [None] * 4


def test_grouping_asks_for_the_domains_once(monkeypatch):
    """`dense_plans_grouped` makes ONE call for a launch's rows, and its
    plans are those of the row-at-a-time answers."""
    model = CasRegister()
    encs = list(_cell_encs("register", 100))
    want = dense_plans_grouped(model, encs)
    calls = []

    def counted(self, rows):
        calls.append(len(rows))
        return [self.dense_domain(e.events) for e in rows]

    monkeypatch.setattr(CasRegister, "dense_domains", counted)
    got = dense_plans_grouped(model, encs)
    assert calls == [len(encs)]
    assert got[1] == want[1] and len(got[0]) == len(want[0])
    for (gi, gp), (wi, wp) in zip(got[0], want[0]):
        assert gi == wi and (gp.kind, gp.n_slots, gp.n_states) == \
            (wp.kind, wp.n_slots, wp.n_states)
        np.testing.assert_array_equal(gp.val_of, wp.val_of)
