"""Chunked wavefront tests (ISSUE 3, checker/schedule.py): differential
pinning of the chunked path against the monolithic reference scan,
eviction/recompaction round-trips, pad_batch_bucketed boundary shapes,
and the defensive env-gate parsing + degraded-platform metadata."""

import random
import subprocess
import sys

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu import platform as plat
from jepsen_jgroups_raft_tpu.checker import schedule
from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.schedule import (ChunkLaunch,
                                                      consume_stats,
                                                      run_chunked,
                                                      scan_chunk,
                                                      snapshot_stats)
from jepsen_jgroups_raft_tpu.history.packing import (bucket_rows,
                                                     encode_history,
                                                     pack_batch,
                                                     pad_batch_bucketed)
from jepsen_jgroups_raft_tpu.models import CasRegister, Counter
from jepsen_jgroups_raft_tpu.ops.dense_scan import (
    dense_plans_grouped, make_dense_batch_checker, make_dense_chunk_checker)
from jepsen_jgroups_raft_tpu.ops.linear_scan import (make_batch_checker,
                                                     make_sort_chunk_checker)

from util import corrupt, random_valid_history


@pytest.fixture(autouse=True)
def _reset_scan_stats():
    """Each test reads its own wavefront counters."""
    consume_stats()
    yield
    consume_stats()


def _mixed_histories(rng, model_kind, n=24):
    """Histories with spread event counts (eviction pressure from
    exhaustion) and some corrupted (eviction pressure from early
    invalid verdicts)."""
    hists = []
    for i in range(n):
        h = random_valid_history(rng, model_kind, n_ops=4 + (i * 7) % 40)
        if i % 3 == 0:
            h = corrupt(rng, h)
        hists.append(h)
    return hists


def _verdicts(hists, model, monkeypatch, chunk, **kw):
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", str(chunk))
    return [r["valid?"] for r in check_histories(hists, model, **kw)]


# ------------------------------------------------------------ differential


@pytest.mark.parametrize("model_kind,model", [
    ("register", CasRegister()), ("counter", Counter())])
def test_chunked_matches_monolithic_dense(model_kind, model, monkeypatch):
    """The acceptance property: chunked and monolithic paths produce
    identical verdicts on random histories (valid and corrupted), for
    both the domain (register) and mask (counter) dense kernels."""
    rng = random.Random(7)
    hists = _mixed_histories(rng, model_kind)
    ref = _verdicts(hists, model, monkeypatch, chunk=0)
    for chunk in (8, 64):
        assert _verdicts(hists, model, monkeypatch, chunk=chunk) == ref


@pytest.fixture(scope="module")
def partition_rows_by_window():
    """64 histories of the partition cell's generator at windows 11-13
    (`benchmarks/generators/partition.py`: its configuration's caps,
    values and minority, at a length and a nemesis interval a test can
    hold, slow majority-side ops so that the windows get there), one
    seed, every fourth corrupted."""
    import json
    from pathlib import Path

    from benchmarks.generators import partition, synth
    from jepsen_jgroups_raft_tpu.history.synth import build_history

    root = Path(__file__).resolve().parents[1]
    with open(root / "benchmarks/configs/register-partition-1k.json") as f:
        config = dict(json.load(f), ops_per_history=180,
                      nemesis_interval_s=0.7, operation_timeout_s=0.15,
                      op_latency_ms=[20, 60])
    want = {11: 22, 12: 21, 13: 21}
    rng, model, out = random.Random(45), CasRegister(), {}
    for _ in range(20_000):
        if all(len(out.get(w, ())) == n for w, n in want.items()):
            break
        rows = partition.partition_rows(rng, config)
        enc = encode_history(build_history(rows), model)
        got = out.setdefault(enc.n_slots, [])
        if enc.n_slots not in want or len(got) == want[enc.n_slots]:
            continue
        if len(got) % 4 == 3:
            bad = encode_history(build_history(
                synth.corrupt(rng, rows, "register")), model)
            if bad.n_slots == enc.n_slots:
                enc = bad
        got.append(enc)
    assert {w: len(out[w]) for w in want} == want
    return out


@pytest.mark.parametrize("w", [11, 12, 13])
def test_wide_partition_rows_match_wgl_cpu(partition_rows_by_window, w):
    """ISSUE 45: FORCE's down-shift is W static slices of the frontier
    where it was one dynamic slice; at W 11-13 that is 11-13 passes
    over 2,048-8,192 words a row. The partition cell's own rows at
    those windows through the chunked wavefront (one group a window,
    the domain family), every verdict wgl_cpu's."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_encoded
    from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu

    model, encs = CasRegister(), partition_rows_by_window[w]
    oracle = [check_encoded_cpu(e, model).valid for e in encs]
    res = check_encoded(encs, model, algorithm="jax", lin_fastpath=False)
    assert [r["valid?"] for r in res] == oracle
    assert {r["decided-tier"] for r in res} == {"dense"}
    assert {r["concurrency-window"] for r in res} == {w}
    assert True in oracle and False in oracle


def test_chunked_matches_monolithic_sort(monkeypatch):
    """Pinned n_configs/n_slots route through the sort-kernel ladder;
    the chunked sort scan must agree with the monolithic rung."""
    rng = random.Random(11)
    model = CasRegister()
    hists = _mixed_histories(rng, "register", n=12)
    kw = dict(algorithm="jax", n_configs=64, n_slots=8)
    ref = _verdicts(hists, model, monkeypatch, chunk=0, **kw)
    assert _verdicts(hists, model, monkeypatch, chunk=8, **kw) == ref


def test_chunked_overflow_escalation_matches(monkeypatch):
    """A capacity-starved sort rung overflows; the chunked path must
    escalate exactly the histories the monolithic path escalates
    (overflow is frozen once the frontier dies — never invented)."""
    rng = random.Random(13)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=20, n_procs=5,
                                  crash_p=0.5) for _ in range(6)]
    kw = dict(algorithm="jax", n_configs=4, n_slots=8)
    ref = _verdicts(hists, model, monkeypatch, chunk=0, **kw)
    assert _verdicts(hists, model, monkeypatch, chunk=4, **kw) == ref


def test_chunked_records_eviction_and_chunk_stats(monkeypatch):
    """The chunked run actually chunks, actually evicts, and tags its
    results; the ablation (chunk=0) leaves the counters untouched."""
    rng = random.Random(17)
    model = CasRegister()
    hists = _mixed_histories(rng, "register")
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    rs = check_histories(hists, model)
    stats = consume_stats()
    assert stats["groups_run"] > 0
    assert stats["chunks_run"] > 0
    assert stats["evicted_rows"] > 0
    assert any(r.get("chunked") for r in rs)

    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "0")
    check_histories(hists, model)
    assert consume_stats()["groups_run"] == 0


# --------------------------------------------------- wavefront round-trips


def _dense_launches(model, hists, e_sched=None):
    encs = [encode_history(h, model) for h in hists]
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest
    launches, subs = [], []
    for idxs, plan in grouped:
        sub = [encs[i] for i in idxs]
        batch = pack_batch(sub)
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states)
        launches.append(ChunkLaunch(
            events=batch["events"], n_events=batch["n_events"],
            init_fn=init_fn, step_fn=step_fn, val_of=plan.val_of,
            e_sched=e_sched, tag=plan.kernel_tag))
        subs.append((idxs, plan, batch))
    return launches, subs


def test_recompaction_roundtrip_matches_monolithic():
    """compact -> re-pad -> verdicts identical: the wavefront with a
    tiny chunk (many eviction/recompaction boundaries) agrees row for
    row with one monolithic launch of the same group batches."""
    rng = random.Random(23)
    model = CasRegister()
    hists = _mixed_histories(rng, "register", n=30)
    launches, subs = _dense_launches(model, hists)
    outs = run_chunked(launches, chunk=4)
    for out, (idxs, plan, batch) in zip(outs, subs):
        kernel = make_dense_batch_checker(model, plan.kind, plan.n_slots,
                                          plan.n_states)
        ref_ok, _ = kernel(batch["events"], plan.val_of)
        np.testing.assert_array_equal(out.ok, np.asarray(ref_ok))


def test_early_exit_on_padded_schedule():
    """When the schedule covers the BUCKETED event length the monolithic
    kernel would scan, a group whose real events end earlier early-exits
    and reports the skipped reference work."""
    rng = random.Random(29)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=10)
             for _ in range(9)]
    launches, _ = _dense_launches(model, hists, e_sched=256)
    [out] = run_chunked(launches, chunk=8)
    assert out.early_exit
    assert out.chunks_run < 256 // 8
    stats = snapshot_stats()
    assert stats["groups_early_exited"] == 1


def test_exact_rows_skips_recompaction():
    """exact_rows launches (LONG merged clusters) never recompact —
    their win is the early exit; verdicts still match the reference."""
    rng = random.Random(31)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=8 + 4 * i)
             for i in range(5)]
    launches, subs = _dense_launches(model, hists)
    for ln in launches:
        ln.exact_rows = True
    outs = run_chunked(launches, chunk=4)
    for out, (idxs, plan, batch) in zip(outs, subs):
        kernel = make_dense_batch_checker(model, plan.kind, plan.n_slots,
                                          plan.n_states)
        ref_ok, _ = kernel(batch["events"], plan.val_of)
        np.testing.assert_array_equal(out.ok, np.asarray(ref_ok))


def test_sort_chunk_kernel_matches_batch_kernel():
    """Direct kernel-level differential for the sort twin, including a
    chunk size that does not divide the event length."""
    rng = random.Random(37)
    model = CasRegister()
    encs = [encode_history(random_valid_history(rng, "register", n_ops=n),
                           model) for n in (5, 9, 14, 20)]
    batch = pack_batch(encs)
    init_fn, step_fn = make_sort_chunk_checker(model, 64, 8)
    [out] = run_chunked([ChunkLaunch(
        events=batch["events"], n_events=batch["n_events"],
        init_fn=init_fn, step_fn=step_fn, tag="sort")], chunk=6)
    kernel = make_batch_checker(model, 64, 8)
    ref_ok, ref_ov = kernel(batch["events"])
    np.testing.assert_array_equal(out.ok, np.asarray(ref_ok))
    np.testing.assert_array_equal(out.overflow, np.asarray(ref_ov))


def test_run_chunked_rejects_nonpositive_chunk():
    with pytest.raises(ValueError):
        run_chunked([], chunk=0)


# ------------------------------------------------ pad_batch_bucketed edges


def test_bucket_rows_series():
    """The pow2+midpoint series: exact bucket values at and around the
    edges, and agreement with pad_batch_bucketed's row padding."""
    assert [bucket_rows(n) for n in (1, 8, 9, 12, 13, 16, 17, 24, 25, 32)] \
        == [8, 8, 12, 12, 16, 16, 24, 24, 32, 32]
    for n in (1, 7, 8, 9, 12, 13, 31, 33, 48, 49):
        ev = np.zeros((n, 4, 5), dtype=np.int32)
        padded, _, B = pad_batch_bucketed(ev, floor_e=None)
        assert B == n
        assert padded.shape[0] == bucket_rows(n)


@pytest.mark.parametrize("B,E,floor_e,expect_B,expect_E", [
    (8, 32, 32, 8, 32),      # both exactly at a bucket edge: no padding
    (12, 32, 32, 12, 32),    # B on a midpoint bucket
    (9, 33, 32, 12, 48),     # both one past an edge
    (5, 17, 32, 8, 32),      # E below floor_e pads up to the floor
    (8, 40, None, 8, 40),    # floor_e=None keeps E exact
])
def test_pad_batch_bucketed_boundaries(B, E, floor_e, expect_B, expect_E):
    ev = np.arange(B * E * 5, dtype=np.int32).reshape(B, E, 5)
    tab = np.arange(B * 3, dtype=np.int32).reshape(B, 3)
    padded, (tab2,), B_out = pad_batch_bucketed(ev, (tab,), floor_e=floor_e)
    assert B_out == B
    assert padded.shape == (expect_B, expect_E, 5)
    np.testing.assert_array_equal(padded[:B, :E], ev)
    assert not padded[B:].any() and not padded[:, E:].any()
    assert tab2.shape[0] == expect_B
    np.testing.assert_array_equal(tab2[:B], tab)


def test_pad_batch_bucketed_multiple_b():
    """multiple_b rounds the bucketed B up for mesh sharding; tables
    follow the final row count."""
    ev = np.ones((12, 8, 5), dtype=np.int32)
    tab = np.ones((12, 2), dtype=np.int32)
    padded, (tab2,), B = pad_batch_bucketed(ev, (tab,), floor_e=None,
                                            multiple_b=8)
    assert B == 12
    assert padded.shape[0] == 16 and padded.shape[0] % 8 == 0
    assert tab2.shape[0] == 16


# ------------------------------------------------------- env gates + notes


def test_env_int_defensive_parsing(monkeypatch, caplog):
    monkeypatch.setenv("JGRAFT_TEST_GATE", "12345")
    assert plat.env_int("JGRAFT_TEST_GATE", 7) == 12345
    monkeypatch.setenv("JGRAFT_TEST_GATE", "not-an-int")
    with caplog.at_level("WARNING"):
        assert plat.env_int("JGRAFT_TEST_GATE", 7) == 7
    assert "not an integer" in caplog.text
    monkeypatch.setenv("JGRAFT_TEST_GATE", "-3")
    assert plat.env_int("JGRAFT_TEST_GATE", 7, minimum=0) == 0
    monkeypatch.setenv("JGRAFT_TEST_GATE", "")
    assert plat.env_int("JGRAFT_TEST_GATE", 7) == 7
    monkeypatch.delenv("JGRAFT_TEST_GATE")
    assert plat.env_int("JGRAFT_TEST_GATE", 7) == 7


def test_chunk_sharding_placement_gate(monkeypatch):
    """Fan-out is the default (whole-group chunks row-sharded over the
    mesh recover the legacy shard_map path's parallelism);
    JGRAFT_GROUP_DEVICES=0 is the single-device ablation."""
    import jax

    from jepsen_jgroups_raft_tpu.parallel.mesh import (chunk_sharding,
                                                       launch_fan_out)

    monkeypatch.delenv("JGRAFT_GROUP_DEVICES", raising=False)
    assert launch_fan_out()
    sh = chunk_sharding()
    n = len(jax.devices())
    if n > 1:
        assert sh is not None and sh.mesh.size == n
    else:
        assert sh is None
    monkeypatch.setenv("JGRAFT_GROUP_DEVICES", "0")
    assert not launch_fan_out()
    assert chunk_sharding() is None


def test_build_dense_launches_sharded_and_verdicts(monkeypatch):
    """Groups stay whole with each launch row-sharded over the mesh
    (`chunk_sharding`), sharded-launch verdicts match the monolithic
    reference, and the JGRAFT_GROUP_DEVICES=0 ablation drops the
    sharding (default single-device placement)."""
    import jax

    from jepsen_jgroups_raft_tpu.checker.schedule import build_dense_launches

    rng = random.Random(47)
    model = CasRegister()
    hists = _mixed_histories(rng, "register", n=40)
    encs = [encode_history(h, model) for h in hists]
    grouped, rest = dense_plans_grouped(model, encs)
    assert not rest
    triples = [(idxs, plan, pack_batch([encs[i] for i in idxs]))
               for idxs, plan in grouped]

    monkeypatch.delenv("JGRAFT_GROUP_DEVICES", raising=False)
    launches, subs = build_dense_launches(model, triples)
    assert len(launches) == len(triples)  # groups stay WHOLE
    assert all(ln.events.shape[0] == len(sub)
               for ln, sub in zip(launches, subs))
    if len(jax.devices()) > 1:
        # every non-LONG group rides the batch-axis sharding
        assert all(getattr(ln.device, "mesh", None) is not None
                   for ln in launches if not ln.exact_rows)
    got = {}
    for out, sub in zip(run_chunked(launches, chunk=8), subs):
        for j, i in enumerate(sub):
            got[i] = bool(out.ok[j])
    for idxs, plan, batch in triples:
        kernel = make_dense_batch_checker(model, plan.kind, plan.n_slots,
                                          plan.n_states)
        ref_ok, _ = kernel(batch["events"], plan.val_of)
        for j, i in enumerate(idxs):
            assert got[i] == bool(ref_ok[j])

    monkeypatch.setenv("JGRAFT_GROUP_DEVICES", "0")
    launches, subs = build_dense_launches(model, triples)
    assert len(launches) == len(triples)
    assert all(ln.device is None for ln in launches)


def test_scan_chunk_env_gate(monkeypatch):
    monkeypatch.delenv("JGRAFT_SCAN_CHUNK", raising=False)
    assert scan_chunk() == schedule.DEFAULT_SCAN_CHUNK
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "0")
    assert scan_chunk() == 0
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "banana")
    assert scan_chunk() == schedule.DEFAULT_SCAN_CHUNK


@pytest.mark.slow
def test_malformed_gate_does_not_crash_import():
    """A malformed integer knob used to raise ValueError out of the
    importer of checker/linearizable.py; now it warns and uses the
    default."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from jepsen_jgroups_raft_tpu.checker import linearizable as m; "
         "print(m.scan_chunk())"],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "JGRAFT_SCAN_CHUNK": "one-hundred-and-twenty-eight"},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == \
        str(schedule.DEFAULT_SCAN_CHUNK)


def test_degraded_platform_note_in_results(monkeypatch):
    """A silently-degraded platform is stamped into every checker
    result; an intended-CPU run (no degrade) carries no such key."""
    rng = random.Random(41)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=6)]
    monkeypatch.setattr(plat, "_DEGRADED_NOTE", None)
    [r] = check_histories(hists, model)
    assert "platform-degraded" not in r
    plat.note_degraded("probe failed: test")
    plat.note_degraded("a later note never overwrites the root cause")
    [r] = check_histories(hists, model)
    assert r["platform-degraded"] == "probe failed: test"
    monkeypatch.setattr(plat, "_DEGRADED_NOTE", None)


def test_perf_scan_stats_summary(monkeypatch):
    """perf.py reports the wavefront counters only when a chunked group
    actually ran (absent beats all-zero in stored results)."""
    from jepsen_jgroups_raft_tpu.checker.perf import scan_stats_summary

    assert scan_stats_summary() is None
    rng = random.Random(43)
    model = CasRegister()
    launches, _ = _dense_launches(
        model, [random_valid_history(rng, "register", n_ops=8)
                for _ in range(4)])
    run_chunked(launches, chunk=4)
    summary = scan_stats_summary()
    assert summary is not None
    assert summary["groups-run"] == 1
    assert summary["chunks-run"] >= 1
