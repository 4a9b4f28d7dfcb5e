"""The `register-100k` deployment of BENCHMARK.json (ISSUE 44), held on
the CPU at a size a test can hold: seeded `synth` register histories
long enough to be LONG (past `LIN_FASTPATH_MAX_EVENTS` 8,192 events:
5,800-6,400 ops at the generator's ~1.46 events an op, and 10,000 ops,
the second shape PR 44 read on the chip), valid, perturbed and planted,
through a `CheckingService` one a request and two of unlike windows a
request against the plain reference, as chunked LONG launches; the LONG
keys of the launch-shape set (the two ladders, the padded launch
against the unpadded one, programs shared inside a ladder step, the
host's record built at graftd's start); the fast lane's length cap; and
the tracing that came with the cell: counter `long_rows`, the readers
(two of them read 0 since PR 50 deleted the segment route they guarded,
from a constant `/stats` serves for them), the generator's gate."""

import json
import random
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.generators import long as long_gen  # noqa: E402
from benchmarks.generators import synth  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from util_bench import bare_ctx, example_ctx  # noqa: E402

from jepsen_jgroups_raft_tpu.checker import autotune, linearizable  # noqa: E402
from jepsen_jgroups_raft_tpu.checker import schedule  # noqa: E402
from jepsen_jgroups_raft_tpu.checker.linearizable import (  # noqa: E402
    LIN_FASTPATH_MAX_EVENTS, check_encoded, fastpath_counters,
    lin_fastpath_plan)
from jepsen_jgroups_raft_tpu.checker.schedule import (  # noqa: E402
    LONG_WIDTH_STEPS, build_dense_launches, launch_shapes, launch_width,
    long_rows, long_width, run_chunked, snapshot_built, snapshot_compiles,
    snapshot_stats)
from jepsen_jgroups_raft_tpu.history.packing import (  # noqa: E402
    encode_history, pack_macro_batch)
from jepsen_jgroups_raft_tpu.history.synth import build_history  # noqa: E402
from jepsen_jgroups_raft_tpu.models import CasRegister  # noqa: E402
from jepsen_jgroups_raft_tpu.ops.dense_scan import (  # noqa: E402
    MERGE_MAX_EVENTS, dense_plans_grouped, make_dense_batch_checker)
from jepsen_jgroups_raft_tpu.platform import install_compile_counters  # noqa: E402
from jepsen_jgroups_raft_tpu.service import buildahead  # noqa: E402
from jepsen_jgroups_raft_tpu.service.daemon import CheckingService  # noqa: E402

CELL = "register-100k.long-run"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, TRAFFIC = mf.cell(ROOT, MANIFEST, CELL)
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
WAIT_S = 300.0
MODEL = CasRegister()
KINDS = ("valid", "perturbed", "planted")
#: (ops of the first served history, more ops a history after it) by the
#: fixture's parameter: just past the LONG threshold, and the 10k-op
#: history PR 44 read on the chip (~14.6k events)
LENGTHS = {"6k-ops": (5800, 300), "10k-ops": (10_000, 0)}


def rows_of(seed, n_ops, max_crashes=3, kind="valid"):
    rows = synth.random_valid_rows(
        random.Random(seed), "register", n_ops, CONFIG["processes"],
        CONFIG["value_range"], CONFIG["crash_probability"], max_crashes)
    if kind == "planted":
        return synth.plant_impossible_read(rows, "register")
    if kind == "perturbed":
        return synth.corrupt(random.Random(seed + 1), rows, "register")
    return rows


def encoded(rows):
    return encode_history(build_history(rows), MODEL)


# ------------------------------------------------------------- served


@pytest.fixture(scope="module", params=sorted(LENGTHS))
def served(request):
    """Three long histories of the configuration at one length, one a
    request, through a `CheckingService`; what came back and what the
    registry counted meanwhile."""
    n_ops, step = LENGTHS[request.param]
    hists = {kind: rows_of(4400 + 7 * k, n_ops + step * k, kind=kind)
             for k, kind in enumerate(KINDS)}
    for h in hists.values():
        assert encoded(h).n_events >= LIN_FASTPATH_MAX_EVENTS
    before = snapshot_stats()
    misses = snapshot_compiles()["shape_misses"]
    svc = CheckingService(store_root=None)
    got = {}
    try:
        for kind, h in hists.items():
            r = svc.submit([build_history(h)], workload="register")
            assert r.wait(WAIT_S) and r.status == "done", r.error
            assert not r.stats.get("fastlane")
            got[kind] = r.results[0]
        stats = svc.stats()
    finally:
        svc.shutdown(wait=True)
    return {"hists": hists, "got": got, "stats": stats,
            "shape_misses": stats["shape_misses"] - misses,
            "long_rows": snapshot_stats()["long_rows"]
            - before["long_rows"]}


@pytest.mark.parametrize("kind", KINDS)
def test_a_served_long_history_agrees_with_the_reference(served, kind):
    want = frontier.linearizable(served["hists"][kind], REF)
    assert want is (kind == "valid") or kind == "perturbed"
    res = served["got"][kind]
    assert res["valid?"] is want
    assert res["decided-tier"] == "dense" and res["kernel"] == "dense"
    assert res["chunked"] is True


def test_the_registry_counts_the_long_rows_that_were_sent(served):
    assert served["long_rows"] == 3
    # `/stats` serves the counter, from the totals
    assert served["stats"]["long_rows"] >= 3


def test_a_served_long_launch_has_a_key_of_the_set(served):
    keys = [k for k in served["stats"]["build_keys"] if k["long"]]
    assert keys, served["stats"]["build_keys"]
    for k in keys:
        assert k["model"] == "CasRegister" and k["kind"] == "domain"
        assert k["width"] == long_width(k["width"]) and k["rows"] == [1]
        assert k["met"] in ("launch", "start")
    assert served["shape_misses"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_two_long_rows_of_unlike_windows_in_one_request_agree_with_the_reference(  # noqa: E501
        kind):
    """The shape PR 44 read on the chip at 1.91 s a pair: two long
    histories of one request whose windows differ (no op crashed in the
    first, up to three in the second), each against the reference."""
    pair = [rows_of(4900, 5800, max_crashes=0, kind=kind),
            rows_of(4907, 6100, max_crashes=3, kind=kind)]
    encs = [encoded(h) for h in pair]
    assert encs[0].n_slots < encs[1].n_slots
    assert min(e.n_events for e in encs) >= LIN_FASTPATH_MAX_EVENTS
    before = snapshot_stats()["long_rows"]
    svc = CheckingService(store_root=None)
    try:
        r = svc.submit([build_history(h) for h in pair],
                       workload="register")
        assert r.wait(WAIT_S) and r.status == "done", r.error
    finally:
        svc.shutdown(wait=True)
    assert snapshot_stats()["long_rows"] == before + 2
    for h, res in zip(pair, r.results):
        assert res["valid?"] is frontier.linearizable(h, REF)
        assert res["kernel"] == "dense" and res["chunked"] is True


# ------------------------------------------------------ the two ladders


@pytest.mark.parametrize("n", [1, 33, 2049, 4225, 65537, 73728, 73729,
                               147456, 150000, 262144])
def test_the_width_ladder_adds_at_most_an_eighth(n):
    w = long_width(n)
    assert n <= w <= max(n + n // LONG_WIDTH_STEPS, 32)
    assert long_width(w) == w <= launch_width(n)
    assert long_width(n + 1) >= w


@pytest.mark.parametrize("lo,hi,same", [
    (131073, 147456, True), (147456, 147457, False),
    (73000, 73728, True), (65536, 65537, False)])
def test_two_lengths_share_a_width_inside_a_step_only(lo, hi, same):
    assert (long_width(lo) == long_width(hi)) is same


@pytest.mark.parametrize("n,shards,want", [(1, 1, 1), (2, 1, 2), (3, 1, 4),
                                           (5, 1, 8), (1, 8, 8), (9, 8, 16)])
def test_long_rows_are_powers_of_two_from_one(n, shards, want):
    assert long_rows(n, shards) == want


def test_a_long_keys_shapes_have_no_gather_and_two_programs_a_bucket():
    shapes = launch_shapes(3, 70000, long=True)
    assert shapes.rows == (1, 2, 4) and shapes.width == 73728
    assert shapes.gather == () and len(shapes) == 6
    assert shapes.step == ((1, 73728), (2, 73728), (4, 73728))
    # the set of every other key is what it was
    assert len(launch_shapes(256, 1024)) == 26


# ------------------------------------- the padded launch, the programs


def long_launch(rows):
    enc = encoded(rows)
    [(idxs, plan)], rest = dense_plans_grouped(MODEL, [enc])
    assert not rest
    batch = pack_macro_batch([enc])
    assert batch["legacy_events"] > MERGE_MAX_EVENTS
    [launch], _ = build_dense_launches(MODEL, [(idxs, plan, batch)])
    assert launch.exact_rows and launch.spec["long"] is True
    return launch, plan, batch


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n_ops", [3000, 5800])
def test_the_padded_launch_gives_the_unpadded_launchs_verdict(n_ops, kind):
    """The wavefront places the row in a device array up the width
    ladder; the monolithic kernel scans the batch as packed."""
    launch, plan, batch = long_launch(rows_of(4500 + n_ops, n_ops,
                                              kind=kind))
    [out] = run_chunked([launch])
    width = long_width(schedule._padded_len(launch, schedule.scan_chunk()))
    assert width >= batch["events"].shape[1]
    kernel = make_dense_batch_checker(MODEL, plan.kind, plan.n_slots,
                                      plan.n_states,
                                      macro_p=batch.get("macro_p"))
    ok, _ = kernel(batch["events"], plan.val_of)
    np.testing.assert_array_equal(out.ok, np.asarray(ok))
    assert bool(out.ok[0]) is frontier.linearizable(
        rows_of(4500 + n_ops, n_ops, kind=kind), REF)


def test_two_lengths_of_one_ladder_step_share_their_programs():
    install_compile_counters()
    # no op crashes: one window, so one kernel key for both
    a = encoded(rows_of(4601, 6000, max_crashes=0))
    b = encoded(rows_of(4602, 6100, max_crashes=0))
    assert a.n_events != b.n_events and a.n_slots == b.n_slots
    [ra] = check_encoded([a], MODEL, algorithm="jax")
    built = snapshot_compiles()
    [rb] = check_encoded([b], MODEL, algorithm="jax")
    after = snapshot_compiles()
    assert ra["valid?"] is True and rb["valid?"] is True
    assert after["programs_built"] == built["programs_built"]
    assert after["shape_misses"] == built["shape_misses"]
    keys = {k["key"] for k in snapshot_built()
            if (k["spec"] or {}).get("long")
            and k["spec"]["n_slots"] == a.n_slots}
    assert len(keys) == 1


def test_graftd_builds_a_recorded_long_key_before_it_is_warm(tmp_path,
                                                             monkeypatch):
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "plans"))
    autotune.reset_for_tests()
    install_compile_counters()
    monkeypatch.setattr(schedule, "_BUILT", {})   # "a new host"
    h = build_history(rows_of(4701, 5900))
    first = CheckingService(store_root=None)
    try:
        r = first.submit([h], workload="register")
        assert r.wait(WAIT_S) and r.status == "done", r.error
    finally:
        first.shutdown(wait=True)
    record = json.loads(next((tmp_path / "plans").rglob(
        buildahead.RECORD_NAME)).read_text())
    [entry] = [k for k in record["keys"] if k["spec"].get("long")]
    assert entry["rows"] == 1 and entry["width"] == long_width(
        entry["width"])
    monkeypatch.setattr(schedule, "_BUILT", {})   # "a new process"
    monkeypatch.setattr(buildahead, "_written", 0)
    second = CheckingService(store_root=None)
    try:
        deadline = time.monotonic() + 120.0
        while not second.stats()["warm"] and time.monotonic() < deadline:
            time.sleep(0.02)
        st = second.stats()
        assert st["warm"] and st["build_ahead"]["source"] == "record"
        [key] = [k for k in st["build_keys"] if k["long"]]
        assert key["met"] == "start" and key["rows"] == [1]
        assert key["width"] == entry["width"]
        r = second.submit([h], workload="register")
        assert r.wait(WAIT_S) and r.status == "done", r.error
        after = second.stats()
        for counter in ("programs_built", "keys_met_by_launch",
                        "shape_misses"):
            assert after[counter] == st[counter], counter
    finally:
        second.shutdown(wait=True)
        autotune.reset_for_tests()


# ------------------------------------------------ the lane's length cap


def test_the_fast_lane_does_not_scan_a_long_row(monkeypatch):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    long_enc = encoded(rows_of(4801, 5900))
    short_enc = encoded(rows_of(4802, 300))
    gated = fastpath_counters()["rows_gated"]
    plan = lin_fastpath_plan([long_enc, short_enc], MODEL)
    assert [idxs for _, idxs in plan] == [[1]]
    assert fastpath_counters()["rows_gated"] == gated + 1
    assert long_enc.n_events >= LIN_FASTPATH_MAX_EVENTS == 8192


# ------------------------------------------------------------ the readers


def reader(name):
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    return mf.load_module(ROOT, "layer_metrics", name)


SERVING = {"stats_before": {"long_rows": 0, "long_rows_segmented": 0,
                            "programs_built": 40, "batches": 1},
           "stats_after": {"long_rows": 0, "long_rows_segmented": 0,
                           "programs_built": 40, "batches": 9},
           "spans_before": {"launch.host": {"n": 1, "s": 0.1}},
           "spans_after": {"launch.host": {"n": 9, "s": 0.9}}}


def test_a_window_without_a_segmented_row_reads_zero_not_nothing():
    """A rehearsal's rows are short and the chip's `auto` keeps a long
    one on the chunked route: the program serves the span registry and
    the counters, launches ran, and none took the segment route."""
    assert reader("segment_share").read(example_ctx(SERVING)) == 0.0
    assert reader("programs_built_in_window").read(
        example_ctx(SERVING)) == 0


def test_the_segmented_share_reads_nothing_where_no_long_row_came():
    share = reader("long_rows_segmented_share")
    assert share.read(example_ctx(SERVING)) is None
    chunked = dict(SERVING, stats_after=dict(SERVING["stats_after"],
                                             long_rows=11))
    assert share.read(example_ctx(chunked)) == 0.0


@pytest.mark.parametrize("name", ["segment_share",
                                  "long_rows_segmented_share"])
def test_a_parent_with_spans_and_without_the_counters_reads_nothing(name):
    ctx = example_ctx({"spans_before": SERVING["spans_before"],
                       "spans_after": SERVING["spans_after"]})
    assert reader(name).read(ctx) is None
    assert reader(name).read(bare_ctx(reader(name).EXAMPLE)) is None


@pytest.fixture(scope="module")
def window():
    """A reader's context as `benchmarks/run.py` makes it, from the
    `/stats` of a real `CheckingService` at the two ends of a window in
    which one long row was served."""
    svc = CheckingService(store_root=None)
    try:
        before = svc.stats()
        r = svc.submit([build_history(rows_of(4950, 5800, max_crashes=0))],
                       workload="register")
        assert r.wait(WAIT_S) and r.status == "done", r.error
        after = svc.stats()
    finally:
        svc.shutdown(wait=True)
    empty = {"fastpath": {}, "tiers": {}}
    return dict(example_ctx({}), before={"stats": before, **empty},
                after={"stats": after, **empty})


def test_stats_serve_long_rows_and_of_the_segment_route_a_zero(window):
    before, after = window["before"]["stats"], window["after"]["stats"]
    assert after["long_rows"] == before["long_rows"] + 1
    # the constant the benchmark's readers need, and no span
    assert before["long_rows_segmented"] == 0 == after["long_rows_segmented"]
    assert "launch.segment" not in after["spans"]
    assert after["spans"]["launch.device"]["n"] > \
        before["spans"]["launch.device"]["n"]


@pytest.mark.parametrize("name", ["segment_share",
                                  "long_rows_segmented_share"])
def test_the_two_routing_readers_read_zero_from_this_program(window, name):
    """PR 50 deleted the route the two guarded: in a window with a long
    row both read 0.0, as on every ledger line since PR 44, until a
    `benchmark` PR takes them out (ROADMAP B1 (s)). The harness's own
    `test_span_metrics.py` wants `segment_share` finite in a rehearsal,
    so `null` is not this PR's to give."""
    assert reader(name).read(window) == 0.0
    assert reader("programs_built_in_window").read(window) is not None


# ------------------------------------------------- the generator's gate


@pytest.mark.parametrize("cap,ops,served", [
    (LIN_FASTPATH_MAX_EVENTS, 100_000, True),   # this tree, the file
    (None, 100_000, False),                     # the tree ISSUE 44 met
    (1 << 20, 100_000, False),                  # a cap past the rows
    (None, 80, True), (None, 2_000, True)])     # rows the lane may scan
def test_no_pool_for_a_program_that_scans_a_long_row_on_the_host(
        monkeypatch, cap, ops, served):
    if cap is None:
        monkeypatch.delattr(linearizable, "LIN_FASTPATH_MAX_EVENTS")
    else:
        monkeypatch.setattr(linearizable, "LIN_FASTPATH_MAX_EVENTS", cap)
    config = dict(CONFIG, ops_per_history=ops)
    if served:
        long_gen.require_long_rows_served(config)
    else:
        with pytest.raises(long_gen.LongRowNotServed, match="host-first"):
            long_gen.require_long_rows_served(config)


def test_the_file_keeps_the_sources_shapes_and_is_not_cut():
    sibling = json.loads((ROOT / "benchmarks" / "configs"
                          / "register-1k.json").read_text())
    differ = {k for k in set(CONFIG) | set(sibling)
              if CONFIG.get(k) != sibling.get(k)}
    assert differ == {"name", "source", "deployment", "generator",
                      "ops_per_history"}
    assert CONFIG["ops_per_history"] == 100_000 and CONFIG["reduced"] == []
    assert (TRAFFIC["clients"], TRAFFIC["client_processes"],
            TRAFFIC["histories_per_request"]) == (1, 1, 1)
    assert TRAFFIC["compare_max_rows"] == 6 and TRAFFIC["warmup_sweep"] == []
    long_gen.require_long_rows_served(CONFIG)
    long_gen.require_long_rows_served({**CONFIG, **CONFIG["rehearsal"]})
    small = dict(CONFIG, ops_per_history=60)
    mix = dict(TRAFFIC, histories_per_request=2)
    assert long_gen.make_requests(random.Random(5), small, mix, 3, 0) \
        == synth.make_requests(random.Random(5), small, mix, 3, 0)
