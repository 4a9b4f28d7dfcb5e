"""The `register-map-10k` deployment of BENCHMARK.json, held on the CPU
at a size a test can hold: its plain reference
(`benchmarks/references/register_map.py`, a map of registers that
forgets the keys the threads have left) on hand-written histories, the
generator's shape (`benchmarks/generators/keyed.py`: keys in order,
overlapping at their seams only, three crashed ops a history), what the
configuration's file states, and the cell's three readers. The served
path is `tests/test_split_from_columns.py`'s."""

import collections
import json
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.generators import keyed  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from util_bench import example_ctx, still_ctx  # noqa: E402

CELL = "register-map-10k.campaign-keyed"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, TRAFFIC = mf.cell(ROOT, MANIFEST, CELL)
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
CONTROL = mf.load_module(ROOT, "references", CONFIG["control"])


def W(p, k, v, typ="ok"):
    return [(p, "invoke", "write", (k, v)), (p, typ, "write", (k, v))]


def R(p, k, v, typ="ok"):
    return [(p, "invoke", "read", (k, None)), (p, typ, "read", (k, v))]


def CAS(p, k, frm, to, typ="ok"):
    return [(p, "invoke", "cas", (k, (frm, to))),
            (p, typ, "cas", (k, (frm, to)))]


@pytest.mark.parametrize("rows,want", [
    (R(0, 0, None), True),
    (R(0, 3, None), True),
    (R(0, 0, 0), False),
    # the keys are registers of their own
    (W(0, 0, 1) + W(1, 1, 2) + R(2, 0, 1) + R(3, 1, 2), True),
    (W(0, 0, 1) + W(1, 1, 2) + R(2, 1, 1), False),
    (W(0, 0, 1) + R(1, 1, None), True),
    (W(0, 0, 1) + CAS(1, 0, 1, 3) + R(2, 0, 3), True),
    (W(0, 0, 1) + CAS(1, 1, 1, 3), False),
    (W(0, 1, 1) + CAS(1, 1, 2, 3, "fail") + R(2, 1, 1), True),
    (W(0, 1, 1) + CAS(1, 1, 1, 3, "fail") + R(2, 1, 3), False),
    # a crashed op may have happened, to the history's end, and may not
    ([(0, "invoke", "write", (0, 4))] + R(1, 0, 4) + R(2, 0, 4), True),
    ([(0, "invoke", "write", (0, 4))] + R(1, 0, None) + R(2, 0, 4), True),
    ([(0, "invoke", "write", (0, 4))] + R(1, 0, 4) + R(2, 0, None), False),
    (W(0, 0, 1) + CAS(1, 0, 1, 2, "info") + R(2, 0, 1) + R(3, 0, 1), True),
    (W(0, 0, 1) + [(1, "invoke", "cas", (0, (0, 2)))] + R(2, 0, 2), False),
    (W(0, 0, 1) + R(1, 0, 7, "info") + R(2, 0, 1), True),
    # two keys open at once, a seam: one order over both
    ([(0, "invoke", "write", (0, 1)), (1, "invoke", "write", (1, 2)),
      (2, "invoke", "read", (0, None)), (1, "ok", "write", (1, 2)),
      (0, "ok", "write", (0, 1)), (2, "ok", "read", (0, None))]
     + R(3, 0, 1) + R(4, 1, 2), True),
    # concurrent last writes of a key: either value, one for every
    # reader (what makes a map that keeps every key unsearchable)
    ([(0, "invoke", "write", (0, 1)), (1, "invoke", "write", (0, 2)),
      (0, "ok", "write", (0, 1)), (1, "ok", "write", (0, 2))]
     + R(2, 0, 1) + R(3, 0, 1), True),
    ([(0, "invoke", "write", (0, 1)), (1, "invoke", "write", (0, 2)),
      (0, "ok", "write", (0, 1)), (1, "ok", "write", (0, 2))]
     + R(2, 0, 1) + R(3, 0, 2), False),
    # the threads move on: key 0 is forgotten once key 2 is written,
    # and what a crashed op of key 0 then does nobody sees
    ([(9, "invoke", "write", (0, 4)), (8, "invoke", "cas", (0, (4, 1)))]
     + W(0, 0, 1) + W(1, 1, 2) + W(2, 2, 3) + W(3, 3, 4) + R(4, 3, 4)
     + R(5, 2, 3), True),
    (W(0, 0, 1) + W(1, 1, 2) + W(2, 2, 3) + R(3, 1, 2) + R(4, 2, 2), False),
    (keyed.plant_impossible_read(W(0, 0, 1) + W(1, 1, 1) + R(2, 1, 1)),
     False),
], ids=["read_of_an_unset_key", "read_of_an_unset_later_key",
        "read_of_a_value_nobody_wrote", "two_keys_two_registers",
        "a_read_sees_its_own_key_only", "an_unwritten_key_stays_unset",
        "cas_ok_takes_effect", "cas_ok_on_another_keys_value",
        "cas_fail_did_not_happen", "cas_fail_leaves_no_value",
        "crashed_write_may_have_happened", "crashed_write_may_happen_late",
        "crashed_write_cannot_unhappen", "info_cas_may_not_have",
        "crashed_cas_still_needs_its_value",
        "read_that_never_completed_constrains_nothing",
        "a_seam_is_one_order_over_both_keys",
        "concurrent_last_writes_either_value",
        "concurrent_last_writes_one_value_for_all",
        "crashed_ops_of_a_forgotten_key", "the_newest_two_keys_are_kept",
        "planted_read_is_invalid"])
def test_register_map_semantics(rows, want):
    assert frontier.linearizable(rows, REF) is want


@pytest.mark.parametrize("rows", [
    W(0, 0, 1) + W(1, 1, 2) + W(2, 2, 3) + R(3, 0, 1),
    W(0, 0, 1) + W(1, 2, 3) + CAS(2, 0, 1, 2),
    W(0, 5, 1) + W(1, 3, 1),
], ids=["a_read", "a_cas", "a_write"])
def test_an_acknowledged_op_of_a_forgotten_key_is_refused_not_answered(rows):
    """Keys that interleave are not this workload: no verdict, and the
    comparison that called ends (`client_worker.compare` lets it
    through), rather than a verdict from a state that forgot."""
    with pytest.raises(REF.KeysOutOfOrder, match="not taken in order"):
        frontier.linearizable(rows, REF)
    assert issubclass(REF.KeysOutOfOrder, ValueError)


def test_the_state_is_the_keys_the_threads_can_still_be_at():
    s = REF.INIT
    for key in range(40):
        s, legal = REF.step(s, ("write", key, key % 5, None, True))
        assert legal
        assert s == ((0, 0) if key == 0 else (key - 1, (key - 1) % 5,
                                              key % 5))
    # equal maps are equal states: a read that changes nothing, a write
    # of the value that is there
    assert REF.step(s, ("read", 39, 4, None, True)) == (s, True)
    assert REF.step(s, ("write", 39, 4, None, True)) == (s, True)
    assert REF.step(s, ("read", 39, 0, None, True)) == (s, False)
    hash(s)


def history(seed, **over) -> list:
    cfg = {**CONFIG, **over}
    return keyed.random_valid_rows(
        random.Random(seed), cfg["ops_per_history"], cfg["ops_per_key"],
        cfg["processes"], cfg["value_range"], cfg["crash_probability"],
        cfg["max_crashes"])


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_a_history_at_the_cells_size_is_the_sources_shape(seed):
    """10,000 ops, keys 0..99 in order, 100 ops each, five threads in
    one group, at most three crashed ops a history; a key is open only
    beside the next one."""
    rows = history(seed)
    invokes = [r for r in rows if r[1] == "invoke"]
    assert len(invokes) == 10_000
    keys = [r[3][0] for r in invokes]
    assert keys == sorted(keys)
    assert collections.Counter(keys) == {k: 100 for k in range(100)}
    assert {r[2] for r in invokes} == {"read", "write", "cas"}
    # an op crashed if it completed `info` or never; its thread is gone
    ends = {}
    for p, typ, _, _ in rows:
        ends[p] = typ
    gone = {p for p, typ in ends.items() if typ in ("invoke", "info")}
    crashed = len(gone)
    assert crashed <= 3
    open_of = {}   # thread -> key of its open op that will complete
    for p, typ, f, (key, _) in rows:
        if p in gone:
            continue
        if typ == "invoke":
            assert p not in open_of
            open_of[p] = key
            live = set(open_of.values())
            assert max(live) - min(live) <= 1, live
            assert len(open_of) <= 5
        else:
            assert open_of.pop(p) == key
    assert not open_of
    # a crashed thread comes back under a fresh process id
    assert len({r[0] for r in rows}) == 5 + crashed
    assert frontier.linearizable(rows, REF) is True


def test_perturbed_and_planted_histories_touch_one_key():
    rows = history(7, ops_per_history=600, ops_per_key=50)
    rng = random.Random(8)
    bad = keyed.corrupt_one_key(rng, rows)
    differ = [i for i, (a, b) in enumerate(zip(rows, bad)) if a != b]
    assert 1 <= len(differ) <= 2
    assert len({rows[i][3][0] for i in differ}) == 1
    assert [r[:1] + r[2:3] for r in bad] == [r[:1] + r[2:3] for r in rows]
    planted = keyed.plant_impossible_read(rows)
    assert planted[:-2] == rows and planted[-1][0] == 10_000
    assert planted[-1][3] == (11, 99)  # the key the threads ended at
    assert frontier.linearizable(planted, REF) is False


def test_the_control_differs_from_the_reference_on_this_kind():
    """At the harness test's sizes and at a tenth of the cell's: the
    control (crashed ops dropped) has to be wrong somewhere, or a run
    with `--control` could end `correct`."""
    traffic = {"histories_per_request": 1, "perturbed_share": 0.1,
               "planted_every": 8}
    config = dict(CONFIG, ops_per_history=1000)
    reqs = keyed.make_requests(random.Random(2**31 + 5), config, traffic,
                               60, 0)
    differ = sum(CONTROL.linearizable(h, REF)
                 is not frontier.linearizable(h, REF)
                 for req in reqs for h in req)
    assert differ >= 2, differ


def test_the_file_states_the_source_and_weakens_no_guarantee():
    with open(ROOT / "benchmarks" / "configs" / "register-1k.json") as fh:
        register = json.load(fh)
    assert (CONFIG["ops_per_history"], CONFIG["ops_per_key"]) == (10_000,
                                                                  100)
    assert CONFIG["processes"] == 5 and CONFIG["value_range"] == 5
    assert CONFIG["max_crashes"] == 3 and CONFIG["reduced"] == []
    assert sorted(CONFIG["assumed"]) == [
        "crash_probability", "max_crashes", "ops_per_key", "processes"]
    assert set(CONFIG["why"]) == set(CONFIG["assumed"])
    for key in ("crash_probability", "max_crashes", "guarantees",
                "deployment", "consistency", "control", "processes",
                "value_range"):
        assert CONFIG[key] == register[key], key
    assert (CONFIG["history_kind"], CONFIG["service_workload"],
            CONFIG["generator"], CONFIG["reference"]) == (
        "register-map", "multi-register", "keyed", "register_map")
    assert len(CONFIG["source"]) <= 200
    for part in ("configs[3]", "workload.clj:7-15", "register.clj:106-117",
                 "raft.clj:24-27", "--ops-per-key 100"):
        assert part in CONFIG["source"]
    listed = [c for c in MANIFEST["configs"]
              if c["name"] == "register-map-10k"][0]
    assert listed["source"] == CONFIG["source"] and listed["reduced"] == []
    # the mix: eight runs in flight, one history a request
    assert (TRAFFIC["clients"], TRAFFIC["client_processes"],
            TRAFFIC["histories_per_request"]) == (8, 8, 1)
    assert (TRAFFIC["loop"], TRAFFIC["wire"]) == ("closed", "binary")
    assert (TRAFFIC["perturbed_share"], TRAFFIC["planted_every"]) == (0.1, 8)
    assert TRAFFIC["warmup_sweep"] == []


# ------------------------------------------------------------- readers


def reader(name):
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    return mf.load_module(ROOT, "layer_metrics", name)


@pytest.mark.parametrize("name", ["units_per_history",
                                  "client_encode_ms_per_history",
                                  "requests_per_batch"])
def test_a_reader_of_the_cell_reads_its_window_or_nothing(name):
    r = reader(name)
    assert r.read(example_ctx(r.EXAMPLE)) == pytest.approx(
        r.EXAMPLE["want"])
    assert r.read(still_ctx(r.EXAMPLE)) is None
    # a program that serves spans and the older counters, and none of
    # this PR's: the parent
    parent = example_ctx({
        "stats_before": {"submitted": 1}, "stats_after": {"submitted": 9},
        "spans_before": {"ingest.decode": {"n": 1, "s": 0.1}},
        "spans_after": {"ingest.decode": {"n": 9, "s": 0.9}}})
    assert r.read(parent) is None


def test_units_per_history_is_one_where_nothing_is_split():
    r = reader("units_per_history")
    ctx = example_ctx({
        "stats_before": {"histories_admitted": 64, "units_admitted": 64},
        "stats_after": {"histories_admitted": 320, "units_admitted": 320}})
    assert r.read(ctx) == 1.0


def test_frames_that_say_nothing_leave_the_encode_reader_silent():
    """Histories admitted, and no frame said what its encoder cost (an
    older client, or the JSON wire): nothing, not 0 ms."""
    r = reader("client_encode_ms_per_history")
    ctx = example_ctx({
        "stats_before": {"histories_admitted": 4},
        "stats_after": {"histories_admitted": 44},
        "spans_before": {"ingest.decode": {"n": 4, "s": 0.1}},
        "spans_after": {"ingest.decode": {"n": 44, "s": 0.9}}})
    assert r.read(ctx) is None
