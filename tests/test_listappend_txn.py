"""Elle's list-append with a transaction as the op (ISSUE 51).

`list-append-txn` histories (`benchmarks/generators/elle_append.py`, at
a size a test can hold) through the program: the transaction graph's
verdict (`checker/txn_graph.py`) is held to the plain reference
(`benchmarks/references/frontier.py` + `list_append_txn.py`, a frontier
search that knows nothing of graphs) on 2,200 seeded histories; that
comparison, and nothing else, holds the claim that the inference is
COMPLETE for this workload. Then: hand-built G0, G1c, G-single, G2 and
their `-realtime` twins named right; the two ways to a unit's encoding
equal arrays, one fingerprint, one frame; graftd on both wires the same
verdicts and anomalies; the device arm's flags (the closure program on
the CPU) equal the host arm's; the spans and counters that came with it
count what was sent.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.generators import elle_append  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from test_submit_columns import framed  # noqa: E402

from jepsen_jgroups_raft_tpu.checker import txn_graph  # noqa: E402
from jepsen_jgroups_raft_tpu.checker.anomaly import certify_history  # noqa: E402
from jepsen_jgroups_raft_tpu.checker.linearizable import (  # noqa: E402
    check_encoded, check_histories)
from jepsen_jgroups_raft_tpu.checker.schedule import (  # noqa: E402
    snapshot_build_keys, snapshot_spans, snapshot_stats, snapshot_tiers)
from jepsen_jgroups_raft_tpu.history import History, Op  # noqa: E402
from jepsen_jgroups_raft_tpu.history.packing import encode_history  # noqa: E402
from jepsen_jgroups_raft_tpu.service import (CheckingService,  # noqa: E402
                                             ServiceClient,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.request import (admit,  # noqa: E402
                                                     build_units,
                                                     encode_units)

WORKLOAD = "list-append-txn"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, _TRAFFIC = mf.cell(ROOT, MANIFEST, "list-append-1k.campaign-txn")
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
TRAFFIC = {"histories_per_request": 5, "perturbed_share": 0.4,
           "planted_every": 5}

#: (transactions, processes, live keys, appends a key): lists of 32
#: and of 40 elements, so elements past 31 and lists past six
SIZES = ((40, 2, 3, 8), (80, 3, 4, 32), (120, 5, 3, 8), (120, 5, 6, 32),
         (60, 4, 3, 40))
SEEDS = (101, 2 ** 31 + 11)
#: requests of five histories a case: 2 x 5 x 4 x 11 x 5 = 2,200
PARTS = 4
REQUESTS = 11


def dicts(rows):
    return [{"process": p, "type": t, "f": f, "value": v}
            for p, t, f, v in rows]


def histories(seed, size, n_requests, first=0):
    n, procs, keys, writes = size
    cfg = dict(CONFIG, ops_per_history=n, processes=procs, key_count=keys,
               max_writes_per_key=writes)
    reqs = elle_append.make_requests(random.Random(seed), cfg, TRAFFIC,
                                     n_requests, first)
    return [h for req in reqs for h in req]


def verdicts(hs, **kw):
    model, _units, encs, from_columns = encode_units(
        [dicts(h) for h in hs], WORKLOAD)
    assert from_columns
    return check_encoded(encs, model, **kw)


# ------------------------------------- the program against the reference


@pytest.mark.parametrize("part", range(PARTS))
@pytest.mark.parametrize("size", SIZES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_graphs_verdict_is_the_frontier_searchs(seed, size, part):
    hs = histories(seed + 17 * part, size, REQUESTS, first=part)
    want = [frontier.linearizable(h, REF) for h in hs]
    got = [r["valid?"] for r in verdicts(hs)]
    assert got == want
    assert len(hs) == REQUESTS * TRAFFIC["histories_per_request"]
    assert True in want and False in want


class KeepsEveryList:
    """The plain reference with its two savings off: every key's readers
    counted as without end, the keys nobody reads too, so that a map
    keeps every list to the history's end; and every list taken as one
    somebody saw, so that an append is never refused for the list it
    makes and a wrong order lives until a read meets it."""

    INIT = REF.INIT
    step = staticmethod(REF.step)

    class Endless:
        def __init__(self, counts):
            self.counts = counts    # the history's, still being filled

        def __contains__(self, key):
            return key in self.counts

        def __getitem__(self, key):
            return 10 ** 9

    class EveryList:
        def get(self, key, default):
            return self

        def __contains__(self, seen):
            return True

    @classmethod
    def encode(cls, *op):
        out = REF.encode(*op)
        if out is None:
            return None
        (mops, reads, readers, _shown), ok = out
        return (mops, reads, cls.Endless(readers), cls.EveryList()), ok


#: sizes at which a search that forgets nothing still ends
SMALL = ((40, 2, 3, 8), (40, 3, 4, 32), (60, 3, 3, 8), (30, 5, 3, 8))


@pytest.mark.parametrize("size", SMALL, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("seed", SEEDS)
def test_forgetting_a_key_never_changes_the_references_answer(seed, size):
    """ISSUE 51 (3) lets the reference drop a key nobody can read again
    only if that never changes an answer, and the same holds for ending
    a configuration at the append no later read can show: the same
    `step` over the same histories (valid, each perturbation, planted,
    crashed transactions) with nothing forgotten and nothing ended
    early."""
    hs = histories(seed + 5, size, 8)
    want = [frontier.linearizable(h, KeepsEveryList) for h in hs]
    assert [frontier.linearizable(h, REF) for h in hs] == want
    assert True in want and False in want


def test_a_reference_stepped_before_its_history_is_encoded_raises():
    """The count of a key's readers is the history's: an op encoded
    after the first `step` starts another count, and the short count
    is loud, not a verdict."""
    ops = [("txn", (("append", 1, 1),), "ok", (("append", 1, 1),)),
           ("txn", (("r", 1, None),), "ok", (("r", 1, (1,)),)),
           ("txn", (("r", 1, None),), "ok", (("r", 1, (1,)),))]
    (t0, _), (t1, _) = REF.encode(*ops[0]), REF.encode(*ops[1])
    state, legal = REF.step(REF.INIT, t0)
    assert legal
    (t2, _) = REF.encode(*ops[2])       # a count of its own
    state, legal = REF.step(state, t1)  # the one reader that was counted
    assert legal
    with pytest.raises(RuntimeError, match="more readers than were counted"):
        REF.step(state, t2)
    with pytest.raises(RuntimeError, match="more readers than were counted"):
        REF.step(REF.INIT, (t0[0], t0[1], {}, {}))


def test_2200_histories_are_compared():
    assert len(SEEDS) * len(SIZES) * PARTS * REQUESTS \
        * TRAFFIC["histories_per_request"] >= 2000


# --------------------------------------------------- hand-built histories

A, R = "append", "r"


def txns(*steps):
    """`(process, "i" | "ok" | "info" | "fail", micro-ops)` rows."""
    names = {"i": "invoke"}
    return [(p, names.get(t, t), "txn", tuple(mops)) for p, t, mops in steps]


def concurrent(*completed):
    """Every transaction invoked before any completes: no real time."""
    inv = [(p, "i", [(f, k, None if f == R else v) for f, k, v in mops])
           for p, mops in enumerate(completed)]
    return txns(*inv, *[(p, "ok", mops) for p, mops in enumerate(completed)])


def one_by_one(*completed):
    """Each transaction completes before the next is invoked, but for a
    trailing `"open"` marker: `(mops, "open")` stays open to the end."""
    rows, late = [], []
    for p, item in enumerate(completed):
        mops, held = item if isinstance(item, tuple) else (item, None)
        inv = [(f, k, None if f == R else v) for f, k, v in mops]
        rows.append((p, "i", inv))
        (late if held else rows).append((p, "ok", mops))
    return txns(*rows, *late)


ANOMALIES = {
    # key 8 is [1, 2]: T0 then T1; key 9 is [1, 2]: T1 then T0
    "G0": concurrent([(A, 8, 1), (A, 9, 2)], [(A, 8, 2), (A, 9, 1)],
                     [(R, 8, (1, 2)), (R, 9, (1, 2))]),
    # T1 is invoked after T0 completed, and T2 reads T1's element first
    "G0-realtime": one_by_one([(A, 7, 1)], [(A, 7, 2)], [(R, 7, (2, 1))]),
    # each reads what the other appended
    "G1c": concurrent([(A, 1, 1), (R, 2, (1,))], [(A, 2, 1), (R, 1, (1,))]),
    # T0 read an element that T1, invoked after T0 completed, appended
    "G1c-realtime": one_by_one([(R, 1, (5,))], [(A, 1, 5)]),
    # T0 saw T1's append to key 2 and not its append to key 1
    "G-single": concurrent([(R, 1, ()), (R, 2, (1,))],
                           [(A, 1, 1), (A, 2, 1)]),
    # a stale read: T1 missed what T0 had appended and acknowledged
    "G-single-realtime": one_by_one([(A, 1, 40)], [(R, 1, ())]),
    # write skew: each read the key the other appended to, empty
    "G2": concurrent([(R, 1, ()), (A, 2, 1)], [(R, 2, ()), (A, 1, 1)]),
    # T0 -rw-> T1 -rw-> T2, and T2 had completed before T0 was invoked
    "G2-realtime": txns((1, "i", [(A, 1, 1), (R, 2, None)]),
                        (2, "i", [(A, 2, 1)]), (2, "ok", [(A, 2, 1)]),
                        (0, "i", [(R, 1, None)]), (0, "ok", [(R, 1, ())]),
                        (1, "ok", [(A, 1, 1), (R, 2, ())])),
}


@pytest.mark.parametrize("kernel", (False, True), ids=("host", "device"))
@pytest.mark.parametrize("name", sorted(ANOMALIES))
def test_each_anomaly_is_named(name, kernel):
    rows = ANOMALIES[name]
    assert frontier.linearizable(rows, REF) is False
    model, _u, encs, _ = encode_units([dicts(rows)], WORKLOAD)
    [res] = txn_graph.check_txn_rows(encs, model, kernel=kernel)
    assert res["valid?"] is False
    assert list(res["anomalies"]) == [name]
    assert len(res["anomalies"][name]["cycle"]) >= 2
    assert res["decided-tier"] == "cycle"
    assert res["kernel"] == ("closure" if kernel else "host-scc")


def test_g2_in_the_single_op_workload_is_refuted_too():
    """`certify_history` over the older `list-append` shape (an op a
    micro-op, the session the transaction): write skew through two
    sessions. The parent's certifier named G0, G1c and G-single and
    answered `valid? True` for this history."""
    h = History()
    for p, t, f, v in [(0, "invoke", "append", ("y", 1)),
                       (1, "invoke", "append", ("x", 1)),
                       (0, "ok", "append", ("y", [1])),
                       (1, "ok", "append", ("x", [1])),
                       (0, "invoke", "read", ("x", None)),
                       (1, "invoke", "read", ("y", None)),
                       (0, "ok", "read", ("x", [])),
                       (1, "ok", "read", ("y", []))]:
        h.append(Op(p, t, f, v))
    out = certify_history(h, kernel=False)
    assert out["valid?"] is False
    assert list(out["anomalies"]) == ["G2"]
    assert len(out["anomalies"]["G2"]["cycle"]) == 4


NON_CYCLE = {
    "G1a-unwritten-read": concurrent([(R, 1, (9,))]),
    "G1a-aborted-read": txns((0, "i", [(A, 1, 9)]), (0, "fail", [(A, 1, 9)]),
                             (1, "i", [(R, 1, None)]),
                             (1, "ok", [(R, 1, (9,))])),
    "incompatible-order": concurrent([(A, 1, 1)], [(A, 1, 2)],
                                     [(R, 1, (1, 2))], [(R, 1, (2, 1))]),
    "duplicate-elements": concurrent([(A, 1, 1)], [(R, 1, (1, 1))]),
    # it read key 1 without its own earlier append
    "internal": concurrent([(A, 1, 1), (R, 1, ())]),
}


@pytest.mark.parametrize("name", sorted(NON_CYCLE))
def test_each_non_cycle_anomaly_is_found(name):
    rows = NON_CYCLE[name]
    assert frontier.linearizable(rows, REF) is False
    [res] = verdicts([rows])
    assert res["valid?"] is False
    assert name in res["anomalies"] and name in res["flags"]


@pytest.mark.parametrize("rows,want", [
    # an `info` transaction somebody observed took effect: a node
    (txns((0, "i", [(A, 1, 1)]), (0, "info", [(A, 1, 1)]),
          (1, "i", [(R, 1, None)]), (1, "ok", [(R, 1, (1,))])), True),
    # one nobody observed constrains nothing
    (txns((0, "i", [(A, 1, 1)]), (1, "i", [(R, 1, None)]),
          (1, "ok", [(R, 1, ())])), True),
    # what an `info` transaction read is unknown: its reads are dropped
    (txns((0, "i", [(R, 1, None), (A, 2, 1)]),
          (0, "info", [(R, 1, None), (A, 2, 1)]),
          (1, "i", [(R, 2, None)]), (1, "ok", [(R, 2, (1,))])), True),
    # its own append, then its own read of it, then the next append
    (concurrent([(A, 1, 1), (R, 1, (1,)), (A, 1, 2)],
                [(R, 1, (1, 2))]), True),
    # ... and never a read between its two appends by somebody else
    (concurrent([(A, 1, 1), (A, 1, 2)], [(R, 1, (1,))]), False),
    # keys and elements are any int32
    (concurrent([(A, -2 ** 31, 2 ** 31 - 1)],
                [(R, -2 ** 31, (2 ** 31 - 1,))]), True),
])
def test_semantics_by_hand(rows, want):
    assert frontier.linearizable(rows, REF) is want
    [res] = verdicts([rows])
    assert res["valid?"] is want


def test_an_element_appended_twice_is_unknown_never_valid():
    rows = concurrent([(A, 1, 1)], [(A, 1, 1)], [(R, 1, (1, 1))])
    [res] = verdicts([rows])
    assert res["valid?"] == "unknown"
    assert "appended twice" in res["error"]


@pytest.mark.parametrize("value,text", [
    ([["append", 1]], "micro-op is"),
    ([["cas", 1, 2]], "unknown micro-op"),
    ([["append", "k", 2.5]], "is not an int32"),
    ([["append", 2 ** 31, 1]], "is not an int32"),
    ("append", "list of micro-ops"),
])
def test_a_malformed_transaction_is_refused_alike_on_both_paths(value, text):
    rows = [{"process": 0, "type": "invoke", "f": "txn", "value": value}]
    with pytest.raises(ValueError, match=text):
        encode_units([rows], WORKLOAD)
    with pytest.raises(ValueError, match=text):
        model, units = build_units([rows], WORKLOAD)
        encode_history(units[0][1], model)


def test_a_completed_read_without_a_list_is_refused():
    rows = dicts(txns((0, "i", [(R, 1, None)]), (0, "ok", [(R, 1, None)])))
    with pytest.raises(ValueError, match="not a list"):
        encode_units([rows], WORKLOAD)


@pytest.mark.parametrize("seed", range(4))
def test_a_stream_no_encoder_made_is_answered_and_never_raises(seed):
    """A frame's arrays are the client's: whatever int32 rows arrive,
    the launch answers each row (a lying client corrupts its own
    verdict) and the dispatcher never sees an exception."""
    from jepsen_jgroups_raft_tpu.history.packing import EncodedHistory

    rng = np.random.default_rng(seed)
    model = txn_graph_model()
    for _ in range(150):
        n = int(rng.integers(0, 40))
        ev = np.stack([rng.integers(0, 8, n), rng.integers(-1, 4, n),
                       rng.integers(-2, 3, n), rng.integers(-2, 5, n),
                       rng.integers(-1, 4, n)], axis=1).astype(np.int32)
        encs = [EncodedHistory(events=e, n_slots=4, n_ops=0, proc=None,
                               op_index=np.arange(n, dtype=np.int32))
                for e in (ev, ev[::-1].copy())]
        out = txn_graph.check_txn_rows(encs, model, kernel=False)
        assert all(r["valid?"] in (True, False, "unknown") for r in out)
        if np.isin(ev[:, 0], (3, 4, 5)).any() and not (ev[:, 0] == 1).any():
            # micro-ops and no invocation at all: nothing was checked
            assert [r["valid?"] for r in out] == ["unknown"] * 2


@pytest.mark.parametrize("seed", range(3))
def test_no_rows_stream_touches_another_rows_verdict(seed):
    """A launch coalesces the rows of several clients: whatever one
    row's stream holds (micro-ops before its first invocation would
    fall to the row before it), the rows beside it are answered as
    they are alone."""
    from jepsen_jgroups_raft_tpu.history.packing import EncodedHistory

    rng = np.random.default_rng(seed)
    hs = histories(900 + seed, SIZES[0], 2)
    model, _u, real, _ = encode_units([dicts(h) for h in hs], WORKLOAD)
    alone = [txn_graph.check_txn_rows([e], model, kernel=False)[0]
             for e in real]
    for _ in range(20):
        n = int(rng.integers(1, 60))
        ev = np.stack([rng.integers(0, 8, n), rng.integers(-1, 4, n),
                       rng.integers(0, 4, n), rng.integers(1, 9, n),
                       rng.integers(-1, 4, n)], axis=1).astype(np.int32)
        junk = EncodedHistory(events=ev, n_slots=4, n_ops=0, proc=None,
                              op_index=np.arange(n, dtype=np.int32))
        encs = [x for e in real for x in (junk, e)] + [junk]
        out = txn_graph.check_txn_rows(encs, model, kernel=False)
        assert [(r["valid?"], r.get("flags"), r.get("anomalies"))
                for r in out[1::2]] \
            == [(r["valid?"], r.get("flags"), r.get("anomalies"))
                for r in alone]


@pytest.mark.parametrize("block", (1, 3_000, 20_000))
def test_a_launch_inferred_in_blocks_is_the_launch_inferred_whole(
        monkeypatch, block):
    """`infer` takes a launch's rows in blocks that fit the cache; what
    it finds of a row depends on no other row, so on no block size."""
    hs = histories(11, SIZES[3], 4)
    model, _u, encs, _ = encode_units([dicts(h) for h in hs], WORKLOAD)
    assert sum(e.n_events for e in encs) < txn_graph.INFER_BLOCK_EVENTS
    whole = txn_graph.infer(encs)
    want = txn_graph.check_txn_rows(encs, model, kernel=False)
    monkeypatch.setattr(txn_graph, "INFER_BLOCK_EVENTS", block)
    parts = txn_graph.infer(encs)
    for name in ("n_nodes", "edges", "edge_base", "node_op", "node_base"):
        assert np.array_equal(getattr(parts, name), getattr(whole, name))
    assert parts.anomalies == whole.anomalies
    assert parts.undecidable == whole.undecidable
    assert txn_graph.check_txn_rows(encs, model, kernel=False) == want
    assert False in [r["valid?"] for r in want]


def test_lists_too_long_for_one_spine_table_are_unknown(monkeypatch):
    monkeypatch.setattr(txn_graph, "_MAX_CELLS", 4)
    hs = histories(7, SIZES[0], 1)
    out = verdicts(hs)
    assert [r["valid?"] for r in out] == ["unknown"] * len(hs)
    assert all("too long" in r["error"] for r in out)


def txn_graph_model():
    return build_units([dicts(ANOMALIES["G2"])], WORKLOAD)[0]


def test_a_weaker_rung_is_refused():
    with pytest.raises(ValueError, match="linearizable rung"):
        verdicts([ANOMALIES["G2"]], consistency="sequential")


def test_a_stream_of_transactions_is_refused_at_open():
    svc = CheckingService(store_root=None, batch_wait=0.0)
    try:
        with pytest.raises(ValueError, match="finished history"):
            svc.streams.open(workload=WORKLOAD)
    finally:
        svc.shutdown(wait=True)


def test_check_histories_takes_the_model():
    model, units = build_units([dicts(ANOMALIES["G2"]),
                                dicts(NON_CYCLE["internal"])], WORKLOAD)
    out = check_histories([u for _, u in units], model, algorithm="auto")
    assert [r["valid?"] for r in out] == [False, False]
    assert [label for label, _ in units] == ["h0", "h1"]


# ------------------------------------------- the two ways to an encoding


@pytest.mark.parametrize("how", ("tuples", "json-lists", "to-dicts"))
@pytest.mark.parametrize("size", SIZES[:3],
                         ids=lambda s: "x".join(map(str, s)))
def test_columns_and_objects_are_one_encoding(size, how):
    hs = histories(7, size, 2)
    rows = [dicts(h) for h in hs]
    if how == "json-lists":
        import json

        rows = json.loads(json.dumps(rows))
    elif how == "to-dicts":
        rows = [History([Op(**d) for d in h]).to_dicts() for h in rows]
    model, units, encs, from_columns = encode_units(rows, WORKLOAD)
    assert from_columns
    o_model, o_units = build_units(rows, WORKLOAD)
    o_encs = [encode_history(h, o_model) for _, h in o_units]
    labels = [label for label, _ in units]
    assert labels == [label for label, _ in o_units] \
        == [f"h{i}" for i in range(len(hs))]
    for got, want in zip(encs, o_encs):
        for name in ("events", "op_index", "proc"):
            a, b = getattr(got, name), getattr(want, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert np.array_equal(a, b), name
        assert (got.n_slots, got.n_ops) == (want.n_slots, want.n_ops)
    assert framed(WORKLOAD, model, labels, encs) \
        == framed(WORKLOAD, o_model, labels, o_encs)
    # `History` objects take the object path, and say so
    assert encode_units([History([Op(**d) for d in dicts(hs[0])])],
                        WORKLOAD)[3] is False


def test_nemesis_rows_are_left_out_of_a_unit():
    rows = dicts(ANOMALIES["G1c"])
    noisy = rows[:2] + [{"process": "nemesis", "type": "info",
                         "f": "partition", "value": None}] + rows[2:]
    a = encode_units([rows], WORKLOAD)[2][0]
    b = encode_units([noisy], WORKLOAD)[2][0]
    assert np.array_equal(a.events, b.events)


def test_the_old_list_append_workload_is_as_it_was():
    """Split per key, its overlay at admission on the JSON wire."""
    rows = [{"process": 0, "type": "invoke", "f": "append", "value": [1, 3]},
            {"process": 0, "type": "ok", "f": "append", "value": [1, [3]]}]
    req = admit([rows], "list-append")
    assert [label for label, _ in req.units] == ["h0/key=1"]
    assert req.txn_anomalies == {"valid?": True, "histories": [
        {"valid?": True, "anomalies": {}, "nodes": 1}]}
    assert admit([dicts(ANOMALIES["G2"])], WORKLOAD).txn_anomalies is None


# ------------------------------------------------ the device arm, on CPU


@pytest.mark.parametrize("size", SIZES[:3],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_closure_programs_flags_are_the_hosts(size):
    hs = histories(23, size, 4)
    model, _u, encs, _ = encode_units([dicts(h) for h in hs], WORKLOAD)
    host = txn_graph.check_txn_rows(encs, model, kernel=False)
    before = snapshot_stats()
    device = txn_graph.check_txn_rows(encs, model, kernel=True)
    after = snapshot_stats()
    assert [r["valid?"] for r in device] == [r["valid?"] for r in host]
    assert [r.get("flags") for r in device] == [r.get("flags") for r in host]
    assert any(r.get("flags") for r in host)
    assert [r.get("anomalies") for r in device] \
        == [r.get("anomalies") for r in host]
    assert after["closure_launches"] > before["closure_launches"]
    # what ran: rows of the bucket x N^3 a squaring, at least one a
    # closure and three closures a launch
    n = max(r["nodes"] for r in host)
    assert after["closure_macs"] - before["closure_macs"] >= 3 * 8 * n ** 3


def test_a_closure_key_is_a_value_and_is_built_ahead():
    from jepsen_jgroups_raft_tpu.checker import schedule

    hs = histories(29, (40, 2, 3, 8), 2)
    model, _u, encs, _ = encode_units([dicts(h) for h in hs], WORKLOAD)
    txn_graph.check_txn_rows(encs, model, kernel=True, serve_rows=32)
    keys = [k for k in snapshot_build_keys() if k["kind"] == "closure"]
    assert keys and all(k["model"] == "ListAppendTxn" for k in keys)
    served = [k for k in keys if set(k["rows"]) >= {8, 16, 32}]
    assert served, keys
    # the record's entry makes a template that names the same key
    built = [k for k in schedule.snapshot_built()
             if (k["spec"] or {}).get("kind") == "closure"
             and set(k["rows"]) >= {8, 16, 32}][0]
    template = schedule.key_template(model, built["spec"], built["width"],
                                     built["lanes"], max(built["rows"]))
    assert schedule.launch_key(template, built["width"]) == built["key"]
    # a second launch of the key builds nothing and misses no shape
    before = schedule.snapshot_compiles()
    waits = snapshot_spans().get("build.ahead", {"n": 0})["n"]
    txn_graph.check_txn_rows(encs, model, kernel=True, serve_rows=32)
    assert snapshot_spans().get("build.ahead", {"n": 0})["n"] == waits
    assert schedule.snapshot_compiles()["shape_misses"] \
        == before["shape_misses"]


def test_edge_width_is_one_value_a_deployment():
    assert txn_graph.edge_width(1024, 9_000) == 16_384
    assert txn_graph.edge_width(1024, 16_384) == 16_384
    assert txn_graph.edge_width(1024, 16_385) == 32_768
    assert txn_graph.edge_width(64, 10) == 1024


def test_a_graph_past_the_node_cap_is_unknown(monkeypatch):
    monkeypatch.setattr(txn_graph, "CYCLE_MAX_NODES_TILED", 4)
    [res] = verdicts([histories(3, (40, 2, 3, 8), 1)[1]])
    assert res["valid?"] == "unknown" and res["cycle-skipped-size"] > 4


# ------------------------------------------------------ spans, counters


def test_spans_and_counters_count_what_was_sent():
    hs = histories(31, (40, 2, 3, 8), 3)
    stats, spans, tiers = snapshot_stats(), snapshot_spans(), \
        snapshot_tiers()
    out = verdicts(hs, serve_rows=None)
    d = {k: snapshot_stats()[k] - stats[k] for k in (
        "txn_rows", "txn_nodes", "txn_edges", "txn_rows_flagged")}
    assert d["txn_rows"] == len(hs)
    assert d["txn_nodes"] == sum(r["nodes"] for r in out)
    assert d["txn_edges"] > d["txn_nodes"]
    assert d["txn_rows_flagged"] == sum(r["valid?"] is False for r in out)
    now = snapshot_spans()
    assert now["launch.graph"]["n"] \
        - spans.get("launch.graph", {"n": 0})["n"] == len(hs)
    assert snapshot_tiers()["cycle"]["rows"] \
        - tiers.get("cycle", {"rows": 0})["rows"] == len(hs)


# --------------------------------------------------------------- served


def _served(rows, binary):
    svc = CheckingService(store_root=None, batch_wait=0.0)
    httpd, port, _ = serve_in_thread(svc)
    try:
        cl = ServiceClient(f"http://127.0.0.1:{port}")
        st0 = cl.stats()
        rec = cl.submit(rows, workload=WORKLOAD, binary=binary)
        while rec["status"] not in ("done", "failed", "cancelled") \
                or "results" not in rec:
            rec = cl.result(rec["id"], wait_s=120.0)
        assert rec["status"] == "done", rec
        st = cl.stats()
        moved = {k: st[k] - st0[k] for k in (
            "txn_rows", "txn_rows_flagged", "histories_admitted",
            "units_admitted")}
        moved["spans"] = {k: st["spans"][k]["n"]
                          - st0["spans"].get(k, {"n": 0})["n"]
                          for k in ("launch.graph", "demux.counterexample")}
        return rec, moved
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=True)


def test_both_wires_answer_the_same_verdicts_and_anomalies():
    hs = histories(37, (40, 2, 3, 8), 2) + [ANOMALIES["G2"]]
    rows = [dicts(h) for h in hs]
    want = [frontier.linearizable(h, REF) for h in hs]
    a, st_a = _served(rows, binary=True)
    b, st_b = _served(rows, binary=False)
    assert a["fingerprint"] == b["fingerprint"]
    assert a["units"] == b["units"] == [f"h{i}" for i in range(len(hs))]
    for rec, st in ((a, st_a), (b, st_b)):
        assert [r["valid?"] for r in rec["results"]] == want
        assert rec["valid?"] is False
        assert st["txn_rows"] == len(hs)
        assert st["txn_rows_flagged"] == want.count(False)
        assert st["histories_admitted"] == st["units_admitted"] == len(hs)
        assert st["spans"]["launch.graph"] == len(hs)
        assert st["spans"]["demux.counterexample"] == want.count(False)
        assert rec["service-stats"]["decided_tier"] == {"cycle": len(hs)}
    assert a["txn-anomalies"] == b["txn-anomalies"]
    per = a["txn-anomalies"]["histories"]
    assert a["txn-anomalies"]["valid?"] is False
    assert [h["valid?"] for h in per] == want
    assert list(per[-1]["anomalies"]) == ["G2"]
    assert [r.get("anomalies") for r in a["results"]] \
        == [r.get("anomalies") for r in b["results"]]
    assert all(r["anomalies"] for r in a["results"] if not r["valid?"])
