"""A workload that is split per key, encoded from its rows' columns
(ISSUE 47).

`request.encode_units` takes `multi-register` and `single-register`
submissions that arrive as op-dict rows through `_split_columns`: the
history's columns once, the rows grouped by key with the key taken off
the value, each key's `OpRow`s through the same `encode_history` body.
The object path (`build_units`: an `Op` a row, `split_by_key` with an
`Op.replace` a row) is the ORACLE: equal labels in `str(key)` order,
equal `EncodedHistory` arrays, one fingerprint, one frame byte for
byte, the same refusals. Histories come from the benchmark's own
generator (`benchmarks/generators/keyed.py`) at a size a test can hold.
Then graftd on both wires: the verdicts, folded to one a history, held
to the plain reference that sees the map whole
(`benchmarks/references/frontier.py` + `register_map.py`), and the
counters and spans that came with the split (`histories_admitted`,
`units_admitted`, `ingest.split`, `client.encode`) count what was sent.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.client_worker import fold_units  # noqa: E402
from benchmarks.generators import keyed  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from test_submit_columns import framed  # noqa: E402
from util_bench import example_ctx  # noqa: E402

from jepsen_jgroups_raft_tpu.checker.schedule import snapshot_spans  # noqa: E402
from jepsen_jgroups_raft_tpu.history.packing import encode_history  # noqa: E402
from jepsen_jgroups_raft_tpu.service import (CheckingService,  # noqa: E402
                                             ServiceClient,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.frame import (decode_frame,  # noqa: E402
                                                   encode_submit_frame)
from jepsen_jgroups_raft_tpu.service.request import (WireHistory,  # noqa: E402
                                                     admit, build_units,
                                                     encode_units)

CELL = "register-map-10k.campaign-keyed"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, TRAFFIC = mf.cell(ROOT, MANIFEST, CELL)
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
WAIT_S = 300.0

SHAPES = ("valid", "perturbed", "planted")
#: (keys, ops a key)
SIZES = ((6, 20), (12, 40))
#: what a wire row carries: the benchmark's four keys with the value a
#: tuple (the binary wire's), the same through JSON (values lists), or
#: everything `Op.to_dict` writes
WIRES = ("four-keys", "json-lists", "to-dicts")


def seeded(shape: str, n_keys: int, per_key: int, n: int = 3,
           seed=0) -> list:
    """`n` seeded `keyed` histories as rows `(process, type, f, (key,
    value))`: the configuration's own shapes but for the sizes."""
    rng = random.Random(f"{shape}/{n_keys}/{per_key}/{seed}")
    hs = [keyed.random_valid_rows(
        rng, n_keys * per_key, per_key, CONFIG["processes"],
        CONFIG["value_range"], CONFIG["crash_probability"],
        CONFIG["max_crashes"]) for _ in range(n)]
    if shape == "perturbed":
        hs = [keyed.corrupt_one_key(rng, h) for h in hs]
    if shape == "planted":
        hs = [keyed.plant_impossible_read(h) for h in hs]
    return hs


def listed(v):
    """A value as JSON hands it over: tuples are lists."""
    return [listed(x) for x in v] if isinstance(v, tuple) else v


def wire(hists, how: str = "four-keys") -> list:
    if how == "to-dicts":
        return [[{"process": p, "type": t, "f": f, "value": v,
                  "time": 1000 * i, "index": i}
                 for i, (p, t, f, v) in enumerate(h)] for h in hists]
    value = listed if how == "json-lists" else (lambda v: v)
    return [[{"process": p, "type": t, "f": f, "value": value(v)}
             for p, t, f, v in h] for h in hists]


def rekeyed(hists, name) -> list:
    """The same histories under other keys (`name(key)`)."""
    return [[(p, t, f, (name(v[0]), v[1])) for p, t, f, v in h]
            for h in hists]


def object_path(rows, workload: str):
    """The path as it stood: an `Op` a row, `split_by_key`."""
    model, units = build_units(rows, workload)
    return (model, units, [label for label, _ in units],
            [encode_history(h, model) for _, h in units])


def assert_same_submission(rows, workload: str = "multi-register"):
    model, units, encs, from_columns = encode_units(rows, workload)
    assert from_columns
    o_model, o_units, o_labels, o_encs = object_path(rows, workload)
    labels = [label for label, _ in units]
    assert labels == o_labels
    assert len(encs) == len(o_encs)
    for got, want in zip(encs, o_encs):
        for name in ("events", "op_index", "proc"):
            a, b = getattr(got, name), getattr(want, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert np.array_equal(a, b), name
        assert (got.n_slots, got.n_ops) == (want.n_slots, want.n_ops)
    assert framed(workload, model, labels, encs) \
        == framed(workload, o_model, o_labels, o_encs)
    # the units are the key's rows, the key off the value, the index
    # the row's place in the whole history; no `Op` built to say so
    for (_, lazy), (_, sub) in zip(units, o_units):
        assert isinstance(lazy, WireHistory)
        assert lazy.to_dicts() == sub.to_dicts()
        assert [list(d) for d in lazy.to_dicts()] \
            == [list(d) for d in sub.to_dicts()]  # key order too
        assert lazy._ops is None
    lazy, sub = units[-1][1], o_units[-1][1]
    assert list(lazy) == list(sub) and len(lazy) == len(sub)
    assert lazy.to_dicts() == sub.to_dicts()
    return labels


@pytest.mark.parametrize("how", WIRES)
@pytest.mark.parametrize("size", SIZES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("shape", SHAPES)
def test_columns_equal_objects_on_keyed_histories(shape, size, how):
    hists = seeded(shape, *size)
    labels = assert_same_submission(wire(hists, how))
    # one unit a key, `key=10` before `key=2`
    n_keys = size[0]
    assert labels == [f"h{i}/key={k}" for i in range(len(hists))
                      for k in sorted(range(n_keys), key=str)]
    if n_keys > 10:
        assert labels.index("h0/key=10") < labels.index("h0/key=2")


@pytest.mark.parametrize("keys", ["strings", "mixed", "pairs"])
def test_other_keys_sort_by_their_text(keys):
    name = {"strings": lambda k: f"k{k}",
            "mixed": lambda k: k if k % 2 else f"{k}",
            "pairs": lambda k: ("shard", k)}[keys]
    hists = rekeyed(seeded("perturbed", 12, 20), name)
    # a key that is itself a pair stays hashable only as a tuple: JSON
    # would hand it over a list, which neither path takes
    labels = assert_same_submission(wire(
        hists, "four-keys" if keys == "pairs" else "json-lists"))
    assert labels[:12] == [f"h0/key={k}" for k in sorted(
        (name(k) for k in range(12)), key=str)]


def test_single_register_is_split_the_same_way():
    assert_same_submission(wire(seeded("planted", 6, 20)),
                           "single-register")


def _edges() -> dict:
    def row(p, t, f, v):
        return {"process": p, "type": t, "f": f, "value": v}

    base = [row(0, "invoke", "write", ["a", 1]),
            row(1, "invoke", "read", ["b", None]),
            row(0, "ok", "write", ["a", 1]),
            row(1, "ok", "read", ["b", None])]
    nemesis = {"process": "nemesis", "type": "info", "f": "partition",
               "value": ["n1", "n2"]}
    return {
        # every op of key "c" crashed: one with an `info`, one with no
        # completion at all; a unit all the same
        "a-key-whose-every-op-crashed": base + [
            row(2, "invoke", "write", ["c", 3]),
            row(3, "invoke", "cas", ["c", [3, 4]]),
            row(2, "info", "write", ["c", 3])],
        # nemesis rows anywhere: left out, whatever their value, and
        # the rows after them keep their place among ALL rows as index
        "nemesis-ops": [nemesis] + base[:2] + [dict(nemesis, value=None),
                                               dict(nemesis, value="x")]
        + base[2:],
        # a completion with no value belongs to no key (`split_by_key`);
        # the key's invocation is then an op that never completed
        "a-completion-without-a-value": base + [
            row(4, "invoke", "write", ["a", 2]),
            row(4, "info", "write", None)],
        # a row whose key is None belongs to no key
        "a-none-key": base + [row(5, "invoke", "read", [None, None]),
                              row(5, "ok", "read", [None, 3])],
        "one-row-keys": [row(0, "invoke", "write", ["z", 1]),
                         row(1, "invoke", "write", ["y", 2])],
        "indexes-said-and-unsaid": [
            dict(d, index=7 * i) if i % 2 else
            (dict(d, index=-1) if i == 2 else d)
            for i, d in enumerate(base)],
        "extras-and-errors": base + [
            row(6, "invoke", "cas", ["a", [1, 2]]),
            dict(row(6, "fail", "cas", ["a", [1, 2]]), error="nope",
                 node="n3", time=17)],
    }


@pytest.mark.parametrize("edge", tuple(_edges()))
def test_columns_equal_objects_on_edge_rows(edge):
    # beside a plain history, so that the submission is never empty
    assert_same_submission([_edges()[edge]] + wire(seeded("valid", 6, 20,
                                                          n=1)))


def _malformed() -> dict:
    ok = _edges()["extras-and-errors"]
    inv = {"process": 0, "type": "invoke", "f": "read", "value": ["a", None]}
    return {
        # an invocation with no value at all names no key
        "untupled-op": ok + [dict(inv, value=None, time=5, node="n1")],
        "untupled-op-with-an-index": ok + [dict(inv, value=None, index=99)],
        "a-value-that-is-no-pair": ok + [dict(inv, value=["a", None, 3])],
        "double-invoke": ok + [inv, dict(inv, index=40)],
        "completion-without-invocation": ok + [
            {"process": 9, "type": "ok", "f": "read", "value": ["a", 1]}],
        "unknown-type": ok + [dict(inv, type="maybe")],
        "unknown-f": ok + [dict(inv, f="frobnicate"),
                           dict(inv, f="frobnicate", type="ok")],
    }


@pytest.mark.parametrize("fault", tuple(_malformed()))
def test_malformed_rows_raise_the_object_paths_error(fault):
    rows = wire(seeded("valid", 6, 20, n=1)) + [_malformed()[fault]]
    with pytest.raises(ValueError) as want:
        object_path(rows, "multi-register")
    with pytest.raises(ValueError) as got:
        encode_units(rows, "multi-register")
    assert str(got.value) == str(want.value)
    assert type(got.value) is type(want.value)


def test_other_refusals_are_the_object_paths_too():
    good = wire(seeded("valid", 6, 20, n=1))
    for bad, error in (
            ([{"process": 0, "f": "read"}], KeyError),
            # a value that cannot be taken apart, an unhashable key
            ([{"process": 0, "type": "invoke", "f": "write", "value": 3}],
             TypeError),
            ([{"process": 0, "type": "invoke", "f": "write",
               "value": [[1, 2], 3]}], TypeError)):
        with pytest.raises(error) as want:
            object_path(good + [bad], "multi-register")
        with pytest.raises(error) as got:
            encode_units(good + [bad], "multi-register")
        assert str(got.value) == str(want.value)
    # a submission whose histories hold no keyed row at all
    nothing = [[{"process": "nemesis", "type": "info", "f": "kill",
                 "value": None}]]
    with pytest.raises(ValueError, match="empty submission"):
        object_path(nothing, "multi-register")
    with pytest.raises(ValueError, match="empty submission"):
        encode_units(nothing, "multi-register")


def test_what_stays_on_the_object_path(monkeypatch):
    from jepsen_jgroups_raft_tpu.service.request import history_from_dicts

    rows = wire(seeded("valid", 6, 20, n=2))
    assert not encode_units([history_from_dicts(h) for h in rows],
                            "multi-register")[3]
    # `list-append`'s admission builds the `History` objects anyway
    appends = [[{"process": 0, "type": "invoke", "f": "append",
                 "value": ["x", 1]},
                {"process": 0, "type": "ok", "f": "append",
                 "value": ["x", [1]]}]]
    assert not encode_units(appends, "list-append")[3]
    monkeypatch.setenv("JGRAFT_ENCODE_VECTOR", "0")
    assert not encode_units(rows, "multi-register")[3]


def test_admit_counts_histories_and_units_from_the_labels():
    rows = wire(seeded("planted", 6, 20))
    req = admit(rows, "multi-register")
    assert req.from_columns
    assert (req.n_histories, req.n_rows) == (3, 18)
    ref = admit(rows, "multi-register", algorithm="auto")
    assert req.fingerprint == ref.fingerprint
    whole = admit(wire([[(p, t, f, v[1]) for p, t, f, v in h
                         if v[0] == 0] for h in seeded("valid", 6, 20)]),
                  "register")
    assert (whole.n_histories, whole.n_rows) == (3, 3)


# ------------------------------------------------------------- served


def reference_verdicts(reqs) -> list:
    return [[frontier.linearizable(h, REF) for h in req] for req in reqs]


@pytest.fixture(scope="module", params=[5, 2**31 + 11])
def requests(request):
    """The cell's own generator, four histories a request of 8 keys x
    30 ops, a quarter perturbed, a planted read in every third."""
    config = dict(CONFIG, ops_per_history=240, ops_per_key=30)
    traffic = {"histories_per_request": 4, "perturbed_share": 0.25,
               "planted_every": 3}
    reqs = keyed.make_requests(random.Random(request.param), config,
                               traffic, 6, 0)
    want = reference_verdicts(reqs)
    flat = [v for req in want for v in req]
    assert True in flat and False in flat
    return reqs, want


def spans_moved(before: dict, names) -> dict:
    after = snapshot_spans()
    zero = {"n": 0, "s": 0.0}
    return {name: (after.get(name, zero)["n"] - before.get(name, zero)["n"],
                   after.get(name, zero)["s"] - before.get(name, zero)["s"])
            for name in names}


@pytest.mark.parametrize("lane", ["frame", "json"])
def test_served_verdicts_folded_are_the_whole_maps(requests, lane,
                                                   monkeypatch):
    """graftd as the cell reaches it (the host certifier's lane on, as
    in a deployment), on the cell's wire and on the JSON one: one
    verdict a history after the fold, equal to the reference's; and
    the counters say what was sent."""
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    reqs, want = requests
    send = mf.load_module(ROOT, "wires", TRAFFIC["wire"]).send \
        if lane == "frame" else (
            lambda cl, hs, workload, consistency: cl.submit(
                wire(hs, "json-lists"), workload=workload,
                consistency=consistency))
    svc = CheckingService(store_root=None)
    httpd, port, _ = serve_in_thread(svc)
    before = snapshot_spans()
    try:
        cl = ServiceClient(f"http://127.0.0.1:{port}")
        acks = [send(cl, req, CONFIG["service_workload"],
                     CONFIG["consistency"]) for req in reqs]
        got = []
        for ack, req in zip(acks, reqs):
            rec = cl.result(ack["id"], wait_s=WAIT_S)
            assert rec["status"] == "done", rec
            assert not rec.get("cached")
            assert len(rec["units"]) == len(rec["results"]) == 8 * len(req)
            got.append(fold_units(rec["units"],
                                  [r["valid?"] for r in rec["results"]],
                                  len(req)))
        stats = cl.stats()
        encoded = cl.encode_stats
        cl.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=True)
    assert got == want
    n_hist = sum(len(req) for req in reqs)
    assert stats["submitted"] == len(reqs)
    assert stats["histories_admitted"] == n_hist
    assert stats["units_admitted"] == 8 * n_hist
    assert stats["batches"] >= 1
    assert stats["batched_requests"] >= stats["batches"]
    moved = spans_moved(before, ("ingest.split", "client.encode"))
    # the split ran once a history: in this process for both lanes (the
    # client's for a frame, graftd's `ingest.decode` for JSON)
    assert moved["ingest.split"][0] == n_hist
    if lane == "frame":
        assert (encoded.columns, encoded.objects) == (len(reqs), 0)
        assert encoded.units == 8 * n_hist
        n, s = moved["client.encode"]
        assert n == 8 * n_hist and 0.0 < s <= encoded.seconds
        assert (stats["encoded_from_columns"],
                stats["encoded_through_objects"]) == (0, 0)
        # what the cell's reader makes of them
        reader = mf.load_module(ROOT, "layer_metrics",
                                "client_encode_ms_per_history")
        ctx = example_ctx({
            "stats_before": {"histories_admitted": 0},
            "stats_after": {"histories_admitted": n_hist},
            "spans_before": before, "spans_after": snapshot_spans()})
        assert reader.read(ctx) == pytest.approx(1e3 * s / n_hist)
    else:
        assert moved["client.encode"] == (0, 0.0)
        assert (stats["encoded_from_columns"],
                stats["encoded_through_objects"]) == (len(reqs), 0)


def test_a_frame_that_says_nothing_of_its_encoder_adds_nothing(requests):
    """The header field is optional, and evidence: a frame without it
    (an older client's) is admitted and counted like any other, and one
    whose field is not two sound numbers is admitted too, the field
    dropped."""
    reqs, _ = requests
    model, units, encs, _ = encode_units(wire(reqs[0]), "multi-register")
    labels = [label for label, _ in units]
    fp, plain = framed("multi-register", model, labels, encs)
    assert decode_frame(plain).client_encode is None
    said = encode_submit_frame("multi-register", "auto", "linearizable",
                               labels, encs, fingerprint=fp,
                               client_encode_s=0.25)
    assert decode_frame(said).client_encode == (0.25, len(labels))
    svc = CheckingService(store_root=None, autostart=False)
    try:
        before = snapshot_spans()
        first = svc.submit_frame(plain)
        assert spans_moved(before, ("client.encode",))["client.encode"] \
            == (0, 0.0)
        second = svc.submit_frame(said)
        assert spans_moved(before, ("client.encode",))["client.encode"] \
            == (len(labels), pytest.approx(0.25))
        # the claim moved no key: one fingerprint, the second attached
        assert second.fingerprint == first.fingerprint == fp
        assert second.attached_to == first.id
        stats = svc.stats()
        assert (stats["histories_admitted"], stats["units_admitted"]) \
            == (2 * len(reqs[0]), 2 * len(labels))
    finally:
        svc.shutdown(wait=False)
    for junk in ({"s": "soon", "units": 3}, {"s": -1.0, "units": 3},
                 {"s": float("inf"), "units": 3}, {"s": 0.1}, [0.1, 3],
                 {"s": 0.1, "units": -2}):
        from jepsen_jgroups_raft_tpu.service.frame import _client_encode

        assert _client_encode({"client_encode": junk}) is None


def test_a_replayed_request_is_counted_again(requests, tmp_path):
    """`histories_admitted` / `units_admitted` on replay: a daemon that
    comes back over a journal took those requests on."""
    reqs, _ = requests
    svc1 = CheckingService(store_root=str(tmp_path), autostart=False)
    svc1.submit(wire(reqs[0]), workload="multi-register")
    svc1.submit(wire(reqs[1], "json-lists"), workload="multi-register")
    assert svc1.stats()["units_admitted"] == 8 * 8
    del svc1  # no shutdown: a kill's on-disk state
    svc2 = CheckingService(store_root=str(tmp_path), autostart=False)
    try:
        stats = svc2.stats()
        assert stats["recovered_requests"] == 2
        assert (stats["histories_admitted"], stats["units_admitted"]) \
            == (8, 64)
    finally:
        svc2.shutdown(wait=False)
