"""A submission encoded from its rows' columns (ISSUE 39).

`request.encode_units` encodes histories that arrive as op-dict rows
from those rows' columns (`OpRow`s zipped from `_wire_columns`, no `Op`
an event) and everything else through the object path as it stood
(`build_units` + `encode_history` over `History` objects), which is the
ORACLE here: equal `EncodedHistory` arrays, so one fingerprint and one
frame byte for byte; the same `ValueError` text for every malformed
input; and the inputs the column path does not take (`History`
objects, ``JGRAFT_ENCODE_VECTOR=0``) counted under `objects` by
`ServiceClient.encode_stats` (workloads split per key: ISSUE 47,
tests/test_split_from_columns.py). One served case: the
same histories as a frame and as JSON are one fingerprint, the same
verdicts, and the JSON one is counted under columns in `/stats`.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.history.ops import History, Op, OpRow
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.service import (CheckingService,
                                             ServiceClient,
                                             serve_in_thread)
from jepsen_jgroups_raft_tpu.service.frame import (decode_frame,
                                                   encode_submit_frame)
from jepsen_jgroups_raft_tpu.service.request import (WireHistory, admit,
                                                     build_units,
                                                     encode_units,
                                                     fingerprint_encodings,
                                                     history_from_dicts,
                                                     rows_from_dicts)

from util import build_history, corrupt, random_valid_history

KINDS = ("counter", "register")
SHAPES = ("valid", "perturbed", "planted", "crashed")
#: what a wire row carries: the benchmark's four keys, or everything
#: `Op.to_dict` writes (`time`, `index` too)
WIRES = ("four-keys", "to-dicts")


def seeded(kind: str, shape: str, n_ops: int, n: int = 3) -> list:
    """`n` seeded histories of `kind`, as `History` objects."""
    rng = random.Random(f"{kind}/{shape}/{n_ops}")
    crash_p = 0.5 if shape == "crashed" else 0.05
    hs = [random_valid_history(rng, kind, n_ops=n_ops, n_procs=5,
                               value_range=5 if kind == "register" else 2,
                               crash_p=crash_p, max_crashes=3)
          for _ in range(n)]
    if shape == "perturbed":
        hs = [corrupt(rng, h) for h in hs]
    if shape == "planted":
        never = 99 if kind == "register" else -5
        hs = [build_history(
            [(o.process, o.type, o.f, o.value) for o in h]
            + [(10_000, "invoke", "read", None),
               (10_000, "ok", "read", never)]) for h in hs]
    return hs


def wire(hists, how: str = "four-keys") -> list:
    if how == "to-dicts":
        return [h.to_dicts() for h in hists]
    return [[{"process": o.process, "type": o.type, "f": o.f,
              "value": o.value} for o in h] for h in hists]


def object_path(rows, workload: str):
    """The path as it stood: an `Op` an event, a `History` a unit."""
    model, units = build_units(rows, workload)
    return (model, [label for label, _ in units],
            [encode_history(h, model) for _, h in units])


def framed(workload, model, labels, encs, client_encode_s=None):
    fp = fingerprint_encodings(model, "auto", encs)
    return fp, encode_submit_frame(workload, "auto", "linearizable",
                                   labels, encs, deadline_ms=None,
                                   priority=0, fingerprint=fp,
                                   client_encode_s=client_encode_s)


def assert_same_submission(rows, workload: str) -> None:
    model, units, encs, from_columns = encode_units(rows, workload)
    assert from_columns
    o_model, o_labels, o_encs = object_path(rows, workload)
    assert [label for label, _ in units] == o_labels
    assert len(encs) == len(o_encs)
    for got, want in zip(encs, o_encs):
        for name in ("events", "op_index", "proc"):
            a, b = getattr(got, name), getattr(want, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert np.array_equal(a, b), name
        assert (got.n_slots, got.n_ops) == (want.n_slots, want.n_ops)
    assert framed(workload, model, o_labels, encs) \
        == framed(workload, o_model, o_labels, o_encs)


@pytest.mark.parametrize("how", WIRES)
@pytest.mark.parametrize("n_ops", (60, 1000))
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_columns_equal_objects_on_seeded_histories(kind, shape, n_ops, how):
    assert_same_submission(wire(seeded(kind, shape, n_ops), how), kind)


def _edge_rows(kind: str) -> dict:
    w = ("add", 1) if kind == "counter" else ("write", 3)
    pair = ([1, 2], "add-and-get") if kind == "counter" else ([3, 4], "cas")
    base = [
        {"process": 0, "type": "invoke", "f": w[0], "value": w[1]},
        {"process": 1, "type": "invoke", "f": "read", "value": None},
        {"process": 0, "type": "ok", "f": w[0], "value": w[1]},
        {"process": 1, "type": "ok", "f": "read", "value": w[1]},
    ]
    nemesis = {"process": "nemesis", "type": "info", "f": "partition",
               "value": ["n1", "n2"]}
    listed = [
        {"process": 2, "type": "invoke", "f": pair[1],
         "value": 1 if kind == "counter" else pair[0]},
        {"process": 2, "type": "ok", "f": pair[1], "value": pair[0]},
    ]
    return {
        # a nemesis row in the middle: left out, and the rows after it
        # keep the position they had among ALL rows as their index
        "nemesis-op": base[:2] + [nemesis, dict(nemesis)] + base[2:],
        "list-value": base + listed,
        # some rows say their index, some do not, one says -1
        "missing-index": [dict(d, index=7 * i) if i % 2 else
                          (dict(d, index=-1) if i == 2 else d)
                          for i, d in enumerate(base + listed)],
        "fail-completion": base + [
            {"process": 3, "type": "invoke", "f": w[0], "value": w[1]},
            {"process": 3, "type": "fail", "f": w[0], "value": w[1],
             "error": "timeout"}],
        "unfinished-invoke": base + [
            {"process": 4, "type": "invoke", "f": w[0], "value": w[1]},
            {"process": 5, "type": "invoke", "f": "read", "value": None}],
        "info-completion": base + [
            {"process": 6, "type": "invoke", "f": w[0], "value": w[1]},
            {"process": 6, "type": "info", "f": w[0], "value": None,
             "error": "indefinite", "node": "n3"}],
        "empty-history": [],
    }


EDGES = tuple(_edge_rows("counter"))


@pytest.mark.parametrize("edge", EDGES)
@pytest.mark.parametrize("kind", KINDS)
def test_columns_equal_objects_on_edge_rows(kind, edge):
    rows = _edge_rows(kind)[edge]
    # beside a plain history, so that the submission is never empty
    assert_same_submission([rows, _edge_rows(kind)["list-value"]], kind)


def _malformed(kind: str) -> dict:
    ok = _edge_rows(kind)["list-value"]
    inv = {"process": 0, "type": "invoke", "f": "read", "value": None}
    return {
        "double-invoke": ok + [inv, dict(inv, index=40)],
        "completion-without-invocation": ok + [
            {"process": 9, "type": "ok", "f": "read", "value": 1}],
        "unknown-type": ok + [dict(inv, type="maybe")],
        "unknown-f": ok + [dict(inv, f="frobnicate"),
                           dict(inv, f="frobnicate", type="ok")],
    }


@pytest.mark.parametrize("fault", tuple(_malformed("counter")))
@pytest.mark.parametrize("kind", KINDS)
def test_malformed_rows_raise_the_same_error(kind, fault):
    rows = [_edge_rows(kind)["list-value"], _malformed(kind)[fault]]
    with pytest.raises(ValueError) as want:
        object_path(rows, kind)
    with pytest.raises(ValueError) as got:
        encode_units(rows, kind)
    assert str(got.value) == str(want.value)


@pytest.mark.parametrize("kind", KINDS)
def test_a_missing_key_and_an_unknown_workload_are_refused(kind):
    rows = [_edge_rows(kind)["list-value"], [{"process": 0, "f": "read"}]]
    with pytest.raises(KeyError):
        object_path(rows, kind)
    with pytest.raises(KeyError):
        encode_units(rows, kind)
    with pytest.raises(ValueError, match="unknown workload"):
        encode_units(rows, kind + "-no-such")
    with pytest.raises(ValueError, match="empty submission"):
        encode_units([], kind)


@pytest.mark.parametrize("edge", EDGES)
@pytest.mark.parametrize("kind", KINDS)
def test_rows_and_wire_history_are_the_client_ops(kind, edge):
    """`rows_from_dicts` holds `history_from_dicts(...).client_ops()`
    field for field of the five; a `WireHistory` answers `to_dicts`
    without building an `Op`, and builds the same `Op`s when asked."""
    dicts = _edge_rows(kind)[edge]
    want = history_from_dicts(dicts).client_ops()
    rows = rows_from_dicts(dicts)
    assert rows == [OpRow(o.process, o.type, o.f, o.value, o.index)
                    for o in want]
    lazy = WireHistory(dicts)
    assert lazy.to_dicts() == want.to_dicts()
    assert [list(d) for d in lazy.to_dicts()] \
        == [list(d) for d in want.to_dicts()]  # key order too
    assert lazy._ops is None
    assert list(lazy) == list(want) and len(lazy) == len(want)
    assert lazy.to_dicts() == want.to_dicts()


# ------------------------------------------------------------ routing


class _Recorder(ServiceClient):
    """A client whose POST goes nowhere: keeps the frames it built."""

    def __init__(self):
        super().__init__("http://127.0.0.1:9")
        self.frames = []

    def _call(self, method, path, body=None, raw=None, **kw):
        self.frames.append(raw)
        return {"id": f"r{len(self.frames)}"}


def _multi_register_rows() -> list:
    return [[
        {"process": 0, "type": "invoke", "f": "write", "value": ["k1", 1]},
        {"process": 0, "type": "ok", "f": "write", "value": ["k1", 1]},
        {"process": 1, "type": "invoke", "f": "read", "value": ["k2", None]},
        {"process": 1, "type": "ok", "f": "read", "value": ["k2", None]},
    ]]


ROUTES = {
    # name: (histories, workload, env, counted under)
    "dict-rows": (lambda: wire(seeded("register", "valid", 60)),
                  "register", None, "columns"),
    "tuples-of-dict-rows": (
        lambda: tuple(tuple(h) for h in wire(seeded("counter", "valid",
                                                    60))),
        "counter", None, "columns"),
    "set-dict-rows": (lambda: wire([random_valid_history(
        random.Random(3), "set", n_ops=30)]), "set", None, "columns"),
    "history-objects": (lambda: seeded("register", "valid", 60),
                        "register", None, "objects"),
    "one-history-object-among-rows": (
        lambda: wire(seeded("counter", "valid", 60))
        + seeded("counter", "valid", 60)[:1], "counter", None, "objects"),
    # split per key from the rows' columns since ISSUE 47
    # (tests/test_split_from_columns.py)
    "independent-workload": (_multi_register_rows, "multi-register", None,
                             "columns"),
    "independent-history-objects": (
        lambda: [history_from_dicts(h) for h in _multi_register_rows()],
        "multi-register", None, "objects"),
    "oracle-arm": (lambda: wire(seeded("register", "valid", 60)),
                   "register", ("JGRAFT_ENCODE_VECTOR", "0"), "objects"),
}


@pytest.mark.parametrize("route", tuple(ROUTES))
def test_encode_stats_says_which_path_a_submission_took(route, monkeypatch):
    make, workload, env, counted = ROUTES[route]
    if env:
        monkeypatch.setenv(*env)
    cl = _Recorder()
    cl.submit(make(), workload=workload, binary=True)
    st = cl.encode_stats
    assert (st.columns, st.objects) == \
        ((1, 0) if counted == "columns" else (0, 1))
    assert st.seconds > 0.0
    # whichever way it went, the frame is the object path's, with what
    # this client's clock read in its header (ISSUE 47)
    model, labels, encs = object_path(make(), workload)
    seconds, n_units = decode_frame(cl.frames[0]).client_encode
    assert 0.0 < seconds <= st.seconds and n_units == len(labels) == st.units
    assert cl.frames == [framed(workload, model, labels, encs,
                                client_encode_s=seconds)[1]]


def test_admit_goes_through_the_same_function(monkeypatch):
    rows = wire(seeded("counter", "planted", 60))
    req = admit(rows, "counter")
    assert req.from_columns
    assert all(isinstance(h, WireHistory) for _, h in req.units)
    ref = admit([history_from_dicts(h) for h in rows], "counter")
    assert not ref.from_columns
    assert req.fingerprint == ref.fingerprint
    assert [label for label, _ in req.units] \
        == [label for label, _ in ref.units]
    assert [h.to_dicts() for _, h in req.units] \
        == [h.to_dicts() for _, h in ref.units]
    monkeypatch.setenv("JGRAFT_ENCODE_VECTOR", "0")
    assert not admit(rows, "counter").from_columns


def test_a_hand_built_history_without_indexes_is_one_submission():
    """`History([...])` leaves `index` at -1; `to_dicts` sends that, and
    the server resolves it to the position on both paths."""
    h = History([Op(0, "invoke", "write", 1), Op(0, "ok", "write", 1),
                 Op(1, "invoke", "read", None), Op(1, "ok", "read", 1)])
    assert {o.index for o in h} == {-1}
    assert_same_submission([h.to_dicts()], "register")


# ------------------------------------------------------------- served


def test_served_frame_and_json_are_one_fingerprint_and_verdict():
    svc = CheckingService(store_root=None, batch_wait=0.0)
    httpd, port, _ = serve_in_thread(svc)
    try:
        cl = ServiceClient(f"http://127.0.0.1:{port}")
        rows = wire(seeded("register", "planted", 60)
                    + seeded("register", "valid", 60))
        r_bin = cl.submit(rows, workload="register", binary=True)
        r_json = cl.submit(rows, workload="register")
        assert r_bin["fingerprint"] == r_json["fingerprint"]
        # the second is an attach or a cached answer, as it always was
        assert r_json.get("attached_to") == r_bin["id"] or r_json["cached"]

        def done(rid):
            rec = cl.result(rid, wait_s=120.0)
            while rec["status"] not in ("done", "failed", "cancelled"):
                rec = cl.result(rid, wait_s=120.0)
            assert rec["status"] == "done", rec
            return rec

        a, b = done(r_bin["id"]), done(r_json["id"])
        assert a["results"] == b["results"]
        assert [r["valid?"] for r in a["results"]] \
            == [False] * 3 + [True] * 3
        assert (cl.encode_stats.columns, cl.encode_stats.objects) == (1, 0)
        st = cl.stats()
        assert (st["encoded_from_columns"],
                st["encoded_through_objects"]) == (1, 0)
        # `History` objects over JSON are op dicts by the time the
        # server sees them: columns again
        cl.submit(seeded("counter", "valid", 60), workload="counter")
        assert cl.stats()["encoded_from_columns"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=True)
