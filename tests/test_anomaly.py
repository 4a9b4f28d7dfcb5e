"""Transactional anomaly rung (ISSUE 19): list-append model
differentials, the Elle-style multi-key graph builder, planted
G0 / G1c / G-single fixtures firing at exactly the right class,
condensation-ablation identity, and the graftd admission overlay that
refutes a submission every per-key unit passes.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker.anomaly import (CLASS_ORDER,
                                                     TxnAnomalyChecker,
                                                     build_txn_graph,
                                                     certify_history,
                                                     certify_planes,
                                                     certify_submission)
from jepsen_jgroups_raft_tpu.checker.independent import \
    IndependentLinearizable
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.models.listappend import (APPEND, APPEND_ANY,
                                                       MAX_ELEM, MAX_LEN,
                                                       READ, ListAppend,
                                                       pack_list,
                                                       unpack_list)

from util import H


# --------------------------------------------------------------- model


def test_pack_unpack_roundtrip_and_bounds():
    for lst in ([], [1], [1, 2, 3], [31, 1, 31], [5, 4, 3, 2, 1, 6]):
        assert unpack_list(pack_list(lst)) == lst
    with pytest.raises(ValueError):
        pack_list([0])
    with pytest.raises(ValueError):
        pack_list([32])
    with pytest.raises(ValueError):
        pack_list([1] * (MAX_LEN + 1))


def test_step_jax_step_columnar_differential():
    """The three step twins agree elementwise over seeded states and
    ops — including illegal transitions (int32 wrap territory)."""
    import jax.numpy as jnp

    m = ListAppend()
    rng = random.Random(0)
    cases = []
    for _ in range(400):
        st = pack_list([rng.randrange(1, 32)
                        for _ in range(rng.randrange(0, MAX_LEN + 1))])
        f = rng.choice([READ, APPEND, APPEND_ANY])
        if f == READ:
            a, b = (st if rng.random() < 0.5
                    else pack_list([rng.randrange(1, 32)])), 0
        elif f == APPEND:
            a, b = st, rng.randrange(1, 32)
        else:
            a, b = rng.randrange(1, 32), 0
        cases.append((st, f, a, b))
    sts = np.array([c[0] for c in cases], np.int32)
    fs = np.array([c[1] for c in cases], np.int32)
    as_ = np.array([c[2] for c in cases], np.int32)
    bs = np.array([c[3] for c in cases], np.int32)
    js, jl = m.jax_step(jnp.array(sts), jnp.array(fs),
                        jnp.array(as_), jnp.array(bs))
    cs, cl = m.step_columnar(sts, fs, as_, bs)
    for i, (st, f, a, b) in enumerate(cases):
        s2, legal = m.step(st, f, a, b)
        assert np.int32(s2) == np.asarray(js)[i], cases[i]
        assert bool(legal) is bool(np.asarray(jl)[i]), cases[i]
        assert np.int32(s2) == cs[i], cases[i]
        assert bool(legal) is bool(cl[i]), cases[i]


def test_encode_columnar_matches_per_pair(monkeypatch):
    """encode_pairs_columnar ≡ the encode_pair loop, byte-identical
    through the production encoder (the models/base.py twin contract,
    pinned via the JGRAFT_ENCODE_VECTOR oracle arm) — crashed appends
    become optional APPEND_ANY, fail ops and unobserved reads drop."""
    m = ListAppend()
    h = H(
        (0, "invoke", "append", 1), (0, "ok", "append", [1]),
        (1, "invoke", "append", 2), (1, "info", "append", None),
        (0, "invoke", "read", None), (0, "ok", "read", [1]),
        (1, "invoke", "append", 3), (1, "fail", "append", None),
        (0, "invoke", "read", None), (0, "info", "read", None),
    )
    vec = encode_history(h, m)
    monkeypatch.setenv("JGRAFT_ENCODE_VECTOR", "0")
    scalar = encode_history(h, m)
    assert np.array_equal(np.asarray(vec.events),
                          np.asarray(scalar.events))
    assert vec.n_slots == scalar.n_slots
    assert list(vec.op_index) == list(scalar.op_index)
    # the pair loop keeps exactly APPEND(ok) + APPEND_ANY(info) +
    # READ(ok): fail ops and unobserved reads drop
    kept = [e for e in (m.encode_pair(p)
                        for p in h.client_ops().pairs()) if e is not None]
    assert sorted(e.f for e in kept) == sorted([APPEND, APPEND_ANY, READ])


def test_malformed_completed_append_is_loud():
    m = ListAppend()
    h = H((0, "invoke", "append", 2), (0, "ok", "append", [1]))
    with pytest.raises(ValueError):
        encode_history(h, m)


# --------------------------------------------- planted anomaly fixtures


def _g1c_history():
    """Cross-key po/wr cycle: each session reads the OTHER key's append
    before its own lands — no ww, no rw, per-key projections clean."""
    return H(
        (1, "invoke", "read", ("y", None)), (1, "ok", "read", ("y", [1])),
        (2, "invoke", "read", ("x", None)), (2, "ok", "read", ("x", [1])),
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (2, "invoke", "append", ("y", 1)), (2, "ok", "append", ("y", [1])),
    )


def _g0_history():
    """Cross-key po/ww cycle: the two sessions' append orders are
    pinned contradictory by a third reader's observations."""
    return H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [2, 1])),
        (1, "invoke", "append", ("y", 1)), (1, "ok", "append", ("y", [1])),
        (2, "invoke", "append", ("y", 2)), (2, "ok", "append", ("y", [1, 2])),
        (2, "invoke", "append", ("x", 2)), (2, "ok", "append", ("x", [2])),
        (3, "invoke", "read", ("x", None)), (3, "ok", "read", ("x", [2, 1])),
        (3, "invoke", "read", ("y", None)), (3, "ok", "read", ("y", [1, 2])),
    )


def _gsingle_history():
    """Single key: a read observes [2] — the rw edge back to append(1)
    closes the ww/wr path, and it is the ONLY rw edge."""
    return H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (1, "invoke", "append", ("x", 2)), (1, "ok", "append", ("x", [1, 2])),
        (2, "invoke", "read", ("x", None)), (2, "ok", "read", ("x", [2])),
    )


def _clean_history():
    return H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (2, "invoke", "append", ("y", 1)), (2, "ok", "append", ("y", [1])),
        (1, "invoke", "append", ("y", 2)), (1, "ok", "append", ("y", [1, 2])),
        (2, "invoke", "read", ("x", None)), (2, "ok", "read", ("x", [1])),
        (1, "invoke", "read", ("y", None)), (1, "ok", "read", ("y", [1, 2])),
    )


def test_plane_builder_labels_the_g1c_shape():
    g = build_txn_graph(_g1c_history())
    assert g is not None and "adj" in g and g["n"] == 4
    sums = {k: int(v.sum()) for k, v in g["planes"].items()}
    assert sums == {"po": 2, "ww": 0, "wr": 2, "rw": 0}
    # adj is exactly the union of the planes
    union = np.zeros_like(g["adj"])
    for p in g["planes"].values():
        union |= p
    assert np.array_equal(union, g["adj"])


def test_planted_anomalies_fire_at_the_right_class():
    for h, want in ((_g0_history(), "G0"), (_g1c_history(), "G1c"),
                    (_gsingle_history(), "G-single")):
        r = certify_history(h)
        assert r["valid?"] is False, (want, r)
        assert set(r["anomalies"]) == {want}, (want, r)
        assert len(r["anomalies"][want]["cycle"]) >= 2, (want, r)
    r = certify_history(_clean_history())
    assert r["valid?"] is True and not r["anomalies"], r


# ------------------------------- G2 and the real-time plane (ISSUE 51)


def _planes(n, **edges):
    """A plane-labeled graph on `n` nodes from `plane=[(u, v), ...]`."""
    planes = {c: np.zeros((n, n), dtype=np.uint8)
              for c in ("po", "ww", "wr", "rw", "rt")}
    for name, pairs in edges.items():
        for u, v in pairs:
            planes[name][u, v] = 1
    adj = np.zeros((n, n), dtype=np.uint8)
    for p in planes.values():
        adj |= p
    return {"n": n, "adj": adj, "planes": planes,
            "op_index": list(range(10, 10 + n))}


#: name -> the graph whose sharpest class it is
PLANE_CASES = {
    "G0": _planes(2, ww=[(0, 1), (1, 0)]),
    "G1c": _planes(2, ww=[(0, 1)], wr=[(1, 0)]),
    "G-single": _planes(2, wr=[(0, 1)], rw=[(1, 0)]),
    "G2": _planes(2, rw=[(0, 1), (1, 0)]),
    "G0-realtime": _planes(2, ww=[(0, 1)], rt=[(1, 0)]),
    "G1c-realtime": _planes(2, wr=[(0, 1)], rt=[(1, 0)]),
    "G-single-realtime": _planes(2, rw=[(0, 1)], rt=[(1, 0)]),
    "G2-realtime": _planes(3, rw=[(0, 1), (1, 2)], rt=[(2, 0)]),
    # a plain class beats a sharper one that needs real time
    "G2 ": _planes(4, rw=[(0, 1), (1, 0)], ww=[(2, 3)], rt=[(3, 2)]),
}


@pytest.mark.parametrize("condense", ("1", "0"))
@pytest.mark.parametrize("name", sorted(PLANE_CASES))
def test_planes_are_named_by_their_sharpest_class(name, condense,
                                                  monkeypatch):
    monkeypatch.setenv("JGRAFT_CYCLE_CONDENSE", condense)
    out = certify_planes(PLANE_CASES[name], kernel=False)
    assert list(out) == list(CLASS_ORDER)
    found = {k: v for k, v in out.items() if v is not None}
    assert list(found) == [name.strip()]
    cycle = found[name.strip()]["cycle"]
    assert len(cycle) >= 2 and set(cycle) <= set(range(10, 14))


def test_a_graph_without_an_rt_plane_is_certified_as_before():
    g = _planes(2, wr=[(0, 1)], rw=[(1, 0)])
    del g["planes"]["rt"]
    out = certify_planes(g, kernel=False)
    assert [k for k, v in out.items() if v is not None] == ["G-single"]
    # the single-op overlay's graphs carry none (its units are served at
    # weaker rungs too, where a real-time edge would refute too much)
    assert "rt" not in build_txn_graph(_g0_history())["planes"]


def test_gsingle_witness_names_the_rw_edge():
    r = certify_history(_gsingle_history())
    w = r["anomalies"]["G-single"]
    u, v = w["rw-edge"]
    assert w["cycle"][0] == u  # witness starts at the rw source
    assert v == w["cycle"][1]


def test_condense_ablation_identity(monkeypatch):
    """JGRAFT_CYCLE_CONDENSE=0 reproduces every verdict and class."""
    fixtures = [_g0_history(), _g1c_history(), _gsingle_history(),
                _clean_history()]

    def classify():
        return [(r["valid?"], sorted(r["anomalies"]))
                for r in (certify_history(h) for h in fixtures)]

    on = classify()
    monkeypatch.setenv("JGRAFT_CYCLE_CONDENSE", "0")
    off = classify()
    assert on == off


def test_kernel_and_host_closure_arms_agree():
    """The G-single reachability closure answers identically through
    the kernel arm and the host arm (kernel=True may still fall back
    to host squaring when no device kernel is routable — the verdict
    identity is the contract either way)."""
    for h in (_gsingle_history(), _clean_history(), _g1c_history()):
        a = certify_history(h, kernel=False)
        b = certify_history(h, kernel=True)
        assert a["valid?"] == b["valid?"]
        assert sorted(a["anomalies"]) == sorted(b["anomalies"])


def test_sharper_than_the_per_key_sequential_rung():
    """THE acceptance shape: the planted G1c passes the per-key
    sequential rung (relaxation rungs ride the independent
    decomposition, which throws away cross-key po) and is refuted by
    the anomaly rung. Per-key LINEARIZABILITY is compositional, so no
    single-op fixture can pass it while carrying a cross-key cycle —
    sequential is the honest comparison."""
    h = _g1c_history()
    seq = IndependentLinearizable(
        ListAppend, consistency="sequential").check({}, h)
    assert seq["valid?"] is True
    assert certify_history(h)["valid?"] is False


def test_checker_facade_and_skip_marker(monkeypatch):
    res = TxnAnomalyChecker().check({}, _g1c_history())
    assert res["valid?"] is False
    # node-cap skip is stamped, never silent
    monkeypatch.setenv("JGRAFT_CYCLE_MAX_OPS", "2")
    from jepsen_jgroups_raft_tpu.checker.schedule import stats_scope

    # the scope's own count: the process's total holds what every
    # earlier test of this worker skipped
    with stats_scope() as scope:
        r = certify_history(_g0_history())
    assert r["valid?"] == "unknown"
    assert r["cycle-skipped-size"] > 2
    assert scope["cycle_size_skips"] == 1


def test_crashed_append_joins_only_when_observed():
    """Required-pull rule: a crashed append is outside the graph unless
    a required op observed its element (then it must have landed)."""
    # crashed append of 2, nobody observes it → 2 nodes (append 1, read)
    h1 = H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (2, "invoke", "append", ("x", 2)), (2, "info", "append", None),
        (3, "invoke", "read", ("x", None)), (3, "ok", "read", ("x", [1])),
    )
    g1 = build_txn_graph(h1)
    assert g1["n"] == 2
    # crashed append of 2 IS observed → it joins, with its ww/wr edges
    h2 = H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (2, "invoke", "append", ("x", 2)), (2, "info", "append", None),
        (3, "invoke", "read", ("x", None)), (3, "ok", "read", ("x", [1, 2])),
    )
    g2 = build_txn_graph(h2)
    assert g2["n"] == 3
    assert int(g2["planes"]["ww"].sum()) == 1  # a(1) → a(2)
    assert int(g2["planes"]["wr"].sum()) == 1  # a(2) → read


def test_duplicate_elements_lose_identification_keep_rw():
    """Two appends of the same element: wr/ww identification is gone
    (conservative), rw edges to genuinely-missing elements survive."""
    h = H(
        (1, "invoke", "append", ("x", 1)), (1, "ok", "append", ("x", [1])),
        (2, "invoke", "append", ("x", 1)), (2, "ok", "append", ("x", [1])),
        (3, "invoke", "append", ("x", 2)), (3, "ok", "append", ("x", [1, 2])),
        (4, "invoke", "read", ("x", None)), (4, "ok", "read", ("x", [1])),
    )
    g = build_txn_graph(h)
    # the read of [1] has no wr (two candidate writers of 1) but an rw
    # to the append of 2 (missing from its observation)
    assert int(g["planes"]["rw"].sum()) >= 1
    r = certify_history(h)
    assert r["valid?"] in (True, False)  # never crashes, never skips


# ------------------------------------------------------ graftd overlay


def test_admission_overlay_refutes_what_units_pass():
    from jepsen_jgroups_raft_tpu.service.request import admit

    g1c = _g1c_history()
    req = admit([[o.to_dict() for o in g1c]], "list-append")
    assert req.txn_anomalies is not None
    assert req.txn_anomalies["valid?"] is False
    hist0 = req.txn_anomalies["histories"][0]
    assert hist0["anomalies"]["G1c"]["cycle"]
    # per-key units finish VALID; the overlay still refutes the verdict
    req.finish("done", [{"valid?": True} for _ in req.units])
    assert req.verdict() is False
    d = req.to_dict()
    assert d["valid?"] is False
    assert d["txn-anomalies"]["histories"][0]["anomalies"]["G1c"]

    clean = _clean_history()
    req2 = admit([[o.to_dict() for o in clean]], "list-append")
    assert req2.txn_anomalies["valid?"] is True
    req2.finish("done", [{"valid?": True} for _ in req2.units])
    assert req2.verdict() is True


def test_submission_certifier_merges():
    sub = certify_submission([_clean_history().client_ops(),
                              _g1c_history().client_ops()])
    assert sub["valid?"] is False
    assert sub["histories"][0]["valid?"] is True
    assert sub["histories"][1]["valid?"] is False


def test_workload_registry_has_list_append():
    from jepsen_jgroups_raft_tpu.service.request import service_workloads
    from jepsen_jgroups_raft_tpu.workload import WORKLOADS

    model_factory, independent = service_workloads()["list-append"]
    assert independent is True
    assert getattr(model_factory(), "txn_anomaly_capable", False)
    assert "list-append" in WORKLOADS
