"""The `register-1k` deployment of BENCHMARK.json, held on the CPU at a
size a test can hold: its plain reference (`benchmarks/references/
cas_register.py`) on hand-written histories, the reference against the
program at the configuration's OWN value range (five values: the domain
kernels' S 8 bucket, which `tests/benchmark_harness/test_reference.py`'s
three values never reach) through the library and through graftd's
served binary lane, and the cell's readers on the windows whose reading
has to be a 0 in the ledger and not a gap."""

import json
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.generators import synth  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from util_bench import example_ctx, still_ctx  # noqa: E402

CELL = "register-1k.campaign"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, TRAFFIC = mf.cell(ROOT, MANIFEST, CELL)
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
WAIT_S = 300.0


def W(p, v):
    return [(p, "invoke", "write", v), (p, "ok", "write", v)]


def R(p, v, typ="ok"):
    return [(p, "invoke", "read", None), (p, typ, "read", v)]


def CAS(p, frm, to, typ="ok"):
    return [(p, "invoke", "cas", (frm, to)), (p, typ, "cas", (frm, to))]


@pytest.mark.parametrize("rows,want", [
    (R(0, None), True),
    (R(0, 0), False),
    (W(0, 1) + W(1, 2) + R(2, 1), False),
    (W(0, 1) + CAS(1, 1, 3) + R(2, 3), True),
    (W(0, 1) + CAS(1, 1, 3) + R(2, 1), False),
    (W(0, 1) + CAS(1, 2, 3), False),
    (W(0, 1) + CAS(1, 2, 3, "fail") + R(2, 1), True),
    (W(0, 1) + CAS(1, 1, 3, "fail") + R(2, 3), False),
    ([(0, "invoke", "write", 4)] + R(1, 4) + R(2, 4), True),
    ([(0, "invoke", "write", 4)] + R(1, None) + R(2, None), True),
    (W(0, 1) + [(1, "invoke", "cas", (1, 2))] + R(2, 2) + R(3, 2), True),
    (W(0, 1) + CAS(1, 1, 2, "info") + R(2, 1) + R(3, 1), True),
    (W(0, 1) + [(1, "invoke", "cas", (0, 2))] + R(2, 2), False),
    (W(0, 1) + [(1, "invoke", "read", None)] + R(2, 3, "info")
     + R(3, 1), True),
    (synth.plant_impossible_read(W(0, 1) + R(1, 1), "register"), False),
    # concurrent writes: either order, but one order for every reader
    ([(0, "invoke", "write", 1), (1, "invoke", "write", 2),
      (0, "ok", "write", 1), (1, "ok", "write", 2)] + R(2, 1), True),
], ids=["read_of_the_unset_register", "read_of_a_value_nobody_wrote",
        "stale_read_after_an_acknowledged_write", "cas_ok_takes_effect",
        "read_of_the_value_a_cas_replaced", "cas_ok_on_another_value",
        "cas_fail_did_not_happen", "cas_fail_leaves_no_value",
        "crashed_write_may_have_happened", "crashed_write_may_not_have",
        "crashed_cas_may_have_happened", "info_cas_may_not_have",
        "crashed_cas_still_needs_its_value",
        "read_that_never_completed_constrains_nothing",
        "planted_read_is_invalid", "concurrent_writes_either_order"])
def test_cas_register_semantics(rows, want):
    assert frontier.linearizable(rows, REF) is want


def test_the_file_states_the_source_and_weakens_no_guarantee():
    with open(ROOT / "benchmarks" / "configs" / "counter-1k.json") as fh:
        counter = json.load(fh)
    assert CONFIG["value_range"] == 5 and CONFIG["processes"] == 5
    assert CONFIG["ops_per_history"] == 1000 and CONFIG["reduced"] == []
    assert sorted(CONFIG["assumed"]) == ["crash_probability", "max_crashes"]
    for key in ("crash_probability", "max_crashes", "guarantees",
                "deployment", "consistency", "control", "generator"):
        assert CONFIG[key] == counter[key], key
    for part in ("configs[0]", "register.clj:21-34", ":106-111"):
        assert part in CONFIG["source"]


def requests(seed):
    """The cell's own generator and shapes but for the length: 120 ops,
    a quarter of the histories perturbed, a planted read in every third
    request."""
    config = dict(CONFIG, ops_per_history=120)
    traffic = {"histories_per_request": 4, "perturbed_share": 0.25,
               "planted_every": 3}
    return synth.make_requests(random.Random(seed), config, traffic, 12, 0)


@pytest.fixture(scope="module", params=[3, 2**31 + 7])
def seeded(request):
    reqs = requests(request.param)
    want = [[frontier.linearizable(h, REF) for h in req] for req in reqs]
    flat = [v for req in want for v in req]
    assert True in flat and False in flat
    return reqs, want


def test_reference_agrees_with_the_library_at_five_values(seeded):
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.packing import encode_history
    from jepsen_jgroups_raft_tpu.history.synth import build_history
    from jepsen_jgroups_raft_tpu.models import CasRegister

    reqs, want = seeded
    hs = [build_history(h) for req in reqs for h in req]
    got = [r["valid?"] for r in check_histories(hs, CasRegister(),
                                                algorithm="auto")]
    assert got == [v for req in want for v in req]
    # what the three-valued case never reaches: a domain past four
    # states, which the domain kernels pad to S 8
    model = CasRegister()
    assert max(len(model.dense_domain(encode_history(h, model).events))
               for h in hs) > 4


def test_reference_agrees_with_the_served_binary_lane(seeded, monkeypatch):
    """graftd as the cell reaches it: `CheckingService` behind its HTTP
    front, the cell's own wire, the host certifier's lane on as in a
    deployment (pytest pins it off for the kernel suites)."""
    from jepsen_jgroups_raft_tpu.service import (CheckingService,
                                                 ServiceClient,
                                                 serve_in_thread)

    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    reqs, want = seeded
    wire = mf.load_module(ROOT, "wires", TRAFFIC["wire"])
    svc = CheckingService(store_root=None)
    httpd, port, _ = serve_in_thread(svc)
    try:
        cl = ServiceClient(f"http://127.0.0.1:{port}")
        acks = [wire.send(cl, req, CONFIG["service_workload"],
                          CONFIG["consistency"]) for req in reqs]
        got = []
        for ack in acks:
            rec = cl.result(ack["id"], wait_s=WAIT_S)
            assert rec["status"] == "done", rec
            assert not rec.get("cached")
            got.append([r["valid?"] for r in rec["results"]])
        cl.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=True)
    assert got == want


def reader(name):
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    return mf.load_module(ROOT, "layer_metrics", name)


def test_a_closed_gate_reads_zero_not_nothing():
    """Rows offered, every one routed kernel-first or scanned and
    discarded: the lane delivered 0 % of them. Nothing offered is
    nothing to read."""
    lane = reader("lane_delivered_share")
    ctx = example_ctx({"fastpath_before": {"rows_scanned": 64,
                                           "rows_gated": 0,
                                           "rows_delivered": 0},
                       "fastpath_after": {"rows_scanned": 96,
                                          "rows_gated": 4000,
                                          "rows_delivered": 0}})
    assert lane.read(ctx) == 0.0
    assert lane.read(still_ctx(lane.EXAMPLE)) is None


@pytest.mark.parametrize("name", ["dispatch_scan_share",
                                  "shape_misses_in_window",
                                  "build_ahead_ms_in_window"])
def test_a_still_window_of_a_serving_program_reads_zero(name):
    """The program serves spans and counters, launches ran, and the
    reader's own span or counter is not among those that moved."""
    r = reader(name)
    ctx = example_ctx({"stats_before": {"shape_misses": 0, "batches": 1},
                       "stats_after": {"shape_misses": 0, "batches": 9},
                       "spans_before": {"launch.host": {"n": 1, "s": 0.1}},
                       "spans_after": {"launch.host": {"n": 9, "s": 0.9}}})
    got = r.read(ctx)
    assert got is not None and got == 0.0
