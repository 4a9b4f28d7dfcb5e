"""Distributed-tier tests (ISSUE 7).

Fast (tier-1) coverage: shard-boundary math, the shard-aware per-host
packers pinned against global-pack-then-shard, the defensive cluster
env parse, and graftd's least-loaded shard routing with placement
stamps. Slow coverage: REAL 2-process clusters over localhost gRPC —
verdicts asserted bitwise-identical to a single-process run of the same
batch (dense grouped + sort rung, macro on and off) and the global-mesh
capability probe.
"""

import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import distributed_worker as dw
from util import random_valid_history

from jepsen_jgroups_raft_tpu.history.packing import (
    encode_history, macro_compact, macro_row_count, pack_batch,
    pack_batch_shard, pack_macro_batch, pack_macro_batch_shard)
from jepsen_jgroups_raft_tpu.models.register import CasRegister
from jepsen_jgroups_raft_tpu.parallel import distributed
from jepsen_jgroups_raft_tpu.parallel.launch import launch_local_cluster
from jepsen_jgroups_raft_tpu.service.scheduler import ShardLoads

REPO = Path(__file__).resolve().parent.parent
WORKER = REPO / "tests" / "distributed_worker.py"


@pytest.fixture
def clean_degrade_note():
    """The malformed-env paths record a process-wide degrade note
    (first-note-wins); restore it so other tests' checker results are
    not stamped by this module's negative cases."""
    import jepsen_jgroups_raft_tpu.platform as plat

    saved = plat._DEGRADED_NOTE
    yield
    plat._DEGRADED_NOTE = saved


# ------------------------------------------------------------ shard math


def test_shard_bounds_balanced():
    assert distributed.shard_bounds(8, 2, 0) == (0, 4)
    assert distributed.shard_bounds(8, 2, 1) == (4, 8)


def test_shard_bounds_uneven_covers_all_rows():
    for n in (1, 2, 3, 5, 7):
        for rows in (0, 1, 5, 13, 100):
            cuts = [distributed.shard_bounds(rows, n, i) for i in range(n)]
            assert cuts[0][0] == 0
            assert cuts[-1][1] == rows
            for (a, b), (c, d) in zip(cuts, cuts[1:]):
                assert b == c  # contiguous, no gap/overlap
                assert a <= b


def test_shard_bounds_fewer_rows_than_shards():
    cuts = [distributed.shard_bounds(2, 4, i) for i in range(4)]
    assert cuts[-1][1] == 2
    assert sum(hi - lo for lo, hi in cuts) == 2  # some shards empty


def test_shard_bounds_granularity_aligns_non_final_cuts():
    for g in (2, 4, 8):
        cuts = [distributed.shard_bounds(100, 3, i, granularity=g)
                for i in range(3)]
        assert cuts[0][0] == 0 and cuts[-1][1] == 100
        for lo, hi in cuts[:-1]:
            assert hi % g == 0  # interior boundaries land on g
        for (a, b), (c, d) in zip(cuts, cuts[1:]):
            assert b == c


def test_shard_bounds_bad_index_raises():
    with pytest.raises(ValueError):
        distributed.shard_bounds(8, 2, 2)


def test_placement_granularity_positive():
    assert distributed.placement_granularity() >= 1


# ----------------------------------------------------- per-host packing


def _mixed_encs(n=13, n_ops=40):
    """Batch with macro-interesting shapes: crashed trailing opens,
    spill-length runs, varying event counts."""
    import random

    rng = random.Random(5)
    model = CasRegister()
    hs = [random_valid_history(rng, "register", n_ops=n_ops,
                               n_procs=4 + (i % 3) * 6,
                               crash_p=0.1, max_crashes=4)
          for i in range(n)]
    return [encode_history(h, model) for h in hs]


def test_macro_row_count_matches_compaction():
    for e in _mixed_encs(6):
        for P in (1, 2, 4, 16):
            assert macro_row_count(e.events, P) == \
                macro_compact(e.events, P).shape[0]


@pytest.mark.parametrize("n_shards", [1, 2, 3, 5])
def test_pack_macro_shard_equals_global_then_shard(n_shards):
    encs = _mixed_encs()
    g = pack_macro_batch(encs)
    parts = [pack_macro_batch_shard(encs, p, n_shards)
             for p in range(n_shards)]
    cat = np.concatenate([pp["events"] for pp in parts])
    assert cat.shape == g["events"].shape
    assert (cat == g["events"]).all()
    assert (np.concatenate([pp["n_events"] for pp in parts])
            == g["n_events"]).all()
    assert (np.concatenate([pp["n_slots"] for pp in parts])
            == g["n_slots"]).all()
    for pp in parts:
        assert pp["macro_p"] == g["macro_p"]
        assert pp["legacy_events"] == g["legacy_events"]
    # shard bookkeeping covers the batch contiguously
    assert parts[0]["shard"][0] == 0
    assert parts[-1]["shard"][1] == len(encs)


@pytest.mark.parametrize("n_shards", [1, 2, 3])
def test_pack_batch_shard_equals_global_then_shard(n_shards):
    encs = _mixed_encs(7)
    g = pack_batch(encs)
    parts = [pack_batch_shard(encs, p, n_shards) for p in range(n_shards)]
    for key in ("events", "op_index", "n_events", "n_slots"):
        cat = np.concatenate([pp[key] for pp in parts])
        assert (cat == g[key]).all(), key


def test_pack_macro_shard_global_padding_rows():
    """n_rows > batch: the trailing pad rows are EV_PAD zeros assigned
    to the trailing shards (the mesh-divisible launch shape
    check_batch_global needs)."""
    encs = _mixed_encs(5)
    n_rows = 8
    parts = [pack_macro_batch_shard(encs, p, 2, n_rows=n_rows)
             for p in range(2)]
    cat = np.concatenate([pp["events"] for pp in parts])
    assert cat.shape[0] == n_rows
    g = pack_macro_batch(encs)
    assert (cat[:5] == g["events"]).all()
    assert (cat[5:] == 0).all()
    assert (np.concatenate([pp["n_events"] for pp in parts])[5:] == 0).all()


def test_pack_shard_n_rows_smaller_than_batch_raises():
    encs = _mixed_encs(4)
    with pytest.raises(ValueError):
        pack_macro_batch_shard(encs, 0, 2, n_rows=2)


# ------------------------------------------- env gates / defensive parse


def test_parse_cluster_env_absent(monkeypatch):
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        monkeypatch.delenv(k, raising=False)
    assert distributed.parse_cluster_env() is None


def test_parse_cluster_env_malformed_is_loud_not_fatal(
        monkeypatch, caplog, clean_degrade_note):
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "two")
    with caplog.at_level("WARNING"):
        assert distributed.parse_cluster_env() is None
    assert any("malformed" in r.message for r in caplog.records)
    # maybe_init_distributed degrades to False instead of raising the
    # bare-int() ValueError the stub used to.
    assert distributed.maybe_init_distributed() is False


def test_parse_cluster_env_inconsistent(monkeypatch, caplog,
                                        clean_degrade_note):
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "5")
    with caplog.at_level("WARNING"):
        assert distributed.parse_cluster_env() is None
    assert any("inconsistent" in r.message for r in caplog.records)


def test_autodetect_gate_off_by_default(monkeypatch):
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.delenv("JGRAFT_DISTRIBUTED_AUTODETECT", raising=False)
    assert distributed.maybe_init_distributed() is False


def test_autodetect_no_cluster_returns_false(monkeypatch, caplog):
    """The docstring's promised autodetection path: on a host with no
    detectable cluster, the bare initialize raises internally and the
    entry degrades to False with a warning — never an exception."""
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("JGRAFT_DISTRIBUTED_AUTODETECT", "1")
    with caplog.at_level("WARNING"):
        assert distributed.maybe_init_distributed() is False
    assert any("autodetect" in r.message for r in caplog.records)


def test_distributed_enabled_gate(monkeypatch):
    monkeypatch.setenv("JGRAFT_DISTRIBUTED", "0")
    assert distributed.distributed_enabled() is False
    assert distributed.wavefront_active() is False
    monkeypatch.setenv("JGRAFT_DISTRIBUTED", "garbage")
    assert distributed.distributed_enabled() is True  # default, loudly


def test_wavefront_inactive_single_process():
    assert distributed.process_count() == 1
    assert distributed.wavefront_active() is False


def test_run_sharded_single_process_no_wire():
    """Outside a cluster run_sharded is the identity wrapper — no
    coordination-service client is touched (there is none)."""
    seen = []

    def check(rows):
        seen.append(len(rows))
        return [{"valid?": True} for _ in rows]

    out = distributed.run_sharded(list(range(5)), check)
    assert len(out) == 5 and seen == [5]


# ------------------------------------------------- graftd shard routing


def test_shard_loads_least_loaded_deterministic():
    s = ShardLoads(3)
    assert s.least_loaded() == 0  # tie → lowest id
    s.add(0, 4)
    assert s.least_loaded() == 1
    s.add(1, 2)
    assert s.least_loaded() == 2
    s.add(2, 8)
    assert s.least_loaded() == 1
    s.done(2, 8)
    assert s.least_loaded() == 2
    s.done(0, 100)  # over-release clamps at zero
    assert s.snapshot() == [0, 2, 0]


def test_service_routes_buckets_to_least_loaded_shards():
    """Two different shape buckets queued before start: the dispatcher
    must route them to DIFFERENT shards (least-loaded, ties to lowest
    id) and stamp the placement into per-request stats."""
    import random

    from jepsen_jgroups_raft_tpu.service import CheckingService

    rng = random.Random(5)
    h_small = random_valid_history(rng, "register", n_ops=20, crash_p=0.0)
    h_big = random_valid_history(rng, "register", n_ops=400, crash_p=0.0)

    def stub(encs, model, algorithm="auto"):
        time.sleep(0.4)  # hold the first shard busy while #2 routes
        return [{"valid?": True}] * len(encs)

    svc = CheckingService(store_root=None, autostart=False, n_workers=2,
                          check_fn=stub, batch_wait=0.0)
    try:
        r1 = svc.submit([h_small], workload="register")
        r2 = svc.submit([h_big], workload="register")
        svc.start()
        assert r1.wait(30) and r2.wait(30)
        assert r1.status == "done" and r2.status == "done"
        p1, p2 = r1.stats["placement"], r2.stats["placement"]
        assert p1["n_shards"] == 2 and p2["n_shards"] == 2
        assert {p1["shard"], p2["shard"]} == {0, 1}, (p1, p2)
        assert "loads_at_dispatch" in p1
        st = svc.stats()
        assert st["workers"] == 2
        assert st["shard_loads"] == [0, 0]  # drained
    finally:
        svc.shutdown(wait=True)


def test_service_single_worker_placement_stamp():
    import random

    from jepsen_jgroups_raft_tpu.service import CheckingService

    rng = random.Random(5)
    h = random_valid_history(rng, "register", n_ops=20, crash_p=0.0)
    svc = CheckingService(
        store_root=None, autostart=False,
        check_fn=lambda encs, model, algorithm="auto":
        [{"valid?": True}] * len(encs), batch_wait=0.0)
    try:
        r = svc.submit([h], workload="register")
        svc.start()
        assert r.wait(30)
        assert r.stats["placement"] == {
            "shard": 0, "n_shards": 1, "loads_at_dispatch": [0]}
        assert svc.stats()["workers"] == 1
    finally:
        svc.shutdown(wait=True)


# --------------------------------------------------- real 2-process runs


def _cluster(mode: str, env_extra=None, n=2):
    extra = {"PYTHONPATH": f"{REPO}:{os.environ.get('PYTHONPATH', '')}"}
    extra.update(env_extra or {})
    outs = launch_local_cluster(
        n, [sys.executable, str(WORKER), mode], vdevs=4,
        env_extra=extra, timeout_s=300.0)
    for pid, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {pid} failed:\n{out[-3000:]}"
    return outs


def _expected_verdicts(monkeypatch, macro: str):
    """Single-process verdicts of the worker's batch, computed in THIS
    process (the seam is inert here — no cluster)."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories

    monkeypatch.setenv("JGRAFT_MACRO_EVENTS", macro)
    hs = dw.build_histories()
    model = CasRegister()
    out = {alg: [r["valid?"] for r in
                 check_histories(hs, model, algorithm=alg)]
           for alg in ("jax", "auto")}
    # the worker's empty-shard case (3 rows, granularity-rounded cut)
    out["tiny"] = [r["valid?"] for r in
                   check_histories(hs[:3], model, algorithm="jax")]
    return out


@pytest.mark.slow
@pytest.mark.parametrize("macro", ["1", "0"])
def test_two_process_verdicts_bitwise_identical(monkeypatch, macro):
    """The ISSUE-7 acceptance pin: a 2-process CPU-mesh run of the
    production checker produces bitwise-identical verdicts to the
    1-process run — dense grouped rows, sort-rung rows, macro on and
    off."""
    expected = _expected_verdicts(monkeypatch, macro)
    outs = _cluster("check", env_extra={"JGRAFT_MACRO_EVENTS": macro})
    for pid, (_, out) in enumerate(outs):
        got = {}
        for line in out.splitlines():
            if line.startswith("VERDICTS "):
                _, alg, payload = line.split(" ", 2)
                got[alg] = json.loads(payload)
        assert got == expected, (pid, got, expected)


@pytest.mark.slow
def test_two_process_global_mesh_capability():
    """The global-mesh collective path: on backends WITH multiprocess
    computations the per-host-packed NamedSharding launch must count
    every history valid; on this box's CPU backend the capability probe
    must answer unsupported — consistently on every process (it drives
    the checker's transport routing)."""
    outs = _cluster("global")
    markers = set()
    for _, out in outs:
        marker = [ln for ln in out.splitlines()
                  if ln.startswith(("GLOBAL-OK", "GLOBAL-UNSUPPORTED"))]
        assert marker, out[-1000:]
        markers.add(marker[-1].split(" ")[0])
    assert len(markers) == 1, markers  # both processes agree
