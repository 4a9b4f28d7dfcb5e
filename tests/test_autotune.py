"""Autotuner tests (PR 6 satellite): plan round-trip through the
fingerprint-keyed store, stale-fingerprint invalidation, corrupt-file
recovery, measurement selection, ablation gates, and an end-to-end
tuned-vs-default verdict differential through the production checker.
"""

import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from util import corrupt, random_valid_history  # noqa: E402

from jepsen_jgroups_raft_tpu.checker import autotune  # noqa: E402
from jepsen_jgroups_raft_tpu.checker.autotune import (  # noqa: E402
    TunedPlan, bucket_signature, default_plan, plan_for, resolve_plan,
    save_plan)

SIG = bucket_signature("dense", 5, 4, 100, 1500)
PLAN = TunedPlan(family="dense", scan_chunk=256, macro_p=8, mesh_fanout=2)


@pytest.fixture
def store(tmp_path, monkeypatch):
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path))
    autotune.reset_for_tests()
    yield tmp_path
    autotune.reset_for_tests()


class TestPlanStore:
    def test_round_trip_and_file_schema(self, store):
        save_plan(SIG, PLAN, samples={"a": [0.1]})
        autotune.reset_for_tests()  # simulate a fresh process
        assert plan_for(SIG) == PLAN
        [path] = list(store.rglob("*.json"))
        raw = json.loads(path.read_text())
        assert raw["version"] == autotune.PLAN_VERSION
        assert raw["fingerprint"] == autotune.host_fingerprint()
        assert raw["signature"] == list(SIG)
        assert raw["plan"]["scan_chunk"] == 256
        assert path.parent.name == autotune.host_fingerprint()
        # counters: the fresh-process read counted as a disk load
        assert autotune.snapshot_counters()["plans_loaded"] == 1

    def test_bucket_signature_buckets_shapes(self, store):
        # two batches that pad to the same launch shapes share a plan
        # (rows 100 and 120 both bucket to 128; events 1400 and 1500 to
        # 1536)
        assert bucket_signature("dense", 5, 4, 120, 1400) == SIG
        assert bucket_signature("dense", 6, 4, 120, 1400) != SIG

    def test_stale_fingerprint_invalidates(self, store):
        save_plan(SIG, PLAN, samples={})
        [path] = list(store.rglob("*.json"))
        raw = json.loads(path.read_text())
        raw["fingerprint"] = "deadbeefdeadbeef"  # host drifted
        path.write_text(json.dumps(raw))
        autotune.reset_for_tests()
        assert plan_for(SIG) is None  # re-measure, never mis-tune
        assert autotune.snapshot_counters()["plan_misses"] == 1

    def test_foreign_fingerprint_directory_never_consulted(self, store,
                                                           monkeypatch):
        save_plan(SIG, PLAN, samples={})
        autotune.reset_for_tests()
        monkeypatch.setattr(autotune, "host_fingerprint",
                            lambda: "0123456789abcdef")
        assert plan_for(SIG) is None

    def test_schema_version_drift_invalidates(self, store):
        save_plan(SIG, PLAN, samples={})
        [path] = list(store.rglob("*.json"))
        raw = json.loads(path.read_text())
        raw["version"] = 999
        path.write_text(json.dumps(raw))
        autotune.reset_for_tests()
        assert plan_for(SIG) is None

    def test_corrupt_plan_file_recovers(self, store):
        save_plan(SIG, PLAN, samples={})
        [path] = list(store.rglob("*.json"))
        path.write_text("{ not json !!")
        autotune.reset_for_tests()
        assert plan_for(SIG) is None  # no crash, a miss
        # and a re-measure overwrites the corpse with a valid file
        better = TunedPlan("dense", 64, 16, 1)
        resolve_plan(SIG, [better], lambda c: 0.01)
        autotune.reset_for_tests()
        assert plan_for(SIG) == better


class TestResolve:
    def test_picks_min_and_persists(self, store, monkeypatch):
        monkeypatch.setenv("JGRAFT_AUTOTUNE_SAMPLES", "2")
        cands = [TunedPlan("dense", c, 16, 8) for c in (0, 128, 256)]
        cost = {0: 0.03, 128: 0.01, 256: 0.02}
        calls = []

        def measure(c):
            calls.append(c.scan_chunk)
            return cost[c.scan_chunk]

        best = resolve_plan(SIG, cands, measure)
        assert best.scan_chunk == 128
        # one warm-up + 2 timed reps per candidate
        assert len(calls) == 3 * 3
        assert autotune.snapshot_counters()["plans_measured"] == 1
        autotune.reset_for_tests()
        assert plan_for(SIG) == best  # persisted; no re-measure needed

    def test_samples_recorded_in_plan_file(self, store):
        cands = [TunedPlan("dense", 0, 16, 8), TunedPlan("dense", 128, 16, 8)]
        resolve_plan(SIG, cands, lambda c: 0.01 if c.scan_chunk else 0.02)
        [path] = list(store.rglob("*.json"))
        raw = json.loads(path.read_text())
        assert len(raw["samples"]) == 2
        for ts in raw["samples"].values():
            assert len(ts) == autotune.sample_reps()


class TestGates:
    def test_autotune_off_restores_default(self, store, monkeypatch):
        monkeypatch.setenv("JGRAFT_AUTOTUNE", "0")
        assert autotune.tuned_group_plan(object(), object(), [1]) is None

    def test_env_knobs_parse_defensively(self, store, monkeypatch):
        monkeypatch.setenv("JGRAFT_AUTOTUNE", "garbage")
        assert autotune.autotune_on() is True  # warn + default
        monkeypatch.setenv("JGRAFT_AUTOTUNE_SAMPLES", "-5")
        assert autotune.sample_reps() == 1  # clamped
        monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", "   ")
        assert str(autotune.store_root()) == autotune.DEFAULT_STORE

    def test_small_groups_never_measure(self, store, monkeypatch):
        from jepsen_jgroups_raft_tpu.history.packing import encode_history
        from jepsen_jgroups_raft_tpu.models import CasRegister
        from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plan

        monkeypatch.setenv("JGRAFT_AUTOTUNE_MIN_ROWS", "64")
        rng = random.Random(1)
        model = CasRegister()
        encs = [encode_history(
            random_valid_history(rng, "register", n_ops=10), model)
            for _ in range(4)]
        plan = dense_plan(model, encs)
        assert autotune.tuned_group_plan(model, plan, encs) is None
        c = autotune.snapshot_counters()
        assert c["plans_measured"] == 0 and c["plan_misses"] == 1

    def test_pack_group_respects_macro_ablation(self, store, monkeypatch):
        from jepsen_jgroups_raft_tpu.history.packing import encode_history
        from jepsen_jgroups_raft_tpu.models import CasRegister

        rng = random.Random(1)
        enc = encode_history(
            random_valid_history(rng, "register", n_ops=10), CasRegister())
        monkeypatch.setenv("JGRAFT_MACRO_EVENTS", "0")
        batch = autotune.pack_group([enc], TunedPlan("dense", 128, 16, 8))
        assert batch["events"].shape[2] == 5  # legacy rows, plan ignored
        monkeypatch.delenv("JGRAFT_MACRO_EVENTS")
        batch = autotune.pack_group([enc], TunedPlan("dense", 128, 4, 8))
        assert "macro_p" in batch and batch["macro_p"] <= 4


@pytest.mark.slow
class TestEndToEnd:
    def test_tuned_vs_default_verdicts_identical(self, store, monkeypatch):
        """The production checker under JGRAFT_AUTOTUNE=1 (measuring +
        applying real plans) must report bitwise-identical verdicts to
        JGRAFT_AUTOTUNE=0 — the ISSUE-6 acceptance differential at test
        scale."""
        from jepsen_jgroups_raft_tpu.checker.linearizable import (
            check_histories)
        from jepsen_jgroups_raft_tpu.models import CasRegister

        monkeypatch.setenv("JGRAFT_AUTOTUNE_MIN_ROWS", "8")
        monkeypatch.setenv("JGRAFT_AUTOTUNE_MIN_CELLS", "64")
        monkeypatch.setenv("JGRAFT_AUTOTUNE_SAMPLE_ROWS", "8")
        monkeypatch.setenv("JGRAFT_AUTOTUNE_SAMPLES", "1")
        rng = random.Random(17)
        model = CasRegister()
        hists = []
        for i in range(24):
            h = random_valid_history(rng, "register", n_ops=16,
                                     n_procs=4, crash_p=0.05,
                                     max_crashes=2)
            if i % 4 == 0:
                h = corrupt(rng, h)
            hists.append(h)

        monkeypatch.setenv("JGRAFT_AUTOTUNE", "0")
        base = [r["valid?"] for r in
                check_histories(hists, model, algorithm="jax")]
        monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
        tuned = [r["valid?"] for r in
                 check_histories(hists, model, algorithm="jax")]
        assert tuned == base
        assert True in base and False in base
        c = autotune.snapshot_counters()
        assert c["plans_measured"] >= 1
        assert list(store.rglob("*.json"))  # persisted
        # a "fresh process" (memory dropped) loads from disk and still
        # agrees
        counters_before = c["plans_loaded"]
        autotune.reset_for_tests()
        again = [r["valid?"] for r in
                 check_histories(hists, model, algorithm="jax")]
        assert again == base
        c2 = autotune.snapshot_counters()
        assert c2["plans_loaded"] >= 1 and c2["plans_measured"] == 0
        assert any(e["source"] == "disk" for e in autotune.applied_log())
        del counters_before
