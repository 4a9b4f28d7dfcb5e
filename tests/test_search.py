"""graftsearch (search/) tests — ISSUE 20 tentpole + satellites.

Tier-1, CPU-only. The load-bearing assertions mirror the issue's
acceptance bars at smoke scale: every operator maps well-formed
histories to histories the packing layer accepts (the soundness
contract); every model family has at least one ``can_invalidate``
operator that actually flips a seeded-valid history to INVALID (the
regression the old `synth.corrupt` write arm failed); two driver runs
under one seed produce identical corpus fingerprints; fitness reads
exactly the verdict fields graftd already attaches; corpus entries are
deduped, minimized before archive, and re-verify INVALID; the recall
harness finds plants whose reachability was proven at plant time; the
`JGRAFT_SEARCH_GUIDED=0` ablation arm runs the same machinery blind.
"""

from __future__ import annotations

import json
import random

import pytest

from jepsen_jgroups_raft_tpu.checker.base import INVALID, UNKNOWN, VALID
from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.history.synth import corrupt
from jepsen_jgroups_raft_tpu.nemesis.package import schedule_pressure
from jepsen_jgroups_raft_tpu.search import (REGISTRY, Corpus, Scenario,
                                            SearchConfig, SearchDriver,
                                            corrupt_once, family_of,
                                            materialize, operators_for,
                                            plant_violations, run_recall,
                                            scenario_fingerprint,
                                            score_candidate)
from jepsen_jgroups_raft_tpu.search.corpus import reverify_entry
from jepsen_jgroups_raft_tpu.search.fitness import (TIER_DISTANCE,
                                                    score_result_row,
                                                    score_txn)
from jepsen_jgroups_raft_tpu.search.operators import (FAMILIES,
                                                      apply_history_op)
from jepsen_jgroups_raft_tpu.search.scenario import mutate
from jepsen_jgroups_raft_tpu.service.daemon import CheckingService
from jepsen_jgroups_raft_tpu.service.request import build_units

from util import H


@pytest.fixture(scope="module")
def service():
    svc = CheckingService(store_root=None, batch_wait=0.0)
    yield svc
    svc.shutdown(wait=True)


def tiny_config(tmp_path, **kw):
    kw.setdefault("families", ("register", "queue"))
    kw.setdefault("population", 10)
    kw.setdefault("generations", 2)
    kw.setdefault("survivors", 4)
    kw.setdefault("edit_space", 8)
    kw.setdefault("seed", 0)
    kw.setdefault("n_ops", 10)
    kw.setdefault("bases_per_family", 2)
    kw.setdefault("corpus_dir", str(tmp_path / "search"))
    return SearchConfig(**kw)


def base_scenario(family, seed=3, n_ops=14):
    return Scenario(family=family, seed=seed, n_ops=n_ops,
                    n_keys=2 if family == "list-append" else 1)


# ------------------------------------------------------------- operators


class TestOperators:
    def test_every_family_has_invalidating_operator(self):
        """Regression for corrupt()'s blind spots: EVERY family — the
        old write arm covered register vacuously and list-append not at
        all — has ≥1 can_invalidate operator that flips some
        seeded-valid base to a host-checker INVALID."""
        for family in FAMILIES:
            flipped = False
            for seed in range(6):
                sc = base_scenario(family, seed=seed)
                hist = materialize(sc)
                model, units = build_units([hist], family)
                assert all(
                    check_histories([uh], model, algorithm="cpu")[0]["valid?"]
                    is VALID for _, uh in units), \
                    f"{family} base seed {seed} must start valid"
                for op in operators_for(family, "history"):
                    if not op.can_invalidate:
                        continue
                    for es in range(12):
                        out = apply_history_op(
                            op, random.Random(f"t:{op.name}:{es}"), hist)
                        if out is None:
                            continue
                        model2, units2 = build_units([out], family)
                        if any(check_histories(
                                [uh], model2,
                                algorithm="cpu")[0]["valid?"] is INVALID
                                for _, uh in units2):
                            flipped = True
                            break
                    if flipped:
                        break
                if flipped:
                    break
            assert flipped, f"no invalidating operator fired for {family}"

    def test_operators_never_break_encode(self):
        """Soundness contract: any applicable operator output (and
        3-deep chains) must survive build_units + encode_history —
        the packing layer never rejects a mutant."""
        for family in FAMILIES:
            sc = base_scenario(family)
            ops = operators_for(family, "history")
            for op in ops:
                for es in range(6):
                    out = apply_history_op(
                        op, random.Random(f"enc:{op.name}:{es}"),
                        materialize(sc))
                    if out is None:
                        continue
                    model, units = build_units([out], family)
                    for _, uh in units:
                        encode_history(uh, model)  # must not raise
            # chains: replayed through materialize, depth 3
            rng = random.Random(f"chain:{family}")
            g = sc
            for _ in range(3):
                op = ops[rng.randrange(len(ops))]
                g = mutate(g, op, rng.randrange(16))
            model, units = build_units([materialize(g)], family)
            for _, uh in units:
                encode_history(uh, model)

    def test_params_operators_stay_in_domain(self):
        sc = base_scenario("register")
        for op in operators_for("register", "params"):
            g = sc
            for es in range(8):
                g = mutate(g, op, es)
            assert 2 <= g.n_procs <= 8
            assert 0.0 < g.crash_p <= 0.6
            assert 2 <= g.value_range <= 8
            assert 0.5 <= g.interval <= 20.0
            materialize(g)  # any nemesis spec it picked must generate

    def test_registry_covers_each_family(self):
        for family in FAMILIES:
            ops = operators_for(family)
            assert any(o.can_invalidate for o in ops), family
            assert any(o.target == "params" for o in ops), family

    def test_crash_injection_is_capped(self):
        """drop-completion/crash-op refuse past the ambiguity budget —
        unbounded crash stacking makes the host check combinatorial."""
        sc = base_scenario("register", n_ops=20)
        g = sc
        for es in range(40):
            g = mutate(g, REGISTRY["crash-op"], es)
        hist = materialize(g)
        n_inv = sum(1 for o in hist if o.type == "invoke")
        n_done = sum(1 for o in hist if o.type in ("ok", "fail"))
        assert n_inv - n_done <= 5 + sc.n_procs  # cap + base crashes


class TestCorruptCompat:
    def test_write_arm_now_mutates(self):
        """The old corrupt() write arm was a silent no-op (it rewrote
        the completion to the value it already carried). A writes-only
        history must now actually change under corruption."""
        rows = []
        for i in range(6):
            rows += [(0, "invoke", "write", i), (0, "ok", "write", i)]
        hist = H(*rows)
        changed = False
        for s in range(8):
            out = corrupt(random.Random(s), hist)
            if [(o.process, o.type, o.f, o.value) for o in out] != \
                    [(o.process, o.type, o.f, o.value) for o in hist]:
                changed = True
                break
        assert changed, "corrupt() write arm is still a silent no-op"

    def test_list_append_arm_exists(self):
        hist = materialize(base_scenario("list-append"))
        assert family_of(hist) == "list-append"
        changed = False
        for s in range(8):
            out = corrupt_once(random.Random(s), hist)
            if [o.value for o in out] != [o.value for o in hist]:
                changed = True
                break
        assert changed, "list-append observed lists never perturbed"

    def test_family_dispatch(self):
        assert family_of(materialize(base_scenario("queue"))) == "queue"
        assert family_of(materialize(base_scenario("set"))) == "set"
        assert family_of(materialize(base_scenario("counter"))) == "counter"


# --------------------------------------------------------------- fitness


class TestFitness:
    def test_tier_distance_orders_the_ladder(self):
        assert TIER_DISTANCE["greedy"] < TIER_DISTANCE["backtrack"] \
            < TIER_DISTANCE["cycle"] < TIER_DISTANCE["host"]
        # kernel tiers collapse: batch composition picks the kernel,
        # not the row — scoring them apart would break determinism
        assert TIER_DISTANCE["mask"] == TIER_DISTANCE["dense"] \
            == TIER_DISTANCE["sort"] == TIER_DISTANCE["host"]

    def test_invalid_beats_valid_beats_nothing(self):
        valid = {"decided-tier": "greedy", "valid?": VALID}
        deep = {"decided-tier": "host", "valid?": VALID}
        unk = {"decided-tier": "host", "valid?": UNKNOWN}
        inv = {"decided-tier": "host", "valid?": INVALID,
               "counterexample": {"minimal-op-count": 4}}
        assert score_result_row(valid) < score_result_row(deep) \
            < score_result_row(unk) < score_result_row(inv)

    def test_smaller_witness_scores_higher(self):
        small = {"decided-tier": "host", "valid?": INVALID,
                 "counterexample": {"minimal-op-count": 3}}
        big = {"decided-tier": "host", "valid?": INVALID,
               "counterexample": {"minimal-op-count": 30}}
        assert score_result_row(small) > score_result_row(big)

    def test_annotation_bonuses(self):
        base = {"decided-tier": "cycle", "valid?": VALID}
        assert score_result_row({**base, "sc-refuted": True}) \
            == pytest.approx(score_result_row(base) + 0.5)
        assert score_result_row({**base, "cycle-skipped-size": 12}) \
            == pytest.approx(score_result_row(base) + 0.3)
        late = {**base, "decided-at-segment": 3, "segments": 4}
        early = {**base, "decided-at-segment": 0, "segments": 4}
        assert score_result_row(late) > score_result_row(early)

    def test_txn_overlay_counts_anomaly_classes(self):
        one = {"valid?": INVALID, "histories": [
            {"anomalies": {"G1c": {"cycle": [1, 2]}}}]}
        two = {"valid?": INVALID, "histories": [
            {"anomalies": {"G1c": {"cycle": [1, 2]},
                           "G-single": {"cycle": [3]}}}]}
        assert score_txn(None) == 0.0
        assert 0.0 < score_txn(one) < score_txn(two)

    def test_candidate_mean_not_sum(self):
        row = {"decided-tier": "greedy", "valid?": VALID}
        assert score_candidate([row]) == pytest.approx(
            score_candidate([row, dict(row)]))


# ---------------------------------------------------------------- corpus


class TestCorpus:
    def test_dedup_and_roundtrip(self, tmp_path):
        corpus = Corpus(str(tmp_path / "c"))
        entry = {"fingerprint": "ab" + "0" * 14, "family": "register",
                 "region": ["register", 3], "kind": "lin", "units": []}
        assert corpus.add(entry) is True
        assert corpus.add(dict(entry)) is False  # fingerprint dedup
        assert len(corpus) == 1
        assert entry["fingerprint"] in corpus
        # reload from disk: content-addressed layout survives restart
        again = Corpus(str(tmp_path / "c"))
        assert again.fingerprints() == {entry["fingerprint"]}
        assert again.load(entry["fingerprint"])["family"] == "register"

    def test_entries_are_json_clean(self, tmp_path):
        corpus = Corpus(str(tmp_path / "c"))
        corpus.add({"fingerprint": "cd" + "1" * 14, "kind": "lin",
                    "units": [{"ops": [{"value": (1, 2)}]}]})
        for e in corpus.entries():
            json.dumps(e)  # archived entries must round-trip as JSON


# ---------------------------------------------- driver: determinism, archive


class TestDriver:
    def test_seed_determinism_identical_corpus(self, tmp_path, service):
        """Same seed ⇒ identical corpus fingerprints."""
        reports = []
        for rep in range(2):
            cfg = tiny_config(tmp_path / f"rep{rep}")
            reports.append(SearchDriver(cfg, service=service).run())
        assert reports[0]["corpus-fingerprints"] == \
            reports[1]["corpus-fingerprints"]
        assert reports[0]["candidates"] == reports[1]["candidates"]
        assert reports[0]["corpus"] >= 1, \
            "smoke run found no violations at all"

    def test_archive_minimizes_and_reverifies(self, tmp_path, service):
        cfg = tiny_config(tmp_path)
        driver = SearchDriver(cfg, service=service)
        rep = driver.run()
        assert rep["unconfirmed"] == 0
        n = 0
        for entry in driver.corpus.entries():
            assert reverify_entry(entry), \
                f"archived entry {entry['fingerprint']} not INVALID"
            for unit in entry.get("units", []):
                n += 1
                assert unit["minimized"] is True
                assert unit["ops"], "minimized witness must keep ops"
        assert n >= 1

    def test_guided_vs_random_smoke(self, tmp_path, service):
        """Ablation arm: same budget, no feedback — both must complete
        and label their reports."""
        g = SearchDriver(tiny_config(tmp_path / "g", guided=True),
                         service=service).run()
        r = SearchDriver(tiny_config(tmp_path / "r", guided=False),
                         service=service).run()
        assert g["arm"] == "guided" and r["arm"] == "random"
        assert g["corpus"] >= 1
        assert r["found-regions"] == []  # random retires nothing
        for rep in (g, r):
            assert rep["per-generation"], rep["arm"]
            for gen in rep["per-generation"]:
                assert gen["candidates"] <= tiny_config(tmp_path).population

    def test_recall_finds_planted_violation(self, tmp_path, service):
        cfg = tiny_config(tmp_path, families=("register", "set", "queue"),
                          population=24, generations=4, survivors=8,
                          edit_space=12, n_ops=12)
        plants = plant_violations(cfg, 3)
        assert len(plants) == 3
        assert {p.base.family for p in plants} == {"register", "set",
                                                   "queue"}
        for p in plants:  # plant proof: the edit really invalidates
            name, es = p.edit
            assert name in REGISTRY and 0 <= es < cfg.edit_space
        report = run_recall(cfg, plants=plants, service=service)
        assert report.planted == 3
        assert len(report.found) >= 1, report.to_dict()
        assert report.recall == pytest.approx(
            len(report.found) / 3)
        assert report.cpu_s > 0 and report.recall_per_cpu_min >= 0


# ------------------------------------------------------------ CLI surface


def test_cli_search_surface(tmp_path, capsys):
    from jepsen_jgroups_raft_tpu.cli import main

    rc = main(["search", "--families", "register", "--population", "8",
               "--generations", "1", "--survivors", "4",
               "--edit-space", "8", "--n-ops", "10", "--seed", "0",
               "--corpus-dir", str(tmp_path / "corpus")])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["arm"] == "guided"
    assert rep["families"] == ["register"]
    assert "corpus-fingerprints" in rep and "cpu_s" in rep


# ------------------------------------------------------- nemesis pressure


def test_schedule_pressure_deterministic():
    assert schedule_pressure("none", 5.0) == {"crash_bias": 0.0,
                                              "crash_burst": 0}
    p = schedule_pressure("kill,partition", 5.0)
    assert p == schedule_pressure("kill,partition", 5.0)
    assert 0.0 < p["crash_bias"] <= 0.4
    assert p["crash_burst"] == 2
    # tighter interval = more pressure, capped
    tight = schedule_pressure("all", 0.5)
    assert tight["crash_bias"] == 0.4
    assert schedule_pressure("kill", 20.0)["crash_bias"] < \
        schedule_pressure("kill", 1.0)["crash_bias"]


# --------------------------------------------------------------- genomes


def test_scenario_fingerprint_stable_and_content_addressed():
    a = base_scenario("register")
    assert scenario_fingerprint(a) == scenario_fingerprint(a)
    b = base_scenario("register", seed=4)
    assert scenario_fingerprint(a) != scenario_fingerprint(b)
    # an applicable edit changes the bytes, hence the fingerprint
    edited = mutate(a, REGISTRY["perturb-read"], 0)
    assert edited.edits == (("perturb-read", 0),)
    assert scenario_fingerprint(edited) != scenario_fingerprint(a)


def test_scenario_roundtrips_through_dict():
    sc = mutate(base_scenario("queue"), REGISTRY["perturb-ticket"], 5)
    assert Scenario.from_dict(sc.to_dict()) == sc
