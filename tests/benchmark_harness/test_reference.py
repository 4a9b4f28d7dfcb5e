"""The plain reference, its control, and the generator.

The reference imports nothing of the program, so its answers are
compared here with the program's own checker on the CPU at a size a
test can hold. The control (crashed ops dropped: one stated guarantee
broken) has to disagree with the reference on every seed, or the
comparison that decides `correct` could not tell a weakened checker
from a sound one."""

import json
import random

import pytest

from benchmarks import manifest as mf
from benchmarks.generators import synth
from benchmarks.references import frontier

from util_bench import ROOT

CONFIG = {"history_kind": None, "ops_per_history": 120, "processes": 5,
          "value_range": 3, "crash_probability": 0.05, "max_crashes": 3}
TRAFFIC = {"histories_per_request": 4, "perturbed_share": 0.25,
           "planted_every": 3}
MANIFEST = mf.load_manifest(ROOT)


def kinds() -> dict:
    """What the configurations of the manifest name, by the kind of
    history each sends: its plain reference, its control, and the
    program's workload that graftd checks it as. A configuration added
    as a file has its reference held to the program's checker by
    arriving."""
    out = {}
    for entry in MANIFEST["configs"]:
        with open(ROOT / entry["file"]) as fh:
            cfg = json.load(fh)
        named = (cfg["reference"], cfg["control"], cfg["service_workload"])
        assert out.setdefault(cfg["history_kind"], named) == named, (
            f"{entry['name']}: another configuration of kind "
            f"{cfg['history_kind']!r} names {out[cfg['history_kind']]}")
    return out


KINDS = kinds()


def requests(kind, seed, n=12):
    cfg = dict(CONFIG, history_kind=kind)
    return synth.make_requests(random.Random(seed), cfg, TRAFFIC, n, 0)


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_reference_agrees_with_the_programs_checker(kind, seed):
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
    from jepsen_jgroups_raft_tpu.history.synth import build_history
    from jepsen_jgroups_raft_tpu.service.request import service_workloads

    ref_name, _, workload = KINDS[kind]
    model = service_workloads()[workload][0]
    ref = mf.load_module(ROOT, "references", ref_name)
    hs = [h for req in requests(kind, seed) for h in req]
    want = [frontier.linearizable(h, ref) for h in hs]
    got = [r["valid?"] for r in check_histories(
        [build_history(h) for h in hs], model(), algorithm="auto")]
    assert got == want
    assert True in want and False in want


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_control_disagrees_with_the_reference(kind, seed):
    ref = mf.load_module(ROOT, "references", KINDS[kind][0])
    control = mf.load_module(ROOT, "references", KINDS[kind][1])
    hs = [h for req in requests(kind, seed, n=15) for h in req]
    differ = sum(control.linearizable(h, ref)
                 is not frontier.linearizable(h, ref) for h in hs)
    assert differ >= 3, differ


@pytest.mark.parametrize("kind", ["counter", "register"])
def test_same_seed_same_requests_other_seed_other(kind):
    a, b, c = requests(kind, 5), requests(kind, 5), requests(kind, 6)
    assert a == b and a != c
    assert all(len(r) == TRAFFIC["histories_per_request"] for r in a)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_planted_read_is_invalid_and_every_third_request_has_one(kind):
    ref = mf.load_module(ROOT, "references", KINDS[kind][0])
    reqs = requests(kind, 9)
    for i, req in enumerate(reqs):
        planted = [h for h in req if h[-1][0] == 10_000]
        assert len(planted) == (1 if i % 3 == 0 else 0)
        for h in planted:
            assert frontier.linearizable(h, ref) is False


def test_a_history_sent_twice_is_refused():
    from benchmarks.client_worker import check_no_repeats

    reqs = requests("register", 4)
    check_no_repeats(reqs)
    with pytest.raises(ValueError, match="twice"):
        check_no_repeats(reqs + [reqs[0]])


def test_the_warm_up_sweep_walks_down_the_request_sizes():
    from benchmarks.client_worker import make_pool

    cfg = dict(CONFIG, history_kind="counter", ops_per_history=30)
    traffic = dict(TRAFFIC, histories_per_request=8, planted_every=0,
                   warmup_requests_per_client=2, warmup_sweep=[6, 3, 1])
    reqs, n_warm = make_pool(synth, random.Random(5), cfg, traffic,
                             n_requests=7, first_request=0, n_clients=2)
    assert [len(r) for r in reqs] == [8] * 4 + [6, 6, 3, 3, 1, 1] + [8] * 3
    assert n_warm == 10
    # the requests of the window are those a pool without a sweep holds
    plain, _ = make_pool(synth, random.Random(5), cfg,
                         dict(traffic, warmup_sweep=[]), 7, 0, 2)
    assert reqs[:4] + reqs[10:] == plain


@pytest.mark.parametrize("rows,want", [
    # a read of the sum just acknowledged
    ([(0, "invoke", "add", 2), (0, "ok", "add", 2),
      (1, "invoke", "read", None), (1, "ok", "read", 2)], True),
    # a stale read after the add completed
    ([(0, "invoke", "add", 2), (0, "ok", "add", 2),
      (1, "invoke", "read", None), (1, "ok", "read", 0)], False),
    # a crashed add may have happened
    ([(0, "invoke", "add", 3),
      (1, "invoke", "read", None), (1, "ok", "read", 3)], True),
    # a failed add did not happen
    ([(0, "invoke", "add", 3), (0, "fail", "add", 3),
      (1, "invoke", "read", None), (1, "ok", "read", 3)], False),
    # add-and-get observes the new value
    ([(0, "invoke", "add-and-get", 1), (0, "ok", "add-and-get", (1, 1)),
      (1, "invoke", "add-and-get", 1), (1, "ok", "add-and-get", (1, 1))],
     False),
])
def test_counter_semantics(rows, want):
    ref = mf.load_module(ROOT, "references", "counter")
    assert frontier.linearizable(rows, ref) is want
