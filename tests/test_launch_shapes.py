"""The closed launch-shape set (ISSUE 32, checker/schedule.py): one
enumeration names every program a launch of the wavefront can ask for,
a key is built whole ahead of its launches, graftd builds the keys it
can know when it starts, and what escapes is counted.

  (a) the enumeration is the truth: whatever `run_chunked` launches is
      a member of `launch_shapes` and of what was built;
  (b) nothing escapes: after a key's build a served campaign builds no
      program and misses no shape;
  (c) same verdicts: the pow2+midpoint row policy the set replaced
      against the set, and both against the host checkers;
  (d) a request during the build waits, acknowledged and undegraded;
  (e) the served path does not measure plan candidates.
"""

import json
import random
import threading
import time

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker import autotune, schedule
from jepsen_jgroups_raft_tpu.checker.base import INVALID, VALID
from jepsen_jgroups_raft_tpu.checker.brute import check_brute
from jepsen_jgroups_raft_tpu.checker.linearizable import (check_encoded,
                                                          check_histories)
from jepsen_jgroups_raft_tpu.checker.schedule import (LaunchShapes,
                                                      launch_rows,
                                                      launch_shapes,
                                                      launch_width,
                                                      long_width,
                                                      snapshot_built,
                                                      snapshot_compiles,
                                                      snapshot_launched,
                                                      snapshot_spans)
from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu
from jepsen_jgroups_raft_tpu.history.packing import (bucket_rows,
                                                     encode_history)
from jepsen_jgroups_raft_tpu.models import CasRegister, Counter
from jepsen_jgroups_raft_tpu.platform import install_compile_counters
from jepsen_jgroups_raft_tpu.service import ServiceClient
from jepsen_jgroups_raft_tpu.service import buildahead
from jepsen_jgroups_raft_tpu.service.admission import AdmissionQueue
from jepsen_jgroups_raft_tpu.service.daemon import CheckingService
from jepsen_jgroups_raft_tpu.service.http import serve_in_thread
from jepsen_jgroups_raft_tpu.service.request import admit
from jepsen_jgroups_raft_tpu.service.scheduler import (
    DEFAULT_MAX_BATCH_ROWS, BatchScheduler)

from util import build_history, corrupt, random_valid_history

MODELS = {"register": CasRegister, "counter": Counter}
NEVER = {"register": 99, "counter": -5}


def planted(hist, kind, at=None):
    """`hist` with an acknowledged read of a value nobody wrote, at row
    `at` (default: the end), so the verdict is certainly invalid."""
    rows = [(o.process, o.type, o.f, o.value) for o in hist]
    at = len(rows) if at is None else at
    rows[at:at] = [(10_000, "invoke", "read", None),
                   (10_000, "ok", "read", NEVER[kind])]
    return build_history(rows)


def campaign(rng, kind, n, n_procs=3, early=False, perturb=True):
    """`n` seeded histories of spread lengths (rows exhaust at different
    spans), one in five perturbed, one in seven with a planted read —
    the history's first op where `early` (no op is open yet, so the
    window stays what it was), so that it is decided long before its
    neighbours exhaust. (A perturbed write may bring a fourth value, and
    with it another key: eight states.)"""
    hists = []
    for i in range(n):
        # three values: a register group's domain pads to four states
        h = random_valid_history(rng, kind, n_ops=6 + (i * 11) % 50,
                                 n_procs=n_procs, crash_p=0.0,
                                 value_range=3)
        if perturb and i % 5 == 1:
            h = corrupt(rng, h)
        if i % 7 == 2:
            h = planted(h, kind, at=0 if early else None)
        hists.append(h)
    return hists


# ------------------------------------------------ the enumeration itself


SERVED_ROWS = (8, 16, 32, 48, 64, 96, 128, 192, 256)


def test_the_set_of_a_served_key_is_twenty_six_programs():
    shapes = launch_shapes(DEFAULT_MAX_BATCH_ROWS, 1024)
    assert shapes.rows == SERVED_ROWS
    assert shapes.init == shapes.rows
    assert shapes.step == tuple((r, 1024) for r in shapes.rows)
    assert shapes.gather == ((16, 8), (32, 16), (48, 32), (64, 48),
                             (96, 64), (128, 96), (192, 128), (256, 192))
    assert len(shapes) == 26 <= 32
    # a width that is no power of two is none of the set's
    assert launch_shapes(256, 997).width == 1024 == launch_width(997)


@pytest.mark.parametrize("n,shards,want", [
    (1, 1, 8), (8, 1, 8), (9, 1, 16), (17, 1, 32), (33, 1, 48),
    (49, 1, 64), (129, 1, 192), (193, 1, 256), (257, 1, 384),
    (3, 8, 8), (9, 3, 18), (33, 8, 48)])
def test_launch_rows_are_powers_of_two_and_midpoints_over_the_shards(
        n, shards, want):
    assert launch_rows(n, shards) == want
    assert want in launch_shapes(n, 128, shards).rows


# --------------------------------------------- (a) the enumeration is the truth


@pytest.mark.parametrize("kind", sorted(MODELS))
@pytest.mark.parametrize("n_rows", [1, 7, 33, 100, 256])
def test_every_launched_shape_is_enumerated_and_built(kind, n_rows,
                                                      monkeypatch):
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    install_compile_counters()
    rng = random.Random(3200 + n_rows)
    hists = campaign(rng, kind, n_rows)
    seen = set(snapshot_launched())
    misses = snapshot_compiles()["shape_misses"]
    rs = check_histories(hists, MODELS[kind](), algorithm="jax")
    assert all(r["valid?"] in (VALID, INVALID) for r in rs)
    assert any(r["valid?"] is INVALID for r in rs) or n_rows < 3
    fresh = [s for s in snapshot_launched() if s not in seen]
    assert fresh or n_rows == 1   # a lone row may repeat a known shape
    built = {b["key"]: b for b in snapshot_built()}
    for shape in snapshot_launched():
        program, key = shape[0], shape[1]
        assert key in built, shape
        width = key[2]
        # a LONG key (another test of this process may have launched
        # one) has its own two ladders
        long = ("long", "True") in key[0]
        assert width == built[key]["width"] == (
            long_width if long else launch_width)(width)
        shards = int(key[3][4:]) if key[3].startswith("mesh") else 1
        shapes = launch_shapes(max(built[key]["rows"]), width, shards,
                               long=long)
        # a library caller builds a bucket when a launch reaches it
        assert set(built[key]["rows"]) <= set(shapes.rows), shape
        assert shape[2] in built[key]["rows"], shape
        if program == "init":
            assert shape[2] in shapes.init and shape[3] == width
        elif program == "step":
            assert shape[2:] in shapes.step
        else:
            assert program == "gather" and shape[2:4] in shapes.gather
            assert shape[4] == width
    # a launch of a built key asked the backend for nothing
    assert snapshot_compiles()["shape_misses"] == misses


def test_recompaction_walks_the_set_bucket_by_bucket(monkeypatch):
    """33 rows start at 48; once the short rows are gone the survivors
    step down through `gather` programs of the set, never past it."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    rng = random.Random(3233)
    model = CasRegister()
    hists = [random_valid_history(rng, "register",
                                  n_ops=8 if i % 11 else 60, n_procs=2,
                                  crash_p=0.0) for i in range(33)]
    before = set(snapshot_launched())
    check_histories(hists, model, algorithm="jax")
    gathers = [s for s in snapshot_launched()
               if s not in before and s[0] == "gather"]
    assert gathers
    for _, _, rows_before, rows_after, _ in gathers:
        assert (rows_before, rows_after) in launch_shapes(33, 64, 8).gather


# ------------------------------------------------- (c) the same verdicts


def served_rows(hists, kind):
    """Each history's (valid?, failing op, counterexample) as graftd's
    launch and demux deliver them, eight histories a request."""
    sched = BatchScheduler(AdmissionQueue())
    reqs = [admit(hists[i:i + 8], kind) for i in range(0, len(hists), 8)]
    sched.execute(reqs)
    return [(res["valid?"], res.get("failing-op-index"),
             json.dumps(res.get("counterexample"), sort_keys=True,
                        default=repr))
            for r in reqs for res in r.results]


@pytest.mark.parametrize("kind", sorted(MODELS))
@pytest.mark.parametrize("seed", [3201, 3202, 3203])
def test_old_row_policy_new_set_and_the_host_checkers_agree(kind, seed,
                                                            monkeypatch):
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    model = MODELS[kind]()
    rng = random.Random(seed)
    hists = campaign(rng, kind, 40, early=True)
    install_compile_counters()
    misses = snapshot_compiles()["shape_misses"]
    new = served_rows(hists, kind)
    # the policy the set replaced: pow2 rows and their midpoints. The
    # enumeration is the one source of rows, so the build follows it.
    monkeypatch.setattr(
        schedule, "launch_rows",
        lambda n, shards=1: -(-bucket_rows(n) // shards) * shards)
    monkeypatch.setattr(schedule, "_BUILT", {})
    assert 24 in launch_shapes(40, 128).rows   # 8 12 16 24 32 48
    old = served_rows(hists, kind)
    assert old == new
    assert snapshot_compiles()["shape_misses"] == misses
    for h, (valid, failing, _) in zip(hists, new):
        cpu = check_encoded_cpu(encode_history(h, model), model)
        assert (valid is VALID) == cpu.valid
        if len(h.client_ops()) <= 16:
            assert check_brute(h, model) == cpu.valid
        if valid is INVALID:
            assert failing is not None


# -------------------------------------------- (b), (d): through graftd


@pytest.fixture()
def served(tmp_path, monkeypatch):
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    install_compile_counters()
    svc = CheckingService(store_root=str(tmp_path / "store"),
                          batch_wait=0.0, autostart=False)
    httpd, port, _ = serve_in_thread(svc)
    yield svc, f"http://127.0.0.1:{port}"
    httpd.shutdown()
    httpd.server_close()
    svc.shutdown(wait=True)


def wait_done(cl, rid):
    rec = cl.result(rid, wait_s=60.0)
    while rec["status"] not in ("done", "failed", "cancelled"):
        rec = cl.result(rid, wait_s=60.0)
    assert rec["status"] == "done", rec
    return rec


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_served_campaign_builds_nothing_after_its_keys(served, kind):
    svc, url = served
    svc.start()
    rng = random.Random(3240)
    cl = ServiceClient(url)
    # the keys' first sight: one request of the campaign's own shapes
    wait_done(cl, cl.submit(campaign(rng, kind, 16, n_procs=2, early=True,
                                     perturb=False),
                            workload=kind, binary=True)["id"])
    # the key's first launch waited for the key whole
    st = svc.stats()
    assert st["warm"]
    assert st["spans"]["build.ahead"]["n"] >= 26
    assert st["programs_built_ahead"] >= 26
    assert any(b["rows"] == list(SERVED_ROWS)
               for b in snapshot_built()
               if b["spec"] and b["spec"]["model"] == MODELS[kind].__name__
               ), [(b["spec"], b["rows"]) for b in snapshot_built()]
    launched = len(snapshot_launched())

    def client(seed, out):
        c = ServiceClient(url)
        r = random.Random(seed)
        ids = [c.submit(campaign(r, kind, n, n_procs=2, early=True,
                                 perturb=False),
                        workload=kind, binary=True)["id"]
               for n in (16, 16, 16, 16)]
        out.extend(wait_done(c, i) for i in ids)

    recs: list = []
    threads = [threading.Thread(target=client, args=(3241 + k, recs))
               for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(recs) == 8
    assert any(not r["valid?"] for r in recs)
    after = svc.stats()
    assert after["shape_misses"] == st["shape_misses"], \
        after["recent_shape_misses"]
    assert after["programs_built"] == st["programs_built"], \
        after["recent_compiles"]
    assert after["spans"]["build.ahead"] == st["spans"]["build.ahead"]
    assert after["degraded_batches"] == 0
    # recompaction was exercised: the campaign asked for gathers
    assert any(s[0] == "gather" for s in snapshot_launched())
    assert len(snapshot_launched()) >= launched


def test_a_request_during_the_build_waits_in_the_queue(served, monkeypatch):
    svc, url = served
    building, release = threading.Event(), threading.Event()
    real = buildahead.build_at_start

    def held_open(*args, **kw):
        with schedule.span("build.ahead", n=0):
            building.set()
            assert release.wait(60.0)
        return real(*args, **kw)

    monkeypatch.setattr(buildahead, "build_at_start", held_open)
    svc.start()
    assert building.wait(30.0)
    before = svc.stats()
    assert before["warm"] is False
    cl = ServiceClient(url)
    hists = campaign(random.Random(3250), "register", 6, n_procs=2)
    ack = cl.submit(hists, workload="register", binary=True)
    # acknowledged: its WAL record is on disk, and nothing ran
    mid = svc.stats()
    assert mid["journal_appends"] == before["journal_appends"] + 1
    assert mid["queue_depth"] == 1 and mid["batches"] == before["batches"]
    time.sleep(0.2)
    assert cl.result(ack["id"], wait_s=0.0)["status"] == "queued"
    release.set()
    rec = wait_done(cl, ack["id"])
    after = svc.stats()
    assert after["warm"] is True
    assert after["degraded_batches"] == before["degraded_batches"] == 0
    # its one batch, and the window groups that ran, both served
    assert after["groups_run"] - before["groups_run"] >= \
        after["batches"] - before["batches"] == 1
    assert rec["cached"] is False
    for res in rec["results"]:
        assert res["decided-tier"] in ("dense", "mask")
        assert "platform-degraded" not in res


# --------------------------------- (e) the served path does not measure


@pytest.fixture()
def open_gates(tmp_path, monkeypatch):
    """The autotuner on, an empty store, and work gates that a 64-row
    group of short histories passes."""
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "plans"))
    monkeypatch.setenv("JGRAFT_AUTOTUNE_MIN_CELLS", "1")
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    autotune.reset_for_tests()
    yield tmp_path / "plans"
    autotune.reset_for_tests()


def sixty_four(kind="register"):
    rng = random.Random(3264)
    return [random_valid_history(rng, kind, n_ops=12, n_procs=2,
                                 crash_p=0.0) for _ in range(64)]


def test_a_served_launch_takes_the_default_plan_and_samples_nothing(
        open_gates, monkeypatch):
    from jepsen_jgroups_raft_tpu.checker import linearizable

    ran_on = []

    def check(*a, **kw):
        ran_on.append((threading.get_ident(), kw.get("serve_rows")))
        return check_encoded(*a, **kw)

    monkeypatch.setattr(linearizable, "check_encoded", check)
    sched = BatchScheduler(AdmissionQueue())   # binds its check path
    reqs = [admit(sixty_four()[i:i + 32], "register") for i in (0, 32)]
    dispatcher = threading.Thread(target=sched.execute, args=(reqs,))
    dispatcher.start()
    dispatcher.join()
    # the scheduler says what it serves, in so many words
    assert ran_on == [(dispatcher.ident, DEFAULT_MAX_BATCH_ROWS)]
    assert all(r.status == "done" for r in reqs)
    c = autotune.snapshot_counters()
    assert c["plan_misses"] >= 1          # the store was asked, and empty
    assert c["samples_run"] == 0 and c["plans_measured"] == 0
    assert not list(open_gates.rglob("*.json"))
    for r in reqs:
        assert r.stats["autotune_plans"] == []


def test_the_same_group_is_measured_off_the_served_path(open_gates,
                                                        monkeypatch):
    """The control: the gates are open, so a library caller measures
    (through a stub sampler, to keep the test short) where the served
    path took the default."""
    sampled = []
    monkeypatch.setattr(
        autotune, "_run_dense_sample",
        lambda model, plan, sample, val_of, cand: sampled.append(cand)
        or 1.0 + len(sampled) * 1e-3)
    model = CasRegister()
    encs = [encode_history(h, model) for h in sixty_four()]
    check_encoded(encs, model, algorithm="jax", serve_rows=64)
    assert not sampled
    check_encoded(encs, model, algorithm="jax")
    assert sampled and autotune.snapshot_counters()["plans_measured"] >= 1


def test_a_served_path_reads_plans_only_as_preloaded(open_gates):
    sig = autotune.bucket_signature("dense", 2, 4, 64, 24)
    plan = autotune.TunedPlan("dense", 64, 4, 1)
    autotune.save_plan(sig, plan, {})
    autotune.reset_for_tests()              # a new process
    assert autotune.plan_for(sig, disk=False) is None   # not read
    autotune.reset_for_tests()
    assert autotune.preload_plans() == 1
    assert autotune.plan_for(sig, disk=False) == plan


# ------------------------------------------------ the build at start


def test_graftd_builds_the_recorded_keys_before_it_is_warm(tmp_path,
                                                           monkeypatch):
    """A service that met a key leaves a record beside the plans; the
    next service of the host builds it when it starts, and a launch of
    that key then finds every program there."""
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "plans"))
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    autotune.reset_for_tests()
    install_compile_counters()
    # "a new host": no key built yet
    monkeypatch.setattr(schedule, "_BUILT", {})
    rng = random.Random(3270)
    hists = [random_valid_history(rng, "counter", n_ops=10, n_procs=4,
                                  crash_p=0.0) for _ in range(8)]
    first = CheckingService(store_root=None, batch_wait=0.0,
                            max_batch_rows=16)
    try:
        r = first.submit(hists, workload="counter")
        assert r.wait(60.0) and r.status == "done"
    finally:
        first.shutdown(wait=True)
    record = json.loads(next((tmp_path / "plans").rglob(
        buildahead.RECORD_NAME)).read_text())
    assert record["keys"] and all(
        k["spec"]["model"] == "Counter" and k["rows"] >= 8
        for k in record["keys"])
    # "a new process": nothing built, nothing loaded
    monkeypatch.setattr(schedule, "_BUILT", {})
    monkeypatch.setattr(buildahead, "_written", 0)
    spans = snapshot_spans().get("build.ahead", {"n": 0})["n"]
    second = CheckingService(store_root=None, batch_wait=0.0,
                             max_batch_rows=16)
    try:
        deadline = time.monotonic() + 60.0
        while not second.stats()["warm"] and time.monotonic() < deadline:
            time.sleep(0.02)
        st = second.stats()
        assert st["warm"] and st["build_ahead"]["source"] == "record"
        assert st["build_ahead"]["keys"] == len(record["keys"])
        assert st["build_ahead"]["programs"] >= 5   # rows 8 and 16
        assert snapshot_spans()["build.ahead"]["n"] > spans
        built = st["programs_built"]
        r = second.submit(hists, workload="counter")
        assert r.wait(60.0) and r.status == "done"
        after = second.stats()
        assert after["programs_built"] == built
        assert after["shape_misses"] == 0
    finally:
        second.shutdown(wait=True)
        autotune.reset_for_tests()


def test_with_no_record_nothing_is_built_unasked(tmp_path, monkeypatch):
    monkeypatch.setenv("JGRAFT_AUTOTUNE", "1")
    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp_path / "plans"))
    autotune.reset_for_tests()
    spans = snapshot_spans().get("build.ahead", {"n": 0})["n"]
    info = buildahead.build_at_start(DEFAULT_MAX_BATCH_ROWS)
    assert info.pop("seconds") >= 0.0
    assert info == {"source": "none", "keys": 0, "programs": 0}
    assert snapshot_spans().get("build.ahead", {"n": 0})["n"] == spans
    autotune.reset_for_tests()


def test_launch_shapes_is_a_value():
    assert launch_shapes(40, 100) == LaunchShapes((8, 16, 32, 48), 128)
    assert np.all(np.diff(launch_shapes(1000, 32, 8).rows) > 0)


# ------------------------------------- one build a bucket, whoever asks


def _one_launch(n_rows, seed=3280):
    """A real launch of `n_rows` short counter histories (one group)."""
    from jepsen_jgroups_raft_tpu.history.packing import pack_batch
    from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plans_grouped

    model = Counter()
    rng = random.Random(seed)
    encs = [encode_history(
        random_valid_history(rng, "counter", n_ops=10, n_procs=3,
                             crash_p=0.0), model) for _ in range(n_rows)]
    [(idxs, plan)], rest = dense_plans_grouped(model, encs)
    assert not rest
    batch = pack_batch([encs[i] for i in idxs])
    [launch], _ = schedule.build_dense_launches(
        model, [(list(idxs), plan, batch)])
    return launch


@pytest.mark.parametrize("upto", [None, 64])
def test_two_threads_that_need_one_bucket_build_it_once(upto, monkeypatch):
    """Both wait for the one build, with the build pool full: neither is
    cancelled, neither builds twice, and what a second caller finds
    pending it waits for."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    monkeypatch.setattr(schedule, "_BUILT", {})
    monkeypatch.setattr(schedule, "BUILD_THREADS", 1)
    monkeypatch.setattr(schedule, "_BUILDERS", None)
    launch = _one_launch(20)
    real, calls, gate = schedule._build_rows, [], threading.Event()

    def slow(launch, key, rows, lower, width):
        calls.append(rows)
        assert gate.wait(60.0)
        return real(launch, key, rows, lower, width)

    monkeypatch.setattr(schedule, "_build_rows", slow)
    got, errors = [], []

    def ask():
        try:
            got.append(schedule.build_keys([launch], upto=upto))
        except BaseException as e:   # CancelledError is one
            errors.append(e)

    threads = [threading.Thread(target=ask) for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30.0
    while not calls and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)      # the second caller finds the first's futures
    gate.set()
    for t in threads:
        t.join(60.0)
    assert not errors and len(got) == 2
    want = list(launch_shapes(max(20, upto or 0), 16, 8).rows) \
        if upto else [launch_rows(20, 8)]
    assert sorted(calls) == want          # each bucket built once
    assert got[0] == got[1] == 3 * len(want) - (1 if upto else 0)
    [entry] = [b for b in snapshot_built() if b["rows"]]
    assert entry["rows"] == want
    schedule._BUILDERS.shutdown(wait=True)


def test_launches_of_one_key_from_two_threads_agree(monkeypatch):
    """Two shard executors launch the same key at once: same verdicts,
    one build."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    monkeypatch.setattr(schedule, "_BUILT", {})
    install_compile_counters()
    launch = _one_launch(12, seed=3281)
    outs, errors = [], []

    def run():
        try:
            outs.append(schedule.run_chunked([launch], build_rows=32))
        except BaseException as e:
            errors.append(e)

    misses = snapshot_compiles()["shape_misses"]
    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    assert not errors and len(outs) == 2
    assert np.array_equal(outs[0][0].ok, outs[1][0].ok)
    assert outs[0][0].ok.all()
    assert snapshot_compiles()["shape_misses"] == misses


@pytest.mark.parametrize("serve_rows", [None, 16])
def test_placement_is_the_same_whoever_calls(serve_rows, monkeypatch):
    """A service's launch is placed exactly as a library caller's (no
    caller is special): by `build_dense_launches`, on the accelerator;
    its rows' groups are counted, and its key is a key of the set."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    monkeypatch.setattr(schedule, "_BUILT", {})
    model = Counter()
    encs = [encode_history(h, model) for h in sixty_four("counter")[:12]]
    groups = schedule.snapshot_stats()["groups_run"]
    rs = check_encoded(encs, model, algorithm="jax", serve_rows=serve_rows)
    assert {r["kernel"] for r in rs} == {"dense-mask"}
    assert schedule.snapshot_stats()["groups_run"] - groups >= 1
    keys = [b for b in snapshot_built() if b["spec"]]
    assert keys and not any(b["spec"]["host"] for b in keys)
    assert all("cpu" not in str(b["key"][-1]) for b in keys)
    if serve_rows:   # built whole for the service's largest launch
        assert any(b["rows"] == [8, 16] for b in keys)


@pytest.mark.parametrize("n_rows", [8, 24, 256])
def test_nothing_is_placed_on_the_host_cpu_beside_a_chip(n_rows,
                                                         monkeypatch):
    """With a TPU as the default backend and a cpu device at hand (as on
    every chip host), no launch of any size is tagged `@host` or placed
    on a cpu device: the host-cpu route lost on the chip (PERF.md
    section 6, PR 32, call M) and is not in the tree."""
    import jax

    assert jax.local_devices(backend="cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    launch = _one_launch(n_rows)
    assert launch.events.shape[0] == n_rows
    assert not launch.tag.endswith("@host")
    assert launch.spec["host"] is False
    # the mesh's sharding or the default device, never a concrete one
    assert launch.device is None or hasattr(launch.device, "mesh")
    assert "cpu" not in schedule.launch_key(launch, 128)[-1]


# ------------------------------- one trace a program kind (ISSUE 43)

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: what JAX's trace event calls the vmapped step of either family, and
#: a row bucket's call of its shared trace
STEP, STEP_AT_ROWS = "step_one", "step_one" + schedule.SHARED_SUFFIX
_TRACED: list = []   # fun_name of every trace event of this process


def trace_events():
    """The trace events since the call, by name, as a live list."""
    from jax import monitoring

    if not _TRACED:
        _TRACED.append(None)    # the listener is registered once
        monitoring.register_event_duration_secs_listener(
            lambda event, _s, **kw: event == TRACE_EVENT and
            _TRACED.append(kw.get("fun_name")))
    start = len(_TRACED)

    class Since:
        def count(self, name):
            return _TRACED[start:].count(name)

    return Since()


@pytest.fixture()
def one_device(monkeypatch):
    """The placement of a one-chip host: no mesh, the default device;
    and "a new process": no key built, no shared trace made."""
    monkeypatch.setenv("JGRAFT_GROUP_DEVICES", "0")
    monkeypatch.setattr(schedule, "_BUILT", {})
    monkeypatch.setattr(schedule, "_SHARED", {})
    install_compile_counters()


def family_launch(kind, n_rows, seed, chunk, n_ops=10, long_every=9):
    """A real launch of one window group of `n_rows` histories of the
    mask (counter) or the domain (register) family, and its key at
    `chunk`: a seed and a chunk of its own give a test a key nothing
    else in the process has built."""
    from jepsen_jgroups_raft_tpu.history.packing import pack_batch
    from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plans_grouped

    model = MODELS[kind]()
    rng = random.Random(seed)
    encs = [encode_history(
        random_valid_history(rng, kind, n_ops=n_ops if i % long_every else 6 * n_ops,
                             n_procs=3, crash_p=0.0, value_range=3), model)
        for i in range(n_rows)]
    groups, rest = dense_plans_grouped(model, encs)
    assert not rest
    idxs, plan = max(groups, key=lambda g: len(g[0]))
    assert plan.kind == {"counter": "mask", "register": "domain"}[kind]
    batch = pack_batch([encs[i] for i in idxs])
    [launch], _ = schedule.build_dense_launches(
        model, [(list(idxs), plan, batch)])
    key, shapes, _ = launch.key_shapes(chunk, launch.events.shape[0], None)
    return launch, key, shapes.width


def built_entry(key):
    [entry] = [b for b in snapshot_built() if b["key"] == key]
    return entry


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_key_built_whole_is_twenty_six_programs_from_three_traces(
        kind, one_device):
    launch, key, _ = family_launch(kind, 12, 4301, chunk=24)
    assert launch.device is None
    since, before = trace_events(), snapshot_compiles()
    assert schedule.build_keys([launch], 24, upto=256) == 26
    entry = built_entry(key)
    assert entry["rows"] == list(SERVED_ROWS)
    assert (entry["traced"], entry["programs"]) == (3, 26)
    after = snapshot_compiles()
    assert after["programs_from_shared_trace"] - \
        before["programs_from_shared_trace"] == 26 == \
        after["programs_built"] - before["programs_built"]
    # the kernel's Python ran once; a bucket's trace is the call's
    assert since.count(STEP) == 1
    assert since.count(STEP_AT_ROWS) == len(SERVED_ROWS)
    assert since.count("gather") == 1 == since.count("init_one")
    assert [k["traced"] for k in schedule.snapshot_build_keys()
            if k["rows"] == list(SERVED_ROWS)] == [3]


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_bucket_of_the_shared_trace_is_bit_equal_to_its_own_jit(
        kind, one_device):
    """init, step and gather at two buckets, on a launch's own operands
    padded as `_init_group` pads them, against the plain
    ``jax.jit(jax.vmap(...))`` the launch carries."""
    import jax

    launch, key, width = family_launch(kind, 12, 4302, chunk=40)
    shared = schedule._programs(launch, key)
    plain = schedule._programs(launch, None)
    assert plain.init is launch.init_fn and plain.step is launch.step_fn
    assert isinstance(shared.step, schedule._RowShared)
    B, E, lanes = launch.events.shape
    rng = np.random.default_rng(4302)

    def same(a, b):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb) > 0
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and np.array_equal(x, y)

    for rows, after in ((16, 8), (48, 32)):
        take = rng.integers(0, B, rows)
        events = np.zeros((rows, width, lanes), launch.events.dtype)
        events[:, :E] = launch.events[take]
        ne, vo = launch.n_events[take], launch.val_of[take]
        assert events.any() and ne.all()
        idx = rng.integers(0, rows, after).astype(np.int32)
        outs = []
        for p in (shared, plain):
            carry = p.init(vo, ne)
            stepped = p.step(carry, events, np.int32(0), np.int32(width))
            outs.append((carry, stepped,
                         p.gather(stepped[0], events, idx)))
        same(*outs)
        _, _decided, exhausted, ok, _ = outs[0][1]
        assert np.asarray(exhausted).all() and np.asarray(ok).all()


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_nine_buckets_from_eight_threads_export_once(kind, one_device):
    """The once-a-key guard: the buckets of a key reach the build
    threads at the same moment."""
    launch, key, width = family_launch(kind, 12, 4303, chunk=56)
    programs = schedule._programs(launch, key)
    assert schedule._programs(launch, key) is programs
    since = trace_events()
    go, errors = threading.Barrier(8), []

    def build(rows):
        try:
            go.wait(30.0)
            for r in rows:
                schedule._build_rows(launch, key, r, None, width)
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=build,
                                args=(SERVED_ROWS[i::8],))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
    assert not errors
    assert since.count(STEP) == 1 == since.count("init_one")
    assert since.count(STEP_AT_ROWS) == len(SERVED_ROWS)


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_launch_of_a_built_key_walks_down_to_eight_rows_and_traces_nothing(
        kind, one_device, monkeypatch):
    """200 rows start at 256; when the short rows are gone the few long
    ones step down through every `gather` program below, each a hit in
    the cache the key's build filled."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    launch, key, width = family_launch(kind, 200, 4304, chunk=16,
                                       long_every=32)
    long_rows = int((launch.n_events > launch.n_events.min() * 3).sum())
    assert 0 < long_rows <= 8 < launch.events.shape[0] - long_rows
    assert schedule.build_keys([launch], 16, upto=256) > 0
    assert built_entry(key)["rows"] == list(SERVED_ROWS)
    before, spans = snapshot_compiles(), snapshot_spans()
    seen = set(snapshot_launched())
    [out] = schedule.run_chunked([launch], build_rows=256)
    assert out.ok.all() and out.evicted_rows > 0
    after = snapshot_compiles()
    assert after["shape_misses"] == before["shape_misses"]
    assert after["programs_built"] == before["programs_built"]
    for stage in ("build.trace", "build.lower", "build.ahead"):
        assert snapshot_spans()[stage] == spans[stage]
    gathers = {s[2:4] for s in snapshot_launched()
               if s not in seen and s[0] == "gather" and s[1] == key}
    # (a register campaign's largest window group may start at 192)
    top = launch_rows(launch.events.shape[0])
    assert top >= 192 and (16, 8) in gathers
    assert gathers == set(launch_shapes(top, width).gather)


def test_a_mesh_placement_keeps_a_trace_a_bucket_and_the_same_verdicts(
        monkeypatch):
    """Which path a mesh placement takes is pinned here: its own jit a
    row bucket (`shard_map`'s body cannot be refined from a symbolic
    row axis), observed from the placement, and what it decides is what
    one device decides."""
    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "8")
    monkeypatch.delenv("JGRAFT_GROUP_DEVICES", raising=False)
    monkeypatch.setattr(schedule, "_BUILT", {})
    monkeypatch.setattr(schedule, "_SHARED", {})
    install_compile_counters()
    rng = random.Random(4305)
    hists = campaign(rng, "register", 40)
    model = CasRegister()

    def verdicts():
        return [(r["valid?"], r.get("failing-op-index"))
                for r in check_histories(hists, model, algorithm="jax")]

    launch, key, _ = family_launch("register", 12, 4305, chunk=8)
    assert launch.device.mesh.size == 8 and key[3] == "mesh8"
    programs = schedule._programs(launch, key)
    assert programs.init is launch.init_fn
    assert programs.step is launch.step_fn
    assert programs.gather is schedule._gather_fn(launch.device)
    before = snapshot_compiles()["programs_from_shared_trace"]
    on_the_mesh = verdicts()
    assert snapshot_compiles()["programs_from_shared_trace"] == before
    meshed = [b for b in snapshot_built() if b["key"][3] == "mesh8"]
    assert meshed and all(b["traced"] == 0 for b in meshed)
    monkeypatch.setenv("JGRAFT_GROUP_DEVICES", "0")
    assert verdicts() == on_the_mesh
    assert any(v is INVALID for v, _ in on_the_mesh)
    single = [b for b in snapshot_built() if b["key"][3] == "default"]
    assert single and all(b["traced"] >= 2 for b in single)
    assert snapshot_compiles()["programs_from_shared_trace"] > before
