"""A key's build on the program's own clock (ISSUE 42): the four stages
of a program's build from JAX's own events, a key at a time; the pause
a launch takes for its key under a name of its own; graftd's start in
spans.

  (a) every program the backend hands over is either loaded from the
      persistent cache or compiled from source, and the two spans add
      up to `programs_built` and `compile_s`;
  (b) a `jit` traced inside another's trace is counted once, so a
      program's stages fit inside its wall;
  (c) a key's seconds add up to the registry's;
  (d) a key is met by graftd's start (the record) or by a launch, and
      only a launch that waits enters `launch.build`;
  (e) `warm_after_s` and the `start.*` spans after a service start.
"""

import json
import os
import random
import subprocess
import sys
import time
from pathlib import Path

import pytest

from jepsen_jgroups_raft_tpu.checker import autotune, schedule
from jepsen_jgroups_raft_tpu.checker.schedule import (BUILD_STAGES,
                                                      snapshot_compiles,
                                                      snapshot_spans)
from jepsen_jgroups_raft_tpu.history.packing import encode_history
from jepsen_jgroups_raft_tpu.models import Counter
from jepsen_jgroups_raft_tpu.ops import dense_scan
from jepsen_jgroups_raft_tpu.platform import install_compile_counters
from jepsen_jgroups_raft_tpu.service import buildahead
from jepsen_jgroups_raft_tpu.service.daemon import CheckingService

from util import random_valid_history

ROOT = Path(__file__).resolve().parents[1]
STAGE_SPANS = tuple("build." + s for s in BUILD_STAGES)
START_SPANS = ("start.recover", "start.backend", "start.plans",
               "start.record")
ZERO = {"n": 0, "s": 0.0}


def moved(before: dict, after: dict) -> dict:
    """{span: {"n", "s"}}: what `after` holds beyond `before`."""
    return {k: {f: v[f] - before.get(k, ZERO)[f] for f in ("n", "s")}
            for k, v in after.items()}


def one_launch(n_rows, seed=4200):
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


# ------------------------- (a), (c): a cold cache, then the same key again

#: one process: builds one key whole up to 32 rows and prints what the
#: registry holds
CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
from jepsen_jgroups_raft_tpu.platform import install_compile_counters, pin_cpu
pin_cpu(8)
install_compile_counters()
from jepsen_jgroups_raft_tpu.checker import schedule
import test_build_spans as t
launch = t.one_launch(20)
before = schedule.snapshot_spans()
programs = schedule.snapshot_compiles()["programs_built"]
waited = schedule.build_keys([launch], upto=32)
print(json.dumps({{
    "waited": waited, "before": before,
    "spans": schedule.snapshot_spans(),
    "programs_before": programs,
    "compiles": schedule.snapshot_compiles(),
    "keys": [{{k: v for k, v in b.items() if k not in ("key", "spec")}}
             for b in schedule.snapshot_built()]}}))
"""


#: placement -> JGRAFT_GROUP_DEVICES: the CPU mesh keeps a trace a row
#: bucket, one device takes the key's three shared traces (ISSUE 43)
PLACEMENTS = {"mesh": "8", "one-device": "0"}


@pytest.fixture(scope="module", params=sorted(PLACEMENTS))
def two_processes(request, tmp_path_factory):
    """The same key built in two fresh processes that share one
    persistent cache, which keeps every program; on the mesh and on one
    device."""
    cache = tmp_path_factory.mktemp("xla-cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JGRAFT_SCAN_CHUNK="16",
               JGRAFT_GROUP_DEVICES=PLACEMENTS[request.param],
               JGRAFT_AUTOTUNE="0", JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=os.pathsep.join([str(ROOT)] + sys.path))
    runs = {}
    for which in ("cold", "warm"):
        p = subprocess.run(
            [sys.executable, "-c",
             CHILD.format(tests=str(ROOT / "tests"))],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=str(ROOT))
        assert p.returncode == 0, p.stderr[-3000:]
        runs[which] = json.loads(p.stdout.strip().splitlines()[-1])
        runs[which]["shared"] = request.param == "one-device"
    return runs


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_every_program_is_loaded_or_compiled(two_processes, which):
    run = two_processes[which]
    spans, compiles = run["spans"], run["compiles"]
    assert compiles["programs_built"] >= run["waited"] == 8
    assert spans["build.load"]["n"] + spans["build.compile"]["n"] == \
        compiles["programs_built"]
    assert spans["build.load"]["s"] + spans["build.compile"]["s"] == \
        pytest.approx(compiles["compile_s"])
    assert compiles["keys_built"] == 1 == compiles["keys_met_by_launch"]


@pytest.mark.parametrize("which,ran,idle", [
    ("cold", "compile", "load"), ("warm", "load", "compile")])
def test_a_cold_cache_compiles_and_a_warm_one_loads(two_processes, which,
                                                    ran, idle):
    run = two_processes[which]
    spans = run["spans"]
    assert spans["build." + idle] == ZERO     # served, and it never ran
    assert spans["build." + ran]["n"] == run["compiles"]["programs_built"]
    assert spans["build." + ran]["s"] > 0.0
    assert {c[3] for c in run["compiles"]["recent_compiles"]} == {ran}
    # Python does its part whatever the cache holds
    assert spans["build.trace"]["n"] >= 8 and spans["build.trace"]["s"] > 0
    assert spans["build.lower"]["n"] >= 8 and spans["build.lower"]["s"] > 0
    misses = run["compiles"]["compile_cache_misses"]
    assert misses == (spans["build.compile"]["n"] if which == "cold" else 0)


@pytest.mark.parametrize("stage", BUILD_STAGES)
@pytest.mark.parametrize("which", ["cold", "warm"])
def test_a_keys_seconds_add_up_to_the_registrys(two_processes, which, stage):
    """Between the two snapshots only the key's build ran, all of it on
    the build threads."""
    run = two_processes[which]
    [key] = run["keys"]
    assert key["met"] == "launch" and key["rows"] == [8, 16, 32]
    d = moved(run["before"], run["spans"])
    assert key[stage + "_s"] == pytest.approx(d["build." + stage]["s"])
    assert key["programs"] == 8 == \
        run["compiles"]["programs_built"] - run["programs_before"]
    assert d["build.load"]["n"] + d["build.compile"]["n"] == 8
    assert key["wait_s"] == pytest.approx(d["build.ahead"]["s"], rel=0.01)
    assert d["build.ahead"]["n"] == 8
    # a stage's `n` is one a program, and one more an export
    assert key["traced"] == (3 if run["shared"] else 0)
    assert d["build.trace"]["n"] == 8 + key["traced"] == d["build.lower"]["n"]
    assert run["compiles"]["programs_from_shared_trace"] == \
        (8 if run["shared"] else 0)


# ------------------------------------------- (b) nesting, and the listener


def test_a_jit_traced_inside_anothers_trace_is_counted_once():
    import jax
    import jax.numpy as jnp

    install_compile_counters()
    install_compile_counters()      # once a process: nothing doubles
    inner = jax.jit(lambda x: jnp.sin(x) * 2)

    def outer_of_issue_42(x):
        return jax.lax.fori_loop(0, 3, lambda i, c: inner(c) + 1, x)

    x = jnp.arange(4.0)
    x.block_until_ready()
    before, built = snapshot_spans(), snapshot_compiles()["programs_built"]
    t0 = time.perf_counter()
    jax.jit(outer_of_issue_42)(x).block_until_ready()
    wall = time.perf_counter() - t0
    d = moved(before, snapshot_spans())
    # `inner`, `sin`, `multiply`, `add` and the loop's `less` each fired
    # a trace event of their own inside the outer's, or inside its
    # lowering
    assert d["build.trace"]["n"] == 1 == d["build.lower"]["n"]
    assert d["build.load"]["n"] + d["build.compile"]["n"] == 1 == \
        snapshot_compiles()["programs_built"] - built
    assert 0 < sum(d[s]["s"] for s in STAGE_SPANS) <= wall


@pytest.mark.parametrize("program", ["init", "step", "gather"])
def test_the_stages_of_a_program_fit_inside_its_wall(program, monkeypatch):
    """`_build_rows`'s two or three calls, one at a time, at a row count
    and a width no launch of the set has."""
    import jax
    import numpy as np

    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    install_compile_counters()
    launch = one_launch(20)
    rows, width = 24, 80
    calls = {
        "init": lambda: schedule._init_carry(
            launch, launch.init_fn,
            np.zeros((rows,) + launch.val_of.shape[1:],
                             launch.val_of.dtype),
            np.zeros((rows,), np.int32)),
    }
    carry = calls["init"]()
    events = schedule._put(launch, np.zeros(
        (rows, width, launch.events.shape[2]), launch.events.dtype))
    calls["step"] = lambda: launch.step_fn(carry, events, np.int32(0),
                                           np.int32(0))
    calls["gather"] = lambda: schedule._gather_fn(launch.device)(
        carry, events, np.zeros((16,), np.int32))
    if program == "init":   # built above: another row count
        rows = 40
    jax.block_until_ready((carry, events))
    before, built = snapshot_spans(), snapshot_compiles()["programs_built"]
    t0 = time.perf_counter()
    jax.block_until_ready(calls[program]())
    wall = time.perf_counter() - t0
    d = moved(before, snapshot_spans())
    assert snapshot_compiles()["programs_built"] - built == 1
    assert d["build.trace"]["n"] == 1 == d["build.lower"]["n"]
    assert 0 < sum(d[s]["s"] for s in STAGE_SPANS) <= wall


@pytest.mark.parametrize("program", ["init", "step", "gather"])
def test_a_bucket_of_a_shared_trace_is_one_program_of_four_stages(
        program, monkeypatch):
    """On one device a row bucket's program is a call of the key's
    shared trace (ISSUE 43): still one trace event, one lowering and one
    load or compile a program, inside its wall; the export that came
    first was one of each stage more, and no program."""
    import jax
    import numpy as np

    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    monkeypatch.setenv("JGRAFT_GROUP_DEVICES", "0")
    install_compile_counters()
    launch = one_launch(20, seed=4300)
    assert launch.device is None
    width, lanes = 112, launch.events.shape[2]
    key = ("test_build_spans", program, width)   # no launch's key
    programs = schedule._programs(launch, key)

    def operands(rows):
        carry = schedule._init_carry(
            launch, programs.init,
            np.zeros((rows,) + launch.val_of.shape[1:],
                     launch.val_of.dtype), np.zeros((rows,), np.int32))
        events = schedule._put(launch, np.zeros((rows, width, lanes),
                                                launch.events.dtype))
        return carry, events

    def call(rows):
        if program == "init":
            return lambda: operands(rows)[0]
        # the operands' own programs are built outside the clock
        carry, events = jax.block_until_ready(operands(rows))
        if program == "step":
            return lambda: programs.step(carry, events, np.int32(0),
                                         np.int32(0))
        return lambda: programs.gather(carry, events,
                                       np.zeros((8,), np.int32))

    def timed(rows):
        run = call(rows)
        before = snapshot_spans()
        built = snapshot_compiles()
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        wall = time.perf_counter() - t0
        after = snapshot_compiles()
        return (moved(before, snapshot_spans()), wall,
                after["programs_built"] - built["programs_built"],
                after["programs_from_shared_trace"]
                - built["programs_from_shared_trace"])

    d, wall, built, shared = timed(24)      # the export, and a bucket
    assert built == 1 == shared
    assert d["build.trace"]["n"] == 2 == d["build.lower"]["n"]
    assert 0 < sum(d[s]["s"] for s in STAGE_SPANS) <= wall
    d, wall, built, shared = timed(40)      # a bucket alone
    assert built == 1 == shared
    assert d["build.trace"]["n"] == 1 == d["build.lower"]["n"]
    assert d["build.load"]["n"] + d["build.compile"]["n"] == 1
    assert 0 < sum(d[s]["s"] for s in STAGE_SPANS) <= wall
    d, _, built, _ = timed(40)              # a hit in the jit's cache
    assert built == 0 == d["build.trace"]["n"]


# --------------------------------- (d), (e): who met a key; graftd's start


def await_warm(svc, seconds=120.0):
    deadline = time.monotonic() + seconds
    while not svc.stats()["warm"] and time.monotonic() < deadline:
        time.sleep(0.02)
    st = svc.stats()
    assert st["warm"]
    return st


@pytest.fixture(scope="module")
def two_starts(tmp_path_factory):
    """A host's first service meets a key at a launch and leaves the
    record; its second builds the key from the record when it starts.
    What `/stats` and the registry said around each."""
    tmp = tmp_path_factory.mktemp("two-starts")
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JGRAFT_AUTOTUNE", "1")
        mp.setenv("JGRAFT_AUTOTUNE_STORE", str(tmp / "plans"))
        mp.setenv("JGRAFT_LIN_FASTPATH", "0")
        mp.setenv("JGRAFT_SCAN_CHUNK", "16")
        autotune.reset_for_tests()
        # "a new process" holds no kernel pair and no key's shared
        # traces either: another test file of this xdist worker may have
        # launched this key (tests/test_launch_shapes.py does), and the
        # first start would then find all five programs in that file's
        # jit caches
        mp.setattr(dense_scan, "_KERNEL_CACHE", {})
        mp.setattr(schedule, "_SHARED", {})
        rng = random.Random(4270)
        hists = [random_valid_history(rng, "counter", n_ops=10, n_procs=4,
                                      crash_p=0.0) for _ in range(8)]
        for which in ("first", "second"):
            # "a new process": nothing built, nothing read
            mp.setattr(schedule, "_BUILT", {})
            mp.setattr(buildahead, "_written", 0)
            before = {"spans": snapshot_spans(),
                      "compiles": snapshot_compiles()}
            t0 = time.monotonic()
            svc = CheckingService(store_root=str(tmp / which),
                                  batch_wait=0.0, max_batch_rows=16,
                                  autostart=False)
            try:
                parked = svc.stats()
                svc.start()
                warm = await_warm(svc)
                warm_wall = time.monotonic() - t0
                r = svc.submit(hists, workload="counter")
                assert r.wait(120.0) and r.status == "done", r.error
                served = svc.stats()
            finally:
                svc.shutdown(wait=True)
            out[which] = {"before": before, "parked": parked, "warm": warm,
                          "warm_wall": warm_wall, "served": served}
        autotune.reset_for_tests()
    return out


@pytest.mark.parametrize("which,met,by_launch", [
    ("first", "launch", 1), ("second", "start", 0)])
def test_a_key_is_met_by_a_launch_or_by_the_start(two_starts, which, met,
                                                  by_launch):
    run = two_starts[which]
    st, before = run["served"], run["before"]["compiles"]
    assert st["build_keys"], st["build_ahead"]
    for key in st["build_keys"]:
        assert key["met"] == met
        assert (key["model"], key["kind"]) == ("Counter", "mask")
        assert key["rows"] == [8, 16] and key["wait_s"] > 0.0
        assert key["load_s"] == 0.0     # tests keep no persistent cache
        if which == "first":    # five programs, if this process has
            assert 1 <= key["programs"] <= 5    # built none of them yet
            assert key["trace_s"] > 0.0 and key["lower_s"] > 0.0
        else:   # this process's jit cache still holds the first's
            assert key["programs"] == 0 == key["trace_s"]
    n = len(st["build_keys"])
    assert st["keys_built"] - before["keys_built"] == n
    assert st["keys_met_by_launch"] - before["keys_met_by_launch"] == \
        by_launch * n
    # the start built what it could know: nothing the first time, the
    # record's keys the second, with the seconds it took
    ahead = run["warm"]["build_ahead"]
    assert ahead["source"] == ("none" if which == "first" else "record")
    assert ahead["keys"] == (0 if which == "first" else n)
    assert (run["warm"]["build_keys"] == []) == (which == "first")
    assert 0.0 < ahead["seconds"] <= run["warm"]["warm_after_s"]


@pytest.mark.parametrize("which", ["first", "second"])
@pytest.mark.parametrize("phase", START_SPANS + ("build.ahead",))
def test_the_start_is_tiled_by_spans(two_starts, which, phase):
    run = two_starts[which]
    d = {p: ZERO for p in START_SPANS + ("build.ahead",)}
    d.update(moved(run["before"]["spans"], run["warm"]["spans"]))
    ahead = run["warm"]["build_ahead"]
    if phase == "build.ahead":      # no record the first time: no wait
        assert d[phase]["n"] == ahead["programs"]
        assert (ahead["programs"] >= 5) == (which == "second")
    elif phase == "start.record":   # `n` the keys it made templates of
        assert d[phase]["n"] == ahead["keys"]
    else:
        assert d[phase]["n"] == 1
    # the phases lie one after another inside construction -> warm
    assert "warm_after_s" not in run["parked"]
    phases = sum(d[p]["s"] for p in START_SPANS + ("build.ahead",))
    assert phases <= run["warm"]["warm_after_s"] <= run["warm_wall"]
    assert run["served"]["warm_after_s"] == run["warm"]["warm_after_s"]


def test_only_a_launch_that_waits_enters_launch_build(tmp_path, monkeypatch):
    """In a profiler session: the first launch of a key waits for it in
    `launch.build`, an annotation on the dispatcher's line inside
    `launch.device`; the next launch of the key does not enter it."""
    import jax
    from jax.profiler import ProfileData

    from jepsen_jgroups_raft_tpu.service import spans as report

    monkeypatch.setenv("JGRAFT_SCAN_CHUNK", "16")
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "0")
    monkeypatch.setattr(schedule, "_BUILT", {})
    install_compile_counters()
    rng = random.Random(4290)
    waves = [[random_valid_history(rng, "counter", n_ops=10, n_procs=4,
                                   crash_p=0.0) for _ in range(8)]
             for _ in range(2)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # as the benchmark's traced run
    svc = CheckingService(store_root=None, batch_wait=0.0,
                          max_batch_rows=16, n_workers=1)
    met = []
    try:
        await_warm(svc)
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for hists in waves:
                r = svc.submit(hists, workload="counter")
                assert r.wait(120.0) and r.status == "done", r.error
                met.append(svc.stats()["keys_met_by_launch"])
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.shutdown(wait=True)
    assert met[1] == met[0]             # the second wave met no key
    data = ProfileData.from_file(str(report.find_trace(tmp_path)))
    lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats) if ev.name == "launch.build" else None)
              for ev in line.events]
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    [dispatcher] = [ln for ln in lines
                    if any(ev[0] == "dispatch.take" for ev in ln)]
    builds = [ev for ev in dispatcher if ev[0] == "launch.build"]
    assert not any(ev[0] == "launch.build" for ln in lines
                   if ln is not dispatcher for ev in ln)
    keys = [k for k in svc.stats()["build_keys"] if k["met"] == "launch"]
    assert len(builds) == len(keys) >= 1
    devices = [ev for ev in dispatcher if ev[0] == "launch.device"]
    assert len(devices) >= 2            # a launch a wave, one that waited
    for _, start, end, args in builds:
        assert args["programs"] >= 5 and args["key"].startswith(
            "Counter/mask/W")
        assert any(s <= start and end <= e for _, s, e, _ in devices)


@pytest.mark.parametrize("reader", ["trace_reduce.name_gaps",
                                    "spans.idle_by_span"])
def test_an_idle_gap_under_launch_build_is_laid_on_it(reader):
    """Both reductions of a trace name a gap by the innermost `launch.`
    span open over it, so the pause needs no edit to either: a device
    idle for 7 s while the dispatcher waits for a key reads
    `launch.build`, not `launch.device`."""
    from benchmarks import trace_reduce

    from jepsen_jgroups_raft_tpu.service import spans as report

    s = 1_000_000_000
    spans = [("dispatch.take", 0, 1 * s), ("launch.device", 1 * s, 10 * s),
             ("launch.build", 2 * s, 9 * s), ("launch.sync", 9 * s, 10 * s)]
    busy = [(0, 1 * s + s // 2), (9 * s + s // 2, 10 * s)]   # idle 1.5-9.5
    if reader == "trace_reduce.name_gaps":
        by = dict(trace_reduce.name_gaps([(busy[0][1], 8 * s)], spans))
    else:
        by = {n: ns / 1e9 for n, ns in report.idle_by_span(
            busy, spans, 0, 10 * s)["by_span"]}
    assert by == {"launch.build": 7.0, "launch.device": 0.5,
                  "launch.sync": 0.5}
