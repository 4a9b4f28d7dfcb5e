"""Spans on graftd's served path (ISSUE 26): the one primitive in the
checker/schedule.py registry, the sites that tile the dispatcher's loop,
a request's phases, the compile counters, and the idle-by-span
arithmetic. CPU only; every wait is bounded."""

from __future__ import annotations

import ast
import random
import threading
import time
from pathlib import Path

import pytest

from jepsen_jgroups_raft_tpu.checker import schedule
from jepsen_jgroups_raft_tpu.checker.schedule import (annotate, launch_span,
                                                      note_span, open_span,
                                                      snapshot_compiles,
                                                      snapshot_spans, span,
                                                      stats_scope)
from jepsen_jgroups_raft_tpu.service import CheckingService
from jepsen_jgroups_raft_tpu.service import spans as report
from jepsen_jgroups_raft_tpu.service.journal import AdmissionJournal

from util import H, random_valid_history

PKG = Path(schedule.__file__).resolve().parents[1]
WAIT_S = 120.0
TILING = ("dispatch.take", "dispatch.scan", "dispatch.linger",
          "launch.host", "launch.device", "demux.results",
          "demux.counterexample", "demux.account", "demux.trace_write")
PHASES = ("queue_wait", "formation_wait", "run", "finish")


def total(name, field="s", since=None):
    now = snapshot_spans().get(name, {"n": 0, "s": 0.0})[field]
    return now - (since or {}).get(name, {"n": 0, "s": 0.0})[field]


def valid_hist(seed, n_ops=24):
    return random_valid_history(random.Random(seed), "register",
                                n_ops=n_ops, crash_p=0.0)


def invalid_hist(salt, n_ops=24):
    rows = []
    for i in range(n_ops - 1):
        v = salt * 100_000 + i
        rows += [(0, "invoke", "write", v), (0, "ok", "write", v)]
    rows += [(1, "invoke", "read", None), (1, "ok", "read", -7)]
    return H(*rows)


def serve_waves(svc, waves=2, per_wave=3, salt0=0):
    """`waves` x `per_wave` requests of three valid rows and an invalid
    one (so none is answered by the fast lane); returns them, done."""
    reqs = []
    for w in range(waves):
        wave = []
        for k in range(per_wave):
            salt = salt0 + 10 * w + k
            wave.append(svc.submit(
                [valid_hist(100 * salt + i) for i in range(3)]
                + [invalid_hist(salt)], workload="register"))
        for r in wave:
            assert r.wait(WAIT_S) and r.status == "done", r.error
        reqs += wave
    return reqs


# ------------------------------------------------------------ primitive


def test_span_records_the_seconds_it_yields():
    before = snapshot_spans()
    with span("t.same") as sp:
        time.sleep(0.01)
    assert sp.s >= 0.01
    assert total("t.same", "s", before) == pytest.approx(sp.s, abs=1e-12)
    assert total("t.same", "n", before) == 1
    with span("t.same", n=7):
        pass
    note_span("t.same", 0.5, n=0)
    assert total("t.same", "n", before) == 8
    assert total("t.same", "s", before) >= sp.s + 0.5


def test_scopes_are_thread_affine_and_totals_are_process_wide():
    before = snapshot_spans()
    seen, gate = {}, threading.Barrier(2)

    def worker(name, seconds):
        with stats_scope(label=name) as scope:
            gate.wait(10)          # both scopes are open from here on
            note_span("t.affine", seconds)
            with span("t.affine." + name):
                pass
            gate.wait(10)
            seen[name] = scope["spans"]

    threads = [threading.Thread(target=worker, args=("a", 1.0)),
               threading.Thread(target=worker, args=("b", 2.0))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    assert seen["a"]["t.affine"] == [1, 1.0]
    assert seen["b"]["t.affine"] == [1, 2.0]
    assert "t.affine.b" not in seen["a"] and "t.affine.a" not in seen["b"]
    assert total("t.affine", "s", before) == pytest.approx(3.0)
    assert total("t.affine", "n", before) == 2


def test_snapshots_do_not_alias():
    note_span("t.alias", 1.0)
    one = snapshot_spans()
    one["t.alias"]["s"] += 100.0
    one["t.gone"] = {"n": 1, "s": 1.0}
    two = snapshot_spans()
    assert "t.gone" not in two
    assert two["t.alias"]["s"] < 100.0
    svc = CheckingService(autostart=False)
    try:
        a, b = svc.stats(), svc.stats()
        assert a["spans"] is not b["spans"]
        a["spans"]["t.alias"]["n"] = -1
        assert svc.stats()["spans"]["t.alias"]["n"] >= 1
        a["recent_compiles"].append("x")
        assert "x" not in svc.stats()["recent_compiles"]
    finally:
        svc.shutdown(wait=False)


def test_open_span_is_the_innermost_on_this_thread():
    assert open_span() is None
    with annotate("t.outer"):
        with span("t.inner"):
            assert open_span() == "t.inner"
        assert open_span() == "t.outer"
    assert open_span() is None
    assert "t.outer" not in snapshot_spans()     # annotate is not timed


def test_no_annotation_object_without_a_session(monkeypatch):
    import jax

    built = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **kw: built.append(a) or 1 / 0)
    with span("t.quiet", rows=3), annotate("t.quiet.too"):
        pass
    assert built == []


def test_a_span_is_an_annotation_on_the_host_plane_of_a_session(tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # as the benchmark's traced run
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with span("launch.device", seq=41, rows=256):
            with span("launch.sync"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(report.find_trace(tmp_path)))
    found = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("launch.device", "launch.sync"):
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"launch.device", "launch.sync"}
    outer, inner = found["launch.device"], found["launch.sync"]
    assert outer[2] == {"seq": 41, "rows": 256}
    assert outer[0] <= inner[0] and \
        inner[0] + inner[1] <= outer[0] + outer[1]
    assert inner[1] >= 2_000_000


def test_launch_span_takes_a_profile_where_the_knob_says(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("JGRAFT_PROFILE_DIR", str(tmp_path))
    with launch_span(rows=2) as sp:
        pass
    assert sp.name == "launch.device"
    assert report.find_trace(tmp_path).name.endswith(".xplane.pb")
    import jax

    # a session someone else began is joined, not fought over
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        with launch_span(rows=2):
            pass
    finally:
        jax.profiler.stop_trace()


def _loops_calling(tree, names=("span", "note_span", "annotate",
                                "launch_span")):
    """Calls of the span primitive that sit lexically inside a loop or a
    comprehension of the same function."""
    bad = []
    loops = (ast.For, ast.While, ast.AsyncFor, ast.ListComp, ast.SetComp,
             ast.DictComp, ast.GeneratorExp)

    def walk(node, in_loop):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                walk(child, False)
                continue
            if isinstance(child, ast.Call):
                f = child.func
                name = f.id if isinstance(f, ast.Name) else \
                    f.attr if isinstance(f, ast.Attribute) else None
                if name in names and in_loop:
                    bad.append(child.lineno)
            walk(child, in_loop or isinstance(child, loops))

    walk(tree, False)
    return bad


def test_no_span_site_inside_a_loop_over_rows_or_events():
    """The rule of the primitive: a site runs per request, per launch or
    per wavefront round. The row- and event-level code holds none."""
    files = [PKG / "checker" / "certify_batch.py",
             PKG / "history" / "packing.py"] + sorted(
                 (PKG / "ops").glob("*.py"))
    assert len(files) > 5
    for path in files:
        tree = ast.parse(path.read_text())
        assert _loops_calling(tree) == [], path
    planted = ast.parse("def f(rows):\n  for r in rows:\n"
                        "    with span('x'):\n      pass\n")
    assert _loops_calling(planted) == [3]


# --------------------------------------------------------- served path


def test_a_served_request_carries_its_phases(tmp_path):
    svc = CheckingService(store_root=str(tmp_path), n_workers=1)
    try:
        reqs = serve_waves(svc, waves=1, per_wave=3)
    finally:
        svc.shutdown()
    for r in reqs:
        phases = r.stats["phases_ms"]
        assert set(PHASES) <= set(phases)
        assert all(v >= 0.0 for v in phases.values())
        latency_ms = (r.finished - r.submitted) * 1e3
        assert sum(phases.values()) == pytest.approx(latency_ms, rel=0.05)
        assert r.to_dict()["service-stats"]["phases_ms"] == phases
        launch = r.stats["scan"]["spans"]
        assert launch["launch.device"]["n"] >= 1
        assert launch["launch.host"]["n"] == 1
        # the wall around the check is read once: it is `batch_wall_s`
        # and the two spans that split it
        assert r.stats["batch_wall_s"] == pytest.approx(
            launch["launch.host"]["s"] + launch["launch.device"]["s"],
            abs=2e-4)


def test_the_fast_lane_stamps_the_scan_phase(tmp_path, monkeypatch):
    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    before = snapshot_spans()
    svc = CheckingService(store_root=str(tmp_path), n_workers=1)
    try:
        [live] = serve_waves(svc, waves=1, per_wave=1, salt0=500)
        lane = svc.submit([valid_hist(9000 + i) for i in range(3)],
                          workload="register")
        assert lane.wait(WAIT_S) and lane.status == "done"
    finally:
        svc.shutdown()
    assert lane.stats.get("fastlane") is True
    assert set(lane.stats["phases_ms"]) == {"queue_wait", "scan", "finish"}
    assert {"scan", *PHASES} <= set(live.stats["phases_ms"])
    assert total("dispatch.scan", "n", before) == 2
    assert total("request.scan", "n", before) == 2
    assert total("request.formation_wait", "n", before) == 1


def test_nine_spans_tile_the_dispatcher_loop(tmp_path, monkeypatch):
    from jepsen_jgroups_raft_tpu.service import scheduler

    ended = []      # (thread, name, clock at its end, seconds), in order
    note = schedule.note_span

    waited_in = []  # the span open where a `build.ahead` wait ended

    def noting(name, seconds, n=1):
        ended.append((threading.get_ident(), name, time.perf_counter(),
                      seconds))
        if name == "build.ahead":
            waited_in.append((threading.get_ident(), schedule.open_span()))
        note(name, seconds, n)

    monkeypatch.setattr(schedule, "note_span", noting)
    monkeypatch.setattr(scheduler, "note_span", noting)
    # no key is built: the first launch waits for its own, in
    # `launch.build`, an annotation nested in the `launch.device` tile
    monkeypatch.setattr(schedule, "_BUILT", {})
    before = snapshot_spans()
    svc = CheckingService(store_root=str(tmp_path), n_workers=1)
    try:
        reqs = serve_waves(svc, waves=3, per_wave=3, salt0=100)
        worker = svc._worker.ident
    finally:
        svc.shutdown()       # joins the worker: every span has ended
    # The dispatcher's own loop, on its own thread: from the start of
    # its first `dispatch.take` to the end of its last tiling span (the
    # take the shutdown woke). Building the service, a loaded host's
    # thread start-up and another service's dispatcher in this process
    # are not this loop's to tile.
    loop = [(end, s) for t, name, end, s in ended
            if t == worker and name in TILING]
    wall = loop[-1][0] - (loop[0][0] - loop[0][1])
    tiled = sum(s for _, s in loop)
    assert tiled >= 0.9 * wall, (tiled, wall)
    assert tiled <= 1.02 * wall
    assert (worker, "launch.build") in waited_in
    # one row in four is invalid, and each is explained
    assert total("demux.counterexample", "n", before) == len(reqs)
    assert total("demux.results", "n", before) == len(reqs)
    assert total("ingest.decode", "n", before) == len(reqs)
    assert total("ingest.fingerprint", "n", before) == len(reqs)
    assert total("journal.append", "n", before) == len(reqs)
    assert 1 <= total("journal.fsync", "n", before) <= 2 * len(reqs)
    assert total("launch.sync", "n", before) >= \
        total("launch.device", "n", before) >= 1
    assert total("launch.sync", "s", before) <= \
        total("launch.device", "s", before)
    for phase in PHASES:
        assert total("request." + phase, "n", before) == len(reqs)
    st = svc.stats()
    assert st["spans"]["launch.device"]["n"] >= 1
    assert st["programs_built"] == snapshot_compiles()["programs_built"]


def test_group_and_pack_are_nested_in_the_host_tile(tmp_path, monkeypatch):
    """`launch.group` (the domain scan and the window grouping) and
    `launch.pack` (the group packs) lie inside `launch.host` as
    `launch.sync` lies inside `launch.device`: they count the launch's
    rows (all of them; those packed for the dense families), they are no
    tile, and the host tile still holds them whole."""
    from benchmarks.layer_metrics import _spans as bench_spans

    from jepsen_jgroups_raft_tpu.service import scheduler

    ended = []      # (thread, name, clock at its end, seconds, n)
    note = schedule.note_span

    def noting(name, seconds, n=1):
        ended.append((threading.get_ident(), name, time.perf_counter(),
                      seconds, n))
        note(name, seconds, n)

    monkeypatch.setattr(schedule, "note_span", noting)
    monkeypatch.setattr(scheduler, "note_span", noting)
    svc = CheckingService(store_root=str(tmp_path), n_workers=1)
    try:
        reqs = serve_waves(svc, waves=2, per_wave=3, salt0=700)
        worker = svc._worker.ident
    finally:
        svc.shutdown()
    assert "launch.group" not in TILING and "launch.pack" not in TILING
    assert tuple(bench_spans.TILING) == TILING
    for r in reqs:
        launch = r.stats["scan"]["spans"]
        rows = r.stats["batch_rows"]
        assert launch["launch.group"]["n"] == rows
        # a request's invalid row writes 23 values, past the domain
        # family: the ladder's, so three rows in four are packed here
        assert launch["launch.pack"]["n"] == rows - rows // 4
        assert launch["launch.group"]["s"] + launch["launch.pack"]["s"] \
            <= launch["launch.host"]["s"] + 1e-6
    mine = [e for e in ended if e[0] == worker]
    hosts = [e for e in mine if e[1] == "launch.host"]
    assert hosts
    floor = 0.0     # the end of the tile before this launch's
    for _, _, end, _, _ in hosts:
        inner = [e for e in mine if floor < e[2] <= end
                 and e[1] in ("launch.group", "launch.pack",
                              "launch.device")]
        names = [e[1] for e in inner]
        # the grouping, then the packs, then the wavefront, all before
        # `launch.host` is noted; the two new ones once a launch
        assert names[:2] == ["launch.group", "launch.pack"], names
        assert names.count("launch.group") == names.count("launch.pack") == 1
        assert inner[0][4] > inner[1][4] > 0
        # the host tile is the check's wall less the device's seconds:
        # it holds the two nested spans whole
        device_s = sum(e[3] for e in inner if e[1] == "launch.device")
        wall_from = inner[0][2] - inner[0][3]
        assert end - wall_from >= device_s + inner[0][3] + inner[1][3] - 1e-4
        floor = end


def test_pack_ms_per_row_reads_the_two_nested_spans():
    """The benchmark's reader of them (`benchmarks/layer_metrics/
    pack_ms_per_row.py`): both spans' milliseconds a PACKED row, nothing
    from a program that serves spans but not these two (the parent), and
    the four cells whose launches pack batches list it."""
    import json

    from benchmarks.layer_metrics import pack_ms_per_row as reader

    def ctx(before, after):
        return {"window_s": 51.0, "before": {"stats": {"spans": before}},
                "after": {"stats": {"spans": after}}}

    host = {"launch.host": {"n": 10, "s": 1.0}}
    assert reader.read(ctx({}, dict(
        host, **{"launch.pack": {"n": 150, "s": 0.02},
                 "launch.group": {"n": 200, "s": 0.01}}))) == \
        pytest.approx(0.2)
    assert reader.read(ctx({}, host)) is None
    assert reader.read({"window_s": 51.0, "before": {"stats": {}},
                        "after": {"stats": {}}}) is None
    manifest = json.loads((PKG.parent / "BENCHMARK.json").read_text())
    [entry] = [m for m in manifest["per_layer"]
               if m["name"] == "pack_ms_per_row"]
    assert (entry["layer"], entry["source"], entry["better"],
            entry["moves"]) == ("kernels", "program_span", "lower",
                                "verdict_p50_ms")
    assert entry["workloads"] == [
        "register-map-10k.campaign-keyed", "counter-1k.campaign",
        "register-1k.campaign", "register-partition-1k.campaign-wide"]


def test_journal_append_feeds_its_latency_window_from_the_span(tmp_path):
    from jepsen_jgroups_raft_tpu.service.request import admit

    before = snapshot_spans()
    j = AdmissionJournal(tmp_path)
    try:
        req = admit([valid_hist(3)], "register")
        assert j.append_submit(req)
        assert j.append_terminal(req)
    finally:
        j.close()
    assert total("journal.append", "n", before) == 1
    assert total("journal.mark", "n", before) == 1
    assert total("journal.fsync", "n", before) == 2
    both = total("journal.append", "s", before) + \
        total("journal.mark", "s", before)
    assert sum(j.append_ms) == pytest.approx(both * 1e3)
    assert total("journal.fsync", "s", before) <= both


def test_compile_counters_name_the_step_that_compiled():
    import jax
    import jax.numpy as jnp

    from jepsen_jgroups_raft_tpu.platform import install_compile_counters

    install_compile_counters()
    install_compile_counters()          # once a process
    x = jnp.arange(5)
    x.block_until_ready()
    before = snapshot_compiles()

    def never_seen_before_26(x):
        return x * 3 + 26

    with stats_scope() as scope, span("t.compiling"):
        jax.jit(never_seen_before_26)(x).block_until_ready()
    after = snapshot_compiles()
    built = after["programs_built"] - before["programs_built"]
    assert built >= 1 and scope["programs_built"] == built
    assert after["compile_s"] > before["compile_s"]
    [(name, seconds, inside, stage)] = [
        c for c in after["recent_compiles"]
        if "never_seen_before_26" in c[0]]
    assert seconds > 0 and inside == "t.compiling"
    assert stage == "compile"           # tests keep no persistent cache
    assert len(after["recent_compiles"]) <= 16


# ------------------------------------------------------ idle by span


def test_idle_by_span_on_a_hand_made_trace():
    ms = 1_000_000
    busy = [(100 * ms, 150 * ms), (140 * ms, 160 * ms),   # overlap
            (165 * ms, 170 * ms),                         # 5 ms gap before
            (400 * ms, 420 * ms)]
    spans = [("dispatch.take", 0, 50 * ms),
             ("dispatch.scan", 50 * ms, 95 * ms),
             ("launch.device", 95 * ms, 200 * ms),
             ("launch.sync", 172 * ms, 190 * ms),
             ("demux.account", 200 * ms, 300 * ms),
             ("journal.mark", 220 * ms, 260 * ms),
             ("journal.fsync", 230 * ms, 250 * ms)]
    out = report.idle_by_span(busy, spans, 0, 500 * ms)
    assert out["busy_ns"] == (60 + 5 + 20) * ms
    assert out["idle_ns"] == 500 * ms - out["busy_ns"]
    by = dict(out["by_span"])
    assert sum(by.values()) == out["idle_ns"]
    assert by == {
        "dispatch.take": 50 * ms,
        "dispatch.scan": 45 * ms,
        report.SHORT: 5 * ms,                    # 160..165
        "launch.sync": 18 * ms,
        # 95..100 (the end of the first gap), 170..172, 190..200
        "launch.device": (5 + 2 + 10) * ms,
        "demux.account": (20 + 40) * ms,
        "journal.mark": (10 + 10) * ms,
        "journal.fsync": 20 * ms,
        report.NO_SPAN: (100 + 80) * ms,         # 300..400, 420..500
    }
    assert [n for n, _ in out["by_span"]][0] == report.NO_SPAN
    assert report.covered_ns(busy, spans, "launch.device") == 65 * ms
    # nothing ran: the whole window is idle, by span
    quiet = report.idle_by_span([], spans[:2], 0, 95 * ms)
    assert quiet["busy_ns"] == 0 and dict(quiet["by_span"]) == {
        "dispatch.take": 50 * ms, "dispatch.scan": 45 * ms}


def test_the_report_reads_a_served_trace(tmp_path, capsys):
    import jax

    svc = CheckingService(store_root=str(tmp_path / "store"), n_workers=1)
    try:
        serve_waves(svc, waves=1, per_wave=2, salt0=300)   # compiles
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            serve_waves(svc, waves=1, per_wave=2, salt0=400)
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.shutdown()
    assert report.main([str(tmp_path / "trace")]) == 0
    text = capsys.readouterr().out
    assert "by the innermost span open on the dispatcher thread" in text
    for name in ("launch.sync", "dispatch.take"):
        assert name in text
    assert report.main([]) == 2
