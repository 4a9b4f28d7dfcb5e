"""Whole runs of the harness at rehearsal sizes on the CPU, and the
pieces that decide `attempted`, `failed` and `correct`.

Every run that uses the repository's own `benchmarks/cache` lives in
this one file, so that one xdist worker makes them one after another.
"""

import argparse
import json
import subprocess
import sys
import threading
import time

import pytest

from benchmarks import manifest as mf
from util_bench import RESULT_KEYS, ROOT, last_json, rehearse

MANIFEST = mf.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def args_for(cell, **kw):
    base = dict(workload=cell, seed=2**31 + 99, seconds=2.0, trace=0,
                rehearse=True, gate=False, control=None,
                root=str(ROOT))
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_contract_shaped_last_line(trace):
    cell = CELLS[trace % len(CELLS)]
    rc, lines, err = rehearse(ROOT, "--workload", cell, "--seed",
                              str(2**31 + 5), "--seconds", "2",
                              "--trace", str(trace))
    assert rc == 0, err[-2000:]
    line = last_json(lines)
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"]
             for m in mf.metrics_of(MANIFEST, group, cell)}
    assert line["metrics"]
    for name, m in line["metrics"].items():
        assert m["unit"] == known[name]
        assert isinstance(m["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(known)
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    # every number compared stands beside its limit, on stderr too
    assert line["compared"]["verdict_mismatches"] == {"value": 0,
                                                      "limit": 0}
    assert line["compared"]["rows_compared"]["value"] > 0
    assert "compared verdict_mismatches" in err
    # the phases' seconds come on earlier lines
    phases = [json.loads(x)["phase"] for x in lines[:-1]
              if x.startswith('{"phase"')]
    for want in ("import_chip", "pool", "server_start", "warmup", "window",
                 "drain", "comparison", "reduce", "total"):
        assert want in phases


def test_no_chip_means_no_result_line():
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(__import__("os").environ, JAX_PLATFORMS="cpu"),
        cwd=str(ROOT))
    assert p.returncode != 0
    assert "NoChip" in p.stderr
    assert not any('"correct"' in x for x in p.stdout.splitlines())


def test_deadline_prints_a_failing_line_and_exits():
    code = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "from benchmarks import run as br\n"
        "r = br.Run(); r.attempted = 7; r.phase = 'stubbed-sleep'\n"
        "br.start_deadline(r, 0.3)\n"
        "time.sleep(30)\n" % str(ROOT))
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert time.monotonic() - t0 < 20
    assert p.returncode == 3
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["attempted"] == 7
    assert line["deadline_phase"] == "stubbed-sleep"


def test_a_sound_run_is_correct_and_a_flipped_verdict_is_not(monkeypatch):
    from benchmarks import run as br
    from jepsen_jgroups_raft_tpu.service.request import CheckRequest

    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", "unused")

    def one_run():
        run = br.Run()
        try:
            return br.run_cell(args_for(CELLS[0]), run)
        finally:
            run.stop_children()

    sound = one_run()
    assert sound["correct"] is True
    assert sound["compared"]["verdict_mismatches"]["value"] == 0
    assert sound["failed"] == 0 and sound["attempted"] > 0

    finish = CheckRequest.finish

    def flipping(self, status, results=None, *a, **kw):
        # the timed path broken underneath: a verdict altered where the
        # request gets it
        if results and isinstance(results[0].get("valid?"), bool):
            results = [dict(results[0],
                            **{"valid?": not results[0]["valid?"]})] \
                + list(results[1:])
        return finish(self, status, results, *a, **kw)

    monkeypatch.setattr(CheckRequest, "finish", flipping)
    broken = one_run()
    assert broken["correct"] is False
    assert broken["compared"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    """`--control` through the harness: the control's verdicts on the
    window's own rows stand where the served ones stood, and the run
    ends `correct: false` on the number that an honest run holds at 0."""
    from benchmarks import run as br

    monkeypatch.setenv("JGRAFT_AUTOTUNE_STORE", "unused")
    _, config, _ = mf.cell(ROOT, MANIFEST, cell)
    run = br.Run()
    try:
        line = br.run_cell(args_for(cell, control=config["control"],
                                    seed=2**31 + 7), run)
    finally:
        run.stop_children()
    assert line["control"] == config["control"]
    assert line["correct"] is False
    assert line["compared"]["verdict_mismatches"]["value"] > 0
    assert line["compared"]["verdict_mismatches"]["limit"] == 0
    # nothing else failed it: the served path itself was sound
    others = {k: v for k, v in line["compared"].items()
              if k not in ("verdict_mismatches", "rows_compared")}
    assert all(v["value"] == 0 for v in others.values())
    assert line["failed"] == 0


# ------------------------------------------------- loop, tally, decide


class StuckClient:
    """Acknowledges, and then never has a verdict."""

    def __init__(self):
        self.closed = False

    def result(self, request_id, wait_s=None):
        time.sleep(min(wait_s or 0.05, 0.05))
        return {"id": request_id, "status": "running"}

    def close(self):
        self.closed = True


def test_the_drain_cap_ends_a_stuck_request_as_not_terminal():
    loop = mf.load_module(ROOT, "loops", "closed")
    control = loop.Control()
    records = []
    pool = iter([(0, ["h0"]), (1, ["h1"])])
    lock = threading.Lock()

    def take():
        with lock:
            return next(pool, None)

    now = time.monotonic()
    control.t_end, control.drain_until = now + 0.2, now + 0.6
    client = StuckClient()
    t0 = time.monotonic()
    loop.run(n_clients=1, make_client=lambda: client,
             send=lambda cl, hs: {"id": "r1", "status": "queued"},
             take_request=take, control=control, on_record=records.append)
    assert time.monotonic() - t0 < 5
    assert [r["status"] for r in records] == ["not_terminal"]
    assert client.closed


def rec(i, t_submit, t_done, status="done", n=4, undecided=0, **kw):
    return dict({"i": i, "n": n, "status": status, "t_submit": t_submit,
                 "t_ack": t_submit + 0.01, "t_done": t_done,
                 "undecided": undecided, "cached": False,
                 "degraded": False}, **kw)


def test_tally_counts_stuck_refused_and_undecided_as_failed():
    from benchmarks.run import tally

    records = [rec(0, 10.5, 11.5), rec(1, 11.0, 12.0),
               rec(2, 12.0, 40.0, status="not_terminal"),
               rec(3, 13.0, 13.1, status="refused"),
               rec(4, 14.0, 15.0, undecided=1),
               rec(5, 9.0, 10.2),       # sent before the window
               rec(6, 19.5, 21.0)]      # done after it
    t = tally(records, 10.0, 20.0, 10.0)
    assert t["attempted"] == 6 and t["failed"] == 3
    assert len(t["in_window"]) == 4
    assert t["values"]["verdict_p50_ms"] == pytest.approx(1000.0)
    # request 6 was in flight at the window's end and ended in the drain
    assert t["drained_p50_ms"] == pytest.approx(1500.0)


def test_rate_counts_a_request_by_the_share_of_its_life_in_the_window():
    from benchmarks.run import histories_in_window

    records = [rec(0, 10.0, 12.0, n=32),            # whole
               rec(1, 9.0, 11.0, n=32),             # second half
               rec(2, 19.0, 23.0, n=32),            # first quarter
               rec(3, 12.0, 13.0, n=32, status="failed"),
               rec(4, 30.0, 31.0, n=32)]            # outside
    assert histories_in_window(records, 10.0, 20.0) == pytest.approx(
        32 + 16 + 8)


@pytest.mark.parametrize("field,bad", [
    ("mismatches", 1), ("cached", True), ("degraded", True),
    ("journal_errors", 1), ("submitted", 9), ("rows_compared", 0)])
def test_each_compared_number_can_fail_the_run(field, bad):
    from benchmarks.run import decide

    compared = [{"mismatches": 0, "rows_compared": 5}]
    records = [rec(0, 1.0, 2.0)]
    d_stats = {"cache_hits": 0, "degraded_batches": 0, "journal_errors": 0,
               "submitted": 3, "journal_appends": 6}
    assert decide(compared, records, d_stats)[0] is True
    if field in compared[0]:
        compared[0][field] = bad
    elif field in records[0]:
        records[0][field] = bad
    else:
        d_stats[field] = bad
    ok, out = decide(compared, records, d_stats)
    assert ok is False
    assert all("limit" in v or "at_least" in v for v in out.values())
