"""Recorded-history verification: store → load → batch-verify on device.

BASELINE config #3's shape with real data: run actual native-cluster tests
(real raft_server processes, real faults), then reload their persisted
history.jsonl files and verify every per-key sub-history as one device
batch — proving the production path (not synthetic histories) drives the
kernel.
"""

import json

from jepsen_jgroups_raft_tpu.checker.recorded import (check_recorded,
                                                      load_run_histories)
from jepsen_jgroups_raft_tpu.cli import main as cli_main

from test_e2e_native import run_native_test

import pytest  # noqa: E402

pytestmark = pytest.mark.slow


def test_recorded_runs_reverify_as_device_batch(tmp_path, capsys):
    # Two real cluster runs: multi-register (independent keys → many
    # sub-histories) under partitions, counter under kills.
    t1 = run_native_test(tmp_path, "multi-register", "map", "partition",
                         seed=21, rate=60.0, concurrency=8, ops_per_key=25)
    t2 = run_native_test(tmp_path, "counter", "counter", "kill", seed=22)
    assert t1["results"]["valid?"] is True
    assert t2["results"]["valid?"] is True
    d1, d2 = t1["store_dir"], t2["store_dir"]

    # Library path: load + split + batch.
    model, subs, wl = load_run_histories(d1)
    assert wl == "multi-register"
    assert len(subs) >= 3  # several keys hit during the run

    summary = check_recorded([d1, d2], algorithm="auto")
    assert summary["valid?"] is True
    assert summary["runs"] == 2
    assert summary["histories"] == len(subs) + 1  # keys + one counter hist
    assert summary["n-invalid"] == 0
    assert summary["run-verdicts"][d1] is True

    # CLI path over the store root (glob discovery), machine-readable out.
    rc = cli_main(["check", str(tmp_path / "store"), "--platform", "cpu"])
    out = capsys.readouterr().out
    assert rc == 0
    parsed = json.loads(out)
    assert parsed["valid?"] is True
    assert parsed["histories"] == summary["histories"]


def test_recorded_election_run_reverifies(tmp_path):
    """Election stores route through LeaderModel's direct check (it is not
    a frontier-search model — recheck used to crash on such stores)."""
    t = run_native_test(tmp_path, "election", "election", "partition",
                        seed=23)
    assert t["results"]["valid?"] is True
    summary = check_recorded([t["store_dir"]], algorithm="auto")
    assert summary["valid?"] is True
    assert summary["n-invalid"] == 0
    assert summary["n-unknown"] == 0


def test_recorded_check_flags_corruption(tmp_path):
    """A tampered recorded history must turn the re-verification invalid —
    the checker is reading the real bytes, not trusting results.json."""
    t = run_native_test(tmp_path, "single-register", "map", None, seed=23)
    d = t["store_dir"]
    lines = (tmp_path / "x").parent  # noqa: F841  (clarity only)
    hist_file = __import__("pathlib").Path(d) / "history.jsonl"
    ops = [json.loads(ln) for ln in hist_file.read_text().splitlines()]
    # Corrupt the last ok read's observed value.
    for o in reversed(ops):
        if o["type"] == "ok" and o["f"] == "read" and o["value"][1] is not None:
            o["value"][1] = (o["value"][1] + 1) % 5 + 10  # impossible value
            break
    hist_file.write_text("\n".join(json.dumps(o) for o in ops) + "\n")
    summary = check_recorded([d])
    assert summary["valid?"] is False
    assert summary["n-invalid"] >= 1


def test_recorded_election_recheck_keeps_majority_invariant(tmp_path):
    """A store whose live run used --majority-election carries `views`
    ops; re-verification must apply the same cross-node invariant, not
    silently weaken to the inspect-only parity model (round-3 advisor
    finding). Two different leaders reported for one term across nodes
    is invalid on recheck — while with no views ops the model degrades
    to parity and passes."""
    d = tmp_path / "store" / "maj" / "t1"
    d.mkdir(parents=True)
    ops = [
        {"process": 0, "type": "invoke", "f": "views", "value": None,
         "time": 0, "index": 0},
        {"process": 0, "type": "ok", "f": "views",
         "value": [["n1", "n1", 5]], "time": 1, "index": 1},
        {"process": 1, "type": "invoke", "f": "views", "value": None,
         "time": 2, "index": 2},
        {"process": 1, "type": "ok", "f": "views",
         "value": [["n2", "n2", 5]], "time": 3, "index": 3},
    ]
    (d / "history.jsonl").write_text(
        "\n".join(json.dumps(o) for o in ops) + "\n")
    (d / "test.json").write_text(json.dumps({"workload": "election"}))
    summary = check_recorded([d])
    assert summary["valid?"] is False
    assert summary["n-invalid"] == 1
