"""CLI layer: the `test` and `serve` commands (reference raft.clj:94-101)."""

import json

import pytest

from jepsen_jgroups_raft_tpu.cli import main
from jepsen_jgroups_raft_tpu.core.serve import _index_html, _run_dirs

#: runs a deployment or a server: seconds each
slow = pytest.mark.slow


@slow
def test_cli_test_command_local_native(tmp_path):
    """Full CLI run over the local native deployment: exit 0 and a
    populated store dir."""
    store = tmp_path / "store"
    rc = main([
        "test", "--workload", "single-register", "--deploy", "local",
        "--node", "n1", "--node", "n2", "--node", "n3",
        "--time-limit", "3", "--quiesce", "0.5", "--rate", "20",
        "--concurrency", "4", "--operation-timeout", "3",
        "--election-ms", "150", "--heartbeat-ms", "50",
        "--repl-timeout-ms", "3000",
        "--store", str(store),
    ])
    runs = _run_dirs(store)
    results = None
    if runs:
        with open(runs[0] / "results.json") as f:
            results = json.load(f)
    assert rc == 0, f"CLI exited {rc}; results={json.dumps(results)[:2000]}"
    assert len(runs) == 1
    assert results["valid?"] is True


@slow
def test_cli_test_command_inmemory_with_nemesis(tmp_path):
    store = tmp_path / "store"
    rc = main([
        "test", "--workload", "counter", "--deploy", "inmemory",
        "--nemesis", "partition",
        "--time-limit", "3", "--quiesce", "0.3", "--rate", "30",
        "--interval", "1", "--concurrency", "4",
        "--operation-timeout", "1", "--store", str(store),
    ])
    assert rc == 0


def test_cli_rejects_unknown_workload():
    with pytest.raises(SystemExit):
        main(["test", "--workload", "nope"])


@pytest.mark.parametrize("argv", [["test"], ["check", "some-run"]],
                         ids=["test", "check"])
def test_algorithm_pallas_is_refused(argv, capsys):
    """The Pallas arm left with PR 50: both parsers that take
    `--algorithm` refuse the name in argparse's own words."""
    with pytest.raises(SystemExit) as e:
        main(argv + ["--algorithm", "pallas"])
    assert e.value.code == 2
    assert "invalid choice: 'pallas'" in capsys.readouterr().err


@slow
def test_serve_index_lists_runs(tmp_path):
    run = tmp_path / "store" / "t" / "20260729T000000"
    run.mkdir(parents=True)
    (run / "results.json").write_text(json.dumps({"valid?": True}))
    (run / "history.jsonl").write_text("")
    bad = tmp_path / "store" / "t" / "20260729T000001"
    bad.mkdir(parents=True)
    (bad / "results.json").write_text(json.dumps({"valid?": False}))
    page = _index_html(tmp_path / "store")
    assert "20260729T000000" in page and "valid" in page
    assert "INVALID" in page  # the failing run is flagged
    assert "history.jsonl" in page


@slow
def test_serve_http_end_to_end(tmp_path):
    """The results server over real HTTP: index lists a recorded run
    with its verdict badge, artifact files are fetchable, and path
    traversal stays confined to the store root (the reference's
    `lein run serve` capability, raft.clj:98-101)."""
    import threading
    import urllib.error
    import urllib.request
    from functools import partial
    from http.server import ThreadingHTTPServer

    from jepsen_jgroups_raft_tpu.core.serve import _Handler

    d = tmp_path / "store" / "demo" / "t1"
    d.mkdir(parents=True)
    (d / "results.json").write_text(json.dumps({"valid?": True}))
    (d / "history.jsonl").write_text("{}\n")
    (tmp_path / "secret.txt").write_text("outside the store root")

    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0), partial(_Handler,
                                  store_root=(tmp_path / "store").resolve()))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{port}"
        index = urllib.request.urlopen(f"{base}/", timeout=5).read().decode()
        assert "demo/t1" in index and "valid" in index
        hist = urllib.request.urlopen(
            f"{base}/demo/t1/history.jsonl", timeout=5).read()
        assert hist == b"{}\n"
        # Traversal attempts must not escape the store root.
        for evil in ("/../secret.txt", "/%2e%2e/secret.txt"):
            try:
                body = urllib.request.urlopen(
                    f"{base}{evil}", timeout=5).read()
                assert b"outside the store root" not in body
            except urllib.error.HTTPError:
                pass  # 404 is the right answer too
    finally:
        httpd.shutdown()
        httpd.server_close()


@slow
def test_cli_weak_election_flag_reverts_to_parity_model(tmp_path):
    """--weak-election must reach the workload (VERDICT r4 #5): the
    default election run checks the cross-node majority model (its
    result carries the `view-count` marker only MajorityLeaderModel
    emits), while the flag reverts to the reference-parity single-client
    model — deterministic markers, not a bet on the random op mix."""
    from jepsen_jgroups_raft_tpu.core.store import load_history

    for flag in (["--weak-election"], []):
        store = tmp_path / ("weak" if flag else "strong")
        rc = main(["test", "-w", "election", "--nemesis", "none",
                   "--time-limit", "3", "--quiesce", "0.5",
                   "--concurrency", "3",
                   "--node", "n1", "--node", "n2", "--node", "n3",
                   "--store", str(store)] + flag)
        assert rc == 0
        run = _run_dirs(store)[0]
        linear = json.load(open(run / "results.json"))["workload"]["linear"]
        assert ("view-count" in linear) is (not flag), (flag, linear)
        if flag:  # parity mode must never generate views ops at all
            fs = {op.f for op in load_history(run)
                  if op.process != "nemesis"}
            assert "views" not in fs, fs
