"""The bench's mid-run wedge watchdog (bench.py): the driver's
end-of-round measurement must never hang forever on a backend that
stops answering AFTER init (2026-07-31: a suite run sat >30 min at zero
CPU — no exception ever surfaced).

These tests drive bench.py as the driver does (a subprocess running the
real CLI) with the watchdog gap shrunk so a legitimate compute span
masquerades as a wedge; the contract under test is "a JSON line always
appears and the process always exits".
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(args, env_extra, timeout=180):
    env = dict(os.environ)
    env.update({"JGRAFT_BENCH_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
               **env_extra)
    return subprocess.run([sys.executable, BENCH, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.soak
def test_watchdog_fires_on_cpu_and_exits():
    """No heartbeat within the gap → the bench must emit an error JSON
    line and EXIT (never hang the driver)."""
    # History synthesis for 800×600 runs long enough that no beat lands
    # within a 2 s gap; the watchdog must fire during it.
    p = _run(["800", "600"], {"JGRAFT_BENCH_WATCHDOG_S": "2"})
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert lines, p.stdout + p.stderr
    last = json.loads(lines[-1])
    assert last["value"] == 0.0
    assert "no progress" in last["error"]
    assert p.returncode == 3, (p.returncode, p.stdout)


@pytest.mark.soak
def test_watchdog_quiet_on_healthy_run():
    """A healthy small run must complete with the watchdog armed at its
    default gap — no spurious firing, real measurement emitted."""
    p = _run(["40", "60"], {})
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert lines, p.stdout + p.stderr
    last = json.loads(lines[-1])
    assert last["value"] > 0, last
    assert "error" not in last, last
    assert p.returncode == 0
