"""CI-sized invocations of the checked-in soak harnesses (scripts/).

The full campaigns (round-3 scale: 16k+ differential histories, 110 hell
runs) are operator-invoked — BASELINE.md cites the exact commands; these
tests pin that the harnesses stay runnable and sound at small scale.
Select just these with `pytest -m soak`.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from jepsen_jgroups_raft_tpu.platform import cpu_subprocess_env

pytestmark = [pytest.mark.slow, pytest.mark.soak]

REPO = Path(__file__).resolve().parents[1]


def _run(script, *args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / script), *args],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env=cpu_subprocess_env())


def test_soak_differential_smoke():
    out = _run("soak_differential.py", "--count", "120", "--seed", "7",
               "--strict-unknown")
    assert out.returncode == 0, out.stdout + out.stderr
    assert '"mismatches": 0' in out.stdout


def test_soak_hell_smoke():
    out = _run("soak_hell.py", "--runs", "1", "--time-limit", "6",
               "--seed", "700")
    assert out.returncode == 0, out.stdout + out.stderr
    assert '"failures": 0' in out.stdout
