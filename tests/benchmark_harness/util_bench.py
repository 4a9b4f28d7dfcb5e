"""Shared helpers of the benchmark-harness tests. Nothing here touches
JAX at import."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root, *args, env=None, timeout=240):
    """Run `benchmarks/run.py --rehearse` of the checkout at `root` in a
    child on the CPU; returns (return code, stdout lines, stderr)."""
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    p = subprocess.run(
        [sys.executable, str(Path(root) / "benchmarks" / "run.py"),
         "--rehearse", *args],
        capture_output=True, text=True, timeout=timeout, env=e,
        cwd=str(root))
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def last_json(lines):
    return json.loads(lines[-1])
