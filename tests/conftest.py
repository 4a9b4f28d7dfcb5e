"""Test env: force JAX onto a virtual 8-device CPU mesh so sharding and
multi-chip paths are exercised without TPU hardware (the driver separately
dry-runs multi-chip via __graft_entry__.dryrun_multichip).

`pin_cpu` sets JAX_PLATFORMS=cpu and updates jax.config, so no
accelerator backend ever initializes under pytest; XLA_FLAGS (the
device count) must be set before the CPU backend initializes, which it
hasn't at conftest import time.
"""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jepsen_jgroups_raft_tpu.platform import pin_cpu  # noqa: E402

pin_cpu(8)

# Autotune off by default under pytest: the measured plans are
# host-dependent (exactly what the fingerprint keying is FOR), so tests
# must be deterministic w.r.t. them; autotune's own tests opt back in
# with monkeypatched env + a tmp plan store. JGRAFT_AUTOTUNE=0 is the
# documented "today's exact behavior" switch.
os.environ.setdefault("JGRAFT_AUTOTUNE", "0")

# Lin-rung fast path (ISSUE 14) off by default under pytest, same
# stance: with it on, every valid lin-rung row decides as
# greedy-witness on the host and the kernel-path tests (chunk stats,
# coalescing, kernel tags) would never see a launch. Tests of the fast
# path itself (tests/test_lin_fastpath.py, service fast-lane tests)
# opt back in with monkeypatched env. JGRAFT_LIN_FASTPATH=0 is the
# documented force-disable/A-B arm; production default stays ON.
os.environ.setdefault("JGRAFT_LIN_FASTPATH", "0")

# The ISSUE-15 host-path knobs (JGRAFT_ENCODE_VECTOR,
# JGRAFT_CERTIFY_BATCH, JGRAFT_JOURNAL_GROUP_MS) stay at their
# production defaults (ON) here, per the house rule: a knob is pinned
# off in kernel-path suites only when it changes ROUTING those suites
# assert on. These change neither routing nor verdicts — encode output
# is byte-identical, the batch certifier picks an ENGINE inside the
# host certify pass (which JGRAFT_LIN_FASTPATH=0 above already keeps
# out of kernel suites), and group commit only coalesces fsyncs.
# Their differential tests (tests/test_hostpath_turbo.py) pin both
# arms explicitly.


@pytest.fixture(autouse=True)
def _a_bench_run_in_process_starts_its_own_clock(request, monkeypatch):
    """`benchmarks/run.py` counts its deadlines from its own import: a
    process is one run. tests/benchmark_harness also runs `run_cell`
    INSIDE the xdist worker, which imported the module when it
    collected, so those runs had what was left of the pool's 150 s
    after everything the worker ran before them, and passed or failed
    with the length of the suite (PERF.md section 7). Their clock
    starts with their test, as a run's does."""
    run = sys.modules.get("benchmarks.run")
    if run is not None and "benchmark_harness" in request.node.nodeid:
        monkeypatch.setattr(run, "T0", time.monotonic())
