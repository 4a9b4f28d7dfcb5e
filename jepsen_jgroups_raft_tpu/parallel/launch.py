"""Local multi-process launcher for the distributed checking topology.

One process per "host", coordinated over localhost gRPC — the CPU-mesh
recipe of ISSUE 7: on a TPU-less box the N-process topology runs with
``--xla_force_host_platform_device_count`` splitting the virtual CPU
devices between processes, exercising exactly the runtime
(`jax.distributed` init, shard-local packing, coordination-service
verdict exchange) a real pod uses — on a pod the operator instead runs
the same command once per host with the standard cluster env set (see
doc/running.md "Multi-host checking").

Consumers: ``bench.py --distributed N`` (the parent side lives here so
the subprocess/socket lifetimes sit inside the lint scan scope),
``scripts/ab_distributed.py``, and tests/test_distributed.py.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from ..platform import cpu_subprocess_env, env_int


def free_coordinator_port() -> int:
    """Ephemeral localhost port for the cluster coordinator."""
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def cluster_child_env(process_id: int, n_processes: int, port: int,
                      vdevs: Optional[int] = None,
                      extra: Optional[Dict[str, str]] = None) -> dict:
    """Environment for one child of the local CPU-mesh topology: the
    standard JAX cluster triple over a localhost coordinator, the CPU
    pin (`platform.cpu_subprocess_env`), and an optional per-process
    virtual device count
    (`vdevs`, also exported as ``JGRAFT_BENCH_VDEVS`` so bench.py's
    cpu pin respects the split instead of raising it back to 8)."""
    env = cpu_subprocess_env()
    # The child pins its own platform/device count; an inherited
    # XLA_FLAGS count would override it (pin_cpu only ever raises).
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": str(n_processes),
        "JAX_PROCESS_ID": str(process_id),
    })
    if vdevs:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={vdevs}"
        env["JGRAFT_BENCH_VDEVS"] = str(vdevs)
    if extra:
        env.update(extra)
    return env


def launch_local_cluster(n_processes: int, command: Sequence[str],
                         vdevs: Optional[int] = None,
                         env_extra: Optional[Dict[str, str]] = None,
                         timeout_s: float = 1800.0) -> List[Tuple[int, str]]:
    """Run `command` as an N-process localhost cluster; returns one
    (returncode, combined-output) pair per process, in process order.
    Children that outlive `timeout_s` (wedged coordinator, a peer
    crashing out of a barrier) are killed with the timeout noted in
    their output — the launcher never hangs its caller, and no child
    survives this call (kill + reap on every path)."""
    port = free_coordinator_port()
    procs: List[subprocess.Popen] = []
    outs: List[Tuple[int, str]] = []
    try:
        for pid in range(n_processes):
            env = cluster_child_env(pid, n_processes, port, vdevs,
                                    env_extra)
            procs.append(subprocess.Popen(
                list(command), env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                out = (out or "") + f"\n[killed: no exit in {timeout_s:.0f}s]"
            outs.append((p.returncode, out))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def run_distributed_bench(argv: Sequence[str]) -> int:
    """Parent side of ``bench.py --distributed N``: strip the flag,
    spawn the N-process CPU-mesh topology running the SAME bench argv,
    and forward process 0's output (the JSON-line contract — every
    process computes the globally merged counts, so one emitter
    suffices). The children's intended platform defaults to cpu (this
    launcher IS the CPU-mesh recipe; a pod runs bench.py per host
    without it), so the degraded-platform gate stays quiet unless the
    operator pinned something else. Exit: 0 when every process exited
    0, else 1 (with the failing processes' output tails on stderr)."""
    argv = list(argv)
    i = argv.index("--distributed")
    try:
        n = int(argv[i + 1])
        if n < 1:
            raise ValueError(n)
    except (IndexError, ValueError):
        print('{"metric": "histories_per_sec", "value": 0.0, '
              '"unit": "hist/s", "vs_baseline": 0.0, '
              '"error": "--distributed needs a positive process count"}',
              flush=True)
        return 2
    child_argv = [sys.executable, os.path.abspath(argv[0])] \
        + argv[1:i] + argv[i + 2:]
    vdevs = env_int("JGRAFT_DISTRIBUTED_VDEVS", max(1, 8 // n), minimum=1)
    extra: Dict[str, str] = {}
    if not os.environ.get("JGRAFT_BENCH_PLATFORM"):
        extra["JGRAFT_BENCH_PLATFORM"] = "cpu"
    outs = launch_local_cluster(n, child_argv, vdevs=vdevs, env_extra=extra)
    rc0, out0 = outs[0]
    sys.stdout.write(out0)
    sys.stdout.flush()
    failed = [pid for pid, (rc, _) in enumerate(outs) if rc != 0]
    for pid in failed:
        print(f"# distributed worker {pid} exited "
              f"{outs[pid][0]}:\n{outs[pid][1][-2000:]}",
              file=sys.stderr, flush=True)
    return 0 if not failed else 1
