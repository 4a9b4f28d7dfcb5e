"""Local multi-process launcher for the distributed checking topology.

One process per "host", coordinated over localhost gRPC — the CPU-mesh
recipe of ISSUE 7: on a TPU-less box the N-process topology runs with
``--xla_force_host_platform_device_count`` splitting the virtual CPU
devices between processes, exercising exactly the runtime
(`jax.distributed` init, shard-local packing, coordination-service
verdict exchange) a real pod uses — on a pod the operator instead runs
the same command once per host with the standard cluster env set (see
doc/running.md "Multi-host checking").

Consumer: tests/test_distributed.py (the subprocess/socket lifetimes
live here so that they sit inside the lint scan scope).
"""

from __future__ import annotations

import socket
import subprocess
from typing import Dict, List, Optional, Sequence, Tuple

from ..platform import cpu_subprocess_env


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
    virtual device count (`vdevs`)."""
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
