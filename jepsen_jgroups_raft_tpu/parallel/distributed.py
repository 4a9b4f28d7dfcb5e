"""Multi-host distributed checking runtime (ISSUE 7 tentpole).

The reference scales its SUT over multiple hosts with JGroups (SURVEY.md
§5.8); the checker backend's multi-host analogue is the JAX distributed
runtime: one process per host, all of the slice's chips visible through
one global device list, the batch axis sharded over every device — ICI
inside a host/slice, DCN between hosts. The harness stays a single
control process (like the reference's control node); only verification
fans out.

Three layers live here, smallest dependency first:

* **Runtime** — `maybe_init_distributed` initializes `jax.distributed`
  from the standard cluster env (``JAX_COORDINATOR_ADDRESS`` /
  ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``), parsed defensively (a
  malformed value warns, records a degrade note, and returns False —
  it must never crash an importer or CLI the way a bare ``int()``
  would), or — opt-in via ``JGRAFT_DISTRIBUTED_AUTODETECT=1`` — from an
  autodetectable cluster environment (bare ``jax.distributed
  .initialize()``, which recognizes SLURM/GKE-style launchers; the
  attempt is wrapped, a non-cluster host just returns False). No-op and
  False on single-host runs, idempotent everywhere.

* **Exchange** — the cross-process transport for host-side values
  (verdict codes, counters). Two flavors, picked by capability: real
  multi-host accelerator pods run device collectives over the global
  mesh (`check_batch_global` below — the pjit/NamedSharding pattern of
  SNIPPETS [1]–[3]); hosts whose backend cannot run multiprocess
  computations (this box's CPU backend: jaxlib answers
  "Multiprocess computations aren't implemented on the CPU backend")
  use the *coordination service* — the gRPC KV store + barriers every
  `jax.distributed` cluster already carries (`exchange_bytes` /
  `exchange_i64` / `barrier`). `collectives_supported()` probes which
  world this is, once. Exchange calls are SPMD-disciplined: every
  process must make the same sequence of calls (each call burns one
  slot of a shared tag counter and two barriers).

* **Sharded wavefront** — `run_sharded` is the seam
  `checker.linearizable.check_encoded` routes through when the process
  is part of a cluster: rows are split into per-process contiguous
  shards (`shard_bounds`, boundaries aligned to the host's mesh
  fan-out via `placement_granularity` — the autotuner's `mesh_fanout`
  plan dimension feeding cross-host placement), each process runs the
  ordinary chunked wavefront on ONLY its shard (per-host packing: its
  event tensors are born on its shard and its host CPU does only its
  share of the encode/pack work), and the per-row verdict codes are
  exchanged so every process returns the full batch's verdicts.
  Soundness is the batch-axis independence argument of
  doc/checker-design.md §8, restated for hosts in §10: a row's verdict
  is a function of that row's event stream alone, so the shard-local
  scan is bitwise-identical to the single-process scan of the same
  rows (pinned by tests/test_distributed.py).
"""

from __future__ import annotations

import itertools
import logging
import os
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..platform import env_int, note_degraded

_log = logging.getLogger(__name__)

#: Wire timeout for the coordination-service exchange (barriers + KV
#: gets). Generous: a barrier waits for the SLOWEST shard's check, and
#: an escalated CPU-ladder row can take minutes.
DEFAULT_TIMEOUT_MS = 600_000


def distributed_enabled() -> bool:
    """Master gate for the distributed wavefront seam.
    ``JGRAFT_DISTRIBUTED=0`` pins single-process behavior even inside a
    cluster (the ablation / escape hatch); parsed defensively."""
    return env_int("JGRAFT_DISTRIBUTED", 1, minimum=0) != 0


def exchange_timeout_ms() -> int:
    return env_int("JGRAFT_DISTRIBUTED_TIMEOUT_MS", DEFAULT_TIMEOUT_MS,
                   minimum=1_000)


# ---------------------------------------------------------------- runtime


def parse_cluster_env() -> Optional[Tuple[str, int, int]]:
    """(coordinator, n_processes, process_id) from the standard JAX
    cluster env, or None when absent OR malformed. Malformed values
    warn and record a degrade note instead of raising: a typo'd
    ``JAX_NUM_PROCESSES`` used to surface as a ``ValueError`` out of
    ``int()`` at CLI start — the single-host degrade must be
    loud, not fatal."""
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc_raw = os.environ.get("JAX_NUM_PROCESSES")
    if not coord or not nproc_raw:
        return None
    pid_raw = os.environ.get("JAX_PROCESS_ID", "0")
    try:
        nproc = int(nproc_raw.strip())
        pid = int(pid_raw.strip() or "0")
    except ValueError:
        note = (f"cluster env malformed (JAX_NUM_PROCESSES={nproc_raw!r}, "
                f"JAX_PROCESS_ID={pid_raw!r}) — running single-process")
        _log.warning("distributed: %s", note)
        note_degraded(note)
        return None
    if nproc < 1 or not 0 <= pid < nproc:
        note = (f"cluster env inconsistent (num_processes={nproc}, "
                f"process_id={pid}) — running single-process")
        _log.warning("distributed: %s", note)
        note_degraded(note)
        return None
    return coord, nproc, pid


def maybe_init_distributed() -> bool:
    """Initialize `jax.distributed` when a cluster environment is
    present. Returns True iff the distributed runtime is (now)
    initialized. Idempotent; safe from CLI entry points.

    Resolution order: the explicit env triple (defensively parsed —
    see `parse_cluster_env`); then, ONLY when
    ``JGRAFT_DISTRIBUTED_AUTODETECT=1``, a bare
    ``jax.distributed.initialize()`` whose launcher autodetection
    covers SLURM/GKE-style clusters (off by default: the bare call is
    a no-op ValueError on a plain host, but autodetection mis-firing
    inside an unrelated batch scheduler would wedge single-host runs
    waiting for phantom peers). Every failure path returns False with
    a warning + degrade note rather than raising."""
    import jax

    if is_initialized():
        return True
    env = parse_cluster_env()
    if env is not None:
        coord, nproc, pid = env
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=nproc, process_id=pid)
        except Exception as e:  # unreachable coordinator, double init race
            note = (f"jax.distributed.initialize failed for "
                    f"{coord} ({type(e).__name__}: {e}) — "
                    "running single-process"[:300])
            _log.warning("distributed: %s", note)
            note_degraded(note)
            return False
        return True
    if env_int("JGRAFT_DISTRIBUTED_AUTODETECT", 0, minimum=0):
        try:
            jax.distributed.initialize()
            return True
        except Exception as e:
            _log.warning("distributed: cluster autodetection found no "
                         "cluster (%s: %s) — running single-process",
                         type(e).__name__, str(e)[:200])
            return False
    return False


def is_initialized() -> bool:
    """Whether the distributed runtime is already up. jax grew a public
    `jax.distributed.is_initialized` only after this pin's 0.4.x, so
    fall back to the coordination-service client's existence (every
    initialized process holds one) — re-calling initialize on an
    already-up runtime raises, which the idempotency contract of
    `maybe_init_distributed` must absorb without a spurious degrade
    note."""
    import jax

    probe = getattr(jax.distributed, "is_initialized", None)
    if probe is not None:
        try:
            return bool(probe())
        except Exception as e:  # noqa: BLE001 — fall to the client probe
            _log.debug("distributed: is_initialized probe failed "
                       "(%s: %s); falling back to client check",
                       type(e).__name__, e)
    try:
        from jax._src.distributed import global_state
    except ImportError:
        return False
    return getattr(global_state, "client", None) is not None


def process_count() -> int:
    """Processes in the cluster; 1 when uninitialized/single-host."""
    try:
        import jax

        return int(jax.process_count())
    except Exception as e:  # noqa: BLE001 — broken jax: act single-host
        _log.debug("distributed: process_count unavailable (%s: %s); "
                   "assuming single-process", type(e).__name__, e)
        return 1


def process_index() -> int:
    try:
        import jax

        return int(jax.process_index())
    except Exception as e:  # noqa: BLE001 — broken jax: act single-host
        _log.debug("distributed: process_index unavailable (%s: %s); "
                   "assuming process 0", type(e).__name__, e)
        return 0


def wavefront_active() -> bool:
    """Whether the checker should run the sharded distributed wavefront:
    a multi-process runtime is up and the env gate allows it."""
    return distributed_enabled() and process_count() > 1


# --------------------------------------------------------------- sharding


def shard_bounds(n_rows: int, n_shards: Optional[int] = None,
                 index: Optional[int] = None,
                 granularity: int = 1) -> Tuple[int, int]:
    """Contiguous [lo, hi) row range of shard `index` out of `n_shards`
    over `n_rows` rows (defaults: this process in the cluster).

    Boundaries are the balanced cuts ``i·n // n_shards`` rounded DOWN
    to a multiple of `granularity` (the last boundary stays exactly
    `n_rows`): with granularity = the host's mesh fan-out, every
    non-final shard's row count divides evenly over its local device
    mesh, so the shard-local row buckets match the shapes the
    single-process path compiles. Shards can be empty when
    n_rows < n_shards — callers must tolerate a zero-row shard."""
    if n_shards is None:
        n_shards = process_count()
    if index is None:
        index = process_index()
    if not 0 <= index < n_shards:
        raise ValueError(f"shard index {index} out of range {n_shards}")
    g = max(1, int(granularity))

    def cut(i: int) -> int:
        if i >= n_shards:
            return n_rows
        return min(n_rows, (i * n_rows // n_shards) // g * g)

    return cut(index), cut(index + 1)


def placement_granularity() -> int:
    """Row granularity of the cross-host split: the host's mesh fan-out
    (`parallel.mesh.chunk_sharding` — the same quantity that
    outer-bounds the autotuner's `mesh_fanout` plan dimension), so each
    host's shard splits evenly over its local devices. 1 when fan-out
    is gated off or the host has one device."""
    from .mesh import chunk_sharding

    sharding = chunk_sharding()
    mesh = getattr(sharding, "mesh", None)
    return int(mesh.size) if mesh is not None else 1


# --------------------------------------------------------------- exchange

#: Exchange sequence counter. Every process makes the same sequence of
#: exchange/barrier calls (SPMD discipline — documented contract of
#: `run_sharded`), so a per-process counter yields
#: cluster-identical tags without any coordination of its own.
_SEQ = itertools.count()


def _coord_client():
    """The coordination-service client `jax.distributed` brought up —
    the gRPC KV store + barrier transport. jax's public surface does
    not re-export it, so this reaches into jax._src (stable across the
    0.4.x line; guarded so a rename degrades loudly, not cryptically)."""
    try:
        from jax._src.distributed import global_state
    except ImportError as e:  # pragma: no cover - jax internals moved
        raise RuntimeError(
            "jax coordination-service client unavailable "
            f"({type(e).__name__}: {e}); cannot exchange across "
            "processes") from e
    client = getattr(global_state, "client", None)
    if client is None:
        raise RuntimeError("jax.distributed is not initialized — no "
                           "coordination-service client to exchange through")
    return client


def barrier(name: str) -> None:
    """Cluster-wide barrier over the coordination service (works on
    every backend — no device collective involved)."""
    _coord_client().wait_at_barrier(f"jgraft/b/{name}", exchange_timeout_ms())


def exchange_bytes(payload: bytes, tag: Optional[str] = None) -> List[bytes]:
    """All-gather one bytes payload per process via the coordination
    service's KV store: set own key, barrier, read every key, barrier,
    then process 0 deletes the keys (a long-lived daemon must not grow
    the coordinator's store without bound). Returns the payloads in
    process order. Every process must call this the same number of
    times in the same order (the shared tag counter and the two
    barriers both assume it).

    Wire format: base64 through the STRING KV API, with one framing
    byte so the stored value is never empty. Both quirks are
    load-bearing on the pinned jaxlib (0.4.36, reproduced): the
    ``*_bytes`` KV variants SEGFAULT the interpreter outright, and an
    empty shard's payload (legal — `shard_bounds` granularity rounding
    can produce a zero-row shard) must still round-trip."""
    import base64

    client = _coord_client()
    n, pid = process_count(), process_index()
    tag = tag or f"x{next(_SEQ)}"
    timeout = exchange_timeout_ms()
    base = f"jgraft/kv/{tag}"
    wire = base64.b64encode(b"\x01" + payload).decode("ascii")
    client.key_value_set(f"{base}/{pid}", wire)
    client.wait_at_barrier(f"{base}/set", timeout)
    out = [base64.b64decode(
        client.blocking_key_value_get(f"{base}/{i}", timeout))[1:]
        for i in range(n)]
    client.wait_at_barrier(f"{base}/got", timeout)
    if pid == 0:
        for i in range(n):
            try:
                client.key_value_delete(f"{base}/{i}")
            except Exception as e:  # noqa: BLE001 — cleanup only; the
                # values were already read by every process
                _log.debug("distributed: kv cleanup of %s/%d failed "
                           "(%s: %s)", base, i, type(e).__name__, e)
    return out


def exchange_i64(arr: Sequence[int], tag: Optional[str] = None) \
        -> List[np.ndarray]:
    """All-gather one int64 vector per process (verdict codes, counter
    totals). Shards may contribute different lengths (uneven row
    shards)."""
    payload = np.asarray(arr, dtype="<i8").tobytes()
    return [np.frombuffer(raw, dtype="<i8") for raw
            in exchange_bytes(payload, tag=tag)]


# ------------------------------------------------------ sharded wavefront

#: Verdict wire codes (checker.base VALID/INVALID/UNKNOWN).
_CODE_INVALID, _CODE_VALID, _CODE_UNKNOWN = 0, 1, 2


def _verdict_code(result: dict) -> int:
    from ..checker.base import INVALID, VALID

    v = result.get("valid?")
    if v is VALID:
        return _CODE_VALID
    if v is INVALID:
        return _CODE_INVALID
    return _CODE_UNKNOWN


def _remote_result(code: int, owner: int) -> dict:
    """Result stub for a row checked by another process: the verdict is
    exact (it rode the wire); without a shared result store the
    explanation detail (witness, timing, kernel tag) stays on the
    owning host's artifacts. With a store configured (ISSUE 11
    tentpole (d)) `run_sharded` upgrades the stub from the owning
    host's published detail record."""
    from ..checker.base import INVALID, UNKNOWN, VALID

    valid = (VALID if code == _CODE_VALID
             else INVALID if code == _CODE_INVALID else UNKNOWN)
    return {"valid?": valid, "algorithm": "jax",
            "kernel": "remote-shard", "process": owner,
            "decided-tier": "remote-shard"}


def _detail_exchange(model, algorithm: str):
    """(store, key_fn) for the cross-host result-detail exchange, or
    (None, None) — inert unless JGRAFT_RESULT_STORE (or the cluster
    dir) names a directory every host shares, and only usable when the
    caller supplied the model the detail keys hash over."""
    if model is None:
        return None, None
    try:
        from ..service.store import detail_fingerprint, detail_store
    except ImportError as e:  # pragma: no cover — partial checkout
        _log.debug("distributed: detail store unavailable (%s)", e)
        return None, None
    store = detail_store()
    if store is None:
        return None, None
    return store, lambda enc: detail_fingerprint(model, algorithm, enc)


def run_sharded(encs: Sequence, check_local: Callable[[list], List[dict]],
                granularity: Optional[int] = None, model=None,
                algorithm: str = "auto") -> List[dict]:
    """The distributed wavefront driver: check only this process's row
    shard through `check_local` (the ordinary single-process pass —
    chunked wavefront, escalation ladder, everything), then exchange
    per-row verdict codes so every process returns the FULL batch's
    results in submission order. Local rows carry their full result
    dicts; remote rows carry `_remote_result` stubs — unless a shared
    result store is configured (`model` given + JGRAFT_RESULT_STORE /
    the cluster dir), in which case each process publishes its local
    rows' full details before the verdict exchange and reads the
    owners' details for remote rows after it (ISSUE 11 tentpole (d):
    witnesses and minimized counterexamples follow the verdict). The
    exchange's barriers order every publish before every read, so a
    shared filesystem needs no extra synchronization; a missing or
    degraded detail record degrades that row to the PR 7 stub, never
    to an error.

    SPMD contract: every process must call with the same batch (same
    row count, same order) — the `check` CLI satisfies it
    by construction (same inputs, same code path). Placement: shard
    boundaries align to `placement_granularity` so each host's rows
    split evenly over its local mesh."""
    n, pid = process_count(), process_index()
    if n <= 1:  # no cluster: the "shard" is the whole batch, no wire
        return check_local(list(encs))
    g = placement_granularity() if granularity is None else granularity
    lo, hi = shard_bounds(len(encs), n, pid, granularity=g)
    local = check_local(list(encs[lo:hi]))
    store, key_fn = _detail_exchange(model, algorithm)
    if store is not None:
        for enc, res in zip(encs[lo:hi], local):
            if isinstance(res, dict) and "valid?" in res:
                # degraded rows are refused by the store's own gate
                store.put_detail(key_fn(enc), res)
    codes = exchange_i64([_verdict_code(r) for r in local])
    results: List[dict] = []
    for p in range(n):
        plo, phi = shard_bounds(len(encs), n, p, granularity=g)
        if p == pid:
            results.extend(local)
        else:
            if len(codes[p]) != phi - plo:
                raise RuntimeError(
                    f"shard {p} exchanged {len(codes[p])} verdicts for "
                    f"{phi - plo} rows — processes disagree on the batch "
                    "(the SPMD contract of run_sharded is broken)")
            for row, c in zip(range(plo, phi), codes[p]):
                stub = _remote_result(int(c), p)
                if store is not None:
                    detail = store.get_detail(key_fn(encs[row]))
                    if detail is not None \
                            and detail.get("valid?") == stub["valid?"]:
                        # the full verdict rode the store; keep the
                        # owner attribution on top of it
                        detail["process"] = p
                        detail["detail-source"] = "result-store"
                        stub = detail
                results.append(stub)
    return results


# ------------------------------------------------- global-mesh collectives

_COLLECTIVES: Optional[bool] = None


def collectives_supported() -> bool:
    """Whether this backend can run ONE computation spanning every
    process's devices (real multi-host accelerator pods: yes; this
    box's CPU backend: jaxlib refuses with "Multiprocess computations
    aren't implemented"). Probed once with a tiny global-mesh psum —
    itself a collective, so every process must reach the probe
    together (same SPMD discipline as the exchange layer). False on
    single-process runs (nothing to span)."""
    global _COLLECTIVES
    if _COLLECTIVES is not None:
        return _COLLECTIVES
    if process_count() <= 1:
        return False
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        mesh = global_mesh()
        axis = mesh.axis_names[0]
        ones = np.ones((len(jax.local_devices()),), dtype=np.int32)
        garr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(axis)), ones)
        total = jax.jit(
            lambda x: x.sum(),
            out_shardings=NamedSharding(mesh, P()))(garr)
        _COLLECTIVES = int(total) == len(jax.devices())
    except Exception as e:  # noqa: BLE001 — any refusal means "route
        _log.info("distributed: global-mesh collectives unavailable "
                  "(%s: %s) — exchanging via the coordination service",
                  type(e).__name__, str(e)[:200])
        _COLLECTIVES = False
    return _COLLECTIVES


def global_mesh(axis_name: Optional[str] = None):
    """1-D mesh over EVERY process's devices, in global device order
    (the call-site mesh of the SNIPPETS [1]–[3] pattern)."""
    import jax
    from jax.sharding import Mesh

    from .mesh import BATCH_AXIS

    return Mesh(np.asarray(jax.devices()), (axis_name or BATCH_AXIS,))


def check_batch_global(model, encs: Sequence) -> Tuple[int, int]:
    """One logical dense check sharded over the GLOBAL mesh — the
    TPU-pod execution shape of the tentpole: per-host packing
    (`history.packing.pack_*_batch_shard` — each process compacts and
    fills ONLY its row shard at batch-globally agreed shapes, so the
    event tensor is born on its shard), `NamedSharding` assembly via
    `jax.make_array_from_process_local_data`, and the sharded dense
    kernel's verdict `psum` riding DCN. Returns the global
    (n_valid, n_unknown) counts, identical on every process.

    Requires `collectives_supported()`; hosts without multiprocess
    computations (CPU meshes on this jax) must use `run_sharded`,
    whose exchange rides the coordination service instead."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..history.packing import (macro_events_on, pack_batch_shard,
                                   pack_macro_batch_shard)
    from ..ops.dense_scan import dense_plan
    from .mesh import sharded_dense_checker

    if not collectives_supported():
        raise RuntimeError(
            "global-mesh collectives unsupported on this backend — "
            "use run_sharded (coordination-service exchange) instead")
    encs = list(encs)
    plan = dense_plan(model, encs)
    if plan is None:
        raise ValueError("check_batch_global needs a dense-eligible batch "
                         "(run_sharded handles the general routing)")
    mesh = global_mesh()
    axis = mesh.axis_names[0]
    n, pid = process_count(), process_index()
    # Pad the batch so it splits exactly: a multiple of the global
    # device count is automatically a multiple of the (equal-size)
    # per-process device groups. Pad rows are EV_PAD no-op histories.
    d_global = int(mesh.devices.size)
    B = len(encs)
    B_pad = -(-B // d_global) * d_global
    lo, hi = shard_bounds(B_pad, n, pid)
    pack = (pack_macro_batch_shard if macro_events_on()
            else pack_batch_shard)
    batch = pack(encs, pid, n, n_rows=B_pad)
    local_ev = batch["events"]
    val_of = np.zeros((hi - lo,) + plan.val_of.shape[1:],
                      dtype=plan.val_of.dtype)
    real = np.zeros((hi - lo,), dtype=bool)
    n_real = max(0, min(hi, B) - lo)
    val_of[:n_real] = plan.val_of[lo:lo + n_real]
    val_of[n_real:] = plan.val_of[:1]
    real[:n_real] = True
    g_events = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis, None, None)), local_ev)
    g_val = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis, None)), val_of)
    g_real = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis)), real)
    fn = sharded_dense_checker(model, mesh, plan.kind, plan.n_slots,
                               plan.n_states, axis,
                               macro_p=batch.get("macro_p"))
    _, _, n_valid, n_unknown = fn(g_events, g_val, g_real)
    # psum outputs are replicated scalars — addressable on every host.
    return int(n_valid), int(n_unknown)  # lint: allow(host-sync)
