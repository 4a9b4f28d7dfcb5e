"""Batch-sharded checking over a `jax.sharding.Mesh`.

Design (TPU-first, per SURVEY.md §2.4/§5.8): histories are independent
problems, so the batch axis shards cleanly over a 1-D device mesh — the
analogue of the reference's per-key `independent/checker` decomposition
(reference workload/register.clj:106-117), with XLA inserting the
collectives. Two entry points:

  * `sharded_batch_checker` — `shard_map` over the mesh: each device scans
    its local shard with the vmapped frontier kernel (ops/linear_scan.py),
    then a `psum` over the mesh axis aggregates the verdict counts. This is
    the "full step" the driver dry-runs multi-chip.
  * `check_batch_sharded` — convenience wrapper: pads the batch to a
    multiple of the mesh size, lays out the input with `NamedSharding`,
    runs, and unpads.

Multi-host: the same mesh spans hosts transparently once
`jax.distributed.initialize` has run (see `parallel/distributed.py`);
in-slice traffic rides ICI, cross-host batch distribution rides DCN.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..history.packing import pad_batch_bucketed
from ..ops.dense_scan import make_dense_single_checker, scan_unroll
from ..ops.linear_scan import DEFAULT_N_CONFIGS, MAX_SLOTS, make_history_checker

BATCH_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = BATCH_AXIS,
              devices: Optional[list] = None) -> Mesh:
    """1-D mesh over the first `n_devices` of `devices` (default: ALL
    devices — in a multi-process runtime that is every process's
    devices, the global mesh of parallel/distributed.py; pass
    `jax.local_devices()` or use `local_mesh` for a host-local one)."""
    devs = jax.devices() if devices is None else list(devices)
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def local_mesh(n_devices: Optional[int] = None,
               axis_name: str = BATCH_AXIS) -> Mesh:
    """1-D mesh over THIS process's devices only. Identical to
    `make_mesh` single-process; in a cluster it is the host-local ICI
    mesh the sharded wavefront fans out over (host numpy arrays can
    only be `device_put` onto addressable devices — a global-mesh
    sharding would reject them)."""
    return make_mesh(n_devices, axis_name, devices=jax.local_devices())


def launch_fan_out() -> bool:
    """Whether the chunked wavefront scheduler may spread a launch's
    rows over the device mesh (`chunk_sharding`). Default on: each
    group chunk then executes exactly like the legacy `shard_map` path
    — the chunk kernels are wrapped in an explicit batch-axis
    `shard_map` (ops/dense_scan._shard_chunk_fns), every device scans
    its row shard, and the per-event ops need no collectives — which on
    the 2-core north-star host is a measured ~2.2× over any
    single-device execution of the same work (mesh-sharded 116 s vs
    250 s unsharded monolithic; Python-level per-device group *slicing*
    was tried first and only reached ~1.4–1.6× overlap with round-robin
    collect bubbles on top, and jit GSPMD sharding propagation without
    the explicit wrap compiled a ~3× slower per-chunk program).
    JGRAFT_GROUP_DEVICES=0 forbids fan-out for ablation (whole-group,
    default-device launches); JGRAFT_GROUP_DEVICES=N caps the fan-out
    mesh at N devices (see `chunk_sharding`)."""
    return os.environ.get("JGRAFT_GROUP_DEVICES") != "0"


def chunk_sharding(n_devices: Optional[int] = None):
    """Batch-axis `NamedSharding` for the chunked wavefront scheduler's
    per-launch arrays (checker/schedule.py), spanning every default-
    backend device — or None (default single-device placement) when
    `launch_fan_out` is gated off or only one device exists. One
    sharding object serves every launch and every recompaction bucket:
    `jax.device_put` under it re-lays out any batch-leading array, so a
    shrinking active set stays mesh-wide without fresh placement
    policy. Groups dispatched asynchronously under the SAME sharding
    still pipeline: each device queues every live group's current
    chunk, so the host blocking on one group's flags never idles the
    ring — the pipelined-dispatch half of the ISSUE-3 tentpole.

    `JGRAFT_GROUP_DEVICES=N` (N ≥ 2) caps the mesh at the first N
    devices: the chunked path pays a per-launch partition rendezvous
    per device, so on hosts where devices are *virtual* (pin_cpu's
    host-platform device split — 8 vdevs sharing 2 physical cores) a
    snugger mesh buys the same core parallelism at a fraction of the
    per-launch overhead. 0 disables fan-out entirely; 1 is clamped to
    single-device placement (None).

    `n_devices` is the per-launch override the autotuner uses
    (checker/autotune.py `mesh_fanout`): it caps the mesh like the env
    knob but per call, so two window groups of one batch can fan out
    differently. The env knob still applies as the outer bound — an
    operator pinning JGRAFT_GROUP_DEVICES=0 must never get fanned-out
    launches from a stale persisted plan."""
    from ..platform import env_int

    if not launch_fan_out():
        return None
    # LOCAL devices only: the wavefront scheduler device_puts host
    # numpy slices under this sharding, which requires every shard to
    # be addressable — in a multi-process runtime each host fans its
    # row shard over its own ICI mesh (parallel/distributed.py owns
    # the cross-host split). Identical to jax.devices() single-process.
    devs = jax.local_devices()
    cap = env_int("JGRAFT_GROUP_DEVICES", len(devs), minimum=0)
    if n_devices is not None:
        cap = min(cap, max(int(n_devices), 0))
    devs = devs[:max(cap, 1)]
    if len(devs) < 2:
        return None
    return NamedSharding(Mesh(np.asarray(devs), (BATCH_AXIS,)),
                         P(BATCH_AXIS))


# jit caches per function object, so rebuilding the shard_map closure per
# call would recompile every launch; cache by (model identity, shapes, mesh).
_CACHE: dict = {}


def sharded_batch_checker(model, mesh: Mesh,
                          n_configs: int = DEFAULT_N_CONFIGS,
                          n_slots: int = MAX_SLOTS,
                          axis_name: str = BATCH_AXIS,
                          macro_p: Optional[int] = None):
    """Build fn(events:[B,E,5], real:[B] bool) ->
    (ok[B], overflow[B], n_valid, n_unknown).

    B must be a multiple of the mesh size (use `check_batch_sharded` for
    automatic padding). ok/overflow stay sharded over the batch axis;
    n_valid/n_unknown are scalar `psum` aggregates (the ICI collective).
    `real` masks padding rows out of the aggregates — EV_PAD histories are
    trivially valid, so counting them would silently inflate n_valid.
    `macro_p` selects the macro-event row format ([B, E_mac, 3+4·P];
    history/packing.py) — a distinct compiled shape, so it keys the
    kernel cache like every other bucketed dim.
    """
    # scan_unroll() in the key: the wrapped kernel bakes it in at trace
    # time (same invariant as every ops/ kernel cache).
    key = (*model.cache_key(), int(n_configs), int(n_slots),
           tuple(mesh.devices.flat), axis_name, scan_unroll(), macro_p)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn

    single = make_history_checker(model, n_configs, n_slots, macro_p)
    vm = jax.vmap(single)

    def local_step(ev, real):  # ev: [B/n, E, 5] local shard
        ok, overflow = vm(ev)
        n_valid = jax.lax.psum(jnp.sum(ok & ~overflow & real), axis_name)
        n_unknown = jax.lax.psum(jnp.sum(overflow & real), axis_name)
        return ok, overflow, n_valid, n_unknown

    # check_vma=False: the scan carry inside the kernel starts from
    # unvarying constants, which the replication checker rejects even
    # though the computation is per-shard independent by construction.
    mapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(), P()),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _CACHE[key] = fn
    return fn


def sharded_dense_checker(model, mesh: Mesh, kind: str, n_slots: int,
                          n_states: int, axis_name: str = BATCH_AXIS,
                          macro_p: Optional[int] = None):
    """Dense-bitset variant of `sharded_batch_checker`:
    fn(events [B,E,5], val_of [B,S], real [B] bool) -> (ok[B],
    overflow[B], n_valid, n_unknown). Same mesh layout; the per-history
    domain table (or the mask-mode dummy) and the padding mask shard with
    the batch; `macro_p` keys the macro-event row format."""
    key = ("dense", kind, *model.cache_key(), int(n_slots),
           int(n_states), tuple(mesh.devices.flat), axis_name,
           scan_unroll(), macro_p)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn

    vm = jax.vmap(make_dense_single_checker(model, kind, n_slots, n_states,
                                            macro_p))

    def local_step(ev, val_of, real):
        ok, overflow = vm(ev, val_of)
        n_valid = jax.lax.psum(jnp.sum(ok & real), axis_name)
        n_unknown = jax.lax.psum(jnp.sum(overflow & real), axis_name)
        return ok, overflow, n_valid, n_unknown

    mapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(), P()),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _CACHE[key] = fn
    return fn


def _real_mask(B_real: int, B_padded: int) -> np.ndarray:
    """[B_padded] bool: True for real rows, False for EV_PAD padding."""
    mask = np.zeros((B_padded,), dtype=bool)
    mask[:B_real] = True
    return mask



def _run_once(model, events: np.ndarray, mesh: Mesh, n_configs: int,
              n_slots: int, macro_p: Optional[int] = None):
    """One sharded launch at a fixed frontier capacity, with mesh-size
    padding handled. B is bucketed (pow2+midpoint series) so escalation rungs
    (whose subset sizes vary run to run) hit the jit cache instead of
    recompiling per call."""
    axis_name = mesh.axis_names[0]
    events, _, B = pad_batch_bucketed(events, floor_e=None,
                                      multiple_b=mesh.devices.size)
    sharding = NamedSharding(mesh, P(axis_name, None, None))
    msharding = NamedSharding(mesh, P(axis_name))
    dev_events = jax.device_put(events, sharding)
    dev_mask = jax.device_put(_real_mask(B, events.shape[0]), msharding)
    fn = sharded_batch_checker(model, mesh, n_configs, n_slots, axis_name,
                               macro_p)
    ok, overflow, _, _ = fn(dev_events, dev_mask)
    # One sharded launch per rung; the ladder blocks here by design.
    return np.asarray(ok)[:B], np.asarray(overflow)[:B]  # lint: allow(host-sync)


def check_batch_sharded(model, events: np.ndarray, mesh: Optional[Mesh] = None,
                        n_configs: Optional[int] = None,
                        n_slots: int = MAX_SLOTS,
                        dense: Optional[tuple] = None,
                        defer: bool = False,
                        macro_p: Optional[int] = None):
    """Check a packed event batch across the mesh.

    events: [B, E, 5] int32 (history/packing.py layout), or a macro
    batch [B, E_mac, 3+4·P] with `macro_p=P` (pack_macro_batch). Pads B
    up to a multiple of the mesh size with EV_PAD histories (trivially
    valid, no FORCE events → sliced off afterwards). Returns (ok[B],
    overflow[B], n_valid, n_unknown) host values corrected for padding.

    `defer=True` returns a zero-arg finalizer instead: the dense-plan
    launch is dispatched asynchronously and the finalizer blocks for the
    host values — callers with several window groups launch them all and
    block once, so the chip pipelines the groups instead of paying a
    host round trip per group (the capacity ladder must block per rung to
    decide escalation, so its finalizer is pre-resolved).

    `dense` — a `ops.dense_scan.DensePlan` — routes the batch to the
    dense-bitset kernel (domain or mask mode): exact, ladder-free, ~10×+
    on small-domain / order-independent workloads.

    Capacity ladder otherwise (unless `n_configs` pins one rung): kernel
    cost is linear in the frontier capacity and "valid" at small capacity
    is final (overflow can only lose configurations — false-INVALID,
    never false-VALID), so the whole batch runs at C=64 and only the
    overflowed minority re-runs at full capacity.
    """
    mesh = mesh or make_mesh()
    if dense is not None:
        axis_name = mesh.axis_names[0]
        events, (val_of,), B = pad_batch_bucketed(
            events, (dense.val_of,), floor_e=None,
            multiple_b=mesh.devices.size)
        sharding = NamedSharding(mesh, P(axis_name, None, None))
        vsharding = NamedSharding(mesh, P(axis_name, None))
        msharding = NamedSharding(mesh, P(axis_name))
        fn = sharded_dense_checker(model, mesh, dense.kind, dense.n_slots,
                                   dense.n_states, axis_name, macro_p)
        mask = _real_mask(B, events.shape[0])
        ok, overflow, n_valid, _ = fn(jax.device_put(events, sharding),
                                      jax.device_put(val_of, vsharding),
                                      jax.device_put(mask, msharding))

        def finalize(ok=ok, n_valid=n_valid, B=B):
            return (np.asarray(ok)[:B], np.zeros((B,), bool),
                    int(n_valid), 0)

        return finalize if defer else finalize()
    ladder = ([n_configs] if n_configs else
              [64, DEFAULT_N_CONFIGS] if DEFAULT_N_CONFIGS > 64
              else [DEFAULT_N_CONFIGS])
    B = events.shape[0]
    ok = np.zeros((B,), dtype=bool)
    overflow = np.zeros((B,), dtype=bool)
    remaining = np.arange(B)
    for rung, C in enumerate(ladder):
        r_ok, r_ovf = _run_once(model, events[remaining], mesh, C, n_slots,
                                macro_p)
        ok[remaining] = r_ok
        overflow[remaining] = r_ovf
        # escalate only undecided rows: overflowed AND not proven valid
        escalate = remaining[r_ovf & ~r_ok]
        if rung + 1 >= len(ladder) or escalate.size == 0:
            break
        remaining = escalate
    # ok counts as valid even when the frontier overflowed: the witnessed
    # linearization is real. Only overflowed-and-not-ok is undecided.
    n_valid = int(np.sum(ok))
    n_unknown = int(np.sum(overflow & ~ok))
    out = (ok, overflow, n_valid, n_unknown)
    return (lambda: out) if defer else out
