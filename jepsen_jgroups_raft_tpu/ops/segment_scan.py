"""Segmented scan: long-history linearizability as parallel segment runs.

The blockwise/streaming treatment SURVEY.md §5.7/§7.4.4 calls for. A
single 100k-op history packs to a ~155k-event stream, and the dense
kernel (ops/dense_scan.py) scans it strictly sequentially — one device,
per-event latency-bound, zero batch parallelism (the round-2 BASELINE
row: 60.3 s). But the scan has *provable cut points*: at any event
boundary where **no live op is open** (live = an op whose FORCE is still
coming; crashed ops never force), every surviving configuration's mask
is a subset of the currently-open *crashed* slots — everything else was
forced and had its bit recycled. Real histories are full of these
quiescent boundaries (a measured config-#5 stream: 8.6k cuts, mean gap
18 events), because client processes spend most of wall-clock time
between ops at the reference's request rates (reference raft.clj:19-22:
10 req/s/thread vs ~ms op latency).

So: cut the stream at quiescent boundaries into K segments, and run all
segments CONCURRENTLY, each vmapped over a small basis of possible
start configurations:

    basis(k) = { (mask m, state s) : m ⊆ C_k, s < S }

where C_k is the crashed-open slot set at cut k (|C_k| ≤ max crashes —
the same quantity that bounds the window; measured ≤ 3 at the cuts of
the config-#5 stream). Crashed slots never close, so C_k ⊆ C_{k+1} and
the composition is well-defined. Each (segment, seed) run produces the
final frontier F_seed[M, S]; because every kernel update (closure OR,
force kill+shift) distributes over union, the segment's effect on ANY
start frontier is the union of its effects on the seeds — each segment
is a join-morphism, fully described by its seed→frontier table. The
host then composes the K tables left to right (tiny boolean relation
chain): VALID iff a nonempty frontier survives to the end. This is
exact — same verdict as the monolithic scan, proven by the differential
tests — not an approximation.

Segment starts re-emit an OPEN event per slot in C_k (copied from the
slot's original OPEN row) so the slot registers re-latch; an OPEN does
not change the frontier, so this is free of semantic drift.

Cost shape: sequential depth drops from E to ~E/K while per-step work
grows by the basis width (≤ 2^c · S) — the classic depth-for-FLOPs
trade, and the right one on a TPU where the monolithic scan leaves the
VPU idle. Histories with no quiescent cuts (fully saturated
concurrency) fall back to the monolithic kernel: `plan` returns None.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..history.packing import EV_FORCE, EV_OPEN, EncodedHistory
from .dense_scan import _pad_domains
# Caps and the shared closure/FORCE machinery come straight from the
# kernel IR (not via dense_scan re-exports): the kernel-contract
# analyzer resolves this module's cap expressions by loading the
# sibling the import names, and it does not chase re-export chains.
from .kernel_ir import (DENSE_MAX_STATES, SEGMENT_MAX_CELLS,
                        SEGMENT_MAX_SLOTS, closure_fixpoint, force_arith,
                        scan_unroll)

#: Segment the stream only when it is long enough to be worth the basis
#: overhead; shorter histories take the plain dense kernel.
LONG_HISTORY_MIN_EVENTS = 8192

#: Target events per segment. Depth/width balance: smaller blocks = more
#: parallelism but more basis-duplicated work and a bigger [K·nb, M, S]
#: carry. ~1-2k events/segment measured best on both CPU mesh and v5e.
DEFAULT_BLOCK_EVENTS = 1536

#: Cap on the per-segment seed basis (2^crashed · S). Beyond this the
#: frontier-carry blowup outweighs the depth win; such histories fall
#: back to the monolithic kernel.
MAX_BASIS = 256

#: CPU cost gate: the basis multiplies total cell work by NB (the depth
#: win buys wall-clock only where per-step width is near-free, i.e. the
#: TPU VPU). On the host, take the segmented path only when one step's
#: cell volume NB·2^W·S stays under this budget (config-#5 shape: 32·
#: 256·4 = 32k ✓; a W=10 16-history batch: 64·1024·4 = 262k ✗ → the
#: monolithic mesh path is faster there on CPU).
CPU_STEP_CELL_BUDGET = 1 << 16


@dataclass
class SegmentPlan:
    """Host-side plan for one long history's segmented run."""

    starts: np.ndarray          # [K] segment start event index
    ends: np.ndarray            # [K] segment end event index (exclusive)
    crash_sets: list            # [K] tuple of crashed-open slot ids at start
    open_rows: list             # [K] tuple of OPEN row indices for crash_sets
    n_slots: int
    n_states: int
    val_of: np.ndarray          # [S] id→value table


def _live_opens(events: np.ndarray) -> np.ndarray:
    """[E] bool per row: True for OPEN rows whose op is later FORCEd
    (live); False for OPEN rows of crashed ops (slot never closes) and
    for non-OPEN rows."""
    E = events.shape[0]
    live = np.zeros((E,), dtype=bool)
    seen_force: dict = {}
    for i in range(E - 1, -1, -1):
        t, s = int(events[i, 0]), int(events[i, 1])
        if t == EV_FORCE:
            seen_force[s] = True
        elif t == EV_OPEN:
            live[i] = seen_force.get(s, False)
            seen_force[s] = False
    return live


def find_cuts(events: np.ndarray):
    """Quiescent boundaries of an event stream.

    Returns (positions, crash_sets, open_rows): cut i is *before* event
    `positions[i]`; `crash_sets[i]` is the tuple of crashed-open slots
    there and `open_rows[i]` their original OPEN row indices. The stream
    start (position 0, empty crash set) is always cut 0.
    """
    live_open = _live_opens(events)
    positions = [0]
    crash_sets = [()]
    open_rows = [()]
    live = 0
    crashed: dict = {}  # slot -> OPEN row
    for i in range(events.shape[0]):
        t, s = int(events[i, 0]), int(events[i, 1])
        if t == EV_OPEN:
            if live_open[i]:
                live += 1
            else:
                crashed[s] = i
        elif t == EV_FORCE:
            live -= 1
        if live == 0:
            positions.append(i + 1)
            cs = tuple(sorted(crashed))
            crash_sets.append(cs)
            open_rows.append(tuple(crashed[c] for c in cs))
    return positions, crash_sets, open_rows


def plan_segments(model, enc: EncodedHistory,
                  block_events: int = DEFAULT_BLOCK_EVENTS,
                  min_events: int = LONG_HISTORY_MIN_EVENTS,
                  ) -> Optional[SegmentPlan]:
    """Decide whether (and how) to run a history segmented. None → use
    the monolithic kernel (stream too short, no usable cuts, basis too
    wide, or model/domain not dense-eligible)."""
    if enc.n_events < min_events:
        return None
    W = max(enc.n_slots, 1)
    domain = model.dense_domain(enc.events)
    if domain is None or W > SEGMENT_MAX_SLOTS or \
            len(domain) > DENSE_MAX_STATES or \
            (1 << W) * len(domain) > SEGMENT_MAX_CELLS:
        return None
    S, val_of = _pad_domains([np.asarray(domain, np.int32)], [0])
    positions, crash_sets, open_rows = find_cuts(enc.events)
    nb = 2 ** max(len(c) for c in crash_sets) * S
    if nb > MAX_BASIS:
        return None
    if jax.default_backend() != "tpu" and \
            nb * (1 << W) * S > CPU_STEP_CELL_BUDGET:
        return None
    # Greedy: next cut ≥ block_events past the segment start.
    starts, ends, segs_cs, segs_or = [0], [], [()], [()]
    for p, cs, orow in zip(positions[1:], crash_sets[1:], open_rows[1:]):
        if p - starts[-1] >= block_events and p < enc.n_events:
            ends.append(p)
            starts.append(p)
            segs_cs.append(cs)
            segs_or.append(orow)
    ends.append(enc.n_events)
    if len(starts) < 2:
        return None
    return SegmentPlan(np.asarray(starts), np.asarray(ends), segs_cs,
                       segs_or, W, S, val_of[0])


def make_segment_kernel(model, n_slots: int, n_states: int, n_events: int):
    """fn(events [K,E,5], val_of [K,S], seed_mask [K,NB], seed_state
    [K,NB]) -> F_final [K,NB,M,S] bool. One run per (segment, seed):
    the dense-domain scan seeded at configuration (mask, state) instead
    of (0, initial); seed_mask < 0 → empty frontier (basis padding).
    Shares the dense kernel's event semantics exactly (same scan_step
    dataflow as ops/dense_scan.make_dense_history_checker; cited there
    against the reference's knossos search, SURVEY.md §3.4)."""
    W, S, E = int(n_slots), int(n_states), int(n_events)
    M = 1 << W
    slot_ids = jnp.arange(W, dtype=jnp.int32)

    def expand_w(w, F, Te):
        Fb = F.reshape(M >> (w + 1), 2, 1 << w, S)
        src = Fb[:, 0].reshape(-1, S).astype(jnp.float32)
        contrib = (src @ Te[w]).reshape(M >> (w + 1), 1 << w, S) > 0
        return jnp.concatenate(
            [Fb[:, :1], (Fb[:, 1] | contrib)[:, None]], axis=1
        ).reshape(M, S)

    def scan_step(carry, ev):
        F, T, slot_open, dirty, val_of = carry
        etype, slot, f, a, b = ev[0], ev[1], ev[2], ev[3], ev[4]
        is_open = etype == EV_OPEN
        is_force = etype == EV_FORCE

        onehot = slot_ids == slot
        upd = onehot & is_open
        # Transition matrices live in the carry, refreshed once per
        # OPEN — not re-derived from model.jax_step W times per closure
        # sweep (same round-5 hoist as the dense kernel; measured there).
        ns, legal = model.jax_step(val_of, f, a, b)
        row = (ns[:, None] == val_of[None, :]) & legal[:, None]  # [S, S']
        T = jnp.where(upd[:, None, None], row[None], T)
        slot_open = jnp.where(upd, True, slot_open)
        dirty = dirty | is_open

        Te = (T & slot_open[:, None, None]).astype(jnp.float32)

        def sweep(F):
            for w in range(W):
                F = expand_w(w, F, Te)
            return F

        F = closure_fixpoint(W, sweep, F, is_force & dirty)
        dirty = dirty & ~is_force

        # Switch-free dispatch (ops/kernel_ir.force_arith): the old
        # lax.switch evaluated all W branches under the segment vmap.
        F_forced, _ = force_arith(F, jnp.clip(slot, 0, W - 1))
        F = jnp.where(is_force, F_forced, F)
        slot_open = slot_open & ~(onehot & is_force)
        return (F, T, slot_open, dirty, val_of), None

    def run_one(events, val_of, seed_mask, seed_state):
        # Seeded frontier; a dead seed (mask < 0) contributes nothing.
        F = ((jnp.arange(M)[:, None] == seed_mask) &
             (jnp.arange(S)[None, :] == seed_state) & (seed_mask >= 0))
        carry = (
            F,
            jnp.zeros((W, S, S), bool), jnp.zeros((W,), bool),
            jnp.bool_(False), val_of,
        )
        carry, _ = lax.scan(scan_step, carry, events,
                            unroll=scan_unroll())
        return carry[0]

    over_basis = jax.vmap(run_one, in_axes=(None, None, 0, 0))
    over_segments = jax.vmap(over_basis, in_axes=(0, 0, 0, 0))
    return jax.jit(over_segments)


_SEG_KERNEL_CACHE: dict = {}


def _segment_kernel(model, W: int, S: int, E: int):
    # scan_unroll() in the key: see dense_scan.make_dense_batch_checker.
    key = (*model.cache_key(), W, S, E, scan_unroll())
    fn = _SEG_KERNEL_CACHE.get(key)
    if fn is None:
        fn = make_segment_kernel(model, W, S, E)
        _SEG_KERNEL_CACHE[key] = fn
    return fn


def _build_segment_arrays(enc: EncodedHistory, plan: SegmentPlan,
                          E_seg: int, NB: int, S: int):
    """Materialize one history's segment/basis inputs.

    events [K,E_seg,5] (re-OPEN prologue + slice, EV_PAD tail),
    seed_mask/seed_state [K,NB] (padded -1), basis index maps for the
    host composition. `S` is the BATCH state count, not the history's
    own: state-table padding duplicates the id-0 value, so the kernel
    can land frontier bits on duplicate state ids — the basis (and the
    composition lookups) must cover them."""
    K = len(plan.starts)
    events = np.zeros((K, E_seg, 5), dtype=np.int32)
    seed_mask = np.full((K, NB), -1, dtype=np.int32)
    seed_state = np.zeros((K, NB), dtype=np.int32)
    basis_index: list = []  # per segment: {(mask, state): basis row}
    for k in range(K):
        s0, e0 = int(plan.starts[k]), int(plan.ends[k])
        pro = len(plan.open_rows[k])
        # Prologue: re-latch each crashed-open slot's registers.
        for j, row in enumerate(plan.open_rows[k]):
            events[k, j] = enc.events[row]
        events[k, pro:pro + (e0 - s0)] = enc.events[s0:e0]
        # Basis: every subset of the crashed set × every state id.
        cs = plan.crash_sets[k]
        idx: dict = {}
        b = 0
        for sub in range(1 << len(cs)):
            mask = 0
            for j, slot in enumerate(cs):
                if sub >> j & 1:
                    mask |= 1 << slot
            for st in range(S):
                seed_mask[k, b] = mask
                seed_state[k, b] = st
                idx[(mask, st)] = b
                b += 1
        basis_index.append(idx)
    return events, seed_mask, seed_state, basis_index


def check_segmented(enc: EncodedHistory, model,
                    block_events: int = DEFAULT_BLOCK_EVENTS,
                    min_events: int = LONG_HISTORY_MIN_EVENTS,
                    ) -> Optional[dict]:
    """Check one long history via the segmented scan. None → caller
    should use the monolithic path."""
    [r] = check_segmented_batch([enc], model, block_events, min_events)
    return r


def check_segmented_batch(encs: Sequence[EncodedHistory], model,
                          block_events: int = DEFAULT_BLOCK_EVENTS,
                          min_events: int = LONG_HISTORY_MIN_EVENTS,
                          ) -> list:
    """Batch form: all eligible histories' segments fly in ONE kernel
    launch (the segment axis is the batch axis). What `auto` sends it
    today (`linearizable._segment_routing_on`): nothing — PR 44 read a
    100k-op register history (~95 segments of ~1,550 events) at 3.60 s
    here against 2.01 s as a chunked LONG launch, and shorter and
    paired ones further behind; `JGRAFT_SEGMENT=1` sends it a launch's
    histories of `LONG_HISTORY_MIN_EVENTS` events or more. Returns a
    result dict per history, or None per history that should take the
    monolithic path. The three parts are named for a profiler session
    (`segment.plan`, `segment.kernel` inside a `launch.device`,
    `segment.compose`); the caller's `launch.segment` span times them
    together."""
    from ..checker.schedule import annotate, launch_span

    results: list = [None] * len(encs)
    with annotate("segment.plan", histories=len(encs)):
        plans = [plan_segments(model, e, block_events, min_events)
                 for e in encs]
        live = [i for i, p in enumerate(plans) if p is not None]
        batch = _segment_batch(encs, plans, live) if live else None
    if batch is None:
        return results
    live, W, S, E_seg, NB, K_tot, operands, maps = batch
    # The segment axis is embarrassingly parallel — shard it over the
    # device mesh (computation follows data; dead padded segments cost
    # one seed check). This is what makes a SINGLE long history use the
    # whole mesh, which the monolithic scan never could.
    kernel = _segment_kernel(model, W, S, E_seg)
    with launch_span(rows=operands[0].shape[0]), annotate(
            "segment.kernel", segments=K_tot, basis=NB, events=E_seg):
        F = np.asarray(  # lint: allow(host-sync) — host composition next
            kernel(*_place_segments(operands)))[:K_tot]
    with annotate("segment.compose", histories=len(live)):
        for i, r in zip(live, _compose(F, maps, NB)):
            results[i] = r
    return results


def _segment_batch(encs, plans, live):
    """One compiled shape across the planned histories `live`: bucket
    everything, build every history's segment and basis arrays, pad the
    segment axis to the device count. Returns ``(live, W, S, E_seg, NB,
    K_tot, (events, val_of, seed_mask, seed_state), maps)``, or None
    where no history is left."""
    # RE-CHECK the basis gates with the batch-bucketed S/W. plan_segments
    # gated each history against its OWN domain size; batching a
    # small-domain many-crash history with a wide-domain one multiplies
    # the first's basis by the batch S and can blow past MAX_BASIS /
    # the CPU budget the gates were measured to protect. Offenders fall
    # back to the monolithic path (result None); shrinking `live` can
    # shrink S, so iterate to stability.
    while True:
        W = max(plans[i].n_slots for i in live)
        S = max(plans[i].n_states for i in live)
        shed = []
        for i in live:
            p = plans[i]
            nb_i = max(1 << len(c) for c in p.crash_sets) * S
            if nb_i > MAX_BASIS or (
                    jax.default_backend() != "tpu" and
                    nb_i * (1 << W) * S > CPU_STEP_CELL_BUDGET):
                shed.append(i)
        if not shed:
            break
        live = [i for i in live if i not in shed]
        if not live:
            return None
    E_seg = 1
    NB = 1
    for i in live:
        p = plans[i]
        pro = max((len(c) for c in p.crash_sets), default=0)
        seg_len = int((p.ends - p.starts).max()) + pro
        E_seg = max(E_seg, seg_len)
        NB = max(NB, max(1 << len(c) for c in p.crash_sets) * S)
    E_seg = _pow2(E_seg)
    NB = _pow2(NB)

    rows_events, rows_val, rows_mask, rows_state = [], [], [], []
    maps = []
    for i in live:
        p = plans[i]
        ev, sm, ss, bidx = _build_segment_arrays(encs[i], p, E_seg, NB, S)
        # Re-bucket this history's S up to the batch S (harmless pad:
        # duplicate id-0 values transition identically).
        val = np.full((len(ev), S), p.val_of[0], dtype=np.int32)
        val[:, :len(p.val_of)] = p.val_of
        rows_events.append(ev)
        rows_val.append(val)
        rows_mask.append(sm)
        rows_state.append(ss)
        maps.append((len(ev), bidx, p))
    events = np.concatenate(rows_events)
    val_of = np.concatenate(rows_val)
    seed_mask = np.concatenate(rows_mask)
    seed_state = np.concatenate(rows_state)

    from ..parallel.mesh import make_mesh
    n_dev = make_mesh().devices.size
    K_tot = events.shape[0]
    K_pad = ((K_tot + n_dev - 1) // n_dev) * n_dev
    if K_pad != K_tot:
        events = np.concatenate(
            [events, np.zeros((K_pad - K_tot,) + events.shape[1:],
                              events.dtype)])
        val_of = np.concatenate(
            [val_of, np.tile(val_of[-1:], (K_pad - K_tot, 1))])
        seed_mask = np.concatenate(
            [seed_mask, np.full((K_pad - K_tot, NB), -1, np.int32)])
        seed_state = np.concatenate(
            [seed_state, np.zeros((K_pad - K_tot, NB), np.int32)])
    return (live, W, S, E_seg, NB, K_tot,
            (events, val_of, seed_mask, seed_state), maps)


def _place_segments(operands) -> tuple:
    """The kernel's four operands, their segment axis sharded over the
    device mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import make_mesh
    mesh = make_mesh()
    ax = mesh.axis_names[0]
    events, *rest = operands
    sh2 = NamedSharding(mesh, P(ax, None))
    return (jax.device_put(events, NamedSharding(mesh, P(ax, None, None))),
            *(jax.device_put(x, sh2) for x in rest))


def _compose(F: np.ndarray, maps: list, NB: int) -> list:
    """Host composition: chain each history's segment relations; a
    result dict a history, in `maps`' order."""
    out = []
    row = 0
    for K, bidx, p in maps:
        reach = {(0, 0)}
        for k in range(K):
            acc = None
            for (m, st) in reach:
                b = bidx[k].get((m, st))
                if b is None:
                    # A reachable config outside the planned basis would
                    # be a soundness bug (cut spaces are nested) — fail
                    # loudly rather than report a verdict.
                    raise AssertionError(
                        f"segment {k}: config ({m},{st}) outside basis")
                f = F[row + k, b]
                acc = f if acc is None else (acc | f)
            if acc is None or not acc.any():
                reach = set()
                break
            ms, sts = np.nonzero(acc)
            reach = set(zip(ms.tolist(), sts.tolist()))
        out.append({
            "valid": bool(reach),
            "segments": K,
            "basis": NB,
            "n_slots": p.n_slots,
        })
        row += K
    return out


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b
