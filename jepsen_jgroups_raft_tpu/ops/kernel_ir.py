"""Unified kernel IR: the one step-parts substrate every scan family
instantiates.

Before this module existed, every performance feature grew 4× by hand:
chunking (ISSUE 3) and macro-event compaction (ISSUE 4) were each ported
separately into the dense/mask kernels (ops/dense_scan.py), the sort
ladder (ops/linear_scan.py) and two kernels since deleted (PR 50) —
four copies of the event-row decode, the macro-latch
application, the arithmetic FORCE dispatch, the chunk-carry schema and
the decided/exhausted flag semantics. This module is the single home of
that shared machinery; each family now keeps ONLY its state-
representation lowering (how a frontier is stored and swept) and plugs
it into the IR through three hooks.

The IR's contract — what a family must supply (doc/checker-design.md §9):

  ``latch(carry, slot, f, a, b, is_open, upd) -> carry``
      Latch ONE op's registers (legacy one-event-per-step stream).
      ``upd`` is the precomputed per-slot write mask
      ``(slot_ids == slot) & is_open``.
  ``macro_latch(carry, pslot, pf, pa, pb, valid, n, eq, upd) -> carry``
      Latch ≤P opens at once (macro stream, history/packing.py
      macro_compact). ``eq``/``upd`` come from :func:`macro_select`;
      slots within a macro are distinct (packing only recycles a slot
      at its FORCE), so at most one payload matches per slot.
  ``force_tail(carry, is_force, slot) -> carry``
      The closure + FORCE phase. Identical for both streams — this is
      the whole macro soundness argument: the latch phases reach the
      same pre-FORCE register state, then run THIS same code, and
      closure is a reachability fixpoint over exactly those registers,
      so verdicts are bitwise-identical (pinned by
      tests/test_macro_events.py and tests/test_kernel_ir.py).

:func:`make_stream_step` assembles the hooks into the per-event
``scan_step``; :class:`KernelParts` bundles (init, scan_step, verdict);
:func:`monolithic_check` and :func:`batch_chunk_checker` are the two
drivers (one step body, two drivers — the chunked wavefront of
checker/schedule.py can never diverge semantically from the reference
scan). The chunk-carry schema ({"inner", "left"}) and the
decided/exhausted eviction flags are defined here ONCE; their soundness
argument (``ok`` is monotone, a dead frontier stays dead, an exhausted
row only has EV_PAD no-ops left) is restated at :func:`chunk_step_fns`.

The eligibility caps and the chunk-carry byte accounting live here too:
the graftcheck kernel-contract analyzer (lint/flow/kernel_contract.py)
proves the VMEM budgets ONCE against this module instead of per family.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..history.packing import EV_FORCE, EV_OPEN, MACRO_MAX_OPENS

# --------------------------------------------------------------- caps
# Family eligibility caps (moved here from the per-family modules so the
# kernel-contract analyzer samples every family's chunk-carry budget
# against ONE module). The families re-import and re-export them, so the
# routing layers keep their existing spellings.

#: Dense-domain caps. Per-event work is ~W · 2^W · S² (closure sweeps)
#: plus 2^W · S (the arithmetic FORCE path), so the dense path is
#: reserved for genuinely small problems — which the reference's own
#: workload shapes are (window ≈ n_procs, domain ≈ 5 values; a few
#: crashed ops' never-retiring slots push long histories to W ≈ 10).
#: Since ISSUE 40 the caps stand at the widest window the family has
#: been read at on a chip (one TPU v5 lite). The readings then, on the
#: bool frontier [2^W, S] (PERF.md section 5, PR 40): one group of S 8
#: register rows of 1,000 ops 223 / 444 / 885-925 ms at W 11 / 12 / 13
#: for 8 rows and 901 / 1,614 ms at W 11 / 12 for 128. Since ISSUE 41
#: the frontier is one uint32 word a configuration (ops/dense_scan.py
#: `expand_packed`) and the same groups read 39.8 / 40.7 ms at W 11 /
#: 12 for 8 rows and 311.8 / 372.6 ms for 128 (PERF.md section 6, PR
#: 41): the caps did NOT move with it (ROADMAP R14 is its own issue,
#: and the partition generator's gate reads the name).
#: Read on two inputs only: crash-free register histories widened to
#: their launch's window (W 11-12, `scripts/sweep_group_cost.py`) and
#: the partition-nemesis histories of `benchmarks/generators/
#: partition.py` (W 11-13, served); no other model, S or length. What
#: the route replaced for those rows: the sort ladder, whose top rung
#: (C = 256) 122 of 124 of them overflowed (frontiers of 1,900-24,800
#: configurations), then the host engines on the caller's thread, 0.6
#: to 8.8 s for a row the first DFS budget leaves. A caller whose W
#: 11-13 rows have frontiers the first rung (C = 64) holds now pays the
#: dense sweep of 16k-65k cells for them: not measured. Past W 13 no
#: memory limit refuses (2^W x 4 B a row: 64 KiB at W 14, where the
#: bool frontier's float sweep held 512 KiB), and the packed kernel's
#: cost a window (x 1.2-1.5 from W 10 to 12) says W 14-15 would cost
#: well under the host's DFS budget: unmeasured, so not taken here.
DENSE_MAX_SLOTS = 13
DENSE_MAX_STATES = 16
DENSE_MAX_CELLS = 65536  # 2^W · S

#: A window past this is WIDE in `/stats` `wide_rows` / `wide_rows_host`
#: (the dense cap until ISSUE 40): counted, never routed on.
WIDE_WINDOW_SLOTS = 10

#: Mask mode has no state dimension (S² → 1), so it affords a wider
#: window: 2^12 bool cells + an int32 subset-sum lane per history.
MASK_DENSE_MAX_SLOTS = 12

#: Sort-ladder caps (ops/linear_scan.py re-exports these under its
#: historical names MAX_SLOTS / DEFAULT_N_CONFIGS). The window cap is
#: 4 mask words with a spare top bit for the all-ones empty-entry
#: sentinel — linear_scan's contract pins it.
SORT_MAX_SLOTS = 127
SORT_DEFAULT_CONFIGS = 256

#: Cycle-tier cap (ISSUE 13): dependency graphs beyond this many nodes
#: skip the MONOLITHIC closure kernel (make_cycle_closure keeps the
#: whole [N, N] slab resident). The adjacency slab at this cap is
#: proven against the VMEM budget by the kernel-contract analyzer
#: (cycle_adjacency_bytes).
CYCLE_MAX_NODES = 512

#: Blocked-closure cap (ISSUE 19): the tiled kernel
#: (make_cycle_closure_tiled) streams [T, N] panels instead of the
#: whole matrix, so the node ceiling rises 8× — the per-k-step panel
#: residency is what the kernel-contract analyzer proves now
#: (cycle_closure_tile_bytes, executed at THIS corner). Rows beyond
#: this cap skip the exact tier entirely (and say so: the
#: cycle-skipped-size annotation, checker/cycle.py).
CYCLE_MAX_NODES_TILED = 4096

#: Default closure tile edge. 256 is the largest pow2 whose panel set
#: fits the VMEM budget at the 4096-node cap ((3·T·N + T²)·4 ≈ 12.9 MB
#: at T=256, N=4096; T=512 would need 25 MB) — and every node bucket
#: the pow2+midpoint series emits above 512 (768, 1024, 1536, ...) is
#: a multiple of 256, so the default tile always divides the bucket.
CYCLE_TILE = 256


def scan_unroll() -> int:
    """Events per lax.scan step across the event-scan kernels (dense,
    mask, sort) — an ablation knob, JGRAFT_SCAN_UNROLL to
    override. Default 1 EVERYWHERE: CPU-mesh measurements did not
    survive re-measurement through the production path (a hand-built
    kernel probe showed unroll=2 at 1.49× on a B=4 × 15.7k-event
    launch, but the same shape through the bucketed production kernels
    measured unroll=1 faster, 11.2 s vs 16.0 s — the round-3 lesson
    about one-probe conclusions, again). Resolved at kernel-build time
    and part of every kernel-cache key."""
    v = os.environ.get("JGRAFT_SCAN_UNROLL")
    if v:
        return max(1, int(v))
    return 1


# ------------------------------------------------------- event-row layout


def macro_row_ints(macro_p: int = MACRO_MAX_OPENS) -> int:
    """int32 lanes of one macro-event row: [mtype, force_slot, n_opens]
    + macro_p × (slot, f, a, b); defaults to the widest row the encoder
    can emit (the MACRO_MAX_OPENS cap). Pure arithmetic on purpose —
    the kernel-contract analyzer (lint/flow/kernel_contract.py)
    executes it statically at the cap to re-prove the chunk event slabs
    against the VMEM budgets."""
    return 3 + 4 * macro_p


def macro_cols(row, macro_p: int):
    """Split one macro-event row [3 + 4·P] (history/packing.py
    macro_compact layout) into (mtype, force_slot, n_opens,
    pslot [P], pf [P], pa [P], pb [P])."""
    pay = row[3:3 + 4 * macro_p].reshape(macro_p, 4)
    return (row[0], row[1], row[2],
            pay[:, 0], pay[:, 1], pay[:, 2], pay[:, 3])


def macro_select(slot_ids, pslot, valid):
    """Masked-scatter helpers for the vectorized multi-slot latch:
    eq [W, P] marks which payload lands in which slot register (slots
    within a macro are distinct — packing only recycles a slot at its
    FORCE — so at most one payload matches per slot), upd [W] which
    slots update at all."""
    eq = (slot_ids[:, None] == pslot[None, :]) & valid[None, :]
    return eq, eq.any(axis=1)


def macro_latch_i32(eq, upd, old, new):
    """old [W] int32 register ← payload values new [P] where upd."""
    return jnp.where(upd, (eq.astype(jnp.int32) * new[None, :]).sum(1),
                     old)


# --------------------------------------------------- shared FORCE/closure


def closure_fixpoint(W: int, sweep, F, active):
    """Iterate `sweep` (one pass over all slots) to the reachability
    fixpoint. Each productive sweep extends every pending linearization
    chain by ≥1 op and chains are ≤W long, so ≤W sweeps suffice; the
    change test is exact even when the frontier representation holds
    redundant entries (it compares the whole array). `active`
    short-circuits non-FORCE events."""

    def cond(c):
        return c[0]

    def body(c):
        _, it, F = c
        F0 = F
        F = sweep(F)
        return (jnp.any(F != F0) & (it < W), it + 1, F)

    _, _, F = lax.while_loop(cond, body, (active, jnp.int32(0), F))
    return F


def force_arith(F, slot_w):
    """Switch-free FORCE dispatch over a dense frontier (the ISSUE-4
    "dense slot dispatch" half): kill configurations missing the forced
    slot's bit, then recycle the bit by moving the bit=1 half of the
    butterfly onto the bit=0 half — both computed *arithmetically* from
    the dynamic slot id (the same style as the sort kernel's bitvec
    math) instead of the old `lax.switch` over W static branches, which
    under vmap lowered to select-over-all-branches: every scan step
    paid W× the one taken branch's [M, S] work.

    The down-shift by the dynamic bit weight 2^slot_w is W = log2(M)
    STATIC slices of one zero-extended copy of the killed frontier, at
    offsets 2^0 .. 2^(W-1), combined by selects on `slot_w == w`
    (ISSUE 45): nothing is indexed by a start that differs a row. Until
    then it was one `lax.dynamic_slice` of that copy, which `vmap` with
    a slot a row turns into a gather and the TPU's compiler into a
    loop over the rows of the launch, a dynamic-slice and a
    dynamic-update-slice an iteration: 73 % of the device's time in
    the batched cells. What is shifted in is dead, and so is every
    configuration that holds the bit afterwards: its source, 2^slot_w
    up, has the bit clear and was killed; no mask follows the shift.
    One form for every launch: on one v5e it read ahead of the dynamic
    slice at 128, at 8 and at ONE row (the LONG launches), windows
    5-13, and ahead of W rolls and of slice + pad (PERF.md section 6,
    PR 45). Both the macro and the JGRAFT_MACRO_EVENTS=0 legacy stream
    share this dispatch, so the macro A/B stays a pure stream-length
    comparison.

    F: the configuration axis M leading, of either representation —
    [M, 1] bool (the mask family) or [M] words whose bits are the states
    (the domain family, ops/dense_scan.dense_step_parts); a
    configuration is dead
    where its entry is all False / 0. slot_w pre-clipped to [0, W).
    Returns (F', any_survivor)."""
    M = F.shape[0]
    # numpy's: a jnp constant is an eager device op a trace
    ids = np.arange(M, dtype=np.int32).reshape((M,) + (1,) * (F.ndim - 1))
    has = ((ids >> slot_w) & 1) == 1                       # bit slot_w
    dead = jnp.zeros((), F.dtype)
    Fk = jnp.where(has, F, dead)
    alive = jnp.any(Fk != dead)
    ext = jnp.concatenate([Fk, jnp.zeros_like(Fk)], axis=0)  # [2M, ...]
    shifted = jnp.zeros_like(Fk)
    for w in range(M.bit_length() - 1):
        shifted = jnp.where(
            slot_w == w,
            lax.slice_in_dim(ext, 1 << w, M + (1 << w), axis=0), shifted)
    return shifted, alive


# ---------------------------------------------------------- stream step


def make_stream_step(n_slots: int, latch: Callable, macro_latch: Callable,
                     force_tail: Callable,
                     macro_p: Optional[int] = None) -> Callable:
    """The single definition of the per-event scan body every family
    shares: decode the event row (legacy [5] or macro [3 + 4·P]),
    compute the latch write masks, call the family's latch hook, then
    the family's closure+FORCE tail. This is where the old per-family
    ``if macro_p is None: ... else: ...`` twins collapsed to — a stream
    format change now happens in exactly one place.

    See the module docstring for the hook signatures; `n_slots` fixes
    the kernel's W (the hooks close over their own W-shaped state)."""
    slot_ids = jnp.arange(int(n_slots), dtype=jnp.int32)
    if macro_p is None:
        def scan_step(carry, ev):
            etype, slot, f, a, b = ev[0], ev[1], ev[2], ev[3], ev[4]
            is_open = etype == EV_OPEN
            is_force = etype == EV_FORCE
            upd = (slot_ids == slot) & is_open
            carry = latch(carry, slot, f, a, b, is_open, upd)
            carry = force_tail(carry, is_force, slot)
            return carry, None
    else:
        P = int(macro_p)

        def scan_step(carry, row):
            mtype, fslot, n, pslot, pf, pa, pb = macro_cols(row, P)
            is_force = mtype == EV_FORCE
            valid = jnp.arange(P, dtype=jnp.int32) < n
            eq, upd = macro_select(slot_ids, pslot, valid)
            carry = macro_latch(carry, pslot, pf, pa, pb, valid, n, eq,
                                upd)
            carry = force_tail(carry, is_force, fslot)
            return carry, None
    return scan_step


# -------------------------------------------------------------- drivers


@dataclass(frozen=True)
class KernelParts:
    """A family's lowered step parts, ready for either driver.

    init:      init(*operands) -> per-row scan carry (``n_operands``
               per-row operands, e.g. the dense kernels' val_of table;
               the sort kernel takes none).
    scan_step: the per-event body (from :func:`make_stream_step`).
    verdict:   carry -> (ok, overflow).
    """

    init: Callable
    scan_step: Callable
    verdict: Callable
    n_operands: int = 0


def monolithic_check(parts: KernelParts) -> Callable:
    """The reference driver: fn(events [E, R], *operands) ->
    (ok, overflow) — one `lax.scan` over the whole stream."""
    def check(events, *operands):
        carry, _ = lax.scan(parts.scan_step, parts.init(*operands),
                            events, unroll=scan_unroll())
        return parts.verdict(carry)

    return check


def chunk_step_fns(parts: KernelParts):
    """The chunk-carry schema + decided/exhausted flag semantics, in
    one place (this used to be duplicated between the dense and sort
    chunk builders). Returns single-row (init_one, step_one):

      init_one(*operands, n_ev) -> {"inner": scan carry, "left": int32}
      step_one(carry, events [E, R], lo, n) -> (carry', decided,
          exhausted, ok, overflow)

    `step_one` scans the `n` events from `lo` on. Both are TRACED
    scalars (ISSUE 32): the whole of a row's event stream is the
    operand, so one compiled program serves every span of a launch —
    the span's offset and length are data, not shape. The scheduler
    (checker/schedule.py) puts a group's events on the device once and
    steps through them; a caller holding only a slab passes
    ``lo=0, n=slab length``.

    Eviction soundness (the checker/linearizable.py contract): `ok` is
    monotone — it only ever ANDs in new conditions — and flips False
    exactly when the frontier dies, after which every event is a no-op
    on the dead frontier, so a `decided` (= ~ok) row's (ok, overflow)
    pair is frozen mid-scan. An `exhausted` row (events_left ≤ 0) only
    has EV_PAD no-ops left, so its current pair is final too. Either
    flag makes the row safe to evict: eviction only ever removes rows
    whose verdict is certain. Chaining step_one over consecutive spans
    applies the identical scan_step sequence as the monolithic
    `lax.scan`, so verdicts are bitwise-identical by construction
    (pinned by the tests/test_kernel_ir.py differentials)."""
    def init_one(*args):
        operands, n_ev = args[:-1], args[-1]
        return {"inner": parts.init(*operands),
                "left": jnp.asarray(n_ev, jnp.int32)}

    def step_one(carry, events, lo, n):
        unroll = scan_unroll()
        lo = jnp.asarray(lo, jnp.int32)
        n = jnp.asarray(n, jnp.int32)

        def at(i, inner):
            ev = lax.dynamic_index_in_dim(events, lo + i, axis=0,
                                          keepdims=False)
            return parts.scan_step(inner, ev)[0]

        def block(b, inner):
            for k in range(unroll):
                inner = at(b * unroll + k, inner)
            return inner

        inner = lax.fori_loop(0, n // unroll, block, carry["inner"])
        if unroll > 1:  # the span's tail, shorter than one block
            inner = lax.fori_loop((n // unroll) * unroll, n, at, inner)
        left = carry["left"] - n
        ok, overflow = parts.verdict(inner)
        return ({"inner": inner, "left": left},
                ~ok, left <= 0, ok, overflow)

    return init_one, step_one


def batch_chunk_checker(parts: KernelParts, mesh=None, jit: bool = True):
    """Batch driver for the wavefront scheduler (checker/schedule.py):
    vmapped (init_fn, step_fn) over the batch axis — the span's offset
    and length are shared by the rows, so they stay scalars — optionally
    wrapped in an explicit `shard_map` over `mesh` (see
    :func:`shard_chunk_fns` — relying on jit's GSPMD sharding
    propagation *placed* the carry sharded but compiled a ~3× slower
    per-chunk program than the explicit wrap on the CPU mesh). Callers
    pad the batch to a multiple of the mesh size
    (schedule.launch_rows)."""
    init_one, step_one = chunk_step_fns(parts)
    init_fn = jax.vmap(init_one)
    step_fn = jax.vmap(step_one, in_axes=(0, 0, None, None))
    if mesh is not None:
        init_fn, step_fn = shard_chunk_fns(
            init_fn, step_fn, mesh, n_init_args=parts.n_operands + 1)
    if jit:
        init_fn = jax.jit(init_fn)
        step_fn = jax.jit(step_fn)
    return init_fn, step_fn


def shard_chunk_fns(init_fn, step_fn, mesh, n_init_args: int):
    """Wrap a vmapped (init_fn, step_fn) chunk-kernel pair in
    `shard_map` over the batch axis of `mesh`. P(axis) acts as a pytree
    prefix over the carry dict (every leaf is batch-leading), the
    span's two scalars are replicated, and the replication check is off
    for the same reason as the monolithic sharded checkers: the
    computation is per-shard independent by construction
    (parallel/mesh.py). Lazy import — parallel.mesh imports the ops
    package at load time."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(mesh.axis_names[0])
    init_sm = shard_map(init_fn, mesh=mesh,
                        in_specs=(spec,) * n_init_args, out_specs=spec,
                        check_vma=False)
    step_sm = shard_map(step_fn, mesh=mesh,
                        in_specs=(spec, spec, P(), P()),
                        out_specs=(spec,) * 5, check_vma=False)
    return init_sm, step_sm


# ------------------------------------------------------- cycle closure


def make_cycle_closure(n_nodes: int):
    """Batched boolean transitive-closure kernel for the exact cycle
    tier (checker/cycle.py, ISSUE 13): ``closure(adj)`` with adj
    [B, N, N] int32 0/1 adjacency matrices (pow2-bucketed N, rows
    padded with zero matrices) returns (has_cycle [B] bool,
    closed [B, N, N]).

    The whole pass is repeated boolean matrix squaring — ``R ← R ∨
    R·R`` — as one batched int32 einsum inside a `lax.while_loop`:
    after k squarings R holds every path of length ≤ 2^k, so
    ceil(log2 N) iterations reach the full transitive closure (the
    loop also exits early when a squaring changes nothing); a set
    diagonal bit then witnesses a cycle. This is exactly the encoded
    substrate's shape — int32 matmul batched over independent rows —
    which is why the tier is essentially free where matmul is free
    (the MXU); off-TPU the caller routes to a host DFS on the same
    adjacency instead (checker/cycle.py, the PLATFORM_ROUTE idiom).
    Entries stay in {0, 1} (re-binarized every iteration), so the
    int32 row sums are bounded by N ≤ CYCLE_MAX_NODES — no overflow.
    """
    n_iter = closure_squarings(n_nodes)

    def closure(adj):
        closed, _ = square_to_fixpoint(adj.astype(jnp.int32), n_iter)
        diag = jnp.diagonal(closed, axis1=1, axis2=2)
        return jnp.any(diag > 0, axis=1), closed

    return jax.jit(closure)


def closure_squarings(n_nodes: int) -> int:
    """Squarings that close any graph on `n_nodes` nodes: after k of
    them R holds every path of length <= 2^k."""
    return max(1, (max(int(n_nodes), 2) - 1).bit_length())


def square_to_fixpoint(a, n_iter: int):
    """`R <- R | R.R` on [B, N, N] int32 0/1 matrices until a squaring
    changes nothing in any row, `n_iter` times at most: (the closure,
    the squarings that ran)."""
    def cond(c):
        i, _, changed = c
        return changed & (i < n_iter)

    def body(c):
        i, a, _ = c
        prod = jnp.einsum("bij,bjk->bik", a, a,
                          preferred_element_type=jnp.int32)
        nxt = jnp.minimum(a + jnp.minimum(prod, 1), 1)
        return (i + 1, nxt, jnp.any(nxt != a))

    i, closed, _ = lax.while_loop(
        cond, body, (jnp.int32(0), a, jnp.bool_(True)))
    return closed, i


def make_txn_closure(n_nodes: int):
    """The closure program of a transaction graph's launch
    (checker/txn_graph.py, ISSUE 51): ``program(codes)`` with codes
    [B, E] int32, one edge each as ``(plane * N + source) * N + target``
    over three planes (0: real time and write order, 1: reads-from, 2:
    anti-dependencies; a pad is ``3 * N * N``, past the table and
    dropped), returns (flags [B, 4] bool, squarings [3] int32).

    The device scatters the edges into the planes and closes three
    unions by `square_to_fixpoint`, each started from the closure
    before it (the closure of `closed(A) | B` is the closure of
    `A | B`): plane 0, + plane 1, + plane 2. Flags a row: a cycle in
    the first, in the second, a plane-2 edge (u, v) whose v reaches u
    in the second (one anti-dependency closing a path of the rest), a
    cycle in the third. `squarings` is what ran: B * N^3 multiply-adds
    each."""
    n = int(n_nodes)
    n_iter = closure_squarings(n)

    def cyclic(closed):
        return jnp.any(jnp.diagonal(closed, axis1=1, axis2=2) > 0, axis=1)

    def program(codes):
        b = codes.shape[0]
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        planes = jnp.zeros((b, 3 * n * n), dtype=jnp.int8).at[
            rows, codes].set(1, mode="drop").reshape(b, 3, n, n)
        k0, i0 = square_to_fixpoint(planes[:, 0].astype(jnp.int32), n_iter)
        k1, i1 = square_to_fixpoint(
            jnp.maximum(k0, planes[:, 1].astype(jnp.int32)), n_iter)
        rw = planes[:, 2].astype(jnp.int32)
        single = jnp.any((rw * jnp.swapaxes(k1, 1, 2)) > 0, axis=(1, 2))
        k2, i2 = square_to_fixpoint(jnp.maximum(k1, rw), n_iter)
        return (jnp.stack([cyclic(k0), cyclic(k1), single, cyclic(k2)],
                          axis=1), jnp.stack([i0, i1, i2]))

    return jax.jit(program)


def cycle_closure_tile(n_nodes: int, tile: int) -> int:
    """Effective tile edge for a bucket: the largest power of two ≤
    ``tile`` that divides ``n_nodes``.  Every bucket the pow2+midpoint
    series emits above 512 is a multiple of 256, so the shipped default
    (CYCLE_TILE) always survives intact; the clamp only matters for
    operator-forced JGRAFT_CYCLE_TILE values that don't divide a
    midpoint bucket (768 = 3·256 admits any pow2 ≤ 256, not 512)."""
    n, t = int(n_nodes), int(tile)
    t = min(t, n)
    if t >= 1:
        t = 1 << (t.bit_length() - 1)  # largest pow2 ≤ t
    while t > 1 and n % t:
        t //= 2
    return max(t, 1)


def make_cycle_closure_tiled(n_nodes: int, tile: int = CYCLE_TILE):
    """Blocked transitive-closure kernel (ISSUE 19): same contract as
    make_cycle_closure — ``closure(adj)`` over [B, N, N] int32 0/1
    adjacency, returns (has_cycle [B] bool, closed [B, N, N]) — but
    built as blocked Floyd–Warshall over [T, T] int32 tiles so the
    live working set per step is panels, not the whole matrix, and the
    node cap rises to CYCLE_MAX_NODES_TILED.

    One pass over the N/T diagonal blocks; for pivot block k (offset
    o = k·T):

      1. close the diagonal block D = A[o:o+T, o:o+T] by repeated
         boolean squaring (ceil(log2 T) iterations — all paths that
         stay inside the pivot block);
      2. fold the closed pivot into its row panel (R ← R ∨ D*·R) and
         column panel (C ← C ∨ C·D*);
      3. A ← A ∨ C·R, streamed one [T, N] row-panel product at a time
         so the largest materialized intermediate is a panel, never
         [N, N].

    This is the textbook blocked FW schedule: after processing pivot
    k, A[i, j] holds every path whose intermediate nodes lie in blocks
    ≤ k, so the final A is the full transitive closure — identical to
    the monolithic squaring (differentially pinned in
    tests/test_cycle_tiled.py).  Soundness is monotone: entries are
    only ever OR-ed with products of existing path bits, so every set
    bit is a real path at every step.  Entries re-binarize after every
    product (jnp.minimum(·, 1)), so int32 row sums stay ≤ N — no
    overflow at any cap.

    Per-k-step residency is what the kernel-contract analyzer proves
    now (cycle_closure_tile_bytes, executed at the
    (CYCLE_MAX_NODES_TILED, CYCLE_TILE) corner); the [B, N, N] slab
    itself lives in HBM like every other chunked carry.
    """
    n, t = int(n_nodes), int(tile)
    if n < 1 or t < 1 or n % t:
        raise ValueError(f"tile {t} does not divide node bucket {n}")
    nt = n // t
    diag_iters = max(1, (max(t, 2) - 1).bit_length())

    def closure(adj):
        a0 = adj.astype(jnp.int32)
        b = a0.shape[0]

        def sq_once(_i, d):
            p = jnp.einsum("bij,bjk->bik", d, d,
                           preferred_element_type=jnp.int32)
            return jnp.minimum(d + jnp.minimum(p, 1), 1)

        def pivot(kb, a):
            o = kb * t
            d = lax.dynamic_slice(a, (0, o, o), (b, t, t))
            d = lax.fori_loop(0, diag_iters, sq_once, d)
            row = lax.dynamic_slice(a, (0, o, 0), (b, t, n))
            row = jnp.minimum(row + jnp.minimum(
                jnp.einsum("bij,bjk->bik", d, row,
                           preferred_element_type=jnp.int32), 1), 1)
            a = lax.dynamic_update_slice(a, row, (0, o, 0))
            col = lax.dynamic_slice(a, (0, 0, o), (b, n, t))
            col = jnp.minimum(col + jnp.minimum(
                jnp.einsum("bij,bjk->bik", col, d,
                           preferred_element_type=jnp.int32), 1), 1)
            a = lax.dynamic_update_slice(a, col, (0, 0, o))

            def fold(ib, a):
                io = ib * t
                ci = lax.dynamic_slice(col, (0, io, 0), (b, t, t))
                ai = lax.dynamic_slice(a, (0, io, 0), (b, t, n))
                p = jnp.einsum("bij,bjk->bik", ci, row,
                               preferred_element_type=jnp.int32)
                ai = jnp.minimum(ai + jnp.minimum(p, 1), 1)
                return lax.dynamic_update_slice(a, ai, (0, io, 0))

            return lax.fori_loop(0, nt, fold, a)

        closed = lax.fori_loop(0, nt, pivot, a0)
        diag = jnp.diagonal(closed, axis1=1, axis2=2)
        return jnp.any(diag > 0, axis=1), closed

    return jax.jit(closure)


# ----------------------------------------------------- contract bindings
# Conservative per-row resident bytes of each family's chunked carry.
# Pure arithmetic on purpose: the graftcheck kernel-contract analyzer
# (lint/flow/kernel_contract.py) executes these statically at the cap
# corners above — ONE set of bindings for every family that chunks
# through the IR, replacing the per-family duplicates.


def dense_chunk_carry_bytes(n_slots: int, n_states: int) -> int:
    """Chunked domain/mask carry. Domain (since ISSUE 41): the packed
    frontier F [2^W] of uint32 words, the states a word's bits — 4 B a
    configuration whatever S <= DENSE_MAX_STATES — + the hoisted row
    masks R [W, ceil(S / g)] words (worst style; booked at S words a
    slot) + slot registers + the events_left lane. Mask mode runs at
    S=1 and keeps its own F [2^W, 1] bool beside an int32 subset-sum
    lane [2^W]: the word term books the lane, the bool term its
    frontier, so one bound holds both families."""
    return ((1 << n_slots) * 4                 # F words | mask sums
            + (1 << n_slots)                   # mask F [2^W, 1] bool
            + n_slots * n_states * 4           # hoisted R (worst style)
            + 4 * n_slots * 4                  # slot registers (int32)
            + 8)                               # ok/dirty/events_left


def sort_chunk_carry_bytes(n_configs: int, n_slots: int) -> int:
    """Chunked sort carry: masks [C, K] uint32 + states [C] int32 +
    slot registers + flags + the events_left lane."""
    k = n_slots // 32 + 1
    return (n_configs * k * 4 + n_configs * 4   # masks + states
            + 3 * n_slots * 4 + n_slots         # slot regs + open
            + 8)                                # ok/overflow/dirty/left


def cycle_adjacency_bytes(n_nodes: int) -> int:
    """Per-row resident bytes of the cycle-closure kernel: the int32
    adjacency/closure matrix plus the squared-product buffer the einsum
    materializes (two [N, N] int32 slabs live across the while_loop
    body). Executed statically at CYCLE_MAX_NODES by the
    kernel-contract analyzer (lint/flow/kernel_contract.py)."""
    return 2 * n_nodes * n_nodes * 4


def cycle_closure_tile_bytes(n_nodes: int, tile: int) -> int:
    """Per-row resident int32 bytes of one pivot step of the blocked
    closure (make_cycle_closure_tiled): the [T, N] row panel, the
    [N, T] column panel, the closed [T, T] diagonal block, and one
    [T, N] product slab from the streamed fold.  This is the
    tile-granularity binding ISSUE 19 moves the cycle budget proof to
    — executed statically at (CYCLE_MAX_NODES_TILED, CYCLE_TILE) by
    the kernel-contract analyzer; the monolithic cycle_adjacency_bytes
    binding stays for the ≤ CYCLE_MAX_NODES arm, which still ships."""
    return (3 * tile * n_nodes + tile * tile) * 4


def cycle_closure_tiles(n_nodes: int, tile: int) -> int:
    """Tile-program count of one blocked-closure pass — bookkeeping for
    the cycle_tiles_run counter (checker/schedule.py):
    per pivot block one diagonal closure, N/T row-panel products, N/T
    column-panel products, and N/T streamed fold products of N/T tiles
    each."""
    nt = max(1, n_nodes // max(1, tile))
    return nt * (1 + 2 * nt + nt * nt)
