"""Dense-bitset frontier kernel: exact linearizability for small domains.

The sort-based kernel (ops/linear_scan.py) represents the frontier as an
explicit list of (mask, state) configurations and pays a sort-dedup per
closure round. For the workloads the reference actually runs, that is
overkill: a CAS register over a handful of values (reference
workload/register.clj:21-34 draws values from [0,5)) has a *reachable
state domain* enumerable straight from the history — the initial value
plus every written / cas-to value. When the domain S and the concurrency
window W are both small, the entire powerset-of-window × domain fits in a
**dense frontier of 2^W configurations × S states**: "some linearization
of exactly the ops in mask m ends in state s".

Since ISSUE 41 the domain family holds it **packed: F[2^W] of uint32
words, one word a configuration, bit s of word m the state s** (S ≤
DENSE_MAX_STATES 16 fits any S in one dtype; the v5e's vector unit is a
32-bit one, and a uint8 frontier read the same or slower on the chip).
Until then it was F[2^W, S] bool and a slot's flow a float32 matmul of
an [S, S] matrix; that kernel lives on in tests/test_packed_frontier.py
as the oracle. The mask family keeps its own F[2^W, 1] bool.

This is the on-device visited-*bitset* form of the search (the shape
BASELINE.json's north star names): dedup is free (a bit can only be set
once), overflow cannot happen (the array IS the configuration space), and
every kernel operation is a static shift along the configuration axis or
an elementwise integer op — no sort, no scatter, no gather, no matmul,
no convert. Measured ~10× over the sort kernel on the north-star shape
(W=5, S=6) in its bool form; it is selected automatically by the checker
whenever a model can enumerate the domain (`Model.dense_domain`) and the
2^W · S cells fit DENSE_MAX_CELLS, with the sort kernel as the
general-case fallback.

Mechanics per event (same event stream as linear_scan — packing.py):

  OPEN w:  latch (f, a, b) into slot registers, mark the slot open; the
           hoisted style packs the op's transition rows once, here:
           R[w, s] = the bitset of the states s steps to (`pack_rows`).
  closure: repeat until fixpoint (≤W sweeps): for each slot w (static
           unroll), configurations without bit w flow through the
           slot's row masks into their twins with bit w: the OR over
           the states a configuration holds of those states' rows,
           moved 2^w places along the configuration axis
           (`expand_packed`). Integer multiplies, ANDs, ORs and
           shifts only.
  FORCE w: survivors must hold bit w (mask with the bit column derived
           arithmetically from the dynamic slot id), then the bit is
           recycled by moving the bit-w=1 half onto the bit-w=0 half —
           W static slices of a zero-extended copy, selected by the
           slot (kernel_ir.force_arith, which takes a frontier of
           either representation; switch-free, ISSUE 4 — the old
           `lax.switch` evaluated all W branches under vmap; no start
           index a row, ISSUE 45 — one `dynamic_slice` with the slot's
           offset is a gather under vmap, a loop over the launch's
           rows on the chip).

The domain table `val_of[S]` is a per-history *input* (id 0 = initial
state), so one compiled kernel serves a whole batch of histories with
different value sets; padding repeats id 0, which is harmless (duplicate
ids transition identically; the search just mirrors them).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..history.packing import EncodedHistory
# The shared step-parts substrate (PR 6 tentpole): eligibility caps,
# macro-latch helpers, the arithmetic FORCE dispatch, the stream-step
# assembly and both drivers live in ops/kernel_ir.py — this module
# keeps only the dense state-representation lowering. The caps and
# helpers are re-exported here so routing layers and tests keep their
# historical import sites.
from .kernel_ir import (DENSE_MAX_CELLS, DENSE_MAX_SLOTS, DENSE_MAX_STATES,
                        MASK_DENSE_MAX_SLOTS, KernelParts,
                        batch_chunk_checker, closure_fixpoint, force_arith,
                        macro_latch_i32, make_stream_step, monolithic_check,
                        scan_unroll)
from .kernel_ir import dense_chunk_carry_bytes  # noqa: F401  (re-export)
from .kernel_ir import macro_row_ints  # noqa: F401  (re-export)


@dataclass(frozen=True)
class DensePlan:
    """How to run a batch on a dense kernel.

    kind "domain": frontier of 2^W x S cells (F[2^W] words, the states
    their bits) over an enumerated value domain;
    `val_of` [B, S] is the per-history id→value table (kernel input).
    kind "mask": frontier F[2^W] for order-independent models
    (model.mask_determined) — per-mask states are subset sums; `val_of`
    is a [B, 1] dummy so both kinds share the (events, val_of) calling
    convention through the batch/mesh plumbing.
    """

    kind: str
    n_slots: int
    n_states: int
    val_of: np.ndarray

    @property
    def kernel_tag(self) -> str:
        """Reporting label (checker results)."""
        return "dense" if self.kind == "domain" else "dense-mask"


def dense_plan(model, encs: Sequence[EncodedHistory]) -> Optional[DensePlan]:
    """Decide whether a batch can run on a dense kernel (domain mode
    first, mask mode second), or None → the general sort kernel. The
    kernel shape is the batch maximum; domain tables are padded with
    their own id-0 (initial) value."""
    if not encs:  # nothing to plan — and _pad_domains would max() over []
        return None
    W = max((e.n_slots for e in encs), default=0)
    domains = []
    for e in encs:
        d = model.dense_domain(e.events)
        if d is None:
            domains = None
            break
        domains.append(np.asarray(d, dtype=np.int32))
    if domains is not None:
        S = max((len(d) for d in domains), default=1)
        if W <= DENSE_MAX_SLOTS and S <= DENSE_MAX_STATES and \
                (1 << W) * S <= DENSE_MAX_CELLS:
            # S buckets to a power of two inside _pad_domains: domain
            # sizes drift batch to batch and each (W, S) pair is a fresh
            # XLA compile; padding states is cheap (S² sits in a tiny
            # matmul), stable shapes are not. W stays exact — its cost
            # is exponential.
            S_b, val_of = _pad_domains(domains, range(len(domains)))
            return DensePlan("domain", max(W, 1), S_b, val_of)
    if W <= MASK_DENSE_MAX_SLOTS and \
            all(model.mask_eligible(e.events) for e in encs):
        dummy = np.zeros((len(encs), 1), dtype=np.int32)
        return DensePlan("mask", max(W, 1), 1, dummy)
    return None


#: Past this event count a history counts as LONG: launch amortization
#: stops being the story and scan depth becomes it (see
#: _merge_long_groups for the round-5 policy reversal).
MERGE_MAX_EVENTS = 4096

#: Long histories merge into one launch only while the group's window
#: spread stays within this many slots of the widest member: per-step
#: cost has a b·B·2^W·S width term, so folding a W=5 history into a
#: W=12 launch would inflate its every step 128× — the depth saving
#: cannot repay that. The measured config-#4 win spans spread 3 (W
#: 6..9); beyond it, clusters launch separately (still merged within
#: each cluster).
MERGE_LONG_MAX_SPREAD = 3


def _merge_long_groups() -> bool:
    """Round-5 policy REVERSAL of per-window launches for LONG
    histories. Launches serialize on a single TPU core, so per-window
    groups pay the SUM of their scan depths (config #4: 4 groups ×
    ~15-20k events ≈ 70k sequential steps), while one merged launch at
    the widest window pays max-E once (~20k steps) at a higher
    per-step width. At config-4 frontier sizes the depth cut wins:
    interleaved in-process A/B on v5e (2026-07-31, 5 reps each):
    merged min 2.348 s / median 2.514 s vs per-window 3.187 / 3.342 —
    1.36× at min, every merged rep faster than every per-window rep.
    (The round-3 number that set the old
    policy — merged 1.9 s vs per-window 1.3 s — was a cross-process
    comparison, a methodology later proved unusable: identical benches
    span 249-677 hist/s across processes.)
    The width term is real, so merging is bounded by
    MERGE_LONG_MAX_SPREAD — and the default is TPU-ONLY: the host mesh
    is throughput-bound at these widths, so the same merge that wins
    1.36× on the chip measured config-4 CPU at 0.61 hist/s vs 1.34
    per-window (2026-07-31 CPU suite). JGRAFT_MERGE_LONG=1 forces merged
    anywhere, =0 forbids."""
    forced = os.environ.get("JGRAFT_MERGE_LONG")
    if forced is not None:
        return forced == "1"
    import jax

    return jax.default_backend() == "tpu"


def _pad_domains(domains, idxs):
    """[len(idxs), S] id→value table from per-history domains, S bucketed
    to a power of two (stable compile shapes), rows padded with their own
    id-0 (initial) value."""
    ds = [domains[i] for i in idxs]
    S = max(len(d) for d in ds)
    S_b = 1
    while S_b < S:
        S_b *= 2
    val_of = np.empty((len(ds), S_b), dtype=np.int32)
    for r, d in enumerate(ds):
        val_of[r, : len(d)] = d
        val_of[r, len(d):] = d[0]
    return S_b, val_of


@dataclass(frozen=True)
class GroupCost:
    """What one window group of SHORT histories costs a backend: a
    table of readings — seconds of `check_encoded` (pack, launch, the
    wavefront's blocking reads) for ONE group, by kernel kind, padded
    states, launch window and rows — interpolated. No closed form over
    rows, 2^W · S and steps came within 15 % of the chip's readings (its
    cost a row rises between 128 and 256 rows, sooner the wider the
    window, and the two kernels differ), so the readings are the model.

    rows: the row counts measured (launch buckets), ascending.
    ms: kind -> {S: {W: milliseconds at each of `rows`}}; a window's
        tuple may stop short of the largest counts.
    steps: kind -> the scan length (longest member's LEGACY events,
        under the default macro stream) the table was measured at; a
        group's cost scales with its own but for `fixed_ms[kind]`, what
        a group costs before its first step.
    """

    rows: tuple
    ms: dict
    steps: dict
    fixed_ms: dict

    def _ms(self, table: dict, w: int, rows: int) -> float:
        at = table[w]
        top = self.rows[len(at) - 1]
        if rows > top:  # past the table: as many times the last reading
            return at[-1] * rows / top
        return float(np.interp(rows, self.rows[:len(at)], at))

    def seconds(self, kind: str, w: int, states: int, rows: int,
                steps: int) -> float:
        """One group of `rows` (a launch bucket) at window `w`, padded
        `states`, `steps` events."""
        by_s = self.ms[kind]
        # more states than were measured: booked as the window with as
        # many cells
        while states > max(by_s):
            w, states = w + 1, states // 2
        table = by_s[min(s for s in by_s if s >= states)]
        lo, hi = min(table), max(table)
        ms = self._ms(table, min(max(w, lo), hi), rows)
        if w > hi:
            # a window past the table: the widest measured, times what
            # that cost over its narrower neighbour, a window
            ms *= (ms / self._ms(table, hi - 1, rows)) ** (w - hi)
        fixed = self.fixed_ms[kind]
        return (fixed + (ms - fixed) * steps / self.steps[kind]) / 1e3


#: One `TPU v5 lite` chip, **both halves read in one session on ISSUE
#: 45's kernel** (FORCE's down-shift as W static slices: it lowered the
#: 128-row readings by half and more and the 8-row ones by a fifth, and
#: `best_partition` minimises this table). `scripts/sweep_group_cost.py
#: run`, then `table`: medians of five warm runs of ONE group whose
#: every row is as wide as its launch — the batched closure waits for
#: its widest row —, made monotone in W and S, gaps filled from the next
#: S; the files: `chiprun_out/p45c/narrow.json` (domain, W 5-10 at S 4
#: and 8), `wide.json` (W 11-13 at S 8: **W 13 is a reading now**, no
#: longer booked from the edge) and `mask.json` (W 5-10); PERF.md
#: section 6, PR 45, call e. **A shape is booked at its launch's device
#: phase (span `launch.device`) plus the median host part of its row
#: count** (`cost_from`): with the loop over the rows gone the device
#: is under half a 128-row group's wall, and the rest of the wall reads
#: in two modes 60-90 ms apart from shape to shape, which is the
#: host's and no window's. A window now costs from the first one up (128
#: rows, S 8: 66 ms at W 5, 80 at W 8, 98 at W 10, 204 at W 12, 400 at
#: W 13, where ISSUE 41's table read 175, 177, 205, 373 and booked 533),
#: so a served batch of the partition cell (W 6-13) is three groups
#: where it was two. S 4 was read up to 128 rows (larger batches of the
#: cell hold a history of five values) and reads what S 8 does.
TPU_GROUP_COST = GroupCost(
    rows=(8, 32, 64, 128, 256, 512, 1024),
    ms={"mask": {1: {
            5: (26.2, 28.2, 36.5, 54.2, 125.5, 279.3, 842.6),
            6: (36.2, 34.9, 40.8, 58.5, 132.3, 291.3, 854.0),
            7: (39.9, 43.3, 50.2, 65.8, 188.8, 339.9, 952.9),
            8: (53.4, 56.1, 64.1, 81.8, 215.6, 388.5, 1100.3),
            9: (64.8, 74.8, 90.2, 105.2, 295.5),
            10: (97.0, 114.3, 138.2, 149.3, 428.0)}},
        "domain": {
            4: {
                5: (24.9, 33.3, 44.4, 65.5),
                6: (26.4, 35.9, 49.3, 68.3),
                7: (27.8, 36.4, 51.5, 77.5),
                8: (28.8, 37.1, 52.3, 79.9),
                9: (29.2, 40.2, 57.4, 88.5),
                10: (29.2, 41.4, 62.0, 98.4)},
            8: {
                5: (27.1, 33.7, 44.4, 66.2, 182.5, 308.6, 760.0),
                6: (28.2, 35.9, 49.3, 68.3, 182.5, 312.8, 764.4),
                7: (29.1, 36.4, 51.5, 77.5, 213.6, 354.0, 858.4),
                8: (29.1, 37.1, 52.3, 79.9, 215.8, 361.2, 881.0),
                9: (29.4, 40.2, 57.4, 88.5, 254.9),
                10: (29.5, 42.3, 62.0, 98.4, 274.9),
                11: (30.7, 48.0, 73.1, 149.8, 335.4),
                12: (32.6, 57.8, 98.4, 203.6, 513.4),
                13: (38.8, 84.2, 153.9, 400.0, 844.7)}}},
    steps={"mask": 2000, "domain": 1614},
    fixed_ms={"mask": 10.0, "domain": 14.7})

#: Off the TPU no cost is fitted and a window group is one window: the
#: host mesh is throughput-bound at these widths (merged launches
#: measured 0.61 against 1.34 hist/s, `_merge_long_groups`). A window
#: of fewer rows than this rides with the next wider one, the rule
#: every backend had before ISSUE 33: a launch and its compile are not
#: worth a handful of rows.
HOST_MIN_GROUP = 16


def _group_cost() -> Optional[GroupCost]:
    """The cost short window groups are formed by, keyed by the backend
    like `_merge_long_groups` and `hoist_transitions`: the chip's, or
    None where none is fitted (one group a window)."""
    import jax

    return TPU_GROUP_COST if jax.default_backend() == "tpu" else None


def best_partition(kind: str, windows: Sequence[tuple],
                   cost: Optional[GroupCost], shards: int = 1) -> list:
    """Partition one kind's windows into launch groups.

    `windows`: (W, rows, states, steps) per window, W ascending —
    `states` the widest domain among its rows (1 for "mask"), `steps`
    its longest history's events. Returns blocks of indices into
    `windows`, each a run of neighbours that launches as ONE group at
    its widest window.

    With a `cost` that is the partition whose summed `cost.seconds` is
    least, found exactly (a kind has a handful of windows, and a block
    is a run of neighbours: folding a window into a wider group without
    the windows between them never pays). A group's rows count as the
    launch will pad them (`schedule.launch_rows`), its states as
    `_pad_domains` will. A domain merge whose padded frontier would
    pass DENSE_MAX_CELLS is no candidate — every history here is
    dense-eligible alone and merging must never shed one — while a
    window on its own always is one (what `flush` does with it is
    today's). Ties go to fewer groups.

    Without a `cost`: one group a window, windows under HOST_MIN_GROUP
    rows pushed up into the next."""
    if cost is None:
        blocks, cur, n = [], [], 0
        for k, (_, rows, _, _) in enumerate(windows):
            cur.append(k)
            n += rows
            if n >= HOST_MIN_GROUP:
                blocks.append(cur)
                cur, n = [], 0
        return blocks + [cur] if cur else blocks
    from ..checker.schedule import launch_rows

    def seconds(i: int, j: int) -> float:
        """windows[i..j] as one group."""
        rows = sum(w[1] for w in windows[i:j + 1])
        states = 1
        while states < max(w[2] for w in windows[i:j + 1]):
            states *= 2
        cells = (1 << windows[j][0]) * states
        if kind == "domain" and j > i and cells > DENSE_MAX_CELLS:
            return float("inf")
        return cost.seconds(kind, windows[j][0], states,
                            launch_rows(rows, shards),
                            max(w[3] for w in windows[i:j + 1]))

    # best[j]: (seconds, groups, blocks) of the cheapest partition of
    # windows[:j]
    best = [(0.0, 0, [])]
    for j in range(len(windows)):
        best.append(min(
            (best[i][0] + seconds(i, j), best[i][1] + 1,
             best[i][2] + [list(range(i, j + 1))])
            for i in range(j + 1)))
    return best[-1][2]


def dense_plans_grouped(model, encs: Sequence[EncodedHistory]):
    """Route each history of a batch to its cheapest dense kernel.

    Returns (groups, rest): `groups` is [(indices, DensePlan)] over the
    dense-eligible histories, partitioned by kernel kind and then into
    WINDOW GROUPS, each launched at its widest member's window. A real
    batch's windows spread with per-history crash counts (the
    north-star batch measures W=5..8) and a step's work is exponential
    in W, but a group also costs a launch and a thousand step latencies
    before its first row: which windows share a launch is
    `best_partition`'s answer for the backend's measured `GroupCost`
    (on the chip a served 128-row batch is ONE group at W 8; off it, a
    group a window). LONG histories (> MERGE_MAX_EVENTS) keep their own
    policy, `_merge_long_groups`.
    `rest` holds the indices that need the sort-kernel ladder (window or
    domain beyond the dense caps); eligibility is per history, so one
    oversized history no longer drags the whole batch off the dense path.
    Every history's domain comes from ONE call, `model.dense_domains`:
    a model may answer it in one pass over the rows' concatenated events
    (the register), the default loops over `dense_domain`."""
    domains = model.dense_domains(encs)
    buckets: dict = {}
    rest: list = []
    for i, (e, d) in enumerate(zip(encs, domains)):
        W = max(e.n_slots, 1)
        if d is not None and W <= DENSE_MAX_SLOTS and \
                len(d) <= DENSE_MAX_STATES and \
                (1 << W) * len(d) <= DENSE_MAX_CELLS:
            buckets.setdefault(("domain", W), []).append(i)
        elif W <= MASK_DENSE_MAX_SLOTS and model.mask_eligible(e.events):
            buckets.setdefault(("mask", W), []).append(i)
        else:
            rest.append(i)
    groups: list = []

    def flush(kind, pending):
        """Emit (indices, plan) for one group, or None when the whole
        group sheds. The launch window is always recomputed from the
        group's OWN histories (never the loop's current bucket window —
        an early flush of short stragglers before a wide long-history
        bucket must not inherit the wide W; kernel cost is 2^W). Domain
        mode additionally re-checks the cell envelope: eligibility used
        each history's own W and unpadded |domain|, but the merged group
        launches at the widest W with S bucketed up to a power of two —
        which can exceed the cap (e.g. stragglers merged into a 2^10
        window with S padded 9→16 = 16384 cells, 2× the cap). The widest
        histories shed to the sort ladder rather than launch an
        oversized kernel."""
        w_eff = max(max(encs[i].n_slots for i in pending), 1)
        if kind == "mask":
            return (pending, DensePlan(
                "mask", w_eff, 1,
                np.zeros((len(pending), 1), dtype=np.int32)))
        S, val_of = _pad_domains(domains, pending)
        while (1 << w_eff) * S > DENSE_MAX_CELLS and pending:
            widest = max(pending, key=lambda i: encs[i].n_slots)
            pending.remove(widest)
            rest.append(widest)
            if pending:
                S, val_of = _pad_domains(domains, pending)
                w_eff = max(max(encs[i].n_slots for i in pending), 1)
        if not pending:
            return None
        return (pending, DensePlan("domain", w_eff, S, val_of))

    merge_long = _merge_long_groups()
    cost = _group_cost()
    shards = 1
    if cost is not None:
        # rows pad to a multiple of the placement's shard count
        from ..parallel.mesh import chunk_sharding

        mesh = getattr(chunk_sharding(), "mesh", None)
        shards = int(mesh.size) if mesh is not None else 1

    def emit(kind, pending):
        g = flush(kind, pending)
        if g is not None:
            groups.append(g)

    def emit_run(kind, run):
        """A run of neighbouring SHORT windows, as the backend's cost
        partitions it."""
        stats = [(w, len(buckets[(kind, w)]),
                  max(len(domains[i]) for i in buckets[(kind, w)])
                  if kind == "domain" else 1,
                  max(encs[i].n_events for i in buckets[(kind, w)]))
                 for w in run]
        for block in best_partition(kind, stats, cost, shards):
            emit(kind, [i for k in block for i in buckets[(kind, run[k])]])

    for kind in ("domain", "mask"):
        windows = sorted(w for k, w in buckets if k == kind)
        long_pool = [i for w in windows for i in buckets[(kind, w)]
                     if encs[i].n_events > MERGE_MAX_EVENTS] \
            if merge_long else []
        if long_pool:
            # Merge long histories of this kind into window-proximate
            # cluster launches (see _merge_long_groups). Shorts never
            # join them: a short history in a long launch would pad its
            # event stream E_long/E_short×, which no launch saving
            # repays.
            pooled = set(long_pool)
            for w in windows:
                buckets[(kind, w)] = [
                    i for i in buckets[(kind, w)] if i not in pooled]
            windows = [w for w in windows if buckets[(kind, w)]]
            by_w = sorted(long_pool, key=lambda i: encs[i].n_slots,
                          reverse=True)
            while by_w:
                w_top = encs[by_w[0]].n_slots
                cut = w_top - MERGE_LONG_MAX_SPREAD
                # Greedy take, re-checking the launch cell envelope
                # as members join (domains pad S to the cluster
                # max, pow2-bucketed): a member whose domain would
                # push 2^w_top · S_pad over the cap waits for a
                # later, narrower cluster instead of forcing flush
                # to shed the WIDEST member to the sort ladder —
                # every history here is dense-eligible alone and
                # must stay on the dense path. (A singleton always
                # fits: per-history eligibility used its own W and
                # unpadded S, and pow2 padding cannot double past
                # the cap at these sizes.)
                take, rest_pool, s_run = [], [], 1
                for i in by_w:
                    if encs[i].n_slots < cut:
                        rest_pool.append(i)
                        continue
                    s_new = max(s_run, len(domains[i])
                                if kind == "domain" else 1)
                    s_pad = 1
                    while s_pad < s_new:
                        s_pad *= 2
                    if take and (1 << w_top) * s_pad > DENSE_MAX_CELLS:
                        rest_pool.append(i)
                        continue
                    take.append(i)
                    s_run = s_new
                by_w = rest_pool
                emit(kind, take)

        run: list = []
        for w in windows:
            bucket = buckets[(kind, w)]
            if any(encs[i].n_events > MERGE_MAX_EVENTS for i in bucket):
                # A window that holds a long history (the merge above is
                # off) launches alone, the shorts before it FIRST:
                # merging them into the long launch would pad their
                # event streams to the long history's length (E
                # dominates kernel work).
                emit_run(kind, run)
                run = []
                emit(kind, bucket)
            else:
                run.append(w)
        emit_run(kind, run)
    return groups, rest


def bit_column(M: int, slot):
    """Bit `slot` of every mask m: [M] int32 for a scalar slot, [M, P]
    for slots [P]. Arithmetic on the dynamic slot id (ISSUE 45): a
    `take` from a constant [M, W] table, which this was, is a gather
    with an index a row under `vmap`."""
    ids = np.arange(M, dtype=np.int32)  # numpy's: see pack_rows
    slot = jnp.asarray(slot, jnp.int32)
    return (ids.reshape((M,) + (1,) * slot.ndim) >> slot) & 1


#: The domain frontier's word: bit s of word m = state s reachable in
#: configuration m. One dtype for every S <= DENSE_MAX_STATES: the
#: v5e's vector unit is a 32-bit one, and a uint8 frontier read the same
#: or slower on the chip (PERF.md section 6, PR 41, call a).
FRONTIER_WORD = jnp.uint32


def _fields(n_states: int):
    """(field width, rows a word) of packed row masks: g rows of one
    slot lie side by side in a word, each in a field of `width` bits
    (S rounded up to a power of two). g <= width / 2 keeps the multiply
    that spreads g state bits over the g fields free of carries."""
    width = 1
    while width < n_states:
        width *= 2
    g = 1
    while 2 * g * width <= 32 and 2 * g < width:
        g *= 2
    return width, g


def row_words(n_states: int) -> int:
    """Words that hold one slot's S row masks, g to a word."""
    return -(-n_states // _fields(n_states)[1])


def pack_rows(rows):
    """[..., S, S'] bool transition rows -> [..., ceil(S / g)] words:
    the bitset of the states s steps to (bit s' of its row) in field
    s % g of word s // g (`_fields`). Any bitset is a row: a padded
    domain repeats id 0, so a step may land on several ids."""
    S = rows.shape[-1]
    width, g = _fields(S)
    n = row_words(S)
    # constants are numpy's: a jnp one is an eager device op a trace,
    # and a key's 26 programs are traced with the GIL held (`setup_s`)
    bit = np.uint32(1) << np.arange(S, dtype=np.uint32)
    R = jnp.bitwise_or.reduce(
        jnp.where(rows, bit, np.uint32(0)), axis=-1)
    R = jnp.concatenate(
        [R, np.zeros(R.shape[:-1] + (n * g - S,), np.uint32)],
        axis=-1).reshape(R.shape[:-1] + (n, g))
    return jnp.bitwise_or.reduce(
        R << (np.arange(g, dtype=np.uint32) * np.uint32(width)), axis=-1)


def expand_packed(w: int, F, R_w, n_states: int):
    """One slot's flow over a packed frontier F [M]: configurations
    without bit w linearize op w through its row masks R_w
    (`pack_rows` of its [S, S'] rows) into their twins with bit w.

    A configuration's contribution is the OR, over the states it holds,
    of those states' rows: g state bits at a time are spread to the g
    fields of a word by one multiply, ANDed with the word that holds
    their g rows, and the fields folded by OR — 2 words a slot at S 8,
    and no operand a state (read on the chip beside a select a state
    and a reduce over a state axis: PERF.md section 6, PR 41). Moving
    the contributions 2^w places up the configuration axis is a static
    roll; the wrap lands where bit w is clear and is masked with the
    rest."""
    M = F.shape[0]
    width, g = _fields(n_states)
    c = np.uint32  # numpy constants: see pack_rows
    mult = sum(1 << (j * (width - 1)) for j in range(g))
    ones = sum(1 << (j * width) for j in range(g))
    field = (1 << width) - 1
    acc = jnp.zeros_like(F)
    for k in range(R_w.shape[0]):
        bits = (F >> c(k * g) if k else F) & c((1 << g) - 1)
        if g > 1:
            bits = (bits * c(mult)) & c(ones)
        acc = acc | (R_w[k] & (bits * c(field)))
    sh = width * g // 2
    while sh >= width:
        acc = acc | (acc >> c(sh))
        sh //= 2
    # static: all ones where configuration m holds bit w
    with_w = (-((np.arange(M, dtype=np.int64) >> w) & 1)).astype(np.uint32)
    return F | (jnp.roll(acc & c(field), 1 << w) & with_w)


def hoist_transitions() -> bool:
    """Whether the DOMAIN kernel keeps transition matrices in the scan
    carry (refreshed once per OPEN) instead of re-deriving them from
    model.jax_step inside every closure sweep. (The mask kernel's
    legality hoist won on BOTH platforms and has no style switch.)
    Backend-keyed at build time, measured 2026-07-31 both ways on idle
    hardware:

      * v5e: hoisted wins every affected config (config 4 merged
        2.415 → 2.15-2.33 s) — per
        step, fusion count is the wall and the hoist removes W
        jax_step+T builds from each sweep iteration.
      * CPU host: hoisted LOSES big at small batch (config 5 B=1
        monolithic: 3.6-4.1 s register-style vs 7.1-7.5 s hoisted,
        same host back-to-back) — the compiled scalar loop paid
        per-step carry traffic ([W,S,S] T threading + per-event row
        build) that the guarded closure never executed.

    JGRAFT_HOIST=1/0 forces either style (ablations); kernel caches
    key on the resolved value."""
    forced = os.environ.get("JGRAFT_HOIST")
    if forced is not None:
        return forced == "1"
    import jax

    return jax.default_backend() == "tpu"


def dense_step_parts(model, n_slots: int, n_states: int,
                     hoist: Optional[bool] = None,
                     macro_p: Optional[int] = None):
    """The domain kernel decomposed for chunked execution: returns
    (init, scan_step, verdict) where `init(val_of) -> carry`,
    `scan_step` is the per-event body, and `verdict(carry) ->
    (valid, overflow)`. The monolithic checker is exactly
    `verdict(lax.scan(scan_step, init(val_of), events))` — one step
    body, two drivers, so the chunked wavefront (checker/schedule.py)
    can never diverge semantically from the reference scan.

    `macro_p`: when set, `scan_step` consumes MACRO-event rows of
    3 + 4·macro_p lanes (history/packing.py macro_compact) — up to
    macro_p opens latched in one vectorized masked scatter, then the
    identical closure+FORCE the one-event-per-step stream runs. The
    batched latch reaches the same pre-FORCE register state the legacy
    stream reaches one event at a time, and closure is a reachability
    fixpoint over exactly those registers, so verdicts are bitwise
    identical (pinned by tests/test_macro_events.py); None keeps the
    legacy [E, 5] row format (the JGRAFT_MACRO_EVENTS=0 ablation).

    The frontier is packed (ISSUE 41): F [2^W] of FRONTIER_WORD, bit s
    of word m = state s reachable in configuration m; `init` sets bit 0
    of word 0. The hoisted style carries the slots' row masks
    (`pack_rows`: [W, ceil(S / g)] words) where it carried [W, S, S]
    bool matrices; the in-sweep style packs the rows it derives. Both
    styles, both stream formats and both drivers share `expand_packed`
    and the one `force_tail` below.

    Step shape notes, each a reading on one TPU v5 lite:
    (round-5) a gather-based rewrite of the bool kernel (Jacobi closure
    over one [W,M,S] gather + einsum, gather-based FORCE) measured ~2×
    SLOWER than the butterfly form (config-4 5.2 s vs 2.4 s, counter
    suite 12.3 s vs 7.0 s, same session) — TPU gathers at these tiny
    shapes cost more than the fusion count they save, which is why the
    module docstring says "no sort, no scatter, no gather".
    (ISSUE 41) one group of 1k-op register histories, every row as wide
    as its launch, S 8, ms at 128 / 8 rows (PERF.md section 6, PR 41,
    calls a and a2; five-run medians): the bool frontier with a float
    matmul a slot (the kernel this replaced) W 12 1,609 / 444, W 10 603
    / 127, W 8 251 / 59; the packed frontier with a select a state W 12
    429 / 61, W 10 311 / 52, W 8 185 / 47; with a reduce over a state
    axis W 12 1,289 / 58, W 10 476 / 43, W 8 185 / 38; **with the
    multiply that spreads g states over a word's fields (this one) W 12
    356 / 43, W 10 285 / 39, W 8 177 / 37**. A butterfly reshape in the
    roll's place read 596 against 545 (W 12, 128 rows, a select a
    state), a uint8 word 604 against 545. A compiled W 12 sweep holds
    10 fusions, 15 slices and a copy where the bool kernel's held 36
    fusions (12 of them the matmuls, lowered to convolutions), 25
    copies and 10 slices.
    The transition placement (carry-hoisted vs in-sweep) is
    backend-keyed: see hoist_transitions()."""
    if hoist is None:
        hoist = hoist_transitions()
    W, S = int(n_slots), int(n_states)
    M = 1 << W
    slot_ids = jnp.arange(W, dtype=jnp.int32)
    word = np.uint32  # FRONTIER_WORD's numpy twin, for constants
    n_words = row_words(S)

    # The two carry styles (hoist_transitions) differ ONLY in how a
    # slot's row masks are produced — everything else (OPEN latch,
    # dirty gating, closure, FORCE kill+recycle, ok accounting) is the
    # shared scan skeleton below, so a semantic fix can never apply to
    # one style and miss the other.
    if hoist:
        extra0 = (jnp.zeros((W, n_words), FRONTIER_WORD),)

        def style_update(extra, upd, f, a, b, val_of):
            (R,) = extra
            ns, legal = model.jax_step(val_of, f, a, b)
            row = pack_rows((ns[:, None] == val_of[None, :]) &
                            legal[:, None])               # [n_words]
            return (jnp.where(upd[:, None], row[None], R),)

        def style_macro_latch(extra, eq, upd, pf, pa, pb, val_of):
            # Per-payload row masks, selected into the slot axis by the
            # (at-most-one-match) eq matrix — the batched twin of
            # style_update's single-row write.
            (R,) = extra
            ns, legal = jax.vmap(
                lambda f_, a_, b_: model.jax_step(val_of, f_, a_, b_)
            )(pf, pa, pb)                                 # [P, S] each
            rows = pack_rows((ns[:, :, None] == val_of[None, None, :]) &
                             legal[:, :, None])     # [P, n_words]
            Rnew = jnp.bitwise_or.reduce(
                jnp.where(eq[:, :, None], rows[None],
                          word(0)), axis=1)        # [W, n_words]
            return (jnp.where(upd[:, None], Rnew, R),)

        def style_sweep(extra, slot_open, val_of):
            (R,) = extra
            Re = jnp.where(slot_open[:, None], R, word(0))

            def sweep(F):  # static unroll; expansions chain w ascending
                for w in range(W):
                    F = expand_packed(w, F, Re[w], S)
                return F

            return sweep
    else:
        extra0 = (jnp.zeros((W,), jnp.int32), jnp.zeros((W,), jnp.int32),
                  jnp.zeros((W,), jnp.int32))

        def style_update(extra, upd, f, a, b, val_of):
            sf, sa, sb = extra
            return (jnp.where(upd, f, sf), jnp.where(upd, a, sa),
                    jnp.where(upd, b, sb))

        def style_macro_latch(extra, eq, upd, pf, pa, pb, val_of):
            sf, sa, sb = extra
            return (macro_latch_i32(eq, upd, sf, pf),
                    macro_latch_i32(eq, upd, sa, pa),
                    macro_latch_i32(eq, upd, sb, pb))

        def style_sweep(extra, slot_open, val_of):
            sf, sa, sb = extra

            def sweep(F):  # static unroll; expansions chain w ascending
                for w in range(W):
                    ns, legal = model.jax_step(val_of, sf[w], sa[w],
                                               sb[w])
                    R_w = pack_rows((ns[:, None] == val_of[None, :]) &
                                    legal[:, None] & slot_open[w])
                    F = expand_packed(w, F, R_w, S)
                return F

            return sweep

    # IR hooks (ops/kernel_ir.make_stream_step): the stream decode and
    # latch-mask math live in the IR; only the dense state lowering —
    # register/transition latch, the closure sweep, the frontier FORCE —
    # is defined here.
    def latch(carry, slot, f, a, b, is_open, upd):
        F, extra, slot_open, ok, dirty, val_of = carry
        extra = style_update(extra, upd, f, a, b, val_of)
        slot_open = jnp.where(upd, True, slot_open)
        dirty = dirty | is_open
        return (F, extra, slot_open, ok, dirty, val_of)

    def macro_latch(carry, pslot, pf, pa, pb, valid, n, eq, upd):
        # Vectorized multi-slot latch: ≤P opens masked-scattered into
        # the slot registers in one step.
        F, extra, slot_open, ok, dirty, val_of = carry
        extra = style_macro_latch(extra, eq, upd, pf, pa, pb, val_of)
        slot_open = slot_open | upd
        dirty = dirty | (n > 0)
        return (F, extra, slot_open, ok, dirty, val_of)

    def force_tail(carry, is_force, slot):
        """Shared closure+FORCE tail: identical for the legacy and
        macro streams (the whole soundness argument — the latch phases
        reach the same registers, then run THIS same code). Closure
        runs only when an OPEN happened since the last one: a closed
        frontier stays closed under FORCE kill+clear (extensions of a
        surviving config are supersets, so they survived and cleared
        too), so back-to-back completions skip the sweeps entirely."""
        F, extra, slot_open, ok, dirty, val_of = carry
        F = closure_fixpoint(W, style_sweep(extra, slot_open, val_of),
                             F, is_force & dirty)
        dirty = dirty & ~is_force
        F_forced, alive = force_arith(F, jnp.clip(slot, 0, W - 1))
        F = jnp.where(is_force, F_forced, F)
        ok = ok & (~is_force | alive)
        slot_open = slot_open & ~((slot_ids == slot) & is_force)
        return (F, extra, slot_open, ok, dirty, val_of)

    scan_step = make_stream_step(W, latch, macro_latch, force_tail,
                                 macro_p)

    def init(val_of):
        F = jnp.zeros((M,), FRONTIER_WORD).at[0].set(1)
        return (
            F, extra0, jnp.zeros((W,), bool),
            jnp.bool_(True), jnp.bool_(False), val_of,
        )

    def verdict(carry):
        # The dense frontier cannot overflow: the array is the whole
        # configuration space. Second output mirrors the sort kernel's
        # (valid, overflow) contract.
        return carry[3], jnp.bool_(False)

    return init, scan_step, verdict


def make_dense_history_checker(model, n_slots: int, n_states: int,
                               hoist: Optional[bool] = None,
                               macro_p: Optional[int] = None):
    """Build fn(events [E,5], val_of [S]) -> (valid, overflow=False)
    (macro_p: [E_mac, 3+4·P] macro rows instead). See
    `dense_step_parts` for the kernel mechanics."""
    init, scan_step, verdict = dense_step_parts(model, n_slots, n_states,
                                                hoist, macro_p)
    return monolithic_check(KernelParts(init, scan_step, verdict,
                                        n_operands=1))


def mask_step_parts(model, n_slots: int, macro_p: Optional[int] = None):
    """Mask-mode kernel decomposed for chunked execution — same
    (init, scan_step, verdict) contract as `dense_step_parts` (incl.
    the `macro_p` macro-event stream mode and its bitwise-identity
    argument); the calling-convention dummy `val_of` is accepted (and
    ignored) by `init` so both dense kinds share one chunk-driver
    signature.

    Mask-mode kernel for order-independent models (counter): the
    frontier is a bare bitset F[2^W] — config m's state is
    base + sums[m], where `sums` holds the subset sum of the open slots'
    deltas (maintained incrementally at OPEN/FORCE with one [M] op) and
    `base` absorbs the delta of every retired op. Legality reuses the
    model's own vectorized jax_step on the derived state vector.

    Returns fn(events [E,5], val_of [1] ignored) -> (valid, False) — the
    dummy second operand keeps both dense kinds on one calling convention
    through the batch/mesh plumbing. The frontier is carried as [M, 1] so
    the force branches are shared with the domain kernel."""
    W = int(n_slots)
    M = 1 << W
    slot_ids = jnp.arange(W, dtype=jnp.int32)

    def expand_w(w, F, legal_all):
        Fb = F.reshape(M >> (w + 1), 2, 1 << w, 1)
        Lb = legal_all[w].reshape(M >> (w + 1), 2, 1 << w)
        grown = Fb[:, 1] | (Fb[:, 0] & Lb[:, 0][..., None])
        return jnp.concatenate([Fb[:, :1], grown[:, None]],
                               axis=1).reshape(M, 1)

    def force_tail(carry, is_force, slot):
        """Shared closure+FORCE tail (identical for legacy and macro
        streams; see dense_step_parts)."""
        (F, base, sums, slot_delta, slot_f, slot_a, slot_b, slot_open,
         ok, dirty) = carry
        # Per-slot legality over ALL M config states at once: state and
        # slot registers are closure-invariant, so this lifts the
        # model.jax_step calls out of the fixpoint loop entirely (the
        # old sweep re-evaluated them W times per iteration). [W, M].
        state = base + sums
        legal_all = jax.vmap(
            lambda f_, a_, b_: (model.jax_step(state, f_, a_, b_)[1])
        )(slot_f, slot_a, slot_b) & slot_open[:, None]

        def sweep(F):
            for w in range(W):
                F = expand_w(w, F, legal_all)
            return F

        # Closure only when dirtied by an OPEN since the last closure
        # (see the domain kernel's force_tail for why that is sound).
        F = closure_fixpoint(W, sweep, F, is_force & dirty)
        dirty = dirty & ~is_force

        F_forced, alive = force_arith(F, jnp.clip(slot, 0, W - 1))
        F = jnp.where(is_force, F_forced, F)
        ok = ok & (~is_force | alive)
        # Retire the forced op: its delta is now part of every
        # survivor's permanent prefix (base), and its slot leaves the
        # open set.
        onehot = slot_ids == slot
        col = bit_column(M, jnp.clip(slot, 0, W - 1))         # [M]
        old_d = jnp.sum(jnp.where(onehot, slot_delta, 0))
        base = base + jnp.where(is_force, old_d, 0)
        sums = jnp.where(is_force, sums - col * old_d, sums)
        slot_delta = jnp.where(onehot & is_force, 0, slot_delta)
        slot_open = slot_open & ~(onehot & is_force)
        return (F, base, sums, slot_delta, slot_f, slot_a, slot_b,
                slot_open, ok, dirty)

    def latch(carry, slot, f, a, b, is_open, upd):
        (F, base, sums, slot_delta, slot_f, slot_a, slot_b,
         slot_open, ok, dirty) = carry
        onehot = slot_ids == slot
        slot_f = jnp.where(upd, f, slot_f)
        slot_a = jnp.where(upd, a, slot_a)
        slot_b = jnp.where(upd, b, slot_b)
        slot_open = jnp.where(upd, True, slot_open)
        dirty = dirty | is_open
        # Maintain sums[m] = Σ_w bit_w(m) · slot_delta[w] as slot
        # w's delta changes from its stale value to this op's.
        col = bit_column(M, jnp.clip(slot, 0, W - 1))
        old_d = jnp.sum(jnp.where(onehot, slot_delta, 0))
        new_d = model.mask_delta(f, a, b)
        sums = jnp.where(is_open, sums + col * (new_d - old_d), sums)
        slot_delta = jnp.where(upd, new_d, slot_delta)
        return (F, base, sums, slot_delta, slot_f, slot_a, slot_b,
                slot_open, ok, dirty)

    def macro_latch(carry, pslot, pf, pa, pb, valid, n, eq, upd):
        (F, base, sums, slot_delta, slot_f, slot_a, slot_b,
         slot_open, ok, dirty) = carry
        sel = eq.astype(jnp.int32)
        # Pre-latch deltas of the opened slots (0 in practice — a
        # recycled slot's delta was zeroed at its FORCE — but the
        # legacy stream computes the general form, so mirror it).
        old_d = (sel * slot_delta[:, None]).sum(0)           # [P]
        new_d = jax.vmap(model.mask_delta)(pf, pa, pb)       # [P]
        slot_f = macro_latch_i32(eq, upd, slot_f, pf)
        slot_a = macro_latch_i32(eq, upd, slot_a, pa)
        slot_b = macro_latch_i32(eq, upd, slot_b, pb)
        slot_open = slot_open | upd
        dirty = dirty | (n > 0)
        cols = bit_column(M, jnp.clip(pslot, 0, W - 1))      # [M, P]
        sums = sums + (cols * jnp.where(valid, new_d - old_d,
                                        0)[None, :]).sum(axis=1)
        slot_delta = macro_latch_i32(eq, upd, slot_delta, new_d)
        return (F, base, sums, slot_delta, slot_f, slot_a, slot_b,
                slot_open, ok, dirty)

    scan_step = make_stream_step(W, latch, macro_latch, force_tail,
                                 macro_p)

    def init(val_of):
        del val_of  # calling-convention dummy (see docstring)
        F = jnp.zeros((M, 1), dtype=bool).at[0, 0].set(True)
        return (
            F, jnp.int32(model.init_state()),
            jnp.zeros((M,), jnp.int32), jnp.zeros((W,), jnp.int32),
            jnp.zeros((W,), jnp.int32), jnp.zeros((W,), jnp.int32),
            jnp.zeros((W,), jnp.int32), jnp.zeros((W,), bool),
            jnp.bool_(True), jnp.bool_(False),
        )

    def verdict(carry):
        return carry[8], jnp.bool_(False)

    return init, scan_step, verdict


def make_mask_dense_history_checker(model, n_slots: int,
                                    macro_p: Optional[int] = None):
    """fn(events [E,5], val_of [1] ignored) -> (valid, False); see
    `mask_step_parts` for the kernel mechanics."""
    init, scan_step, verdict = mask_step_parts(model, n_slots, macro_p)
    return monolithic_check(KernelParts(init, scan_step, verdict,
                                        n_operands=1))


def make_dense_single_checker(model, kind: str, n_slots: int,
                              n_states: int,
                              macro_p: Optional[int] = None):
    """Unified single-history factory: fn(events [E,5], val_of [S])
    (macro_p: macro rows of 3+4·P lanes instead of [E,5])."""
    if kind == "mask":
        return make_mask_dense_history_checker(model, n_slots, macro_p)
    return make_dense_history_checker(model, n_slots, n_states,
                                      macro_p=macro_p)


_KERNEL_CACHE: dict = {}


def make_dense_batch_checker(model, kind: str, n_slots: int, n_states: int,
                             jit: bool = True,
                             macro_p: Optional[int] = None):
    """vmapped: fn(events [B,E,5], val_of [B,S]) -> (valid[B], overflow[B]).
    `macro_p` selects the macro-event row format (and keys the cache —
    a P bucket is a distinct compiled shape, like rows/events)."""
    # scan_unroll() and hoist_transitions() key the cache: the build
    # closures resolve them at trace time, so an env change
    # mid-process (ablation sweeps) must map to a distinct compiled
    # kernel.
    key = (*model.cache_key(), kind, int(n_slots), int(n_states), jit,
           scan_unroll(), hoist_transitions(), macro_p)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        single = make_dense_single_checker(model, kind, n_slots, n_states,
                                           macro_p)
        fn = jax.vmap(single)
        if jit:
            fn = jax.jit(fn)
        _KERNEL_CACHE[key] = fn
    return fn


def make_dense_chunk_checker(model, kind: str, n_slots: int, n_states: int,
                             jit: bool = True, mesh=None,
                             macro_p: Optional[int] = None):
    """Chunked twin of `make_dense_batch_checker` for the wavefront
    scheduler (checker/schedule.py). `macro_p` selects the macro-event
    stream (events are then [B, chunk, 3+4·P] macro rows and `n_events`
    counts MACRO rows — the scheduler's exhaustion/span math already
    runs on whatever counts the launch carries). Returns
    (init_fn, step_fn):

      init_fn(val_of [B,S], n_events [B] int32) -> carry (pytree,
          batch-leading: the per-row scan carry + an `events_left` lane)
      step_fn(carry, events [B,chunk,5]) -> (carry',
          decided [B], exhausted [B], ok [B], overflow [B])

    `decided` = the row's verdict is already certain mid-scan. For the
    dense kernels that is exactly `~ok`: `ok` is monotone (it only ever
    ANDs in new conditions) and a dead frontier stays dead — every
    subsequent event is a no-op on an all-false F — so an invalid row's
    (ok, overflow) pair is frozen the moment it turns invalid.
    `exhausted` = the row's real events are all consumed (the remaining
    schedule is EV_PAD no-ops), so the current (ok, overflow) IS the
    final verdict. Either flag makes the row safe to evict: eviction
    only ever removes rows whose verdict is certain (the soundness
    contract in checker/linearizable.py is untouched).

    Chaining `step_fn` over E/chunk chunks applies the identical
    `scan_step` sequence as the monolithic `lax.scan`, so verdicts are
    bitwise-identical by construction (pinned by tests/test_chunked_scan
    differential tests).

    `mesh`: when given, both fns are wrapped in an explicit `shard_map`
    over the batch axis (pytree-prefix P(axis) specs; every carry leaf
    is batch-leading by vmap construction). Relying on jit's GSPMD
    sharding propagation instead *placed* the carry sharded but
    compiled a ~3x slower per-chunk program than the legacy shard_map
    path on the CPU mesh (probe: 5.5 s propagated vs 1.6 s shard_map
    vs 1.5 s legacy whole-scan on one 256x512 group) — the execution
    shape must be explicit, not inferred. Callers pad the batch to a
    multiple of the mesh size (schedule._bucket_launch_rows)."""
    key = ("chunk", *model.cache_key(), kind, int(n_slots), int(n_states),
           jit, scan_unroll(), hoist_transitions(), mesh, macro_p)
    fns = _KERNEL_CACHE.get(key)
    if fns is None:
        parts = (mask_step_parts(model, n_slots, macro_p)
                 if kind == "mask"
                 else dense_step_parts(model, n_slots, n_states,
                                       macro_p=macro_p))
        init, scan_step, verdict = parts
        fns = batch_chunk_checker(
            KernelParts(init, scan_step, verdict, n_operands=1),
            mesh=mesh, jit=jit)
        _KERNEL_CACHE[key] = fns
    return fns
