"""Batched NumPy certifier core (ISSUE 15 tentpole (b)).

`consistency.certify_encoded` decides most rows at the lin rung and the
weak rungs, one row at a time, in pure Python — after PR 14 it IS the
production hot path, so its per-`model.step` interpreter overhead is
the fleet's wall clock. This module runs the certifier's GREEDY path
(flips == 0: direct commits, eager read-only commits, sweeps, and
first-choice candidate commits — everything short of a backtracking
RESTORE) vectorized across a whole batch of rows with columnar
`Model.step` twins (`Model.step_columnar`, numpy arrays over the batch
axis), falling back row-by-row to the scalar engine the moment a row
would need a restore (a dead end), hits anything the columnar pass
cannot faithfully mirror, or exceeds the shape caps.

Equivalence contract (doc/checker-design.md §17): for every row the
outcome triple is IDENTICAL to ``certify_encoded(enc, model,
max_steps=...)`` —

* a row the batch scan completes certified returns ``(True, "greedy",
  0)``, and the scalar engine would have returned exactly that: the
  scan mirrors the scalar commit rules step for step (eager read-only
  commits at OPEN, post-commit sweeps, direct FORCE commits, and the
  value-guided candidate ordering ``(enables, will-be-forced, open
  order)`` including the 1-step lookahead and the enable/observe
  bitmask short-circuit), and a scalar run that never restores a
  choice point never reads its stack — so the two paths traverse the
  same state sequence and count the same `model.step` calls;
* a row whose mirrored step count exceeds its abort budget returns
  ``(False, None, 0)`` — the scalar wrapper aborts at the same
  cumulative count (counts are monotone, so "exceeds anywhere" equals
  "exceeds at the same totals");
* every other row — a dead end (no legal candidate at an uncommitted
  FORCE), a malformed stream, shape caps — re-runs the SCALAR engine
  from scratch, which owns backtracking, flip budgets, and error
  behavior, so tiers ``backtrack``/undecided and every raise are
  byte-for-byte the scalar's.

``JGRAFT_CERTIFY_BATCH=0`` disables the batch pass entirely (the
ablation/differential arm: every row takes today's scalar engine);
``JGRAFT_CERTIFY_BATCH_MIN`` is the engagement floor (default 96 rows
— below it the numpy pass costs more than it saves; tests pin identity
by forcing 1). A measured per-bucket gate (the PR-14 idiom:
`certify_batch_min_hit` / `certify_batch_min_obs`) routes
backtrack-dominated buckets scalar-first once their observed
batch-decided fraction proves the scan is pure overhead there —
routing only, never verdicts. Every knob parses via
`platform.env_int`/`env_float` (garbage never crashes an importer).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..history.packing import (EV_FORCE, EV_OPEN, EncodedHistory,
                               bucket_rows)
from ..platform import env_float, env_int

#: Window cap for the slot tables: a row holding more concurrent slots
#: than this (a pathologically crash-polluted stream) routes scalar —
#: the per-slot sweep/candidate loops cost O(S) numpy passes per event.
_SLOT_CAP = 64

#: Default engagement floor (rows). The batch pass costs ~O(#FORCEs)
#: numpy call rounds regardless of B while the scalar engine costs
#: O(B·E) interpreter steps, so the crossover is a nearly E-independent
#: row count: measured on the 1-CPU host it sits at ~96-128 rows for
#: the happy-path families (queue at 200-op streams: 0.27x at B=16,
#: 1.17x at B=128, 1.73x at B=256). The floor therefore aims the core
#: at the bulk surfaces (campaign batches, rung ladders, big submissions)
#: and keeps small graftd requests on the scalar engine they already
#: win.
_DEFAULT_MIN_ROWS = 96

# Row status codes for the scan.
_ACTIVE, _CERT, _FALLBACK, _ABORT = 0, 1, 2, 3

#: "Unbounded" abort-budget sentinel (rows with no max_steps).
_NO_BUDGET = np.int64(1) << 62

#: Composite candidate-ordering key: (enables, not-forced, open-order
#: id) packed into one int64 so argmin over the slot axis mirrors the
#: scalar sort on ``(0, enables, forced_rank, k)``.
_KEY_ENABLES = np.int64(1) << 40
_KEY_OPTIONAL = np.int64(1) << 39


def certify_batch_on() -> bool:
    """Whether the batched certifier core fronts the scalar engine.
    ``JGRAFT_CERTIFY_BATCH=0`` restores the row-by-row scalar loop
    everywhere (outcomes are identical either way — pinned by the
    differential tests; this is the A/B arm)."""
    return env_int("JGRAFT_CERTIFY_BATCH", 1, minimum=0) != 0


def certify_batch_min_rows() -> int:
    """Engagement floor (``JGRAFT_CERTIFY_BATCH_MIN``, default 96)."""
    return env_int("JGRAFT_CERTIFY_BATCH_MIN", _DEFAULT_MIN_ROWS,
                   minimum=1)


def certify_batch_min_hit() -> float:
    """Measured-gating floor (``JGRAFT_CERTIFY_BATCH_MIN_HIT``, default
    0.25): a bucket whose observed batch-decided fraction (rows the
    scan settled itself — certified or aborted — without a scalar
    re-run) sits below this routes scalar-first. The backtrack-
    dominated families (register at long streams: ~all rows need a
    restore) otherwise pay the full vectorized scan AND the scalar
    engine per row — measured 0.76x there, vs 2.0-2.5x on the
    happy-path families. Routing only, never verdicts — the same
    stance as `autotune.lin_fastpath_route`."""
    return env_float("JGRAFT_CERTIFY_BATCH_MIN_HIT", 0.25, minimum=0.0)


def certify_batch_min_obs() -> int:
    """Rows a bucket must be observed over before the hit-rate gate may
    route it scalar-first (``JGRAFT_CERTIFY_BATCH_MIN_OBS``, default
    64): trying IS measuring, so unknown buckets always try."""
    return env_int("JGRAFT_CERTIFY_BATCH_MIN_OBS", 64, minimum=1)


# Process-local measured gate, keyed like `autotune.lin_fastpath_sig`
# (family x pow2+midpoint event bucket — hit-rate is a property of the
# workload family; fragmenting by window would starve the gate). In-
# memory only, unlike the lin gate's fingerprint store: the batch core
# runs UNDER that gate, engages per call, and must stay deterministic
# under pytest where the autotune store is off.
_GATE_LOCK = threading.Lock()
_GATE: Dict[tuple, List[int]] = {}   # sig -> [rows_observed, hits]


def _gate_sig(model, enc: EncodedHistory) -> tuple:
    return (type(model).__name__, bucket_rows(max(enc.n_events, 1), 32))


def _gate_allows(sig: tuple) -> bool:
    with _GATE_LOCK:
        rows, hits = _GATE.get(sig, (0, 0))
    if rows < certify_batch_min_obs():
        return True
    return hits / rows >= certify_batch_min_hit()


def _gate_observe(sig: tuple, rows: int, hits: int) -> None:
    with _GATE_LOCK:
        rec = _GATE.setdefault(sig, [0, 0])
        rec[0] += rows
        rec[1] += hits


def reset_gate() -> None:
    """Forget every measured bucket (tests + the A/B harness: a cold
    gate re-observes from scratch)."""
    with _GATE_LOCK:
        _GATE.clear()


def certify_many(encs: Sequence[EncodedHistory], model,
                 max_steps=None, budget: Optional[int] = None
                 ) -> List[Tuple[bool, Optional[str], int]]:
    """Batch entry: per-row ``(certified, tier, flips)`` triples,
    outcome-identical to calling :func:`..consistency.certify_encoded`
    per row with the same per-row ``max_steps`` (scalar | sequence |
    None) and ``budget``. Routes eligible rows through the vectorized
    greedy scan and everything else — ineligible rows, fallback rows —
    through the scalar engine."""
    from .consistency import certify_encoded

    n = len(encs)
    if isinstance(max_steps, (list, tuple, np.ndarray)):
        ms_list = [None if m is None or m <= 0 else int(m)
                   for m in max_steps]
    else:
        ms_list = [None if max_steps is None or max_steps <= 0
                   else int(max_steps)] * n

    results: List = [None] * n
    batch_idx: List[int] = []
    if certify_batch_on() and getattr(model, "step_columnar", None):
        batch_idx = [i for i in range(n)
                     if encs[i].n_events > 0
                     and encs[i].n_slots <= _SLOT_CAP
                     and _gate_allows(_gate_sig(model, encs[i]))]
        if len(batch_idx) < certify_batch_min_rows():
            batch_idx = []
    if batch_idx:
        status = _batch_scan([encs[i] for i in batch_idx], model,
                             [ms_list[i] for i in batch_idx])
        obs: Dict[tuple, List[int]] = {}
        for j, i in enumerate(batch_idx):
            if status[j] == _CERT:
                results[i] = (True, "greedy", 0)
            elif status[j] == _ABORT:
                results[i] = (False, None, 0)
            # _FALLBACK rows re-run scalar below
            rec = obs.setdefault(_gate_sig(model, encs[i]), [0, 0])
            rec[0] += 1
            # an ABORT is a batch win too: the scan settled the row
            # (same abort the scalar wrapper reaches) without a
            # scalar re-run
            rec[1] += int(status[j] != _FALLBACK)
        for sig, (rows, hits) in obs.items():
            _gate_observe(sig, rows, hits)
    for i in range(n):
        if results[i] is None:
            results[i] = certify_encoded(encs[i], model, budget=budget,
                                         max_steps=ms_list[i])
    return results


def _decode_forced(events: np.ndarray) -> np.ndarray:
    """Per-OPEN-event will-this-op-ever-be-FORCEd flags, vectorized.

    Within one slot, opens and forces strictly alternate (a slot is
    recycled only by a FORCE; a crashed op holds its slot forever), so
    the i-th open in a slot is forced iff the slot sees at least i+1
    forces. Returns an int8 array over EVENTS (1 at forced OPEN rows)."""
    et = events[:, 0]
    slots = events[:, 1]
    out = np.zeros(len(events), dtype=np.int8)
    open_pos = np.flatnonzero(et == EV_OPEN)
    force_pos = np.flatnonzero(et == EV_FORCE)
    if not len(open_pos):
        return out
    n_slots = int(slots.max()) + 1 if len(slots) else 1
    force_count = np.bincount(slots[force_pos], minlength=n_slots)
    # rank of each open within its slot (opens are in stream order)
    oslots = slots[open_pos]
    order = np.argsort(oslots, kind="stable")
    sorted_slots = oslots[order]
    starts = np.searchsorted(sorted_slots, np.arange(n_slots), "left")
    rank_sorted = np.arange(len(open_pos)) - starts[sorted_slots]
    rank = np.empty(len(open_pos), dtype=np.int64)
    rank[order] = rank_sorted
    out[open_pos] = (rank < force_count[oslots]).astype(np.int8)
    return out


def _row_guide(model, events: np.ndarray, forced_at_open: np.ndarray):
    """Per-row value-guide masks aligned with OPEN events, via the
    scalar `_value_guide_masks` (one python pass per row; models
    without the enable/observe hooks answer None after one op, and a
    too-wide domain bails at ~63 distinct values, so the pass is cheap
    exactly where it is useless). Returns (ok, em_at_event, om_at_event)
    with int64 masks (zeros when the guide is off)."""
    from .consistency import _value_guide_masks

    open_pos = np.flatnonzero(events[:, 0] == EV_OPEN)
    ops = [tuple(r) for r in events[open_pos][:, 2:5].tolist()]
    forced = [bool(v) for v in forced_at_open[open_pos].tolist()]
    em_ev = np.zeros(len(events), dtype=np.int64)
    om_ev = np.zeros(len(events), dtype=np.int64)
    guide = _value_guide_masks(model, ops, forced)
    if guide is None:
        return False, em_ev, om_ev
    em_ev[open_pos] = guide[0]
    om_ev[open_pos] = guide[1]
    return True, em_ev, om_ev


def _batch_scan(encs: Sequence[EncodedHistory], model,
                ms_list: Sequence[Optional[int]]) -> np.ndarray:
    """The vectorized greedy scan. Returns per-row status codes
    (_CERT / _FALLBACK / _ABORT). See the module docstring for the
    equivalence argument; the step-count bookkeeping deliberately
    mirrors the scalar engine's wrapper call for call.

    Iteration shape: the scalar engine's state only changes at commits
    (mutator FORCEs and candidate commits) — between two FORCEs every
    event is an OPEN (or PAD), eager read-only probes all evaluate at
    the SAME state, and slots within one open run are distinct (a slot
    is recycled only by a FORCE; `macro_compact` leans on the same
    fact) — so each outer iteration advances every active row through
    its ENTIRE current open run as one ragged gather/scatter plus one
    columnar step call, then handles one FORCE (or one candidate
    commit at it). The trip count is therefore ~#FORCEs + #candidate
    commits, not #events, which is what makes the numpy pass win on
    the host."""
    B = len(encs)
    E = max(e.n_events for e in encs)
    S = max(max(e.n_slots for e in encs), 1)
    step = model.step_columnar
    # read-only opcode lookup table (encoder-produced fcodes are dense
    # and bounded by the model's n_fcodes; clip guards hand-built junk)
    n_f = int(getattr(model, "n_fcodes", 0) or 0) + 1
    ro_lut = np.zeros(n_f + 1, dtype=bool)
    for fc in (getattr(model, "readonly_fcodes", ()) or ()):
        if 0 <= int(fc) < n_f:
            ro_lut[int(fc)] = True
    slot_col = np.arange(S, dtype=np.int64)[None, :]

    ev = np.zeros((B, E, 5), dtype=np.int32)
    n_ev = np.zeros(B, dtype=np.int64)
    forced_at = np.zeros((B, E), dtype=np.int8)
    em_at = np.zeros((B, E), dtype=np.int64)
    om_at = np.zeros((B, E), dtype=np.int64)
    gok = np.zeros(B, dtype=bool)
    # next FORCE position at-or-after each event position (per row)
    nf_at = np.zeros((B, E + 1), dtype=np.int64)
    n_ops_total = 0
    for i, e in enumerate(encs):
        ne = e.n_events
        ev[i, :ne] = e.events
        n_ev[i] = ne
        fa = _decode_forced(e.events)
        forced_at[i, :ne] = fa
        gok[i], em_at[i, :ne], om_at[i, :ne] = \
            _row_guide(model, e.events, fa)
        fpos = np.flatnonzero(e.events[:, 0] == EV_FORCE)
        nxt = np.searchsorted(fpos, np.arange(ne + 1), side="left")
        nf_at[i, :ne + 1] = np.where(nxt < len(fpos),
                                     fpos[np.minimum(nxt, len(fpos) - 1)]
                                     if len(fpos) else ne, ne)
        nf_at[i, ne + 1:] = ne
        n_ops_total = max(n_ops_total, e.n_ops)

    ms = np.full(B, _NO_BUDGET, dtype=np.int64)
    for i, m in enumerate(ms_list):
        if m is not None:
            ms[i] = m
    # with no abort budget anywhere, the mirrored step accounting can
    # be skipped wholesale (the weak-rung apply_rung path)
    any_budget = bool((ms < _NO_BUDGET).any())

    rows = np.arange(B)
    pos = np.zeros(B, dtype=np.int64)
    state = np.full(B, np.int32(model.init_state()), dtype=np.int32)
    count = np.zeros(B, dtype=np.int64)
    status = np.zeros(B, dtype=np.int8)
    kcnt = np.zeros(B, dtype=np.int64)

    occ = np.zeros((B, S), dtype=bool)       # slot holds a live op
    sdone = np.zeros((B, S), dtype=bool)     # that op already committed
    sro = np.zeros((B, S), dtype=bool)       # read-only opcode
    sforced = np.zeros((B, S), dtype=bool)   # op will see a FORCE
    sf = np.zeros((B, S), dtype=np.int32)
    sa = np.zeros((B, S), dtype=np.int32)
    sb = np.zeros((B, S), dtype=np.int32)
    sk = np.zeros((B, S), dtype=np.int64)    # open-order op id
    sem = np.zeros((B, S), dtype=np.int64)
    som = np.zeros((B, S), dtype=np.int64)

    def abort_check(m):
        a = m & (count > ms)
        status[a] = _ABORT
        return m & ~a

    def sweep(m):
        """Post-commit eager pass: every occupied, read-only,
        uncommitted slot gets one legality probe at the (already
        advanced) state — exactly the scalar `sweep`, evaluated as ONE
        2D-broadcast columnar step over the whole slot table (read-only
        commits are state-preserving and each pending op is probed once
        at the same state, so the committed set and the step count are
        probe-order-independent; counts are monotone, so adding a
        sweep's probes in bulk reaches the same abort decision as the
        scalar's one-at-a-time wrapper)."""
        mm = (occ & sro & ~sdone) & m[:, None]
        if not mm.any():
            return
        if any_budget:
            count[:] += mm.sum(axis=1)
            abort_check(m)
        _, lg = step(state[:, None], sf, sa, sb)
        sdone[:] |= mm & lg

    # Each iteration lands every active row on its next FORCE (or the
    # end) and resolves one commit there, so the trip count is bounded
    # by #FORCEs + #candidate commits + 1 ≤ E + ops; anything past
    # that is a malformed stream — routed scalar, where the error
    # behavior is authoritative.
    max_iter = E + n_ops_total + 4
    for _ in range(max_iter):
        act = status == _ACTIVE
        if not act.any():
            break
        if int((status == _FALLBACK).sum()) > B // 2:
            # dead-end-dominated batch (the backtrack-heavy families):
            # most rows are headed for the scalar engine anyway, so
            # stop paying the vectorized scan for the rest — FALLBACK
            # is always outcome-preserving, only slower
            status[act] = _FALLBACK
            break

        # ---- bulk open-run phase: advance every active row to its
        # next FORCE, storing/eager-probing the run's opens ----------
        run_to = nf_at[rows, np.minimum(pos, E)]
        m_run = act & (run_to > pos)
        if m_run.any():
            r = np.flatnonzero(m_run)
            lens = (run_to - pos)[r]
            tot = int(lens.sum())
            row_rep = np.repeat(r, lens)
            cum = np.cumsum(lens)
            idx = (np.arange(tot) - np.repeat(cum - lens, lens)
                   + np.repeat(pos[r], lens))
            rowev = ev[row_rep, idx]
            is_open = rowev[:, 0] == EV_OPEN
            f0, a0, b0 = rowev[:, 2], rowev[:, 3], rowev[:, 4]
            sl0 = rowev[:, 1]
            badm = is_open & (sl0 >= S)
            if badm.any():
                status[row_rep[badm]] = _FALLBACK
            is_ro = is_open & ro_lut[np.minimum(f0, n_f)]
            # eager probes: one columnar step at the run's (constant)
            # state for every read-only open in it; counts first (the
            # scalar wrapper aborts before using a result, and counts
            # are monotone so "exceeds mid-run" ≡ "exceeds on the
            # run's total")
            if any_budget:
                count[:] += np.bincount(row_rep[is_ro], minlength=B)
                abort_check(m_run)
            eager = np.zeros(tot, dtype=bool)
            if is_ro.any():
                _, lg = step(state[row_rep], f0, a0, b0)
                eager = is_ro & lg
            live = (status[row_rep] == _ACTIVE) & is_open
            rr, ss = row_rep[live], np.minimum(sl0[live], S - 1)
            # slots within one run are distinct per row, so this
            # scatter never collides on (row, slot)
            occ[rr, ss] = True
            sdone[rr, ss] = eager[live]
            sro[rr, ss] = is_ro[live]
            sforced[rr, ss] = forced_at[row_rep, idx][live] != 0
            sf[rr, ss] = f0[live]
            sa[rr, ss] = a0[live]
            sb[rr, ss] = b0[live]
            opens_before = np.cumsum(is_open) - is_open
            run_open_rank = (opens_before
                             - np.repeat(opens_before[cum - lens], lens))
            sk[rr, ss] = (kcnt[row_rep] + run_open_rank)[live]
            sem[rr, ss] = em_at[row_rep, idx][live]
            som[rr, ss] = om_at[row_rep, idx][live]
            kcnt[r] += np.bincount(row_rep[is_open],
                                   minlength=B)[r]
            adv = np.flatnonzero(m_run & (status == _ACTIVE))
            pos[adv] = run_to[adv]

        act = status == _ACTIVE
        done_rows = act & (pos >= n_ev)
        status[done_rows] = _CERT
        m_force = act & ~done_rows
        if not m_force.any():
            continue

        # ---- FORCE phase: skip committed ops, direct-commit legal
        # ones (+sweep), or run one candidate commit ------------------
        cur = ev[rows, np.minimum(pos, E - 1)]
        sl = np.minimum(cur[:, 1], S - 1)
        bad_slot = m_force & (cur[:, 1] >= S)
        status[bad_slot] = _FALLBACK
        m_force = m_force & ~bad_slot

        fdone = sdone[rows, sl]
        mskip = m_force & fdone
        r = np.flatnonzero(mskip)
        occ[r, sl[r]] = False
        pos[r] += 1
        mchk = m_force & ~fdone
        # a FORCE must close a live op; anything else is malformed —
        # scalar raises, so hand the row to it
        bad = mchk & ~occ[rows, sl]
        status[bad] = _FALLBACK
        mchk = mchk & ~bad
        if mchk.any():
            tf = sf[rows, sl]
            ta = sa[rows, sl]
            tb = sb[rows, sl]
            if any_budget:
                count[mchk] += 1
                mchk = abort_check(mchk)
            ns, lg = step(state, tf, ta, tb)
            mlegal = mchk & lg
            # direct greedy commit: the scalar re-steps for the commit
            # (+1) then sweeps
            if any_budget:
                count[mlegal] += 1
                mlegal = abort_check(mlegal)
            r = np.flatnonzero(mlegal)
            if len(r):
                state[r] = ns[r]
                sdone[r, sl[r]] = True
                sweep(mlegal)
                mlegal = mlegal & (status == _ACTIVE)
                r = np.flatnonzero(mlegal)
                occ[r, sl[r]] = False
                pos[r] += 1
            mcand = mchk & ~lg & (status == _ACTIVE)
            if mcand.any():
                # ---- one vectorized candidate commit (the scalar
                # candidates() + first-choice commit) per stalled row;
                # the row stays at the FORCE and re-probes it next
                # iteration ----------------------------------------
                # candidates() re-probes the forced op first (+1)
                if any_budget:
                    count[mcand] += 1
                    mcand = abort_check(mcand)
                cm = (occ & ~sdone & mcand[:, None]
                      & (slot_col != sl[:, None]))
                if any_budget:
                    count[:] += cm.sum(axis=1)
                    mcand = abort_check(mcand)
                ns2, lg2 = step(state[:, None], sf, sa, sb)
                cl = cm & lg2
                # guide short-circuit: a mask proving the candidate
                # exposes nothing the forced op observes skips the
                # 1-step lookahead (enables stays 1)
                om_e = som[rows, sl][:, None]
                need = cl & (~gok[:, None] | ((sem & om_e) != 0))
                if any_budget:
                    count[:] += need.sum(axis=1)
                    mcand = abort_check(mcand)
                _, lg3 = step(ns2, tf[:, None], ta[:, None],
                              tb[:, None])
                enables = np.where(need & lg3, np.int64(0),
                                   np.int64(1))
                key = (enables * _KEY_ENABLES
                       + np.where(sforced, np.int64(0),
                                  np.int64(1)) * _KEY_OPTIONAL
                       + sk)
                key = np.where(cl, key, _NO_BUDGET)
                best = key.argmin(axis=1)
                picked = key[rows, best] < _NO_BUDGET
                # dead end: the scalar engine would start restoring
                # choice points — its territory
                status[mcand & ~picked] = _FALLBACK
                mpick = mcand & picked
                # the scalar main loop re-steps the choice to commit
                if any_budget:
                    count[mpick] += 1
                    mpick = abort_check(mpick)
                r = np.flatnonzero(mpick)
                if len(r):
                    state[r] = ns2[r, best[r]]
                    sdone[r, best[r]] = True
                    sweep(mpick)
    status[status == _ACTIVE] = _FALLBACK  # trip-count bound: malformed
    return status
