"""Weaker-consistency rungs: relaxed-precedence scans over the packed
event tensors.

The ladder below full linearizability (ROADMAP item 4) is built from ONE
observation: the packed event stream (history/packing.py) encodes ALL
real-time precedence through FORCE placement — an op must linearize
between its OPEN and its FORCE. A weaker consistency rung is therefore a
*stream transform*, not a new engine: defer each op's FORCE along the
axis the rung cares about and re-run the identical frontier machinery
(dense/mask/sort kernels, macro compaction, chunked eviction, autotune
bucketing, graftd coalescing — all of it consumes `EncodedHistory` and
applies unchanged).

Rungs (strong → weak), by FORCE placement:

  ``linearizable``  — FORCE at the op's real-time completion (the
                      untouched encoding).
  ``sequential``    — FORCE deferred to just before the same process's
                      NEXT op opens (or end of stream): cross-process
                      real-time edges are dropped, per-process program
                      order is kept.
  ``session``       — (monotonic-reads tier) FORCE deferred to just
                      before the same process's next *read* opens
                      (``Model.readonly_fcodes``), else end of stream:
                      only reads must observe their session's earlier
                      ops.

Soundness (doc/checker-design.md §12 for the full argument):

  * Monotone relaxation: every rung only moves FORCEs later (clamped to
    ``max(original, deferred)``), so any linearization witness survives
    each step down the ladder — a history passing linearizability
    passes every weaker rung, and a FAIL at a weak rung certifies
    non-linearizability (the rung-ordering property tests pin both).
  * Positive certification: a ``sequential`` witness linearizes each op
    before its process's next op opens, hence before that op — the
    witness respects program order, so a PASS certifies sequential
    consistency. (The rung may be stricter than full SC: stream order
    still carries the cross-process edges the interval encoding cannot
    drop — exact SC checking is NP-hard and out of scope; this is the
    tractable interval-order relaxation.) A ``session`` PASS certifies
    that each read observes all earlier same-session ops — monotonic
    reads + read-your-writes.

Why the rungs are CHEAPER: a weaker rung admits more witnesses, so the
value-guided bounded-backtrack certifier below (an O(events · window)
host scan with a fixed flip budget, no kernel launch) succeeds on the
overwhelming majority of valid histories. Rows it cannot certify fall
through to the ordinary kernel ladder on the relaxed stream; the
certifier never *refutes*, so its answers are sound by construction
(the committed order IS a witness). Soundness + tier ordering live in
doc/checker-design.md §15.
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..history.packing import EV_FORCE, EV_OPEN, EncodedHistory
from ..platform import env_int

#: Rung names, strongest first. Index = position in the ladder.
CONSISTENCY_LEVELS = ("linearizable", "sequential", "session")

_ALIASES = {
    "lin": "linearizable",
    "linearizability": "linearizable",
    "seq": "sequential",
    "monotonic-reads": "session",
    "monotonic": "session",
}


def normalize_consistency(name: Optional[str]) -> str:
    """Canonical rung name (aliases accepted); ValueError on unknowns —
    the service maps that to a 400 at admission, never into the queue."""
    if name is None:
        return "linearizable"
    n = _ALIASES.get(str(name).strip().lower(), str(name).strip().lower())
    if n not in CONSISTENCY_LEVELS:
        raise ValueError(
            f"unknown consistency {name!r}; valid: "
            f"{CONSISTENCY_LEVELS} (aliases: {sorted(_ALIASES)})")
    return n


def rung_index(name: str) -> int:
    return CONSISTENCY_LEVELS.index(normalize_consistency(name))


def greedy_on() -> bool:
    """Whether the greedy witness certifier runs before the kernel pass
    on weaker rungs. ``JGRAFT_GREEDY_CERTIFY=0`` disables it — the
    ablation arm (rung verdicts must be identical either way, pinned by
    tests) and the A/B denominator."""
    return env_int("JGRAFT_GREEDY_CERTIFY", 1, minimum=0) != 0


#: Default BASE flip budget for the bounded-backtrack certifier:
#: enough to untangle the mutator ambiguity that defeats the pure
#: greedy scan on the register/cas family (measured: 98/100 seeded
#: 200-op register histories certify under 64 flips where PR-9 greedy
#: managed 9/100), small enough that an adversarial history cannot
#: turn the cheap tier into a search engine — undecided rows take the
#: exact kernel ladder. The EFFECTIVE per-row budget scales with
#: stream length (`_effective_budget`): wrong turns accumulate
#: linearly with ops, so a flat budget silently starved long histories
#: (1000-op register decided fraction 0.67 flat vs 1.0 scaled,
#: measured at ~equal wall — undecided rows are the expensive ones).
DEFAULT_BACKTRACK_BUDGET = 64

#: Events per base-budget unit in the length scaling.
_BUDGET_SCALE_EVENTS = 256

#: Most-recent choice points kept restorable. Dropping the oldest when
#: the stack outgrows this bounds certifier memory to
#: O(cap · ops/word) regardless of history length; a search that needs
#: deeper backtracking returns undecided (never wrong).
_BACKTRACK_STACK_CAP = 128


def greedy_backtrack_budget() -> int:
    """Resolved BASE flip budget (JGRAFT_GREEDY_BACKTRACK; 0 restores
    the PR-9 no-backtrack greedy behavior — the ablation arm)."""
    return env_int("JGRAFT_GREEDY_BACKTRACK", DEFAULT_BACKTRACK_BUDGET,
                   minimum=0)


class _AbortBudget(Exception):
    """Internal: the certifier's step-count abort budget ran out
    (ISSUE 14). Converted to an undecided answer — never a verdict."""


def _effective_budget(base: int, n_events: int) -> int:
    """Per-row budget: the base, scaled linearly past
    `_BUDGET_SCALE_EVENTS` events (64 at ≤256 events, ~448 at a
    2000-event 1000-op register history)."""
    return base * max(1, n_events // _BUDGET_SCALE_EVENTS)


# ----------------------------------------------------- stream relaxation


def relax_encoded(enc: EncodedHistory, model,
                  consistency: str) -> EncodedHistory:
    """Re-encode one packed history with the rung's relaxed FORCE
    placement (module docstring). Pure host transform on the packed
    tensors; slot assignment is re-run so the relaxed stream is a
    first-class `EncodedHistory` every kernel family accepts.

    An encoding without per-event process ids (`proc is None` — hand
    built, or loaded from an older artifact) cannot be relaxed
    per-process; it is returned UNCHANGED, which is conservative and
    sound in both directions (the rung is then exactly linearizability
    for that row: a pass still implies the weaker guarantee, a fail
    still certifies non-linearizability)."""
    consistency = normalize_consistency(consistency)
    if consistency == "linearizable" or enc.n_events == 0:
        return enc
    proc = enc.proc
    if proc is None or len(proc) != enc.n_events:
        return enc
    events = enc.events
    op_index = enc.op_index
    readonly = frozenset(getattr(model, "readonly_fcodes", ()) or ())

    # -- decode the stream back into ops -------------------------------
    # op record: [open_pos, f, a, b, open_idx, pid, force_pos|-1,
    #             force_idx] (force_idx = the completion row's history
    #             index — FORCE rows must keep reporting it so rung
    #             counterexamples point at the completion, like the
    #             original encoding's op_index convention).
    ops: List[list] = []
    active: dict = {}          # slot -> op record index
    per_proc: dict = {}        # pid -> [op record index...] in open order
    for pos in range(enc.n_events):
        et = int(events[pos, 0])
        slot = int(events[pos, 1])
        if et == EV_OPEN:
            k = len(ops)
            ops.append([pos, int(events[pos, 2]), int(events[pos, 3]),
                        int(events[pos, 4]), int(op_index[pos]),
                        int(proc[pos]), -1, -1])
            active[slot] = k
            per_proc.setdefault(int(proc[pos]), []).append(k)
        elif et == EV_FORCE:
            k = active.pop(slot)
            ops[k][6] = pos
            ops[k][7] = int(op_index[pos])

    # -- per-process deferral targets ----------------------------------
    END = enc.n_events
    anchor: List[Optional[int]] = [None] * len(ops)  # forced ops only
    for pid, ks in per_proc.items():
        for j, k in enumerate(ks):
            if ops[k][6] < 0:
                continue  # optional op: never forced, nothing to move
            later = ks[j + 1:]
            if consistency == "sequential":
                cand = ops[later[0]][0] if later else END
            else:  # session: next same-process READ open
                cand = END
                for k2 in later:
                    if ops[k2][1] in readonly:
                        cand = ops[k2][0]
                        break
            # Monotone-relaxation clamp: never move a FORCE earlier
            # than its real-time position (ill-formed inputs included).
            anchor[k] = cand if cand > ops[k][6] else ops[k][6]

    # -- rebuild: opens at their positions, forces just before their
    # anchor opens (END = past everything); ties among deferred forces
    # keep original completion order. kind 0 (force) sorts before kind 1
    # (open) at the same anchor, which is exactly "just before".
    items = []
    for k, o in enumerate(ops):
        items.append((o[0], 1, k, EV_OPEN))
        if o[6] >= 0:
            items.append((anchor[k], 0, o[6], EV_FORCE, k))
    items.sort(key=lambda it: (it[0], it[1], it[2]))

    n_ev = len(items)
    out = np.zeros((n_ev, 5), dtype=np.int32)
    out_idx = np.empty(n_ev, dtype=np.int32)
    out_proc = np.empty(n_ev, dtype=np.int32)
    slot_of: dict = {}
    free: List[int] = []
    next_slot = 0
    for j, it in enumerate(items):
        if it[3] == EV_OPEN:
            k = it[2]
            if free:
                s = heapq.heappop(free)
            else:
                s = next_slot
                next_slot += 1
            slot_of[k] = s
            out[j] = (EV_OPEN, s, ops[k][1], ops[k][2], ops[k][3])
            out_idx[j] = ops[k][4]
        else:
            k = it[4]
            s = slot_of[k]
            out[j] = (EV_FORCE, s, 0, 0, 0)
            heapq.heappush(free, s)
            out_idx[j] = ops[k][7]
        out_proc[j] = ops[k][5]
    return EncodedHistory(events=out, op_index=out_idx,
                          n_slots=next_slot, n_ops=len(ops),
                          proc=out_proc)


# ----------------------------------- value-guided backtracking certifier


def _value_guide_masks(model, ops, forced):
    """Per-op (enable_mask, observe_mask) bitmasks over the observed
    value domain — GSet's membership-mask encoding trick applied to the
    certifier's choice ordering: `enable_mask[k] & observe_mask[e]`
    answers "can committing k expose a state e observes?" in one AND.
    None when the model lacks the enable/observe hooks, answers None
    for some op, or the domain outgrows the word — the step-lookahead
    fallback then orders candidates instead (exact, just slower)."""
    from ..models.base import EncodedOp

    if not (hasattr(model, "enable_values")
            and hasattr(model, "observe_values")):
        return None
    dom: dict = {}
    em = [0] * len(ops)
    om = [0] * len(ops)
    for k, (f, a, b) in enumerate(ops):
        eo = EncodedOp(f, a, b, forced[k])
        evs = model.enable_values(eo)
        ovs = model.observe_values(eo)
        if evs is None or ovs is None:
            return None
        for vals, masks in ((evs, em), (ovs, om)):
            for v in vals:
                if v not in dom:
                    if len(dom) >= 63:
                        return None
                    dom[v] = len(dom)
                masks[k] |= 1 << dom[v]
    return em, om


def certify_encoded(enc: EncodedHistory, model,
                    budget: Optional[int] = None,
                    max_steps: Optional[int] = None
                    ) -> Tuple[bool, Optional[str], int]:
    """Witness construction on an encoded stream, with value-guided
    bounded backtracking (the ISSUE-13 widening of PR 9's one-pass
    greedy). Returns ``(certified, tier, flips)`` — tier "greedy" when
    the first-choice path succeeded, "backtrack" when recovering from
    ``flips`` wrong turns did, None when undecided.

    Commit rules (the PR-9 rules, now restartable):

      * EAGER observations: a pending READ-ONLY op (an opcode in
        `readonly_fcodes` — never mutates at ANY state) that is legal
        NOW commits immediately — provably lossless: if any witness
        places a read-only op elsewhere, moving it to the current legal
        point yields another witness (the op preserves state), so eager
        commits never foreclose anything and are NOT choice points.
      * LAZY mutations: a state-changing op commits only at its own
        FORCE, or when a forced op needs its effect.
      * CHOICE POINTS: every FORCE of a mutator is a decision — commit
        it directly (when legal), or commit some older pending op first
        and re-try. The pure greedy took the first option and aborted
        on any dead end; this certifier snapshots (pos, state, done)
        per decision and, on a dead end, restores the most recent
        snapshot with untried options — up to ``budget`` flips
        (`JGRAFT_GREEDY_BACKTRACK`), after which it returns undecided.
      * VALUE-GUIDED ordering: candidate commits are ranked by whether
        they can expose a state the blocked op observes (the
        enable/observe bitmask intersection above, confirmed by a
        1-step lookahead; pure lookahead for models without the hooks
        — this is what places a crashed queue landmine ENQ_ANY/DEQ_ANY
        lazily at the first state where it unblocks a forced op), then
        will-be-forced ops before optional crashed ops (known outcomes
        before poison), then open order.

    Soundness is unchanged from PR 9: True is returned only when a
    complete legal witness respecting every [OPEN, FORCE] interval was
    built, so True is a sound VALID for whatever rung produced the
    stream; False/undecided NEVER refutes — callers fall through to the
    exact kernel ladder (doc/checker-design.md §15).

    ``max_steps`` (ISSUE 14): an ABORT budget on total `model.step`
    calls. The flip budget bounds backtracking but not the scan's raw
    candidate-enumeration work, so a hopeless row on the linearizable
    fast path could otherwise cost an unbounded fraction of its kernel
    wall; past the budget the row returns undecided (never wrong — the
    kernels answer). None/0 = unbounded, today's exact behavior; the
    lin fast path passes a length-scaled budget
    (JGRAFT_LIN_FASTPATH_ABORT · events, checker/linearizable.py).

    NOTE: `StreamingCertifier` below is this scan's resumable twin —
    commit rules and candidate ordering are mirrored BY HAND (see its
    lock-step contract note for why they are not unified)."""
    state = model.init_state()
    step = model.step
    if max_steps is not None and max_steps > 0:
        raw_step, left = step, [int(max_steps)]

        def step(s, f, a, b):
            left[0] -= 1
            if left[0] < 0:
                raise _AbortBudget()
            return raw_step(s, f, a, b)
    readonly = frozenset(getattr(model, "readonly_fcodes", ()) or ())
    if budget is None:
        budget = _effective_budget(greedy_backtrack_budget(),
                                   enc.n_events)
    events = enc.events.tolist()
    n_ev = len(events)

    # -- pre-decode: flat op table + per-event (etype, op id) ----------
    ops: List[tuple] = []          # (f, a, b) per op, in open order
    op_forced: List[bool] = []     # will this op's slot see a FORCE?
    ev_ops: List[tuple] = []       # (etype, op id) per event position
    active: dict = {}
    for pos in range(n_ev):
        et, slot = events[pos][0], events[pos][1]
        if et == EV_OPEN:
            k = len(ops)
            ops.append((events[pos][2], events[pos][3], events[pos][4]))
            op_forced.append(False)
            active[slot] = k
            ev_ops.append((EV_OPEN, k))
        elif et == EV_FORCE:
            k = active.pop(slot)
            op_forced[k] = True
            ev_ops.append((EV_FORCE, k))
        else:
            ev_ops.append((0, -1))
    opened_by = [0] * (n_ev + 1)   # #ops opened among events[:pos]
    for pos in range(n_ev):
        opened_by[pos + 1] = opened_by[pos] + (
            1 if ev_ops[pos][0] == EV_OPEN else 0)
    guide = _value_guide_masks(model, ops, op_forced)

    def sweep(state, done, pending):
        # One pass suffices: read-only commits leave the state (the
        # only legality input) unchanged.
        for k in pending:
            if not (done >> k) & 1 and ops[k][0] in readonly \
                    and step(state, *ops[k])[1]:
                done |= 1 << k
        return done

    def candidates(state, done, pending, e):
        """Ordered commit options at op e's FORCE. None = commit e
        directly (listed first when legal — the greedy choice);
        otherwise an older pending op id, value-guided order."""
        te = ops[e]
        s_e, legal_e = step(state, *te)
        out = []
        if legal_e:
            out.append((-1, 0, 0, -1, None))
        for k in pending:
            if (done >> k) & 1 or k == e:
                continue
            s2, legal = step(state, *ops[k])
            if not legal:
                continue
            if guide is not None and not (guide[0][k] & guide[1][e]):
                enables = 1  # mask proves k exposes nothing e observes
            else:
                enables = 0 if step(s2, *te)[1] else 1
            out.append((0, enables, 0 if op_forced[k] else 1, k, k))
        out.sort(key=lambda t: t[:4])
        return [t[4] for t in out]

    flips = 0
    # choice points: [pos, state, done, candidates|None (lazy), next].
    # A None candidate list is computed only on first restore — the
    # never-backtracked common path (every valid unambiguous row) pays
    # one direct step() per FORCE exactly like the PR-9 scan, not a
    # full candidate enumeration.
    stack: deque = deque(maxlen=_BACKTRACK_STACK_CAP)
    pending: List[int] = []
    pos, done = 0, 0
    try:
        while pos < n_ev:
            et, k = ev_ops[pos]
            if et == EV_OPEN:
                f, a, b = ops[k]
                # Eager-commit at open when read-only and already legal
                # (the rest of `pending` was swept at this same state).
                if f in readonly and step(state, f, a, b)[1]:
                    done |= 1 << k
                else:
                    pending.append(k)
                pos += 1
                continue
            if et != EV_FORCE or (done >> k) & 1:
                pos += 1
                continue
            s_k, legal_k = step(state, *ops[k])
            choice = None
            if legal_k:
                # greedy direct commit; alternatives resolve lazily
                if budget > 0 and any(not (done >> o) & 1
                                      for o in pending):
                    stack.append([pos, state, done, None, 1])
            else:
                cands = candidates(state, done, pending, k)
                if cands:
                    if len(cands) > 1 and budget > 0:
                        stack.append([pos, state, done, cands, 1])
                    choice = cands[0]
                else:
                    # dead end: restore the most recent choice point
                    # with an untried option (one restore = one flip)
                    while stack:
                        cp = stack[-1]
                        if cp[3] is None:  # lazy: enumerate at its state
                            kc = ev_ops[cp[0]][1]
                            pc = [o for o in range(opened_by[cp[0]])
                                  if not (cp[2] >> o) & 1]
                            cp[3] = candidates(cp[1], cp[2], pc, kc)
                        if cp[4] < len(cp[3]):
                            flips += 1
                            if flips > budget:
                                return False, None, flips
                            pos, state, done = cp[0], cp[1], cp[2]
                            choice = cp[3][cp[4]]
                            cp[4] += 1
                            k = ev_ops[pos][1]
                            pending = [o for o in range(opened_by[pos])
                                       if not (done >> o) & 1]
                            break
                        stack.pop()
                    else:
                        return False, None, flips  # undecided — kernels
            commit = k if choice is None else choice
            state = step(state, *ops[commit])[0]
            done = sweep(state, done | (1 << commit), pending)
            if choice is None:
                pos += 1
            # else: stay at pos — re-evaluate k's FORCE at the new state
            pending = [o for o in pending if not (done >> o) & 1]
    except _AbortBudget:
        return False, None, flips  # abort budget spent — undecided
    return True, ("greedy" if flips == 0 else "backtrack"), flips


def greedy_certify(enc: EncodedHistory, model,
                   budget: Optional[int] = None) -> bool:
    """Boolean view of :func:`certify_encoded` (the historical PR-9
    entry; True = sound VALID witness built, False = undecided)."""
    return certify_encoded(enc, model, budget=budget)[0]


# ------------------------------------------------- resumable certifier


class StreamingCertifier:
    """Incremental twin of :func:`certify_encoded` for streaming
    sessions (ISSUE 14 tentpole (3)). `feed` consumes settled event
    suffixes (the `IncrementalEncoder` output) and advances the same
    witness construction, keeping the certifier's carry — (state,
    done-set, pending, backtrack stack) — BETWEEN appends, so a
    long-lived session's per-append cost is O(segment) instead of the
    per-append full restart's O(history). The carry lives next to
    `CarriedScan`'s ``{inner, left}`` kernel carry and, like it, is
    never journaled: a crash resume replays the journaled segments
    through the identical deterministic pipeline, so the rebuilt
    certifier state is field-for-field identical to the uninterrupted
    session's (pinned by tests/test_stream.py).

    Differences from the one-shot scan, and why they are sound:

      * ``op_forced`` is learned as FORCEs settle (the one-shot
        pre-scans the whole stream). It only RANKS candidates
        (will-be-forced before optional), so a late-learned force can
        cost flips, never a wrong answer; the value-guide masks are
        recomputed when an op's FORCE settles for the same reason.
      * `certified` mid-stream means the settled PREFIX has a complete
        witness — exactly what the per-append restart certified — and
        the final `feed` (after the encoder's end-of-history settle)
        certifies the whole history.
      * Once undecided (flip budget spent, no restorable choice point)
        the certifier is PERMANENTLY dead and the caller's kernel
        carry takes over — it never un-decides, matching the one-shot
        contract that undecided falls to the exact ladder.

    The flip budget is length-scaled like the one-shot's
    (`_effective_budget` over TOTAL settled events, re-resolved per
    feed, so a growing session earns budget as it grows).

    LOCK-STEP CONTRACT with :func:`certify_encoded`: `_sweep` /
    `_candidates` / `_scan` mirror the one-shot's commit rules and
    candidate ordering on purpose — the one-shot stays a hand-tuned
    closure loop because it is the MEASURED hot path (the weak-rung
    and lin-fastpath A/B numbers are pinned on it; the queue family
    clears its acceptance bar by <1%, so method-dispatch overhead is
    not free). A change to commit rules, ordering, or budgets in
    either implementation must be mirrored in the other; the
    cross-engine differential (tests/test_lin_fastpath.py
    TestStreamingCertifier, random cuts vs the one-shot) is the
    drift tripwire. The one-shot's `max_steps` abort budget is
    deliberately absent here: a feed's work is already bounded by the
    segment plus the length-scaled flip budget, and stream units have
    their own size caps (JGRAFT_STREAM_GREEDY_MAX_EVENTS)."""

    def __init__(self, model, budget: Optional[int] = None):
        from ..models.base import EncodedOp

        self._EncodedOp = EncodedOp
        self._model = model
        self._step = model.step
        self._readonly = frozenset(
            getattr(model, "readonly_fcodes", ()) or ())
        self._base_budget = budget
        # op table / event tape (append-only across feeds)
        self._ops: List[tuple] = []
        self._op_forced: List[bool] = []
        self._ev_ops: List[tuple] = []
        self._opened_by: List[int] = [0]
        self._active: dict = {}      # slot -> op id (spans feeds)
        # value-guide masks (grown per op; falls back to lookahead)
        self._guide_ok = (hasattr(model, "enable_values")
                          and hasattr(model, "observe_values"))
        self._dom: dict = {}
        self._em: List[int] = []
        self._om: List[int] = []
        # the carry proper
        self._state = model.init_state()
        self._done = 0
        self._pending: List[int] = []
        self._stack: deque = deque(maxlen=_BACKTRACK_STACK_CAP)
        self._pos = 0
        self._flips = 0
        self._dead = False

    # ------------------------------------------------------ accessors

    @property
    def certified(self) -> bool:
        """True while every settled event so far is covered by a
        complete legal witness (sound VALID for the settled prefix)."""
        return not self._dead

    @property
    def tier(self) -> Optional[str]:
        """Decided-tier attribution: "greedy" while the first-choice
        path carried, "backtrack" once any flip was spent; None once
        undecided."""
        if self._dead:
            return None
        return "greedy" if self._flips == 0 else "backtrack"

    def carry_state(self) -> dict:
        """The certifier's carry, for the resume-identity tests (a
        resumed session's replay must land field-for-field here)."""
        return {
            "pos": self._pos,
            "ops": len(self._ops),
            "done": self._done,
            "state": self._state,
            "pending": tuple(self._pending),
            "flips": self._flips,
            "stack_depth": len(self._stack),
            "dead": self._dead,
        }

    # ---------------------------------------------------------- guide

    def _guide_add(self, k: int) -> None:
        """(Re)compute op k's enable/observe masks — on OPEN, and again
        when its FORCE settles (the hooks may key on `forced`)."""
        if not self._guide_ok:
            return
        f, a, b = self._ops[k]
        eo = self._EncodedOp(f, a, b, self._op_forced[k])
        evs = self._model.enable_values(eo)
        ovs = self._model.observe_values(eo)
        if evs is None or ovs is None:
            self._guide_ok = False
            return
        masks = [0, 0]
        for j, vals in enumerate((evs, ovs)):
            for v in vals:
                if v not in self._dom:
                    if len(self._dom) >= 63:
                        self._guide_ok = False
                        return
                    self._dom[v] = len(self._dom)
                masks[j] |= 1 << self._dom[v]
        self._em[k], self._om[k] = masks

    # ----------------------------------------------------------- scan

    def feed(self, events) -> bool:
        """Consume one settled suffix ([n, 5] int32 rows) and advance
        the witness; returns `certified`."""
        rows = np.asarray(events).tolist() if len(events) else []
        for row in rows:
            et, slot = row[0], row[1]
            if et == EV_OPEN:
                k = len(self._ops)
                self._ops.append((row[2], row[3], row[4]))
                self._op_forced.append(False)
                self._em.append(0)
                self._om.append(0)
                self._active[slot] = k
                self._ev_ops.append((EV_OPEN, k))
                self._guide_add(k)
            elif et == EV_FORCE:
                k = self._active.pop(slot)
                self._op_forced[k] = True
                self._ev_ops.append((EV_FORCE, k))
                self._guide_add(k)
            else:
                self._ev_ops.append((0, -1))
            self._opened_by.append(
                self._opened_by[-1] + (1 if et == EV_OPEN else 0))
        if self._dead:
            return False
        return self._scan()

    def _sweep(self, state, done, pending) -> int:
        step, readonly, ops = self._step, self._readonly, self._ops
        for k in pending:
            if not (done >> k) & 1 and ops[k][0] in readonly \
                    and step(state, *ops[k])[1]:
                done |= 1 << k
        return done

    def _candidates(self, state, done, pending, e) -> list:
        step, ops = self._step, self._ops
        te = ops[e]
        legal_e = step(state, *te)[1]
        out = []
        if legal_e:
            out.append((-1, 0, 0, -1, None))
        for k in pending:
            if (done >> k) & 1 or k == e:
                continue
            s2, legal = step(state, *ops[k])
            if not legal:
                continue
            if self._guide_ok and not (self._em[k] & self._om[e]):
                enables = 1  # mask proves k exposes nothing e observes
            else:
                enables = 0 if step(s2, *te)[1] else 1
            out.append((0, enables,
                        0 if self._op_forced[k] else 1, k, k))
        out.sort(key=lambda t: t[:4])
        return [t[4] for t in out]

    def _scan(self) -> bool:
        """The certify_encoded main loop over the not-yet-consumed
        tape suffix, reading/writing the instance carry."""
        step, ops, ev_ops = self._step, self._ops, self._ev_ops
        readonly, opened_by = self._readonly, self._opened_by
        base = (self._base_budget if self._base_budget is not None
                else greedy_backtrack_budget())
        budget = _effective_budget(base, len(ev_ops))
        state, done, pending = self._state, self._done, self._pending
        stack, pos, flips = self._stack, self._pos, self._flips
        n_ev = len(ev_ops)
        ok = True
        while pos < n_ev:
            et, k = ev_ops[pos]
            if et == EV_OPEN:
                f, a, b = ops[k]
                if f in readonly and step(state, f, a, b)[1]:
                    done |= 1 << k
                else:
                    pending.append(k)
                pos += 1
                continue
            if et != EV_FORCE or (done >> k) & 1:
                pos += 1
                continue
            legal_k = step(state, *ops[k])[1]
            choice = None
            if legal_k:
                if budget > 0 and any(not (done >> o) & 1
                                      for o in pending):
                    stack.append([pos, state, done, None, 1])
            else:
                cands = self._candidates(state, done, pending, k)
                if cands:
                    if len(cands) > 1 and budget > 0:
                        stack.append([pos, state, done, cands, 1])
                    choice = cands[0]
                else:
                    while stack:
                        cp = stack[-1]
                        if cp[3] is None:
                            kc = ev_ops[cp[0]][1]
                            pc = [o for o in range(opened_by[cp[0]])
                                  if not (cp[2] >> o) & 1]
                            cp[3] = self._candidates(cp[1], cp[2], pc,
                                                     kc)
                        if cp[4] < len(cp[3]):
                            flips += 1
                            if flips > budget:
                                ok = False
                                break
                            pos, state, done = cp[0], cp[1], cp[2]
                            choice = cp[3][cp[4]]
                            cp[4] += 1
                            k = ev_ops[pos][1]
                            pending = [o for o in range(opened_by[pos])
                                       if not (done >> o) & 1]
                            break
                        stack.pop()
                    else:
                        ok = False  # no restorable choice — undecided
                    if not ok:
                        break
            commit = k if choice is None else choice
            state = step(state, *ops[commit])[0]
            done = self._sweep(state, done | (1 << commit), pending)
            if choice is None:
                pos += 1
            pending = [o for o in pending if not (done >> o) & 1]
        self._state, self._done, self._pending = state, done, pending
        self._pos, self._flips = pos, flips
        if not ok:
            self._dead = True
        return not self._dead


# ------------------------------------------------------------ batch entry


def apply_rung(encs: Sequence[EncodedHistory], model, consistency: str):
    """Certify/relax a batch at `consistency`. Returns (out, certified,
    tiers): `certified[i]` True where a witness already proves the row
    VALID at the rung (then `out[i]` is whichever encoding certified it
    and `tiers[i]` is "greedy" or "backtrack" — the decided-tier
    attribution); otherwise `out[i]` is the rung-relaxed encoding for
    the ordinary kernel ladder and `tiers[i]` is None.

    Certification order exploits monotone relaxation: a witness for the
    ORIGINAL (linearizable) stream is a witness for every weaker rung,
    and the original stream's FORCE order — real completion order, an
    approximation of the linearization order — is exactly the guidance
    the certifier needs, so it succeeds there on most valid histories
    and the row never pays the relaxation pass at all. Rows it misses
    relax and retry (the relaxed stream admits rung-only witnesses,
    e.g. stale reads); rows still undecided go to the kernels on the
    relaxed stream."""
    from .certify_batch import certify_many

    consistency = normalize_consistency(consistency)
    n = len(encs)
    out: list = list(encs)
    certified = [False] * n
    tiers: list = [None] * n
    greedy = greedy_on()
    # Pass 1: certify the ORIGINAL streams, batched across the rows
    # (checker/certify_batch.py — outcome-identical to the per-row
    # scalar loop; JGRAFT_CERTIFY_BATCH=0 restores it exactly).
    first = ([i for i in range(n) if encs[i].n_events > 0]
             if greedy else [])
    res = certify_many([encs[i] for i in first], model)
    for i, (ok, tier, _) in zip(first, res):
        if ok:
            certified[i] = True
            tiers[i] = tier
    # Pass 2: relax the misses and retry on the rung's stream.
    retry = [i for i in range(n) if not certified[i]]
    for i in retry:
        out[i] = relax_encoded(encs[i], model, consistency)
    if greedy:
        retry = [i for i in retry if out[i].n_events > 0]
        res = certify_many([out[i] for i in retry], model)
        for i, (ok, tier, _) in zip(retry, res):
            if ok:
                certified[i] = True
                tiers[i] = tier
    return out, certified, tiers
