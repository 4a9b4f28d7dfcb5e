"""Batching scheduler: cross-request coalescing over the chunked scan.

The substrate PR 3/4 built — chunked `ChunkLaunch` dispatch with
decided-row eviction, pow2+midpoint shape buckets, macro-event
compaction — amortizes kernel work across the ROWS of one caller's
batch. This module extends the amortization across CALLERS: pending
requests whose encodings pack into the same shape bucket are coalesced
into one `check_encoded` batch, so many small tenant histories ride a
single dense or sort launch; per-request verdicts are demuxed back
by row count after the wavefront evicts them.

Soundness of the coalescing (doc/checker-design.md §8): every kernel
family treats the batch axis as fully independent — rows never exchange
state (frontier carries are per-row, eviction/recompaction is a gather
over rows, window grouping only re-orders rows between launches) — so
the verdict of a row is a function of that row's event stream alone,
and a demuxed verdict is bitwise-identical to the verdict of the same
history checked in isolation (pinned by tests/test_service.py
differentials).

Ordering: requests are served by EFFECTIVE deadline
``min(deadline, submitted + aging_cap) - priority_credit·priority`` —
the deadline drives urgency, the aging cap bounds how long a
far-deadline request can be overtaken (starvation-free: after
`AGING_CAP_S` of waiting, a request's key stops growing and arrival
time breaks ties), and priority buys a fixed head start rather than a
strict class (a priority flood cannot starve the plain tier forever).
A batch is formed from the head request's shape bucket; when it is
small and the head deadline is not imminent, the scheduler lingers for
more same-bucket arrivals until ``JGRAFT_SERVICE_BATCH_WAIT_MS`` have
passed since the batch's OLDEST member was admitted — the classic
batching-window trade (latency of the head vs occupancy of the launch):
time spent queued behind a busy dispatcher counts against the window,
so a saturated service never waits blind for company it already has.
A LONE request has none yet, however long it queued: its window opens
at the take, as it always did, so light closed-loop traffic (two
clients taking turns) coalesces again. Past the window a batch waits
only for company in sight: a submission a handler has announced and is
still decoding (``AdmissionQueue.announce``), and never longer than one
window from the take.

Resilience: the device path failing MID-CHECK (backend teardown,
injected fault) degrades the batch to the host-only ladder
(`checker.linearizable.check_encoded_host` — CPU frontier, budgeted
DFS), stamping ``platform-degraded`` into every affected result and
recording the root cause via `platform.note_degraded`; the request
completes with a sound verdict instead of erroring.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional

from ..checker import autotune
from ..checker.schedule import (annotate, note_span, note_tier, span,
                                stats_scope)
from ..history.packing import bucket_rows
from ..platform import (env_int, install_compile_counters,
                        is_backend_init_failure, note_degraded)
from .admission import AdmissionQueue
from .request import CANCELLED, DONE, FAILED, RUNNING, CheckRequest

LOG = logging.getLogger("jgraft.service")

#: Default linger window for batch formation (ms), counted from the
#: admission of the batch's oldest member, or from the take for a lone
#: request. Small against check time, large against localhost submit
#: bursts: concurrent tenants submitting within one RPC round trip
#: coalesce, a lone request pays ≤ this after the take, and requests
#: that found company while they queued behind a busy dispatcher pay
#: ≤ this in all, queue wait included: past it they launch at once,
#: unless a submission is being decoded right then: that one is waited
#: for, at most one window from the take.
DEFAULT_BATCH_WAIT_MS = 50

#: A request waiting this long is as urgent as scheduling ever treats
#: it (its effective deadline stops receding) — the starvation bound.
AGING_CAP_S = 30.0

#: Seconds of deadline credit per priority unit.
PRIORITY_CREDIT_S = 1.0

#: Cap on rows (check units) per coalesced launch batch.
DEFAULT_MAX_BATCH_ROWS = 256


class WatchdogDegrade(Exception):
    """Internal signal: the hung-batch watchdog marked this retry
    ``force_host`` — route it through the same degrade arm a dying
    device path takes (host ladder + ``platform-degraded`` stamp, never
    cached). Not a platform failure, so the process-wide degrade
    registry is never written for it."""


class ShardLoads:
    """Load accounting for graftd's worker shards (ISSUE 7 tentpole
    (c)). A shard is one execution lane — one worker thread per
    host/device group — and its load is the rows dispatched to it and
    not yet finished. `least_loaded` is the routing rule the daemon's
    dispatcher applies to every formed batch: independent shape-bucket
    batches land on different shards and check CONCURRENTLY instead of
    serializing through one worker. Deterministic (ties break to the
    lowest shard id) so placement is testable; thread-safe (the
    executors release from their own threads)."""

    def __init__(self, n_shards: int):
        self.n_shards = max(1, int(n_shards))
        self._loads = [0] * self.n_shards  # guarded_by(_lock)
        self._lock = threading.Lock()

    def least_loaded(self) -> int:
        with self._lock:
            return min(range(self.n_shards), key=lambda k: self._loads[k])

    def add(self, shard: int, rows: int) -> None:
        with self._lock:
            self._loads[shard] += rows

    def done(self, shard: int, rows: int) -> None:
        with self._lock:
            self._loads[shard] = max(0, self._loads[shard] - rows)

    def snapshot(self) -> List[int]:
        with self._lock:
            return list(self._loads)


def batch_wait_s() -> float:
    """Resolved linger window (JGRAFT_SERVICE_BATCH_WAIT_MS: its length
    from the oldest member's admission, 0 = never wait; defensive
    parse — garbage warns and keeps the default)."""
    return env_int("JGRAFT_SERVICE_BATCH_WAIT_MS", DEFAULT_BATCH_WAIT_MS,
                   minimum=0) / 1000.0


def effective_deadline(req: CheckRequest,
                       aging_cap_s: float = AGING_CAP_S) -> float:
    """Scheduling key (smaller = sooner). See module docstring."""
    return (min(req.deadline, req.submitted + aging_cap_s)
            - PRIORITY_CREDIT_S * req.priority)


def bucket_signature(req: CheckRequest) -> tuple:
    """Shape bucket a request's rows pack into — the coalescing key.

    Two requests with the same signature ride one `check_encoded` batch
    whose group packing pads them into shared jit-cache shapes: same
    model family (one kernel family), same algorithm, the same
    consistency rung (a weaker rung relaxes the WHOLE batch's streams
    before the kernels see them — checker/consistency.py — so mixed
    rungs cannot share a launch), and the same pow2+midpoint EVENT
    bucket (`bucket_rows(E, 32)` — the floor_e=32 series
    `pad_batch_bucketed` pads short groups to). Window grouping inside
    the checker re-buckets rows further by concurrency window; that is
    invisible here because it happens after concatenation. Mixed-MODEL
    submissions need no scheduler changes: different models simply form
    different buckets, each riding the same formation/linger/execute
    machinery (the ISSUE-10 acceptance row pins this).

    A request of a workload that is split per key (`multi-register`:
    one unit a key) is signed like any other, by the bucket of its
    LONGEST unit, whatever the number of its units. Read before it was
    kept (ISSUE 47): a key of 100 ops is at most 200 events and ~146 in
    the mean (a failed cas leaves no event), so the longest of a
    10k-op history's 100 units lay in bucket 192 in 120 requests of
    120, and two such requests share a signature as two slices of
    1k-op histories do; `_choose` then fits two of them under
    `max_batch_rows` 256 and never a third (`/stats`
    `batched_requests` over `batches`)."""
    e_max = max((e.n_events for e in req.encs), default=0)
    return (type(req.model).__name__, req.algorithm, req.consistency,
            bucket_rows(max(e_max, 1), 32))


def _stamp_taken(reqs: List[CheckRequest]) -> None:
    now = time.monotonic()
    for r in reqs:
        r.taken = now


def _stamp_phases(r: CheckRequest, results_at: float) -> None:
    """Write ``r.stats["phases_ms"]`` from the request's stamps, just
    before it turns terminal, and add each phase to its `request.*`
    span. A phase whose closing stamp is unset (0.0: the lane did not
    scan the request, or answered it before any launch) is left out and
    the next one starts where the last ended, so the phases always sum
    to submit -> now."""
    last, phases = r.submitted, {}
    for phase, t in (("queue_wait", r.taken), ("scan", r.scanned),
                     ("formation_wait", r.run_started),
                     ("run", results_at), ("finish", time.monotonic())):
        if t:
            seconds = max(0.0, t - last)
            last = max(last, t)
            phases[phase] = round(seconds * 1e3, 3)
            note_span("request." + phase, seconds)
    r.stats["phases_ms"] = phases


class BatchScheduler:
    """Forms and executes coalesced batches from an AdmissionQueue."""

    def __init__(self, queue: AdmissionQueue,
                 check_fn: Optional[Callable] = None,
                 host_fallback: Optional[Callable] = None,
                 max_batch_rows: Optional[int] = None,
                 batch_wait: Optional[float] = None,
                 aging_cap_s: float = AGING_CAP_S):
        from ..checker.linearizable import check_encoded, check_encoded_host

        def _check_local(encs, model, algorithm="auto",
                         consistency="linearizable", lin_fastpath=None):
            # distribute=False: graftd's admission queue is HOST-local
            # — different daemon processes hold different batches, so
            # the cross-host SPMD seam (which barriers on every process
            # checking the SAME batch) would deadlock a clustered
            # daemon. Multi-host graftd is shard-routed per host
            # instead: one daemon per host, each with its own workers
            # (doc/checker-design.md §10).
            # serve_rows (ISSUE 32): a key is built whole for this
            # scheduler's largest batch before its first launch, and a
            # launch plan is what memory held when graftd started, or
            # the default; nothing is measured on this thread.
            return check_encoded(encs, model, algorithm=algorithm,
                                 distribute=False,
                                 consistency=consistency,
                                 lin_fastpath=lin_fastpath,
                                 serve_rows=self.max_batch_rows)

        #: device-path seam (tests inject failures / gates here).
        self.check_fn = check_fn or _check_local
        self.host_fallback = host_fallback or check_encoded_host
        #: graftd fast lane (ISSUE 14): enabled only on the DEFAULT
        #: check path — an injected check_fn is a test/ops seam that
        #: must observe every batch, so the lane never short-circuits
        #: it. `_default_host_fallback` gates whether the degrade arm
        #: may receive the lin_fastpath kwarg (an injected fallback
        #: predates it).
        self.fastlane_enabled = check_fn is None
        self._default_host_fallback = host_fallback is None
        self.max_batch_rows = (max_batch_rows if max_batch_rows is not None
                               else env_int("JGRAFT_SERVICE_MAX_BATCH_ROWS",
                                            DEFAULT_MAX_BATCH_ROWS,
                                            minimum=1))
        self.batch_wait = (batch_wait if batch_wait is not None
                           else batch_wait_s())
        self.aging_cap_s = aging_cap_s
        self.queue = queue
        self._seq = 0  # guarded_by(_seq_lock)
        #: batches of two requests or more whose linger window had
        #: already passed when they were taken (`/stats`
        #: `lingers_elapsed`, beside `batches`);
        #: over span `dispatch.linger`'s `n`, how often the window was
        #: spent in the queue and not on the dispatcher
        self.lingers_elapsed = 0  # guarded_by(_seq_lock)
        self._seq_lock = threading.Lock()

    # ------------------------------------------------------ formation

    def _choose(self, pending: List[CheckRequest]) -> List[CheckRequest]:
        """Head request by effective deadline, plus every same-bucket
        request that fits the row cap, in deadline order. A ``solo``
        request (poison-batch quarantine split, watchdog force-host
        retry — ISSUE 8) never coalesces: it forms a singleton batch so
        a deterministically-crashing rider cannot take innocent
        neighbors down with it again."""
        ordered = sorted(pending, key=lambda r: (
            effective_deadline(r, self.aging_cap_s), r.submitted))
        head = ordered[0]
        if head.solo:
            return [head]
        sig = bucket_signature(head)
        batch, rows = [], 0
        for r in ordered:
            if r.solo or bucket_signature(r) != sig:
                continue
            if batch and rows + r.n_rows > self.max_batch_rows:
                break
            batch.append(r)
            rows += r.n_rows
        return batch

    def fastlane(self, batch: List[CheckRequest]
                 ) -> tuple[List[CheckRequest], List[CheckRequest]]:
        """graftd fast lane (ISSUE 14): certify each popped request's
        rows on the host BEFORE the batch lingers, occupies a shard
        queue, or launches a kernel. Returns ``(decided, live)`` —
        decided requests are already finished DONE (sub-batch-latency
        verdicts; the daemon accounts/traces them), live ones proceed
        to the ordinary coalesced launch with the redundant in-checker
        fast path suppressed (execute passes ``lin_fastpath=False``).

        All-or-nothing per request: a partially-certifiable request
        stays live whole — its rows ride one launch and demux by row
        count, so evicting a subset would tear the fingerprint/trace
        contract. What a hopeless request costs here is bounded by the
        abort budget and by the measured gate (`lin_fastpath_plan`,
        consulted per request: a request is what the lane delivers, so
        its row count is the class the gate keeps its costs by), which
        the lane tells what it DELIVERED (ISSUE 28): a request finished
        here reports its rows as hits, one that goes live reports them
        scanned with no hit — and `execute` reports what the launch it
        then rode cost a row — so a row class whose requests never
        certify whole stops being scanned.
        Tier attribution is noted HERE, only for delivered requests
        (the pass defers to the lane): a discarded partial result's
        rows are decided — and attributed — by the kernel launch they
        proceed to, never double-counted."""
        from ..checker.linearizable import (LIN_FASTPATH_ALGOS,
                                            lin_fastpath_commit,
                                            lin_fastpath_on,
                                            lin_fastpath_pass,
                                            lin_fastpath_plan)

        if not batch or not self.fastlane_enabled \
                or not lin_fastpath_on():
            return [], batch
        from ..checker.base import VALID

        decided, live = [], []
        for r in batch:
            if (r.terminal or r.cancelled.is_set()
                    or r.consistency != "linearizable"
                    or r.algorithm not in LIN_FASTPATH_ALGOS
                    or r.force_host or not r.encs
                    or getattr(r.model, "txn_graph", False)):
                # (a transaction unit has no host certifier: its graph
                # is inferred and closed inside the launch)
                live.append(r)
                continue
            # the lane TRIED this request (scanned it, or was told by
            # the gate not to): execute() may suppress the in-checker
            # pass for it (and only for it)
            r._fp_tried = True
            plan = lin_fastpath_plan(r.encs, r.model)
            rs: list = [None] * len(r.encs)
            scans: list = []
            wall = 0.0
            if plan:
                with span("dispatch.scan") as scan:
                    rs = lin_fastpath_pass(r.encs, r.model, plan=plan,
                                           defer=scans)
                wall = scan.s
                r.scanned = time.monotonic()
            # the pass deliberately leaves 0-event rows undecided (the
            # kernel path stamps them "trivial"); here they are
            # host-decidable for free and must not force an otherwise
            # fully-certified request onto the batch path
            for j, enc in enumerate(r.encs):
                if rs[j] is None and enc.n_events <= 0:
                    rs[j] = {"valid?": VALID, "algorithm": "trivial",
                             "op-count": 0, "decided-tier": "trivial"}
            # honor a cancel that landed DURING the scan — the batch
            # path's demux re-checks at the same point (first-wins
            # finish keeps the race harmless either way)
            whole = all(res is not None for res in rs)
            deliver = whole and not r.cancelled.is_set()
            lin_fastpath_commit(scans, used=deliver)
            if not whole:
                live.append(r)
                continue
            if not deliver:
                r.finish(CANCELLED)
                decided.append(r)
                continue
            tiers: dict = {}
            for res in rs:
                t = res["decided-tier"]
                tiers[t] = tiers.get(t, 0) + 1
                note_tier(t, wall_s=wall / max(len(rs), 1))
            r.stats = {
                "fastlane": True,
                "batched_requests": 0,
                "batch_rows": r.n_rows,
                "batch_wall_s": round(wall, 4),
                "decided_tier": tiers,
                "placement": {"shard": None, "n_shards": 0},
                "degraded": False,
            }
            _stamp_phases(r, results_at=0.0)
            r.finish(DONE, results=rs)
            decided.append(r)
        return decided, live

    def next_batch(self, timeout: float,
                   on_decided=None) -> List[CheckRequest]:
        """Block up to `timeout` for a batch. After the first pick, if
        the launch is far from full and the head's deadline allows,
        linger for same-bucket arrivals until the batch-wait window
        closes (deadline order is preserved: the linger only ever ADDS
        rows to the head's launch, it never reorders across buckets).

        The window opened when the batch's OLDEST member was admitted
        (`min(r.submitted)`; `_choose` orders by effective deadline, so
        that need not be the head), not at the take: the dispatcher
        waits ``batch_wait - age`` and no longer. A batch that queued
        for part of the window waits the rest, and one that queued
        longer behind a busy dispatcher launches at once — whatever
        was going to arrive with it is in the queue and was taken with
        it, or is being decoded right now: while a submission is
        announced (`AdmissionQueue.announce`; any bucket's, the queue
        cannot tell before the decode) a batch whose window has passed
        is held for it, until it lands or gives up and at most one
        window from the take, because the launch it misses by
        milliseconds costs it a whole cycle. A batch of ONE request
        was taken without company, so its age says nothing of who is
        still coming: its window opens at the take, at an idle service
        and behind a busy dispatcher alike (two clients in a closed
        loop would otherwise take turns, each launched alone the moment
        the other's launch ends). The window also closes when the launch
        is full: the wait is on the admission queue's condition, not a
        sleep. Span `dispatch.linger` is entered for every batch the
        linger applies to and records the seconds waited;
        `lingers_elapsed` counts those whose window had passed at the
        take (never a lone request's).

        ``on_decided`` (ISSUE 14): when given, the fast lane certifies
        the popped requests FIRST — before the linger, so a decided
        request's latency is the host scan, not the batching window —
        and decided requests are delivered to the callback instead of
        the returned batch (linger top-ups ride the lane too)."""
        with span("dispatch.take"):
            batch = self.queue.take(self._choose, timeout)
        if not batch:
            return batch
        _stamp_taken(batch)
        if on_decided is not None:
            done, batch = self.fastlane(batch)
            if done:
                on_decided(done)
            if not batch:
                return []
        head = batch[0]
        rows = sum(r.n_rows for r in batch)
        now = time.monotonic()
        if (self.batch_wait > 0 and rows < self.max_batch_rows
                and not head.solo
                and head.deadline - now > self.batch_wait):
            sig = bucket_signature(head)
            # a lone request has no company yet: its window opens here
            opened = (now if len(batch) == 1
                      else min(r.submitted for r in batch))
            closes = opened + self.batch_wait
            holds = now + self.batch_wait

            def topup(pending: List[CheckRequest]) -> List[CheckRequest]:
                extra, extra_rows = [], rows
                for r in sorted(pending, key=lambda r: (
                        effective_deadline(r, self.aging_cap_s),
                        r.submitted)):
                    if r.solo or bucket_signature(r) != sig:
                        continue
                    if extra_rows + r.n_rows > self.max_batch_rows:
                        break
                    extra.append(r)
                    extra_rows += r.n_rows
                return extra

            extras: List[CheckRequest] = []
            with span("dispatch.linger"):
                if closes <= now:
                    with self._seq_lock:
                        self.lingers_elapsed += 1
                while rows < self.max_batch_rows:
                    t = time.monotonic()
                    # either wait wakes on every admission; a different
                    # bucket's arrival is left in the queue and the
                    # wait goes on
                    if t < closes:
                        more = self.queue.take(topup, timeout=closes - t)
                    elif t < holds:
                        # the window has passed: only company in sight
                        # is waited for. A submission a handler is
                        # decoding lands in tens of milliseconds and
                        # would otherwise wait out this whole launch;
                        # with none announced this returns at once
                        more = self.queue.take(topup, timeout=holds - t,
                                               while_arriving=True)
                        if not more:
                            break
                    else:
                        break
                    _stamp_taken(more)
                    rows += sum(r.n_rows for r in more)
                    extras.extend(more)
            if on_decided is not None and extras:
                done, extras = self.fastlane(extras)
                if done:
                    on_decided(done)
            batch.extend(extras)
        # Requests cancelled between pop and here stay in the batch:
        # execute() finalizes them as CANCELLED (dropping them silently
        # would leave their waiters blocked forever).
        return batch

    # ------------------------------------------------------ execution

    def execute(self, batch: List[CheckRequest],
                placement: Optional[dict] = None) -> dict:
        """Run one coalesced batch and demux; returns batch-level stats
        for the daemon's counters. Cancelled requests are finalized
        without results (a cancel landing mid-chunk is honored at
        demux: the row work is already spent, the verdict is simply
        not delivered). `placement` (the daemon's shard-routing record:
        shard id, shard count, loads at dispatch) is stamped into every
        request's stats so a tenant's trace shows WHERE its launch ran."""
        live = []
        for r in batch:
            if r.terminal:
                # stale watchdog-requeue twin: the other copy already
                # delivered the client-visible result (finish is
                # first-wins) — nothing to execute or finalize.
                continue
            if r.cancelled.is_set():
                r.finish(CANCELLED)
            else:
                r.status = RUNNING
                r.run_started = time.monotonic()
                live.append(r)
        if not live:
            return {"requests": 0, "rows": 0, "degraded": False,
                    "wall_s": 0.0, "tiers": {}}
        with self._seq_lock:
            self._seq += 1
            seq = self._seq
        install_compile_counters()  # the scope below counts this launch's
        encs = [e for r in live for e in r.encs]
        model = live[0].model
        algorithm = live[0].algorithm
        consistency = live[0].consistency
        # Weaker-rung batches pass the knob through; the default rung
        # keeps the historical check_fn arity (injected seams predate
        # the consistency parameter).
        check_kw = ({"consistency": consistency}
                    if consistency != "linearizable" else {})
        if consistency == "linearizable" and self.fastlane_enabled \
                and live and all(getattr(r, "_fp_tried", False)
                                 for r in live):
            # ISSUE 14: the dispatch fast lane TRIED every request in
            # this batch (scanned it, or consulted the measured gate
            # and was routed kernel-first) — the in-checker fast path
            # scanning them again inside check_encoded would be the
            # double-scan the rung-skip satellite closes. Requests the
            # lane skipped WITHOUT trying (force_host retries,
            # cancelled-at-pop, non-kernel algorithms) keep the
            # checker/host-ladder fast path: for them nothing was
            # tried yet. Only on the default check path (injected
            # seams keep their arity).
            check_kw["lin_fastpath"] = False
        host_kw = dict(check_kw)
        if not self._default_host_fallback:
            host_kw.pop("lin_fastpath", None)
        label = "graftd:" + ",".join(r.id for r in live)
        degraded_note_local = None
        # Autotune consult marker (PR 6): the checker applies per-bucket
        # plans inside check_encoded; snapshot the applied-plan SEQUENCE
        # (not the bounded log's length — that pins at the bound once
        # trimming starts). Entries are additionally filtered to THIS
        # thread (ISSUE 7): with multiple shard executors running
        # concurrently, "everything after the mark" would include
        # neighbor shards' plans — the thread filter keeps each batch's
        # stamp to exactly the plans its own launch consulted.
        autotune_mark = autotune.applied_seq()
        t0 = time.perf_counter()
        with stats_scope(label=label) as scan, \
                annotate("launch.host", seq=seq, rows=len(encs)):
            try:
                if any(r.force_host for r in live):
                    # Hung-batch watchdog second strike (ISSUE 8): the
                    # first requeue re-ran the device path and it hung
                    # again, so this retry goes STRAIGHT to the bounded
                    # host ladder — a slower sound verdict instead of a
                    # third chance to wedge a shard. Raising
                    # WatchdogDegrade reuses the degrade arm below
                    # verbatim (stamped degraded, therefore never
                    # cached).
                    raise WatchdogDegrade(
                        "hung batch exceeded its deadline twice; "
                        "watchdog forced the host ladder")
                results = self.check_fn(encs, model, algorithm=algorithm,
                                        **check_kw)
            except Exception as e:
                # Device path died mid-check (backend teardown,
                # injected fault): degrade THIS batch to the
                # host-only ladder — a slower sound verdict beats a
                # failed request. The stamp is LOCAL to this batch's
                # results; the process-wide first-note-wins registry is
                # only written for platform-level failures (backend
                # init / runtime-gone flavors), where "this process is
                # degraded" is genuinely true of later batches too — a
                # one-off non-platform error must not poison every
                # healthy verdict a long-lived daemon produces after it
                # (check_encoded stamps all results whenever the
                # registry holds a note).
                LOG.warning("graftd batch seq=%d device path failed; "
                            "degrading %d rows to host CPU",
                            seq, len(encs), exc_info=True)
                degraded_note_local = (
                    f"graftd degraded to host CPU mid-check: "
                    f"{type(e).__name__}: {e}"[:300])
                if is_backend_init_failure(e):
                    note_degraded(degraded_note_local)
                results = [self.host_fallback(enc, model, **host_kw)
                           for enc in encs]
                for res in results:
                    res["platform-degraded"] = degraded_note_local
            wall = time.perf_counter() - t0
            # Everything in the check outside the kernel launches: the
            # grouping, the packing, building the launches, the result
            # dicts, any host escalation. The scope holds this launch's
            # `launch.device` seconds, so nothing in `check_fn` falls
            # between the two spans.
            device_s = scan.get("spans", {}).get("launch.device",
                                                 (0, 0.0))[1]
            note_span("launch.host", max(0.0, wall - device_s))
        if (check_kw.get("lin_fastpath") is False
                and degraded_note_local is None
                and not scan.get("programs_built")):
            # The lane consulted the gate for every request of this
            # launch, so it owes the gate the other side: what a row
            # cost through the kernels, under each request's own row
            # class. A launch that built or loaded a program is not a
            # cost sample (the scope counts this launch's own).
            from ..checker.linearizable import lin_fastpath_observe_kernel

            for r in live:
                lin_fastpath_observe_kernel(r.encs, model, r.n_rows,
                                            wall / len(encs))
        results_at = time.monotonic()
        scan_counters = {k: v for k, v in scan.items()
                         if k not in ("label", "tiers", "spans")}
        scan_counters["spans"] = {
            k: {"n": v[0], "s": round(v[1], 6)}
            for k, v in scan.get("spans", {}).items()}
        autotune_plans = autotune.applied_since(
            autotune_mark, thread_id=threading.get_ident())
        batch_tiers: dict = {}
        cursor = 0
        for r in live:
            with span("demux.results"):
                mine = results[cursor:cursor + r.n_rows]
                cursor += r.n_rows
                # Tier attribution (ISSUE 13): which decision-ladder
                # tier decided each of this request's rows — the
                # per-request trace record's capacity-model evidence,
                # aggregated daemon-wide into /stats decided_tier.
                tiers: dict = {}
                for res in mine:
                    t = res.get("decided-tier") if res else None
                    if t is not None:
                        tiers[t] = tiers.get(t, 0) + 1
                        batch_tiers[t] = batch_tiers.get(t, 0) + 1
                r.stats = {
                    "batched_requests": len(live),
                    "batch_rows": len(encs),
                    "batch_seq": seq,
                    "batch_wall_s": round(wall, 4),
                    "scan": dict(scan_counters, label=label),
                    "autotune_plans": autotune_plans,
                    "decided_tier": tiers,
                    "placement": dict(placement) if placement else
                    {"shard": 0, "n_shards": 1},
                    "degraded": degraded_note_local is not None,
                }
            if r.cancelled.is_set():
                r.finish(CANCELLED)
            elif any(res is None for res in mine):
                r.finish(FAILED, error="checker returned no verdict")
            else:
                self._attach_counterexamples(r, mine)
                _stamp_phases(r, results_at)
                r.finish(DONE, results=mine)
        return {"requests": len(live), "rows": len(encs),
                "degraded": degraded_note_local is not None,
                "wall_s": wall, "seq": seq, "tiers": batch_tiers}

    #: Skip counterexample minimization for units beyond this many ops:
    #: the greedy pair-drop is bounded anyway (counterexample.py caps),
    #: but even the suffix-truncation re-search costs a CPU frontier
    #: pass — a tenant submitting huge invalid histories should not
    #: stall the shard's demux.
    MAX_COUNTEREXAMPLE_OPS = 2048

    def _attach_counterexamples(self, r: CheckRequest, mine: list) -> None:
        """ISSUE-10 satellite: a `fail` verdict leaving graftd (result
        record AND trace record — the daemon writes traces from the
        same result lists) carries the minimized witness
        (checker/counterexample.py), not a raw op dump. Best-effort:
        explanation failures must never take down a sound verdict."""
        from ..checker.base import INVALID
        from ..checker.counterexample import attach_counterexample
        from ..checker.linearizable import DEFAULT_MAX_CPU_CONFIGS

        if getattr(r.model, "txn_graph", False):
            # a flagged transaction row's witness is its anomaly's name
            # and cycle, from the edges its launch inferred off its
            # encoding (so on both wires)
            from ..checker.txn_graph import explain

            todo = [res for res in mine if res.get("valid?") is INVALID]
            if todo:
                with span("demux.counterexample", n=len(todo)):
                    for res in todo:
                        try:
                            explain(res)
                        except Exception:
                            LOG.warning("anomaly explanation failed for "
                                        "%s", r.id, exc_info=True)
            return
        todo = [(label, hist, res) for (label, hist), res
                in zip(r.units, mine)
                if res.get("valid?") is INVALID
                and res.get("op-count", 0) <= self.MAX_COUNTEREXAMPLE_OPS]
        if not todo:
            return
        # one span a request; it counts the invalid rows explained
        with span("demux.counterexample", n=len(todo)):
            for label, hist, res in todo:
                try:
                    attach_counterexample(res, hist, r.model,
                                          max_cpu_configs=
                                          DEFAULT_MAX_CPU_CONFIGS,
                                          consistency=r.consistency)
                except Exception:
                    LOG.warning("counterexample attach failed for %s/%s",
                                r.id, label, exc_info=True)
