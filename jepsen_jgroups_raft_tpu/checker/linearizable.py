"""Linearizability checker with selectable execution backend.

Equivalent of `jepsen.checker/linearizable {:model m :algorithm ...}`
(reference register.clj:106-111, counter.clj:133-137) with the north-star
addition: algorithm ``"jax"`` runs the search on TPU (BASELINE.json —
"`jepsen.checker/linearizable` gains an `:algorithm :jax` option behind the
existing Checker protocol").

Algorithms:
  * ``"jax"``  — pack to event tensors, run the on-device frontier kernel
                 (ops/linear_scan.py); batched across histories.
  * ``"cpu"``  — the unbounded host frontier search (wgl_cpu.py).
  * ``"dfs"``  — the knossos/porcupine-style DFS-with-undo (dfs_cpu.py):
                 a genuinely different search order.
  * ``"race"`` — run the on-device frontier kernel AND the DFS engine
                 concurrently; per history, the first engine to decide
                 wins — the knossos.competition/analysis analogue
                 (reference raft_test.clj:26,41,64: :linear vs :wgl,
                 first finisher's answer is taken).
  * ``"auto"`` — jax when the history fits the kernel window, with sound
                 escalation: any verdict the kernel cannot certify
                 (window overflow, frontier overflow on an invalid result)
                 is re-checked on the CPU twin.

Soundness contract: a kernel "valid" is always sound (only reachable
configurations are ever retained, so a surviving linearization is real); a
kernel "invalid" is sound unless the frontier overflowed its fixed capacity,
in which case we escalate instead of reporting.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import numpy as np

from ..history.ops import History
from ..history.packing import (EncodedHistory, bucket_rows, encode_history,
                               macro_events_on, pack_batch,
                               pack_macro_batch, pad_batch_bucketed)
from ..ops.dense_scan import (MASK_DENSE_MAX_SLOTS, MERGE_MAX_EVENTS,
                              dense_plans_grouped, make_dense_batch_checker)
from ..ops.kernel_ir import WIDE_WINDOW_SLOTS
from ..ops.linear_scan import (DEFAULT_N_CONFIGS, MAX_SLOTS, bucket_slots,
                               make_batch_checker, make_sort_chunk_checker)
from ..platform import degraded_note, env_int, install_compile_counters
from . import autotune
from .base import Checker, INVALID, UNKNOWN, VALID
from .dfs_cpu import SearchBudgetExceeded, check_encoded_dfs
from .schedule import (ChunkLaunch, build_dense_launches, launch_span,
                       note_tier, note_wide, run_chunked, scan_chunk,
                       snapshot_compiles, span)
from .wgl_cpu import FrontierOverflow, check_encoded_cpu


#: Default CPU-frontier cap. The search is worst-case exponential in the
#: concurrency window (SURVEY.md §7.4.1); an unbounded fallback would hang
#: rather than answer on adversarial histories (e.g. 40 mutually-concurrent
#: writes). Capped, it reports "unknown" instead — the same stance the
#: reference community takes when knossos becomes "unfeasible to verify"
#: (reference doc/intro.md:35-41), but as a clean verdict, not an OOM.
DEFAULT_MAX_CPU_CONFIGS = 1 << 18

# --------------------------------------- lin-rung fast path (ISSUE 14)
# The cheap-decision tier (checker/consistency.certify_encoded) runs as
# a PRE-KERNEL pass on the un-relaxed stream at the linearizable rung:
# a witness respecting every [OPEN, FORCE] interval of the original
# encoding IS a linearization, so a certified row is a sound VALID
# decided on the host in O(E·W) — no kernel launch, no batch slot.
# Undecided rows fall through to the ordinary ladder unchanged, so
# verdicts are bitwise-identical with the path force-disabled
# (JGRAFT_LIN_FASTPATH=0, the ablation/A-B arm). The worst case (host
# scan AND kernel) is bounded two ways: a length-scaled abort budget
# per row, and measured per-bucket gating (checker/autotune.py
# lin_fastpath_route) that routes a bucket kernel-first once a verdict
# the caller used costs more from the certifier than from the kernels.

#: Algorithms the fast path fronts: the kernel-launching selectors. An
#: explicit "cpu"/"dfs" keeps its host engine (tests use them as
#: oracles), and "race" already runs its own host engine concurrently.
#: Shared with graftd's dispatch fast lane (service/scheduler.py) so
#: the two surfaces can never drift.
LIN_FASTPATH_ALGOS = ("auto", "jax")


def lin_fastpath_on() -> bool:
    """Whether the linearizable-rung pre-kernel certify pass runs.
    Default ON; ``JGRAFT_LIN_FASTPATH=0`` force-disables (defensive
    parse — garbage keeps the default)."""
    return env_int("JGRAFT_LIN_FASTPATH", 1, minimum=0) != 0


def lin_abort_steps() -> int:
    """Per-event abort budget for the fast path's host scan
    (``JGRAFT_LIN_FASTPATH_ABORT``, default 32 `model.step` calls per
    stream event; 0 = unbounded). A hopeless row aborts after
    budget·E steps, bounding its cost to a fraction of its kernel
    wall. Calibrated on the 200×1k host-CPU A/B (2026-08-04): valid
    rows certify in ~2–8 step calls per event (register 8 ms/row, the
    heaviest backtracking family queue ~15 ms/row, both far under the
    budget), while an uncertifiable row at 128/event burned ~2.5× its
    per-row kernel wall — 32/event keeps the worst case under it."""
    return env_int("JGRAFT_LIN_FASTPATH_ABORT", 32, minimum=0)


_FP_LOCK = threading.Lock()
_FP_ZERO = {"rows_scanned": 0, "rows_certified": 0, "rows_delivered": 0,
            "rows_gated": 0, "rows_rung_skipped": 0, "events_scanned": 0,
            "certify_wall_s": 0.0}
_FP_COUNTERS = dict(_FP_ZERO)


def _fp_bump(**kw) -> None:
    with _FP_LOCK:
        for k, v in kw.items():
            _FP_COUNTERS[k] += v


def fastpath_counters() -> dict:
    """Process-wide lin-fastpath counters (non-destructive):
    rows_scanned/rows_certified (what the scan did), rows_delivered
    (certified rows whose verdict the caller used: delivered over
    scanned is the hit share the gate routes on), rows_gated (routed
    kernel-first by the measured gate: gated over gated + scanned is
    how often it engages), rows_rung_skipped (weak-rung re-entries that
    skipped the redundant second scan — the ISSUE-14 double-scan
    satellite's evidence), and the summed certify wall."""
    with _FP_LOCK:
        return dict(_FP_COUNTERS)


def consume_fastpath_counters() -> dict:
    """Return and reset the counters."""
    global _FP_COUNTERS
    with _FP_LOCK:
        out = dict(_FP_COUNTERS)
        _FP_COUNTERS = dict(_FP_ZERO)
        return out


def _fp_buckets(encs: Sequence[EncodedHistory], model,
                batch_rows: int) -> dict:
    """Row indices of `encs` by the autotuner's gating bucket, for rows
    of a batch of `batch_rows` rows. 0-event rows are in no bucket:
    they keep their "trivial" tier."""
    fam = type(model).__name__
    buckets: dict = {}
    for i, e in enumerate(encs):
        if e.n_events > 0:
            buckets.setdefault(autotune.lin_fastpath_sig(
                fam, e.n_events, batch_rows), []).append(i)
    return buckets


#: Rows longer than this many events (by their gating bucket's padded
#: length) are never scanned host-first: the gate's rule is decided
#: before it is tried. The certifier is Python by the event, and what a
#: long row's pending crashed ops cost it grows with the row: a 100k-op
#: register history (146-147k events, 1-3 ops crashed) took 235-326 s
#: to certify, six of six at tier `backtrack` (my host runs, PR 44),
#: where the kernels decide it in seconds; and the gate cannot learn
#: that in time, because it closes a row class only after
#: `lin_fastpath_min_obs` (64) rows on each side, and a class of
#: one-history requests that all certify never shows it a kernel row.
#: The same length is where a row starts to count as LONG in `/stats`
#: `long_rows` (`_check_encoded`): one threshold for the lane's gate and
#: the counter.
LIN_FASTPATH_MAX_EVENTS = 8192


def lin_fastpath_plan(encs: Sequence[EncodedHistory], model) -> list:
    """Group a linearizable-rung batch (what its caller delivers
    together: `check_encoded`'s batch, one request in graftd's lane)
    into the autotuner's gating buckets and consult the gate once per
    bucket: returns ``[(sig, row indices)]`` for the buckets it routes
    host-first and counts the rows of the others as gated. A length
    bucket past `LIN_FASTPATH_MAX_EVENTS` is gated unasked."""
    plan = []
    for sig, idxs in _fp_buckets(encs, model, len(encs)).items():
        if sig[2] <= LIN_FASTPATH_MAX_EVENTS and \
                autotune.lin_fastpath_route(sig):
            plan.append((sig, idxs))
        else:
            _fp_bump(rows_gated=len(idxs))
    return plan


def lin_fastpath_commit(scans: list, used: bool) -> None:
    """Hand the gate what `lin_fastpath_pass(defer=scans)` held back:
    each bucket's scanned rows and wall, with its certified rows as
    hits only if the caller `used` those verdicts."""
    for sig, rows, certified, wall_s in scans:
        hits = certified if used else 0
        autotune.lin_fastpath_observe(sig, rows=rows, hits=hits,
                                      wall_s=wall_s)
        _fp_bump(rows_delivered=hits)


def lin_fastpath_pass(encs: Sequence[EncodedHistory], model,
                      plan: Optional[list] = None,
                      defer: Optional[list] = None) -> list:
    """Run the certifier over a linearizable-rung batch; returns one
    result dict per row, None where undecided (the caller sends those
    through the kernel ladder). Only the buckets of `plan`
    (`lin_fastpath_plan`, made here when not given) are scanned. What
    the gate learns from a scan is how many of its verdicts were USED,
    which only the caller knows: `check_encoded` evicts every certified
    row from the kernel batch, so without `defer` the pass commits its
    certified rows as hits itself and notes their tiers. graftd's fast
    lane (service/scheduler.py) passes a list as ``defer``: its
    all-or-nothing rule may DISCARD a partially-certified request's
    results, so the pass appends ``(sig, rows, certified, wall_s)`` per
    bucket there and the lane commits them (`lin_fastpath_commit`) once
    it knows, and notes tiers itself for the requests it delivers — a
    discarded row must not be attributed here only to be attributed
    again by the kernel that decides it. rows_scanned/rows_certified
    count SCAN outcomes and stay unconditional."""
    from .certify_batch import certify_many

    results: list = [None] * len(encs)
    if plan is None:
        plan = lin_fastpath_plan(encs, model)
    scans = [] if defer is None else defer
    abort = lin_abort_steps()
    for sig, idxs in plan:
        t0 = time.perf_counter()
        hits = 0
        # whole bucket through the batched certifier core (ISSUE 15;
        # outcome-identical to the per-row scalar loop — the
        # JGRAFT_CERTIFY_BATCH=0 arm — with the same per-row length-
        # scaled abort budgets)
        certs = certify_many(
            [encs[i] for i in idxs], model,
            max_steps=[abort * max(encs[i].n_events, 1) if abort
                       else None for i in idxs])
        for i, (ok, tier, _) in zip(idxs, certs):
            if ok:
                hits += 1
                results[i] = {
                    "valid?": VALID,
                    "algorithm": "greedy-witness",
                    "op-count": encs[i].n_ops,
                    "concurrency-window": encs[i].n_slots,
                    # namespaced distinctly from the weak-rung
                    # certifier's greedy/backtrack so fleet tier
                    # attribution never conflates the two hit-rates
                    "decided-tier": tier + "@lin",
                }
        dt = time.perf_counter() - t0
        # Fair-share wall attribution, same stance as the weak rung:
        # every scanned row (certified or not) cost ~dt/len(idxs); the
        # certified rows book that share, the undecided rows' verdict
        # cost is the kernel tier's wall.
        per_row = dt / max(len(idxs), 1)
        if defer is None:
            for i in idxs:
                if results[i] is not None:
                    note_tier(results[i]["decided-tier"],
                              wall_s=per_row)
        scans.append((sig, len(idxs), hits, dt))
        _fp_bump(rows_scanned=len(idxs), rows_certified=hits,
                 events_scanned=sum(encs[i].n_events for i in idxs),
                 certify_wall_s=dt)
    if defer is None:
        lin_fastpath_commit(scans, used=True)
    return results


def lin_fastpath_observe_kernel(encs: Sequence[EncodedHistory], model,
                                batch_rows: int, wall_s_per_row: float
                                ) -> None:
    """Give the gate the kernel side of its comparison: `encs`, rows of
    a batch of `batch_rows` rows (the row class the routing used), were
    decided through the kernel ladder at `wall_s_per_row` — the wall of
    the launch they rode over all its rows."""
    if not autotune.autotune_on():
        return
    for sig, idxs in _fp_buckets(encs, model, batch_rows).items():
        if sig[2] <= LIN_FASTPATH_MAX_EVENTS:   # past it: nothing to learn
            autotune.lin_fastpath_observe_kernel(
                sig, rows=len(idxs), wall_s=wall_s_per_row * len(idxs))


def _observe_kernel_cost(rest: Sequence[EncodedHistory], model,
                         batch_rows: int, kernel_path) -> list:
    """Run `kernel_path(rest)`, timed as the gate's kernel sample. A
    call during which the backend built or loaded a program is not a
    cost sample (the first launch at a shape is 5-10 s of compile)."""
    install_compile_counters()
    built = snapshot_compiles()["programs_built"]
    t0 = time.perf_counter()
    results = kernel_path(rest)
    dt = time.perf_counter() - t0
    if snapshot_compiles()["programs_built"] == built:
        lin_fastpath_observe_kernel(rest, model, batch_rows,
                                    dt / len(rest))
    return results


def check_histories(
    histories: Sequence[History],
    model,
    algorithm: str = "auto",
    n_configs: Optional[int] = None,
    n_slots: Optional[int] = None,
    witness: bool = False,
    max_cpu_configs: Optional[int] = DEFAULT_MAX_CPU_CONFIGS,
    consistency: str = "linearizable",
) -> list[dict]:
    """Check a batch of histories; returns one result dict per history.

    The batch is the unit of TPU work: all histories are packed, padded to a
    common event length, and verified in one vmapped kernel launch.
    n_configs/n_slots default to auto: the concurrency window is sized to
    the batch's real maximum (exact ≤16 slots, else bucketed to
    SLOT_BUCKETS 31/63/95/127) — per-event closure work scales with C×W,
    so a snug window is a direct kernel-speed win.

    ``consistency`` selects the verdict's rung on the weaker-consistency
    ladder (checker/consistency.py): "linearizable" (default),
    "sequential", "session" — weaker rungs re-run the SAME machinery on
    a relaxed-precedence re-encoding, with a greedy witness fast path.
    """
    encs = [encode_history(h, model) for h in histories]
    return check_encoded(encs, model, algorithm, n_configs, n_slots,
                         witness, max_cpu_configs,
                         consistency=consistency)


def check_encoded(
    encs: Sequence[EncodedHistory],
    model,
    algorithm: str = "auto",
    n_configs: Optional[int] = None,
    n_slots: Optional[int] = None,
    witness: bool = False,
    max_cpu_configs: Optional[int] = DEFAULT_MAX_CPU_CONFIGS,
    distribute: bool = True,
    consistency: str = "linearizable",
    lin_fastpath: Optional[bool] = None,
    serve_rows: Optional[int] = None,
) -> list[dict]:
    """Pack-once/check-many entry: verify histories that are ALREADY
    encoded (`history.packing.encode_history`), one result dict each.

    This is the seam the checking service (service/scheduler.py) batches
    through: graftd encodes every submission exactly once at admission
    (the encoding bytes are also its result-cache fingerprint), then
    re-enters here with the concatenation of many tenants' encodings —
    the dense grouping, pow2+midpoint bucketing, and chunked wavefront
    below treat those foreign rows exactly like a single caller's batch
    (rows are independent along the batch axis; doc/checker-design.md
    §8). `check_histories` is the encode-then-delegate wrapper.

    Multi-host (ISSUE 7): inside an initialized `jax.distributed`
    cluster this entry runs the SHARDED wavefront — each process checks
    only its contiguous row shard through the ordinary machinery below
    and the per-row verdicts are exchanged so every process returns the
    full batch (parallel/distributed.run_sharded; placement model in
    doc/checker-design.md §10). The caller contract is SPMD: every
    process calls with the same batch — true of the `check` CLI run
    once per host. `distribute=False` (graftd's
    per-host scheduler, whose admission queues are host-local) and
    ``JGRAFT_DISTRIBUTED=0`` both pin the single-process path; outside
    a cluster the seam is inert by construction.

    ``consistency`` (checker/consistency.py): a weaker rung relaxes the
    batch's FORCE placement ONCE here, greedy-certifies what a host
    witness scan can, and re-enters this entry at the linearizable rung
    with the relaxed encodings — so every downstream path (dense
    grouping, bucketing, chunked wavefront, distribution, graftd
    coalescing) serves the rungs unchanged. Results carry a
    ``consistency`` key whenever a non-default rung decided them.

    ``lin_fastpath`` (ISSUE 14): None = the default — at the
    linearizable rung with a kernel algorithm, run the pre-kernel
    certify pass (`lin_fastpath_pass`) and send only undecided rows to
    the kernels. False = skip it; the weak-rung recursion below passes
    False because its rows were ALREADY scanned by the rung certifier
    on a superset-legality stream (a second scan of the relaxed bytes
    is pure waste — rows_rung_skipped counts the saved work), and
    graftd's fast lane passes False after certifying at dispatch.

    ``serve_rows`` (ISSUE 32): the caller is a service whose launches
    hold up to this many rows (graftd's scheduler passes its batch
    cap). A kernel key the batch meets for the first time is then built
    WHOLE, every row bucket up to that count, before it is launched
    (checker/schedule.py `build_keys`), and launch plans are taken as
    memory holds them (`autotune.preload_plans`, else the default):
    nothing is read from the plan store and no candidate is compiled or
    timed on the calling thread. None (a library caller) builds a
    bucket when a launch reaches it and may measure a plan.
    """
    from ..parallel import distributed

    consistency = _normalize_rung(consistency)
    if getattr(model, "txn_graph", False):
        # A transaction model's rows (ISSUE 51) go to the cycle family
        # as the others go to theirs: the transactions' dependency
        # graph, inferred and closed inside this launch
        # (checker/txn_graph.py). The verdict is strict
        # serializability, which is the linearizable rung with a
        # transaction as the op; there is no weaker rung of it here.
        from .txn_graph import check_txn_rows

        if consistency != "linearizable":
            raise ValueError(
                f"{type(model).__name__} rows are checked at the "
                f"linearizable rung (strict serializability) only, "
                f"not {consistency!r}")
        results = check_txn_rows(encs, model, serve_rows=serve_rows,
                                 explain_flagged=serve_rows is None)
        note = degraded_note()
        if note:
            for r in results:
                r.setdefault("platform-degraded", note)
        return results
    if consistency != "linearizable":
        from .consistency import apply_rung

        t0 = time.perf_counter()
        relaxed, certified, tiers = apply_rung(encs, model, consistency)
        dt_cert = time.perf_counter() - t0
        results: list[Optional[dict]] = [None] * len(encs)
        todo: list[int] = []
        # Wall attribution: each certified row carries its FAIR share
        # of the whole certify+relax pass (dt_cert / batch rows) — the
        # undecided rows' share stays unattributed here on purpose
        # (their verdict cost is the kernel tier's wall); booking the
        # full batch wall onto the few certified rows would inflate
        # the cheap tier's reported cost arbitrarily.
        per_row = dt_cert / max(len(encs), 1)
        for i, (enc, ok) in enumerate(zip(relaxed, certified)):
            if ok:
                results[i] = {
                    "valid?": VALID, "algorithm": "greedy-witness",
                    "op-count": enc.n_ops,
                    "concurrency-window": enc.n_slots,
                    "decided-tier": tiers[i],
                }
                note_tier(tiers[i], wall_s=per_row)
            else:
                todo.append(i)
        # Exact cycle tier (ISSUE 13, checker/cycle.py): a dependency
        # cycle among the required ops is a sharp SC refutation, and
        # at the sequential rung it implies the kernel verdict INVALID
        # (doc §15) — so undecided rows consult it BEFORE paying the
        # relax + kernel-ladder pass. The tier only ever refutes;
        # cycle-free rows fall through unchanged.
        cycle_skips: dict = {}
        if todo and consistency == "sequential":
            from .cycle import cycle_tier_on, find_cycles

            if cycle_tier_on():
                t0 = time.perf_counter()
                cyc = find_cycles([encs[i] for i in todo], model)
                dt_cyc = time.perf_counter() - t0
                hits = [(j, i) for j, i in enumerate(todo)
                        if cyc[j] is not None and "cycle" in cyc[j]]
                # rows too big for the tier get the size-skip stamped
                # on whatever result the ladder reaches below (ISSUE
                # 19 satellite: the cap skip used to be invisible)
                cycle_skips.update(
                    (i, cyc[j]["skipped-size"]) for j, i in
                    enumerate(todo)
                    if cyc[j] is not None and "skipped-size" in cyc[j])
                for j, i in hits:
                    results[i] = {
                        "valid?": INVALID, "algorithm": "cycle",
                        "op-count": encs[i].n_ops,
                        "concurrency-window": encs[i].n_slots,
                        "decided-tier": "cycle",
                        "cycle": cyc[j]["cycle"],
                        "exact-sc-refutation": True,
                    }
                    note_tier("cycle", wall_s=dt_cyc / len(hits))
                if hits:
                    todo = [i for i in todo if results[i] is None]
        if todo:
            # lin_fastpath=False (ISSUE-14 satellite): these rows were
            # already scanned by the rung certifier above — on the
            # ORIGINAL stream and again on the relaxed one, whose
            # legality is a superset — so the lin fast path re-scanning
            # the relaxed bytes could never certify what apply_rung
            # just failed to. The counter proves the skip fires — and
            # only counts when the fast path would otherwise have run
            # (a JGRAFT_LIN_FASTPATH=0 ablation run must keep its
            # lin-fastpath counters absent, not claim saved scans).
            if algorithm in LIN_FASTPATH_ALGOS and lin_fastpath_on():
                _fp_bump(rows_rung_skipped=len(todo))
            sub = check_encoded([relaxed[i] for i in todo], model,
                                algorithm, n_configs, n_slots, witness,
                                max_cpu_configs, distribute,
                                consistency="linearizable",
                                lin_fastpath=False,
                                serve_rows=serve_rows)
            for i, r in zip(todo, sub):
                results[i] = r
        if consistency == "session":
            _annotate_sc_refutations(encs, results, model)
        for i, n_skipped in cycle_skips.items():
            if results[i] is not None:
                results[i]["cycle-skipped-size"] = n_skipped
        for r in results:
            r["consistency"] = consistency
        return results  # type: ignore[return-value]

    def _run_kernels(rest):
        if distribute and distributed.wavefront_active() and len(rest) > 1:
            return distributed.run_sharded(
                rest,
                lambda sub: _check_encoded(sub, model, algorithm,
                                           n_configs, n_slots, witness,
                                           max_cpu_configs, serve_rows),
                # the result-detail exchange (ISSUE 11 tentpole (d))
                # keys its store records over (model, algorithm, row
                # encoding); inert unless a shared store dir is
                # configured
                model=model, algorithm=algorithm)
        return _check_encoded(rest, model, algorithm, n_configs,
                              n_slots, witness, max_cpu_configs,
                              serve_rows)

    # Lin-rung pre-kernel fast path (ISSUE 14): certify on the host,
    # evict VALID rows from the batch BEFORE grouping/bucketing/
    # chunked-wavefront. Inside an active distributed wavefront the
    # certify pass itself is deterministic, but the measured gate
    # (autotune linfp records) is HOST-LOCAL state — two cluster
    # processes with different gate histories would evict different
    # rows and the SPMD collectives would mismatch — so sharded
    # batches stay kernel-first UNLESS the shared gate store
    # (ISSUE 18: autotune.linfp_shared_dir, JGRAFT_LINFP_DIR /
    # cluster-dir fallback) is configured: then every rank seeds its
    # gate from the same published snapshot and routes identically.
    # Residual race, documented: a publish landing between two ranks'
    # FIRST touch of the same bucket can still diverge their routing —
    # worst case a collective mismatch (an error/hang, i.e. liveness),
    # never a verdict change. graftd's per-host lane is unaffected
    # (its scheduler pins distribute=False).
    distributing = (distribute and distributed.wavefront_active()
                    and len(encs) > 1)
    gate_shared = autotune.linfp_shared_dir() is not None
    fp = None
    fronted = bool(encs and (not distributing or gate_shared)
                   and algorithm in LIN_FASTPATH_ALGOS
                   and lin_fastpath_on())
    if fronted and lin_fastpath is not False:
        fp = lin_fastpath_pass(encs, model)
        if not any(r is not None for r in fp):
            fp = None

    def _kernel_path(rest):
        # both paths meet here: whoever consulted the gate gives it the
        # kernels' cost for the rows it kept, under the row class it
        # consulted with (under lin_fastpath=False that was graftd's
        # lane, per request: scheduler.execute feeds its launches)
        if (fronted and lin_fastpath is not False
                and autotune.autotune_on()):
            return _observe_kernel_cost(rest, model, len(encs),
                                        _run_kernels)
        return _run_kernels(rest)

    if fp is not None:
        todo = [i for i, r in enumerate(fp) if r is None]
        results = fp
        if todo:
            for i, r in zip(todo, _kernel_path([encs[i] for i in todo])):
                results[i] = r
    else:
        results = _kernel_path(encs)
    note = degraded_note()
    if note:
        # Part of this process's work did not run where it was asked
        # to (platform.note_degraded): stamp every result so a degraded
        # run is distinguishable from an intended-CPU run in stored
        # artifacts.
        for r in results:
            r.setdefault("platform-degraded", note)
    return results


def _normalize_rung(name) -> str:
    """Cheap-path normalization: the default rung never imports the
    consistency module (keeps the hot linearizable path import-free)."""
    if name in (None, "linearizable"):
        return "linearizable"
    from .consistency import normalize_consistency

    return normalize_consistency(name)


def _annotate_sc_refutations(encs, results, model) -> None:
    """Session-rung SC evidence (ISSUE 13): the implemented session
    guarantee (monotonic reads + read-your-writes) does NOT imply full
    sequential consistency — a monotonic-writes violation can honestly
    PASS the rung — so a dependency cycle here is attached as an
    annotation, never a verdict change: ``sc-refuted`` marks results
    whose history is exactly proven non-SC even though the weaker rung
    holds (the sharper-than-relaxation acceptance evidence, pinned in
    tests/test_cycle.py). Best-effort and ablation-gated like the
    verdict tier."""
    from .cycle import cycle_tier_on, find_cycles

    if not cycle_tier_on():
        return
    try:
        cyc = find_cycles(encs, model)
    except Exception:
        return  # evidence must never take down a sound verdict
    for r, c in zip(results, cyc):
        if c is None or r is None:
            continue
        if "cycle" in c:
            r["sc-refuted"] = True
            r["sc-cycle"] = c["cycle"]
        elif "skipped-size" in c:
            r["cycle-skipped-size"] = c["skipped-size"]


def _check_encoded(
    encs: Sequence[EncodedHistory],
    model,
    algorithm: str = "auto",
    n_configs: Optional[int] = None,
    n_slots: Optional[int] = None,
    witness: bool = False,
    max_cpu_configs: Optional[int] = DEFAULT_MAX_CPU_CONFIGS,
    serve_rows: Optional[int] = None,
) -> list[dict]:
    results: list[Optional[dict]] = [None] * len(encs)

    if algorithm == "dfs":
        return [_check_dfs(e, model, witness,
                           max_steps=DEFAULT_DFS_BUDGET) for e in encs]

    if algorithm == "race":
        return _race(encs, model, n_configs, n_slots, witness,
                     max_cpu_configs)

    wide = [e.n_slots > WIDE_WINDOW_SLOTS and e.n_events > 0 for e in encs]
    if algorithm in LIN_FASTPATH_ALGOS:
        n_long = sum(e.n_events >= LIN_FASTPATH_MAX_EVENTS for e in encs)
        if any(wide) or n_long:
            note_wide(wide_rows=sum(wide), long_rows=n_long)

    if algorithm == "auto":
        # Wide-window fast path: a history whose concurrency window is
        # beyond every dense kernel is frontier-hostile (breadth-first
        # cost ~2^W), but usually DFS-trivial when valid (one witness
        # suffices; a round-3 hell-soak 19-slot counter history decided
        # in 4.3k DFS configs after 70s of doomed frontier work). Spend
        # a small DFS budget first; undecided histories take the normal
        # kernel → CPU → full-budget-DFS ladder below.
        first = [i for i, e in enumerate(encs)
                 if e.n_slots > MASK_DENSE_MAX_SLOTS and e.n_events > 0]
        if first:
            with span("launch.escalate", n=len(first)):
                for i in first:
                    r = _check_dfs(encs[i], model, witness,
                                   max_steps=FAST_DFS_BUDGET)
                    if r["valid?"] is not UNKNOWN:
                        results[i] = r
            note_wide(wide_rows_host=sum(
                results[i] is not None for i in first))

    if algorithm in LIN_FASTPATH_ALGOS:
        undecided = [e if results[i] is None else None
                     for i, e in enumerate(encs)]
        todo = [e for e in undecided if e is not None]
        # A backend failure propagates: a check that asked for the
        # accelerator never carries on on the host in its place.
        jax_res = _jax_pass(todo, model, n_configs, n_slots,
                            serve_rows=serve_rows)
        it = iter(jax_res)
        results = [r if r is not None else next(it) for r in results]
        if algorithm == "jax":
            for i, r in enumerate(results):
                if r is None:
                    results[i] = {
                        "valid?": UNKNOWN,
                        "algorithm": "jax",
                        "error": "kernel capacity exceeded "
                        f"(window {encs[i].n_slots} slots); "
                        "use algorithm='auto' or 'cpu'",
                    }
            return results  # type: ignore[return-value]

    left = [i for i, r in enumerate(results) if r is None]
    if not left:
        return results  # type: ignore[return-value]
    if algorithm != "auto":   # "cpu": the host engine was what was asked
        _escalate(encs, results, left, model, algorithm, witness,
                  max_cpu_configs)
        return results  # type: ignore[return-value]
    # What the device pass left undecided goes to the host engines, on
    # the calling thread (graftd's dispatcher): one span over all of it.
    with span("launch.escalate", n=len(left)):
        _escalate(encs, results, left, model, algorithm, witness,
                  max_cpu_configs)
    note_wide(wide_rows_host=sum(
        wide[i] and results[i].get("valid?") is not UNKNOWN for i in left))
    return results  # type: ignore[return-value]


def _escalate(encs, results, left, model, algorithm, witness,
              max_cpu_configs) -> None:
    """The host engines of `algorithm="auto"` (and of "cpu") for the
    rows `left` undecided: budgeted DFS and the CPU frontier twin."""
    for i in left:
        dfs_exhausted = False
        if algorithm == "auto" and \
                encs[i].n_slots > MASK_DENSE_MAX_SLOTS:
            # Wide windows that the kernels couldn't decide: try the
            # budgeted DFS BEFORE the CPU frontier twin — it explores in
            # a different order and often finds a single witness where
            # breadth-first frontiers explode (a round-3 hell-soak
            # counter history with a 19-slot crashed window decided in
            # 4.3k DFS configs after the 2^18-config frontier overflowed;
            # frontier-first wasted minutes on it). Budget exhaustion
            # falls through to the frontier, whose overflow cap is the
            # final "unfeasible to verify" verdict (reference
            # doc/intro.md:35-41 stance).
            r2 = _check_dfs(encs[i], model, witness,
                            max_steps=DEFAULT_DFS_BUDGET)
            if r2["valid?"] is not UNKNOWN:
                results[i] = r2
                continue
            dfs_exhausted = True  # deterministic: a re-run cannot differ
        if results[i] is None:
            results[i] = _check_cpu(encs[i], model, witness, max_cpu_configs)
        if results[i].get("valid?") is UNKNOWN and algorithm == "auto" \
                and not dfs_exhausted:
            r2 = _check_dfs(encs[i], model, witness,
                            max_steps=DEFAULT_DFS_BUDGET)
            if r2["valid?"] is not UNKNOWN:
                results[i] = r2


def _jax_pass(encs, model, n_configs=None, n_slots=None,
              note: bool = True, serve_rows: Optional[int] = None):
    """Run the on-device pass over a batch of encoded histories. Returns a
    result dict per history, or None where the kernel could not certify a
    verdict (window beyond MAX_SLOTS, or frontier overflow at top
    capacity) — the caller escalates those. `serve_rows`: see
    `check_encoded`."""
    results: list[Optional[dict]] = [None] * len(encs)
    cap = n_slots or MAX_SLOTS
    fits = [i for i, e in enumerate(encs)
            if e.n_slots <= cap and e.n_events > 0]
    for i, e in enumerate(encs):
        if e.n_events == 0:
            if note:
                note_tier("trivial")
            results[i] = {"valid?": VALID, "algorithm": "trivial",
                          "op-count": 0, "decided-tier": "trivial"}
    # Macro-event compaction (ISSUE-4 tentpole): every kernel family
    # consumes the macro stream unless JGRAFT_MACRO_EVENTS=0 pins the
    # legacy one-event-per-step stream (the differential/ablation path;
    # verdicts are bitwise-identical either way). Eligibility/grouping
    # above stays on the legacy encoding — only kernel consumption
    # switches.
    _group_pack = pack_macro_batch if macro_events_on() else pack_batch
    if fits:
        # Dense-bitset kernel first: exact (no overflow, no escalation)
        # and ~10× the sort kernel when the model's state domain is
        # enumerable and the window is small — the shapes the reference's
        # own workloads produce. Pinned n_configs/n_slots are sort-kernel
        # knobs, so an explicit pin keeps the sort path (tests rely on
        # capacity semantics).
        if n_configs is None and n_slots is None:
            # nested in the launch's host tile (`launch.host`), as
            # `launch.sync` is in `launch.device`: the domain scan and
            # the window grouping of the launch's rows
            with span("launch.group", n=len(fits)):
                grouped, rest = dense_plans_grouped(
                    model, [encs[i] for i in fits])
        else:
            grouped, rest = [], list(range(len(fits)))
        if grouped and scan_chunk() > 0:
            # Chunked wavefront (ISSUE 3, checker/schedule.py): the
            # event scan runs in fixed-size chunks, decided/exhausted
            # rows are evicted and survivors recompacted between
            # chunks, groups early-exit when empty, and every group's
            # chunk is row-sharded over the device mesh and dispatched
            # (async) before any result is blocked on — the placement
            # policy lives in build_dense_launches. The schedule
            # covers the event length the MONOLITHIC kernel would scan
            # (pad_batch_bucketed's floor_e=32 series for short
            # groups, exact for LONG ones) so `early_exit` reports
            # genuinely skipped reference work. JGRAFT_SCAN_CHUNK=0 is
            # the only way into the monolithic launch loop below, kept
            # because the differential matrices of tests/ use it as the
            # reference this wavefront is compared against.
            planned = []
            for idxs, plan in grouped:
                sub = [fits[j] for j in idxs]
                sub_encs = [encs[i] for i in sub]
                # Per-bucket autotuned plan (checker/autotune.py):
                # consulted per window group — a persisted plan loads,
                # a big-enough unplanned bucket measures once in
                # process (never for a service: `serve_rows`),
                # everything else (JGRAFT_AUTOTUNE=0, small groups,
                # LONG clusters) keeps today's defaults. The
                # plan's macro payload cap acts at pack time; its
                # chunk/fan-out halves act in build_dense_launches.
                tuned = autotune.tuned_group_plan(
                    model, plan, sub_encs, measure=serve_rows is None)
                planned.append((sub, plan, sub_encs, tuned))
            # the group packs of the launch, nested in `launch.host`
            # beside `launch.group`: one pass a group over its rows'
            # concatenated events (history/packing.py `_macro_fill`)
            with span("launch.pack", n=sum(len(p[0]) for p in planned)):
                triples = [
                    (sub, plan,
                     autotune.pack_group(sub_encs, tuned,
                                         window=plan.n_slots), tuned)
                    for sub, plan, sub_encs, tuned in planned]
            launches, subs = build_dense_launches(model, triples)
            with launch_span(rows=sum(len(sub) for sub in subs)):
                outs = run_chunked(launches, build_rows=serve_rows)
            for sub, out in zip(subs, outs):
                # Slices overlap on devices, so per-launch kernel
                # walls are not additive; each row reports its slice's
                # (overlapped) wall share, same stance as the
                # monolithic marginal-delta attribution.
                dt = out.wall_s / max(len(sub), 1)
                for j, i in enumerate(sub):
                    r = _jx(VALID if out.ok[j] else INVALID, encs[i],
                            dt, kernel=out.tag, note=note)
                    r["chunked"] = True
                    results[i] = r
        elif grouped:
            # Launch every window group BEFORE blocking on any result:
            # jax dispatch is async, so the device pipelines the groups
            # while the host packs the next one — blocking per group
            # would serialize a full round trip per window group (the
            # config-4 end-to-end gap was launch-loop overhead, not
            # kernel time).
            t0 = time.perf_counter()
            launched = []  # (sub, tag, ok_device, B)
            n_launched = 0
            with launch_span(rows=sum(len(idxs) for idxs, _ in grouped)):
                for idxs, plan in grouped:
                    sub = [fits[j] for j in idxs]
                    batch = _group_pack([encs[i] for i in sub])
                    # Bucketing trades padding work for jit-cache
                    # stability. For LONG histories the trade inverts:
                    # bucketing a 12k-event cluster to 16k events adds
                    # 33% sequential scan depth to every member, while
                    # the compile cache only ever sees a handful of
                    # long launches per process (merged clusters —
                    # _merge_long_groups — make them fewer still, and
                    # can exceed 16 rows, so exactness keys on
                    # long-ness alone, not group size).
                    e_len = batch["events"].shape[1]
                    # Exactness keys on the LEGACY event length: the
                    # policy was calibrated on it, and a macro batch's
                    # ~2× shorter row count must not silently halve its
                    # threshold.
                    e_legacy = batch.get("legacy_events", e_len)
                    exact = e_legacy > MERGE_MAX_EVENTS
                    ev, (val_of,), B = pad_batch_bucketed(
                        batch["events"], (plan.val_of,),
                        floor_b=len(sub) if exact else 8,
                        floor_e=None if exact else 32)
                    kernel = make_dense_batch_checker(
                        model, plan.kind, plan.n_slots, plan.n_states,
                        macro_p=batch.get("macro_p"))
                    ok, _ = kernel(ev, val_of)
                    launched.append((sub, plan.kernel_tag, ok, B))
                    n_launched += len(sub)
                t_prev = t0
                for g, (sub, tag, ok, B) in enumerate(launched):
                    # blocks: device → host
                    ok = np.asarray(ok)[:B]  # lint: allow(host-sync)
                    t_now = time.perf_counter()
                    # Per-history time under pipelining: the MARGINAL
                    # wall this group added (delta between successive
                    # blocking reads; the first group also absorbs the
                    # shared pack+launch span). Groups overlap on
                    # device, so exact per-group kernel attribution
                    # does not exist — this keeps sums meaningful.
                    dt = t_now - t_prev
                    t_prev = t_now
                    for j, i in enumerate(sub):
                        results[i] = _jx(VALID if ok[j] else INVALID,
                                         encs[i], dt / max(len(sub), 1),
                                         kernel=tag, note=note)
        # Histories beyond the dense caps continue to the sort ladder.
        fits = [fits[j] for j in rest]
    if fits:
        eff_slots = n_slots or bucket_slots(
            max(encs[i].n_slots for i in fits)
        )
        # Capacity ladder: per-event work is linear in the frontier
        # capacity C, and a "valid" at small C is final (overflow can
        # only drop configurations, i.e. cause false-INVALID, never
        # false-VALID) — so run everything at a small C and re-run only
        # the overflowed minority at full capacity. Typical histories
        # (bounded concurrency window) decide on the first rung, ~4×
        # cheaper than launching everything at DEFAULT_N_CONFIGS.
        ladder = ([n_configs] if n_configs else
                  [64, DEFAULT_N_CONFIGS] if DEFAULT_N_CONFIGS > 64
                  else [DEFAULT_N_CONFIGS])
        remaining = fits
        for rung, eff_configs in enumerate(ladder):
            # The C-ladder consumes the macro stream too: every rung's
            # kernel (chunked or monolithic) keys on the batch's P.
            # The rung consults the autotuner like the dense groups do
            # (family "sort", capacity in the signature).
            rung_encs = [encs[i] for i in remaining]
            tuned = (autotune.tuned_sort_plan(model, rung_encs,
                                              eff_configs, eff_slots,
                                              measure=serve_rows is None)
                     if scan_chunk() > 0 else None)
            batch = (autotune.pack_group(rung_encs, tuned)
                     if tuned is not None else _group_pack(rung_encs))
            t0 = time.perf_counter()
            if scan_chunk() > 0:
                # Chunked sort scan (ISSUE 3): same rung, but decided
                # rows evict between chunks and the rung early-exits
                # when every row is decided. The ladder still blocks
                # per rung — the escalation decision needs the flags.
                # A tuned fan-out shards the rung over the mesh — the
                # pre-autotune rung was single-device, which the 8-vdev
                # host measured 1.84× slower at sort shapes (autotune
                # docstring); no plan keeps today's placement.
                rung_sharding = autotune.sort_rung_sharding(tuned)
                init_fn, step_fn = make_sort_chunk_checker(
                    model, eff_configs, eff_slots,
                    mesh=getattr(rung_sharding, "mesh", None),
                    macro_p=batch.get("macro_p"))
                e_sched = bucket_rows(batch["events"].shape[1], 32)
                with launch_span(rows=len(remaining)):
                    [out] = run_chunked([ChunkLaunch(
                        events=batch["events"],
                        n_events=batch["n_events"],
                        init_fn=init_fn, step_fn=step_fn,
                        e_sched=e_sched, device=rung_sharding,
                        tag="sort",
                        chunk=(tuned.scan_chunk or max(e_sched, 1))
                        if tuned is not None else None)],
                        build_rows=serve_rows)
                ok, overflow = out.ok, out.overflow
            else:
                kernel = make_batch_checker(model, eff_configs, eff_slots,
                                            macro_p=batch.get("macro_p"))
                # Bucket both compile-shape dims (batch, events) to
                # powers of two so repeated calls hit the jit cache
                # instead of recompiling per batch size. Pad rows/events
                # are EV_PAD no-ops.
                ev, _, B = pad_batch_bucketed(batch["events"])
                with launch_span(rows=len(remaining)):
                    ok, overflow = kernel(ev)
                    ok, overflow = ok[:B], overflow[:B]
                    # The ladder must block per rung to decide
                    # escalation.
                    ok = np.asarray(ok)  # lint: allow(host-sync)
                    overflow = np.asarray(  # lint: allow(host-sync)
                        overflow)
            dt = time.perf_counter() - t0
            escalate = []
            for j, i in enumerate(remaining):
                if ok[j]:
                    results[i] = _jx(VALID, encs[i], dt / len(remaining),
                                     note=note)
                elif not overflow[j]:
                    results[i] = _jx(INVALID, encs[i],
                                     dt / len(remaining), note=note)
                elif rung + 1 < len(ladder):
                    escalate.append(i)
                # else: overflowed at top capacity → undecided (None)
            remaining = escalate
            if not remaining:
                break
    return results


#: DFS step budget in race mode: enough for any history the harness
#: produces at its scale, small enough that adversarial backtracking
#: cannot wedge the race (the frontier engines decide those).
DEFAULT_DFS_BUDGET = 4_000_000

#: Budget for auto mode's wide-window DFS fast path (sub-second):
#: valid histories typically decide in thousands of steps; adversarial
#: ones exhaust this quickly and fall through to the frontier ladder.
FAST_DFS_BUDGET = 300_000


def _race(encs, model, n_configs, n_slots, witness, max_cpu_configs):
    """Race the on-device frontier kernel against the DFS engine; per
    history the first decided verdict wins (knossos.competition analogue,
    reference raft_test.clj:26). Histories neither engine decides fall
    back to the capped CPU frontier — which can itself report UNKNOWN on
    adversarial histories (the reference community's stance when knossos
    becomes "unfeasible to verify", doc/intro.md:35-41)."""
    import threading

    decided: list[Optional[dict]] = [None] * len(encs)
    lock = threading.Lock()

    def record(i, res):
        with lock:
            if decided[i] is None:
                res["raced"] = True
                decided[i] = res
                # Tier attribution belongs to the WINNER only — the
                # losing engine's work on the same row must not double-
                # count rows (fractions would exceed 1.0).
                tier = res.get("decided-tier")
                if tier is not None:
                    note_tier(tier, wall_s=res.get("time-s", 0.0))

    def jax_side():
        try:
            rs = _jax_pass(encs, model, n_configs, n_slots, note=False)
        except Exception:
            # The DFS side carries the race — but never silently: an
            # always-failing kernel (model bug, shape regression) would
            # otherwise degrade every race to single-engine unnoticed.
            import logging
            logging.getLogger(__name__).warning(
                "race: jax engine failed, DFS/CPU carries this batch",
                exc_info=True)
            return
        for i, r in enumerate(rs):
            if r is not None:
                record(i, r)

    def dfs_side():
        # Cheapest histories first: win the race where DFS is strong.
        order = sorted(range(len(encs)), key=lambda i: encs[i].n_events)
        for i in order:
            with lock:
                if decided[i] is not None:
                    continue
            r = _check_dfs(encs[i], model, witness,
                           max_steps=DEFAULT_DFS_BUDGET, note=False)
            if r["valid?"] is not UNKNOWN:
                record(i, r)

    threads = [threading.Thread(target=jax_side),
               threading.Thread(target=dfs_side)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, r in enumerate(decided):
        if r is None:
            decided[i] = _check_cpu(encs[i], model, witness, max_cpu_configs)
    return decided


def _check_dfs(enc: EncodedHistory, model, witness: bool = False,
               max_steps: Optional[int] = None, note: bool = True) -> dict:
    if enc.n_events == 0:
        if note:
            note_tier("trivial")
        return {"valid?": VALID, "algorithm": "trivial", "op-count": 0,
                "decided-tier": "trivial"}
    t0 = time.perf_counter()
    try:
        r = check_encoded_dfs(enc, model, max_steps=max_steps,
                              witness=witness)
    except SearchBudgetExceeded as e:
        return {"valid?": UNKNOWN, "algorithm": "dfs", "error": str(e)}
    if note:
        note_tier("host", wall_s=time.perf_counter() - t0)
    out = {
        "valid?": VALID if r.valid else INVALID,
        "algorithm": "dfs",
        "op-count": enc.n_ops,
        "concurrency-window": enc.n_slots,
        "configs-explored": r.configs_explored,
        "decided-tier": "host",
    }
    if not r.valid:
        out["failing-op-index"] = r.failing_op_index
    if r.witness is not None:
        out["witness"] = r.witness
    return out



def kernel_tier(tag: str) -> str:
    """Decided-tier name of a kernel tag (ISSUE 13 attribution): the
    mask kernel is its own (cheapest) tier, the domain kernel reports
    "dense", the sort ladder "sort"."""
    if "mask" in tag:
        return "mask"
    if "sort" in tag:
        return "sort"
    return "dense"


def _jx(valid, enc: EncodedHistory, secs: float,
        kernel: str = "sort", note: bool = True) -> dict:
    tier = kernel_tier(kernel)
    if note:
        note_tier(tier, wall_s=secs)
    return {
        "valid?": valid,
        "algorithm": "jax",
        "kernel": kernel,
        "op-count": enc.n_ops,
        "concurrency-window": enc.n_slots,
        "time-s": secs,
        "decided-tier": tier,
    }


def check_encoded_host(enc: EncodedHistory, model, witness: bool = False,
                       max_cpu_configs: Optional[int]
                       = DEFAULT_MAX_CPU_CONFIGS,
                       consistency: str = "linearizable",
                       lin_fastpath: Optional[bool] = None) -> dict:
    """Host-only verdict ladder for one encoded history: the capped CPU
    frontier first, the budgeted DFS when the frontier reports UNKNOWN —
    never a device launch. This is graftd's degrade path (the service
    re-checks a batch through it when the device pass raises mid-check),
    mirroring `auto` mode's escalation order without re-entering jax.
    A weaker ``consistency`` rung relaxes/greedy-certifies exactly like
    `check_encoded`, so degraded rung verdicts match the device path —
    and at the linearizable rung the same pre-frontier certify fast
    path runs (ISSUE 14; `lin_fastpath=False` skips it, e.g. graftd's
    fast lane having already certified at dispatch)."""
    if getattr(model, "txn_graph", False):
        # a transaction row's host arm: the same edges, no launch
        from .txn_graph import check_txn_rows

        return check_txn_rows([enc], model, kernel=False)[0]
    if enc.n_events == 0:
        note_tier("trivial")
        return {"valid?": VALID, "algorithm": "trivial", "op-count": 0,
                "decided-tier": "trivial"}
    consistency = _normalize_rung(consistency)
    if consistency != "linearizable":
        from .consistency import apply_rung

        orig = enc

        def annotate_session(res: dict) -> dict:
            # Same sc-refuted evidence the device path attaches to
            # EVERY session-rung result (certified ones included) —
            # the degrade path must not silently drop it. Host DFS
            # arm: no device launch; best-effort like the device twin.
            from .cycle import cycle_tier_on, find_cycles

            if consistency == "session" and cycle_tier_on():
                try:
                    [c] = find_cycles([orig], model, kernel=False)
                except Exception:
                    c = None
                if c is not None and "cycle" in c:
                    res["sc-refuted"] = True
                    res["sc-cycle"] = c["cycle"]
                elif c is not None and "skipped-size" in c:
                    res["cycle-skipped-size"] = c["skipped-size"]
            return res

        [enc], [certified], [tier] = apply_rung([enc], model, consistency)
        if certified:
            note_tier(tier)
            return annotate_session(
                {"valid?": VALID, "algorithm": "greedy-witness",
                 "op-count": enc.n_ops,
                 "concurrency-window": enc.n_slots,
                 "decided-tier": tier,
                 "consistency": consistency})
        if consistency == "sequential":
            # Exact cycle tier on the degrade path too (host DFS arm:
            # no device launch) — same verdict the frontier would
            # reach, decided without the search.
            from .cycle import cycle_tier_on, find_cycles

            if cycle_tier_on():
                [c] = find_cycles([orig], model, kernel=False)
                if c is not None and "cycle" in c:
                    note_tier("cycle")
                    return {"valid?": INVALID, "algorithm": "cycle",
                            "op-count": orig.n_ops,
                            "concurrency-window": orig.n_slots,
                            "decided-tier": "cycle",
                            "cycle": c["cycle"],
                            "exact-sc-refutation": True,
                            "consistency": consistency}
    if consistency == "linearizable" and lin_fastpath is not False \
            and lin_fastpath_on():
        # Lin-rung fast path, host flavor (ISSUE 14): a witness on the
        # un-relaxed stream is a lin witness, so a certified row skips
        # the (worst-case exponential) frontier search entirely. Same
        # gating bucket + abort budget as the device path's pass.
        from .consistency import certify_encoded

        # Its own row class: this row rides no launch and the
        # alternative is the host search, so a kernel-cost verdict
        # must not take the certifier away from it. No kernel sample
        # is ever folded into LINFP_NO_LAUNCH, which leaves the rule at
        # "tries unless nothing was ever delivered".
        sig = autotune.lin_fastpath_sig(type(model).__name__,
                                        enc.n_events,
                                        autotune.LINFP_NO_LAUNCH)
        if autotune.lin_fastpath_route(sig):
            abort = lin_abort_steps()
            t0 = time.perf_counter()
            ok, tier, _ = certify_encoded(
                enc, model,
                max_steps=abort * max(enc.n_events, 1) if abort
                else None)
            dt = time.perf_counter() - t0
            autotune.lin_fastpath_observe(sig, rows=1, hits=int(ok),
                                          wall_s=dt)
            _fp_bump(rows_scanned=1, rows_certified=int(ok),
                     rows_delivered=int(ok),
                     events_scanned=enc.n_events, certify_wall_s=dt)
            if ok:
                note_tier(tier + "@lin", wall_s=dt)
                return {"valid?": VALID, "algorithm": "greedy-witness",
                        "op-count": enc.n_ops,
                        "concurrency-window": enc.n_slots,
                        "decided-tier": tier + "@lin"}
        else:
            _fp_bump(rows_gated=1)
    r = _check_cpu(enc, model, witness, max_cpu_configs)
    if r.get("valid?") is UNKNOWN:
        r2 = _check_dfs(enc, model, witness, max_steps=DEFAULT_DFS_BUDGET)
        if r2["valid?"] is not UNKNOWN:
            r = r2
    if consistency != "linearizable":
        r = annotate_session(r)
        r["consistency"] = consistency
    return r


def _check_cpu(enc: EncodedHistory, model, witness: bool,
               max_configs: Optional[int] = DEFAULT_MAX_CPU_CONFIGS,
               note: bool = True) -> dict:
    t0 = time.perf_counter()
    try:
        r = check_encoded_cpu(enc, model, max_configs=max_configs,
                              witness=witness)
    except FrontierOverflow as e:
        return {"valid?": UNKNOWN, "algorithm": "cpu", "error": str(e)}
    if note:
        note_tier("host", wall_s=time.perf_counter() - t0)
    out = {
        "valid?": VALID if r.valid else INVALID,
        "algorithm": "cpu",
        "op-count": enc.n_ops,
        "concurrency-window": enc.n_slots,
        "configs-explored": r.configs_explored,
        "max-frontier": r.max_frontier,
        "decided-tier": "host",
    }
    if not r.valid:
        out["failing-op-index"] = r.failing_op_index
    if r.witness is not None:
        out["witness"] = r.witness
    return out


class LinearizableChecker(Checker):
    """Checker-protocol wrapper around `check_histories` for one history.
    ``consistency`` selects the ladder rung (checker/consistency.py)."""

    def __init__(self, model, algorithm: str = "auto",
                 n_configs: Optional[int] = None,
                 n_slots: Optional[int] = None,
                 max_cpu_configs: Optional[int] = DEFAULT_MAX_CPU_CONFIGS,
                 consistency: str = "linearizable"):
        self.model = model
        self.algorithm = algorithm
        self.n_configs = n_configs
        self.n_slots = n_slots
        self.max_cpu_configs = max_cpu_configs
        self.consistency = _normalize_rung(consistency)

    def check(self, test, history, opts=None) -> dict:
        from .counterexample import (attach_counterexample,
                                     write_counterexample_html)

        if not isinstance(history, History):
            history = History(history)
        hist = history.client_ops()
        # witness=True so the host engines produce the explanation during
        # the verdict run — attach_counterexample then only re-searches
        # when the kernel (verdict-only) was the decider.
        [result] = check_histories(
            [hist], self.model, self.algorithm, self.n_configs, self.n_slots,
            witness=True, max_cpu_configs=self.max_cpu_configs,
            consistency=self.consistency,
        )
        if result.get("valid?") is INVALID:
            attach_counterexample(result, hist, self.model,
                                  max_cpu_configs=self.max_cpu_configs,
                                  consistency=self.consistency)
            write_counterexample_html(result, hist,
                                      (test or {}).get("store_dir"),
                                      "counterexample.html")
        return result
