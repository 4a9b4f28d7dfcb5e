"""Chunked wavefront scheduler: decided-row eviction + pipelined dispatch.

The ISSUE-3 tentpole. The monolithic execution path pays one padded-E
`lax.scan` per window group, strictly serialized across groups: every
row rides the full scan even after its verdict is certain (frontier died
→ invalid) or its real events are exhausted (the remaining schedule is
EV_PAD no-ops), and group k+1's kernel queues behind group k's on one
device. That is the classic finished-sequences-in-the-batch inefficiency
of batched inference; the fix here is the same shape as iteration-level
(continuous) batching in serving stacks (PAPERS.md: Orca/vLLM):

  * **Chunking** — the event scan advances in `chunk`-event units
    (`JGRAFT_SCAN_CHUNK`, 0 = legacy monolithic scan) through the
    chunked kernels of ops/dense_scan.py / ops/linear_scan.py, whose
    carry returns per-row `decided` / `exhausted` flags alongside the
    frontier. Sync-free spans coalesce: no row can exhaust before
    min(alive `n_events`) — host data — so the scheduler launches one
    kernel up to the next possible-retirement boundary instead of one
    per chunk (`_span_chunks`; per-launch overhead otherwise eats the
    eviction win). A group's events cross to the device once; a span's
    offset and length are traced scalars of the step program.
  * **Eviction** — between chunks the flags come back to the host, the
    verdicts of finished rows are recorded, and survivors are
    recompacted to a smaller row bucket of the launch-shape set
    (`launch_shapes`, below: the one enumeration of every program a
    launch can ask for, built whole ahead of a key's launches — so
    recompaction never triggers a fresh XLA compile).
  * **Early exit** — a group stops the moment all rows are decided.
    The chunk schedule covers the group's *bucketed* event length (what
    the legacy monolithic kernel scans), so skipping trailing pad
    chunks is a genuine saving over the monolithic reference, and is
    what `early_exit` reports.
  * **Pipelining** — each round dispatches every live group's next
    chunk before blocking on any result (JAX async dispatch), each
    chunk row-sharded over the device mesh
    (`parallel.mesh.chunk_sharding`), so one group's chunk executes
    mesh-wide exactly like the legacy `shard_map` path while the other
    groups' chunks queue behind it on every device — the host blocking
    on one group's flags never idles the ring.

Soundness (the checker/linearizable.py contract, unchanged): eviction
only ever removes rows whose verdict is already certain. A `decided`
row's (ok, overflow) pair is frozen — `ok` is monotone and flips False
exactly when the frontier empties, after which every event is a no-op on
the dead frontier — and an `exhausted` row only has EV_PAD no-ops left.
The scheduler maps the final pairs exactly as the monolithic caller
does (ok → valid; ~ok & ~overflow → invalid; ~ok & overflow → escalate),
so the chunked path can never report a verdict the monolithic scan would
not have (pinned bitwise by tests/test_chunked_scan.py differentials).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from ..history.packing import bucket_rows
from ..platform import env_int

#: Default events per chunk. Calibrated on the north-star host-CPU bench
#: shape (1000×1k register, window groups W=5..8, real event counts
#: ~1472..1645 padded to a 2048 bucket): the win is bounded by how soon
#: after its last real event a row is evicted, so finer chunks help
#: until per-launch overhead bites — the calibration grid (legacy
#: ≈ c256 ≈ 12.2 s, c128 11.6 s, c64 11.5 s on a 256-row scale model;
#: per-launch overhead stays negligible down to 64) picks 128: most
#: north-star rows retire at the 1536 boundary (chunk 12/16) and the
#: rest one chunk later, vs chunk 7/8 for 256. JGRAFT_SCAN_CHUNK
#: overrides (0 = legacy monolithic scan — the ablation hook and
#: reference implementation).
DEFAULT_SCAN_CHUNK = 128


def scan_chunk() -> int:
    """Resolved chunk size: 0 disables chunking (legacy monolithic
    scan). Parsed defensively — a non-integer env value warns and uses
    the default instead of crashing the importer."""
    return env_int("JGRAFT_SCAN_CHUNK", DEFAULT_SCAN_CHUNK, minimum=0)


# ------------------------------------------------------------------ stats
# Aggregated across every wavefront this process runs (thread-safe: race
# mode drives the jax pass from a worker thread). checker/perf.py reads
# the innermost `stats_scope` so stored per-run artifacts never
# accumulate across checker invocations.

_STATS_LOCK = threading.Lock()
_STATS_ZERO = {"chunks_run": 0, "evicted_rows": 0, "groups_run": 0,
               "groups_early_exited": 0, "pipeline_overlap_s": 0.0,
               # cycle-tier counters (ISSUE 19): rows that skipped the
               # exact tier for size (the previously-silent cap skip),
               # graph nodes before/after SCC condensation, non-trivial
               # SCCs hit, and blocked-closure tile programs run.
               "cycle_size_skips": 0, "cycle_nodes_pre": 0,
               "cycle_nodes_post": 0, "cycle_scc_hits": 0,
               "cycle_tiles_run": 0,
               # compile counters (ISSUE 26): fed by the jax.monitoring
               # listeners `platform.install_compile_counters` registers.
               # Scoped like the rest, so a launch's `stats["scan"]`
               # shows the programs THAT launch built.
               "programs_built": 0, "compile_s": 0.0,
               "compile_cache_misses": 0,
               # launch-shape set (ISSUE 32): programs the build-ahead
               # of a key built or loaded, and programs a launch built
               # or loaded AFTER its key was built (healthy: 0).
               "programs_built_ahead": 0, "shape_misses": 0,
               # a key at a time (ISSUE 42): keys whose first build has
               # ended and, of those, the keys a launch waited for (after
               # a host's first run the record builds them at the start:
               # healthy 0)
               "keys_built": 0, "keys_met_by_launch": 0,
               # one trace a program kind (ISSUE 43): programs built or
               # loaded by calling a key's shared trace at a row count
               # (`_RowShared`); on one device, every program of the set
               "programs_from_shared_trace": 0,
               # wide windows (ISSUE 40): rows past WIDE_WINDOW_SLOTS that
               # entered the kernel ladder and, of those, the rows a host
               # engine decided
               "wide_rows": 0, "wide_rows_host": 0,
               # long histories (ISSUE 44): rows of at least
               # LIN_FASTPATH_MAX_EVENTS events that entered the kernel
               # ladder
               "long_rows": 0,
               # transaction graphs (ISSUE 51): rows of a transaction
               # model that entered `txn_graph.check_txn_rows`, their
               # nodes and edges, the rows a flag or a non-cycle anomaly
               # refuted, the closure programs launched, and the
               # multiply-adds of the squarings that ran in them
               # (rows of the bucket x N^3 a squaring)
               "txn_rows": 0, "txn_nodes": 0, "txn_edges": 0,
               "txn_rows_flagged": 0, "closure_launches": 0,
               "closure_macs": 0}
_STATS = dict(_STATS_ZERO)
#: (scope dict, owner thread id) pairs; guarded by _STATS_LOCK,
#: innermost last. The owner id makes attribution THREAD-AFFINE under
#: concurrent scopes (graftd's multi-worker shards, ISSUE 7): counters
#: recorded by a thread that owns scopes land ONLY in that thread's
#: scopes — two shard executors checking concurrently no longer sum
#: each other's counters into both batches' stats. Counters from a
#: thread owning NO scope (race mode's engine threads) keep the old
#: every-active-scope behavior, so single-worker attribution is
#: unchanged bit for bit.
_SCOPES: List[tuple] = []


def _scope_targets() -> list:
    """The scopes a record from the calling thread lands in (see
    `_SCOPES`); the caller holds _STATS_LOCK."""
    if not _SCOPES:
        return []
    tid = threading.get_ident()
    owned = [s for s, o in _SCOPES if o == tid]
    return owned if owned else [s for s, _ in _SCOPES]


def _add_stats(**kw) -> None:
    with _STATS_LOCK:
        targets = _scope_targets()
        for k, v in kw.items():
            _STATS[k] += v
            for scope in targets:
                scope[k] += v


def note_wide(**kw) -> None:
    """Record the wide-window counters (ISSUE 40), like `note_cycle`:
    `wide_rows`, `wide_rows_host`; and the long-history one (ISSUE
    44): `long_rows`."""
    _add_stats(**kw)


def note_txn(**kw) -> None:
    """Record the transaction-graph counters (ISSUE 51), like
    `note_wide`."""
    _add_stats(**kw)


def note_cycle(**kw) -> None:
    """Record cycle-tier counters (ISSUE 19) into the active scopes +
    process totals — ``cycle_size_skips`` is the previously-invisible
    cap skip (satellite: a row too big for the exact tier now leaves a
    trace in every stats surface), the rest are the ``cycle_*``
    fields of a run's stored stats. Unknown keys are a programming
    error, caught loudly here rather than silently minted as new
    counters."""
    for k in kw:
        if k not in _STATS_ZERO:
            raise KeyError(f"unknown cycle counter {k!r}")
    _add_stats(**kw)


@contextlib.contextmanager
def stats_scope(label: Optional[str] = None):
    """Explicit per-run counter scope (ISSUE-4 satellite): counters
    accumulated while the scope is active land in the yielded dict too,
    isolated from everything before it. `core/runner.run_test` wraps
    each test's checking phase in one, so a process running
    back-to-back checks (soaks) stores per-run counters instead of
    process-lifetime accumulation. Nesting-safe (scopes stack) and
    thread-safe; the process-wide totals that `consume_stats` serves
    are untouched.

    `label` threads a caller identity through the scope (ISSUE-5: the
    checking service labels each coalesced launch with the request ids
    riding it — "graftd:req-a,req-b" — so per-request trace records can
    attribute their shared launch's counters). The label is carried in
    the yielded dict under the non-counter key ``"label"``; `_add_stats`
    only ever touches counter keys, so it is never accumulated into."""
    scope = dict(_STATS_ZERO)
    if label is not None:
        scope["label"] = label
    with _STATS_LOCK:
        _SCOPES.append((scope, threading.get_ident()))
    try:
        yield scope
    finally:
        with _STATS_LOCK:
            # Remove by IDENTITY: list.remove compares by equality, and
            # two scopes with identical counters (e.g. nested, both
            # still zero) are equal dicts — remove() would pop the
            # outer one and crash the outer exit.
            for i, (s, _) in enumerate(_SCOPES):
                if s is scope:
                    del _SCOPES[i]
                    break


def snapshot_stats(scoped: bool = False) -> dict:
    """Copy of the accumulated chunked-scan counters (non-destructive).
    `scoped=True` returns the innermost active `stats_scope`'s counters
    — this run's work only; with concurrent scopes (multi-worker
    graftd) the innermost scope OWNED BY THIS THREAD wins — falling
    back to the process totals when no scope is active (direct
    `check_histories` callers outside a test run)."""
    with _STATS_LOCK:
        if scoped and _SCOPES:
            tid = threading.get_ident()
            for s, o in reversed(_SCOPES):
                if o == tid:
                    return dict(s)
            return dict(_SCOPES[-1][0])
        return dict(_STATS)


def consume_stats() -> dict:
    """Return and reset the accumulated process-wide counters. Active
    scopes are not reset — they already hold only their own span's
    counters."""
    global _STATS
    with _STATS_LOCK:
        out = dict(_STATS)
        _STATS = dict(_STATS_ZERO)
        return out


# ------------------------------------------------------ tier attribution
# ISSUE 13: every verdict records which tier of the decision ladder
# decided it (greedy / backtrack / cycle / mask / dense / sort / host /
# trivial). At fleet scale the cheap-tier hit-rate IS the capacity
# model, so the per-tier decided counts and wall time are first-class
# counters next to the chunked-scan stats: same process-wide totals +
# thread-affine scope attribution, surfaced by checker/perf.py and
# graftd's per-request stats.

_TIERS: dict = {}  # tier -> [rows, wall_s]; guarded by _STATS_LOCK


def note_tier(tier: str, rows: int = 1, wall_s: float = 0.0) -> None:
    """Record `rows` verdicts decided by `tier` (and the wall seconds
    attributed to them). Scope targeting mirrors `_add_stats`: a thread
    owning scopes feeds only its own (each scope's tier dict lives
    under the non-counter key ``"tiers"``)."""
    with _STATS_LOCK:
        t = _TIERS.setdefault(tier, [0, 0.0])
        t[0] += rows
        t[1] += wall_s
        for scope in _scope_targets():
            e = scope.setdefault("tiers", {}).setdefault(tier, [0, 0.0])
            e[0] += rows
            e[1] += wall_s


def _format_tiers(raw: dict) -> dict:
    return {k: {"rows": v[0], "wall_s": v[1]} for k, v in raw.items()}


def snapshot_tiers(scoped: bool = False) -> dict:
    """Copy of the per-tier decided counters, ``{tier: {"rows",
    "wall_s"}}`` (non-destructive). `scoped=True` reads the innermost
    scope owned by this thread, like `snapshot_stats`."""
    with _STATS_LOCK:
        if scoped and _SCOPES:
            tid = threading.get_ident()
            for s, o in reversed(_SCOPES):
                if o == tid:
                    return _format_tiers(s.get("tiers", {}))
            return _format_tiers(_SCOPES[-1][0].get("tiers", {}))
        return _format_tiers(_TIERS)


def consume_tiers() -> dict:
    """Return and reset the process-wide per-tier counters; active
    scopes keep their own accumulations, like `consume_stats`."""
    global _TIERS
    with _STATS_LOCK:
        out = _format_tiers(_TIERS)
        _TIERS = {}
        return out


# ------------------------------------------------------------------ spans
# ISSUE 26: named durations on the served path, in the same registry as
# the counters and tiers above: process-wide totals plus thread-affine
# scope attribution under the non-counter key ``"spans"``. While a
# `jax.profiler` session is active every span is also a
# `jax.profiler.TraceAnnotation` on the calling thread, so it lands on
# the host plane of the same trace as the device's `XLA Ops` /
# `XLA Modules` lines, on the profiler's clock. No switch: the totals
# are always on, the annotations follow the profiler.
#
# Rule (tests/test_spans.py pins it statically): a span site runs per
# request, per launch or per wavefront round, never inside a loop over
# rows or events.

_SPANS: dict = {}  # name -> [n, seconds]; guarded by _STATS_LOCK
#: most recent compiles, newest last: (fun_name, seconds, innermost
#: span open on the compiling thread or None, "load" where the persistent
#: cache gave the program and "compile" where XLA compiled it from
#: source); guarded by _STATS_LOCK
_RECENT_COMPILES: collections.deque = collections.deque(maxlen=16)
#: most recent shape misses, newest last: (fun_name, seconds, program
#: kind, key, rows, width); guarded by _STATS_LOCK
_RECENT_MISSES: collections.deque = collections.deque(maxlen=16)
#: the span a key's programs are built in, ahead of its launches
BUILD_AHEAD = "build.ahead"
#: the stages of one program's build (ISSUE 42), each a span
#: `build.<stage>` in thread-seconds with `n` programs, from JAX's own
#: events (`platform.install_compile_counters` says which): Python
#: tracing it to a jaxpr, the jaxpr to an MLIR module (both the build
#: thread's own CPU seconds: a wait for the GIL is not a second of
#: tracing), then either the persistent cache loading it or XLA
#: compiling it from source (both the backend's wall seconds)
BUILD_STAGES = ("trace", "lower", "load", "compile")
#: per-thread stack of open span names (`.names`), so that a compile
#: can be stamped with the step it interrupted; `.shape`, the program a
#: launch is asking for (`_launching`); `.building`, the `_BUILT` entry
#: of the key the thread is building (`_building`)
_OPEN = threading.local()
_LAUNCH_SEQ = itertools.count(1)


_PROFILE_STATE = None  # jax's profiler state, once JAX has loaded it


def _profiling() -> bool:
    """True while a `jax.profiler` session started from this process is
    active. A process that has not imported JAX is not made to."""
    global _PROFILE_STATE
    state = _PROFILE_STATE
    if state is None:
        prof = sys.modules.get("jax._src.profiler")
        if prof is None:
            return False
        state = _PROFILE_STATE = prof._profile_state
    return state.profile_session is not None


def _span_add(name: str, seconds: float, n: int) -> None:
    """`note_span`'s body; the caller holds _STATS_LOCK."""
    t = _SPANS.get(name)
    if t is None:
        t = _SPANS[name] = [0, 0.0]
    t[0] += n
    t[1] += seconds
    for scope in _scope_targets():
        e = scope.setdefault("spans", {}).setdefault(name, [0, 0.0])
        e[0] += n
        e[1] += seconds


def note_span(name: str, seconds: float, n: int = 1) -> None:
    """Add a duration taken from stamps (a request's phases) to span
    `name`. Scope targeting mirrors `note_tier`."""
    with _STATS_LOCK:
        _span_add(name, seconds, n)


class annotate:
    """Names a block without timing it: the name is what `open_span`
    answers on this thread inside the block (so a compile is stamped
    with it) and, while a profiler session is active, a
    `jax.profiler.TraceAnnotation` with the keyword arguments as its
    arguments. With no session no annotation object is built."""

    __slots__ = ("name", "_args", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self._args = args
        self._ann = None

    def __enter__(self):
        try:
            _OPEN.names.append(self.name)
        except AttributeError:
            _OPEN.names = [self.name]
        if _profiling():
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name,
                                                     **self._args)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _OPEN.names.pop()
        return False


class span(annotate):
    """``with span("launch.device", rows=256) as sp: ...``: an
    `annotate` block that is also timed: the elapsed seconds are added
    to the registry under `name` and left in ``sp.s``, so that a site
    which also feeds a counter reads the clock once. `n` is what the
    span counts (default: itself, 1)."""

    __slots__ = ("n", "s", "_t0")

    def __init__(self, name: str, n: int = 1, **args):
        annotate.__init__(self, name, **args)
        self.n = n
        self.s = 0.0

    def __enter__(self):
        annotate.__enter__(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self._t0
        annotate.__exit__(self, *exc)
        note_span(self.name, self.s, self.n)
        return False


def open_span() -> Optional[str]:
    """Innermost span open on the calling thread, or None."""
    names = getattr(_OPEN, "names", None)
    return names[-1] if names else None


def _format_spans(raw: dict) -> dict:
    return {k: {"n": v[0], "s": v[1]} for k, v in raw.items()}


def snapshot_spans() -> dict:
    """Fresh copy of the process-wide span totals,
    ``{name: {"n", "s"}}``."""
    with _STATS_LOCK:
        return _format_spans(_SPANS)


def _stage_add(stage: str, seconds: float, programs: int = 0) -> None:
    """`seconds` of build stage `stage` to its span and to the key the
    calling thread is building, if any, with the `programs` the stage
    handed over; the caller holds _STATS_LOCK."""
    _span_add("build." + stage, seconds, 1)
    entry = getattr(_OPEN, "building", None)
    if entry is not None:
        entry[stage + "_s"] += seconds
        entry["programs"] += programs


def note_build_stage(stage: str, seconds: float) -> None:
    """One program traced (`"trace"`) or lowered (`"lower"`) on this
    thread, in the thread's own CPU seconds."""
    with _STATS_LOCK:
        _stage_add(stage, seconds)


def note_compile(fun_name: str, seconds: float,
                 loaded: bool = False) -> None:
    """One program built or loaded by the backend (the
    `/jax/core/compile/backend_compile_duration` event), stamped with
    the span it interrupted on this thread. `loaded`: the persistent
    cache gave it (span `build.load`); otherwise XLA compiled it from
    source (`build.compile`: a cold cache, a new tree, or a read lost to
    the cache's file lock), so the two spans' `n` sum to
    `programs_built` and their `s` to `compile_s`. Inside `build.ahead`
    it is a program built ahead; inside a launch of the closed shape set
    (`_launching`) it is a SHAPE MISS — the key was declared built and
    the launch still had to build — and is logged with the
    ``(key, rows, width)`` that asked for it."""
    inside = open_span()
    shape = getattr(_OPEN, "shape", None)
    ahead = inside == BUILD_AHEAD
    miss = shape is not None and not ahead
    stage = "load" if loaded else "compile"
    _add_stats(programs_built=1, compile_s=seconds,
               programs_built_ahead=int(ahead), shape_misses=int(miss),
               programs_from_shared_trace=int(
                   SHARED_SUFFIX in fun_name))
    with _STATS_LOCK:
        _stage_add(stage, seconds, programs=1)
        _RECENT_COMPILES.append((fun_name, seconds, inside, stage))
        if miss:
            _RECENT_MISSES.append((fun_name, seconds) + shape)


def note_cache_miss() -> None:
    """One program the persistent compile cache did not hold."""
    _add_stats(compile_cache_misses=1)


def snapshot_compiles() -> dict:
    """The process-wide compile counters, the most recent compiles,
    newest last, as ``[fun_name, seconds, open span, "load" |
    "compile"]``, and the most recent shape misses as ``[fun_name,
    seconds, program, key, rows, width]``."""
    with _STATS_LOCK:
        out = {k: _STATS[k] for k in (
            "programs_built", "compile_s", "compile_cache_misses",
            "programs_built_ahead", "shape_misses", "keys_built",
            "keys_met_by_launch", "programs_from_shared_trace")}
        out["recent_compiles"] = [list(c) for c in _RECENT_COMPILES]
        out["recent_shape_misses"] = [list(c) for c in _RECENT_MISSES]
        return out


@contextlib.contextmanager
def launch_span(rows: int):
    """The one wrapper of a kernel launch site: a whole profiler session
    around it when JGRAFT_PROFILE_DIR names a directory (the XLA
    profiler hook, SURVEY.md section 5.1), and the `launch.device` span
    inside it, so the span's annotation lands in that trace."""
    profile_dir = os.environ.get("JGRAFT_PROFILE_DIR")
    with contextlib.ExitStack() as stack:
        if profile_dir and not _profiling():
            import jax

            stack.enter_context(jax.profiler.trace(profile_dir))
        yield stack.enter_context(
            span("launch.device", seq=next(_LAUNCH_SEQ), rows=rows))


# ------------------------------------------------------------- wavefront


@dataclass
class ChunkLaunch:
    """One window group queued for chunked execution.

    events: [B, E, 5] packed group batch (host numpy; pack_batch layout).
    n_events: [B] real event count per row (EncodedHistory.n_events).
    init_fn/step_fn: the chunked kernel pair from
        ops.dense_scan.make_dense_chunk_checker or
        ops.linear_scan.make_sort_chunk_checker.
    val_of: [B, S] per-history domain table (dense kernels) or None
        (sort kernel — its init_fn takes only n_events).
    e_sched: event length the chunk schedule must cover — the BUCKETED
        length the legacy monolithic kernel would scan (so early exit
        measures real savings vs the reference path); defaults to E.
    device: placement for this group's carry + chunk slices — a jax
        Device, a batch-axis Sharding
        (`parallel.mesh.chunk_sharding`: rows spread over the mesh,
        row buckets padded to a multiple of the shard count), or None
        for default single-device placement.
    tag: kernel label for result/bench reporting.
    """

    events: np.ndarray
    n_events: np.ndarray
    init_fn: Callable
    step_fn: Callable
    val_of: Optional[np.ndarray] = None
    e_sched: Optional[int] = None
    device: Optional[object] = None
    tag: str = "dense-chunk"
    #: LONG merged clusters keep their own schedule length and nearly
    #: their own row count (the next power of two from 1, `long_rows`:
    #: extra rows are pure width work on a depth-bound launch) and skip
    #: recompaction — their row counts are tiny, so eviction's value
    #: there is the early exit, not bucket shrinking. The name is from
    #: before ISSUE 44, when the rows and the device width were exact
    #: and every such launch built its own programs as it came.
    exact_rows: bool = False
    #: Per-launch chunk override (checker/autotune.py plans): None
    #: inherits the run-wide chunk (JGRAFT_SCAN_CHUNK), a positive
    #: value pins THIS launch's chunk — a whole-schedule value makes
    #: the launch effectively monolithic (one span, one flag sync)
    #: while staying on the wavefront driver.
    chunk: Optional[int] = None
    #: What names this launch's compiled kernel pair: everything
    #: `make_*_chunk_checker` keys its cache on, as JSON-able values
    #: (`build_dense_launches` fills it). With the event lanes, the
    #: device width and the placement it is the KEY of the launch-shape
    #: set (`launch_shapes`); None falls back to the pair's identity.
    spec: Optional[dict] = None

    # What the launch-shape set's machinery (`launch_key`, `build_keys`,
    # `_build_task`) asks of a launch; `ClosureLaunch` answers the same
    # five for its one program a row bucket.

    @property
    def lanes(self) -> int:
        return int(self.events.shape[2])

    @property
    def n_rows(self) -> int:
        return int(self.events.shape[0])

    @staticmethod
    def n_programs(lower: Optional[int]) -> int:
        """Programs of one row bucket: `init`, `step` and, onto the
        bucket `lower`, `gather`."""
        return 3 if lower is not None else 2

    def key_shapes(self, chunk: int, rows: int,
                   upto: Optional[int]) -> tuple:
        """(key, its LaunchShapes, the row bucket needed) for a launch
        of `rows` rows: the one bucket or, with `upto`, the key WHOLE,
        every bucket up to the larger of the two. A LONG key is whole
        at the launch's own bucket, whatever `upto` says."""
        e_pad = _padded_len(self, self.chunk or chunk or 1)
        shards = _n_shards(self.device)
        if self.exact_rows:
            shapes = launch_shapes(rows, e_pad, shards, long=True)
            need = shapes.rows[-1]
        else:
            shapes = launch_shapes(max(rows, upto or 0), e_pad, shards)
            need = launch_rows(rows, shards)
        return launch_key(self, shapes.width), shapes, need

    def build_rows(self, key: tuple, rows: int, lower: Optional[int],
                   width: int) -> None:
        """On operands shaped, typed and placed exactly as
        `_init_group`, `_dispatch` and `_collect` make them; the step
        scans no event."""
        import jax

        init, step, gather = _programs(self, key)
        vo = None
        if self.val_of is not None:
            vo = np.zeros((rows,) + self.val_of.shape[1:],
                          dtype=self.val_of.dtype)
        carry = _init_carry(self, init, vo, np.zeros((rows,), np.int32))
        events = _put(self, np.zeros((rows, width, self.lanes),
                                     dtype=self.events.dtype))
        out = step(carry, events, np.int32(0), np.int32(0))
        if lower is not None:
            out = (out, gather(carry, events,
                               np.zeros((lower,), np.int32)))
        jax.block_until_ready(out)


@dataclass
class GroupOutcome:
    """Per-group result of a wavefront run; ok/overflow are [B_real].
    `chunks_run` counts LAUNCHES — sync-free spans are coalesced into
    one launch each (`_span_chunks`), so it is ≤ the chunk-unit count
    the schedule covers."""

    ok: np.ndarray
    overflow: np.ndarray
    wall_s: float
    chunks_run: int
    evicted_rows: int
    early_exit: bool
    tag: str = ""


@dataclass
class _GroupState:
    launch: ChunkLaunch
    events: object                     # device [rows, width, lanes]
    key: tuple                         # launch-shape key
    programs: "_Programs"              # what the key's launches call
    width: int                         # device event length
    chunk: int                         # this group's resolved chunk size
    scheduled: int                     # chunks the monolithic path implies
    slot_rows: np.ndarray              # [padded_B] original row id or -1
    carry: object                      # device pytree
    ok: np.ndarray                     # [B_real] final verdicts
    overflow: np.ndarray
    recorded: np.ndarray               # [B_real] bool
    cursor: int = 0                    # chunk-units already scanned
    launches_run: int = 0              # coalesced launches dispatched
    evicted: int = 0
    done: bool = False
    early_exit: bool = False
    t_start: float = 0.0
    wall_s: float = 0.0
    pending: Optional[tuple] = None
    intervals: List[tuple] = field(default_factory=list)  # in-flight spans


def build_dense_launches(model, groups):
    """Build the wavefront launch list for dense window groups — the
    one home of the placement policy.

    groups: iterable of (rows, plan, batch) or (rows, plan, batch,
    tuned) — `rows` the caller's row ids, `plan` a DensePlan, `batch`
    the group's pack_batch OR pack_macro_batch dict (a "macro_p" key
    routes the group through the macro-event chunk kernels; `n_events`
    then counts macro rows, which is exactly what the span/exhaustion
    math must run on), `tuned` an optional checker/autotune.py
    TunedPlan applying this group's measured {scan_chunk, mesh_fanout}
    (the macro payload half of a plan acts earlier, at pack time —
    autotune.pack_group). The launch order is policy and lives HERE:
    largest group first, so big groups' chunks queue ahead of small
    ones on every device (callers must not pre-sort). Returns
    (launches, subs): subs[k] holds the row ids behind launches[k], in
    row order. Nothing is placed on the host cpu beside an accelerator
    (PERF.md section 6, PR 32, call M: the chip's loser).

    Groups stay WHOLE and each chunk's kernel is an explicit
    `shard_map` over the batch axis of the device mesh
    (`parallel.mesh.chunk_sharding`; the wrap lives in
    ops/dense_scan._shard_chunk_fns): every device scans its row shard
    — the exact execution shape of the legacy `shard_map` path, whose
    row-parallelism is the measured win on every backend (2-core
    north-star A/B: mesh-sharded 116 s vs 250 s single-device
    monolithic). Two cheaper-looking alternatives lost: Python-level
    per-device group *slicing* reached only ~1.4–1.6× overlap with
    round-robin collect bubbles, and relying on jit's GSPMD sharding
    propagation kept the carry *placed* sharded but compiled a ~3×
    slower per-chunk program than the explicit wrap. Cross-group
    pipelining comes free: all live groups' chunks queue on every
    device, so the host blocking on one group's flags never idles the
    ring. LONG merged clusters (exact_rows) stay on the default device
    with nearly their own row count (`long_rows`) — depth-bound few-row
    launches, sharding buys nothing."""
    from ..ops.dense_scan import MERGE_MAX_EVENTS, make_dense_chunk_checker
    from ..parallel.mesh import chunk_sharding

    sharding = chunk_sharding()
    launches: list = []
    subs: list = []
    for grp in sorted(groups, key=lambda g: -len(g[0])):
        rows, plan, batch = grp[:3]
        tuned = grp[3] if len(grp) > 3 else None
        e_len = batch["events"].shape[1]
        # The LONG-group exact-padding policy was calibrated on LEGACY
        # event counts; a macro batch's ~2× shorter row count must not
        # silently halve its threshold. The scan schedule itself runs
        # on macro rows.
        e_legacy = batch.get("legacy_events", e_len)
        exact = e_legacy > MERGE_MAX_EVENTS
        e_sched = e_len if exact else bucket_rows(e_len, 32)
        if exact:
            placement = None
        elif tuned is not None and tuned.mesh_fanout > 0:
            # Per-group fan-out from the measured plan; the env knob
            # (JGRAFT_GROUP_DEVICES) stays the outer bound inside
            # chunk_sharding.
            placement = chunk_sharding(tuned.mesh_fanout)
        else:
            placement = sharding
        # A tuned scan_chunk pins this launch's chunk; 0 means "one
        # whole-schedule span" — effectively the monolithic reference
        # launch, still on the wavefront driver (same verdict path).
        chunk_override = None
        if tuned is not None and not exact:
            chunk_override = tuned.scan_chunk or max(e_sched, 1)
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            mesh=getattr(placement, "mesh", None),
            macro_p=batch.get("macro_p"))
        spec = {"model": type(model).__name__,
                "model_key": repr(model.cache_key()),
                "kind": plan.kind, "n_slots": int(plan.n_slots),
                "n_states": int(plan.n_states),
                "macro_p": batch.get("macro_p"),
                # always False (nothing is placed on the host cpu);
                # the field stays so that a host's `launch-keys.json`
                # reads as it was written
                "host": False,
                "fanout": _n_shards(placement)}
        if exact:
            # a LONG key (ISSUE 44): its rows and width come from the
            # LONG ladders of `launch_shapes`; absent from every other
            # key, so that a record reads as it was written
            spec["long"] = True
        launches.append(ChunkLaunch(
            events=batch["events"], n_events=batch["n_events"],
            init_fn=init_fn, step_fn=step_fn, val_of=plan.val_of,
            e_sched=e_sched, device=placement, tag=plan.kernel_tag,
            exact_rows=exact, chunk=chunk_override, spec=spec))
        subs.append(list(rows))
    return launches, subs


def key_template(model, spec: dict, width: int, lanes: int,
                 rows: int) -> Optional[ChunkLaunch]:
    """A launch that holds no history and names the dense key a record
    describes (`snapshot_built`: a launch's `spec`, its device width
    and lanes, the rows built), for `build_keys`. It goes through
    `build_dense_launches`, the one home of the placement policy; None
    where this process would not place the key as recorded (another
    fan-out), or the record is of another stream format."""
    from ..ops.dense_scan import MERGE_MAX_EVENTS, DensePlan

    if spec.get("kind") == CLOSURE_KIND:
        launch = ClosureLaunch(spec, int(width), int(rows))
        return launch if lanes == launch.lanes and \
            spec == closure_spec(model, spec.get("n_nodes", 0)) else None
    macro_p = spec.get("macro_p")
    if lanes != (5 if macro_p is None else 3 + 4 * int(macro_p)):
        return None
    n_states = int(spec["n_states"])
    plan = DensePlan(spec["kind"], int(spec["n_slots"]), n_states,
                     np.zeros((rows, n_states), dtype=np.int32))
    batch = {"events": np.broadcast_to(np.zeros((1, 1, 1), np.int32),
                                       (rows, width, lanes)),
             "n_events": np.zeros((rows,), np.int32),
             "legacy_events": MERGE_MAX_EVENTS + 1 if spec.get("long")
             else 1}
    if macro_p is not None:
        batch["macro_p"] = int(macro_p)
    [launch], _ = build_dense_launches(model, [(range(rows), plan, batch)])
    return launch if launch.spec == spec else None


# ---------------------------------------------------- closure programs
# ISSUE 51. A transaction graph's launch (checker/txn_graph.py) runs ONE
# program, `ops/kernel_ir.make_txn_closure`, and its key is a value like
# a dense key's: the node bucket (`spec`), one lane, the edge width, the
# placement; its row buckets are the set's (`launch_shapes`), up to
# `closure_rows_cap`, one program each and no gather. So it goes through
# `build_keys`, `/stats` `build_keys`, `launch-keys.json` and graftd's
# build at start like any key, and a launch that builds is a shape miss.

CLOSURE_KIND = "closure"
#: cells (rows x N x N) of one closure launch's plane; more rows are
#: launched in parts
CLOSURE_MAX_CELLS = 1 << 26


def closure_rows_cap(n_nodes: int) -> int:
    """Rows of one closure launch at node bucket `n_nodes`: a power of
    two, LAUNCH_ROW_FLOOR at the least (64 at N 1,024: each of a few
    live [B, N, N] int32 arrays is then 256 MB)."""
    cap = LAUNCH_ROW_FLOOR
    while cap * 2 * n_nodes * n_nodes <= CLOSURE_MAX_CELLS:
        cap *= 2
    return cap


def closure_spec(model, n_nodes: int) -> dict:
    return {"model": type(model).__name__,
            "model_key": repr(model.cache_key()), "kind": CLOSURE_KIND,
            "n_nodes": int(n_nodes), "n_slots": 0, "n_states": 0}


@functools.lru_cache(maxsize=None)
def _closure_program(n_nodes: int):
    from ..ops.kernel_ir import make_txn_closure

    return make_txn_closure(n_nodes)


@dataclass
class ClosureLaunch:
    """One closure launch, or the template of its key: `spec`
    (`closure_spec`), the edges a row (`width`), the rows."""

    spec: dict
    width: int
    rows: int
    device = None
    exact_rows = False
    lanes = 1

    @property
    def n_nodes(self) -> int:
        return int(self.spec["n_nodes"])

    @property
    def program(self):
        return _closure_program(self.n_nodes)

    @property
    def n_rows(self) -> int:
        return self.rows

    @staticmethod
    def n_programs(lower: Optional[int]) -> int:
        return 1

    def key_shapes(self, chunk: int, rows: int,
                   upto: Optional[int]) -> tuple:
        """The set's row buckets up to `closure_rows_cap`, one program
        each and no gather (`long`: the shape of such a key)."""
        top = min(max(rows, upto or 0), closure_rows_cap(self.n_nodes))
        return (launch_key(self, self.width),
                LaunchShapes(launch_shapes(top, self.width).rows,
                             self.width, long=True), launch_rows(rows))

    def build_rows(self, key: tuple, rows: int, lower: Optional[int],
                   width: int) -> None:
        import jax

        jax.block_until_ready(self.program(self.pad_codes(rows, width)))

    def pad_codes(self, rows: int, width: int) -> np.ndarray:
        """A launch's operand with no edge in it: every code past the
        planes."""
        n = self.n_nodes
        return np.full((rows, width), 3 * n * n, dtype=np.int32)


def run_closure(launch: ClosureLaunch, serve_rows: Optional[int] = None):
    """(operand, run) of one closure launch: `operand` is the
    [row bucket, width] int32 array of pads the caller writes its rows'
    edge codes into, `run()` launches it and gives (flags [bucket, 4],
    squarings [3]). The launch never builds: this waits for its bucket
    or, for a service (`serve_rows`), for its key whole, as
    `_init_group` does."""
    key, shapes, need = launch.key_shapes(0, launch.rows, serve_rows)
    built = _BUILT.get(key)
    if built is None or not built["rows"].issuperset(
            shapes.rows if serve_rows else (need,)):
        build_keys([launch], upto=serve_rows)
    operand = launch.pad_codes(need, launch.width)

    def run():
        with _launching(CLOSURE_KIND, key, need, launch.width):
            flags, iters = launch.program(operand)
            return np.asarray(flags), np.asarray(iters)  # lint: allow(host-sync)

    return operand, run


# --------------------------------------------------- the launch-shape set
# ISSUE 32. Every program a launch of the wavefront can ask the backend
# for is named HERE, by one function, for a key and a row count:
#
#   key    what picks the compiled kernel pair and its operands' lanes:
#          the kernel's own cache key (`ChunkLaunch.spec`), the event
#          lanes (5, or 3 + 4·P), the device event length `width`, the
#          placement.
#   rows   a power of two from LAUNCH_ROW_FLOOR up and, from 32 rows on,
#          the midpoints between them (rounded to the placement's shard
#          count).
#   width  a power of two from LAUNCH_WIDTH_FLOOR up: the group's whole
#          padded event stream lives on the device, and a span's offset
#          and length are traced scalars of the step program
#          (ops/kernel_ir.chunk_step_fns), so a span is not a shape.
#
# For one key that is, per row bucket, one `init` program, one `step`
# program and one `gather` program onto the next bucket down
# (recompaction walks down bucket by bucket): 3·len(rows) - 1 programs,
# 26 for the nine buckets 8…256 of a served launch. `launch_rows`,
# `launch_width`, `_init_group`, `_dispatch`, `_collect` and the
# build-ahead all read `launch_shapes`, so what is built ahead and what
# is launched cannot drift apart (tests/test_launch_shapes.py holds them
# together through `snapshot_launched`). A launch never builds: it asks
# `build_keys` for its bucket (a service's: for its key whole, once) and
# waits; what a launch builds all the same is a shape miss
# (`note_compile`).
#
# A LONG launch (ISSUE 44: a cluster past MERGE_MAX_EVENTS legacy
# events, `ChunkLaunch.exact_rows`, `spec["long"]`) is in the set by two
# ladders of its own. Its schedule stays its own length (the scan reads
# not one pad event: a span's offset and length are traced), but the
# device event length is that length rounded up the LONG width ladder
# (`long_width`: eight steps an octave, at most an eighth more zeros to
# place), so that two histories of nearly one length share a key; its
# rows are a power of two from 1, with no midpoints and no `gather` (a
# LONG launch never recompacts): two programs a row bucket, and a
# service builds the buckets up to the launch's own, never up to its
# batch cap (256 rows of 200,000 events are gigabytes of zeros).

#: smallest row bucket of a launch
LAUNCH_ROW_FLOOR = 8
#: the power of two from which the row buckets take midpoints too
LAUNCH_ROW_MIDPOINTS = 32
#: smallest device event length of a launch
LAUNCH_WIDTH_FLOOR = 32


def _pow2_from(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def launch_rows(n: int, shards: int = 1) -> int:
    """Row bucket for `n` live rows: the next power of two from
    LAUNCH_ROW_FLOOR or, from LAUNCH_ROW_MIDPOINTS up, the midpoint
    between two (…, 32, 48, 64, 96, 128, 192, 256), padded up to a
    multiple of the placement's shard count so a sharded launch always
    splits evenly over the mesh. A group's time on the chip grows with
    its padded rows (1k-op histories, W 6 to 8: 96 -> 128 rows costs
    35-41 ms of 136-168, 24 -> 32 rows 2-10 ms of 54-81; PERF.md
    section 6, PR 32, call M), so the midpoints earn their programs
    where rows are many; below 32 rows a bucket more buys back
    milliseconds."""
    n = max(int(n), 1)
    b = _pow2_from(n, LAUNCH_ROW_FLOOR)
    if b > LAUNCH_ROW_MIDPOINTS and n <= b - b // 4:
        b -= b // 4
    return -(-b // shards) * shards


def launch_width(e_pad: int) -> int:
    """Device event length for a schedule of `e_pad` events."""
    return _pow2_from(max(int(e_pad), 1), LAUNCH_WIDTH_FLOOR)


#: steps an octave of the LONG width ladder
LONG_WIDTH_STEPS = 8


def long_width(e_pad: int) -> int:
    """Device event length for a LONG launch's schedule of `e_pad`
    events: the next of LONG_WIDTH_STEPS evenly spaced lengths between
    two powers of two (…, 65536, 73728, 81920, …, 131072, …), at most
    an eighth past `e_pad`. The padding is EV_PAD rows the schedule
    never reaches."""
    top = launch_width(e_pad)
    step = max(top // (2 * LONG_WIDTH_STEPS), 1)
    return min(top, -(-max(int(e_pad), 1) // step) * step)


def long_rows(n: int, shards: int = 1) -> int:
    """Row bucket of a LONG launch of `n` rows: the next power of two
    from 1 (a LONG cluster is a few rows; each padded row is width
    work on a depth-bound launch), a multiple of the shard count."""
    return -(-_pow2_from(max(int(n), 1), 1) // shards) * shards


@dataclass(frozen=True)
class LaunchShapes:
    """The programs of one key: `rows` ascending, every one at the one
    `width`; `long`: a LONG key, which has no `gather`."""

    rows: tuple
    width: int
    long: bool = False

    @property
    def init(self) -> tuple:
        return self.rows

    @property
    def step(self) -> tuple:
        return tuple((r, self.width) for r in self.rows)

    @property
    def gather(self) -> tuple:
        """(rows before, rows after): onto the next bucket down; none
        for a LONG key, which never recompacts."""
        return () if self.long else tuple(zip(self.rows[1:],
                                              self.rows[:-1]))

    def __len__(self) -> int:
        return len(self.init) + len(self.step) + len(self.gather)


def launch_shapes(max_rows: int, width: int, shards: int = 1,
                  long: bool = False) -> LaunchShapes:
    """The finite set of programs a launch of up to `max_rows` rows of
    one key can ever ask for: it starts at `launch_rows(max_rows)` and
    recompaction only ever walks down the buckets. `long`: a LONG key's,
    by `long_rows` and `long_width`."""
    bucket = long_rows if long else launch_rows
    top = bucket(max_rows, shards)
    rows, n = [], 1
    while True:
        b = bucket(n, shards)
        rows.append(b)
        if b >= top:
            break
        n = b + 1
    return LaunchShapes(tuple(rows),
                        (long_width if long else launch_width)(width),
                        long)


def _n_shards(placement) -> int:
    """Shard count of a launch placement: mesh size for a batch-axis
    Sharding, 1 for a concrete device or default placement."""
    mesh = getattr(placement, "mesh", None)
    return int(mesh.size) if mesh is not None else 1


def _placement_name(placement) -> str:
    if placement is None:
        return "default"
    mesh = getattr(placement, "mesh", None)
    if mesh is not None:
        return f"mesh{int(mesh.size)}"
    return f"{placement.platform}:{placement.id}"


def launch_key(launch: ChunkLaunch, width: int) -> tuple:
    """The key of `launch`'s shape set (hashable, printable)."""
    spec = launch.spec
    kernel = (tuple(sorted((k, str(v)) for k, v in spec.items()))
              if spec is not None
              else (("tag", launch.tag), ("fns", id(launch.step_fn))))
    return (kernel, launch.lanes, int(width),
            _placement_name(launch.device))


#: key -> {"rows": set of row buckets built, "spec", "width", "lanes",
#: "met", "wait_s"}, guarded by _BUILD_LOCK, and the key's build on the
#: program's own clock, "programs" and "<stage>_s" a BUILD_STAGES stage,
#: guarded by _STATS_LOCK (the listeners' lock; taken inside the other,
#: never around it)
_BUILT: dict = {}
_BUILD_LOCK = threading.RLock()
#: every (program, key, rows[, rows after], width) a launch asked for;
#: guarded by _STATS_LOCK, bounded
_LAUNCHED: set = set()
_LAUNCHED_CAP = 8192


def _new_key_entry(launch: ChunkLaunch, width: int, met: str) -> dict:
    entry = {"rows": set(), "spec": launch.spec, "width": width,
             "lanes": launch.lanes, "met": met,
             "wait_s": 0.0, "programs": 0, "traced": 0}
    entry.update((stage + "_s", 0.0) for stage in BUILD_STAGES)
    return entry


def snapshot_built() -> list:
    """The keys built in this process: ``{"key", "spec", "width",
    "lanes", "rows"}`` each, rows ascending, and each key's build
    (ISSUE 42): `met`, ``"start"`` where graftd's start built it from
    the host's record and ``"launch"`` where a launch met it first and
    waited; `programs` built or loaded for it on the build threads and
    their thread-seconds by stage, `trace_s`, `lower_s`, `load_s`,
    `compile_s`; `wait_s`, the wall seconds callers waited for it in
    `build.ahead` (a call that waits for several keys at once books
    each second to the key whose bucket it was waiting for then, so the
    keys' `wait_s` sum to the span's seconds)."""
    with _BUILD_LOCK, _STATS_LOCK:
        return [{"key": k, **v, "rows": sorted(v["rows"])}
                for k, v in _BUILT.items()]


def snapshot_build_keys() -> list:
    """`snapshot_built` as `/stats` serves it under `build_keys`: what
    names the key from its `spec` (`model`, `kind`, `n_slots`,
    `n_states`; None for a launch without one) in the place of `key`,
    `spec` and `lanes`."""
    out = []
    for k in snapshot_built():
        spec = k.pop("spec") or {}
        del k["key"], k["lanes"]
        out.append({**{f: spec.get(f) for f in (
            "model", "kind", "n_slots", "n_states")},
            "long": bool(spec.get("long")),
            **({"n_nodes": spec["n_nodes"]} if "n_nodes" in spec else {}),
            **k})
    return out


def key_name(entry: dict) -> str:
    """A key for a human: the argument of `launch.build` and of the
    start's log line."""
    spec = entry["spec"]
    if spec is None:
        return f"unnamed/w{entry['width']}"
    if spec.get("kind") == CLOSURE_KIND:
        return f"{spec['model']}/closure/N{spec['n_nodes']}/w{entry['width']}"
    return (f"{spec['model']}/{spec['kind']}/W{spec['n_slots']}"
            f"/S{spec['n_states']}/w{entry['width']}"
            + ("/long" if spec.get("long") else ""))


@contextlib.contextmanager
def _building(entry: dict):
    """Names the key the calling thread builds, for the listeners'
    per-key seconds (`_stage_add`)."""
    _OPEN.building = entry
    try:
        yield
    finally:
        _OPEN.building = None


def snapshot_launched() -> list:
    """What the launches of this process asked for, as ``("init", key,
    rows, width)``, ``("step", key, rows, width)`` and ``("gather",
    key, rows before, rows after, width)``."""
    with _STATS_LOCK:
        return sorted(_LAUNCHED, key=repr)


class _launching:
    """Names the program the calling thread is about to ask for, for
    `note_compile` (a build there is a shape miss) and for
    `snapshot_launched`."""

    __slots__ = ("shape",)

    def __init__(self, program: str, key: tuple, *dims):
        self.shape = (program, key) + dims

    def __enter__(self):
        _OPEN.shape = self.shape
        with _STATS_LOCK:
            if len(_LAUNCHED) < _LAUNCHED_CAP:
                _LAUNCHED.add(self.shape)
        return self

    def __exit__(self, *exc):
        _OPEN.shape = None
        return False


def _put(launch: ChunkLaunch, x):
    """Place a launch operand: under the launch's placement, or on the
    default device. Always a device array, so that a group's events
    cross to the device once, and always COMMITTED to its place: a call
    of a shared trace (`_RowShared`) commits what it returns, and a jit
    keeps a program apart for operands that are not, so events put and
    left uncommitted would meet a second program of the same shapes the
    first time they came back from a gather."""
    import jax

    return jax.device_put(x, launch.device if launch.device is not None
                          else jax.local_devices()[0])


def _init_carry(launch: ChunkLaunch, init, val_of, n_events):
    if launch.val_of is not None:
        return init(_put(launch, val_of), _put(launch, n_events))
    return init(_put(launch, n_events))


_GATHERS: dict = {}


def _gather_fn(placement):
    """The recompaction program of a placement: carry and events
    gathered onto a smaller row bucket. Under a batch-axis sharding the
    outputs are pinned back to it, so the next step splits evenly
    again."""
    fn = _GATHERS.get(placement)
    if fn is None:
        import jax

        def gather(carry, events, idx):
            return jax.tree_util.tree_map(lambda x: x[idx],
                                          (carry, events))

        kw = ({"out_shardings": placement}
              if getattr(placement, "mesh", None) is not None else {})
        fn = _GATHERS[placement] = jax.jit(gather, **kw)
    return fn


# ------------------------------------------- one trace a program kind
# ISSUE 43. The row count is `vmap`'s batch axis and nothing else: the
# nine step programs of a key are the same Python, and tracing it nine
# times was the wall graftd's start waited for (PERF.md section 5). So a
# key's three program kinds are each traced and lowered ONCE, with the
# row axis a symbol (`jax.export`), and a row bucket's program is that
# one lowering called at the bucket's row count. Rows is the only
# symbolic axis: width, lanes, W, S and P pick the kernel's loops and
# tables and stay static, as the key has them; the span's offset and
# length were traced scalars already.

#: what `note_compile` knows a program of a shared trace by
SHARED_SUFFIX = "_at_rows"


class _RowShared:
    """A jitted function traced once for every row count it is called
    at. The first call exports it with its operands' leading axes
    symbolic: `dims` gives each operand's symbol, or None for an operand
    without a row axis (a pytree operand's leaves all lead with the
    rows). Every call, the first too, goes through ONE
    ``jax.jit(exported.call)``: at a row count it has not seen, that
    lowers the call (no Python of the kernel runs), refines the module
    to the count and builds or loads the program; from then on the
    count is a hit in the jit's in-memory cache, for a launch as for
    the build-ahead, because both call this object. The lock is the
    once-a-key guard: a key's buckets go to the build threads at the
    same moment, and each would otherwise export for itself. The
    export's trace and lowering fire JAX's events on the thread that
    makes it, so they are booked to the key that thread builds
    (`_stage_add`), which also counts the export (`traced`)."""

    __slots__ = ("_fn", "_dims", "_call", "_lock")

    def __init__(self, fn, dims: tuple):
        self._fn, self._dims = fn, dims
        self._call = None
        self._lock = threading.Lock()

    def __call__(self, *args):
        call = self._call
        if call is None:
            call = self._export(args)
        return call(*args)

    def _export(self, args):
        with self._lock:
            if self._call is not None:
                return self._call
            import jax
            from jax import export

            scope = export.SymbolicScope()
            sym = {d: export.symbolic_shape(d, scope=scope)[0]
                   for d in set(self._dims) - {None}}

            def like(x, d):
                shape = x.shape if d is None else (sym[d],) + x.shape[1:]
                return jax.ShapeDtypeStruct(shape, x.dtype)

            exported = export.export(self._fn)(*(
                jax.tree_util.tree_map(lambda x, d=d: like(x, d), a)
                for a, d in zip(args, self._dims)))

            def at_rows(*a):
                return exported.call(*a)

            at_rows.__name__ = at_rows.__qualname__ = \
                getattr(self._fn, "__name__", "program") + SHARED_SUFFIX
            entry = getattr(_OPEN, "building", None)
            if entry is not None:
                with _STATS_LOCK:
                    entry["traced"] += 1
            self._call = jax.jit(at_rows)
            return self._call


class _Programs(NamedTuple):
    """What a launch of a key and the key's build both call."""

    init: Callable
    step: Callable
    gather: Callable


#: key -> its _Programs of shared traces; guarded by _BUILD_LOCK. Not in
#: `_BUILT` (a key's record is JSON), and like the jit caches it
#: outlives a reset of it
_SHARED: dict = {}


def _programs(launch: ChunkLaunch, key: Optional[tuple]) -> _Programs:
    """The three programs of `launch`, one trace each for all the row
    buckets of its key. Two kinds of launch keep a trace a bucket, by
    what is observed of the launch: a LONG launch (its key has one row
    bucket, or a few: nothing to share, and its `gather` is never
    called), and a mesh placement, whose `shard_map` body jaxlib's
    shape refinement cannot take (it segfaults on the module, CPU mesh,
    JAX 0.9.0; PERF.md section 6, PR 43). No key (the tests' way to
    the launch's own jits) does too."""
    if key is None or launch.exact_rows or _n_shards(launch.device) > 1:
        return _Programs(launch.init_fn, launch.step_fn,
                         _gather_fn(launch.device))
    with _BUILD_LOCK:
        programs = _SHARED.get(key)
        if programs is None:
            rows = ("rows",) * (2 if launch.val_of is not None else 1)
            programs = _SHARED[key] = _Programs(
                _RowShared(launch.init_fn, rows),
                _RowShared(launch.step_fn, ("rows", "rows", None, None)),
                _RowShared(_gather_fn(launch.device),
                           ("rows", "rows", "rows_after")))
        return programs


def _build_rows(launch, key: tuple, rows: int, lower: Optional[int],
                width: int) -> None:
    """Ask for the programs of one row bucket of `launch`'s key (a
    dense key's three, a closure key's one)."""
    with annotate(BUILD_AHEAD, rows=rows):
        launch.build_rows(key, rows, lower, width)


#: threads that build a key's row buckets side by side (XLA releases
#: the GIL while it compiles)
BUILD_THREADS = 8
_BUILDERS = None   # the pool, made when first needed
#: (key, row bucket) -> Future of a build asked for and not yet done;
#: guarded by _BUILD_LOCK
_PENDING: dict = {}


def _build_task(launch: ChunkLaunch, key: tuple, rows: int,
                lower: Optional[int], width: int, stop) -> None:
    """One row bucket of one key, on a build thread. `stop()` true
    leaves it unbuilt (the service that asked is gone)."""
    try:
        if stop is None or not stop():
            with _building(_BUILT[key]):
                _build_rows(launch, key, rows, lower, width)
            with _BUILD_LOCK:
                _BUILT[key]["rows"].add(rows)
    finally:
        with _BUILD_LOCK:
            _PENDING.pop((key, rows), None)


def build_keys(launches: List[ChunkLaunch], chunk: Optional[int] = None,
               rows: Optional[int] = None, upto: Optional[int] = None,
               stop: Optional[Callable[[], bool]] = None,
               met: str = "launch") -> int:
    """Build what is not built yet of `launches`' keys (templates or
    real launches; each names a key by its fns, lanes, schedule and
    placement) on the build threads, and wait for it in ONE
    `build.ahead` span. `rows` (default: the launch's own) is the row
    count needed; `chunk` is the run's, as `run_chunked` resolves it.

    Without `upto` that is the one row bucket needed: a library caller
    builds a bucket when a launch or a recompaction reaches it, as
    lazily as the jit cache did. With `upto` (a service's largest
    launch) it is the key WHOLE, every bucket up to the larger of
    `rows` and `upto`: one known pause the first time a key is met,
    after which no launch of the key ever builds. A bucket that another
    thread is building already is waited for, not built twice.
    Returns the number of programs waited for. `stop()` true leaves
    what has not started unbuilt.

    `met` says who asks: a launch (`_init_group`, recompaction) or
    graftd's start (``"start"``, `service/buildahead.py`); a key keeps
    the first. A launch's wait is named on the thread that takes it,
    `annotate("launch.build", key=, programs=)` around the span: on the
    dispatcher's line of a profiler session the pause lies inside
    `launch.device` under a name of its own. A launch whose key is
    built never enters it."""
    global _BUILDERS
    chunk = scan_chunk() if chunk is None else chunk
    waits = []   # (launch, key, row bucket, lower, width, future)
    fresh = []   # the entries of the keys this call met first
    with _BUILD_LOCK:
        if _BUILDERS is None:
            from concurrent.futures import ThreadPoolExecutor

            _BUILDERS = ThreadPoolExecutor(
                BUILD_THREADS, thread_name_prefix="build-ahead")
        for launch in launches:
            key, shapes, need = launch.key_shapes(
                chunk, rows if rows is not None else launch.n_rows, upto)
            entry = _BUILT.get(key)
            if entry is None:
                entry = _BUILT[key] = _new_key_entry(launch, shapes.width,
                                                     met)
                fresh.append(entry)
            built = entry["rows"]
            lower = dict(shapes.gather)
            # the heaviest first: a step program's cost grows with its
            # rows
            for r in reversed(shapes.rows if upto else (need,)):
                if r in built:
                    continue
                fut = _PENDING.get((key, r))
                if fut is None:
                    fut = _PENDING[(key, r)] = _BUILDERS.submit(
                        _build_task, launch, key, r, lower.get(r),
                        shapes.width, stop)
                waits.append((launch, key, r, lower.get(r), shapes.width,
                              fut))
    if not waits:
        return 0
    n = sum(w[0].n_programs(w[3]) for w in waits)
    named = (annotate("launch.build", key=key_name(_BUILT[waits[0][1]]),
                      programs=n)
             if met == "launch" else contextlib.nullcontext())
    waited: dict = {}   # key -> seconds of this call's wait booked to it
    with named, span(BUILD_AHEAD, n=n, keys=len(launches)):
        t0 = time.perf_counter()
        for launch, key, r, low, width, fut in waits:
            fut.result()
            if r not in _BUILT[key]["rows"] and not (stop and stop()):
                # another caller's `stop` left it unbuilt: build here
                with _building(_BUILT[key]):
                    _build_rows(launch, key, r, low, width)
                with _BUILD_LOCK:
                    _BUILT[key]["rows"].add(r)
            t1 = time.perf_counter()
            waited[key] = waited.get(key, 0.0) + t1 - t0
            t0 = t1
    with _BUILD_LOCK:
        for key, s in waited.items():
            _BUILT[key]["wait_s"] += s
        done = [e for e in fresh if e["rows"]]
    if done:
        _add_stats(keys_built=len(done), keys_met_by_launch=sum(
            1 for e in done if e["met"] == "launch"))
    return n


def _padded_len(launch: ChunkLaunch, chunk: int) -> int:
    """Events the launch's chunk schedule covers."""
    E = launch.events.shape[1]
    e_sched = max(launch.e_sched or E, E, 1)
    return ((e_sched + chunk - 1) // chunk) * chunk


def _pad_idx(positions, bucket: int) -> np.ndarray:
    """Gather index padded to the bucket by repeating the first entry
    (pad slots are masked out of every flag read via slot_rows == -1)."""
    idx = np.full((bucket,), positions[0], dtype=np.int32)
    idx[: len(positions)] = positions
    return idx


def _init_group(launch: ChunkLaunch, chunk: int,
                build_rows: Optional[int] = None) -> _GroupState:
    chunk = launch.chunk or chunk  # a stored plan's override
    B, E, lanes = launch.events.shape
    e_pad = _padded_len(launch, chunk)
    # a LONG cluster too: its schedule is its own length (`e_pad`), its
    # rows and device width come from the LONG ladders of the set
    key, shapes, rows = launch.key_shapes(chunk, B, build_rows)
    width = shapes.width
    built = _BUILT.get(key)
    if built is None or not built["rows"].issuperset(
            shapes.rows if build_rows else (rows,)):
        # a launch never builds: it waits for its bucket or, for a
        # service, for its key whole
        build_keys([launch], chunk, upto=build_rows)
    # Row width follows the stream format: 5 legacy fields or
    # 3 + 4·P macro lanes (history/packing.py macro_compact). Pad rows
    # and the tail past E are zeros: EV_PAD no-ops.
    events = np.zeros((rows, width, lanes), dtype=launch.events.dtype)
    events[:B, :E] = launch.events
    slot_rows = np.full((rows,), -1, dtype=np.int32)
    slot_rows[:B] = np.arange(B, dtype=np.int32)
    ne = np.zeros((rows,), dtype=np.int32)
    ne[:B] = launch.n_events
    vo = None
    if launch.val_of is not None:
        vo = np.empty((rows,) + launch.val_of.shape[1:],
                      dtype=launch.val_of.dtype)
        vo[:B] = launch.val_of
        vo[B:] = launch.val_of[:1]
    programs = _programs(launch, key)
    with _launching("init", key, rows, width):
        carry = _init_carry(launch, programs.init, vo, ne)
    return _GroupState(
        launch=launch, events=_put(launch, events), key=key,
        programs=programs, width=width,
        chunk=chunk, scheduled=e_pad // chunk,
        slot_rows=slot_rows, carry=carry,
        ok=np.zeros((B,), dtype=bool), overflow=np.zeros((B,), dtype=bool),
        recorded=np.zeros((B,), dtype=bool), t_start=time.perf_counter())


def _span_chunks(g: _GroupState) -> int:
    """How many chunks the next launch coalesces. Flag syncs only pay
    for themselves at boundaries where a row can actually retire, and
    exhaustion is host-predictable: `n_events` is host data, so no live
    row can exhaust before min(alive `n_events`). The span therefore
    jumps to the first possible-retirement boundary in ONE launch
    instead of one launch per chunk — on the north-star shape that
    collapses ~11 sync-free launches per group into 2, and per-launch
    dispatch overhead (multi-device rendezvous, flag readback) was
    measured to eat the entire eviction win when paid per chunk. The
    span's length is a traced scalar of the step program, so any
    length is the same program. Soundness: a
    `decided` (~ok) row inside a coalesced span is caught at the next
    sync — its verdict is frozen (see module docstring), so it is
    recorded late, never differently; only eviction latency moves."""
    chunk = g.chunk
    live = g.slot_rows[g.slot_rows >= 0]
    live = live[~g.recorded[live]]
    lo = g.cursor * chunk
    first = int(g.launch.n_events[live].min()) if live.size else 0
    p = max(1, -(-(first - lo) // chunk))  # ceil, ≥1 once overdue
    return min(p, g.scheduled - g.cursor)


def _dispatch(g: _GroupState) -> None:
    n_chunks = _span_chunks(g)
    t0 = time.perf_counter()
    with _launching("step", g.key, g.slot_rows.shape[0], g.width):
        out = g.programs.step(g.carry, g.events,
                              np.int32(g.cursor * g.chunk),
                              np.int32(n_chunks * g.chunk))
    g.pending = (t0, n_chunks, out)


def _collect(g: _GroupState) -> None:
    """Block for the pending launch, record finished rows, evict, and
    recompact survivors when they fit a smaller row bucket."""
    t_disp, width, (carry, decided, exhausted, ok, overflow) = g.pending
    g.pending = None
    g.carry = carry
    # blocks: device → host (the wavefront's per-round sync point)
    with span("launch.sync"):
        decided = np.asarray(decided)      # lint: allow(host-sync)
        exhausted = np.asarray(exhausted)  # lint: allow(host-sync)
        ok = np.asarray(ok)                # lint: allow(host-sync)
        overflow = np.asarray(overflow)    # lint: allow(host-sync)
    g.intervals.append((t_disp, time.perf_counter()))
    g.cursor += width
    g.launches_run += 1

    real = g.slot_rows >= 0
    finished = (decided | exhausted) & real
    rows = g.slot_rows[finished]
    fresh = rows[~g.recorded[rows]]
    if fresh.size:
        pos = np.flatnonzero(finished)[~g.recorded[rows]]
        g.ok[fresh] = ok[pos]
        g.overflow[fresh] = overflow[pos]
        g.recorded[fresh] = True
        if g.cursor < g.scheduled:
            g.evicted += int(fresh.size)

    alive = np.flatnonzero(real & ~(decided | exhausted))
    alive = alive[~g.recorded[g.slot_rows[alive]]]
    if alive.size == 0 or g.cursor >= g.scheduled:
        # Defensive tail: every row's events fit the schedule, so an
        # un-recorded row at schedule end cannot happen — but if it did,
        # its current verdict is the monolithic one (only EV_PAD left).
        left = g.slot_rows[alive] if alive.size else \
            np.empty((0,), np.int32)
        for p, r in zip(alive, left):
            if not g.recorded[r]:
                g.ok[r], g.overflow[r] = ok[p], overflow[p]
                g.recorded[r] = True
        g.done = True
        g.early_exit = g.cursor < g.scheduled
        g.wall_s = time.perf_counter() - g.t_start
        return

    if g.launch.exact_rows:
        return  # no recompaction (see ChunkLaunch.exact_rows)
    have = g.slot_rows.shape[0]
    bucket = launch_rows(int(alive.size), _n_shards(g.launch.device))
    if bucket >= have:
        return
    # Walk down the set's buckets, one `gather` program a step: the
    # first brings the survivors to the front, the rest only shorten.
    survivors = g.slot_rows[alive]
    positions = alive
    rows_down = [r for r in launch_shapes(
        have, g.width, _n_shards(g.launch.device)).rows if r < have]
    for after in reversed(rows_down):
        if after < bucket:
            break
        if after not in _BUILT[g.key]["rows"]:
            # a library caller builds a bucket when it reaches it; a
            # service built them all before the key's first launch
            build_keys([g.launch], g.chunk, rows=after)
        with _launching("gather", g.key, have, after, g.width):
            g.carry, g.events = g.programs.gather(
                g.carry, g.events, _pad_idx(positions, after))
        positions = np.arange(alive.size, dtype=np.int32)
        have = after
    g.slot_rows = np.full((have,), -1, dtype=np.int32)
    g.slot_rows[: alive.size] = survivors


def _overlap_seconds(intervals: List[tuple]) -> float:
    """Total wall time during which ≥2 group chunks were in flight —
    estimated from (dispatch, collect) spans; collects happen in round
    order so this is an upper-bound estimate, reported as such."""
    events = []
    for a, b in intervals:
        events.append((a, 1))
        events.append((b, -1))
    events.sort()
    depth = 0
    overlap = 0.0
    prev = None
    for t, d in events:
        if prev is not None and depth >= 2:
            overlap += t - prev
        depth += d
        prev = t
    return overlap


def run_chunked(launches: List[ChunkLaunch],
                chunk: Optional[int] = None,
                record_stats: bool = True,
                build_rows: Optional[int] = None) -> List[GroupOutcome]:
    """Run window groups through the chunked wavefront; one
    GroupOutcome per launch, in order. Each round dispatches every live
    group's next chunk before blocking on any result, so group kernels
    overlap on their per-group devices (JAX async dispatch).
    A launch's own `chunk` field overrides the run-wide `chunk`
    (autotuned per-group plans). `record_stats=False` keeps a run out
    of the process/scope counters — the autotuner's short candidate
    samples must not inflate the eviction evidence the per-run stores
    report. `build_rows`, a service's largest launch:
    a key these launches meet for the first time is built whole up to
    that many rows before it is launched (`build_keys`); None builds a
    row bucket when a launch reaches it."""
    chunk = scan_chunk() if chunk is None else chunk
    if chunk <= 0 and not (launches and all(ln.chunk for ln in launches)):
        raise ValueError("run_chunked needs a positive chunk size "
                         "(JGRAFT_SCAN_CHUNK=0 selects the legacy "
                         "monolithic path at the call site; per-launch "
                         "ChunkLaunch.chunk overrides may substitute)")
    groups = []
    for ln in launches:
        # a group's first span starts on the device while the host pads
        # and places the next group's events
        g = _init_group(ln, chunk, build_rows)
        _dispatch(g)
        groups.append(g)
    while True:
        live = [g for g in groups if not g.done]
        if not live:
            break
        for g in live:
            _collect(g)
            if not g.done:
                # Refill this launch's device queue BEFORE collecting
                # the next one (streaming, not bulk-synchronous): a
                # round barrier would drain every device queue while
                # the host walks the collect order, and the bubble is
                # pure loss.
                _dispatch(g)
    all_spans = [iv for g in groups for iv in g.intervals]
    if record_stats:
        _add_stats(chunks_run=sum(g.launches_run for g in groups),
                   evicted_rows=sum(g.evicted for g in groups),
                   groups_run=len(groups),
                   groups_early_exited=sum(1 for g in groups
                                           if g.early_exit),
                   pipeline_overlap_s=_overlap_seconds(all_spans))
    return [GroupOutcome(ok=g.ok, overflow=g.overflow, wall_s=g.wall_s,
                         chunks_run=g.launches_run, evicted_rows=g.evicted,
                         early_exit=g.early_exit, tag=g.launch.tag)
            for g in groups]


# ------------------------------------------------------- streaming carry


#: Sentinel event budget for a stream carry: the session does not know
#: its total event count, so `exhausted` (events_left ≤ 0) must never
#: fire — retirement is decided by the session (decided flag / finish).
STREAM_EVENTS_SENTINEL = 1 << 30

#: Events per carried launch while catching a backlog up (the per-append
#: suffix is usually far smaller and rides one padded launch).
STREAM_FEED_CHUNK = 1024


class CarriedScan:
    """Re-entrant chunk carry for ONE streamed history row (ISSUE 12).

    The chunked wavefront's carry (`ops/kernel_ir.chunk_step_fns`:
    ``{inner, left}`` + decided/exhausted flags) already makes the scan
    re-enterable at any chunk boundary; this class owns that carry
    ACROSS appends of a streaming session instead of across chunks of
    one launch. Each `feed` advances the identical `scan_step` sequence
    the monolithic kernel would run over the concatenated stream —
    suffix padding is EV_PAD no-op rows — so after feeding the whole
    stream the (ok, overflow) pair is bitwise-identical to the one-shot
    scan (the §14 soundness argument; chunk-chaining half pinned by
    tests/test_kernel_ir.py, the cross-append half by
    tests/test_stream.py).

    Flags follow the frozen-verdict rule: `ok` is monotone, so the
    moment it flips False mid-stream the verdict INVALID (or, with
    `overflow`, escalate-to-host) is FINAL — no later append can
    resurrect a dead frontier. That is what lets a session surface a
    violation at the earliest deciding segment and then evict the row.

    The kernel window is fixed at build time (`bucket_slots`); a
    session whose window outgrows it rebuilds a wider carry and
    re-feeds the accumulated stream (deterministic, so the rebuilt
    carry equals an uninterrupted wider scan). `fits()` answers whether
    a rebuild is needed.
    """

    def __init__(self, model, n_slots: int,
                 n_configs: Optional[int] = None):
        from ..ops.linear_scan import (DEFAULT_N_CONFIGS, bucket_slots,
                                       make_sort_chunk_checker)

        self.model = model
        self.n_configs = int(n_configs or DEFAULT_N_CONFIGS)
        # raises ValueError past MAX_SLOTS — the session escalates
        self.slots_cap = bucket_slots(max(int(n_slots), 1))
        init_fn, self._step = make_sort_chunk_checker(
            model, self.n_configs, self.slots_cap)
        self.carry = init_fn(
            np.asarray([STREAM_EVENTS_SENTINEL], np.int32))
        self.fed = 0          # events consumed (pre-padding)
        self.launches = 0
        self.ok = True
        self.overflow = False

    @property
    def decided(self) -> bool:
        """Frozen-verdict retirement: ~ok is final mid-stream."""
        return not self.ok

    def fits(self, n_slots: int) -> bool:
        return int(n_slots) <= self.slots_cap

    def feed(self, events: np.ndarray) -> None:
        """Advance the carry over an event suffix ([n, 5] int32).
        Stops early (evicts) the moment the row decides — the remaining
        suffix cannot change a frozen verdict."""
        n = int(events.shape[0])
        lo = 0
        while lo < n and not self.decided:
            span = events[lo:lo + STREAM_FEED_CHUNK]
            lo += span.shape[0]
            pad = bucket_rows(span.shape[0], 32)
            if pad != span.shape[0]:
                padded = np.zeros((pad, 5), dtype=np.int32)
                padded[: span.shape[0]] = span
                span = padded
            carry, _dec, _exh, ok, overflow = self._step(
                self.carry, span[None, :, :], np.int32(0),
                np.int32(span.shape[0]))
            self.carry = carry
            # blocks: device → host (the per-append sync point)
            self.ok = bool(np.asarray(ok)[0])  # lint: allow(host-sync)
            self.overflow = bool(
                np.asarray(overflow)[0])       # lint: allow(host-sync)
            self.launches += 1
        self.fed += n
