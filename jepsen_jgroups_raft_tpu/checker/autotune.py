"""Per-shape-bucket autotuner: measured, fingerprint-keyed plan selection.

The kernel IR (ops/kernel_ir.py) made every execution knob uniform
across kernel families; this module is what that uniformity unlocks —
the PR 6 tentpole's second half. Every tuning knob used to be a global
default (`JGRAFT_SCAN_CHUNK`, the macro payload cap, the
`JGRAFT_GROUP_DEVICES` fan-out) even though the right value is a
per-shape decision: a 16-row window group drowns in 8-way shard_map
rendezvous that a 1000-row group amortizes, and a short-event group
pays chunk-boundary flag syncs that buy it nothing. The autotuner picks
``{family, scan_chunk, macro_payload_cap, mesh_fanout}`` per SHAPE
BUCKET from short measured in-process samples, and persists the winning
plan so later processes load instead of re-measure.

Measurement discipline (the repo's hard-won rule): cross-process
numbers measure the host's mood, not the machine, so every candidate is
sampled IN-PROCESS, interleaved (candidate order rotates inside each
rep), on a row-sample of the actual batch, with one untimed warm-up rep
absorbing XLA compiles. Sample runs go through the very launch path the
plan will drive (`checker/schedule.run_chunked` with
``record_stats=False``) so the measured config IS the applied config.

Persistence: ``store/autotune/<host-fingerprint>/<bucket>.json``
(JGRAFT_AUTOTUNE_STORE overrides the root). The fingerprint hashes the
STABLE host identity — cpu count, backend platform, device count,
jax/jaxlib versions — deliberately excluding load averages: a busy host
should not fork the plan store, but a toolchain swap or the r05→r06
~2.9× host change MUST. A stale or foreign fingerprint, a corrupt file,
or an unknown schema version all mean "re-measure, never silently
mis-tune".

Soundness: every candidate is a launch-shape configuration of the SAME
kernels — chunk size, payload cap and fan-out never change which events
are scanned or in what order beyond what the chunked-vs-monolithic
equivalence already covers — so verdicts are bitwise-identical tuned vs
default (pinned by tests/test_autotune.py).
``JGRAFT_AUTOTUNE=0`` disables consultation entirely and restores
today's exact behavior.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from dataclasses import asdict, dataclass
from pathlib import Path
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..history.packing import (MACRO_MAX_OPENS, bucket_rows,
                               macro_events_on, pack_batch,
                               pack_macro_batch)
from ..platform import env_float, env_int, env_str

_log = logging.getLogger(__name__)

#: Plan-file schema version; unknown versions are re-measured.
PLAN_VERSION = 1

#: Default plan-store root (gitignored alongside the test stores).
DEFAULT_STORE = "store/autotune"


def autotune_on() -> bool:
    """Whether plans are consulted/measured at all. Default ON; the
    measurement work-gates below keep small batches on the untuned
    path, so tiny runs behave exactly as before either way.
    JGRAFT_AUTOTUNE=0 restores today's behavior bit for bit. Parsed
    defensively (platform.env_int): garbage warns and keeps the
    default."""
    return env_int("JGRAFT_AUTOTUNE", 1, minimum=0) != 0


def sample_reps() -> int:
    """Timed reps per candidate (after one untimed warm-up rep).
    More reps harden the pick against host jitter at measurement
    cost."""
    return env_int("JGRAFT_AUTOTUNE_SAMPLES", 2, minimum=1)


def min_rows() -> int:
    """Work gate: groups with fewer rows than this never trigger a
    measurement (loading a persisted plan is always allowed) — the
    sample cost cannot amortize."""
    return env_int("JGRAFT_AUTOTUNE_MIN_ROWS", 64, minimum=1)


def min_cells() -> int:
    """Second work gate: rows × events must reach this many scanned
    cells before a measurement triggers."""
    return env_int("JGRAFT_AUTOTUNE_MIN_CELLS", 1 << 16, minimum=1)


def sample_rows_cap() -> int:
    """Rows per candidate sample run. The sample must stay
    representative of the LAUNCH shape the plan will drive — fan-out
    cost scales with rows-per-device, so an 8-device candidate sampled
    at 16 rows (2/device) mis-ranks against the full batch; 64 keeps
    ≥8 rows/device on the widest fan-out this repo ships."""
    return env_int("JGRAFT_AUTOTUNE_SAMPLE_ROWS", 64, minimum=1)


def store_root() -> Path:
    """Plan-store root; JGRAFT_AUTOTUNE_STORE overrides (defensively:
    a blank value keeps the default rather than writing to cwd)."""
    raw = os.environ.get("JGRAFT_AUTOTUNE_STORE", "")
    raw = raw.strip() if raw else ""
    return Path(raw) if raw else Path(DEFAULT_STORE)


# --------------------------------------------------------- fingerprint


def fingerprint_info() -> dict:
    """The STABLE host identity a plan is valid for. Excludes load
    averages on purpose (see module docstring)."""
    info = {"cpu_count": os.cpu_count()}
    try:
        import jax

        info["platform"] = jax.default_backend()
        info["devices"] = len(jax.devices())
        info["jax"] = jax.__version__
    except Exception:  # noqa: BLE001 — fingerprinting must never raise
        info["platform"] = "?"
    try:
        import jaxlib

        info["jaxlib"] = jaxlib.__version__
    except Exception:  # noqa: BLE001
        info["jaxlib"] = "?"
    return info


def host_fingerprint() -> str:
    """Short stable hash of `fingerprint_info` — the plan-store
    directory key. A host change (r05→r06-style drift: different
    cpu_count, toolchain, platform) lands in a different directory, so
    stale tunings are never silently applied."""
    raw = json.dumps(fingerprint_info(), sort_keys=True)
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


# ---------------------------------------------------------------- plans


@dataclass(frozen=True)
class TunedPlan:
    """One bucket's execution plan.

    family:      kernel family tag the plan was measured for ("dense",
                 "dense-mask", "sort") — recorded for reporting and as
                 a guard: a plan never applies across families.
    scan_chunk:  chunk size for the wavefront launch; 0 = one
                 whole-schedule span (the monolithic reference shape).
    macro_p:     macro payload cap for pack_macro_batch; 0 = the legacy
                 one-event-per-step stream.
    mesh_fanout: devices the launch fans out over (outer-bounded by
                 JGRAFT_GROUP_DEVICES inside chunk_sharding); 1 =
                 single-device.
    """

    family: str
    scan_chunk: int
    macro_p: int
    mesh_fanout: int


def default_plan(family: str) -> TunedPlan:
    """Today's global defaults, as a plan — the baseline candidate
    every measurement must beat."""
    from ..parallel.mesh import chunk_sharding
    from .schedule import scan_chunk

    sharding = chunk_sharding()
    fan = int(getattr(sharding, "mesh", None).size) if sharding is not None \
        else 1
    return TunedPlan(family=family, scan_chunk=scan_chunk(),
                     macro_p=MACRO_MAX_OPENS if macro_events_on() else 0,
                     mesh_fanout=fan)


def bucket_signature(family: str, n_slots: int, n_states: int,
                     n_rows: int, n_events: int) -> tuple:
    """The shape bucket a plan is keyed by: kernel family, exact
    window/state shape (they pick the compiled kernel), the
    pow2+midpoint row/event buckets (they pick the launch shape — the
    same series `pad_batch_bucketed` pads to, so two batches that share
    compiled shapes share a plan), and the macro-stream mode: plans
    measured under the macro stream must never leak into a
    JGRAFT_MACRO_EVENTS=0 ablation run (the macro A/B must stay a pure
    stream comparison)."""
    return (family, int(n_slots), int(n_states),
            bucket_rows(max(int(n_rows), 1)),
            bucket_rows(max(int(n_events), 1), 32),
            int(macro_events_on()))


def _sig_name(sig: tuple) -> str:
    fam, w, s, b, e, macro = sig
    return f"{fam}-w{w}-s{s}-b{b}-e{e}-m{macro}.json"


# ------------------------------------------------------ store + counters

_LOCK = threading.Lock()
_MISS = object()          # negative-cache sentinel (see plan_for)
_MEM: dict = {}           # sig -> TunedPlan | _MISS (this process)
_APPLIED: List[dict] = []  # bounded log of applied plans (service stamps)
_APPLIED_SEQ = 0           # monotone id of the last applied entry
_COUNTERS = {"plans_loaded": 0, "plans_measured": 0, "plan_misses": 0,
             "samples_run": 0}


def preload_plans() -> int:
    """Read every launch plan the store holds for this host into memory
    (graftd calls it when it starts: its launches then take plans from
    memory alone, `plan_for(disk=False)`, so that the launch shapes of
    a served process are fixed when it starts); returns how many.
    Unreadable, stale and foreign files are skipped exactly as
    `plan_for` skips them."""
    if not autotune_on():
        return 0
    n = 0
    try:
        paths = sorted((store_root() / host_fingerprint()).glob("*.json"))
    except OSError:
        return 0
    for path in paths:
        try:
            raw = json.loads(path.read_text())
            if not isinstance(raw, dict) or "plan" not in raw:
                continue   # a gate or arm record, not a launch plan
            sig = tuple(raw["signature"])
            if _plan_path(sig) != path:
                continue
        except (OSError, ValueError, KeyError, TypeError):
            continue
        with _LOCK:
            known = sig in _MEM
        if not known and _load_plan(sig) is not None:
            n += 1
    return n


def snapshot_counters() -> dict:
    with _LOCK:
        return dict(_COUNTERS)


def consume_counters() -> dict:
    """Return and reset the counters."""
    with _LOCK:
        out = dict(_COUNTERS)
        for k in _COUNTERS:
            _COUNTERS[k] = 0
        return out


def applied_log() -> List[dict]:
    """Bounded log of {seq, signature, plan, source} entries, in
    application order (the recording thread id stays internal)."""
    with _LOCK:
        return [{k: v for k, v in e.items() if k != "thread"}
                for e in _APPLIED]


def applied_seq() -> int:
    """Monotone id of the most recent applied-plan entry. Callers
    attributing plans to a span (graftd's scheduler) snapshot this
    BEFORE the work and read `applied_since` after — slicing the
    bounded log by LENGTH would break the moment trimming starts (the
    length pins at the bound and the slice goes permanently empty)."""
    with _LOCK:
        return _APPLIED_SEQ


def applied_since(seq: int, thread_id: Optional[int] = None) -> List[dict]:
    """Entries applied after `seq` that are still inside the bounded
    log (a span applying more than the bound keeps the newest).
    `thread_id` restricts to plans applied BY that thread — graftd's
    concurrent shard executors (ISSUE 7) each stamp only the plans
    their own batch's launch consulted, not a neighbor shard's."""
    with _LOCK:
        return [{k: v for k, v in e.items() if k != "thread"}
                for e in _APPLIED
                if e["seq"] > seq
                and (thread_id is None or e.get("thread") == thread_id)]


def _record_applied(sig: tuple, plan: TunedPlan, source: str) -> None:
    global _APPLIED_SEQ
    with _LOCK:
        _APPLIED_SEQ += 1
        _APPLIED.append({"seq": _APPLIED_SEQ, "signature": list(sig),
                         "plan": asdict(plan), "source": source,
                         "thread": threading.get_ident()})
        del _APPLIED[:-256]


def reset_for_tests() -> None:
    """Drop the in-memory plan cache + counters (tests simulate fresh
    processes)."""
    with _LOCK:
        _MEM.clear()
        _APPLIED.clear()
        _LINFP_MEM.clear()
        _CYCLE_MEM.clear()
        for k in _COUNTERS:
            _COUNTERS[k] = 0


def _plan_path(sig: tuple) -> Path:
    return store_root() / host_fingerprint() / _sig_name(sig)


def plan_for(sig: tuple, disk: bool = True) -> Optional[TunedPlan]:
    """Look a bucket's plan up: in-memory first, then the fingerprint
    directory on disk. Corrupt files, schema drift, and fingerprint
    mismatch (an operator copying plan files across hosts) all return
    None — re-measure, never silently mis-tune.

    Misses are negative-cached in memory: a long-lived daemon consults
    per window group and per ladder rung on EVERY batch, and a
    below-work-gate bucket would otherwise pay a disk stat per consult
    forever. The sentinel is replaced by `save_plan` when this process
    measures; a plan persisted by a DIFFERENT process mid-flight is
    picked up on the next process start (acceptable — cross-process
    plan sharing is a restart-time optimization, not a liveness
    contract). `disk=False` (a service's launches) stops at memory:
    what `preload_plans` read when the process started."""
    with _LOCK:
        plan = _MEM.get(sig)
    if plan is _MISS:
        return None
    if plan is not None:
        _bump("plans_loaded")
        _record_applied(sig, plan, "memory")
        return plan
    if not disk:
        return _miss(sig)
    plan = _load_plan(sig)
    if plan is not None:
        _bump("plans_loaded")
        _record_applied(sig, plan, "disk")
    return plan


def _load_plan(sig: tuple) -> Optional[TunedPlan]:
    """Disk half of `plan_for`: the plan into memory, or a negative
    entry."""
    path = _plan_path(sig)
    try:
        raw = json.loads(path.read_text())
    except FileNotFoundError:
        return _miss(sig)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        _log.warning("autotune: unreadable plan %s (%s: %s) — "
                     "re-measuring", path, type(e).__name__, e)
        return _miss(sig)
    try:
        if raw.get("version") != PLAN_VERSION:
            raise ValueError(f"schema version {raw.get('version')!r}")
        if raw.get("fingerprint") != host_fingerprint():
            raise ValueError("host fingerprint mismatch")
        if raw.get("signature") != list(sig):
            raise ValueError("bucket signature mismatch")
        plan = TunedPlan(**{k: raw["plan"][k] for k in
                            ("family", "scan_chunk", "macro_p",
                             "mesh_fanout")})
    except (KeyError, TypeError, ValueError, AttributeError) as e:
        _log.warning("autotune: stale/corrupt plan %s (%s: %s) — "
                     "re-measuring", path, type(e).__name__, e)
        return _miss(sig)
    with _LOCK:
        _MEM[sig] = plan
    return plan


def _miss(sig: tuple):
    with _LOCK:
        _MEM[sig] = _MISS
        _COUNTERS["plan_misses"] += 1
    return None


def _bump(key: str) -> None:
    with _LOCK:
        _COUNTERS[key] += 1


def save_plan(sig: tuple, plan: TunedPlan, samples: dict) -> None:
    """Persist a measured plan (atomic tmp+rename; persistence failures
    warn and keep the in-memory plan — a read-only store must not break
    checking)."""
    with _LOCK:
        _MEM[sig] = plan
    path = _plan_path(sig)
    payload = {
        "version": PLAN_VERSION,
        "fingerprint": host_fingerprint(),
        "fingerprint_info": fingerprint_info(),
        "signature": list(sig),
        "plan": asdict(plan),
        "samples": samples,
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(payload, indent=2))
        os.replace(tmp, path)
    except OSError as e:
        _log.warning("autotune: could not persist plan %s (%s: %s)",
                     path, type(e).__name__, e)


# ----------------------------------------------------------- measurement


def resolve_plan(sig: tuple, candidates: Sequence[TunedPlan],
                 measure: Callable[[TunedPlan], float]) -> TunedPlan:
    """Measure `candidates` interleaved (one untimed warm-up rep per
    candidate absorbs XLA compiles, then
    `sample_reps` timed rounds with the candidate order rotating so
    slow host drift cancels instead of biasing one candidate), pick the
    best-of-min, persist, and return. The caller has already missed
    `plan_for`."""
    times: dict = {c: [] for c in candidates}
    for c in candidates:     # warm-up: compile every candidate's shapes
        measure(c)
    reps = sample_reps()
    for rep in range(reps):
        order = list(candidates)[rep % len(candidates):] + \
            list(candidates)[:rep % len(candidates)]
        for c in order:
            times[c].append(measure(c))
    best = min(candidates, key=lambda c: min(times[c]))
    samples = {json.dumps(asdict(c)): [round(t, 5) for t in ts]
               for c, ts in times.items()}
    save_plan(sig, best, samples)
    _bump("plans_measured")
    _record_applied(sig, best, "measured")
    return best


def pack_group(encs: Sequence, tuned: Optional[TunedPlan],
               window: Optional[int] = None) -> dict:
    """Pack one group's encodings under a plan's macro payload cap —
    or under today's defaults when no plan applies (tuned None). The
    JGRAFT_MACRO_EVENTS=0 ablation is absolute: a persisted macro plan
    must never re-enable the macro stream under it. `window`, the
    group's kernel window, fixes the payload width by the kernel key
    instead of by the rows (`pack_macro_batch`)."""
    if not macro_events_on():
        return pack_batch(encs)
    if tuned is None:
        return pack_macro_batch(encs, window=window)
    if tuned.macro_p <= 0:
        return pack_batch(encs)
    return pack_macro_batch(encs, cap=tuned.macro_p, window=window)


def _chunk_candidates(default_chunk: int, e_sched: int) -> List[int]:
    """Chunk sizes worth sampling for a schedule of `e_sched` events:
    the global default, its neighbors, and 0 (one whole-schedule span).
    Values ≥ the schedule collapse into 0's shape and are dropped."""
    cands = [default_chunk, default_chunk * 2, 0]
    out: List[int] = []
    for c in cands:
        if c >= max(e_sched, 1):
            c = 0
        if c not in out:
            out.append(c)
    return out


def _fanout_candidates() -> List[int]:
    """Fan-out widths worth sampling: the full mesh, a 2-device mesh,
    and single-device. On hosts where devices are virtual (pin_cpu's
    8 vdevs over 2 cores) the snug meshes routinely win — the
    per-launch partition rendezvous scales with device count."""
    from ..parallel.mesh import chunk_sharding

    sharding = chunk_sharding()
    full = int(sharding.mesh.size) if sharding is not None else 1
    out = [full]
    for n in (max(full // 2, 2), 2, 1):
        if n < full and n not in out:
            out.append(n)
    return out


def _macro_candidates() -> List[int]:
    """Macro payload caps worth sampling. The macro stream's 1.8× win
    is established, so the legacy stream (0) is only re-sampled via the
    cap ladder's smallest rung — a narrower cap trades more rows for
    narrower ones, which can win on the host."""
    if not macro_events_on():
        return [0]
    return [MACRO_MAX_OPENS, 4]


def tuned_group_plan(model, plan, encs: Sequence,
                     measure: bool = True) -> Optional[TunedPlan]:
    """Consult (and, for large-enough groups, measure) the plan for one
    dense window group. `plan` is the group's ops.dense_scan.DensePlan;
    `encs` the group's encodings in plan row order. Returns None —
    today's exact behavior — when autotuning is off, the group is LONG
    (the merged-cluster policies are separately measured), or the group
    is below the work gates with no persisted plan. `measure=False`
    (a service's launch, ISSUE 32): the plan is what memory holds
    (`preload_plans`) or None; the store is not read and no candidate
    is compiled or timed."""
    if not autotune_on() or not encs:
        return None
    from ..ops.dense_scan import MERGE_MAX_EVENTS

    e_max = max(e.n_events for e in encs)
    if e_max > MERGE_MAX_EVENTS:
        return None
    sig = bucket_signature(plan.kernel_tag, plan.n_slots, plan.n_states,
                           len(encs), e_max)
    found = plan_for(sig, disk=measure)
    if found is not None:
        return found
    if not measure or len(encs) < min_rows() \
            or len(encs) * e_max < min_cells():
        return None
    k = min(len(encs), sample_rows_cap())
    sample = list(encs[:k])
    val_of = np.asarray(plan.val_of[:k])
    e_sched = bucket_rows(e_max, 32)

    def sample_wall(cand: TunedPlan) -> float:
        return _run_dense_sample(model, plan, sample, val_of, cand)

    candidates = _coordinate_candidates(plan.kernel_tag, e_sched)
    return resolve_plan(sig, candidates, sample_wall)


def _coordinate_candidates(family: str, e_sched: int) -> List[TunedPlan]:
    """The candidate grid, kept deliberately small (each distinct
    launch shape is an XLA compile during measurement): chunk ladder ×
    {default fan-out} plus fan-out ladder × {default chunk} plus macro
    ladder × {default chunk+fanout} — a star around the default rather
    than the full cross product."""
    base = default_plan(family)
    out: List[TunedPlan] = [base]

    def add(**kw):
        c = TunedPlan(**{**asdict(base), **kw})
        if c not in out:
            out.append(c)

    for chunk in _chunk_candidates(base.scan_chunk or 128, e_sched):
        add(scan_chunk=chunk)
    for fan in _fanout_candidates():
        add(mesh_fanout=fan)
    for p in _macro_candidates():
        add(macro_p=p)
    return out


def _run_dense_sample(model, plan, sample: Sequence, val_of: np.ndarray,
                      cand: TunedPlan) -> float:
    """One timed sample run of a dense group candidate, through the
    exact launch path the plan will drive (build_dense_launches'
    placement mapping, run_chunked driver, stats suppressed)."""
    from ..ops.dense_scan import make_dense_chunk_checker
    from ..parallel.mesh import chunk_sharding
    from .schedule import ChunkLaunch, run_chunked

    _bump("samples_run")
    batch = pack_group(sample, cand, window=plan.n_slots)
    e_len = batch["events"].shape[1]
    e_sched = bucket_rows(e_len, 32)
    sharding = chunk_sharding(cand.mesh_fanout)
    init_fn, step_fn = make_dense_chunk_checker(
        model, plan.kind, plan.n_slots, plan.n_states,
        mesh=getattr(sharding, "mesh", None),
        macro_p=batch.get("macro_p"))
    chunk = cand.scan_chunk or max(e_sched, 1)
    launch = ChunkLaunch(
        events=batch["events"], n_events=batch["n_events"],
        init_fn=init_fn, step_fn=step_fn, val_of=val_of,
        e_sched=e_sched, device=sharding, tag="autotune-sample",
        chunk=chunk)
    t0 = time.perf_counter()
    run_chunked([launch], chunk=chunk, record_stats=False)
    return time.perf_counter() - t0


def tuned_sort_plan(model, encs: Sequence, n_configs: int,
                    n_slots: int, measure: bool = True
                    ) -> Optional[TunedPlan]:
    """Sort-ladder twin of `tuned_group_plan` for one capacity rung;
    the rung's frontier capacity rides the signature's state slot (it
    picks the compiled kernel exactly like S does for the dense
    family).

    The sort rung's base plan pins `mesh_fanout=1` — TODAY'S behavior:
    unlike the dense groups, the pre-autotune sort rung never got the
    PR 3 mesh fan-out (single-device vmap). That makes fan-out the
    rung's headline candidate dimension: on the 8-vdev host mesh a
    fanned-out sort rung measured 1.84× over the single-device default
    at the wide-domain register shape (2026-08-04, this host) — the
    kind of per-bucket mis-calibration this module exists to find."""
    if not autotune_on() or not encs:
        return None
    e_max = max(e.n_events for e in encs)
    sig = bucket_signature("sort", n_slots, n_configs, len(encs), e_max)
    found = plan_for(sig, disk=measure)
    if found is not None:
        return found
    if not measure or len(encs) < min_rows() \
            or len(encs) * e_max < min_cells():
        return None
    sample = list(encs[:min(len(encs), sample_rows_cap())])
    e_sched = bucket_rows(e_max, 32)

    def sample_wall(cand: TunedPlan) -> float:
        return _run_sort_sample(model, n_configs, n_slots, sample, cand)

    base = TunedPlan(**{**asdict(default_plan("sort")), "mesh_fanout": 1})
    candidates: List[TunedPlan] = [base]
    for chunk in _chunk_candidates(base.scan_chunk or 128, e_sched):
        c = TunedPlan(**{**asdict(base), "scan_chunk": chunk})
        if c not in candidates:
            candidates.append(c)
    for fan in _fanout_candidates():
        c = TunedPlan(**{**asdict(base), "mesh_fanout": fan})
        if c not in candidates:
            candidates.append(c)
    for p in _macro_candidates():
        c = TunedPlan(**{**asdict(base), "macro_p": p})
        if c not in candidates:
            candidates.append(c)
    return resolve_plan(sig, candidates, sample_wall)


# ------------------------------------- lin fast-path gating (ISSUE 14)
# The linearizable-rung pre-kernel certify pass (checker/linearizable
# `lin_fastpath_pass`) has a measured worst case: a batch whose rows the
# host certifier cannot decide pays the host scan AND the kernel. The
# autotuner therefore grows a `lin_fastpath` dimension: per (model
# family, event shape-bucket, row class of the caller's batch) it
# accumulates what the certifier cost and how many of its
# verdicts the caller USED, beside what the kernels cost for the rows
# they decided — persisted in the SAME host-fingerprinted store as the
# launch plans (a host change invalidates both walls exactly like chunk
# timings) — and `lin_fastpath_route` answers whether a bucket should
# try the host certifier first or go kernel-first: host-first only while
# a verdict from the certifier is cheaper than the same verdict from the
# kernels (ISSUE 28). Gating only ever affects ROUTING, never verdicts
# (undecided and gated rows always reach the kernels), and is part of
# the measured autotuner: with JGRAFT_AUTOTUNE=0 the fast path always
# tries (flag-only behavior, no host-state dependence — what the
# deterministic test environment pins).

#: lin-fastpath record schema version; unknown versions re-observe.
#: 2 (ISSUE 28): `hits` are verdicts the caller used, not rows the scan
#: certified, and the record carries the kernel side.
LINFP_VERSION = 2

#: Row class of a caller that rides no launch: the host ladder
#: (`check_encoded_host`), whose alternative is the host search. No
#: kernel sample is ever folded into it, so it stays on "tries unless
#: nothing was ever delivered".
LINFP_NO_LAUNCH = 0

_LINFP_FIELDS = (("rows", int), ("hits", int), ("certify_wall_s", float),
                 ("kernel_rows", int), ("kernel_wall_s", float))

_LINFP_MEM: dict = {}   # sig -> {field: value for _LINFP_FIELDS}


def lin_fastpath_min_obs() -> int:
    """Rows a side of a bucket must have been observed over before the
    gate may route it kernel-first (JGRAFT_LIN_FASTPATH_MIN_OBS,
    default 64): trying IS measuring, so unknown buckets always try."""
    return env_int("JGRAFT_LIN_FASTPATH_MIN_OBS", 64, minimum=1)


def lin_fastpath_sig(family: str, n_events: int,
                     batch_rows: int = 1) -> tuple:
    """Gating bucket: model family, the pow2+midpoint event bucket (the
    same floor-32 series the launch shapes pad to), and the row class
    of the caller's batch — `bucket_rows` of the rows it delivers
    together: `check_encoded`'s batch (which is also its launch), one
    request in graftd's lane (`LINFP_NO_LAUNCH` for the host ladder).
    The row class is there because both sides depend on it: what the
    lane can deliver whole depends on how many rows a request holds (a
    one-history request mostly certifies, a 32-history request hardly
    ever does), and a kernel row costs ~1.5 ms in a 256-row launch and
    two orders more alone. Window/state shape is deliberately absent —
    certify cost scales with E·W but fragmenting the observations per
    window would starve the gate of samples."""
    rows = int(batch_rows)
    return ("linfp", str(family), bucket_rows(max(int(n_events), 1), 32),
            bucket_rows(rows) if rows > 0 else LINFP_NO_LAUNCH)


def _linfp_name(sig: tuple) -> str:
    return f"linfp-{sig[1]}-e{sig[2]}-r{sig[3]}.json"


def _linfp_path(sig: tuple) -> Path:
    return store_root() / host_fingerprint() / _linfp_name(sig)


def linfp_shared_dir() -> Optional[Path]:
    """Shared gate-store directory (ISSUE 18 satellite): when
    JGRAFT_LINFP_DIR (fallback: the cluster rendezvous dir,
    JGRAFT_SERVICE_CLUSTER_DIR) names a directory every replica can
    reach, lin-fastpath gate records replicate through
    ``<dir>/linfp/`` so the fast path can re-enable inside distributed
    wavefronts: every rank routes off the same published snapshot
    instead of its private observation history. Unset → None (gating
    stays host-local, wavefronts stay kernel-first)."""
    raw = env_str("JGRAFT_LINFP_DIR", "").strip()
    if not raw:
        raw = (env_str("JGRAFT_SERVICE_CLUSTER_DIR") or "").strip()
    if not raw:
        return None
    return Path(raw) / "linfp"


def _linfp_shared_path(sig: tuple) -> Optional[Path]:
    d = linfp_shared_dir()
    if d is None:
        return None
    return d / _linfp_name(sig)


def _load_linfp(path: Path, sig: tuple, require_host: bool) -> \
        Optional[dict]:
    """Parse one gate record, or None. Shared records skip the
    host-fingerprint check: what matters inside a wavefront is that
    every rank routes off the SAME snapshot, so every field the rule
    reads (both row counts, both walls) travels in the record and the
    publisher's walls stand in for the reader's — routing only, never
    verdicts. Host-local records keep the strict check so a toolchain
    swap re-observes, exactly like plans."""
    try:
        raw = json.loads(path.read_text())
        if (raw.get("version") == LINFP_VERSION
                and raw.get("signature") == list(sig)
                and (not require_host
                     or raw.get("fingerprint") == host_fingerprint())):
            return {k: cast(raw[k]) for k, cast in _LINFP_FIELDS}
        _log.warning("autotune: stale lin-fastpath record %s — "
                     "re-observing", path)
    except FileNotFoundError:
        pass
    except (OSError, json.JSONDecodeError, UnicodeDecodeError,
            KeyError, TypeError, ValueError) as e:
        _log.warning("autotune: unreadable lin-fastpath record %s "
                     "(%s: %s) — re-observing", path, type(e).__name__, e)
    return None


def _linfp_record(sig: tuple) -> dict:
    """The bucket's in-memory record, seeded on first touch from the
    fingerprint store — or, when the local file is absent/stale, from
    the shared gate dir, which is how a fresh replica inherits the
    cluster's gate history instead of paying min_obs rows of
    re-observation. Corrupt/stale/foreign files mean 'start fresh,
    never silently mis-gate' — same stance as `plan_for`."""
    with _LOCK:
        rec = _LINFP_MEM.get(sig)
        if rec is not None:
            return rec
    fresh = _load_linfp(_linfp_path(sig), sig, require_host=True)
    if fresh is None:
        shared = _linfp_shared_path(sig)
        if shared is not None:
            fresh = _load_linfp(shared, sig, require_host=False)
    if fresh is None:
        fresh = {k: cast() for k, cast in _LINFP_FIELDS}
    with _LOCK:
        rec = _LINFP_MEM.setdefault(sig, fresh)
    return rec


def _linfp_host_first(rec: dict) -> bool:
    """The rule, on one record; the caller holds _LOCK."""
    n = lin_fastpath_min_obs()
    if rec["rows"] < n:
        return True   # the certifier's side is still unknown: try
    if rec["hits"] == 0:
        return False  # nothing delivered is decisive alone
    if rec["kernel_rows"] < n:
        return True   # no kernel cost to hold the certifier's against
    return (rec["certify_wall_s"] / rec["hits"]
            < rec["kernel_wall_s"] / rec["kernel_rows"])


def lin_fastpath_route(sig: tuple) -> bool:
    """True → run the host certifier first for this bucket; False →
    kernel-first. Host-first while the bucket is still unknown, or while
    a verdict the caller used costs less from the certifier than a row
    costs through the kernels at this row class
    (``certify_wall_s / hits < kernel_wall_s / kernel_rows``); a bucket
    whose first `lin_fastpath_min_obs` scanned rows delivered nothing
    closes without waiting for a kernel sample. Routing only: a gated
    bucket's rows take the ordinary kernel ladder unchanged. A closed
    bucket is observed no further (neither side), so it stays closed
    until its record is deleted (doc/running.md)."""
    if not autotune_on():
        return True
    rec = _linfp_record(sig)
    with _LOCK:
        return _linfp_host_first(rec)


def lin_fastpath_observe(sig: tuple, rows: int, hits: int,
                         wall_s: float) -> None:
    """Fold one certify outcome into the bucket's record and persist
    it: `rows` scanned in `wall_s`, of which the caller USED `hits`
    verdicts (0 for a scan whose results were thrown away)."""
    _linfp_fold(sig, rows=rows, hits=hits, certify_wall_s=wall_s)


def lin_fastpath_observe_kernel(sig: tuple, rows: int,
                                wall_s: float) -> None:
    """Fold one kernel launch's cost into the bucket's record: `rows`
    of this bucket decided through the kernel ladder in `wall_s` (their
    share of the wall of the launch they rode). The caller leaves out a
    launch that compiled. A bucket already routed kernel-first takes no
    more samples: its record is settled, and a served launch should
    not pay a file write for it."""
    if rows <= 0 or not lin_fastpath_route(sig):
        return
    _linfp_fold(sig, kernel_rows=rows, kernel_wall_s=wall_s)


def _linfp_fold(sig: tuple, **deltas) -> None:
    """Add `deltas` to the bucket's record and persist it (atomic
    tmp+rename, best-effort — a read-only store degrades gating to
    in-memory, never checking)."""
    if not autotune_on() or not any(deltas.values()):
        return
    rec = _linfp_record(sig)
    with _LOCK:
        for k, v in deltas.items():
            rec[k] += v
        payload = {
            "version": LINFP_VERSION,
            "fingerprint": host_fingerprint(),
            "fingerprint_info": fingerprint_info(),
            "signature": list(sig),
            **{k: round(rec[k], 6) for k, _ in _LINFP_FIELDS},
            # what an operator reads the gate by: the two costs the
            # rule compares, and which way it currently falls
            "certify_s_per_used_verdict": round(
                rec["certify_wall_s"] / rec["hits"], 6)
            if rec["hits"] else None,
            "kernel_s_per_row": round(
                rec["kernel_wall_s"] / rec["kernel_rows"], 6)
            if rec["kernel_rows"] else None,
            "host_first": _linfp_host_first(rec),
            "updated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        }
    # publish locally AND (when configured) into the shared gate dir,
    # so sibling replicas inherit the observation. Shared writes race
    # last-writer-wins across replicas; each writer's record carries a
    # complete, internally consistent observation history, so whichever
    # lands is a valid gate input (per-pid tmp names keep the renames
    # atomic and non-colliding).
    targets = [_linfp_path(sig)]
    shared = _linfp_shared_path(sig)
    if shared is not None:
        targets.append(shared)
    for path in targets:
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".{os.getpid()}.tmp")
            tmp.write_text(json.dumps(payload, indent=2))
            os.replace(tmp, path)
        except OSError as e:
            _log.warning("autotune: could not persist lin-fastpath "
                         "record %s (%s: %s)", path, type(e).__name__, e)


# ------------------------------------------------- cycle-tier arm store
# ISSUE 19: the exact cycle tier has two routing dimensions per node
# bucket — condense-vs-direct (host Tarjan pre-pass or straight to the
# detector) and kernel-vs-DFS (batched closure launch or host 3-color
# DFS). Host-CPU thresholds are explicitly re-calibratable (ROADMAP
# item 5), so the choice is measured per bucket with the same
# plan-store discipline as the launch plans: fingerprint-keyed JSON,
# version/signature checks, in-memory negative cache, interleaved
# rotated best-of-min measurement. Arm choice is ROUTING ONLY — every
# arm is verdict-identical (differentially pinned in
# tests/test_cycle_tiled.py), so a stale or foreign record can only
# cost time, never answers.

#: cycle-arm record schema version; unknown versions re-measure.
CYCLE_ARM_VERSION = 1

#: Measurable arms, in deterministic measurement order: "condense" =
#: host Tarjan SCC pre-pass (detection IS the pre-pass), "dfs" = direct
#: host 3-color DFS, "kernel" = direct batched closure launch.
CYCLE_ARMS = ("condense", "dfs", "kernel")

_CYCLE_MEM: dict = {}   # sig -> arm str | _MISS


def cycle_arm_sig(n_bucket: int) -> tuple:
    """Arm bucket: the pow2+midpoint node bucket alone. The arm
    tradeoff is a property of graph size and host-vs-device matmul
    cost, not of the model family — fragmenting per family would
    starve small buckets of measurements."""
    return ("cycle-arm", int(n_bucket))


def _cycle_arm_path(sig: tuple) -> Path:
    return store_root() / host_fingerprint() / f"cycle-arm-n{sig[1]}.json"


def cycle_arm_for(sig: tuple) -> Optional[str]:
    """The bucket's measured arm: memory, then the fingerprint store.
    Same failure stance as `plan_for` — corrupt/stale/foreign records
    return None (re-measure, never silently mis-route), misses are
    negative-cached so per-batch consults stay disk-free."""
    with _LOCK:
        arm = _CYCLE_MEM.get(sig)
    if arm is _MISS:
        return None
    if arm is not None:
        _bump("plans_loaded")
        return arm
    path = _cycle_arm_path(sig)
    try:
        raw = json.loads(path.read_text())
    except FileNotFoundError:
        return _cycle_miss(sig)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        _log.warning("autotune: unreadable cycle-arm record %s (%s: %s)"
                     " — re-measuring", path, type(e).__name__, e)
        return _cycle_miss(sig)
    arm = raw.get("arm")
    if (raw.get("version") != CYCLE_ARM_VERSION
            or raw.get("fingerprint") != host_fingerprint()
            or raw.get("signature") != list(sig)
            or arm not in CYCLE_ARMS):
        _log.warning("autotune: stale/corrupt cycle-arm record %s — "
                     "re-measuring", path)
        return _cycle_miss(sig)
    with _LOCK:
        _CYCLE_MEM[sig] = arm
    _bump("plans_loaded")
    return arm


def _cycle_miss(sig: tuple):
    with _LOCK:
        _CYCLE_MEM[sig] = _MISS
        _COUNTERS["plan_misses"] += 1
    return None


def save_cycle_arm(sig: tuple, arm: str, samples: dict) -> None:
    """Persist a measured arm (atomic tmp+rename; persistence failures
    warn and keep the in-memory arm)."""
    with _LOCK:
        _CYCLE_MEM[sig] = arm
    path = _cycle_arm_path(sig)
    payload = {
        "version": CYCLE_ARM_VERSION,
        "fingerprint": host_fingerprint(),
        "fingerprint_info": fingerprint_info(),
        "signature": list(sig),
        "arm": arm,
        "samples": samples,
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        tmp.write_text(json.dumps(payload, indent=2))
        os.replace(tmp, path)
    except OSError as e:
        _log.warning("autotune: could not persist cycle-arm record %s "
                     "(%s: %s)", path, type(e).__name__, e)


def resolve_cycle_arm(sig: tuple,
                      measures: "dict[str, Callable[[], float]]") -> str:
    """Measure the available arms interleaved (one untimed warm-up rep
    each absorbs XLA compiles, then `sample_reps` rounds with rotating
    order — the resolve_plan discipline verbatim), pick best-of-min,
    persist, return. `measures` maps arm name → zero-arg wall-seconds
    measurement over the SAME batch of graphs; the caller asserts
    verdict identity across arms before trusting any timing."""
    arms = [a for a in CYCLE_ARMS if a in measures]
    times: dict = {a: [] for a in arms}
    for a in arms:
        measures[a]()
    reps = sample_reps()
    for rep in range(reps):
        order = arms[rep % len(arms):] + arms[:rep % len(arms)]
        for a in order:
            times[a].append(measures[a]())
    best = min(arms, key=lambda a: min(times[a]))
    samples = {a: [round(t, 6) for t in ts] for a, ts in times.items()}
    save_cycle_arm(sig, best, samples)
    _bump("plans_measured")
    return best


def sort_rung_sharding(tuned: Optional[TunedPlan]):
    """The sort rung's launch placement under a plan: None (today's
    single-device rung) without a plan or at fanout ≤ 1, else the
    capped batch-axis sharding."""
    if tuned is None or tuned.mesh_fanout <= 1:
        return None
    from ..parallel.mesh import chunk_sharding

    return chunk_sharding(tuned.mesh_fanout)


def _run_sort_sample(model, n_configs: int, n_slots: int,
                     sample: Sequence, cand: TunedPlan) -> float:
    from ..ops.linear_scan import make_sort_chunk_checker
    from .schedule import ChunkLaunch, run_chunked

    _bump("samples_run")
    batch = pack_group(sample, cand)
    e_sched = bucket_rows(batch["events"].shape[1], 32)
    sharding = sort_rung_sharding(cand)
    init_fn, step_fn = make_sort_chunk_checker(
        model, n_configs, n_slots, mesh=getattr(sharding, "mesh", None),
        macro_p=batch.get("macro_p"))
    chunk = cand.scan_chunk or max(e_sched, 1)
    launch = ChunkLaunch(
        events=batch["events"], n_events=batch["n_events"],
        init_fn=init_fn, step_fn=step_fn, e_sched=e_sched,
        device=sharding, tag="autotune-sample", chunk=chunk)
    t0 = time.perf_counter()
    run_chunked([launch], chunk=chunk, record_stats=False)
    return time.perf_counter() - t0
