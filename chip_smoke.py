#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the checker still starts on
the chip.

One process that owns the accelerator from start to end (no child ever
imports jax). It drives the program through the entry points a user
calls — `check_histories` / `check_encoded`, then graftd over loopback —
at BASELINE.json config 1's real shape (1000 independent 1k-op
CAS-register histories, 5 processes), then launches every other kernel
family once by name against its host reference. Data comes from
`--seed` through history/synth.py.

Every phase prints one JSON line when it ends. A phase that fails ends
the script at once: non-zero exit, last line `{"ok": false, ...}`.
Nothing is caught and carried on from. On success the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

A run that finds no TPU fails at the gate. `--rehearse` shrinks the
shapes (nothing else) so the whole script can be walked on the CPU; a
rehearsal always ends `"ok": false` with reason `rehearsal` — it is
never reported as a chip run. `--chips 4` runs ONLY the four-device
mesh phase and its one-device comparison.

Times printed here are smoke observations, not benchmark results.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import random
import sys
import tempfile
import threading
import time
import traceback

#: sizes: (real, rehearsal). Rehearsal shrinks shapes only.
SIZES = {
    "n_histories": (1000, 48),
    "n_ops": (1000, 120),
    "ref_sample": (100, 16),
    "clients": (8, 8),
    "mask_histories": (128, 12),
    "mask_ops": (1000, 80),
    "sort_histories": (32, 6),
    "sort_ops": (200, 60),
    "cycle_rows": (8, 3),
    "cycle_ops_small": (300, 40),
    "cycle_ops_big": (800, 90),
}

_PHASE = "start"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def phase(name: str) -> None:
    global _PHASE
    _PHASE = name


def need(cond, msg: str) -> None:
    """A smoke assertion: unlike `assert` it survives `python -O`."""
    if not cond:
        raise AssertionError(f"[{_PHASE}] {msg}")


def verdicts(results) -> list:
    return [r["valid?"] for r in results]


def tier_counts(results) -> dict:
    return dict(collections.Counter(
        r.get("decided-tier", "?") for r in results))


def tag_counts(results) -> dict:
    return dict(collections.Counter(
        r["kernel"] for r in results if "kernel" in r))


def scan_counters(scope: dict) -> dict:
    return {k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in scope.items() if k not in ("label", "tiers")}


def cache_entries(path) -> int:
    if not path:  # no cache (CPU-pinned process)
        return 0
    try:
        return len(os.listdir(path))
    except OSError:  # not created yet
        return 0


def need_clean(results, what: str) -> None:
    """Neither the process nor any result may say the platform
    degraded."""
    from jepsen_jgroups_raft_tpu import platform as plat

    need(plat.degraded_note() is None,
         f"{what}: platform degraded: {plat.degraded_note()}")
    bad = [r for r in results if "platform-degraded" in r]
    need(not bad, f"{what}: {len(bad)} results carry platform-degraded")


def need_kernel_rows(results, what: str) -> dict:
    """Some row must have been decided by a kernel; the rows a kernel
    tag decided."""
    tags = tag_counts(results)
    need(tags, f"{what}: no row was decided by a kernel")
    return tags


def host_reference(encs, model, idxs) -> list:
    from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu

    return [check_encoded_cpu(encs[i], model).valid for i in idxs]


def make_batch(rng, n, n_ops, corrupt_share=0.1, n_procs=5,
               max_crashes=3, kind="register", planted=1, value_range=3,
               crash_p=0.05):
    """`n` seeded valid histories of which a seeded share is perturbed
    with `synth.corrupt` (which may or may not break a history — the
    checker decides). `planted` evenly spaced rows also get an
    acknowledged read of a value nobody wrote, so invalid verdicts are
    certain (one per graftd client slice in the config-1 batch)."""
    from jepsen_jgroups_raft_tpu.history.synth import (build_history,
                                                       corrupt,
                                                       random_valid_history)

    hs = [random_valid_history(rng, kind, n_ops=n_ops, n_procs=n_procs,
                               value_range=value_range, crash_p=crash_p,
                               max_crashes=max_crashes)
          for _ in range(n)]
    for i in rng.sample(range(n), max(1, int(n * corrupt_share))):
        hs[i] = corrupt(rng, hs[i])
    never = 99 if kind == "register" else -5
    for i in range(0, n, max(1, n // planted))[:planted]:
        rows = [(o.process, o.type, o.f, o.value) for o in hs[i]]
        rows += [(10_000, "invoke", "read", None),
                 (10_000, "ok", "read", never)]
        hs[i] = build_history(rows)
    return hs


# ----------------------------------------------------------- phases


def phase_library(sz, rng, model):
    """BASELINE config 1 through the library entry points: once at
    defaults, once with the host certifier off so every row reaches a
    kernel. Each arm runs twice — the first pass pays compilation and
    autotune sampling, the second is warm."""
    import jax

    from jepsen_jgroups_raft_tpu.checker import autotune
    from jepsen_jgroups_raft_tpu.checker.linearizable import (
        check_encoded, check_histories)
    from jepsen_jgroups_raft_tpu.checker.schedule import stats_scope
    from jepsen_jgroups_raft_tpu.history.packing import encode_history

    phase("synth")
    t0 = time.perf_counter()
    hists = make_batch(rng, sz["n_histories"], sz["n_ops"],
                       planted=sz["clients"])
    synth_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    encs = [encode_history(h, model) for h in hists]
    emit({"phase": "synth", "histories": len(hists),
          "ops_per_history": sz["n_ops"],
          "max_events": max(e.n_events for e in encs),
          "windows": dict(collections.Counter(e.n_slots for e in encs)),
          "synth_s": round(synth_s, 3),
          "encode_s": round(time.perf_counter() - t0, 3)})

    def timed(fn):
        t0 = time.perf_counter()
        with stats_scope() as scope:
            out = fn()
        return out, time.perf_counter() - t0, scope

    phase("library-default")
    mark = autotune.applied_seq()
    run = functools.partial(check_histories, hists, model, algorithm="auto")
    _, cold_s, _ = timed(run)
    res_default, warm_s, scope = timed(run)
    need_clean(res_default, "default run")
    tiers = tier_counts(res_default)
    certified = sum(n for t, n in tiers.items() if t.endswith("@lin"))
    emit({"phase": "library-default", "rows": len(res_default),
          "first_pass_s": round(cold_s, 3), "warm_pass_s": round(warm_s, 3),
          "decided_tier": tiers,
          "host_certified_share": round(certified / len(res_default), 4),
          "kernel_tags": tag_counts(res_default),
          "valid": verdicts(res_default).count(True),
          "invalid": verdicts(res_default).count(False),
          "scan": scan_counters(scope)})

    phase("library-forced-kernel")
    run = functools.partial(check_encoded, encs, model, algorithm="auto",
                            lin_fastpath=False)
    _, cold_s, _ = timed(run)
    res_forced, warm_s, scope = timed(run)
    need_clean(res_forced, "forced-kernel run")
    need(scope["chunks_run"] > 0,
         f"forced-kernel run launched no chunk: {scan_counters(scope)}")
    tiers = tier_counts(res_forced)
    need(not any(t.endswith("@lin") for t in tiers),
         f"the host certifier decided rows with lin_fastpath=False: {tiers}")
    tags = need_kernel_rows(res_forced, "forced-kernel run")
    need(verdicts(res_default) == verdicts(res_forced),
         "default and forced-kernel runs disagree")
    v = verdicts(res_forced)
    need(True in v and False in v and "unknown" not in v,
         f"both verdicts must occur and none be unknown: "
         f"{collections.Counter(map(str, v))}")
    peak = jax.devices()[0].memory_stats() or {}
    emit({"phase": "library-forced-kernel", "rows": len(res_forced),
          "first_pass_s": round(cold_s, 3), "warm_pass_s": round(warm_s, 3),
          "decided_tier": tiers, "kernel_tags": tags,
          "valid": v.count(True), "invalid": v.count(False),
          "scan": scan_counters(scope),
          "autotune_plans": [
              {"signature": e["signature"], "plan": e["plan"],
               "source": e["source"]}
              for e in autotune.applied_since(mark)][:12],
          "peak_bytes_in_use": peak.get("peak_bytes_in_use")})

    phase("library-host-reference")
    # every invalid row plus a seeded sample of the rest
    bad = [i for i, ok in enumerate(v) if ok is not True]
    rest = [i for i, ok in enumerate(v) if ok is True]
    k = max(0, sz["ref_sample"] - len(bad))
    sample = sorted(bad + rng.sample(rest, min(k, len(rest))))
    t0 = time.perf_counter()
    ref = host_reference(encs, model, sample)
    wrong = [i for i, r in zip(sample, ref) if r != v[i]]
    need(not wrong, f"kernel verdict != wgl_cpu on rows {wrong[:10]}")
    emit({"phase": "library-host-reference", "rows_compared": len(sample),
          "invalid_compared": len(bad), "mismatches": 0,
          "reference": "checker/wgl_cpu.check_encoded_cpu",
          "seconds": round(time.perf_counter() - t0, 3)})
    return hists, v


def phase_service(sz, hists, expect):
    """graftd in this same process, driven over loopback: concurrent
    clients each submit a slice of the library batch, half of them as
    binary frames; one resubmission must answer from the cache."""
    from jepsen_jgroups_raft_tpu.service import ServiceClient
    from jepsen_jgroups_raft_tpu.service.daemon import CheckingService
    from jepsen_jgroups_raft_tpu.service.http import serve_in_thread

    phase("service")
    n_clients = sz["clients"]
    per = len(hists) // n_clients
    slices = [hists[c * per:(c + 1) * per] for c in range(n_clients)]
    records: list = [None] * n_clients
    errors: list = []
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as store:
        svc = CheckingService(store_root=store)
        httpd, port, _ = serve_in_thread(svc)
        try:
            url = f"http://127.0.0.1:{port}"

            def client(c):
                try:
                    cl = ServiceClient(url, timeout=600.0)
                    rec = cl.submit(slices[c], workload="register",
                                    binary=bool(c % 2))
                    while rec.get("status") not in ("done", "failed",
                                                    "cancelled"):
                        rec = cl.result(rec["id"], wait_s=10.0)
                    records[c] = rec
                    cl.close()
                except Exception as e:  # re-raised on the main thread
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            got: list = []
            for c, rec in enumerate(records):
                need(rec["status"] == "done",
                     f"request {c} ended {rec['status']}: "
                     f"{rec.get('error')}")
                need_clean(rec["results"], f"service request {c}")
                got += verdicts(rec["results"])
            need(got == expect[:len(got)],
                 "graftd verdicts differ from the library phase")
            cl = ServiceClient(url, timeout=600.0)
            again = cl.check(slices[0], workload="register")
            need(again.get("cached") is True,
                 f"resubmission was not served from the cache: "
                 f"{ {k: again.get(k) for k in ('status', 'cached')} }")
            need(verdicts(again["results"]) == expect[:per],
                 "cached verdicts differ")
            stats = cl.stats()
            cl.close()
        finally:
            httpd.shutdown()
            httpd.server_close()
            svc.shutdown(wait=True)
    need(stats["batched_requests"] > stats["batches"] >= 1,
         f"no cross-request batching: {stats['batched_requests']} "
         f"requests over {stats['batches']} batches")
    need(stats["degraded_batches"] == 0,
         f"{stats['degraded_batches']} degraded batches")
    need(stats["failed"] == 0, f"{stats['failed']} failed requests")
    emit({"phase": "service", "clients": n_clients,
          "histories_per_request": per, "binary_requests": n_clients // 2,
          "rows": len(got), "wall_s": round(wall, 3),
          "batches": stats["batches"],
          "batched_requests": stats["batched_requests"],
          "batch_rows": stats["batch_rows"],
          "fastpath_requests": stats["fastpath_requests"],
          "degraded_batches": stats["degraded_batches"],
          "cache_hits": stats["cache_hits"], "resubmit_cached": True,
          "decided_tier": stats.get("decided_tier")})


def run_family(name, encs, model, want_tag, rng, ref_rows, **kw):
    """One batch through `check_encoded` with the host certifier off,
    compared row by row against wgl_cpu."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_encoded
    from jepsen_jgroups_raft_tpu.checker.schedule import stats_scope

    t0 = time.perf_counter()
    with stats_scope() as scope:
        res = check_encoded(encs, model, lin_fastpath=False, **kw)
    wall = time.perf_counter() - t0
    need_clean(res, name)
    tags = need_kernel_rows(res, name)
    need(any(want_tag in t for t in tags),
         f"{name}: no row went through a {want_tag!r} kernel: {tags}")
    v = verdicts(res)
    decided = [i for i, x in enumerate(v) if x in (True, False)]
    need(decided, f"{name}: no row decided")
    sample = sorted(rng.sample(decided, min(ref_rows, len(decided))))
    t0 = time.perf_counter()
    ref = host_reference(encs, model, sample)
    wrong = [i for i, r in zip(sample, ref) if r != v[i]]
    need(not wrong, f"{name}: kernel verdict != wgl_cpu on rows {wrong[:10]}")
    line = {"phase": f"family-{name}", "rows": len(res),
            "kernel_tags": tags, "decided_tier": tier_counts(res),
            "valid": v.count(True), "invalid": v.count(False),
            "undecided": len(v) - len(decided),
            "rows_compared": len(sample), "mismatches": 0,
            "first_pass_s": round(wall, 3),
            "reference_s": round(time.perf_counter() - t0, 3),
            "scan": scan_counters(scope)}
    return res, line


def phase_families(sz, rng, rehearse):
    from jepsen_jgroups_raft_tpu.checker import cycle
    from jepsen_jgroups_raft_tpu.checker.linearizable import check_encoded
    from jepsen_jgroups_raft_tpu.checker.schedule import stats_scope
    from jepsen_jgroups_raft_tpu.history.packing import encode_history
    from jepsen_jgroups_raft_tpu.history.synth import (build_history,
                                                       random_valid_history)
    from jepsen_jgroups_raft_tpu.models.counter import Counter
    from jepsen_jgroups_raft_tpu.models.register import CasRegister
    from jepsen_jgroups_raft_tpu.ops.kernel_ir import (CYCLE_MAX_NODES,
                                                       DENSE_MAX_SLOTS)

    reg = CasRegister()

    # mask: BASELINE config 2, the counter workload.
    phase("family-mask")
    counter = Counter()
    hs = make_batch(rng, sz["mask_histories"], sz["mask_ops"],
                             kind="counter")
    encs = [encode_history(h, counter) for h in hs]
    _, line = run_family("mask", encs, counter, "mask", rng,
                         ref_rows=32, algorithm="auto")
    need(line["valid"], "mask: no valid row")
    emit(line)

    # sort: register histories the dense kernels cannot take. A value
    # domain past DENSE_MAX_STATES sends a row to the sort ladder's
    # first rung; windows past DENSE_MAX_SLOTS escalate to its top rung
    # (and may overflow it: under algorithm="jax" that is `unknown`).
    phase("family-sort")
    hs = make_batch(rng, sz["sort_histories"], sz["sort_ops"],
                    corrupt_share=0.25, value_range=40)
    hs += make_batch(rng, 4, sz["sort_ops"], n_procs=8, crash_p=0.2,
                     max_crashes=5)
    encs = [encode_history(h, reg) for h in hs]
    wide = sum(e.n_slots > DENSE_MAX_SLOTS for e in encs)
    res, line = run_family("sort", encs, reg, "sort", rng,
                           ref_rows=len(encs), algorithm="jax")
    need(line["valid"] and line["invalid"], "sort: both verdicts must occur")
    line["rows_past_dense_slots"] = wide
    emit(line)

    # cycle closure: the sequential rung on rows with a planted
    # dependency cycle (a process reads the initial value after its own
    # acknowledged write), then both closure kernels against the host
    # DFS through find_cycles.
    phase("family-cycle")

    def planted(n_ops, cyclic):
        base = random_valid_history(rng, "register", n_ops=n_ops,
                                    n_procs=5, crash_p=0.05, max_crashes=3)
        rows = [(o.process, o.type, o.f, o.value) for o in base]
        if cyclic:
            p = 1000 + n_ops
            rows += [(p, "invoke", "write", 2), (p, "ok", "write", 2),
                     (p, "invoke", "read", None), (p, "ok", "read", None)]
        return build_history(rows)

    buckets = {}
    for label, n_ops in (("small", sz["cycle_ops_small"]),
                         ("big", sz["cycle_ops_big"])):
        hs = [planted(n_ops, cyclic=bool(i % 2))
              for i in range(sz["cycle_rows"])]
        buckets[label] = [encode_history(x, reg) for x in hs]
    t0 = time.perf_counter()
    with stats_scope() as scope:
        res = check_encoded(buckets["small"], reg, algorithm="auto",
                            consistency="sequential")
    need_clean(res, "cycle rung")
    by_cycle = [r for r in res if r.get("algorithm") == "cycle"]
    need(by_cycle and all(r["valid?"] is False for r in by_cycle),
         f"sequential rung: the cycle tier refuted nothing: "
         f"{tier_counts(res)}")
    rung_s = time.perf_counter() - t0
    arms = {}
    for label, encs in buckets.items():
        nodes = [g["n"] for g in
                 (cycle.build_sc_graph(e, reg) for e in encs) if g]
        if not rehearse:
            need((max(nodes) <= CYCLE_MAX_NODES) == (label == "small"),
                 f"cycle {label}: node counts {nodes} are on the wrong "
                 f"side of CYCLE_MAX_NODES={CYCLE_MAX_NODES}")
        with stats_scope() as ks:
            on = cycle.find_cycles(encs, reg, kernel=True)
        off = cycle.find_cycles(encs, reg, kernel=False)
        flags = [c is not None and "cycle" in c for c in on]
        need(flags == [c is not None and "cycle" in c for c in off],
             f"cycle {label}: closure kernel != host DFS")
        need(True in flags and False in flags,
             f"cycle {label}: both answers must occur: {flags}")
        if label == "big" and not rehearse:
            need(ks["cycle_tiles_run"] > 1,
                 "cycle big: the tiled closure kernel did not launch")
        arms[label] = {"max_nodes": max(nodes), "cyclic": flags.count(True),
                       "acyclic": flags.count(False),
                       "tiles_run": ks["cycle_tiles_run"]}
    emit({"phase": "family-cycle", "rung_rows": len(res),
          "rung_decided_tier": tier_counts(res),
          "rung_s": round(rung_s, 3), "closure_vs_dfs": arms,
          "mismatches": 0, "scan": scan_counters(scope)})


def phase_mesh(sz, rng, model):
    """`--chips 4`: config 1 through check_encoded on the four-device
    mesh and on one device; identical verdicts, and the launch's carry
    and outputs really live on four distinct devices."""
    import jax
    import numpy as np

    from jepsen_jgroups_raft_tpu.checker.linearizable import check_encoded
    from jepsen_jgroups_raft_tpu.checker.schedule import (
        build_dense_launches, stats_scope)
    from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                         pack_macro_batch)
    from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plans_grouped

    phase("mesh")
    need(len(jax.devices()) >= 4,
         f"--chips 4 needs four devices, found {len(jax.devices())}")
    hists = make_batch(rng, sz["n_histories"], sz["n_ops"])
    encs = [encode_history(h, model) for h in hists]

    def run():
        t0 = time.perf_counter()
        check_encoded(encs, model, algorithm="auto", lin_fastpath=False)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        with stats_scope() as scope:
            res = check_encoded(encs, model, algorithm="auto",
                                lin_fastpath=False)
        return res, cold, time.perf_counter() - t0, scope

    res4, cold4, warm4, scope4 = run()
    need_clean(res4, "mesh run")
    need(scope4["chunks_run"] > 0, "mesh run launched no chunk")
    # one device: the existing per-call gate, for this run only
    os.environ["JGRAFT_GROUP_DEVICES"] = "0"
    try:
        res1, cold1, warm1, scope1 = run()
    finally:
        del os.environ["JGRAFT_GROUP_DEVICES"]
    need_clean(res1, "one-device run")
    need(verdicts(res4) == verdicts(res1),
         "mesh and one-device verdicts differ")
    v = verdicts(res4)
    need(True in v and False in v, "both verdicts must occur")

    # Where the arrays live: the biggest window group's own launch,
    # built by the one home of the placement policy, stepped once.
    grouped, _ = dense_plans_grouped(model, encs)
    idxs, plan = max(grouped, key=lambda g: len(g[0]))
    batch = pack_macro_batch([encs[i] for i in idxs])
    [launch], _ = build_dense_launches(model, [(idxs, plan, batch)])
    need(launch.device is not None, "the launch was not fanned out")
    n_dev = launch.device.mesh.size
    rows = -(-len(idxs) // n_dev) * n_dev

    def put(a):  # pad with copies of row 0, place like the launch
        a = np.concatenate([a, np.repeat(a[:1], rows - len(a), axis=0)])
        return jax.device_put(a, launch.device)

    carry = launch.init_fn(put(launch.val_of),
                           put(launch.n_events.astype(np.int32)))
    width = min(32, launch.events.shape[1])
    out = launch.step_fn(carry, put(launch.events[:, :width]),
                         np.int32(0), np.int32(width))
    jax.block_until_ready(out)

    def homes(tree):
        return sorted({d.id for leaf in jax.tree_util.tree_leaves(tree)
                       for d in leaf.sharding.device_set})

    carry_devs, out_devs = homes(out[0]), homes(out[1:])
    need(len(carry_devs) == 4 and len(out_devs) == 4,
         f"launch not spread over four devices: carry on {carry_devs}, "
         f"outputs on {out_devs}")
    shard_devs = sorted(s.device.id for s in out[3].addressable_shards)
    need(len(set(shard_devs)) == 4,
         f"output shards addressable on {shard_devs}")
    emit({"phase": "mesh", "rows": len(res4),
          "mesh_devices": [str(d) for d in launch.device.mesh.devices.flat],
          "carry_device_ids": carry_devs, "output_device_ids": out_devs,
          "output_shard_device_ids": shard_devs,
          "verdicts_identical": True,
          "valid": v.count(True), "invalid": v.count(False),
          "mesh": {"first_pass_s": round(cold4, 3),
                   "warm_pass_s": round(warm4, 3),
                   "kernel_tags": tag_counts(res4),
                   "scan": scan_counters(scope4)},
          "one_device": {"first_pass_s": round(cold1, 3),
                         "warm_pass_s": round(warm1, 3),
                         "kernel_tags": tag_counts(res1),
                         "scan": scan_counters(scope1)}})


# ------------------------------------------------------------- main


def run(args) -> dict:
    from jepsen_jgroups_raft_tpu.platform import enable_compile_cache

    phase("gate")
    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit({"phase": "gate", "device": device, "jax": jax.__version__,
          "rehearse": args.rehearse, "chips": args.chips,
          "seed": args.seed})
    if not args.rehearse and device["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices() answered {device['platform']}")
    emit({"phase": "compile-cache", "dir": cache_dir,
          "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "entries_before": entries_before})

    from jepsen_jgroups_raft_tpu.models.register import CasRegister

    sz = {k: v[1 if args.rehearse else 0] for k, v in SIZES.items()}
    rng = random.Random(args.seed)
    model = CasRegister()
    if args.chips == 4:
        phase_mesh(sz, rng, model)
    else:
        hists, expect = phase_library(sz, rng, model)
        phase_service(sz, hists, expect)
        phase_families(sz, rng, args.rehearse)
    phase("compile-cache-after")
    emit({"phase": "compile-cache-after", "dir": cache_dir,
          "entries_before": entries_before,
          "entries_after": cache_entries(cache_dir)})
    return device


def fail(reason: str, device=None) -> int:
    emit({"ok": False, "reason": reason, "phase": _PHASE,
          **({"device": device} if device else {})})
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the four-device mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="shrink the shapes for a CPU walk-through; "
                         "always ends ok=false, reason=rehearsal")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = run(args)
    except Exception as e:
        traceback.print_exc()
        return fail(f"{type(e).__name__}: {e}"[:500])
    phase("done")
    emit({"phase": "done", "total_s": round(time.perf_counter() - t0, 3)})
    if args.rehearse:
        return fail("rehearsal", device)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
