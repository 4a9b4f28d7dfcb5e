#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process that owns the chip serves: graftd as its users reach it
(`CheckingService` + its HTTP front, program defaults) on loopback. The
load comes from child processes that never touch the chip. A request is
timed on the client from just before `submit` to the terminal record in
hand. Once the window has closed, the server is gone and the device's
peak memory has been read, the children run the plain reference over
the histories whose verdicts the window returned, and `correct` says
whether every verdict agreed.

Every phase prints one JSON line with its wall seconds when it ends; the
last line of standard output is the result. The whole process carries
one deadline, far under the driver's limit on a run: on reaching it the
run prints a failing result that names the phase it was in, and exits.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.layer_metrics import tier_rows  # noqa: E402

#: seconds from process start after which a run gives up; the driver
#: stops a run at 360.
DEADLINE_S = 335.0
#: what the phases after the window may take at most, in seconds
SHUTDOWN_CAP_S = 10.0
COMPARE_CAP_S = 60.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Run:
    """State of one run that the deadline and the phases share."""

    def __init__(self):
        self.phase = "start"
        self.phase_t0 = T0
        self.device = {"platform": None, "kind": None, "count": 0}
        self.children: list = []
        self.attempted = 0
        self.failed = 0

    def enter(self, name: str, **extra) -> None:
        now = time.monotonic()
        emit({"phase": self.phase, "seconds": now - self.phase_t0,
              **extra})
        self.phase, self.phase_t0 = name, now

    def stop_children(self) -> None:
        for c in self.children:
            c.stop()


class Child:
    def __init__(self, spec: dict, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, str(Path(__file__).with_name(
                "client_worker.py")), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, bufsize=1)
        self.inbox: queue.Queue = queue.Queue()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                self.inbox.put(json.loads(line))
            except ValueError:
                sys.stderr.write(f"client child said: {line}")
        self.inbox.put({"kind": "eof"})

    def tell(self, obj: dict) -> None:
        try:
            self.proc.stdin.write(json.dumps(obj) + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, ValueError):
            pass

    def hear(self, kind: str, until: float) -> dict:
        """Next message of `kind`; raises when the child ends first or
        `until` (monotonic) passes."""
        while True:
            left = until - time.monotonic()
            if left <= 0:
                raise TimeoutError(f"no {kind!r} from a client child")
            try:
                msg = self.inbox.get(timeout=left)
            except queue.Empty:
                continue
            if msg["kind"] == kind:
                return msg
            if msg["kind"] == "eof":
                raise RuntimeError(
                    f"a client child ended (rc {self.proc.poll()}) "
                    f"before saying {kind!r}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.tell({"cmd": "exit"})
            try:
                self.proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass


def start_deadline(run: Run, seconds: float) -> threading.Timer:
    def fire():
        emit({"phase": run.phase, "deadline_s": seconds,
              "error": "the run reached its own deadline"})
        sys.stderr.write(f"deadline of {seconds:.0f} s reached in phase "
                         f"{run.phase!r}\n")
        for c in run.children:
            if c.proc.poll() is None:
                c.proc.kill()
        for c in run.children:
            c.proc.wait()
        emit(result_line(False, run.attempted, max(run.failed, 1), {},
                         run.device, compared={}, extra={
                             "deadline_phase": run.phase}))
        sys.stdout.flush()
        os._exit(3)

    t = threading.Timer(max(0.0, T0 + seconds - time.monotonic()), fire)
    t.daemon = True
    t.start()
    return t


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None, extra=None) -> dict:
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["compared"] = compared  # last, so the end of the line shows it
    return line


def fs_type(path: Path) -> str:
    best, typ = "", "unknown"
    try:
        with open("/proc/mounts") as fh:
            for row in fh:
                _, mount, kind = row.split()[:3]
                if str(path).startswith(mount) and len(mount) > len(best):
                    best, typ = mount, kind
    except OSError:
        pass
    return typ


class CompileLog:
    """Every program the process builds or loads, by JAX's own
    monitoring events, with the moment it ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles: list = []   # (monotonic at end, seconds, name)
        self.misses: list = []

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw) -> None:
        if event == self.EVENT:
            self.compiles.append((time.monotonic(), seconds,
                                  kw.get("fun_name", "?")))

    def _event(self, event, **kw) -> None:
        if event == self.MISS:
            self.misses.append(time.monotonic())

    def between(self, t0, t1) -> int:
        return sum(1 for t, _, _ in self.compiles if t0 <= t <= t1)

    def seconds_between(self, t0, t1) -> float:
        return sum(s for t, s, _ in self.compiles if t0 <= t <= t1)

    def names_between(self, t0, t1) -> dict:
        return dict(collections.Counter(
            n for t, _, n in self.compiles if t0 <= t <= t1))

    def misses_between(self, t0, t1) -> int:
        return sum(1 for t in self.misses if t0 <= t <= t1)


def read_counters(svc) -> dict:
    """The program's own counters, read in-process (`/stats` serves the
    first of them)."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import \
        fastpath_counters
    from jepsen_jgroups_raft_tpu.checker.schedule import snapshot_tiers

    return {"stats": svc.stats(), "fastpath": fastpath_counters(),
            "tiers": snapshot_tiers()}


class Sampler(threading.Thread):
    """Samples a few counters while the trace runs, so that an idle gap
    of the device can be named by what advanced on the host in it."""

    KEYS = ("submitted", "completed", "fastpath_requests", "batches",
            "journal_group_commits")

    def __init__(self, svc, period_s=0.05):
        super().__init__(daemon=True)
        self.svc, self.period_s = svc, period_s
        self.samples: list = []
        self.stop_flag = threading.Event()

    def run(self) -> None:
        from jepsen_jgroups_raft_tpu.checker.linearizable import \
            fastpath_counters

        while not self.stop_flag.is_set():
            st = self.svc.stats()
            row = {k: st.get(k, 0) for k in self.KEYS}
            row["certify_wall_s"] = fastpath_counters().get(
                "certify_wall_s", 0.0)
            row["queue_depth"] = st.get("queue_depth", 0)
            self.samples.append((time.time_ns(), row))
            self.stop_flag.wait(self.period_s)


def run_cell(args, run: Run) -> dict:
    """Everything between the deadline's start and the result line.
    `args.gate` false skips the look for a chip (tests, rehearsals)."""
    root = Path(args.root).resolve()
    manifest = mf.load_manifest(root)
    entry, config, traffic = mf.cell(root, manifest, args.workload,
                                     rehearse=args.rehearse)
    e2e = mf.metrics_of(manifest, "end_to_end", entry["name"])
    layer = mf.metrics_of(manifest, "per_layer", entry["name"])
    window_s = float(args.seconds)

    # -- directories: all inside the checkout, fixed paths
    cache = mf.bench_dir(root) / "cache"
    cell_dir = cache / entry["name"]
    store = cell_dir / "run-store"
    shutil.rmtree(store, ignore_errors=True)
    store.mkdir(parents=True)
    # the trace is written and read back once: it goes to TMPDIR
    trace_dir = Path(tempfile.mkdtemp(prefix="graftd-bench-trace-")) \
        if args.trace else None
    if args.gate:  # a rehearsal on the CPU keeps no compile cache
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              str(cache / "jax"))
        # Every program goes into the cache, however fast it compiled,
        # so that only a checkout's first run compiles. graftd's own
        # entry points leave JAX's thresholds (1 s, no size floor) as
        # they are; a run with these two set to "1" and "0" in its
        # environment is at JAX's defaults (PERF.md, section 2).
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ["JGRAFT_AUTOTUNE_STORE"] = str(cell_dir / "autotune")

    try:
        import jepsen_jgroups_raft_tpu  # noqa: F401  the system under test
    except ImportError as e:
        raise NoProgram(f"the program is not in this checkout: {e}")

    # -- client children: make the pool while the parent reaches the chip
    run.enter("spawn")
    n_proc = int(traffic["client_processes"])
    n_clients = int(traffic["clients"])
    per = int(traffic["histories_per_request"])
    span_s = window_s + traffic["drain_cap_s"] + min(
        traffic["warmup_cap_s"], 30.0)
    n_req = int(traffic["pool_hist_per_s"] * span_s / per / n_proc) \
        + 2 * traffic["warmup_requests_per_client"] * n_clients // n_proc + 4
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(root)] + [p for p in sys.path if p]))
    for name in [k for k in env if k.startswith(("JAX_COMPILATION_CACHE",
                                                 "JAX_PERSISTENT_CACHE"))]:
        del env[name]
    for c in range(n_proc):
        spec = {"root": str(root), "child": c, "seed": args.seed,
                "config": config, "traffic": traffic, "n_requests": n_req,
                "first_request": c * n_req,
                "n_clients": n_clients // n_proc
                + (1 if c < n_clients % n_proc else 0)}
        run.children.append(Child(spec, env))

    # -- the chip
    run.enter("import_chip", client_processes=n_proc,
              pool_requests=n_req * n_proc)
    import jax

    devs = jax.devices()
    run.device.update(platform=devs[0].platform, kind=devs[0].device_kind,
                      count=len(devs))
    if args.gate and (devs[0].platform != "tpu"
                      or len(devs) < entry["chips"]):
        raise NoChip(f"the cell asks for {entry['chips']} TPU chip(s); "
                     f"JAX found {len(devs)} x {devs[0].platform}")
    if args.gate:
        with open(mf.bench_dir(root) / "peaks.json") as fh:
            if devs[0].device_kind not in json.load(fh):
                raise NoChip(f"device kind {devs[0].device_kind!r} is not "
                             f"in benchmarks/peaks.json")
    compiles = CompileLog()
    compiles.install()

    # -- graftd as its users reach it
    run.enter("server_start", device=run.device)
    from jepsen_jgroups_raft_tpu.service.daemon import CheckingService
    from jepsen_jgroups_raft_tpu.service.http import serve_in_thread

    svc = CheckingService(store_root=str(store))
    httpd, port, _ = serve_in_thread(svc)
    url = f"http://127.0.0.1:{port}"
    try:
        return serve_and_measure(args, run, root, entry, config, traffic,
                                 svc, httpd, url, compiles, window_s,
                                 trace_dir, e2e, layer, store)
    except BaseException:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=False)
        raise


class NoChip(RuntimeError):
    pass


class NoProgram(RuntimeError):
    pass


def serve_and_measure(args, run, root, entry, config, traffic, svc, httpd,
                      url, compiles, window_s, trace_dir, e2e, layer, store):
    import jax

    run.enter("pool", wal_filesystem=fs_type(store),
              compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    pool_until = T0 + 150.0
    ready = [c.hear("ready", pool_until) for c in run.children]

    run.enter("warmup", pool_s=max(m["seconds"] for m in ready))
    t_warm0 = time.monotonic()
    for c in run.children:
        c.tell({"cmd": "start", "url": url})
    warm_until = t_warm0 + traffic["warmup_cap_s"]
    warmed = 0
    for c in run.children:
        try:
            c.hear("warm", warm_until)
            warmed += 1
        except TimeoutError:
            break
    # The window opens on a quiet compiler: the traffic has run for
    # `warmup_quiet_s` without a program that the compile cache did not
    # hold, or the cap is reached. So a cold cache gets the long warm-up
    # it needs, and a full one the short one. (A program loaded from
    # the cache costs some 25 ms, one compiled 1 to 3 s.)
    quiet_s = traffic["warmup_quiet_s"]
    while time.monotonic() < warm_until:
        last = compiles.misses[-1] if compiles.misses else t_warm0
        if time.monotonic() - max(last, t_warm0) >= quiet_s:
            break
        time.sleep(0.1)

    # -- the window
    t_start = time.monotonic() + 0.05
    t_end = t_start + window_s
    drain_until = t_end + traffic["drain_cap_s"]
    run.enter("window", children_warm=warmed,
              compiles_in_warmup=compiles.between(t_warm0, t_start),
              compile_s_in_warmup=compiles.seconds_between(t_warm0, t_start),
              cache_misses_in_warmup=compiles.misses_between(
                  t_warm0, t_start))
    setup_s = t_start - T0
    time.sleep(max(0.0, t_start - time.monotonic()))
    for c in run.children:
        c.tell({"cmd": "window", "t_start": t_start, "t_end": t_end,
                "drain_until": drain_until})
    before = read_counters(svc)
    tracing = None
    if args.trace:
        tracing = start_tracing(svc, trace_dir, t_start, t_end, traffic)
    time.sleep(max(0.0, t_end - time.monotonic()))
    after = read_counters(svc)

    run.enter("drain", window_s=window_s)
    drained = [c.hear("drained", drain_until + 15.0) for c in run.children]
    records = [r for m in drained for r in m["records"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    run.device["memory_peak_bytes"] = peak
    trace = finish_tracing(tracing) if tracing else None

    run.enter("shutdown", loops_alive=sum(m["loop_alive"] for m in drained))
    final_stats = svc.stats()
    httpd.shutdown()
    httpd.server_close()
    shut = threading.Thread(target=svc.shutdown, daemon=True,
                            kwargs={"wait": True,
                                    "timeout": SHUTDOWN_CAP_S})
    shut.start()
    shut.join(SHUTDOWN_CAP_S)
    jax.clear_caches()
    shutil.rmtree(store, ignore_errors=True)

    run.enter("comparison", shutdown_clean=not shut.is_alive())
    per_child = max(1, traffic["compare_max_rows"] // len(run.children))
    for c in run.children:
        c.tell({"cmd": "compare", "max_rows": per_child,
                "control": args.control})
    compared = [c.hear("compared", time.monotonic() + COMPARE_CAP_S)
                for c in run.children]

    run.enter("reduce", reference_s=max(m["seconds"] for m in compared))
    if trace:
        emit({"trace_events": trace["n_events"],
              "trace_read_s": trace["read_s"],
              "trace_stop_s": trace["stop_s"]})
    t = tally(records, t_start, t_end, window_s)
    run.attempted, run.failed = t["attempted"], t["failed"]
    in_window, values = t["in_window"], dict(t["values"], setup_s=setup_s)
    ctx = {"window_s": window_s,
           "before": before, "after": after, "requests": in_window,
           "acks_ms": t["acks_ms"], "trace": trace,
           "compiles_in_window": compiles.between(t_start, t_end)}
    d_stats = {k: after["stats"].get(k, 0) - before["stats"].get(k, 0)
               for k in ("submitted", "completed", "failed", "rejected",
                         "cache_hits", "batches", "batch_rows",
                         "degraded_batches", "fastpath_requests",
                         "journal_appends", "journal_errors",
                         "journal_group_commits")}
    correct, compared_out = decide(compared, records, d_stats)
    for m in compared:
        for ex in m["examples"]:
            sys.stderr.write(f"mismatch: {json.dumps(ex)}\n")

    metrics = {}
    which = layer if args.trace else e2e
    for m in which:
        if args.trace:
            reader = mf.load_module(root, "layer_metrics", m["name"])
            v = reader.read(ctx)
        else:
            v = values.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    extra = {"window": {
        "requests_completed": len(in_window),
        "histories_completed": sum(r["n"] for r in in_window),
        "rows_due": sum(m["rows_due"] for m in compared),
        "reference_invalid": sum(m["reference_invalid"] for m in compared),
        "statuses": dict(collections.Counter(
            r["status"] for r in t["sent"])),
        "compiles_in_window": ctx["compiles_in_window"],
        "cache_misses_in_window": compiles.misses_between(t_start, t_end),
        "compile_s_in_window": compiles.seconds_between(t_start, t_end),
        "programs_in_window": compiles.names_between(t_start, t_end),
        "counters": d_stats,
        "decided_tier": tier_rows(ctx),
        "queue_depth_end": final_stats.get("queue_depth"),
        "hist_per_s_completed_only": sum(
            r["n"] for r in in_window) / window_s,
        "drained_p50_ms": t["drained_p50_ms"],
        "end_to_end": values}}
    if args.control:
        extra["control"] = args.control
    device = dict(run.device)
    breakdown = None
    if trace and trace["busy_s"]:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {"device_ops": trace["device_ops"][:10],
                     "idle_gaps": trace["idle_gaps"][:10]}
    return result_line(correct, run.attempted, run.failed, metrics, device,
                       compared_out, breakdown, extra)


def tally(records, t_start, t_end, window_s) -> dict:
    """What the clients' records say of the window. `attempted`: the
    requests sent in it. `failed`: those of them that were refused,
    ended other than `done`, came back with an undecided row, or were
    not terminal when the drain's cap ended. The latencies are those of
    all the requests whose verdict arrived inside the window."""
    in_window = [r for r in records if r["status"] == "done"
                 and t_start <= r["t_done"] <= t_end]
    sent = [r for r in records if t_start <= r["t_submit"] <= t_end]
    bad = [r for r in sent if r["status"] != "done" or r["undecided"]]
    values = {}
    if in_window:
        latencies = [(r["t_done"] - r["t_submit"]) * 1e3 for r in in_window]
        values["hist_per_s"] = histories_in_window(
            records, t_start, t_end) / window_s
        values["verdict_p50_ms"] = statistics.median(latencies)
    # the requests that the window's end found in flight: where their
    # latency is the window's own, the drain served them no faster
    drained = [(r["t_done"] - r["t_submit"]) * 1e3 for r in sent
               if r["status"] == "done" and r["t_done"] > t_end]
    return {"attempted": len(sent), "failed": len(bad),
            "in_window": in_window, "sent": sent, "values": values,
            "drained_p50_ms": statistics.median(drained) if drained
            else None,
            "acks_ms": [(r["t_ack"] - r["t_submit"]) * 1e3
                        for r in in_window if r["t_ack"] is not None]}


def decide(compared, records, d_stats) -> tuple:
    """`correct`, and every number it rests on beside its limit. The
    verdicts are compared exactly, so the limit is 0; the other numbers
    hold the run to the guarantees the configuration states, as far as
    a run can show them: no answer from the result cache (it would be a
    verdict nobody checked), none degraded to the CPU, no journal
    append that failed, no acknowledgement without its WAL record."""
    checks = {
        "verdict_mismatches": sum(m["mismatches"] for m in compared),
        "cached_answers": sum(r["cached"] for r in records)
        + d_stats["cache_hits"],
        "degraded_answers": sum(r["degraded"] for r in records)
        + d_stats["degraded_batches"],
        "journal_errors": d_stats["journal_errors"],
        "acks_without_wal_record": max(
            0, d_stats["submitted"] - d_stats["journal_appends"]),
    }
    rows = sum(m["rows_compared"] for m in compared)
    out = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    out["rows_compared"] = {"value": rows, "at_least": 1}
    return rows >= 1 and not any(checks.values()), out


def histories_in_window(records, t_start, t_end) -> float:
    """Histories' worth of checking done inside the window: each
    request that ended `done` counts its histories by the share of its
    life, from `submit` to the verdict in hand, that lies inside the
    window. Counting a request only at the instant it completes would
    count in whole launches: the 256 rows in flight come back together
    every few seconds, a tenth of a window's work at a time."""
    total = 0.0
    for r in records:
        if r["status"] != "done" or r["undecided"]:
            continue
        inside = min(r["t_done"], t_end) - max(r["t_submit"], t_start)
        if inside > 0:
            total += r["n"] * inside / (r["t_done"] - r["t_submit"])
    return total


def start_profiler(directory) -> None:
    """Device operations and the host's own spans; no Python frames."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def stop_profiler():
    """Stop the trace and hand it over as `ProfileData`. The trace of
    one launch is some 2 M device events, about 200 MB as a file, and
    nothing but this process reads it. It is taken from the session in
    memory: `jax.profiler.stop_trace()` writes the file first, which
    took 61 to 150 s and more where this takes 30 to 53 s, and that is
    what keeps a traced run under its deadline (PERF.md, section 7). A
    JAX without this session object fails the traced run loudly."""
    from jax._src import profiler as internal
    from jax.profiler import ProfileData

    state = internal._profile_state
    with state.lock:
        xspace = state.profile_session.stop()
        state.reset()
    return ProfileData.from_serialized_xspace(xspace)


def start_tracing(svc, trace_dir, t_start, t_end, traffic) -> dict:
    """Trace from one launch's completion to the next, so that the span
    holds one whole cycle of the loop (scan, linger, launch, demux) and
    with it one launch's device work, however short against the cycle;
    `trace_seconds` caps the wait for either. The profiler records some
    2 M device events a launch and needs a minute or more to hand them
    over, so the span lies early in the window, and the work runs on a
    thread of its own: the window's clock does not wait for it."""
    cap = float(traffic["trace_seconds"])
    state = {"dir": trace_dir, "sampler": Sampler(svc)}

    def await_batch(until) -> None:
        seen = svc.stats()["batches"]
        while time.monotonic() < until and svc.stats()["batches"] == seen:
            time.sleep(0.02)

    def body():
        time.sleep(max(0.0, t_start - time.monotonic()))
        await_batch(min(t_end, time.monotonic() + cap))
        state["before"] = read_counters(svc)
        state["sampler"].start()
        state["t0"], state["t0_ns"] = time.monotonic(), time.time_ns()
        start_profiler(trace_dir)
        await_batch(min(t_end, time.monotonic() + cap))
        time.sleep(0.05)
        state["after"] = read_counters(svc)
        state["t1"] = time.monotonic()
        state["data"] = stop_profiler()
        state["stop_s"] = time.monotonic() - state["t1"]
        state["sampler"].stop_flag.set()

    state["thread"] = threading.Thread(target=body, daemon=True)
    state["thread"].start()
    return state


def finish_tracing(state) -> dict:
    from benchmarks import trace_reduce

    # writing the trace out can take minutes; the run's own deadline
    # bounds the wait
    state["thread"].join(max(1.0, T0 + DEADLINE_S - 45.0 - time.monotonic()))
    shutil.rmtree(state["dir"], ignore_errors=True)
    if state.get("data") is None:
        return None
    t_read = time.monotonic()
    events, first_ns = trace_reduce.device_events(state.pop("data"))
    span_s = state["t1"] - state["t0"]
    # the span on the events' own clock: from the earliest timestamp of
    # the trace, so that the idle time before the first operation and
    # after the last counts among the gaps
    span_ns = None if first_ns is None else (
        first_ns, first_ns + int(span_s * 1e9))
    out = trace_reduce.reduce(events, span_s, span_ns=span_ns)
    sampler = state["sampler"]
    # the sampler reads the epoch clock; the trace's clock starts
    # elsewhere, so both are laid on the moment the trace began
    samples = [(t - state["t0_ns"] + (first_ns or 0), row)
               for t, row in sampler.samples]
    out["idle_gaps"] = trace_reduce.name_gaps(
        out.pop("gaps"), samples, sampler.period_s)
    out["read_s"] = time.monotonic() - t_read
    out["stop_s"] = state["stop_s"]
    rows = tier_rows(state)
    out["kernel_rows"] = sum(rows.get(t, 0)
                             for t in ("dense", "mask", "sort"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="shrink the shapes for a walk-through on the CPU; "
                         "a rehearsal always ends correct=false")
    ap.add_argument("--control", default=None,
                    help="put this control (a file of references/) in "
                         "the program's place: its verdicts on the "
                         "window's rows are compared instead of the served "
                         "ones, and the run has to end correct=false; "
                         "never used by the driver")
    ap.add_argument("--root", default=str(ROOT), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    args.gate = not args.rehearse
    run = Run()
    timer = start_deadline(run, DEADLINE_S)
    try:
        line = run_cell(args, run)
    except (NoChip, NoProgram) as e:
        # no accelerator, or nothing to measure: no result line at all
        run.stop_children()
        sys.stderr.write(f"{type(e).__name__}: {e}\n")
        return 2
    except Exception as e:
        import traceback

        traceback.print_exc()
        run.stop_children()
        emit({"phase": run.phase, "error": f"{type(e).__name__}: {e}"[:500]})
        emit(result_line(False, run.attempted, max(run.failed, 1), {},
                         run.device, {}, extra={"failed_phase": run.phase}))
        return 1
    finally:
        timer.cancel()
    run.stop_children()
    run.enter("done")
    emit({"phase": "total", "seconds": time.monotonic() - T0})
    if args.rehearse:
        line["correct"] = False
        line["rehearsal"] = True
        line["compared"] = line.pop("compared")  # stays the last key
    for name, row in line["compared"].items():
        sys.stderr.write(f"compared {name}: {json.dumps(row)}\n")
    emit(line)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
