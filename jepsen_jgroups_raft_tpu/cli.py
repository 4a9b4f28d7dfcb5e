"""Command-line entry point: run tests, browse results.

Equivalent of the reference's CLI layer (src/jepsen/jgroups/raft.clj:94-101
wiring jepsen.cli/run! with single-test-cmd + serve-cmd):

  python -m jepsen_jgroups_raft_tpu test  [flags]   — compose + run a test
  python -m jepsen_jgroups_raft_tpu serve [flags]   — results web server

Flags mirror the reference's cli-opts (raft.clj:14-51) plus the jepsen
built-ins the docs exercise (--node/--nodes-file, --concurrency,
--time-limit, --test-count; doc/running.md:88,152). The state machine is
selected from the workload exactly like identify-state-machine
(server.clj:103-109). Exit status is 0 iff every run's history verified
(jepsen.cli behavior: a failed analysis fails the command).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .core.compose import DEFAULTS, compose_test
from .core.runner import run_test
from .nemesis.package import FAULTS, SCHEDULES, SPECIALS
from .workload import WORKLOADS

# workload → native state machine (identify-state-machine, server.clj:103-109)
# The scenario tier's set/queue live in one register of the replicated
# map (CAS retry loops — workload/set.py, workload/queue.py), so they
# ride the "map" SM on every deployment tier.
WORKLOAD_SM = {
    "single-register": "map",
    "multi-register": "map",
    "counter": "counter",
    "election": "election",
    "set": "map",
    "queue": "map",
}


def _add_test_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--workload", "-w", default=DEFAULTS["workload"],
                   choices=sorted(WORKLOADS),
                   help="workload name (raft.clj:29-33)")
    p.add_argument("--nemesis", default=None,
                   help="comma-separated faults %s, workload-paired "
                        "schedules %s, or special %s "
                        "(raft.clj:35-39, nemesis.clj:8-29); set/queue "
                        "default to their paired schedule when omitted"
                        % (sorted(FAULTS), sorted(SCHEDULES),
                           sorted(SPECIALS)))
    p.add_argument("--rate", type=float, default=DEFAULTS["rate"],
                   help="approximate ops/sec (raft.clj:19-22)")
    p.add_argument("--ops-per-key", type=int, default=DEFAULTS["ops_per_key"],
                   help="op cap per key (raft.clj:24-27)")
    p.add_argument("--interval", type=float, default=DEFAULTS["interval"],
                   help="seconds between nemesis ops (raft.clj:41-44)")
    p.add_argument("--operation-timeout", type=float,
                   default=DEFAULTS["operation_timeout"],
                   help="client op timeout, seconds (raft.clj:48-51)")
    p.add_argument("--stale-reads", action="store_true",
                   help="allow dirty local reads (raft.clj:14-17; "
                        "quorum_reads = not stale_reads, raft.clj:92)")
    p.add_argument("--weak-election", action="store_true",
                   help="election workload: drop back to the reference-"
                        "parity single-client model (leader.clj:58-62) "
                        "instead of the default cross-node majority "
                        "checker")
    p.add_argument("--time-limit", type=float, default=DEFAULTS["time_limit"],
                   help="main-phase duration, seconds")
    p.add_argument("--quiesce", type=float, default=DEFAULTS["quiesce"],
                   help="post-phase quiet period, seconds (raft.clj:86-90's "
                        "sleep 10)")
    p.add_argument("--concurrency", type=int, default=DEFAULTS["concurrency"],
                   help="client worker count")
    p.add_argument("--test-count", type=int, default=1,
                   help="number of runs")
    p.add_argument("--node", action="append", default=None,
                   help="node name (repeatable)")
    p.add_argument("--nodes-file", default=None,
                   help="file with one node name per line")
    p.add_argument("--store", default="store",
                   help="results directory root")
    p.add_argument("--algorithm", default="auto",
                   choices=["auto", "jax", "cpu", "dfs", "race"],
                   help="linearizability engine (:algorithm :jax analogue; "
                        "race = kernel vs DFS, first finisher wins, the "
                        "knossos.competition analogue)")
    p.add_argument("--consistency", default="linearizable",
                   choices=["linearizable", "sequential", "session"],
                   help="consistency ladder rung for the workload's "
                        "frontier checker (checker/consistency.py): "
                        "weaker rungs drop real-time edges, keep "
                        "per-process order, and decide measurably "
                        "cheaper")
    p.add_argument("--platform", default=None,
                   choices=["cpu", "tpu"],
                   help="pin the JAX backend for checking (e.g. cpu when "
                        "no accelerator is reachable); default: JAX's "
                        "platform autodetection")
    p.add_argument("--deploy", default="local",
                   choices=["local", "inmemory", "ssh"],
                   help="SUT deployment tier: local native processes, "
                        "in-process fake, or ssh remote hosts")
    p.add_argument("--ssh-user", default="root")
    p.add_argument("--ssh-private-key", default=None,
                   help="identity file for the ssh tier (running.md:88)")
    p.add_argument("--election-ms", type=int, default=300)
    p.add_argument("--heartbeat-ms", type=int, default=100)
    p.add_argument("--repl-timeout-ms", type=int, default=30000,
                   help="server-side replication timeout "
                        "(server/src/jgroups/raft/server.clj:37)")
    p.add_argument("--compact-every", type=int, default=0,
                   help="server snapshots + compacts its log after this "
                        "many applied entries (0 = off); lagging/new "
                        "members catch up via InstallSnapshot")


def _nodes_from(args) -> list:
    if args.node:
        return list(args.node)
    if args.nodes_file:
        lines = Path(args.nodes_file).read_text().splitlines()
        return [ln.strip() for ln in lines if ln.strip()]
    return [f"n{i}" for i in range(1, 6)]


def _build_deployment(args, nodes):
    """Returns (db, net, conn_factory, shutdown_fn)."""
    sm = WORKLOAD_SM[args.workload]
    if args.deploy == "inmemory":
        from .core.db import InMemoryDB, InMemoryNet
        from .sut.inmemory import InMemoryCluster, LatencyPlan
        cluster = InMemoryCluster(nodes, LatencyPlan())
        return (InMemoryDB(cluster), InMemoryNet(cluster), cluster.conn,
                cluster.shutdown)
    if args.deploy == "ssh":
        from .deploy.ssh import RemoteRaftCluster, RemoteRaftDB, IptablesNet
        cluster = RemoteRaftCluster(
            nodes, sm=sm, ssh_user=args.ssh_user,
            ssh_key=args.ssh_private_key,
            election_ms=args.election_ms, heartbeat_ms=args.heartbeat_ms,
            repl_timeout_ms=args.repl_timeout_ms,
            compact_every=args.compact_every)
        return (RemoteRaftDB(cluster), IptablesNet(cluster),
                cluster.conn_factory(), cluster.shutdown)
    from .deploy.local import BlockNet, LocalCluster, LocalRaftDB
    cluster = LocalCluster(
        nodes, sm=sm, election_ms=args.election_ms,
        heartbeat_ms=args.heartbeat_ms,
        repl_timeout_ms=args.repl_timeout_ms,
        compact_every=args.compact_every)
    return (LocalRaftDB(cluster), BlockNet(cluster), cluster.conn_factory(),
            cluster.shutdown)


def cmd_test(args) -> int:
    if args.platform:
        # Must land before the first backend initialization (the checker's
        # first device use); config update after `import jax` is fine.
        import jax
        jax.config.update("jax_platforms", args.platform)
    nodes = _nodes_from(args)
    ok = True
    for i in range(args.test_count):
        db, net, conn_factory, shutdown = _build_deployment(args, nodes)
        opts = {
            "nodes": nodes,
            "workload": args.workload,
            "nemesis": args.nemesis,
            "rate": args.rate,
            "ops_per_key": args.ops_per_key,
            "interval": args.interval,
            "operation_timeout": args.operation_timeout,
            "stale_reads": args.stale_reads,
            "time_limit": args.time_limit,
            "quiesce": args.quiesce,
            "concurrency": args.concurrency,
            "conn_factory": conn_factory,
            "store_root": args.store,
            "algorithm": args.algorithm,
            "consistency": args.consistency,
        }
        if args.workload == "election":
            # Default-on majority model: wired whenever the deployment
            # can snapshot every node's view (local + ssh clusters can);
            # --weak-election drops back to reference parity.
            opts["weak_election"] = args.weak_election
            probe = getattr(db, "cluster", None)
            probe = getattr(probe, "views_probe", None)
            if probe is not None:
                opts["views_probe"] = probe
        test = compose_test(opts, db=db, net=net)
        try:
            test = run_test(test)
        finally:
            shutdown()
        res = test["results"]
        # Strict: "unknown" (checker budget exceeded / checker crashed) is
        # NOT a pass — jepsen's CLI likewise fails the command on any
        # non-true analysis.
        verdict = res.get("valid?")
        valid = verdict is True
        ok = ok and valid
        label = {True: "VALID", False: "INVALID"}.get(verdict,
                                                      f"UNKNOWN ({verdict})")
        print(f"run {i + 1}/{args.test_count}: {label}  "
              f"store={test.get('store_dir')}")
        if not valid:
            print(json.dumps(res, indent=2, default=str)[:4000])
    # Everything looks good! ヽ('ー`)ノ — or not.
    print("Everything looks good!" if ok else "Analysis invalid! (ノಥ益ಥ)ノ")
    return 0 if ok else 1


def cmd_serve(args) -> int:
    from .core.serve import serve
    return serve(args.store, host=args.host, port=args.port)


def cmd_serve_checker(args) -> int:
    """graftd: the always-on multi-tenant checking daemon (service/) —
    queued admission, cross-request batching over the chunked scan,
    degrade-to-CPU resilience. Trace records land in the same store/
    layout the `serve` browser reads."""
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    from .service.http import serve_checker
    return serve_checker(store_root=args.store, host=args.host,
                         port=args.port, queue_capacity=args.queue,
                         batch_wait=(args.batch_wait_ms / 1000.0
                                     if args.batch_wait_ms is not None
                                     else None),
                         n_workers=args.workers,
                         cluster_dir=args.cluster_dir,
                         replica_id=args.replica_id)


def cmd_check(args) -> int:
    """Re-verify recorded runs: store → load → per-key split → one
    on-device batch (BASELINE config #3's shape). Accepts run dirs or
    store roots (every run dir beneath them)."""
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    run_dirs = []
    for p in args.paths:
        p = Path(p)
        if (p / "history.jsonl").exists():
            run_dirs.append(p)
        else:
            run_dirs.extend(sorted(
                d.parent for d in p.glob("**/history.jsonl")
                if not d.parent.name == "latest"))
    if not run_dirs:
        print("no run dirs (history.jsonl) found", file=sys.stderr)
        return 2
    from .checker.recorded import check_recorded
    summary = check_recorded(run_dirs, workload=args.workload,
                             algorithm=args.algorithm)
    print(json.dumps(summary, indent=2, default=str))
    return 0 if summary["valid?"] is True else 1


def cmd_search(args) -> int:
    """graftsearch (ISSUE 20): coverage-guided scenario search. Default
    mode runs the open-ended generation loop and prints the run report;
    --recall K plants K known violations first and reports
    found-vs-missed per CPU-minute."""
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    from .search.driver import SearchDriver, search_config_from_env
    from .search.recall import run_recall

    overrides = {}
    for flag, key in (("population", "population"),
                      ("generations", "generations"),
                      ("survivors", "survivors"),
                      ("edit_space", "edit_space"),
                      ("seed", "seed"),
                      ("corpus_dir", "corpus_dir")):
        v = getattr(args, flag)
        if v is not None:
            overrides[key] = v
    if args.arm is not None:
        overrides["guided"] = args.arm == "guided"
    overrides["families"] = tuple(
        f.strip() for f in args.families.split(",") if f.strip())
    overrides["consistency"] = args.consistency
    overrides["n_ops"] = args.n_ops
    if args.service_url:
        overrides["service_url"] = args.service_url
    cfg = search_config_from_env(**overrides)
    if args.recall:
        rep = run_recall(cfg, k=args.recall).to_dict()
    else:
        rep = SearchDriver(cfg).run()
    print(json.dumps(rep, indent=2, default=str))
    return 0


def main(argv=None) -> int:
    from .platform import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="jepsen_jgroups_raft_tpu",
        description="TPU-native distributed-systems test harness")
    sub = ap.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("test", help="run a test (single-test-cmd analogue)")
    _add_test_flags(t)
    t.set_defaults(fn=cmd_test)
    s = sub.add_parser("serve", help="results web server (serve-cmd)")
    s.add_argument("--store", default="store")
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--port", type=int, default=8080)
    s.set_defaults(fn=cmd_serve)
    sc = sub.add_parser("serve-checker",
                        help="graftd: always-on multi-tenant checking "
                             "daemon (HTTP+JSON, cross-request batching)")
    sc.add_argument("--store", default="store",
                    help="trace-record root (browsable via `serve`)")
    sc.add_argument("--host", default="0.0.0.0")
    sc.add_argument("--port", type=int, default=8091)
    sc.add_argument("--queue", type=int, default=None,
                    help="admission queue capacity "
                         "(default: JGRAFT_SERVICE_QUEUE or 64)")
    sc.add_argument("--batch-wait-ms", type=int, default=None,
                    help="batch-formation linger window, from the "
                         "admission of a batch's oldest request "
                         "(default: JGRAFT_SERVICE_BATCH_WAIT_MS or 50)")
    sc.add_argument("--workers", type=int, default=None,
                    help="worker shards — one per host/device group; "
                         "batches route to the least-loaded shard "
                         "(default: JGRAFT_SERVICE_WORKERS or 1)")
    sc.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="pin the JAX backend for checking")
    sc.add_argument("--cluster-dir", default=None,
                    help="shared cluster directory (result store + "
                         "leases + per-replica journals; default: "
                         "JGRAFT_SERVICE_CLUSTER_DIR or single-replica)")
    sc.add_argument("--replica-id", default=None,
                    help="stable replica identity inside the cluster "
                         "dir (default: JGRAFT_SERVICE_REPLICA_ID; keep "
                         "it stable across restarts so the replica "
                         "replays its own journal)")
    sc.set_defaults(fn=cmd_serve_checker)
    se = sub.add_parser(
        "search",
        help="graftsearch: coverage-guided scenario search over graftd "
             "(mutation registry + verdict-signal fitness + minimized "
             "corpus under store/search/)")
    se.add_argument("--families",
                    default="register,set,queue,list-append",
                    help="comma-separated model families to search")
    se.add_argument("--population", type=int, default=None,
                    help="candidates per generation "
                         "(default: JGRAFT_SEARCH_POP or 48)")
    se.add_argument("--generations", type=int, default=None,
                    help="default: JGRAFT_SEARCH_GENERATIONS or 8")
    se.add_argument("--survivors", type=int, default=None,
                    help="survivor pool size "
                         "(default: JGRAFT_SEARCH_SURVIVORS or 12)")
    se.add_argument("--edit-space", type=int, default=None,
                    help="mutation edit-seed space "
                         "(default: JGRAFT_SEARCH_EDIT_SPACE or 24)")
    se.add_argument("--seed", type=int, default=None,
                    help="run seed (default: JGRAFT_SEARCH_SEED or 0); "
                         "same seed => identical corpus fingerprints")
    se.add_argument("--corpus-dir", default=None,
                    help="corpus root (default: JGRAFT_SEARCH_DIR or "
                         "store/search)")
    se.add_argument("--arm", choices=["guided", "random"], default=None,
                    help="override JGRAFT_SEARCH_GUIDED (random = the "
                         "blind-mutation ablation arm)")
    se.add_argument("--consistency", default="linearizable")
    se.add_argument("--n-ops", type=int, default=20,
                    help="base-history length per scenario")
    se.add_argument("--recall", type=int, default=None, metavar="K",
                    help="plant K known violations and report recall "
                         "per CPU-minute instead of open-ended search")
    se.add_argument("--service-url", default=None,
                    help="evaluate through a running graftd daemon "
                         "(binary frames for non-transactional "
                         "workloads); default: in-process service")
    se.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    se.set_defaults(fn=cmd_search)
    c = sub.add_parser("check",
                       help="re-verify recorded runs as one device batch")
    c.add_argument("paths", nargs="+",
                   help="run dirs or store roots to load")
    c.add_argument("--workload", default=None, choices=sorted(WORKLOADS),
                   help="override the workload recorded in test.json")
    c.add_argument("--algorithm", default="auto",
                   choices=["auto", "jax", "cpu", "dfs", "race"])
    c.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    c.set_defaults(fn=cmd_check)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
