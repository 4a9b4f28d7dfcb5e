"""One load-generating child. It never touches the chip
(JAX_PLATFORMS=cpu in its environment), so the clients' work does not
share the server's interpreter lock.

It makes its share of the request pool from the seed, drives its
clients through the mix's loop, and, once the window has closed and the
server is gone, runs the plain reference over the histories whose
verdicts came back inside the window. The parent talks to it in JSON
lines: commands on stdin, answers on stdout.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as mf  # noqa: E402


_SAY_LOCK = threading.Lock()


def say(obj) -> None:
    with _SAY_LOCK:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()


class Pool:
    """The child's requests, each sent at most once."""

    def __init__(self, requests, first):
        self.requests = requests
        self.first = first
        self._next = 0
        self._lock = threading.Lock()

    def take(self):
        with self._lock:
            k = self._next
            if k >= len(self.requests):
                return None
            self._next += 1
        return self.first + k, self.requests[k]

    def histories(self, i):
        return self.requests[i - self.first]


def check_no_repeats(requests) -> None:
    """A history sent twice is answered from the result cache without
    being checked, so the generator refuses to build such a pool."""
    seen = set()
    for req in requests:
        for h in req:
            key = hash(tuple(h))
            if key in seen:
                raise ValueError("the pool holds the same history twice")
            seen.add(key)


def make_pool(generator, rng, config, traffic, n_requests, first_request,
              n_clients) -> tuple:
    """The child's requests in the order they are sent, and how many of
    them are the warm-up's. The warm-up sends `warmup_requests_per_client`
    requests of the mix's own size from every client, and then its
    sweep: one request of each size in `warmup_sweep` from every
    client, so that the launches walk down the row buckets that
    recompaction steps through in the window, and those programs are
    built or loaded before it opens."""
    requests = generator.make_requests(rng, config, traffic, n_requests,
                                       first_request)
    at = n_clients * int(traffic["warmup_requests_per_client"])
    sweep = [int(s) for s in traffic.get("warmup_sweep", [])]
    for size in reversed(sweep):
        requests[at:at] = generator.make_requests(
            rng, config, dict(traffic, histories_per_request=size,
                              planted_every=0), n_clients, 0)
    check_no_repeats(requests)
    return requests, at + n_clients * len(sweep)


def verdict_rows(rec) -> list:
    ans = rec.get("answer") or {}
    return [r.get("valid?") for r in ans.get("results") or []]


def summarise(rec) -> dict:
    """What the parent needs of one request; the answer stays here."""
    ans = rec.get("answer") or {}
    results = ans.get("results") or []
    stats = ans.get("service-stats") or {}
    return {
        "i": rec["i"], "n": rec.get("n", 0), "status": rec["status"],
        "t_submit": rec["t_submit"], "t_ack": rec.get("t_ack"),
        "t_done": rec.get("t_done"), "error": rec.get("error"),
        "undecided": sum(1 for r in results
                         if r.get("valid?") not in (True, False)),
        "cached": bool(ans.get("cached")),
        "degraded": bool(stats.get("degraded")) or any(
            "platform-degraded" in r for r in results),
    }


def main() -> int:
    spec = json.loads(sys.argv[1])
    root = Path(spec["root"])
    config, traffic = spec["config"], spec["traffic"]
    t0 = time.monotonic()
    generator = mf.load_module(root, "generators", config["generator"])
    rng = random.Random(mf.derive_seed(spec["seed"], "pool", spec["child"]))
    requests, n_warm = make_pool(generator, rng, config, traffic,
                                 spec["n_requests"], spec["first_request"],
                                 spec["n_clients"])
    pool = Pool(requests, spec["first_request"])
    loop = mf.load_module(root, "loops", traffic["loop"])
    wire = mf.load_module(root, "wires", traffic["wire"])
    from jepsen_jgroups_raft_tpu.service.client import ServiceClient

    say({"kind": "ready", "child": spec["child"], "requests": len(requests),
         "seconds": time.monotonic() - t0})

    records: list = []
    rec_lock = threading.Lock()
    control = loop.Control()
    warm_left = [n_warm]
    loop_thread = None
    recs: list = []
    t_start = t_end = 0.0
    consistency = config["consistency"]

    def on_record(rec):
        with rec_lock:
            records.append(rec)
            warm_left[0] -= 1
            if warm_left[0] == 0:
                say({"kind": "warm", "child": spec["child"]})

    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "start":
            url = cmd["url"]
            loop_thread = threading.Thread(
                target=loop.run, daemon=True, kwargs=dict(
                    n_clients=spec["n_clients"],
                    make_client=lambda: ServiceClient(url, timeout=60.0),
                    send=lambda cl, hs: wire.send(
                        cl, hs, config["service_workload"], consistency),
                    take_request=pool.take, control=control,
                    on_record=on_record))
            loop_thread.start()
        elif cmd["cmd"] == "window":
            control.drain_until = cmd["drain_until"]
            control.t_end = cmd["t_end"]
            t_start, t_end = cmd["t_start"], cmd["t_end"]
            loop_thread.join(max(0.0, cmd["drain_until"] - time.monotonic())
                             + 10.0)
            control.abort.set()
            with rec_lock:
                recs = list(records)
            say({"kind": "drained", "child": spec["child"],
                 "loop_alive": loop_thread.is_alive(),
                 "records": [summarise(r) for r in recs
                             if r.get("t_done", t_end) >= t_start]})
        elif cmd["cmd"] == "compare":
            due = [r for r in recs if r["status"] == "done"
                   and t_start <= r["t_done"] <= t_end]
            say(dict(compare(root, config, spec, pool, due, cmd),
                     kind="compared", child=spec["child"]))
        elif cmd["cmd"] == "exit":
            break
    return 0


def compare(root, config, spec, pool, due, cmd) -> dict:
    """Run the plain reference over the histories whose verdicts the
    window returned (all of them, or a seeded sample of `max_rows`),
    and count the verdicts that differ. With a control, the control's
    verdict on each of those rows stands in the served verdict's place:
    such a run has to come out as not correct."""
    from benchmarks.references import frontier

    model = mf.load_module(root, "references", config["reference"])
    control = (mf.load_module(root, "references", cmd["control"])
               if cmd.get("control") else None)
    rows = [(r["i"], k, v) for r in due
            for k, v in enumerate(verdict_rows(r))]
    if len(rows) > cmd["max_rows"]:
        rng = random.Random(mf.derive_seed(spec["seed"], "sample",
                                           spec["child"]))
        rows = rng.sample(rows, cmd["max_rows"])
    t0 = time.monotonic()
    mismatches, invalid, undecided = [], 0, 0
    for i, k, verdict in rows:
        if verdict is not True and verdict is not False:
            undecided += 1  # `unknown` is no verdict: counted as failed
            continue
        history = pool.histories(i)[k]
        if control is not None:
            verdict = control.linearizable(history, model)
        want = frontier.linearizable(history, model)
        invalid += not want
        if verdict is not want:
            mismatches.append({"request": i, "history": k,
                               "served": verdict, "reference": want})
    return {"rows_due": sum(len(verdict_rows(r)) for r in due),
            "rows_compared": len(rows) - undecided,
            "reference_invalid": invalid,
            "mismatches": len(mismatches), "examples": mismatches[:5],
            "seconds": time.monotonic() - t0}


if __name__ == "__main__":
    try:
        code = main()
    except BrokenPipeError:
        code = 1
    sys.stdout.flush()
    os._exit(code)
