"""The `register-partition-1k` deployment of BENCHMARK.json (ISSUE 40),
held on the CPU at a size a test can hold: its generator
(`benchmarks/generators/partition.py`: histories that are linearizable
by construction, timeouts only inside a cut and under the two caps),
the plain reference against the program through the library and through
graftd's served binary lane on windows on BOTH sides of 10, the tier
that decides each window, and the tracing that came with the cell:
span `launch.escalate` and the `/stats` counters `wide_rows` and
`wide_rows_host`."""

import collections
import json
import random
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tests" / "benchmark_harness"))

from benchmarks import manifest as mf  # noqa: E402
from benchmarks.generators import partition  # noqa: E402
from benchmarks.references import frontier  # noqa: E402
from util_bench import example_ctx, still_ctx  # noqa: E402

from jepsen_jgroups_raft_tpu.checker import schedule  # noqa: E402
from jepsen_jgroups_raft_tpu.checker.linearizable import (  # noqa: E402
    check_encoded, check_histories)
from jepsen_jgroups_raft_tpu.checker.schedule import (  # noqa: E402
    snapshot_spans, snapshot_stats)
from jepsen_jgroups_raft_tpu.history.packing import encode_history  # noqa: E402
from jepsen_jgroups_raft_tpu.history.synth import build_history  # noqa: E402
from jepsen_jgroups_raft_tpu.models import CasRegister  # noqa: E402
from jepsen_jgroups_raft_tpu.ops.kernel_ir import (  # noqa: E402
    DENSE_MAX_SLOTS, WIDE_WINDOW_SLOTS)
from jepsen_jgroups_raft_tpu.service.daemon import CheckingService  # noqa: E402

CELL = "register-partition-1k.campaign-wide"
MANIFEST = mf.load_manifest(ROOT)
_, CONFIG, TRAFFIC = mf.cell(ROOT, MANIFEST, CELL)
REF = mf.load_module(ROOT, "references", CONFIG["reference"])
CONTROL = mf.load_module(ROOT, "references", CONFIG["control"])
WAIT_S = 300.0
#: the configuration's own caps, values, rate and minority, at a length
#: and a nemesis interval a test can hold: two cuts inside 180 ops
SMALL = dict(CONFIG, ops_per_history=180, nemesis_interval_s=0.7,
             operation_timeout_s=0.15)
#: slower majority-side ops: more of the five threads pending at once,
#: so that windows reach 12 and 13 at this length
SLOW = dict(SMALL, op_latency_ms=[20, 60])
MIX = {"histories_per_request": 4, "perturbed_share": 0.25,
       "planted_every": 3}
COUNTERS = ("wide_rows", "wide_rows_host")


def window(rows) -> int:
    return encode_history(build_history(rows), CasRegister()).n_slots


# ------------------------------------------------------------ generator


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_every_unperturbed_history_is_linearizable(seed):
    rng = random.Random(seed)
    for _ in range(24):
        assert frontier.linearizable(
            partition.partition_rows(rng, SMALL), REF) is True


def test_same_seed_same_requests_other_seed_others():
    def make(seed):
        return partition.make_requests(random.Random(seed), SMALL, MIX, 6, 0)

    a, b, c = make(5), make(5), make(6)
    assert a == b and a != c
    assert all(len(r) == MIX["histories_per_request"] for r in a)
    for i, req in enumerate(a):
        assert sum(h[-1][0] == 10_000 for h in req) == (i % 3 == 0)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_timeouts_fall_inside_cuts_and_under_both_caps(seed):
    """A crashed op is an invocation whose process never completes it:
    its next row, if any, is an `info`, and the thread comes back under
    a fresh process id."""
    rng = random.Random(seed)
    most = 0
    for _ in range(30):
        clock: list = []
        rows = partition.partition_rows(rng, SMALL, clock)
        assert len(clock) == len(rows) and clock == sorted(clock)
        assert sum(r[1] == "invoke" for r in rows) == 180
        last = {}
        for i, (p, typ, _, _) in enumerate(rows):
            if typ == "invoke":
                assert p not in last or rows[last[p]][1] in ("ok", "fail")
            last[p] = i
        open_at = {}
        for i, (p, typ, _, _) in enumerate(rows):
            if typ == "invoke":
                open_at[p] = i
            elif typ in ("ok", "fail"):
                del open_at[p]
        by_cut = collections.Counter()
        for p, i in open_at.items():
            cut = partition.cut_of(clock[i], SMALL["nemesis_interval_s"])
            assert cut >= 0, "a timeout outside a cut"
            by_cut[cut] += 1
        assert sum(by_cut.values()) <= CONFIG["max_crashes"] == 8
        assert max(by_cut.values(), default=0) <= \
            CONFIG["max_crashes_per_cut"] == 4
        most = max(most, sum(by_cut.values()))
    assert most == 8   # the cap is reached, two cuts of four


@pytest.mark.parametrize("cap,crashes,served", [
    (DENSE_MAX_SLOTS, 8, True),    # this tree: 8 + 5 threads = 13
    (10, 8, False),                # the tree ISSUE 40 started from
    (12, 8, False), (13, 9, False),
    (10, 5, True),                 # a cut the older cap would hold
    (10, 1, True)])                # the rehearsal block on any tree
def test_no_pool_for_a_program_that_would_escalate_the_widest_window(
        monkeypatch, cap, crashes, served):
    """The one thing the generator reads of the program: a checkout whose
    device families end under `max_crashes` + the threads fails in its
    pool phase, soon, and not in a window of host escalations."""
    from jepsen_jgroups_raft_tpu.ops import kernel_ir

    monkeypatch.setattr(kernel_ir, "DENSE_MAX_SLOTS", cap)
    config = dict(SMALL, max_crashes=crashes)
    assert partition.widest_window(config) == crashes + 5
    if served:
        assert len(partition.make_requests(
            random.Random(1), config, MIX, 1, 0)) == 1
    else:
        with pytest.raises(partition.WindowNotServed, match="on the host"):
            partition.make_requests(random.Random(1), config, MIX, 1, 0)


def test_the_rehearsal_block_and_the_file_pass_the_gate_on_this_tree():
    assert partition.widest_window(CONFIG) == 13 <= DENSE_MAX_SLOTS
    partition.require_device_window(CONFIG)
    partition.require_device_window({**CONFIG, **CONFIG["rehearsal"]})


@pytest.mark.parametrize("t,cut", [(0.0, -1), (4.99, -1), (5.0, 0),
                                   (9.99, 0), (10.0, -1), (15.0, 1),
                                   (20.0, -1), (27.5, 2)])
def test_cuts_last_from_every_odd_interval_to_the_next_even_one(t, cut):
    assert partition.cut_of(t, CONFIG["nemesis_interval_s"]) == cut


@pytest.mark.parametrize("seed", [5, 2**31 + 11, 3_000_000_019])
def test_a_full_size_history_stays_inside_the_widest_dense_window(seed):
    """The configuration as the cell runs it: 1,000 ops, two cuts, at
    most eight timeouts, so a window of at most eight crashed ops and
    five live ones: the widest the dense domain family holds, and no
    row of the cell is left to the sort ladder."""
    rng = random.Random(seed)
    windows = collections.Counter()
    for _ in range(24):
        rows = partition.partition_rows(rng, CONFIG)
        assert sum(r[1] == "invoke" for r in rows) == 1000
        assert frontier.linearizable(rows, REF) is True
        windows[window(rows)] += 1
    assert max(windows) <= CONFIG["max_crashes"] + CONFIG["processes"] \
        == DENSE_MAX_SLOTS
    assert max(windows) > WIDE_WINDOW_SLOTS   # the cell is wide


def test_the_file_keeps_the_sources_shapes_and_states_its_cuts():
    with open(ROOT / "benchmarks" / "configs" / "register-1k.json") as fh:
        register = json.load(fh)
    for key in ("guarantees", "consistency", "deployment", "reference",
                "control", "service_workload", "history_kind",
                "processes", "ops_per_history", "value_range"):
        assert CONFIG[key] == register[key], key
    assert CONFIG["rate_hz_per_thread"] == 10
    assert CONFIG["nemesis_interval_s"] == 5
    assert CONFIG["generator"] == "partition"
    assert CONFIG["reduced"] == ["max_crashes", "max_crashes_per_cut"]
    assert sorted(CONFIG["assumed"]) == ["op_latency_ms",
                                         "operation_timeout_s", "partition"]
    for key in CONFIG["assumed"] + CONFIG["reduced"]:
        assert CONFIG["why"][key], key
    for part in ("configs[2]", "doc/intro.md:39-41", "raft.clj:14-51"):
        assert part in CONFIG["source"]
    campaign = mf.load_json(ROOT, "traffic", "campaign")
    differ = {k for k in set(campaign) | set(TRAFFIC)
              if campaign.get(k) != TRAFFIC.get(k)}
    assert differ == {"name", "who", "compare_max_rows"}


# -------------------------------------- the reference against the program


@pytest.fixture(scope="module", params=[3, 2**31 + 7])
def seeded(request):
    reqs = partition.make_requests(random.Random(request.param), SLOW,
                                   MIX, 12, 0)
    want = [[frontier.linearizable(h, REF) for h in req] for req in reqs]
    flat = [v for req in want for v in req]
    assert True in flat and False in flat
    windows = [window(h) for req in reqs for h in req]
    assert min(windows) < WIDE_WINDOW_SLOTS < max(windows)
    return reqs, want


def test_reference_agrees_with_the_library_on_both_sides_of_ten(seeded):
    reqs, want = seeded
    hs = [build_history(h) for req in reqs for h in req]
    res = check_histories(hs, CasRegister(), algorithm="auto")
    assert [r["valid?"] for r in res] == [v for req in want for v in req]


def test_reference_agrees_with_the_served_binary_lane(seeded, monkeypatch):
    from jepsen_jgroups_raft_tpu.service import (ServiceClient,
                                                 serve_in_thread)

    monkeypatch.setenv("JGRAFT_LIN_FASTPATH", "1")
    reqs, want = seeded
    wire = mf.load_module(ROOT, "wires", TRAFFIC["wire"])
    svc = CheckingService(store_root=None)
    httpd, port, _ = serve_in_thread(svc)
    try:
        cl = ServiceClient(f"http://127.0.0.1:{port}")
        acks = [wire.send(cl, req, CONFIG["service_workload"],
                          CONFIG["consistency"]) for req in reqs]
        got = []
        for ack in acks:
            rec = cl.result(ack["id"], wait_s=WAIT_S)
            assert rec["status"] == "done", rec
            assert not rec.get("cached")
            got.append([r["valid?"] for r in rec["results"]])
        cl.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown(wait=True)
    assert got == want


@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_control_disagrees_with_the_reference(seed):
    reqs = partition.make_requests(random.Random(seed), SMALL, MIX, 15, 0)
    hs = [h for req in reqs for h in req]
    assert len(hs) == 60
    differ = sum(CONTROL.linearizable(h, REF)
                 is not frontier.linearizable(h, REF) for h in hs)
    assert differ >= 3, differ


@pytest.fixture(scope="module")
def by_window():
    """One unperturbed history a window, from one seeded pool."""
    rng = random.Random(40)
    out = {}
    for _ in range(400):
        rows = partition.partition_rows(rng, SLOW)
        out.setdefault(window(rows), rows)
    return out


@pytest.mark.parametrize("w,tier", [(9, "dense"), (10, "dense"),
                                    (11, "dense"), (12, "dense"),
                                    (13, "host")])
def test_the_tier_that_decides_a_window(by_window, w, tier):
    """Windows 11 to 13 at S 8 are the dense domain family's since
    ISSUE 40 (every such row overflowed the sort ladder's top rung and
    was decided on the host); past MASK_DENSE_MAX_SLOTS `auto` spends
    its first DFS budget before the device pass, and decides this
    row."""
    assert DENSE_MAX_SLOTS == 13
    rows = by_window[w]
    before = snapshot_stats()
    [r] = check_histories([build_history(rows)], CasRegister(),
                          algorithm="auto")
    assert r["valid?"] is True and r["concurrency-window"] == w
    assert r["decided-tier"] == tier
    moved = {k: snapshot_stats()[k] - before[k] for k in COUNTERS}
    assert moved["wide_rows"] == (w > WIDE_WINDOW_SLOTS)
    assert moved["wide_rows_host"] == (tier == "host")


def test_a_window_of_13_that_the_dfs_leaves_is_the_dense_familys(by_window):
    """`jax` spends no DFS budget: the row goes to the device pass as a
    W 13 row that outlasted `auto`'s first budget does (one in twelve
    of the cell's W 13 rows), and the domain family decides it, where
    it took two rungs of the ladder and the host engines before."""
    before = snapshot_stats()
    [r] = check_histories([build_history(by_window[13])], CasRegister(),
                          algorithm="jax")
    assert r["valid?"] is True and r["decided-tier"] == "dense"
    moved = {k: snapshot_stats()[k] - before[k] for k in COUNTERS}
    assert moved == {"wide_rows": 1, "wide_rows_host": 0}


# ------------------------------------------------------------- the tracing


def burst(w, n_ops=6, n_vals=3):
    """Sequential churn, then `w` concurrent completed writes: a
    history whose window is `w` exactly."""
    rows = []
    for i in range(n_ops):
        rows += [(0, "invoke", "write", i % n_vals),
                 (0, "ok", "write", i % n_vals)]
    rows += [(p + 1, "invoke", "write", p % n_vals) for p in range(w)]
    rows += [(p + 1, "ok", "write", p % n_vals) for p in range(w)]
    return rows


def moved_since(before_stats, before_spans):
    st, sp = snapshot_stats(), snapshot_spans()
    zero = {"n": 0, "s": 0.0}
    return ({k: st[k] - before_stats[k] for k in COUNTERS},
            sp.get("launch.escalate", zero)["n"]
            - before_spans.get("launch.escalate", zero)["n"])


def test_counters_and_spans_on_a_batch_of_windows_8_10_and_12():
    model = CasRegister()
    encs = [encode_history(build_history(burst(w)), model)
            for w in (8, 10, 12)]
    assert [e.n_slots for e in encs] == [8, 10, 12]
    # as routed: the dense family takes all three, nothing else runs
    st, sp = snapshot_stats(), snapshot_spans()
    res = check_encoded(encs, model, algorithm="auto")
    assert [r["decided-tier"] for r in res] == ["dense"] * 3
    assert moved_since(st, sp) == ({"wide_rows": 1, "wide_rows_host": 0}, 0)
    # a pinned capacity keeps the sort ladder: one rung of two
    # configurations, which every burst overflows, then the host: a
    # `launch.escalate` over the three rows
    st, sp = snapshot_stats(), snapshot_spans()
    res = check_encoded(encs, model, algorithm="auto", n_configs=2)
    assert [r["valid?"] for r in res] == [True] * 3
    assert [r["decided-tier"] for r in res] == ["host"] * 3
    assert moved_since(st, sp) == ({"wide_rows": 1, "wide_rows_host": 1}, 3)
    # `jax` alone never escalates: the rows stay undecided
    st, sp = snapshot_stats(), snapshot_spans()
    res = check_encoded(encs, model, algorithm="jax", n_configs=2)
    assert [r["valid?"] for r in res] == ["unknown"] * 3
    assert moved_since(st, sp) == ({"wide_rows": 1, "wide_rows_host": 0}, 0)
    # and the host engine asked for by name is no escalation
    st, sp = snapshot_stats(), snapshot_spans()
    check_encoded(encs[2:], model, algorithm="cpu")
    assert moved_since(st, sp) == ({"wide_rows": 0, "wide_rows_host": 0}, 0)


def test_the_nine_tiles_still_sum_with_the_escalation(tmp_path, monkeypatch):
    """A served batch whose rows take a rung of the ladder and then the
    host engines: `launch.escalate` is nested in the launch's two tiles,
    so the nine still tile the dispatcher's loop."""
    from jepsen_jgroups_raft_tpu.service import scheduler
    from test_spans import TILING

    ended = []
    note = schedule.note_span

    def noting(name, seconds, n=1):
        ended.append((threading.get_ident(), name, time.perf_counter(),
                      seconds))
        note(name, seconds, n)

    monkeypatch.setattr(schedule, "note_span", noting)
    monkeypatch.setattr(scheduler, "note_span", noting)

    def ladder(encs, model, **kw):
        return check_encoded(encs, model, n_configs=2, **kw)

    svc = CheckingService(store_root=str(tmp_path), n_workers=1,
                          check_fn=ladder)
    try:
        for k in range(3):
            r = svc.submit([build_history(burst(9, n_ops=6 + k)),
                            build_history(burst(11, n_ops=6 + k))],
                           workload="register")
            assert r.wait(WAIT_S) and r.status == "done", r.error
            assert [x["decided-tier"] for x in r.results] == ["host"] * 2
        worker = svc._worker.ident
        stats = svc.stats()
    finally:
        svc.shutdown()
    mine = [(name, end, s) for t, name, end, s in ended if t == worker]
    loop = [(end, s) for name, end, s in mine if name in TILING]
    wall = loop[-1][0] - (loop[0][0] - loop[0][1])
    tiled = sum(s for _, s in loop)
    assert 0.9 * wall <= tiled <= 1.02 * wall, (tiled, wall)
    nested = collections.Counter(name for name, _, _ in mine)
    assert nested["launch.escalate"] == 3
    inside = sum(s for name, _, s in mine if name == "launch.escalate")
    launch = sum(s for name, _, s in mine
                 if name in ("launch.host", "launch.device"))
    assert inside <= launch
    # `/stats` serves the two, from the process's totals
    for name in COUNTERS:
        assert stats[name] == snapshot_stats()[name]
    assert stats["wide_rows"] >= 3 and stats["wide_rows_host"] >= 3


# ------------------------------------------------------------- the readers


def reader(name):
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    return mf.load_module(ROOT, "layer_metrics", name)


SERVING = {"stats_before": {"wide_rows": 0, "wide_rows_host": 0,
                            "batches": 1},
           "stats_after": {"wide_rows": 0, "wide_rows_host": 0,
                           "batches": 9},
           "spans_before": {"launch.host": {"n": 1, "s": 0.1}},
           "spans_after": {"launch.host": {"n": 9, "s": 0.9}}}


def test_a_window_without_an_escalation_reads_zero_not_nothing():
    """A rehearsal keeps to one dense key: the program serves the span
    registry and the counters, launches ran, and no host engine did."""
    got = reader("escalate_share").read(example_ctx(SERVING))
    assert got is not None and got == 0.0


def test_the_device_share_reads_nothing_where_no_wide_row_came():
    share = reader("wide_rows_device_share")
    assert share.read(example_ctx(SERVING)) is None
    assert share.read(still_ctx(share.EXAMPLE)) is None
    all_device = dict(SERVING, stats_after=dict(SERVING["stats_after"],
                                                wide_rows=40))
    assert share.read(example_ctx(all_device)) == 100.0


def test_a_parent_with_spans_and_without_the_counters_reads_nothing():
    """The parent serves `spans` but neither the span nor the counters:
    a 0 there would say no host engine ran, which nobody counted."""
    ctx = example_ctx({"spans_before": SERVING["spans_before"],
                       "spans_after": SERVING["spans_after"]})
    assert reader("escalate_share").read(ctx) is None
