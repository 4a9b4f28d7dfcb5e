"""Dense-bitset kernel: correctness against the goldens and the CPU twin.

The dense kernel (ops/dense_scan.py) is an alternate exact representation
of the same search the sort kernel runs; every test here is differential —
same verdicts as the unbounded CPU frontier and the sort kernel — plus
routing tests that pin when the checker auto-selects it.
"""

import random

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu
from jepsen_jgroups_raft_tpu.history.ops import (INFO, INVOKE, OK, History,
                                                 Op)
from jepsen_jgroups_raft_tpu.history.packing import encode_history, pack_batch
from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
from jepsen_jgroups_raft_tpu.models.counter import Counter
from jepsen_jgroups_raft_tpu.models.register import CasRegister
from jepsen_jgroups_raft_tpu.ops.dense_scan import (DENSE_MAX_SLOTS,
                                                    dense_plan,
                                                    make_dense_batch_checker)


def _h(rows):
    h = History()
    for r in rows:
        h.append(Op(*r))
    return h


def test_register_domain_enumeration():
    m = CasRegister()
    h = _h([(0, INVOKE, "write", 3), (0, OK, "write", 3),
            (1, INVOKE, "cas", (3, 9)), (1, OK, "cas", (3, 9)),
            (2, INVOKE, "read", None), (2, OK, "read", 9)])
    enc = encode_history(h, m)
    dom = m.dense_domain(enc.events)
    # initial (NIL) first, then writes ∪ cas-to — read values excluded.
    assert dom[0] == m.init_state()
    assert set(dom[1:]) == {3, 9}


def test_counter_routes_to_mask_mode():
    """The counter has no enumerable value domain, but its state is
    order-independent (Σ deltas) — the plan falls through to mask mode."""
    m = Counter()
    h = _h([(0, INVOKE, "add", 1), (0, OK, "add", 1)])
    enc = encode_history(h, m)
    assert m.dense_domain(enc.events) is None
    plan = dense_plan(m, [enc])
    assert plan is not None and plan.kind == "mask"
    assert plan.n_states == 1


def test_plan_rejects_wide_windows():
    m = CasRegister()
    width = DENSE_MAX_SLOTS + 2
    h = History()
    for p in range(width):
        h.append(Op(p, INVOKE, "write", 1))
    for p in range(width):
        h.append(Op(p, OK, "write", 1))
    enc = encode_history(h, m)
    assert enc.n_slots == width
    assert dense_plan(m, [enc]) is None


def test_auto_routes_register_to_dense_kernel():
    rs = check_histories(
        [_h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
             (1, INVOKE, "read", None), (1, OK, "read", 1)])],
        CasRegister(), algorithm="jax")
    assert rs[0]["valid?"] is True
    assert rs[0]["kernel"] == "dense"


def test_dense_verdicts_on_goldens():
    m = CasRegister()
    valid = _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
                (1, INVOKE, "read", None), (1, OK, "read", 1)])
    invalid = _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
                  (1, INVOKE, "read", None), (1, OK, "read", 2)])
    # Info write observed later: valid, and the crashed slot never forces.
    info_applied = _h([(0, INVOKE, "write", 7), (0, INFO, "write", 7),
                       (1, INVOKE, "read", None), (1, OK, "read", 7)])
    # Info write must not be REQUIRED to have applied.
    info_optional = _h([(0, INVOKE, "write", 7), (0, INFO, "write", 7),
                        (1, INVOKE, "read", None), (1, OK, "read", None)])
    rs = check_histories([valid, invalid, info_applied, info_optional],
                         m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [True, False, True, True]
    assert all(r["kernel"] == "dense" for r in rs)


def test_nonzero_initial_value():
    m = CasRegister(initial=5)
    ok = _h([(0, INVOKE, "read", None), (0, OK, "read", 5),
             (1, INVOKE, "cas", (5, 2)), (1, OK, "cas", (5, 2)),
             (2, INVOKE, "read", None), (2, OK, "read", 2)])
    bad = _h([(0, INVOKE, "read", None), (0, OK, "read", 0)])
    rs = check_histories([ok, bad], m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [True, False]
    assert rs[0]["kernel"] == "dense"


def test_heterogeneous_domains_in_one_batch():
    m = CasRegister()
    h1 = _h([(0, INVOKE, "write", 100), (0, OK, "write", 100),
             (1, INVOKE, "read", None), (1, OK, "read", 100)])
    h2 = _h([(0, INVOKE, "write", -3), (0, OK, "write", -3),
             (1, INVOKE, "read", None), (1, OK, "read", -3)])
    h3 = _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
             (1, INVOKE, "read", None), (1, OK, "read", 2)])
    rs = check_histories([h1, h2, h3], m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [True, True, False]


@pytest.mark.parametrize("crash_p", [0.0, 0.15])
def test_differential_random_histories_vs_cpu(crash_p):
    """Dense kernel verdicts == unbounded CPU frontier on random valid and
    corrupted register histories (the same protocol the sort kernel's
    differential test uses)."""
    m = CasRegister()
    rng = random.Random(77)
    encs, hists = [], []
    for i in range(40):
        h = random_valid_history(rng, "register", n_ops=60, n_procs=4,
                                 crash_p=crash_p, max_crashes=3)
        if i % 2:  # corrupt half: flip one ok-read's value
            ops = list(h)
            reads = [j for j, op in enumerate(ops)
                     if op.type == OK and op.f == "read"
                     and op.value is not None]
            if reads:
                j = rng.choice(reads)
                ops[j] = ops[j].replace(value=ops[j].value + 1)
                h = ops
        hists.append(h)
        encs.append(encode_history(h, m))

    plan = dense_plan(m, encs)
    assert plan is not None and plan.kind == "domain"
    kernel = make_dense_batch_checker(m, plan.kind, plan.n_slots,
                                      plan.n_states)
    ok, overflow = kernel(pack_batch(encs)["events"], plan.val_of)
    assert not np.asarray(overflow).any()
    for i, enc in enumerate(encs):
        expect = check_encoded_cpu(enc, m).valid
        assert bool(ok[i]) is expect, f"history {i}: dense != cpu"


@pytest.mark.parametrize("crash_p", [0.0, 0.15])
def test_mask_mode_differential_counter_vs_cpu(crash_p):
    """Mask-mode kernel verdicts == unbounded CPU frontier on random
    valid and corrupted counter histories (incl. add-and-get ordering
    constraints and optimistic info semantics)."""
    m = Counter()
    rng = random.Random(78)
    encs = []
    for i in range(40):
        h = random_valid_history(rng, "counter", n_ops=50, n_procs=4,
                                 crash_p=crash_p, max_crashes=3)
        if i % 2:  # corrupt half: bump a completed read or an
            # add-and-get's observed new value ((delta, new) tuple)
            ops = list(h)
            cands = [j for j, op in enumerate(ops)
                     if op.type == OK and op.value is not None
                     and op.f in ("read", "add-and-get")]
            if cands:
                j = rng.choice(cands)
                if ops[j].f == "read":
                    ops[j] = ops[j].replace(value=ops[j].value + 1)
                else:
                    delta, new = ops[j].value
                    ops[j] = ops[j].replace(value=(delta, new + 1))
                h = ops
        encs.append(encode_history(h, m))

    plan = dense_plan(m, encs)
    assert plan is not None and plan.kind == "mask"
    kernel = make_dense_batch_checker(m, plan.kind, plan.n_slots,
                                      plan.n_states)
    ok, overflow = kernel(pack_batch(encs)["events"], plan.val_of)
    assert not np.asarray(overflow).any()
    for i, enc in enumerate(encs):
        expect = check_encoded_cpu(enc, m).valid
        assert bool(ok[i]) is expect, f"history {i}: mask-dense != cpu"


def test_mask_mode_counter_goldens():
    """The reference's pinned CounterModel semantics through the mask
    kernel (raft_test.clj's three cases live in test_checker.py; these
    cover the kernel-facing essentials, incl. negative deltas)."""
    m = Counter()
    valid = _h([(0, INVOKE, "add", 2), (0, OK, "add", 2),
                (1, INVOKE, "add-and-get", 3), (1, OK, "add-and-get", (3, 5)),
                (2, INVOKE, "read", None), (2, OK, "read", 5)])
    stale = _h([(0, INVOKE, "add", 2), (0, OK, "add", 2),
                (1, INVOKE, "read", None), (1, OK, "read", 1)])
    decr = _h([(0, INVOKE, "add", 4), (0, OK, "add", 4),
               (1, INVOKE, "decr", 1), (1, OK, "decr", 1),
               (2, INVOKE, "read", None), (2, OK, "read", 3)])
    # info add may or may not apply: read of 0 AND read of 7 both fine,
    # but only consistently (0 then 7 ok; 7 then 0 impossible).
    info_ok = _h([(0, INVOKE, "add", 7), (0, INFO, "add", 7),
                  (1, INVOKE, "read", None), (1, OK, "read", 0),
                  (2, INVOKE, "read", None), (2, OK, "read", 7)])
    info_bad = _h([(0, INVOKE, "add", 7), (0, INFO, "add", 7),
                   (1, INVOKE, "read", None), (1, OK, "read", 7),
                   (2, INVOKE, "read", None), (2, OK, "read", 0)])
    # A wrong add-and-get observation must be caught (state+delta != new).
    aag_bad = _h([(0, INVOKE, "add", 2), (0, OK, "add", 2),
                  (1, INVOKE, "add-and-get", 3),
                  (1, OK, "add-and-get", (3, 6))])
    rs = check_histories([valid, stale, decr, info_ok, info_bad, aag_bad],
                         m, algorithm="jax")
    assert [r["valid?"] for r in rs] == [True, False, True, True, False,
                                         False]
    assert all(r["kernel"] == "dense-mask" for r in rs)


def test_read_of_unreachable_value_dies():
    m = CasRegister()
    h = _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
            (1, INVOKE, "read", None), (1, OK, "read", 42)])  # 42 ∉ domain
    rs = check_histories([h], m, algorithm="jax")
    assert rs[0]["valid?"] is False


@pytest.mark.parametrize("model_kind", ["register", "counter"])
def test_all_engines_agree_on_one_corpus(model_kind):
    """Every engine, one corpus: brute-force oracle == CPU frontier ==
    DFS == sort kernel == dense/dense-mask kernel on the same
    randomized valid+corrupted histories.
    The strongest cross-check in the suite: any single-engine regression
    breaks a direct equality against the exponential oracle."""
    from jepsen_jgroups_raft_tpu.checker.brute import check_brute
    from jepsen_jgroups_raft_tpu.checker.dfs_cpu import check_encoded_dfs
    from jepsen_jgroups_raft_tpu.history.synth import corrupt

    model = CasRegister() if model_kind == "register" else Counter()
    rng = random.Random(1234)
    cases = []
    for trial in range(60):
        h = random_valid_history(rng, model_kind, n_ops=8, n_procs=3)
        if trial % 2:
            h = corrupt(rng, h)
        cases.append(h)
    encs = [encode_history(h, model) for h in cases]
    expected = [check_brute(h, model) for h in cases]

    def assert_decided(r, i, label):
        # UNKNOWN must not masquerade as agreement with an invalid oracle
        # verdict: every engine must DECIDE these tiny histories.
        assert r["valid?"] in (True, False), f"{label} undecided case {i}: {r}"
        assert r["valid?"] is expected[i], f"{label} case {i}"

    # dense / dense-mask via the auto route
    dense_rs = check_histories(cases, model, algorithm="jax")
    for i, r in enumerate(dense_rs):
        assert_decided(r, i, "dense")
        if encs[i].n_events:
            assert r["kernel"].startswith("dense"), r

    # sort kernel (pinned capacity forces it)
    sort_rs = check_histories(cases, model, algorithm="jax", n_configs=128)
    for i, r in enumerate(sort_rs):
        assert_decided(r, i, "sort")

    # host engines
    for i, e in enumerate(encs):
        if e.n_events == 0:
            continue
        assert check_encoded_cpu(e, model).valid == expected[i], i
        assert check_encoded_dfs(e, model).valid == expected[i], i


def test_pinned_capacity_keeps_sort_kernel():
    """Explicit n_configs is a sort-kernel knob: pinning it must bypass
    the dense path (capacity-escalation tests depend on it)."""
    h = _h([(0, INVOKE, "write", 1), (0, OK, "write", 1)])
    rs = check_histories([h], CasRegister(), algorithm="jax", n_configs=64)
    assert rs[0]["valid?"] is True
    assert rs[0].get("kernel") == "sort"


def test_early_flush_keeps_stragglers_window_snug():
    """Regression: flushing short stragglers ahead of a long-history
    bucket must launch them at THEIR OWN max window, not the long
    bucket's (kernel cost is 2^W; inheriting the wide W silently
    multiplied the stragglers' work)."""
    import random

    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (MERGE_MAX_EVENTS,
                                                        dense_plans_grouped)

    m = CasRegister()
    rng = random.Random(4)
    # A few short narrow histories (below HOST_MIN_GROUP)...
    short = [encode_history(
        random_valid_history(rng, "register", n_ops=10, n_procs=2,
                             crash_p=0.0), m) for _ in range(3)]
    # ...plus one long wide history that triggers the early flush.
    long_h = encode_history(
        random_valid_history(rng, "register",
                             n_ops=MERGE_MAX_EVENTS, n_procs=5,
                             crash_p=0.03, max_crashes=3), m)
    assert long_h.n_events > MERGE_MAX_EVENTS
    encs = short + [long_h]
    groups, rest = dense_plans_grouped(m, encs)
    assert not rest
    for idxs, plan in groups:
        w_own = max(encs[i].n_slots for i in idxs)
        assert plan.n_slots == max(w_own, 1), (idxs, plan.n_slots)


def test_merge_long_clusters_by_window_spread(monkeypatch):
    """Round-5 policy: long histories merge into cluster launches while
    their windows stay within MERGE_LONG_MAX_SPREAD of the cluster's
    widest member (measured 1.36x on config 4) — but a window outlier
    must NOT be folded in (width inflation 2^dW per step outruns any
    depth saving)."""
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (
        MERGE_LONG_MAX_SPREAD, MERGE_MAX_EVENTS, dense_plans_grouped)

    monkeypatch.setenv("JGRAFT_MERGE_LONG", "1")
    m = CasRegister()
    rng = random.Random(11)
    mk = lambda procs, crashes: encode_history(
        random_valid_history(rng, "register", n_ops=MERGE_MAX_EVENTS + 512,
                             n_procs=procs, crash_p=0.02 if crashes else 0.0,
                             max_crashes=crashes), m)
    # Windows cluster around 5-8 (5 procs + crashes) and 2 (2 procs).
    wide = [mk(5, 3) for _ in range(4)]
    narrow = [mk(2, 0) for _ in range(2)]
    encs = wide + narrow
    assert all(e.n_events > MERGE_MAX_EVENTS for e in encs)
    wide_ws = sorted(encs[i].n_slots for i in range(4))
    assert wide_ws[-1] > wide_ws[0], "seed must spread the wide windows"
    groups, rest = dense_plans_grouped(m, encs)
    assert not rest
    for idxs, plan in groups:
        ws = [encs[i].n_slots for i in idxs]
        # Snug launch window, bounded spread inside each cluster.
        assert plan.n_slots == max(max(ws), 1)
        assert max(ws) - min(ws) <= MERGE_LONG_MAX_SPREAD
    # Cross-window merging must actually have happened (this is what
    # per-window grouping can never produce — the test fails if the
    # merge block is deleted or disabled).
    assert any(len({encs[i].n_slots for i in idxs}) > 1
               for idxs, _ in groups)
    # The narrow pair must not ride in a wide cluster (spread guard).
    assert wide_ws[-1] - 2 > MERGE_LONG_MAX_SPREAD
    assert len(groups) >= 2


def test_merge_long_cap_overflow_splits_not_sheds(monkeypatch):
    """A cluster whose padded cell envelope would exceed DENSE_MAX_CELLS
    must SPLIT (later members wait for a narrower cluster), never shed a
    dense-eligible history to the sort ladder (code-review r5 finding:
    the first merge cut let flush() shed the widest member)."""
    from jepsen_jgroups_raft_tpu.history.ops import INFO
    from jepsen_jgroups_raft_tpu.ops.dense_scan import (DENSE_MAX_CELLS,
                                                        MERGE_MAX_EVENTS,
                                                        dense_plans_grouped)

    m = CasRegister()

    def mk(n_vals, window, n_ops):
        """Long history: sequential write churn over `n_vals` distinct
        values (domain = initial + n_vals), ending in a burst of
        `window` concurrent COMPLETED writes (any serialization of
        writes is legal) — n_slots = window without involving the
        crashed-op prune."""
        h = History()
        for i in range(n_ops):
            v = i % n_vals
            h.append(Op(0, INVOKE, "write", v))
            h.append(Op(0, OK, "write", v))
        for p in range(window):
            h.append(Op(p + 1, INVOKE, "write", p % n_vals))
        for p in range(window):
            h.append(Op(p + 1, OK, "write", p % n_vals))
        return encode_history(h, m)

    monkeypatch.setenv("JGRAFT_MERGE_LONG", "1")
    half = MERGE_MAX_EVENTS  # events ≈ 2 ops each → long
    x = mk(7, 13, half)            # W=13, S=8 → 65536 = cap, eligible
    y1 = mk(15, 10, half)          # W=10, S=16 padded (inside the spread)
    y2 = mk(15, 10, half)
    encs = [x, y1, y2]
    assert x.n_slots == 13 and y1.n_slots == 10
    assert all(e.n_events > MERGE_MAX_EVENTS for e in encs)
    # Merged at w_top=13 with S padded to 16 would be 131072 > cap.
    assert (1 << 13) * 8 == DENSE_MAX_CELLS < (1 << 13) * 16
    groups, rest = dense_plans_grouped(m, encs)
    assert rest == [], "dense-eligible history shed to the sort ladder"
    got = sorted(tuple(sorted(idxs)) for idxs, _ in groups)
    assert got == [(0,), (1, 2)], got


def test_merge_long_verdict_parity(monkeypatch):
    """Merged and per-window launches are the same search over the same
    events — verdicts must be identical, including an invalid history."""
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history

    m = CasRegister()
    rng = random.Random(12)
    hs = [random_valid_history(rng, "register", n_ops=4200, n_procs=p,
                               crash_p=0.02, max_crashes=c)
          for p, c in [(5, 3), (4, 2), (3, 0), (5, 1)]]
    # Corrupt one: flip a read's observed value to something impossible.
    bad = History()
    flipped = False
    for op in hs[1]:
        if not flipped and op.type == OK and op.f == "read" \
                and op.value is not None:
            bad.append(Op(op.process, op.type, op.f, op.value + 100))
            flipped = True
        else:
            bad.append(op)
    assert flipped
    hs[1] = bad
    verdicts = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("JGRAFT_MERGE_LONG", flag)
        rs = check_histories(hs, m, algorithm="jax")
        verdicts[flag] = [r["valid?"] for r in rs]
    assert verdicts["0"] == verdicts["1"]
    assert verdicts["1"][1] is False
    assert verdicts["1"][0] is True


def test_hoist_styles_verdict_parity(monkeypatch):
    """The carry-hoisted and register-style domain kernels are the same
    search (hoist_transitions is a backend-keyed perf trade): verdicts
    must match on valid, invalid, and crashed-op histories."""
    from jepsen_jgroups_raft_tpu.history.synth import random_valid_history

    m = CasRegister()
    rng = random.Random(13)
    hs = [random_valid_history(rng, "register", n_ops=300, n_procs=p,
                               crash_p=0.1, max_crashes=3)
          for p in (2, 3, 5)]
    bad = History()
    flipped = False
    for op in hs[0]:
        if not flipped and op.type == OK and op.f == "read" \
                and op.value is not None:
            bad.append(Op(op.process, op.type, op.f, op.value + 50))
            flipped = True
        else:
            bad.append(op)
    assert flipped
    hs.append(bad)
    verdicts = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("JGRAFT_HOIST", flag)
        rs = check_histories(hs, m, algorithm="jax")
        verdicts[flag] = [r["valid?"] for r in rs]
    assert verdicts["0"] == verdicts["1"]
    assert verdicts["1"][3] is False
    assert verdicts["1"][0] is True


# ISSUE 33: window groups are formed by what they cost the backend.
# (W, rows, states, steps) a window; steps ~2000 and ~1620 are the
# benchmark cell's 1k-op counter and register histories. The batches
# marked "measured" are those the chip sweep timed partition by
# partition (PERF.md section 6, PR 33; the domain ones again in PR 41,
# on the packed kernel; both kinds again in PR 45, on the kernel
# without FORCE's loop over the rows): the pick is the fastest
# measured at 128 rows and within 4-9 % of it past that, but for the
# 256-row domain batch (its comment).
_E = 1998
_R = 1616


def _tpu():
    from jepsen_jgroups_raft_tpu.ops.dense_scan import TPU_GROUP_COST

    return TPU_GROUP_COST


@pytest.mark.parametrize("kind, windows, cost, want", [
    # a served 128-row counter batch of the cell: one launch at W 8
    ("mask", [(6, 26, 1, _E), (7, 61, 1, _E), (8, 41, 1, _E)], _tpu,
     [[0, 1, 2]]),
    # measured: 128 and 256 rows of the cell, both kinds
    ("mask", [(5, 5, 1, _E), (6, 27, 1, _E), (7, 58, 1, _E),
              (8, 38, 1, _E)], _tpu, [[0, 1, 2, 3]]),
    ("domain", [(5, 4, 4, _R), (6, 32, 4, _R), (7, 59, 5, _R),
                (8, 33, 5, _R)], _tpu, [[0, 1, 2, 3]]),
    # ... where, since ISSUE 45 took the loop over the rows out of
    # FORCE, a window costs from W 5 up and the 256-row batches split
    # (mask: 156.1 ms measured against 150.3 for [[5, 6], [7, 8]], the
    # fastest, and 173.9 merged; domain: 219.4 against 160.9 for
    # [[5, 6, 7], [8]] and 249.7 merged: the measured partitions carry
    # the host's two modes, 60-90 ms apart, PERF.md section 7)
    ("mask", [(5, 9, 1, _E), (6, 58, 1, _E), (7, 116, 1, _E),
              (8, 73, 1, _E)], _tpu, [[0, 1], [2], [3]]),
    ("domain", [(5, 9, 4, _R), (6, 65, 4, _R), (7, 108, 5, _R),
                (8, 74, 5, _R)], _tpu, [[0, 1], [2], [3]]),
    # measured: the library's 1000-row batch, where every group is
    # long enough to amortise its launch and width decides
    ("mask", [(5, 32, 1, _E), (6, 230, 1, _E), (7, 440, 1, _E),
              (8, 298, 1, _E)], _tpu, [[0], [1], [2], [3]]),
    # (ISSUE 45's reading: mask 740.9 ms measured against 686.8 for
    # [[5, 6], [7], [8]], the fastest, +7.9 %; domain, W 7 and 8
    # together, 768.8 against 706.0 for the same, +8.9 %)
    ("domain", [(5, 30, 5, _R), (6, 243, 5, _R), (7, 426, 5, _R),
                (8, 301, 5, _R)], _tpu, [[0], [1], [2, 3]]),
    # a window alone
    ("mask", [(7, 40, 1, _E)], _tpu, [[0]]),
    # three stragglers beside 60 rows: their launch costs more than
    # the width they add
    ("mask", [(5, 3, 1, _E), (7, 60, 1, _E)], _tpu, [[0, 1]]),
    # a step's cells outgrow the launch saved: W 10 stays apart from
    # W 6 for the mask kernel at 200 rows, W 12 for the domain kernel at
    # 200; at 60 rows the packed domain kernel (ISSUE 41) reads W 10
    # within a fifth of W 6 and the two share a launch
    ("domain", [(6, 60, 4, _R), (10, 60, 4, _R)], _tpu, [[0, 1]]),
    ("domain", [(6, 200, 8, _R), (12, 200, 8, _R)], _tpu, [[0], [1]]),
    ("mask", [(6, 200, 1, _E), (10, 200, 1, _E)], _tpu, [[0], [1]]),
    # a served 128-row batch of the partition cell (W 6-13): three
    # launches (W 6-9 | 10-11 | 12-13) since ISSUE 45's kernel, on
    # which W 12-13 cost twice and four times W 10 at 128 rows and
    # every window the same at 8; ISSUE 41's table made two
    ("domain", [(6, 5, 8, _R), (7, 14, 8, _R), (8, 26, 8, _R),
                (9, 33, 8, _R), (10, 29, 8, _R), (11, 14, 8, _R),
                (12, 4, 8, _R), (13, 1, 8, _R)], _tpu,
     [[0, 1, 2, 3], [4, 5], [6, 7]]),
    # short histories never pay a long scan for a launch saved
    ("mask", [(6, 60, 1, 600), (7, 60, 1, 4000)], _tpu, [[0], [1]]),
    # a merge whose padded frontier passes DENSE_MAX_CELLS is no
    # candidate: 2^13 * 16 cells
    ("domain", [(7, 6, 16, _E), (13, 6, 8, _E)], _tpu, [[0], [1]]),
    # windows under the table's narrowest are booked as that one
    ("mask", [(2, 4, 1, 100), (3, 5, 1, 100), (4, 6, 1, 120)], _tpu,
     [[0, 1, 2]]),
    # off the TPU: a group a window, windows under 16 rows pushed up
    ("mask", [(6, 26, 1, _E), (7, 61, 1, _E), (8, 41, 1, _E)],
     lambda: None, [[0], [1], [2]]),
    ("domain", [(5, 3, 4, _E), (6, 20, 4, _E), (7, 5, 4, _E)],
     lambda: None, [[0, 1], [2]]),
])
def test_best_partition(kind, windows, cost, want):
    from jepsen_jgroups_raft_tpu.ops.dense_scan import best_partition

    assert best_partition(kind, windows, cost()) == want


def test_group_cost_reads_its_table():
    """A reading at a measured point is the table's; the scan's length
    scales all but the fixed part; what the table does not hold is
    booked from what it does."""
    c = _tpu()
    at = dict(zip(c.rows, c.ms["mask"][1][8]))
    fixed = c.fixed_ms["mask"]
    assert c.seconds("mask", 8, 1, 128, 2000) == pytest.approx(
        at[128] / 1e3)
    assert c.seconds("mask", 8, 1, 128, 1000) == pytest.approx(
        (fixed + (at[128] - fixed) / 2) / 1e3)
    assert c.seconds("mask", 8, 1, 96, 2000) == pytest.approx(
        (at[64] + at[128]) / 2e3)
    assert c.seconds("mask", 3, 1, 8, 2000) == \
        c.seconds("mask", 5, 1, 8, 2000)
    # rows past a window's last reading, a window past the table, 16
    # states as one window more of 8, and 4 states from their own table
    w9, w10 = c.ms["mask"][1][9], c.ms["mask"][1][10]
    assert c.seconds("mask", 10, 1, 512, 2000) == pytest.approx(
        2 * w10[-1] / 1e3)
    assert c.seconds("mask", 11, 1, 8, 2000) == pytest.approx(
        w10[0] * w10[0] / w9[0] / 1e3)
    assert c.seconds("domain", 7, 16, 64, 1614) == \
        c.seconds("domain", 8, 8, 64, 1614)
    # (since ISSUE 45's kernel S 4 reads what S 8 does at 128 rows)
    assert c.seconds("domain", 8, 4, 128, 1614) <= \
        c.seconds("domain", 8, 8, 128, 1614)
    assert c.seconds("domain", 8, 4, 32, 1614) < \
        c.seconds("domain", 10, 8, 32, 1614)
    # a launch a window wider, or of more states, never reads cheaper
    for by_s in c.ms.values():
        for table in by_s.values():
            for w in sorted(table)[1:]:
                assert all(a >= b for a, b in zip(table[w], table[w - 1]))


def _burst(model, window, n_ops, n_vals=3):
    """Sequential churn, then `window` concurrent completed ops: a
    history whose window is `window` exactly and whose register domain
    holds the initial value and `n_vals` more."""
    counter = isinstance(model, Counter)
    h = History()
    for i in range(n_ops):
        v = 1 if counter else i % n_vals
        h.append(Op(0, INVOKE, "add" if counter else "write", v))
        h.append(Op(0, OK, "add" if counter else "write", v))
    for p in range(window):
        h.append(Op(p + 1, INVOKE, "add" if counter else "write",
                    1 if counter else p % n_vals))
    for p in range(window):
        h.append(Op(p + 1, OK, "add" if counter else "write",
                    1 if counter else p % n_vals))
    return encode_history(h, model)


@pytest.mark.parametrize("model", [CasRegister(), Counter()],
                         ids=["domain", "mask"])
def test_grouping_follows_the_backends_cost(model, monkeypatch):
    """`dense_plans_grouped` over real encodings: merged at the widest
    window under the chip's cost, today's partition off it."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    encs = [_burst(model, w, 20) for w in (3, 3, 4, 5, 5, 5)]
    groups, rest = dense_scan.dense_plans_grouped(model, encs)
    assert not rest
    # off the TPU six rows are stragglers of one group, as ever
    assert [(sorted(i), p.n_slots) for i, p in groups] == \
        [([0, 1, 2, 3, 4, 5], 5)]
    many = [_burst(model, w, 20) for w in [3] * 16 + [4] * 3 + [5] * 17]
    groups, _ = dense_scan.dense_plans_grouped(model, many)
    assert [(len(i), p.n_slots) for i, p in groups] == \
        [(16, 3), (20, 5)]
    monkeypatch.setattr(dense_scan, "_group_cost", _tpu)
    groups, rest = dense_scan.dense_plans_grouped(model, many)
    assert not rest
    assert [(len(i), p.n_slots) for i, p in groups] == [(36, 5)]


def test_cost_merge_never_sheds_past_the_cell_cap(monkeypatch):
    """Two windows that are dense-eligible alone and whose merge would
    launch 2^13 * 16 cells stay two groups under the chip's cost;
    nothing goes to the sort ladder."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    m = CasRegister()
    monkeypatch.setattr(dense_scan, "_group_cost", _tpu)
    encs = [_burst(m, 13, 30, n_vals=7), _burst(m, 7, 30, n_vals=15),
            _burst(m, 7, 30, n_vals=15)]
    assert (1 << 13) * 16 > dense_scan.DENSE_MAX_CELLS
    groups, rest = dense_scan.dense_plans_grouped(m, encs)
    assert rest == []
    assert sorted((sorted(i), p.n_slots, p.n_states)
                  for i, p in groups) == [([0], 13, 8), ([1, 2], 7, 16)]


@pytest.mark.parametrize("merge_long", ["0", "1"])
def test_long_histories_keep_their_policy_under_the_cost(monkeypatch,
                                                         merge_long):
    """The chip's cost forms SHORT groups only: a long history launches
    with its own (merged cluster, or its window's bucket), the shorts
    around it never ride in that launch."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    m = CasRegister()
    monkeypatch.setattr(dense_scan, "_group_cost", _tpu)
    monkeypatch.setenv("JGRAFT_MERGE_LONG", merge_long)
    long_h = _burst(m, 6, dense_scan.MERGE_MAX_EVENTS // 2 + 8)
    assert long_h.n_events > dense_scan.MERGE_MAX_EVENTS
    encs = [_burst(m, 4, 20), _burst(m, 5, 20), long_h,
            _burst(m, 7, 20), _burst(m, 8, 20)]
    groups, rest = dense_scan.dense_plans_grouped(m, encs)
    assert not rest
    got = sorted((sorted(i), p.n_slots) for i, p in groups)
    if merge_long == "1":
        # the long pool first, then every short in one group
        assert got == [([0, 1, 3, 4], 8), ([2], 6)]
    else:
        # the long window is a barrier between the runs of shorts
        assert got == [([0, 1], 5), ([2], 6), ([3, 4], 8)]


@pytest.mark.parametrize("kind, model", [("register", CasRegister()),
                                         ("counter", Counter())])
def test_merged_groups_verdict_parity(kind, model, monkeypatch):
    """One launch at the widest window and a launch a window are the
    same search over the same events: identical verdicts, planted
    invalid rows included."""
    from jepsen_jgroups_raft_tpu.ops import dense_scan

    rng = random.Random(33)
    hs = [random_valid_history(rng, kind, n_ops=60, n_procs=p,
                               crash_p=0.1, max_crashes=c)
          for p, c in [(2, 0), (3, 1), (5, 3), (4, 2), (5, 0), (3, 0),
                       (5, 2), (2, 1)] * 3]
    planted = (1, 6, 13, 20)
    for k in planted:
        bad, flipped = History(), False
        for op in hs[k]:
            if not flipped and op.type == OK and op.f == "read" \
                    and op.value is not None:
                op = Op(op.process, op.type, op.f, op.value + 100)
                flipped = True
            bad.append(op)
        assert flipped
        hs[k] = bad
    encs = [encode_history(h, model) for h in hs]
    assert len({e.n_slots for e in encs}) >= 3
    verdicts, n_groups = {}, {}
    for name, cost in (("per-window", lambda: None), ("merged", _tpu)):
        monkeypatch.setattr(dense_scan, "_group_cost", cost)
        n_groups[name] = len(dense_scan.dense_plans_grouped(model,
                                                            encs)[0])
        verdicts[name] = [r["valid?"] for r in check_histories(
            hs, model, algorithm="jax")]
    assert n_groups["merged"] == 1 < n_groups["per-window"]
    assert verdicts["merged"] == verdicts["per-window"]
    assert all(verdicts["merged"][k] is False for k in planted)
    assert verdicts["merged"].count(True) >= len(hs) - len(planted) - 4
