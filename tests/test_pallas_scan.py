"""Pallas dense-scan kernel: differential correctness.

Interpret mode runs the kernel's exact dataflow on CPU; verdicts must
match the XLA dense kernel and the unbounded CPU frontier on the same
batches (goldens + randomized valid/corrupted histories). The Mosaic
lowering is compiled for a described v5e in tests/test_tpu_compile.py.
"""

import random

import numpy as np

from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu
from jepsen_jgroups_raft_tpu.history.ops import INFO, INVOKE, OK, History, Op
from jepsen_jgroups_raft_tpu.history.packing import (encode_history,
                                                     pack_batch,
                                                     pad_batch_bucketed)
from jepsen_jgroups_raft_tpu.history.synth import random_valid_history
from jepsen_jgroups_raft_tpu.models.register import CasRegister
from jepsen_jgroups_raft_tpu.ops.dense_scan import dense_plan
from jepsen_jgroups_raft_tpu.ops.pallas_scan import make_pallas_batch_checker


def _h(rows):
    h = History()
    for r in rows:
        h.append(Op(*r))
    return h


def _maybe_corrupt_read(h, rng):
    """Bump one successful read's value so the history turns invalid
    (when it has any such read) — the standard corruption used by every
    differential here and in the TPU subprocess script."""
    ops = list(h)
    reads = [j for j, op in enumerate(ops)
             if op.type == OK and op.f == "read" and op.value is not None]
    if not reads:
        return h
    j = rng.choice(reads)
    ops[j] = ops[j].replace(value=ops[j].value + 1)
    return ops


def _run_pallas(encs, model, interpret=True):
    plan = dense_plan(model, encs)
    assert plan is not None and plan.kind == "domain"
    ev, (val_of,), B = pad_batch_bucketed(pack_batch(encs)["events"],
                                          (plan.val_of,))
    kernel = make_pallas_batch_checker(model, plan.n_slots, plan.n_states,
                                       ev.shape[1], interpret=interpret)
    ok, overflow = kernel(ev, val_of)
    return np.asarray(ok)[:B], np.asarray(overflow)[:B]


def test_pallas_goldens_interpret():
    m = CasRegister()
    hists = [
        _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
            (1, INVOKE, "read", None), (1, OK, "read", 1)]),       # valid
        _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
            (1, INVOKE, "read", None), (1, OK, "read", 2)]),       # invalid
        _h([(0, INVOKE, "write", 7), (0, INFO, "write", 7),
            (1, INVOKE, "read", None), (1, OK, "read", 7)]),       # info ok
        _h([(0, INVOKE, "cas", (0, 3)), (0, OK, "cas", (0, 3))]),  # cas≠init
    ]
    encs = [encode_history(h, m) for h in hists]
    ok, overflow = _run_pallas(encs, m)
    assert not overflow.any()
    assert list(ok) == [True, False, True, False]


def test_pallas_differential_vs_cpu_interpret():
    m = CasRegister()
    rng = random.Random(99)
    encs = []
    for i in range(24):
        h = random_valid_history(rng, "register", n_ops=40, n_procs=4,
                                 crash_p=0.15, max_crashes=3)
        if i % 2:
            h = _maybe_corrupt_read(h, rng)
        encs.append(encode_history(h, m))
    ok, overflow = _run_pallas(encs, m)
    assert not overflow.any()
    for i, enc in enumerate(encs):
        assert bool(ok[i]) is check_encoded_cpu(enc, m).valid, i


def test_pallas_exact_event_shapes_pad_to_sublane_rule():
    """Exact (non-bucketed) event lengths reach the kernel when the
    checker takes the few-long-histories exact-shapes path; the wrapper
    must pad E to a multiple of 8 (Mosaic's sublane block rule for
    multi-tile grids) without changing verdicts. E=37 → 40 here."""
    m = CasRegister()
    rng = random.Random(7)
    encs = []
    for i in range(12):
        h = random_valid_history(rng, "register", n_ops=18, n_procs=3,
                                 crash_p=0.1, max_crashes=2)
        if i % 3 == 0:
            h = _maybe_corrupt_read(h, rng)
        encs.append(encode_history(h, m))
    plan = dense_plan(m, encs)
    assert plan is not None and plan.kind == "domain"
    # floor_e=None keeps the exact max event length instead of bucketing
    # to a power of two; append EV_PAD no-op events to force an odd E.
    ev, (val_of,), B = pad_batch_bucketed(pack_batch(encs)["events"],
                                          (plan.val_of,), floor_e=None)
    if ev.shape[1] % 8 == 0:
        ev = np.concatenate(
            [ev, np.zeros((ev.shape[0], 5, 5), ev.dtype)], axis=1)
    assert ev.shape[1] % 8 != 0, "shape must exercise the E-padding path"
    kernel = make_pallas_batch_checker(m, plan.n_slots, plan.n_states,
                                       ev.shape[1], interpret=True)
    ok = np.asarray(kernel(ev, val_of)[0])[:B]
    for i, enc in enumerate(encs):
        assert bool(ok[i]) is check_encoded_cpu(enc, m).valid, i


def test_algorithm_pallas_is_first_class():
    rs = check_histories(
        [_h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
             (1, INVOKE, "read", None), (1, OK, "read", 1)]),
         _h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
             (1, INVOKE, "read", None), (1, OK, "read", 9)])],
        CasRegister(), algorithm="pallas")
    assert [r["valid?"] for r in rs] == [True, False]
    assert all(r["kernel"] == "pallas" for r in rs)


def test_algorithm_pallas_covers_every_window_group():
    """Regression: the routing flag must survive the group loop — with
    two dense window groups, the second used to silently fall back to
    the XLA dense kernel (the loop rebinds `kernel` to the compiled
    callable, clobbering the parameter it was read from)."""
    rng = random.Random(17)
    hists = (
        [random_valid_history(rng, "register", n_ops=6, n_procs=1,
                              crash_p=0.0) for _ in range(16)] +  # W=1
        [random_valid_history(rng, "register", n_ops=12, n_procs=3,
                              crash_p=0.0) for _ in range(16)]    # W~3
    )
    rs = check_histories(hists, CasRegister(), algorithm="pallas")
    assert all(r["valid?"] is True for r in rs)
    kernels = {r["kernel"] for r in rs}
    assert kernels == {"pallas"}, kernels


def test_env_opt_in_routes_through_pallas(monkeypatch):
    monkeypatch.setenv("JGRAFT_KERNEL", "pallas")
    rs = check_histories(
        [_h([(0, INVOKE, "write", 1), (0, OK, "write", 1),
             (1, INVOKE, "read", None), (1, OK, "read", 1)])],
        CasRegister(), algorithm="jax")
    assert rs[0]["valid?"] is True
    assert rs[0]["kernel"] == "pallas"  # routing really took the opt-in
