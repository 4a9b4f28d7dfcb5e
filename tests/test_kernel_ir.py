"""IR-instantiation differential tests (PR 6 satellite).

The kernel IR (ops/kernel_ir.py) now owns the stream decode, macro
latch, FORCE dispatch, chunk-carry schema and both drivers; every
family only supplies its state lowering. These tests prove the
refactor preserved behavior bit for bit: for each family (dense
domain, dense mask, sort) × stream format
(macro on/off) × driver (monolithic vs chunked), verdicts are
identical to each other and to the CPU oracle — the exact contract
the pre-refactor per-family code was pinned to by
tests/test_chunked_scan.py and tests/test_macro_events.py.
"""

import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from util import corrupt, random_valid_history  # noqa: E402

from jepsen_jgroups_raft_tpu.checker.wgl_cpu import (  # noqa: E402
    check_encoded_cpu)
from jepsen_jgroups_raft_tpu.history.packing import (  # noqa: E402
    bucket_opens, encode_history, max_open_run, pack_batch,
    pack_macro_batch)
from jepsen_jgroups_raft_tpu.models import CasRegister, Counter  # noqa: E402
from jepsen_jgroups_raft_tpu.ops import kernel_ir  # noqa: E402
from jepsen_jgroups_raft_tpu.ops.dense_scan import (  # noqa: E402
    dense_plan, make_dense_batch_checker, make_dense_chunk_checker)
from jepsen_jgroups_raft_tpu.ops.linear_scan import (  # noqa: E402
    make_batch_checker, make_sort_chunk_checker)


def _mixed_batch(workload, model, n=10, n_ops=18, seed=11):
    """Encoded histories with both polarities + CPU-oracle verdicts.
    `corrupt` only *may* break linearizability, so corrupt rows are
    re-rolled until the oracle actually flips."""
    rng = random.Random(seed)
    hists = [random_valid_history(rng, workload, n_ops=n_ops, n_procs=4,
                                  crash_p=0.1, max_crashes=2)
             for _ in range(n)]
    encs = [encode_history(h, model) for h in hists]
    oracle = [check_encoded_cpu(e, model).valid for e in encs]
    want_invalid = max(2, n // 4)
    for i in range(n):
        if oracle.count(False) >= want_invalid:
            break
        if not oracle[i]:
            continue
        for _ in range(25):
            h = corrupt(rng, hists[i])
            e = encode_history(h, model)
            if not check_encoded_cpu(e, model).valid:
                hists[i], encs[i], oracle[i] = h, e, False
                break
    assert True in oracle and False in oracle  # both polarities exercised
    return encs, oracle


def _chunked_verdicts(init_fn, step_fn, events, n_events, val_of=None,
                      chunk=8):
    """Drive the IR chunk-carry schema by hand: verdicts recorded at
    each row's first decided/exhausted flag — eviction semantics
    without the scheduler."""
    B, E = events.shape[0], events.shape[1]
    e_pad = ((E + chunk - 1) // chunk) * chunk
    if e_pad != E:
        padded = np.zeros((B, e_pad, events.shape[2]), events.dtype)
        padded[:, :E] = events
        events = padded
    ne = np.asarray(n_events, np.int32)
    carry = (init_fn(val_of, ne) if val_of is not None else init_fn(ne))
    out_ok = np.zeros((B,), bool)
    out_ovf = np.zeros((B,), bool)
    recorded = np.zeros((B,), bool)
    for lo in range(0, e_pad, chunk):
        # the whole stream is the operand; the span is two scalars
        carry, dec, exh, ok, ovf = step_fn(carry, events, lo, chunk)
        done = (np.asarray(dec) | np.asarray(exh)) & ~recorded
        out_ok[done] = np.asarray(ok)[done]
        out_ovf[done] = np.asarray(ovf)[done]
        recorded |= done
    assert recorded.all()  # every row decided or exhausted by schedule end
    return out_ok, out_ovf


class TestDenseFamilies:
    @pytest.mark.parametrize("macro", [False, True])
    def test_domain_monolithic_chunked_oracle_identical(self, macro):
        model = CasRegister()
        encs, oracle = _mixed_batch("register", model)
        plan = dense_plan(model, encs)
        assert plan is not None and plan.kind == "domain"
        macro_p = None
        if macro:
            batch = pack_macro_batch(encs)
            macro_p = batch["macro_p"]
        else:
            batch = pack_batch(encs)
        ev = batch["events"]
        ok_mono, _ = make_dense_batch_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=macro_p)(ev, plan.val_of)
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=macro_p)
        ok_chunk, _ = _chunked_verdicts(init_fn, step_fn, ev,
                                        batch["n_events"], plan.val_of)
        assert list(np.asarray(ok_mono)) == oracle
        assert list(ok_chunk) == oracle

    @pytest.mark.parametrize("macro", [False, True])
    def test_mask_monolithic_chunked_oracle_identical(self, macro):
        model = Counter()
        encs, oracle = _mixed_batch("counter", model, seed=5)
        plan = dense_plan(model, encs)
        assert plan is not None and plan.kind == "mask"
        macro_p = None
        if macro:
            batch = pack_macro_batch(encs)
            macro_p = batch["macro_p"]
        else:
            batch = pack_batch(encs)
        ev = batch["events"]
        ok_mono, _ = make_dense_batch_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=macro_p)(ev, plan.val_of)
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states,
            macro_p=macro_p)
        ok_chunk, _ = _chunked_verdicts(init_fn, step_fn, ev,
                                        batch["n_events"], plan.val_of)
        assert list(np.asarray(ok_mono)) == oracle
        assert list(ok_chunk) == oracle


class TestSortFamily:
    @pytest.mark.parametrize("macro", [False, True])
    def test_sort_monolithic_chunked_oracle_identical(self, macro):
        model = CasRegister()
        encs, oracle = _mixed_batch("register", model, seed=23)
        W = max(e.n_slots for e in encs)
        macro_p = None
        if macro:
            batch = pack_macro_batch(encs)
            macro_p = batch["macro_p"]
        else:
            batch = pack_batch(encs)
        ev = batch["events"]
        ok_mono, ovf_mono = make_batch_checker(model, n_configs=128,
                                               n_slots=W,
                                               macro_p=macro_p)(ev)
        assert not np.asarray(ovf_mono).any()
        init_fn, step_fn = make_sort_chunk_checker(model, 128, W,
                                                   macro_p=macro_p)
        ok_chunk, ovf_chunk = _chunked_verdicts(init_fn, step_fn, ev,
                                                batch["n_events"])
        assert not ovf_chunk.any()
        assert list(np.asarray(ok_mono)) == oracle
        assert list(ok_chunk) == oracle


class TestIrPieces:
    def test_macro_row_ints_matches_packed_width(self):
        rng = random.Random(2)
        model = CasRegister()
        encs = [encode_history(
            random_valid_history(rng, "register", n_ops=24, n_procs=5),
            model)]
        batch = pack_macro_batch(encs)
        assert batch["events"].shape[2] == \
            kernel_ir.macro_row_ints(batch["macro_p"])
        assert batch["macro_p"] == bucket_opens(
            max_open_run(encs[0].events))

    def test_chunk_step_flags_semantics(self):
        # decided == ~ok and exhausted == (events consumed ≥ n_events):
        # the IR's one definition of the eviction flags.
        model = CasRegister()
        rng = random.Random(3)
        enc = None
        for _ in range(40):  # corrupt() only MAY invalidate — re-roll
            h = corrupt(rng, random_valid_history(rng, "register",
                                                  n_ops=12, n_procs=3,
                                                  crash_p=0.0))
            e = encode_history(h, model)
            if not check_encoded_cpu(e, model).valid:
                enc = e
                break
        assert enc is not None
        plan = dense_plan(model, [enc])
        batch = pack_batch([enc])
        init_fn, step_fn = make_dense_chunk_checker(
            model, plan.kind, plan.n_slots, plan.n_states)
        ev = batch["events"]
        E = ev.shape[1]
        e_pad = ((E + 3) // 4) * 4
        padded = np.zeros((1, e_pad, 5), np.int32)
        padded[:, :E] = ev
        carry = init_fn(plan.val_of, batch["n_events"])
        saw_decided = False
        for lo in range(0, e_pad, 4):
            carry, dec, exh, ok, _ = step_fn(carry, padded, lo, 4)
            dec, ok = np.asarray(dec), np.asarray(ok)
            assert (dec == ~ok).all()
            saw_decided = saw_decided or dec[0]
        assert saw_decided  # the invalid row froze mid-scan
        assert np.asarray(exh)[0]

    def test_carry_bytes_bindings(self):
        # The single-module contract accounting the lint gate executes
        # statically — sanity-pin it dynamically too.
        d = kernel_ir.dense_chunk_carry_bytes(kernel_ir.DENSE_MAX_SLOTS,
                                              kernel_ir.DENSE_MAX_STATES)
        s = kernel_ir.sort_chunk_carry_bytes(
            kernel_ir.SORT_DEFAULT_CONFIGS, kernel_ir.SORT_MAX_SLOTS)
        assert 0 < d <= 16 << 20
        assert 0 < s <= 16 << 20
        # ISSUE 41: the domain frontier is one uint32 word a
        # configuration whatever S, so the widest row's carry is the
        # words (beside the mask family's bool column) plus registers
        # — not 2^W x S cells
        configs = 1 << kernel_ir.DENSE_MAX_SLOTS
        assert 5 * configs < d < 6 * configs
        assert d < configs * kernel_ir.DENSE_MAX_STATES
        assert kernel_ir.macro_row_ints() == 67

    def test_families_reexport_ir_caps(self):
        # Routing layers and tests import caps from their historical
        # sites; those must stay the IR's values (one definition).
        from jepsen_jgroups_raft_tpu.ops import dense_scan, linear_scan

        assert dense_scan.DENSE_MAX_SLOTS is kernel_ir.DENSE_MAX_SLOTS
        assert linear_scan.MAX_SLOTS is kernel_ir.SORT_MAX_SLOTS
        assert linear_scan.DEFAULT_N_CONFIGS is \
            kernel_ir.SORT_DEFAULT_CONFIGS
