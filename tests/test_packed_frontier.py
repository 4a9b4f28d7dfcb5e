"""The domain family's packed frontier (ISSUE 41): one word a
configuration, the states its bits (ops/dense_scan.py `pack_rows`,
`expand_packed`, `dense_step_parts`), held to the representation it
replaced: F [2^W, S] bool, a sweep of W float matmuls. That kernel lives
on HERE, as the oracle (`_float_expand`, `_float_step_parts`: the
parent's code, word for word but for the names).

  (i)   the packed sweep against the float-matmul sweep on random
        frontiers and random NON-deterministic transition matrices, and
        the whole step (legacy and macro rows, hoisted and in-sweep
        styles) against the float step on encoded histories: the same
        frontier bit for bit after every scan.
  (ii)  `kernel_ir.force_arith` on [M] words, [M, 1] and [M, S] bools:
        the same survivors, the same `alive`; since ISSUE 45 (the
        down-shift as W static slices selected by the slot) each
        representation at every window 1-13 and every slot against a
        numpy kill-and-shift written here, alone and under `vmap` with
        a slot a row, and the mask family's arithmetic bit column
        against the table it replaced.
  (iii) whole-history verdicts of `make_dense_history_checker` against
        checker/wgl_cpu.py on seeded register histories with crashed
        ops, windows up to 10.
  (iv)  is tests/test_tpu_compile.py's (one file holds the compiler).
  (v)   the lowering of the batched step (ISSUE 45): nothing in it is
        indexed by a start that differs a row.
"""

import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from util import corrupt, random_valid_history  # noqa: E402

from jepsen_jgroups_raft_tpu.checker.wgl_cpu import (  # noqa: E402
    check_encoded_cpu)
from jepsen_jgroups_raft_tpu.history.packing import (  # noqa: E402
    encode_history, pack_batch, pack_macro_batch)
from jepsen_jgroups_raft_tpu.models import CasRegister  # noqa: E402
from jepsen_jgroups_raft_tpu.ops import dense_scan  # noqa: E402
from jepsen_jgroups_raft_tpu.ops.kernel_ir import (  # noqa: E402
    closure_fixpoint, force_arith, macro_latch_i32, make_stream_step)


# ------------------------------------------------------------ the oracle


def _float_expand(w, F, T_w):
    """The parent's `expand_w`: F [M, S] bool, T_w [S, S'] float32."""
    M, S = F.shape
    Fb = F.reshape(M >> (w + 1), 2, 1 << w, S)
    src = Fb[:, 0].reshape(-1, S).astype(jnp.float32)
    contrib = (src @ T_w).reshape(M >> (w + 1), 1 << w, S) > 0
    return jnp.concatenate(
        [Fb[:, :1], (Fb[:, 1] | contrib)[:, None]], axis=1
    ).reshape(M, S)


def _float_step_parts(model, n_slots, n_states, hoist, macro_p):
    """The parent's `dense_step_parts`: frontier F [2^W, S] bool, the
    hoisted carry T [W, S, S] bool. Returns (init, scan_step)."""
    W, S = int(n_slots), int(n_states)
    M = 1 << W
    slot_ids = jnp.arange(W, dtype=jnp.int32)

    if hoist:
        extra0 = (jnp.zeros((W, S, S), bool),)

        def style_update(extra, upd, f, a, b, val_of):
            (T,) = extra
            ns, legal = model.jax_step(val_of, f, a, b)
            row = (ns[:, None] == val_of[None, :]) & legal[:, None]
            return (jnp.where(upd[:, None, None], row[None], T),)

        def style_macro_latch(extra, eq, upd, pf, pa, pb, val_of):
            (T,) = extra
            ns, legal = jax.vmap(
                lambda f_, a_, b_: model.jax_step(val_of, f_, a_, b_)
            )(pf, pa, pb)
            rows = ((ns[:, :, None] == val_of[None, None, :]) &
                    legal[:, :, None])
            Tnew = jnp.tensordot(eq.astype(jnp.float32),
                                 rows.astype(jnp.float32),
                                 axes=([1], [0])) > 0
            return (jnp.where(upd[:, None, None], Tnew, T),)

        def style_sweep(extra, slot_open, val_of):
            (T,) = extra
            Te = (T & slot_open[:, None, None]).astype(jnp.float32)

            def sweep(F):
                for w in range(W):
                    F = _float_expand(w, F, Te[w])
                return F

            return sweep
    else:
        extra0 = (jnp.zeros((W,), jnp.int32),) * 3

        def style_update(extra, upd, f, a, b, val_of):
            sf, sa, sb = extra
            return (jnp.where(upd, f, sf), jnp.where(upd, a, sa),
                    jnp.where(upd, b, sb))

        def style_macro_latch(extra, eq, upd, pf, pa, pb, val_of):
            sf, sa, sb = extra
            return (macro_latch_i32(eq, upd, sf, pf),
                    macro_latch_i32(eq, upd, sa, pa),
                    macro_latch_i32(eq, upd, sb, pb))

        def style_sweep(extra, slot_open, val_of):
            sf, sa, sb = extra

            def sweep(F):
                for w in range(W):
                    ns, legal = model.jax_step(val_of, sf[w], sa[w],
                                               sb[w])
                    T_w = ((ns[:, None] == val_of[None, :]) &
                           legal[:, None] &
                           slot_open[w]).astype(jnp.float32)
                    F = _float_expand(w, F, T_w)
                return F

            return sweep

    def latch(carry, slot, f, a, b, is_open, upd):
        F, extra, slot_open, ok, dirty, val_of = carry
        extra = style_update(extra, upd, f, a, b, val_of)
        slot_open = jnp.where(upd, True, slot_open)
        return (F, extra, slot_open, ok, dirty | is_open, val_of)

    def macro_latch(carry, pslot, pf, pa, pb, valid, n, eq, upd):
        F, extra, slot_open, ok, dirty, val_of = carry
        extra = style_macro_latch(extra, eq, upd, pf, pa, pb, val_of)
        return (F, extra, slot_open | upd, ok, dirty | (n > 0), val_of)

    def force_tail(carry, is_force, slot):
        F, extra, slot_open, ok, dirty, val_of = carry
        F = closure_fixpoint(W, style_sweep(extra, slot_open, val_of),
                             F, is_force & dirty)
        dirty = dirty & ~is_force
        F_forced, alive = force_arith(F, jnp.clip(slot, 0, W - 1))
        F = jnp.where(is_force, F_forced, F)
        ok = ok & (~is_force | alive)
        slot_open = slot_open & ~((slot_ids == slot) & is_force)
        return (F, extra, slot_open, ok, dirty, val_of)

    def init(val_of):
        F = jnp.zeros((M, S), dtype=bool).at[0, 0].set(True)
        return (F, extra0, jnp.zeros((W,), bool),
                jnp.bool_(True), jnp.bool_(False), val_of)

    return init, make_stream_step(W, latch, macro_latch, force_tail,
                                  macro_p)


def _unpack(F, S):
    """[..., M] words -> [..., M, S] bool."""
    F = np.asarray(F).astype(np.int64)
    return ((F[..., None] >> np.arange(S)) & 1).astype(bool)


def _unpack_rows(R, S):
    """`pack_rows`' words [..., n] -> the rows [..., S, S'] bool: row s
    lies in field s % g of word s // g."""
    width, g = dense_scan._fields(S)
    R = np.asarray(R).astype(np.int64)
    rows = np.stack([(R[..., s // g] >> ((s % g) * width)) & ((1 << width)
                                                              - 1)
                     for s in range(S)], axis=-1)
    return _unpack(rows, S)


def _pack(Fb):
    """[M, S] bool -> [M] words of the kernel's dtype."""
    S = Fb.shape[-1]
    ints = (np.asarray(Fb, np.int64) << np.arange(S)).sum(-1)
    return jnp.asarray(ints, dense_scan.FRONTIER_WORD)


# ------------------------------------------------------- (i) the sweep


@pytest.mark.parametrize("S", [2, 5, 8, 16])
@pytest.mark.parametrize("W", [3, 4, 5, 6, 7, 8])
def test_packed_sweep_equals_float_matmul_sweep(W, S):
    """Random frontiers, random non-deterministic transition matrices
    (a state may step to none, one or several), gated slots among
    them: W expansions chained, then the fixpoint."""
    rng = np.random.default_rng(1000 * W + S)
    M = 1 << W
    for density in (0.05, 0.3, 0.7):
        Fb = rng.random((M, S)) < density
        T = rng.random((W, S, S)) < rng.choice([0.1, 0.3, 0.6])
        T[rng.integers(W)] = False  # a closed slot: rows of zeros
        R = dense_scan.pack_rows(jnp.asarray(T))
        width, g = dense_scan._fields(S)
        assert g * width <= 32 and 2 * g <= max(width, 2)
        assert R.shape == (W, dense_scan.row_words(S))
        assert R.dtype == dense_scan.FRONTIER_WORD
        assert (_unpack_rows(R, S) == T).all()
        Tf = jnp.asarray(T, jnp.float32)

        def packed_sweep(F):
            for w in range(W):
                F = dense_scan.expand_packed(w, F, R[w], S)
            return F

        def float_sweep(F):
            for w in range(W):
                F = _float_expand(w, F, Tf[w])
            return F

        Fp, Ff = _pack(Fb), jnp.asarray(Fb)
        for w in range(W):  # one expansion at a time
            one = dense_scan.expand_packed(w, Fp, R[w], S)
            assert (_unpack(one, S) == np.asarray(
                _float_expand(w, Ff, Tf[w]))).all(), (w, density)
        assert (_unpack(jax.jit(packed_sweep)(Fp), S)
                == np.asarray(float_sweep(Ff))).all()
        closed_p = closure_fixpoint(W, packed_sweep, Fp, jnp.bool_(True))
        closed_f = closure_fixpoint(W, float_sweep, Ff, jnp.bool_(True))
        assert (_unpack(closed_p, S) == np.asarray(closed_f)).all()


def _register_batch(seed, n=6, n_ops=24, max_crashes=2, n_procs=3):
    rng = random.Random(seed)
    model = CasRegister()
    hists = [random_valid_history(rng, "register", n_ops=n_ops,
                                  n_procs=n_procs, value_range=4,
                                  crash_p=0.15, max_crashes=max_crashes)
             for _ in range(n)]
    hists[1::2] = [corrupt(rng, h) for h in hists[1::2]]
    return model, [encode_history(h, model) for h in hists]


@pytest.mark.parametrize("hoist", [True, False],
                         ids=["hoisted", "in-sweep"])
@pytest.mark.parametrize("macro", [False, True], ids=["legacy", "macro"])
@pytest.mark.parametrize("W,S", [(5, 8), (6, 16), (7, 8), (8, 16)])
def test_packed_step_equals_float_step(W, S, macro, hoist):
    """The whole step over encoded histories (valid and corrupted,
    crashed ops, launched wider than they need: padded ids repeat the
    initial value, so rows are non-deterministic): the carried frontier
    and `ok` equal the float kernel's after the scan."""
    model, encs = _register_batch(seed=41 * W + S)
    plan = dense_scan.dense_plan(model, encs)
    assert plan.kind == "domain" and plan.n_slots <= W
    assert plan.n_states <= S
    val_of = np.concatenate(
        [plan.val_of, np.repeat(plan.val_of[:, :1], S - plan.n_states,
                                axis=1)], axis=1)
    if macro:
        batch = pack_macro_batch(encs)
        macro_p = batch["macro_p"]
    else:
        batch, macro_p = pack_batch(encs), None
    events = jnp.asarray(batch["events"])

    init_p, step_p, verdict_p = dense_scan.dense_step_parts(
        model, W, S, hoist=hoist, macro_p=macro_p)
    init_f, step_f = _float_step_parts(model, W, S, hoist, macro_p)

    def run(init, step):
        return jax.jit(jax.vmap(
            lambda ev, v: lax.scan(step, init(v), ev)[0]))(events, val_of)

    cp, cf = run(init_p, step_p), run(init_f, step_f)
    assert cp[0].shape == (len(encs), 1 << W)
    assert (_unpack(cp[0], S) == np.asarray(cf[0])).all()
    assert (np.asarray(cp[3]) == np.asarray(cf[3])).all()       # ok
    assert (np.asarray(cp[2]) == np.asarray(cf[2])).all()       # open
    oracle = [check_encoded_cpu(e, model).valid for e in encs]
    assert list(np.asarray(jax.vmap(verdict_p)(cp)[0])) == oracle
    if hoist:  # the carried row masks are the carried matrices, packed
        assert (_unpack_rows(cp[1][0], S) == np.asarray(cf[1][0])).all()


# --------------------------------------------------- (ii) force_arith


@pytest.mark.parametrize("W", [1, 3, 6, 9])
def test_force_arith_agrees_across_representations(W):
    """[M] words, [M, S] bool and [M, 1] bool: the same survivors
    moved to the same places, the same `alive`, for every slot."""
    rng = np.random.default_rng(W)
    M, S = 1 << W, 8
    for slot in range(W):
        for density in (0.0, 0.02, 0.5):
            Fb = rng.random((M, S)) < density
            if density and slot % 2:
                # survivors only where the forced bit is missing
                Fb[((np.arange(M) >> slot) & 1) == 1] = False
            Fw, alive_w = force_arith(_pack(Fb), jnp.int32(slot))
            Fs, alive_s = force_arith(jnp.asarray(Fb), jnp.int32(slot))
            assert Fw.dtype == dense_scan.FRONTIER_WORD
            assert (_unpack(Fw, S) == np.asarray(Fs)).all()
            assert bool(alive_w) == bool(alive_s)
            # the mask family's [M, 1]: a configuration is alive where
            # any of its states is
            F1 = Fb.any(axis=1, keepdims=True)
            F1f, alive_1 = force_arith(jnp.asarray(F1), jnp.int32(slot))
            assert F1f.shape == (M, 1) and F1f.dtype == bool
            assert (np.asarray(F1f)[:, 0]
                    == np.asarray(Fs).any(axis=1)).all()
            assert bool(alive_1) == bool(alive_s)


def test_force_arith_under_vmap_takes_a_slot_a_row():
    rng = np.random.default_rng(7)
    W, S, B = 5, 4, 6
    Fb = rng.random((B, 1 << W, S)) < 0.3
    slots = jnp.asarray(rng.integers(0, W, B), jnp.int32)
    Fw, aw = jax.vmap(force_arith)(jnp.stack([_pack(f) for f in Fb]),
                                   slots)
    Fs, as_ = jax.vmap(force_arith)(jnp.asarray(Fb), slots)
    assert (_unpack(Fw, S) == np.asarray(Fs)).all()
    assert (np.asarray(aw) == np.asarray(as_)).all()


def _np_force(F, slot):
    """FORCE on a numpy frontier, configuration axis leading: a
    configuration without bit `slot` takes over its twin with the bit
    (the op linearized, its bit recycled), one with it dies."""
    M = F.shape[0]
    ids = np.arange(M)
    without = ids[((ids >> slot) & 1) == 0]
    out = np.zeros_like(F)
    out[without] = F[without | (1 << slot)]
    return out, bool(F[((ids >> slot) & 1) == 1].any())


def _random_frontier(rng, rep, shape_m):
    """A frontier of one of the three representations `force_arith`
    takes, as numpy: [M] words, [M, 1] bool, [M, S] bool."""
    if rep == "words":
        return rng.integers(0, 1 << 8, shape_m).astype(np.uint32) * (
            rng.random(shape_m) < 0.5)
    return rng.random(shape_m + ((1,) if rep == "mask" else (4,))) < 0.4


REPRESENTATIONS = ["words", "mask", "states"]


@pytest.mark.parametrize("rep", REPRESENTATIONS)
@pytest.mark.parametrize("W", range(1, 14))
def test_force_arith_is_kill_and_shift_at_every_slot(W, rep):
    """ISSUE 45: every window the dense families serve, every slot, all
    three representations, against the numpy oracle above."""
    rng = np.random.default_rng(100 * W + len(rep))
    force = jax.jit(force_arith)
    for slot in range(W):
        F = _random_frontier(rng, rep, (1 << W,))
        if slot % 3 == 2:  # nothing holds the forced bit: all die
            F[((np.arange(1 << W) >> slot) & 1) == 1] = 0
        got, alive = force(jnp.asarray(F), jnp.int32(slot))
        want, want_alive = _np_force(F, slot)
        assert got.dtype == F.dtype and got.shape == F.shape
        assert (np.asarray(got) == want).all(), (W, slot)
        assert bool(alive) == want_alive, (W, slot)


@pytest.mark.parametrize("rows", [1, 8, 128])
@pytest.mark.parametrize("rep", REPRESENTATIONS)
@pytest.mark.parametrize("W", [1, 2, 5, 8, 11, 13])
def test_force_arith_under_vmap_with_a_slot_a_row(W, rep, rows):
    """The launch's form: one slot a row (1 row is the LONG launch's, 8
    and 128 the batched buckets' ends), every slot among them."""
    rng = np.random.default_rng(1000 * W + 10 * rows + len(rep))
    F = _random_frontier(rng, rep, (rows, 1 << W))
    slots = (np.arange(rows) + rng.integers(0, W)) % W
    got, alive = jax.jit(jax.vmap(force_arith))(
        jnp.asarray(F), jnp.asarray(slots, jnp.int32))
    for r in range(rows):
        want, want_alive = _np_force(F[r], int(slots[r]))
        assert (np.asarray(got[r]) == want).all(), (W, r, slots[r])
        assert bool(alive[r]) == want_alive, (W, r, slots[r])


@pytest.mark.parametrize("W", range(1, 14))
def test_bit_column_is_the_bit_table(W):
    """The mask family's `(ids >> slot) & 1` against the constant
    [M, W] table a `take` used to read, for every slot, and as [M, P]
    columns for a macro row's payload slots."""
    M = 1 << W
    table = (np.arange(M)[:, None] >> np.arange(W)[None, :]) & 1
    for slot in range(W):
        col = dense_scan.bit_column(M, jnp.int32(slot))
        assert col.shape == (M,) and col.dtype == jnp.int32
        assert (np.asarray(col) == table[:, slot]).all()
    pslot = np.arange(4) % W
    cols = jax.jit(lambda p: dense_scan.bit_column(M, p))(
        jnp.asarray(pslot, jnp.int32))
    assert cols.shape == (M, 4)
    assert (np.asarray(cols) == table[:, pslot]).all()
    per_row = jax.vmap(lambda s: dense_scan.bit_column(M, s))(
        jnp.arange(W, dtype=jnp.int32))
    assert (np.asarray(per_row) == table.T).all()


# ------------------------------------------- (iii) whole histories


@pytest.mark.parametrize("macro", [False, True], ids=["legacy", "macro"])
@pytest.mark.parametrize("max_crashes,seed", [(0, 3), (2, 5), (4, 7),
                                              (5, 11), (6, 13), (6, 32)])
def test_history_checker_verdicts_equal_wgl_cpu(max_crashes, seed, macro):
    """Seeded register histories whose crashed ops hold their slots to
    the end (windows up to 10), half of them corrupted: the monolithic
    driver's verdicts are wgl_cpu's."""
    model, encs = _register_batch(seed, n=6, n_ops=40,
                                  max_crashes=max_crashes, n_procs=4)
    plan = dense_scan.dense_plan(model, encs)
    assert plan is not None and plan.kind == "domain"
    assert plan.n_slots <= 10
    if max_crashes >= 4:
        assert plan.n_slots >= 6
    if seed == 32:
        assert plan.n_slots == 10  # the widest the CPU case reaches
    if macro:
        batch = pack_macro_batch(encs)
        macro_p = batch["macro_p"]
    else:
        batch, macro_p = pack_batch(encs), None
    check = jax.jit(jax.vmap(dense_scan.make_dense_history_checker(
        model, plan.n_slots, plan.n_states, macro_p=macro_p)))
    ok, overflow = check(batch["events"], plan.val_of)
    assert not np.asarray(overflow).any()
    oracle = [check_encoded_cpu(e, model).valid for e in encs]
    assert list(np.asarray(ok)) == oracle
    assert True in oracle


def test_init_is_bit_zero_of_word_zero():
    init, _, verdict = dense_scan.dense_step_parts(CasRegister(), 4, 8)
    carry = init(jnp.zeros((8,), jnp.int32))
    F = np.asarray(carry[0])
    assert F.shape == (16,) and F.dtype == np.dtype(
        dense_scan.FRONTIER_WORD)
    assert F[0] == 1 and not F[1:].any()
    assert bool(verdict(carry)[0])


def test_frontier_word_holds_the_state_cap():
    from jepsen_jgroups_raft_tpu.ops.kernel_ir import DENSE_MAX_STATES

    assert np.iinfo(dense_scan.FRONTIER_WORD).bits >= DENSE_MAX_STATES
    # a full row of the widest domain survives the packing
    rows = jnp.ones((DENSE_MAX_STATES, DENSE_MAX_STATES), bool)
    assert _unpack_rows(dense_scan.pack_rows(rows),
                        DENSE_MAX_STATES).all()


# ------------------------------------- (v) the batched step's lowering


@pytest.mark.parametrize("macro", [False, True], ids=["legacy", "macro"])
@pytest.mark.parametrize("kind", ["domain", "mask"])
def test_batched_step_indexes_nothing_by_a_start_a_row(kind, macro):
    """ISSUE 45: the StableHLO of the vmapped `step_one` of the W 8
    keys the cells launch. A `dynamic_slice` or `take` whose index
    differs a row lowers to a `stablehlo.gather` with a start a row,
    and the TPU's compiler runs that as a loop over the rows of the
    launch (73 % of the device's time until this issue). What may stay
    is the event fetch: `dynamic_index_in_dim` at `lo + i`, ONE start
    for every row (under `vmap` a gather whose one slice spans the
    rows)."""
    import re

    from jepsen_jgroups_raft_tpu.models import Counter
    from jepsen_jgroups_raft_tpu.ops.kernel_ir import macro_row_ints

    rows, W, E = 16, 8, 64
    model, S = (CasRegister(), 8) if kind == "domain" else (Counter(), 1)
    macro_p = 4 if macro else None
    lanes = macro_row_ints(macro_p) if macro else 5
    init_fn, step_fn = dense_scan.make_dense_chunk_checker(
        model, kind, W, S, macro_p=macro_p)
    carry = jax.eval_shape(init_fn,
                           jax.ShapeDtypeStruct((rows, S), jnp.int32),
                           jax.ShapeDtypeStruct((rows,), jnp.int32))
    text = step_fn.lower(
        carry, jax.ShapeDtypeStruct((rows, E, lanes), jnp.int32),
        np.int32(0), np.int32(E)).as_text()
    gathers = re.findall(r'"stablehlo\.gather"\(.*', text)
    assert len(gathers) == 1, gathers
    # the fetch: one index vector, a slice that holds every row's event
    assert f"slice_sizes = array<i64: {rows}, 1, {lanes}>" in gathers[0]
    assert re.search(r"tensor<2xi32>\) -> tensor<%dx1x%dxi32>"
                     % (rows, lanes), gathers[0]), gathers[0]
    for op in ("stablehlo.dynamic_slice", "stablehlo.dynamic_gather",
               "stablehlo.dynamic_update_slice", "stablehlo.scatter",
               "stablehlo.case"):
        assert op not in text, op
    # the span's loop and the closure's: no third
    assert text.count("stablehlo.while") == 2
