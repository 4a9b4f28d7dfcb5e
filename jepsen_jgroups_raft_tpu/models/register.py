"""Compare-and-set register model.

Equivalent of knossos.model/cas-register as used by the reference's register
workload (reference workload/register.clj:106-111): ops are read / write /
cas over a single register whose initial value is nil.

Completion semantics mirror the reference client:
  * reads are idempotent, so indefinite failures were already turned into
    ``fail`` by the error taxonomy (register.clj:72) — an info read carries
    no constraint and is dropped here too;
  * a CAS that returned false is recorded ``fail`` ``:cas-fail``
    (register.clj:82-84) and dropped — it never mutated the register;
  * info writes/cas may or may not have applied: optional ops.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..history.ops import FAIL, INFO, OK, OpPair
from .base import NIL, EncodedOp, Model, _i32

READ = 0
WRITE = 1
CAS = 2

F_NAMES = {"read": READ, "write": WRITE, "cas": CAS}


class CasRegister(Model):
    name = "cas-register"
    n_fcodes = 3
    readonly_fcodes = (READ,)

    def __init__(self, initial: Optional[int] = None):
        self.initial = NIL if initial is None else _i32(initial)

    def init_state(self) -> int:
        return self.initial

    def step(self, state, f, a, b):
        if f == READ:
            return state, state == a
        if f == WRITE:
            return a, True
        if f == CAS:
            if state == a:
                return b, True
            return state, False
        raise ValueError(f"bad opcode {f}")

    def jax_step(self, state, f, a, b):
        is_write = f == WRITE
        is_cas = f == CAS
        match = state == a
        legal = is_write | match  # read/cas legal iff observed/from matches
        new_state = jnp.where(
            is_write, a, jnp.where(is_cas & match, b, state)
        )
        return new_state, legal

    def step_columnar(self, state, f, a, b):
        """Numpy batch twin of `step` (models/base.py contract): same
        select logic as `jax_step`, host-side."""
        import numpy as np

        is_write = f == WRITE
        is_cas = f == CAS
        match = state == a
        legal = is_write | match
        new_state = np.where(is_write, a,
                             np.where(is_cas & match, b, state))
        return new_state.astype(np.int32), legal

    def dense_domain(self, events):
        """Reachable register values: initial ∪ {a of writes} ∪ {b of cas}
        (a write sets a; a successful cas sets b; reads keep state). Read
        expectations outside this set simply never match — the config dies
        at that read's FORCE, which is the correct verdict."""
        import numpy as np

        from ..history.packing import EV_OPEN

        opens = events[events[:, 0] == EV_OPEN]
        vals = {int(self.initial)}
        vals.update(int(v) for v in opens[opens[:, 2] == WRITE][:, 3])
        vals.update(int(v) for v in opens[opens[:, 2] == CAS][:, 4])
        return [int(self.initial)] + sorted(vals - {int(self.initial)})

    def dense_domains(self, encs):
        """`dense_domain` of every row of a launch in ONE pass over the
        rows' concatenated events: the (row, value) pairs of writes' a
        and cas' b, deduplicated and sorted by one `np.unique` over
        row << 32 | (value − INT32_MIN), split at the row boundaries.
        Each list is [initial] + the ascending signed values without
        the initial — `dense_domain`'s, value for value."""
        import numpy as np

        from ..history.packing import EV_OPEN

        B = len(encs)
        if not B:
            return []
        lo = np.iinfo(np.int32).min
        cat = np.concatenate([e.events for e in encs])
        rid = np.repeat(np.arange(B, dtype=np.int64),
                        [len(e.events) for e in encs])
        et, f = cat[:, 0], np.ascontiguousarray(cat[:, 2])
        opens = et == EV_OPEN
        w = np.flatnonzero(opens & (f == WRITE))
        c = np.flatnonzero(opens & (f == CAS))
        vals = np.concatenate([cat[:, 3].take(w), cat[:, 4].take(c)])
        keys = np.unique(
            (np.concatenate([rid.take(w), rid.take(c)]) << 32)
            | (vals.astype(np.int64) - lo))
        initial = int(self.initial)
        row, val = keys >> 32, (keys & 0xFFFFFFFF) + lo
        keep = val != initial
        ends = np.cumsum(np.bincount(row[keep], minlength=B)).tolist()
        val = val[keep].tolist()
        return [[initial] + val[a:b]
                for a, b in zip([0] + ends[:-1], ends)]

    def enable_values(self, enc: EncodedOp):
        """Linearizing a write exposes state a; a cas exposes its
        to-value b; a read exposes nothing."""
        if enc.f == WRITE:
            return (enc.a,)
        if enc.f == CAS:
            return (enc.b,)
        return ()

    def observe_values(self, enc: EncodedOp):
        """A read is legal iff the state equals its returned value; a
        cas iff the state equals its from-value; a write observes
        nothing (unconditionally legal)."""
        if enc.f == READ:
            return (enc.a,)
        if enc.f == CAS:
            return (enc.a,)
        return ()

    def rw_classify(self, f: int, a: int, b: int):
        """Cycle-tier roles (models/base.py contract — the register IS
        a last-writer-wins cell): READ observes a, WRITE exposes a, CAS
        observes a then exposes b. Every encoded register op
        classifies, so register histories always build a graph."""
        if f == READ:
            return ("r", a)
        if f == WRITE:
            return ("w", a)
        if f == CAS:
            return ("rw", a, b)
        return None

    def _encode(self, pair: OpPair) -> Optional[EncodedOp]:
        f = pair.f
        forced = pair.ctype == OK
        if f == "read":
            if not forced:
                return None  # unknown read constrains nothing
            value = pair.completion.value
            return EncodedOp(READ, _i32(value), 0, True)
        if f == "write":
            return EncodedOp(WRITE, _i32(pair.invoke.value), 0, forced)
        if f == "cas":
            frm, to = pair.invoke.value
            return EncodedOp(CAS, _i32(frm), _i32(to), forced)
        raise ValueError(f"cas-register: unknown op f={f!r}")

    def encode_pairs_columnar(self, pairs):
        """Tight-loop twin of `_encode` (see Model.encode_pairs_columnar;
        differential tests pin the two byte-identical)."""
        fs, as_, bs = [], [], []
        forced, ips, cps = [], [], []
        i32 = _i32
        for ip, cp, inv, comp in pairs:
            ctype = comp.type if comp is not None else INFO
            if ctype == FAIL:
                continue
            fo = ctype == OK
            f = inv.f
            if f == "read":
                if not fo:
                    continue  # unknown read constrains nothing
                fs.append(READ)
                as_.append(i32(comp.value))
                bs.append(0)
            elif f == "write":
                fs.append(WRITE)
                as_.append(i32(inv.value))
                bs.append(0)
            elif f == "cas":
                frm, to = inv.value
                fs.append(CAS)
                as_.append(i32(frm))
                bs.append(i32(to))
            else:
                raise ValueError(f"cas-register: unknown op f={f!r}")
            forced.append(fo)
            ips.append(ip)
            cps.append(cp)
        return fs, as_, bs, forced, ips, cps

    def prune_observe_enable(self, fs, as_, bs):
        """Columnar enable/observe (singletons): write enables a, cas
        enables b; read observes a, cas observes a (mirrors
        enable_values/observe_values exactly)."""
        import numpy as np

        f = np.asarray(fs, dtype=np.int32)
        a = np.asarray(as_, dtype=np.int32)
        b = np.asarray(bs, dtype=np.int32)
        enable_has = f != READ
        enable_val = np.where(f == CAS, b, a)
        observe_has = f != WRITE
        observe_val = a
        return enable_val, enable_has, observe_val, observe_has
