"""Long-history scaling: BASELINE.json configs #4 and #5 at suite-friendly
sizes. The checker's event scan is linear in
history length with fixed frontier width, so these must stay seconds-fast
— the axis the reference's checker could not scale on (doc/intro.md:35-41,
SURVEY.md §5.7)."""

import random


from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.history.ops import OK
from jepsen_jgroups_raft_tpu.history.synth import (build_history,
                                                   random_valid_history)
from jepsen_jgroups_raft_tpu.models.register import CasRegister


def test_independent_10k_op_histories_verify():
    """Config #4 shape: multi-key independent histories, 10k ops each."""
    rng = random.Random(4)
    model = CasRegister()
    hs = [random_valid_history(rng, "register", n_ops=10_000, n_procs=5,
                               crash_p=0.02, max_crashes=4)
          for _ in range(2)]
    res = check_histories(hs, model, algorithm="jax")
    assert all(r["valid?"] is True for r in res)
    assert all(r["algorithm"] == "jax" for r in res)


def test_single_50k_op_history_verifies():
    """Config #5 shape: one long register history through the scan kernel."""
    rng = random.Random(5)
    model = CasRegister()
    h = random_valid_history(rng, "register", n_ops=50_000, n_procs=5,
                             crash_p=0.01, max_crashes=4)
    res = check_histories([h], model, algorithm="jax")
    assert res[0]["valid?"] is True
    assert res[0]["algorithm"] == "jax"


def test_long_history_catches_late_violation():
    """A single stale read buried at the END of a long history must flip
    the verdict — no silent truncation of the tail."""
    rng = random.Random(6)
    model = CasRegister()
    h = random_valid_history(rng, "register", n_ops=3_000, n_procs=5,
                             crash_p=0.0)
    rows = [(o.process, o.type, o.f, o.value) for o in h]
    # find the last completed write and append a contradicting read
    last_w = next(v for p, t, f, v in reversed(rows)
                  if t == OK and f == "write")
    rows += [(0, "invoke", "read", None), (0, OK, "read", last_w + 17)]
    bad = build_history(rows)
    res = check_histories([bad], model, algorithm="jax")
    assert res[0]["valid?"] is False
