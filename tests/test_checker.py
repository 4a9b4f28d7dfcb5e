"""Checker tests: golden histories (the reference's raft_test.clj strategy —
tiny adversarial histories through the production checker, SURVEY.md §4),
plus differential tests of brute-force vs CPU frontier vs TPU kernel."""

import os
import random

import numpy as np
import pytest

from jepsen_jgroups_raft_tpu.checker.brute import check_brute
from jepsen_jgroups_raft_tpu.checker.dfs_cpu import check_encoded_dfs
from jepsen_jgroups_raft_tpu.checker.linearizable import check_histories
from jepsen_jgroups_raft_tpu.checker.wgl_cpu import check_encoded_cpu
from jepsen_jgroups_raft_tpu.history.ops import INFO, INVOKE, OK, FAIL
from jepsen_jgroups_raft_tpu.history.packing import (
    EV_FORCE,
    EV_OPEN,
    encode_history,
    pack_batch,
)
from jepsen_jgroups_raft_tpu.models import CasRegister, Counter
from jepsen_jgroups_raft_tpu.ops.linear_scan import make_batch_checker

from util import H, corrupt, random_valid_history


def cpu_check(hist, model):
    return check_encoded_cpu(encode_history(hist, model), model).valid


def jax_check(hist, model, n_configs=64):
    enc = encode_history(hist, model)
    batch = pack_batch([enc])
    kernel = make_batch_checker(model, n_configs=n_configs, n_slots=8)
    ok, overflow = kernel(batch["events"])
    assert not bool(overflow[0]), "unexpected frontier overflow in test"
    return bool(ok[0])


# ---------------------------------------------------------------- golden --
# Counter goldens mirror the semantics pinned by the reference's unit tests
# (test/jepsen/jgroups/raft_test.clj via SURVEY.md §4): interleaved ops with
# an unapplied info op must pass; a stale read must fail; an info op that
# *was* applied plus a later contradicting read must fail.


class TestCounterGoldens:
    def test_valid_interleaved_with_unapplied_info(self):
        h = H(
            (0, INVOKE, "add", 1),
            (1, INVOKE, "read", None),
            (1, OK, "read", 0),          # read before the add applied
            (0, OK, "add", 1),
            (2, INVOKE, "add", 2),       # crashes: never completes
            (3, INVOKE, "read", None),
            (3, OK, "read", 1),          # consistent iff crashed add unapplied
        )
        m = Counter()
        assert check_brute(h, m) is True
        assert cpu_check(h, m) is True
        assert jax_check(h, m) is True

    def test_invalid_stale_read(self):
        h = H(
            (0, INVOKE, "add", 1),
            (0, OK, "add", 1),
            (1, INVOKE, "read", None),
            (1, OK, "read", 0),          # stale: add already completed
        )
        m = Counter()
        assert check_brute(h, m) is False
        assert cpu_check(h, m) is False
        assert jax_check(h, m) is False

    def test_invalid_applied_info_then_contradicting_read(self):
        h = H(
            (0, INVOKE, "add", 1),
            (0, INFO, "add", 1),         # unknown: may have applied
            (1, INVOKE, "read", None),
            (1, OK, "read", 1),          # proves it DID apply
            (2, INVOKE, "read", None),
            (2, OK, "read", 0),          # ...then contradicts it
        )
        m = Counter()
        assert check_brute(h, m) is False
        assert cpu_check(h, m) is False
        assert jax_check(h, m) is False

    def test_add_and_get_constrains(self):
        h = H(
            (0, INVOKE, "add-and-get", 2),
            (0, OK, "add-and-get", (2, 2)),
            (1, INVOKE, "add-and-get", 3),
            (1, OK, "add-and-get", (3, 6)),   # should be 5
        )
        m = Counter()
        assert check_brute(h, m) is False
        assert cpu_check(h, m) is False
        assert jax_check(h, m) is False


class TestRegisterGoldens:
    def test_read_of_never_written_value(self):
        h = H(
            (0, INVOKE, "write", 1),
            (0, OK, "write", 1),
            (1, INVOKE, "read", None),
            (1, OK, "read", 2),
        )
        m = CasRegister()
        assert check_brute(h, m) is False
        assert cpu_check(h, m) is False
        assert jax_check(h, m) is False

    def test_concurrent_write_read_either_value_ok(self):
        for observed in (None, 7):
            h = H(
                (0, INVOKE, "write", 7),
                (1, INVOKE, "read", None),
                (1, OK, "read", observed),
                (0, OK, "write", 7),
            )
            m = CasRegister()
            assert check_brute(h, m) is True
            assert cpu_check(h, m) is True
            assert jax_check(h, m) is True

    def test_cas_chain(self):
        h = H(
            (0, INVOKE, "write", 0),
            (0, OK, "write", 0),
            (1, INVOKE, "cas", (0, 3)),
            (1, OK, "cas", True),
            (2, INVOKE, "read", None),
            (2, OK, "read", 3),
        )
        m = CasRegister()
        assert cpu_check(h, m) is True
        assert jax_check(h, m) is True

    def test_info_write_observed_later_is_valid(self):
        h = H(
            (0, INVOKE, "write", 5),
            (0, INFO, "write", 5),
            (1, INVOKE, "read", None),
            (1, OK, "read", 5),
        )
        m = CasRegister()
        assert check_brute(h, m) is True
        assert cpu_check(h, m) is True
        assert jax_check(h, m) is True

    def test_info_write_must_not_be_required_twice(self):
        # info write observed, then old value read again: invalid
        h = H(
            (0, INVOKE, "write", 1),
            (0, OK, "write", 1),
            (1, INVOKE, "write", 5),
            (1, INFO, "write", 5),
            (2, INVOKE, "read", None),
            (2, OK, "read", 5),
            (3, INVOKE, "read", None),
            (3, OK, "read", 1),
        )
        m = CasRegister()
        assert check_brute(h, m) is False
        assert cpu_check(h, m) is False
        assert jax_check(h, m) is False


# -------------------------------------------------------------- packing --


class TestPacking:
    def test_slot_recycling_and_events(self):
        h = H(
            (0, INVOKE, "write", 1),
            (0, OK, "write", 1),
            (1, INVOKE, "write", 2),
            (1, OK, "write", 2),
        )
        enc = encode_history(h, CasRegister())
        # sequential ops share one slot
        assert enc.n_slots == 1
        assert enc.events[:, 0].tolist() == [EV_OPEN, EV_FORCE, EV_OPEN, EV_FORCE]
        assert enc.n_ops == 2

    def test_concurrency_window(self):
        h = H(
            (0, INVOKE, "write", 1),
            (1, INVOKE, "write", 2),
            (2, INVOKE, "write", 3),
            (2, OK, "write", 3),
            (1, OK, "write", 2),
            (0, OK, "write", 1),
        )
        enc = encode_history(h, CasRegister())
        assert enc.n_slots == 3

    def test_fail_dropped(self):
        h = H(
            (0, INVOKE, "cas", (0, 1)),
            (0, FAIL, "cas", (0, 1)),
        )
        enc = encode_history(h, CasRegister())
        assert enc.n_events == 0
        assert enc.n_ops == 0

    def test_pack_batch_pads(self):
        h1 = H((0, INVOKE, "write", 1), (0, OK, "write", 1))
        h2 = H(
            (0, INVOKE, "write", 1), (0, OK, "write", 1),
            (1, INVOKE, "read", None), (1, OK, "read", 1),
        )
        m = CasRegister()
        batch = pack_batch([encode_history(h1, m), encode_history(h2, m)])
        assert batch["events"].shape == (2, 4, 5)
        assert batch["n_events"].tolist() == [2, 4]
        # padding rows are EV_PAD
        assert batch["events"][0, 2:, 0].tolist() == [0, 0]


# --------------------------------------------------------- differential --


@pytest.mark.parametrize("model_kind", ["register", "counter"])
def test_differential_random_histories(model_kind):
    """brute == cpu == jax on randomized small histories, valid + corrupted."""
    rng = random.Random(42)
    model = CasRegister() if model_kind == "register" else Counter()
    n_mismatch = 0
    cases = []
    for trial in range(120):
        h = random_valid_history(rng, model_kind, n_ops=7, n_procs=3)
        if trial % 2:
            h = corrupt(rng, h)
        cases.append(h)
    kernel = make_batch_checker(model, n_configs=128, n_slots=8)
    encs = [encode_history(h, model) for h in cases]
    nonempty = [i for i, e in enumerate(encs) if e.n_events > 0]
    batch = pack_batch([encs[i] for i in nonempty])
    ok, overflow = kernel(batch["events"])
    ok = np.asarray(ok)
    assert not np.asarray(overflow).any()
    jax_verdicts = {i: bool(ok[j]) for j, i in enumerate(nonempty)}
    for i, h in enumerate(cases):
        expected = check_brute(h, model)
        got_cpu = check_encoded_cpu(encs[i], model).valid
        assert got_cpu == expected, f"cpu mismatch on case {i}"
        got_dfs = check_encoded_dfs(encs[i], model).valid
        assert got_dfs == expected, f"dfs mismatch on case {i}"
        got_jax = jax_verdicts.get(i, True)
        assert got_jax == expected, f"jax mismatch on case {i}"


def _cas_chain_history(width, procs_offset=0, break_at=None):
    """`width` mutually-concurrent cas ops chained 0→1→…→width, all invoked
    before any completes (concurrency window = width). From state k only
    cas(k→k+1) is legal, so the frontier stays ≈width+1 configs — wide
    window WITHOUT frontier explosion, isolating the multi-word-mask path.
    break_at=j makes cas_j expect the wrong from-value (invalid history)."""
    from jepsen_jgroups_raft_tpu.history.ops import Op

    rows = [Op(500, INVOKE, "write", 0), Op(500, OK, "write", 0)]
    for i in range(width):
        frm = i if break_at != i else i + 500  # unsatisfiable from-value
        rows.append(Op(procs_offset + i, INVOKE, "cas", (frm, i + 1)))
    for i in range(width):
        rows.append(Op(procs_offset + i, OK, "cas",
                       (i if break_at != i else i + 500, i + 1)))
    return rows


@pytest.mark.parametrize("width", [40, 64, 100])
def test_wide_window_on_device_matches_cpu(width):
    """≥64 concurrent open ops decided on-device (multi-word masks — the
    round-1 31-slot cap is gone; reference runs use --concurrency 100,
    doc/running.md:88), differential against the unbounded CPU twin."""
    m = CasRegister()
    valid = _cas_chain_history(width)
    invalid = _cas_chain_history(width, break_at=width // 2)
    encs = [encode_history(h, m) for h in (valid, invalid)]
    assert encs[0].n_slots >= width
    kernel = make_batch_checker(m, n_configs=2 * width + 8,
                                n_slots=encs[0].n_slots)
    batch = pack_batch(encs)
    ok, overflow = kernel(batch["events"])
    assert not np.asarray(overflow).any()
    assert bool(ok[0]) is True
    assert bool(ok[1]) is False
    assert check_encoded_cpu(encs[0], m).valid is True
    assert check_encoded_cpu(encs[1], m).valid is False


def test_wide_window_with_info_ops_auto_stays_on_device():
    """Crashed (info) ops hold slots forever — the exact checker-pressure
    regime the reference documents (doc/intro.md:35-41). 50 crashed chained
    cas ops + live traffic: window >31, auto must decide it on-device."""
    from jepsen_jgroups_raft_tpu.history.ops import Op

    rows = [Op(500, INVOKE, "write", 0), Op(500, OK, "write", 0)]
    # 50 chained crashed cas ops with the read observing the chain TIP:
    # every link's to-value is observed (by the next link's from, and
    # the last by the read), so the dead-crashed-op prune cannot retire
    # any of them and the full >31 window reaches the kernel — while
    # the frontier stays linear (prefix chains), not exponential. (The
    # read used to observe mid-chain value 7, whose unobserved tail the
    # prune now provably drops, shrinking the window to ~8.)
    for i in range(50):
        rows.append(Op(i, INVOKE, "cas", (i, i + 1)))  # never completes
    rows.append(Op(600, INVOKE, "read", None))
    rows.append(Op(600, OK, "read", 50))  # chain fully linearized
    for i in range(50):
        rows.append(Op(i, INFO, "cas", (i, i + 1)))
    # auto now tries a budgeted DFS first on wide windows (measured
    # ~2000× faster on wide valid histories, round-3 soak) — it must
    # DECIDE, whichever engine answers.
    results = check_histories([rows], CasRegister(), algorithm="auto",
                              n_configs=256)
    assert results[0]["valid?"] is True
    assert results[0]["algorithm"] in ("jax", "dfs")
    assert results[0]["concurrency-window"] > 31
    # And the on-device sort kernel itself can still decide it when
    # asked explicitly (the capability this test originally pinned).
    [r] = check_histories([rows], CasRegister(), algorithm="jax",
                          n_configs=256)
    assert r["valid?"] is True and r["algorithm"] == "jax"


def test_prune_decides_chained_crashed_cas_cheaply():
    """The previous wide-window fixture, kept as a prune showcase: 50
    chained crashed cas ops whose tail nobody observes collapse to the
    handful that can still explain the read — window ~8, not 51."""
    from jepsen_jgroups_raft_tpu.history.ops import Op

    rows = [Op(500, INVOKE, "write", 0), Op(500, OK, "write", 0)]
    for i in range(50):
        rows.append(Op(i, INVOKE, "cas", (i, i + 1)))  # never completes
    rows.append(Op(600, INVOKE, "read", None))
    rows.append(Op(600, OK, "read", 7))  # chain linearized up to 7
    for i in range(50):
        rows.append(Op(i, INFO, "cas", (i, i + 1)))
    results = check_histories([rows], CasRegister(), algorithm="auto")
    assert results[0]["valid?"] is True
    assert results[0]["algorithm"] == "jax"
    assert results[0]["concurrency-window"] <= 10


def test_uncorrupted_random_histories_always_valid():
    rng = random.Random(7)
    m = CasRegister()
    for _ in range(60):
        h = random_valid_history(rng, "register", n_ops=10, n_procs=4)
        assert cpu_check(h, m) is True


# ------------------------------------------------------------ check API --


def test_check_histories_auto_batches_and_falls_back():
    rng = random.Random(3)
    m = Counter()
    hs = [random_valid_history(rng, "counter", n_ops=12, n_procs=4)
          for _ in range(8)]
    results = check_histories(hs, m, algorithm="auto")
    assert all(r["valid?"] is True for r in results)
    assert any(r["algorithm"] == "jax" for r in results)


def test_dfs_differential_on_goldens_and_wide_windows():
    """DFS engine agrees with the frontier twin on the structured wide
    histories too (different search order, same verdicts)."""
    m = CasRegister()
    for width in (10, 40, 64):
        for break_at in (None, width // 2):
            h = _cas_chain_history(width, break_at=break_at)
            enc = encode_history(h, m)
            expected = check_encoded_cpu(enc, m).valid
            assert check_encoded_dfs(enc, m).valid == expected


def test_race_returns_first_finisher():
    """algorithm='race': kernel vs DFS, every history decided, verdicts
    correct, and results flagged as raced (knossos.competition analogue)."""
    rng = random.Random(11)
    m = CasRegister()
    hs = [random_valid_history(rng, "register", n_ops=12, n_procs=4)
          for _ in range(6)]
    hs.append(H(
        (0, INVOKE, "write", 1),
        (0, OK, "write", 1),
        (1, INVOKE, "read", None),
        (1, OK, "read", 2),
    ))
    results = check_histories(hs, m, algorithm="race")
    for r in results[:-1]:
        assert r["valid?"] is True
    assert results[-1]["valid?"] is False
    assert all(r.get("raced") or r["algorithm"] == "cpu" for r in results)
    assert {r["algorithm"] for r in results} <= {"jax", "dfs", "cpu"}


def test_dfs_witness_and_failing_index():
    h = H(
        (0, INVOKE, "add", 1),
        (0, OK, "add", 1),
        (1, INVOKE, "read", None),
        (1, OK, "read", 0),
    )
    [r] = check_histories([h], Counter(), algorithm="dfs", witness=True)
    assert r["valid?"] is False
    assert r["failing-op-index"] == 3  # the stale read's completion
    h2 = H(
        (0, INVOKE, "add", 1),
        (0, OK, "add", 1),
        (1, INVOKE, "read", None),
        (1, OK, "read", 1),
    )
    [r2] = check_histories([h2], Counter(), algorithm="dfs", witness=True)
    assert r2["valid?"] is True
    assert r2["witness"] == [0, 2]  # linearization order by op index


def test_check_histories_cpu_reports_counterexample():
    h = H(
        (0, INVOKE, "add", 1),
        (0, OK, "add", 1),
        (1, INVOKE, "read", None),
        (1, OK, "read", 0),
    )
    [r] = check_histories([h], Counter(), algorithm="cpu")
    assert r["valid?"] is False
    assert r["failing-op-index"] == 3  # the stale read's completion


def test_counterexample_artifact_rendered(tmp_path):
    """An invalid verdict explains itself: failing op + witness prefix in
    the result, and a highlighted-timeline HTML in the store dir — even
    when the deciding engine was the TPU kernel (which returns only the
    verdict)."""
    from jepsen_jgroups_raft_tpu.checker.linearizable import (
        LinearizableChecker)
    from jepsen_jgroups_raft_tpu.history.ops import Op

    hist = [
        Op(0, INVOKE, "write", 1, time=0, index=0),
        Op(0, OK, "write", 1, time=10, index=1),
        Op(1, INVOKE, "read", None, time=20, index=2),
        Op(1, OK, "read", 3, time=30, index=3),  # 3 was never written
    ]
    test = {"store_dir": str(tmp_path)}
    r = LinearizableChecker(CasRegister(), algorithm="jax").check(test, hist)
    assert r["valid?"] is False
    ce = r["counterexample"]
    assert ce["failing-op"]["index"] == 3
    assert ce["failing-op"]["f"] == "read"
    assert "no linearization order" in ce["explanation"]
    assert [v["index"] for v in ce["witness-prefix"]] == [0]  # the write
    html = (tmp_path / "counterexample.html").read_text()
    assert "bad" in html and "VIOLATION" in html


def test_counterexample_per_key_in_independent(tmp_path):
    from jepsen_jgroups_raft_tpu.checker.independent import (
        IndependentLinearizable)
    from jepsen_jgroups_raft_tpu.history.ops import Op

    hist = [
        Op(0, INVOKE, "write", (7, 1), time=0, index=0),
        Op(0, OK, "write", (7, 1), time=10, index=1),
        Op(1, INVOKE, "read", (7, None), time=20, index=2),
        Op(1, OK, "read", (7, 2), time=30, index=3),  # stale
        Op(2, INVOKE, "write", (8, 5), time=0, index=4),
        Op(2, OK, "write", (8, 5), time=10, index=5),  # key 8 is fine
    ]
    test = {"store_dir": str(tmp_path)}
    r = IndependentLinearizable(CasRegister).check(test, hist)
    assert r["valid?"] is False
    assert r["results"]["7"]["valid?"] is False
    assert "counterexample" in r["results"]["7"]
    assert r["results"]["8"]["valid?"] is True
    assert (tmp_path / "counterexample-7.html").exists()


def test_unavailable_pinned_backend_raises():
    """A pinned backend that cannot initialize fails the check: the
    error propagates out of `check_histories` — no verdict is produced
    on the host in the accelerator's place. Runs in a subprocess so the
    broken pin cannot leak into this process's jax; the history is
    invalid, so no host certifier can decide it before a kernel is
    asked for."""
    import subprocess
    import sys

    from jepsen_jgroups_raft_tpu.platform import cpu_subprocess_env

    env = cpu_subprocess_env()
    env["JAX_PLATFORMS"] = "nosuchbackend"
    code = (
        "from jepsen_jgroups_raft_tpu.checker.linearizable import"
        " check_histories\n"
        "from jepsen_jgroups_raft_tpu.models import CasRegister\n"
        "from jepsen_jgroups_raft_tpu.history.ops import History, Op\n"
        "h = History()\n"
        "for r in [(0, 'invoke', 'write', 1), (0, 'ok', 'write', 1),\n"
        "          (1, 'invoke', 'read', None), (1, 'ok', 'read', 9)]:\n"
        "    h.append(Op(*r))\n"
        "try:\n"
        "    rs = check_histories([h], CasRegister(), algorithm='auto')\n"
        "except RuntimeError as e:\n"
        "    assert 'nosuchbackend' in str(e), e\n"
        "    print('RAISED_OK')\n"
        "else:\n"
        "    print('VERDICT', rs)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=180,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RAISED_OK" in out.stdout, out.stdout + out.stderr[-2000:]
