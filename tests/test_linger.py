"""The batch-formation linger (ISSUE 37): its window opens when the
batch's oldest member was admitted, not when the dispatcher took it
(for a request taken alone, at the take, as ever); past it a batch is
held only for a submission a handler has announced.

Seams and stamps, no shares of a wall clock: the scheduler's clock is a
fake one, a request's age is its `submitted` stamp set back, and what is
asserted is the timeout the scheduler hands `AdmissionQueue.take`, the
batch it returns, span `dispatch.linger`'s count and counter
`lingers_elapsed`. The served cases run the real queue with a window far
longer than the test may take, so a wait that did not end early fails.
"""

from __future__ import annotations

import threading
import time
import types

import pytest

from jepsen_jgroups_raft_tpu.checker.schedule import snapshot_spans
from jepsen_jgroups_raft_tpu.service import CheckingService
from jepsen_jgroups_raft_tpu.service import scheduler as sched_mod
from jepsen_jgroups_raft_tpu.service.admission import AdmissionQueue
from jepsen_jgroups_raft_tpu.service.request import admit
from jepsen_jgroups_raft_tpu.service.scheduler import (BatchScheduler,
                                                       bucket_signature)

from util import H

WINDOW = 0.1
WAIT_S = 120.0  # upper bound, not a sleep: first XLA compile dominates


class Clock:
    """The scheduler's `time`: `monotonic` moves only when told to."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        return self.t


@pytest.fixture()
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
        monotonic=c.monotonic, perf_counter=time.perf_counter))
    return c


class ScriptedQueue(AdmissionQueue):
    """The real queue, with the waiting scripted: the first `take` (the
    dispatcher's own) looks once; each later one (the linger's) admits
    the scripted `arrivals` [(seconds from now, request)] that fall
    inside the timeout it was asked for, running the real selection
    after each as a wake-up does, and moves the clock by what it
    waited. `asked` is every timeout of a plain wait in order, `held`
    those of the waits asked to end once nothing is arriving."""

    def __init__(self, clock, arrivals=(), announced=False):
        super().__init__(capacity=64)
        self.clock, self.arrivals, self.asked = clock, list(arrivals), []
        #: the scripted arrivals are announced submissions (`announce`)
        self.announced = announced
        self.held = []

    @property
    def arriving(self):
        return len(self.arrivals) if self.announced else 0

    def take(self, chooser, timeout, while_arriving=False):
        if not self.asked:
            self.asked.append(timeout)
            return super().take(chooser, 0.0)
        (self.held if while_arriving else self.asked).append(timeout)
        if while_arriving and not self.arriving:
            return super().take(chooser, 0.0)  # no company in sight
        left = timeout
        while self.arrivals and self.arrivals[0][0] <= left:
            dt, req = self.arrivals.pop(0)
            self.clock.t += dt
            left -= dt
            self.put(req, retry_after_s=1.0)
            chosen = super().take(chooser, 0.0)
            if chosen:
                return chosen
        self.clock.t += left
        return []


def hist(seed, n_ops=20):
    """Sequential writes, distinct by `seed`, always `2 * n_ops` events:
    every history of one `n_ops` packs into one shape bucket."""
    rows = []
    for i in range(n_ops):
        v = seed * 100_000 + i
        rows += [(0, "invoke", "write", v), (0, "ok", "write", v)]
    return H(*rows)


def request(now, age=0.0, seed=1, n_ops=20, priority=0, slack=3600.0):
    req = admit([hist(seed, n_ops)], "register", priority=priority)
    req.submitted = now - age
    req.deadline = now + slack
    return req


def linger_counts(sched):
    span = snapshot_spans().get("dispatch.linger", {"n": 0, "s": 0.0})
    return span["n"], span["s"], sched.lingers_elapsed


def form(clock, members, arrivals=(), announced=False, **kw):
    """Queue `members`, take one batch; returns (scheduler, queue,
    batch, Δ span n, Δ span s, Δ lingers_elapsed)."""
    queue = ScriptedQueue(clock, arrivals, announced)
    kw.setdefault("batch_wait", WINDOW)
    sched = BatchScheduler(queue, **kw)
    for r in members:
        queue.put(r, retry_after_s=1.0)
    n0, s0, e0 = linger_counts(sched)
    batch = sched.next_batch(timeout=0.0)
    n1, s1, e1 = linger_counts(sched)
    return sched, queue, batch, n1 - n0, s1 - s0, e1 - e0


# ---------------------------------------------------- when the window opens


@pytest.mark.parametrize("members, wait", [
    # (1) queued behind a busy dispatcher for longer than the window
    pytest.param([(2 * WINDOW, 0), (1.5 * WINDOW, 0)], None,
                 id="older-than-the-window"),
    # (2) half the window spent in the queue: the other half is waited
    pytest.param([(WINDOW / 2, 0), (WINDOW / 4, 0)], WINDOW / 2,
                 id="half-the-window"),
    # (3) just admitted at an idle service: the whole window, as ever
    pytest.param([(0.0, 0)], WINDOW, id="just-admitted"),
    pytest.param([(0.03, 0), (0.02, 0)], WINDOW - 0.03,
                 id="the-older-of-two"),
    # (5) `_choose` puts the higher priority first: the head is the
    # younger one, and the window is the old rider's
    pytest.param([(0.0, 5), (3 * WINDOW, 0)], None,
                 id="young-head-old-rider"),
    # a lone request found no company in the queue, however long it
    # queued: its window opens at the take
    pytest.param([(2 * WINDOW, 0)], WINDOW, id="lone-and-old"),
    pytest.param([(WINDOW / 2, 0)], WINDOW, id="lone-and-half-old"),
])
def test_window_opens_at_the_oldest_admission(clock, members, wait):
    reqs = [request(clock.t, age=age, seed=i, priority=prio)
            for i, (age, prio) in enumerate(members)]
    sched, queue, batch, dn, ds, de = form(clock, reqs)
    assert sorted(r.id for r in batch) == sorted(r.id for r in reqs)
    assert batch[0].id == reqs[0].id  # the head is the first member
    assert dn == 1  # eligible: the span is entered once, waited or not
    if wait is None:
        assert queue.asked == [0.0]  # no blind wait on the queue
        # asked once whether company is in sight; none is: no wait
        assert queue.held == [pytest.approx(WINDOW)]
        assert de == 1 and ds < 0.05
        assert clock.t == 1000.0
    else:
        assert queue.asked == [0.0, pytest.approx(wait)]
        assert de == 0
        assert clock.t == pytest.approx(1000.0 + wait)


def test_two_clients_in_a_closed_loop_pair_again(clock):
    """Two clients, one request each in flight: while A's launch ran,
    B's next request queued for longer than the window; A's next is
    still in the client when the dispatcher takes B (nothing is
    announced), and lands 48 ms on. B is alone, so its window opens at
    the take and the two share a launch; were B's age spent, each
    would launch the moment the other's launch ends, alone, for ever."""
    window = 0.05
    b = request(clock.t, age=0.4, seed=1)
    a = request(clock.t, seed=2)
    sched, queue, batch, dn, ds, de = form(
        clock, [b], arrivals=[(0.048, a)], batch_wait=window,
        max_batch_rows=2)
    assert batch == [b, a]
    assert queue.asked == [0.0, pytest.approx(window)] and queue.held == []
    assert (dn, de) == (1, 0)
    assert clock.t == pytest.approx(1000.048)  # full: no longer waited


# (6) the conditions that never wait are the ones that never waited
@pytest.mark.parametrize("case", ["solo", "short-slack", "no-window"])
def test_never_waits(clock, case):
    req = request(clock.t, slack=WINDOW / 2 if case == "short-slack"
                  else 3600.0)
    req.solo = case == "solo"
    kw = {"batch_wait": 0.0} if case == "no-window" else {}
    sched, queue, batch, dn, ds, de = form(clock, [req], **kw)
    assert batch == [req]
    assert queue.asked == [0.0]
    assert (dn, de) == (0, 0)  # not eligible: neither span nor counter


# (4) a full launch closes the window; a launch with room waits on
@pytest.mark.parametrize("cap, asked", [
    pytest.param(2, [WINDOW], id="full-closes-early"),
    pytest.param(3, [WINDOW, WINDOW - 0.01], id="room-left-waits-on"),
])
def test_window_closes_when_the_launch_is_full(clock, cap, asked):
    a = request(clock.t, seed=1)
    b = request(clock.t, seed=2)
    sched, queue, batch, dn, ds, de = form(
        clock, [a], arrivals=[(0.01, b)], max_batch_rows=cap)
    assert batch == [a, b]
    assert b.taken == pytest.approx(1000.01)  # stamped when it was taken
    assert queue.asked[1:] == [pytest.approx(t) for t in asked]
    assert (dn, de) == (1, 0)
    assert clock.t == pytest.approx(1000.01 if cap == 2
                                    else 1000.0 + WINDOW)


def test_top_up_keeps_the_row_cap_and_the_bucket(clock):
    """The top-up rule is the take's: same bucket, no `solo`, deadline
    order up to the row cap. A quarantined arrival, another bucket's
    and one that does not fit stay queued."""
    a = request(clock.t, seed=1)
    alone = request(clock.t, seed=2)
    alone.solo = True
    other = request(clock.t, seed=3, n_ops=200)
    assert bucket_signature(other) != bucket_signature(a)
    fits = request(clock.t, seed=4)
    wide = admit([hist(5), hist(6)], "register")
    wide.submitted, wide.deadline = clock.t, clock.t + 3600.0
    sched, queue, batch, *_ = form(
        clock, [a], max_batch_rows=3,
        arrivals=[(0.01, alone), (0.01, other), (0.01, fits), (0.01, wide)])
    assert batch == [a, fits]
    assert queue.depth == 3
    assert clock.t == pytest.approx(1000.0 + WINDOW)


# ------------------------------------- company in sight past the window


@pytest.mark.parametrize("lands, rides, waited", [
    # a submission being decoded lands 20 ms on: waited for, and rides
    pytest.param(0.02, True, 0.02, id="lands-inside-the-hold"),
    # one that takes longer than a window from the take is not
    pytest.param(2 * WINDOW, False, WINDOW, id="the-hold-is-one-window"),
])
def test_a_spent_window_still_waits_for_an_announced_submission(
        clock, lands, rides, waited):
    """The window of `old` passed in the queue, so nothing is waited
    for blind; a submission that a handler has announced is company in
    sight, and is waited for: until it lands, and never longer than
    one window from the take."""
    old = [request(clock.t, age=3 * WINDOW, seed=i) for i in (1, 2)]
    late = request(clock.t, seed=3)
    sched, queue, batch, dn, ds, de = form(
        clock, old, arrivals=[(lands, late)], announced=True)
    assert batch == (old + [late] if rides else old)
    assert queue.held[0] == pytest.approx(WINDOW)  # from the take
    assert queue.asked == [0.0]
    assert (dn, de) == (1, 1)  # eligible, and its own window had passed
    assert clock.t == pytest.approx(1000.0 + waited)


def test_an_unannounced_arrival_is_not_waited_for_past_the_window(clock):
    old = [request(clock.t, age=3 * WINDOW, seed=i) for i in (1, 2)]
    sched, queue, batch, dn, ds, de = form(
        clock, old, arrivals=[(0.02, request(clock.t, seed=3))])
    assert batch == old and queue.asked == [0.0]
    assert clock.t == 1000.0 and queue.depth == 0


def test_announce_counts_a_submission_until_it_is_put_or_given_up():
    queue = AdmissionQueue(capacity=2)
    assert queue.arriving == 0
    with queue.announce():
        assert queue.arriving == 1
        queue.put(request(time.monotonic(), seed=1), retry_after_s=1.0)
        assert queue.arriving == 0  # in the queue: no longer arriving
    assert queue.arriving == 0
    with pytest.raises(ValueError):
        with queue.announce():
            assert queue.arriving == 1
            raise ValueError("malformed")
    assert queue.arriving == 0
    # another thread's announcement is its own
    inside, leave = threading.Event(), threading.Event()

    def other():
        with queue.announce():
            inside.set()
            leave.wait(10.0)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(10.0)
    with queue.announce():
        assert queue.arriving == 2
    assert queue.arriving == 1
    leave.set()
    t.join(10.0)
    assert queue.arriving == 0


class Watched(AdmissionQueue):
    """The real queue; `holding` is set when the linger asks it to
    wait for company in sight, `lingering` when it asks for any wait
    after the dispatcher's own take."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.takes = 0
        self.lingering, self.holding = threading.Event(), threading.Event()

    def take(self, chooser, timeout, while_arriving=False):
        self.takes += 1
        if self.takes > 1:
            self.lingering.set()
        if while_arriving:
            self.holding.set()
        return super().take(chooser, timeout, while_arriving)


def test_the_hold_ends_when_the_announced_submission_is_given_up():
    """The real condition variable: a batch whose window has passed is
    held while a submission is announced, and released the moment the
    announcement ends without a put (a cache hit, a refusal): long
    before the window from the take, which would outlast the test."""
    queue = Watched(capacity=8)
    sched = BatchScheduler(queue, batch_wait=300.0)
    now = time.monotonic()
    old = [request(now, age=301.0, seed=i) for i in (1, 2)]
    for r in old:
        queue.put(r, retry_after_s=1.0)
    entered, leave = threading.Event(), threading.Event()

    def handler():
        with queue.announce():
            entered.set()
            leave.wait(30.0)

    h = threading.Thread(target=handler)
    h.start()
    assert entered.wait(10.0)
    got = []
    t = threading.Thread(target=lambda: got.append(
        sched.next_batch(timeout=5.0)))
    t.start()
    assert queue.holding.wait(10.0)
    assert queue.depth == 0 and got == []  # taken, and held
    leave.set()
    t.join(10.0)
    h.join(10.0)
    assert got == [old] and sched.lingers_elapsed == 1


# ------------------------------------------------------- the real queue


def test_another_buckets_arrival_is_left_in_the_queue():
    """(7) The real condition variable, and a window that would outlast
    the test: an admission of another bucket wakes the linger, is not
    taken, and the wait goes on until one of the batch's own bucket
    fills the launch."""
    queue = Watched(capacity=8)
    sched = BatchScheduler(queue, batch_wait=600.0, max_batch_rows=2)
    now = time.monotonic()
    a, other = request(now, seed=1), request(now, seed=2, n_ops=200)
    b = request(now, seed=3)
    assert bucket_signature(other) != bucket_signature(a)
    queue.put(a, retry_after_s=1.0)
    got = []
    t = threading.Thread(target=lambda: got.append(
        sched.next_batch(timeout=5.0)))
    t.start()
    assert queue.lingering.wait(10.0)  # taken: the linger has begun
    queue.put(other, retry_after_s=1.0)
    queue.put(b, retry_after_s=1.0)
    t.join(10.0)
    assert got == [[a, b]]
    assert queue.depth == 1 and other.taken == 0.0


def test_idle_service_holds_the_window_and_a_full_launch_ends_it():
    """(3, 4) served: a lone request at an idle service is held (the
    window is far longer than this test may take), a second of its
    bucket submitted inside the window rides the launch, and the launch
    being full then ends the wait at once."""
    svc = CheckingService(store_root=None, batch_wait=600.0,
                          max_batch_rows=2)
    try:
        n0 = snapshot_spans().get("dispatch.linger", {"n": 0})["n"]
        a = svc.submit([hist(11)], workload="register")
        until = time.monotonic() + 30.0
        while svc.queue.depth and time.monotonic() < until:
            time.sleep(0.001)
        assert svc.queue.depth == 0 and not a.terminal  # held
        b = svc.submit([hist(12)], workload="register")
        assert a.wait(WAIT_S) and b.wait(WAIT_S)
        assert a.verdict() is True and b.verdict() is True
        assert a.stats["batched_requests"] == 2
        assert a.stats["batch_seq"] == b.stats["batch_seq"]
        st = svc.stats()
        assert st["batches"] == 1 and st["lingers_elapsed"] == 0
        assert st["spans"]["dispatch.linger"]["n"] - n0 == 1
    finally:
        svc.shutdown(wait=True)


def test_a_service_announces_a_submission_before_it_decodes_it(monkeypatch):
    from jepsen_jgroups_raft_tpu.service import daemon

    svc = CheckingService(store_root=None, autostart=False)
    seen = []
    real = daemon.admit

    def admit_seen(*a, **kw):
        seen.append(svc.queue.arriving)
        return real(*a, **kw)

    monkeypatch.setattr(daemon, "admit", admit_seen)
    try:
        svc.submit([hist(31)], workload="register")
        assert seen == [1]
        assert svc.queue.arriving == 0 and svc.queue.depth == 1
        with pytest.raises(ValueError):
            svc.submit([hist(32)], workload="no-such-workload")
        assert svc.queue.arriving == 0
    finally:
        svc.shutdown(wait=True)


def test_requests_that_queued_past_the_window_launch_at_once():
    """(1) served: requests admitted while nothing took them (a stopped
    dispatcher stands in for a busy one), older than the window when it
    starts, are launched without a wait that would outlast the test."""
    svc = CheckingService(store_root=None, batch_wait=600.0,
                          autostart=False)
    try:
        reqs = [svc.submit([hist(20 + i)], workload="register")
                for i in range(2)]
        for r in reqs:
            r.submitted -= 601.0
        svc.start()
        for r in reqs:
            assert r.wait(WAIT_S) and r.verdict() is True
        st = svc.stats()
        assert st["lingers_elapsed"] == st["batches"] >= 1
    finally:
        svc.shutdown(wait=True)


def test_a_lone_request_that_queued_past_the_window_is_held_for_company():
    """Served, the two-client case: one request admitted while nothing
    took it, older than the window when the dispatcher starts, is
    alone, so it is held as at an idle service (the window would
    outlast the test); the other client's request then shares its
    launch, which is full and goes."""
    svc = CheckingService(store_root=None, batch_wait=600.0,
                          max_batch_rows=2, autostart=False)
    try:
        b = svc.submit([hist(41)], workload="register")
        b.submitted -= 601.0
        svc.start()
        until = time.monotonic() + 30.0
        while svc.queue.depth and time.monotonic() < until:
            time.sleep(0.001)
        assert svc.queue.depth == 0 and not b.terminal  # taken, and held
        a = svc.submit([hist(42)], workload="register")
        assert a.wait(WAIT_S) and b.wait(WAIT_S)
        assert a.stats["batched_requests"] == 2
        assert a.stats["batch_seq"] == b.stats["batch_seq"]
        st = svc.stats()
        assert st["batches"] == 1 and st["lingers_elapsed"] == 0
    finally:
        svc.shutdown(wait=True)

