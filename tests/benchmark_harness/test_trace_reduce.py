"""The reduction from device events to busy and idle time, on a small
list of events in the shape the chip's trace gives them (names as a
v5e trace of the dense kernel has them, times set by hand so that the
answer is known)."""

import json

import pytest

from benchmarks import trace_reduce

from util_bench import ROOT

with open(ROOT / "tests" / "benchmark_harness" / "data"
          / "trace_small.json") as fh:
    FIXTURE = json.load(fh)
EVENTS = [tuple(e) for e in FIXTURE["events"]]


def test_busy_is_the_union_of_the_intervals():
    out = trace_reduce.reduce(EVENTS, FIXTURE["window_s"])
    assert out["busy_s"] == pytest.approx(FIXTURE["busy_s"])
    assert out["window_s"] == FIXTURE["window_s"]
    assert out["devices"] == 1 and out["n_events"] == len(EVENTS)


def test_nested_operations_count_once_in_busy_but_by_name_in_ops():
    out = trace_reduce.reduce(EVENTS, FIXTURE["window_s"])
    ops = dict(out["device_ops"])
    assert ops["while.17"] == pytest.approx(0.004)
    assert ops["fusion.113"] == pytest.approx(0.0015)
    assert sum(ops.values()) > out["busy_s"]


def test_gaps_are_the_idle_stretches_longest_first():
    out = trace_reduce.reduce(EVENTS, FIXTURE["window_s"])
    assert [g[1] for g in out["gaps"]] == FIXTURE["gaps_ns"]


def test_two_devices_are_averaged():
    twin = EVENTS + [("/device:TPU:1", n, s, d) for _, n, s, d in EVENTS[:1]]
    out = trace_reduce.reduce(twin, FIXTURE["window_s"])
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((FIXTURE["busy_s"] + 0.004) / 2)


def test_span_clips_and_counts_the_edges_as_gaps():
    out = trace_reduce.reduce(EVENTS, 0.02, span_ns=(0, 20_000_000))
    assert out["busy_s"] == pytest.approx(FIXTURE["busy_s"])
    assert sum(d for _, d in out["gaps"]) == pytest.approx(
        20_000_000 - FIXTURE["busy_s"] * 1e9)


def test_no_event_gives_no_busy_time():
    out = trace_reduce.reduce([], 2.0)
    assert out["busy_s"] == 0 and out["device_ops"] == []


def test_gaps_are_named_by_the_counters_that_advanced():
    period = 0.05
    samples = [(int(k * period * 1e9), {"completed": k // 4, "batches": 0})
               for k in range(40)]
    gaps = [(int(0.1e9), int(1.0e9)), (int(1.5e9), int(0.01e9))]
    named = dict(trace_reduce.name_gaps(gaps, samples, period))
    assert named["advanced:completed"] == pytest.approx(1.0)
    assert named["gaps_under_0.1s"] == pytest.approx(0.01)
