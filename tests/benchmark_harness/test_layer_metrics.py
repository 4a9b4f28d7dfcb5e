"""Each per-layer reader on a small hand-made context: the number where
the run holds something to read, nothing where it does not."""

import pytest

from benchmarks import manifest as mf
from util_bench import ROOT

MANIFEST = mf.load_manifest(ROOT)


def ctx(**kw):
    before = {"stats": {"submitted": 10, "completed": 8, "batches": 2,
                        "batch_rows": 100, "fastpath_requests": 1,
                        "journal_group_commits": 20},
              "fastpath": {"certify_wall_s": 1.0},
              "tiers": {"backtrack@lin": {"rows": 10, "wall_s": 0.0},
                        "mask": {"rows": 90, "wall_s": 0.0}}}
    after = {"stats": {"submitted": 50, "completed": 48, "batches": 6,
                       "batch_rows": 1100, "fastpath_requests": 11,
                       "journal_group_commits": 80},
             "fastpath": {"certify_wall_s": 21.0},
             "tiers": {"backtrack@lin": {"rows": 110, "wall_s": 0.0},
                       "mask": {"rows": 990, "wall_s": 0.0}}}
    base = {"window_s": 40.0, "before": before, "after": after,
            "requests": [], "acks_ms": [5.0, 7.0, 100.0],
            "compiles_in_window": 0,
            "trace": {"busy_s": 0.5, "window_s": 8.0, "kernel_rows": 250,
                      "device_ops": [["while.17", 0.4]]}}
    base.update(kw)
    return base


WANT = {"ack_p50_ms": 7.0, "journal_fsyncs_per_req": 1.5,
        "fastlane_share": 25.0, "batch_rows_mean": 250.0,
        "certify_busy_share": 50.0, "host_certified_share": 10.0,
        "kernel_ms_per_row": 2.0, "device_idle_share": 93.75,
        "compiles_in_window": 0}


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_reader_gives_the_known_number(name):
    reader = mf.load_module(ROOT, "layer_metrics", name)
    assert reader.read(ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["kernel_ms_per_row", "device_idle_share"])
def test_trace_readers_give_nothing_without_device_time(name):
    reader = mf.load_module(ROOT, "layer_metrics", name)
    assert reader.read(ctx(trace=None)) is None
    idle = {"busy_s": 0.0, "window_s": 8.0, "kernel_rows": 0,
            "device_ops": []}
    assert reader.read(ctx(trace=idle)) is None


@pytest.mark.parametrize("name", ["journal_fsyncs_per_req", "fastlane_share",
                                  "batch_rows_mean", "host_certified_share",
                                  "ack_p50_ms"])
def test_counter_readers_give_nothing_when_nothing_moved(name):
    reader = mf.load_module(ROOT, "layer_metrics", name)
    still = ctx(acks_ms=[])
    still["after"] = still["before"]
    assert reader.read(still) is None
