"""Every `per_layer` entry of BENCHMARK.json against the reader it names
and the cells it lists, and one traced rehearsal a cell whose result
line carries the metrics that read the program's spans (ISSUE 26).
Rules over whatever entries are there: no count of readers, no list of
names, no cell's name (`test_extend.py` runs this file on a copy that
holds more of each)."""

import math

import pytest

from benchmarks import manifest as mf
from util_bench import (ROOT, copy_benchmark, example_ctx, last_json,
                        reader_with_example, rehearse, zero_is_a_reading)

MANIFEST = mf.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry_lists_its_cells_and_its_reader_brings_an_example(
        metric):
    """Beside what `test_manifest.py` holds every metric entry to."""
    name = metric["name"]
    reader = reader_with_example(name)
    example_ctx(reader.EXAMPLE)  # raises on a key no context holds
    # the driver refuses a PR that adds a cell while a metric lists none
    listed = metric.get("workloads")
    assert isinstance(listed, list) and listed, (
        f"per_layer {name!r}: give it an explicit, non-empty 'workloads' "
        "list of the cells whose runs hold something for it to read")
    assert set(listed) <= set(CELLS), (name, listed)


def test_unattributed_share_is_for_one_worker_only(capsys):
    reader = mf.load_module(ROOT, "layer_metrics",
                            "dispatcher_unattributed_share")
    ctx = example_ctx(reader.EXAMPLE)
    assert reader.read(ctx) == pytest.approx(10.0)
    err = capsys.readouterr().err
    assert err.startswith("dispatcher shares: {") and err.count("\n") == 1
    ctx["after"]["stats"]["workers"] = 2
    assert reader.read(ctx) is None


def read_in_every_rehearsal(cell):
    """The per-layer metrics that list `cell` and that a rehearsal on the
    CPU has something to read for: the readers of the program's spans,
    and the counters whose healthy reading is 0."""
    return [m["name"] for m in mf.metrics_of(MANIFEST, "per_layer", cell)
            if m["source"] == "program_span"
            or (m["source"] == "program_counter" and zero_is_a_reading(
                reader_with_example(m["name"])))]


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reports_the_cells_span_metrics(cell, tmp_path):
    """On a copy, so that this run shares no `benchmarks/cache` with the
    runs of test_run.py on another worker. A rehearsal's window is too
    short to bound any of them: they only have to be there, finite. The
    window is long enough that launches end inside it also beside five
    other xdist workers (PR 26's 4 s saw none there, and its readers
    had nothing to read)."""
    names = read_in_every_rehearsal(cell)
    copy_benchmark(tmp_path)
    rc, lines, err = rehearse(tmp_path, "--workload", cell, "--seed",
                              str(2**31 + 26), "--seconds", "10",
                              "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    metrics = last_json(lines)["metrics"]
    assert set(names) <= set(metrics), sorted(set(names) - set(metrics))
    for name in names:
        assert math.isfinite(metrics[name]["value"]), name
    if "dispatcher_unattributed_share" in names:
        assert "dispatcher shares: {" in err
        # what no span covers cannot be more than the window; how much of
        # a loaded host's window the dispatcher spent descheduled between
        # two spans is not this test's to bound
        assert metrics["dispatcher_unattributed_share"]["value"] <= 100.0
