"""The per-layer metrics that read the program's spans (ISSUE 26): each
reader on the example it brings, nothing from a program that serves no
spans, and one traced rehearsal whose result line carries them all."""

import math
import shutil

import pytest

from benchmarks import manifest as mf
from util_bench import ROOT, last_json, rehearse

MANIFEST = mf.load_manifest(ROOT)
CELL = "counter-1k.campaign"


def example_ctx(example):
    """The hand-made context of a reader's own `EXAMPLE`: a 40 s window
    with the example's span totals and counters before and after."""
    def stats(side):
        return {"workers": 1, "spans": example[f"spans_{side}"],
                **example.get(f"stats_{side}", {})}

    return {"window_s": 40.0, "before": {"stats": stats("before")},
            "after": {"stats": stats("after")}}


def brings_an_example(name):
    return hasattr(mf.load_module(ROOT, "layer_metrics", name), "EXAMPLE")


NAMES = [m["name"] for m in MANIFEST["per_layer"]
         if brings_an_example(m["name"])]


def test_the_fourteen_are_in_the_manifest_as_the_issue_has_them():
    assert len(NAMES) == 14
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NAMES:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["source"] == ("program_counter"
                               if name == "compile_ms_in_window"
                               else "program_span")
        assert m["moves"] in ("hist_per_s", "verdict_p50_ms")
    assert {by_name[n]["layer"] for n in NAMES} == {
        "ingest and admission", "journal", "scheduler", "kernels",
        "demux and records", "compile cache"}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_its_own_example(name):
    reader = mf.load_module(ROOT, "layer_metrics", name)
    ctx = example_ctx(reader.EXAMPLE)
    assert reader.read(ctx) == pytest.approx(reader.EXAMPLE["want"])


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_nothing_from_a_program_without_spans(name):
    """The parent commit's `/stats` has neither `spans` nor the compile
    counters: the reader returns None and does not raise, so the result
    line leaves the metric out."""
    reader = mf.load_module(ROOT, "layer_metrics", name)
    bare = {"window_s": 40.0,
            "before": {"stats": {"submitted": 1, "workers": 1}},
            "after": {"stats": {"submitted": 9, "workers": 1}}}
    assert reader.read(bare) is None


@pytest.mark.parametrize("name", [n for n in NAMES
                                  if n != "compile_ms_in_window"])
def test_span_reader_gives_nothing_when_nothing_moved(name):
    reader = mf.load_module(ROOT, "layer_metrics", name)
    ctx = example_ctx(reader.EXAMPLE)
    ctx["after"] = ctx["before"]
    assert reader.read(ctx) is None


def test_unattributed_share_is_for_one_worker_only(capsys):
    reader = mf.load_module(ROOT, "layer_metrics",
                            "dispatcher_unattributed_share")
    ctx = example_ctx(reader.EXAMPLE)
    assert reader.read(ctx) == pytest.approx(10.0)
    err = capsys.readouterr().err
    assert err.startswith("dispatcher shares: {") and err.count("\n") == 1
    ctx["after"]["stats"]["workers"] = 2
    assert reader.read(ctx) is None


def test_a_traced_rehearsal_reports_all_fourteen(tmp_path):
    """On a copy, so that this run shares no `benchmarks/cache` with the
    runs of test_run.py on another worker. A rehearsal's window is too
    short to bound any of them: they only have to be there, finite."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    rc, lines, err = rehearse(tmp_path, "--workload", CELL, "--seed",
                              str(2**31 + 26), "--seconds", "4",
                              "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    metrics = last_json(lines)["metrics"]
    assert set(NAMES) <= set(metrics), sorted(set(NAMES) - set(metrics))
    for name in NAMES:
        assert math.isfinite(metrics[name]["value"]), name
    assert "dispatcher shares: {" in err
    assert abs(metrics["dispatcher_unattributed_share"]["value"]) < 25.0
