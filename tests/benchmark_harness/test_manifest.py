"""BENCHMARK.json and the files it names agree."""

import json
import re

import pytest

from benchmarks import manifest as mf

from util_bench import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = mf.load_manifest(ROOT)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_unique_and_well_formed(group):
    names = [e["name"] for e in MANIFEST[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"]
                         + MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "layer" in metric:  # per-layer: a reader of its own, no bound
        assert "bound" not in metric
        reader = mf.load_module(ROOT, "layer_metrics", metric["name"])
        assert callable(reader.read)
        e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
        moved = e2e[metric["moves"]]
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    else:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


def test_setup_s_is_an_end_to_end_metric():
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    entry, config, traffic = mf.cell(ROOT, MANIFEST, cell["name"])
    for kind, name in (("generators", config["generator"]),
                       ("references", config["reference"]),
                       ("references", config["control"]),
                       ("loops", traffic["loop"]),
                       ("wires", traffic["wire"])):
        mf.load_module(ROOT, kind, name)
    listed = [c for c in MANIFEST["configs"]
              if c["name"] == entry["config"]][0]
    # the cuts of scale the file states are those the manifest lists
    assert config["reduced"] == listed["reduced"]
    assert all(isinstance(k, str) and k for k in config["reduced"])
    assert set(config["guarantees"]) == {"verdict", "acknowledgement",
                                         "cache", "platform"}
    assert mf.metrics_of(MANIFEST, "per_layer", cell["name"]), (
        f"no per_layer metric lists {cell['name']!r}: add one (a reader "
        "under benchmarks/layer_metrics/ and its entry) whose "
        "'workloads' names the cell; lengthening the list of a metric "
        "that is there is a `benchmark` PR's to do")
    e2e = {m["name"] for m in mf.metrics_of(MANIFEST, "end_to_end",
                                           cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry(config):
    assert config["file"].startswith(tuple(MANIFEST["paths"]))
    assert (ROOT / config["file"]).is_file()
    assert len(config["source"]) <= 200
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert config["name"] in used


def test_paths_hold_the_command_and_no_rooted_path():
    assert MANIFEST["command"][1].startswith("benchmarks/")
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir()


def test_peaks_table_names_its_source_and_the_v5e():
    with open(ROOT / "benchmarks" / "peaks.json") as fh:
        peaks = json.load(fh)
    assert peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        mf.cell(ROOT, MANIFEST, "no-such.cell")


def test_derived_seeds_differ_by_stream_and_take_large_seeds():
    big = 2**31 + 12345
    a = mf.derive_seed(big, "pool", 0)
    assert a == mf.derive_seed(big, "pool", 0)
    assert len({a, mf.derive_seed(big, "pool", 1),
                mf.derive_seed(big, "sample", 0),
                mf.derive_seed(big + 1, "pool", 0)}) == 4
