"""A cell, a configuration, a traffic mix and a per-layer metric are
each added as new files plus entries in BENCHMARK.json: no file that is
there is edited. Shown on a copy of the benchmark in a temporary
directory, which also shows that the harness reads only BENCHMARK.json
and the files under `paths` (the program comes from PYTHONPATH)."""

import json
import shutil

import pytest

from util_bench import RESULT_KEYS, ROOT, last_json, rehearse


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-copy")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    return root


def add_files(root):
    bench = root / "benchmarks"
    config = json.loads((bench / "configs" / "counter-1k.json").read_text())
    config.update(name="dummy-counter", value_range=2)
    (bench / "configs" / "dummy-counter.json").write_text(
        json.dumps(config))
    mix = json.loads((bench / "traffic" / "campaign.json").read_text())
    mix.update(name="dummy-mix", perturbed_share=0.5)
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "dummy_rows.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(r['n'] for r in ctx['requests']))\n")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "dummy-counter", "source": "a test",
        "file": "benchmarks/configs/dummy-counter.json", "reduced": [],
        "why": "a test"})
    manifest["workloads"].append({
        "name": "dummy-counter.dummy-mix", "config": "dummy-counter",
        "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "dummy_rows", "unit": "rows", "better": "higher",
        "source": "host_clock", "layer": "a test", "moves": "hist_per_s",
        "workloads": ["dummy-counter.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def test_new_cell_config_mix_and_metric_as_files_only(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmarks").rglob("*")
              if p.is_file()}
    add_files(copy)
    assert all(p.read_bytes() == b for p, b in before.items())
    rc, lines, err = rehearse(
        copy, "--workload", "dummy-counter.dummy-mix", "--seed", "77",
        "--seconds", "2", "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    line = last_json(lines)
    assert RESULT_KEYS <= set(line)
    assert line["metrics"]["dummy_rows"]["value"] > 0
    assert line["metrics"]["dummy_rows"]["unit"] == "rows"
    assert line["compared"]["verdict_mismatches"]["value"] == 0
    assert line["window"]["reference_invalid"] > 0
    # the metric is read only in the cell that lists it
    rc, lines, err = rehearse(
        copy, "--workload", "counter-1k.campaign", "--seed", "78",
        "--seconds", "2", "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    assert "dummy_rows" not in last_json(lines)["metrics"]


def test_without_the_program_there_is_no_result(copy):
    rc, lines, err = rehearse(copy, "--workload", "counter-1k.campaign",
                              "--seed", "1", "--seconds", "1", "--trace",
                              "0", env={"PYTHONPATH": ""})
    assert rc != 0
    assert "NoProgram" in err
    assert not any('"correct"' in x for x in lines)
