"""A cell, a configuration of another kind of history with a reference
of its own, a traffic mix and two per-layer metrics are each added as
new files plus entries in BENCHMARK.json: no file that is there is
edited. Shown on a copy of the benchmark in a temporary directory: the
new cell runs there, and the copy's own suite (the tests of the
manifest, of the readers and of the references, which are under `paths`
and so closed to the PR that brings a cell) passes there with the
additions and holds them to its rules. That also shows that the harness
reads only BENCHMARK.json and the files under `paths` (the program
comes from PYTHONPATH)."""

import json
import os
import re
import subprocess
import sys

import pytest

from util_bench import (RESULT_KEYS, ROOT, copy_benchmark, last_json,
                        rehearse)

HARNESS = "tests/benchmark_harness"
SUITE = ("test_manifest.py", "test_layer_metrics.py",
         "test_span_metrics.py", "test_reference.py")
NEW_CELL = "dummy-register.dummy-mix"

#: a CAS register, initially unset; the plain reference of the new
#: configuration, as `references/frontier.py` asks for a model
REGISTER = '''\
"""Single-key register, initially unset: read, write, cas. A cas that
completed `ok` took effect; one that failed did not happen."""

INIT = None


def encode(f, value, ctype, cvalue):
    if ctype == "fail":
        return None
    forced = ctype == "ok"
    if f == "read":
        return (("read", cvalue, None), True) if forced else None
    if f == "write":
        return ("write", value, None), forced
    if f == "cas":
        return ("cas", value[0], value[1]), forced
    raise ValueError(f"register: unknown f {f!r}")


def step(state, op):
    f, a, b = op
    if f == "read":
        return state, state == a
    if f == "write":
        return a, True
    return (b, True) if state == a else (state, False)
'''

SPAN_READER = '''\
"""Frames decoded in the window: how often span `ingest.decode`
ended."""

from benchmarks.layer_metrics._spans import N

EXAMPLE = {"spans_before": {"ingest.decode": {"n": 10, "s": 1.0}},
           "spans_after": {"ingest.decode": {"n": 50, "s": 5.0}},
           "want": 40.0}


def read(ctx):
    n = N(ctx, "ingest.decode")
    return None if n is None else float(n)
'''

COUNTER_READER = '''\
"""Requests graftd refused at admission in the window. 0 on a run
whose load the queue held."""

EXAMPLE = {"stats_before": {"rejected": 1}, "stats_after": {"rejected": 4},
           "want": 3}
ZERO_IS_A_READING = True


def read(ctx):
    after = ctx["after"]["stats"]
    if "rejected" not in after:
        return None
    return after["rejected"] - ctx["before"]["stats"].get("rejected", 0)
'''


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-copy")
    copy_benchmark(root)
    return root


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-extended")
    copy_benchmark(root)
    add_files(root)
    return root


def add_files(root):
    bench = root / "benchmarks"
    config = json.loads((bench / "configs" / "counter-1k.json").read_text())
    config.update(name="dummy-register", service_workload="register",
                  history_kind="register", reference="dummy_register",
                  reduced=["ops_per_history"], ops_per_history=200)
    (bench / "configs" / "dummy-register.json").write_text(
        json.dumps(config))
    (bench / "references" / "dummy_register.py").write_text(REGISTER)
    mix = json.loads((bench / "traffic" / "campaign.json").read_text())
    mix.update(name="dummy-mix", perturbed_share=0.5)
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "dummy_decodes.py").write_text(SPAN_READER)
    (bench / "layer_metrics" / "dummy_rejected.py").write_text(
        COUNTER_READER)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "dummy-register", "source": "a test",
        "file": "benchmarks/configs/dummy-register.json",
        "reduced": ["ops_per_history"], "why": "a test"})
    manifest["workloads"].append({
        "name": NEW_CELL, "config": "dummy-register",
        "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    manifest["per_layer"] += [
        {"name": "dummy_decodes", "unit": "count", "better": "higher",
         "source": "program_span", "layer": "a test",
         "moves": "hist_per_s", "workloads": [NEW_CELL]},
        {"name": "dummy_rejected", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "a test",
         "moves": "hist_per_s", "workloads": [NEW_CELL]}]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def run_suite(root, *files):
    """The copy's own tests in a child `pytest`, every run of the whole
    benchmark deselected: `benchmarks` and the tests are the copy's, the
    program is the checkout's. Returns (return code, passes, output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{ROOT}")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--rootdir", str(root), "-k", "not rehearsal",
         *(str(root / HARNESS / f) for f in files)],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(root))
    passed = re.search(r"(\d+) passed", p.stdout)
    return p.returncode, int(passed.group(1)) if passed else 0, p.stdout


def test_new_cell_config_mix_and_metrics_as_files_only(extended):
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        for p in (ROOT / path).rglob("*"):
            if p.is_file() and not {"cache", "__pycache__"} & set(p.parts):
                there = extended / p.relative_to(ROOT)
                assert there.read_bytes() == p.read_bytes(), there
    rc, lines, err = rehearse(
        extended, "--workload", NEW_CELL, "--seed", "77",
        "--seconds", "2", "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    line = last_json(lines)
    assert RESULT_KEYS <= set(line)
    assert line["metrics"]["dummy_decodes"]["value"] > 0
    assert line["metrics"]["dummy_decodes"]["unit"] == "count"
    assert line["metrics"]["dummy_rejected"]["value"] == 0
    # the new reference agrees with the program's register model
    assert line["compared"]["verdict_mismatches"]["value"] == 0
    assert line["compared"]["rows_compared"]["value"] > 0
    assert line["window"]["reference_invalid"] > 0
    # a metric is read only in the cells that list it
    assert not {"ack_p50_ms", "compiles_in_window"} & set(line["metrics"])
    rc, lines, err = rehearse(
        extended, "--workload", "counter-1k.campaign", "--seed", "78",
        "--seconds", "2", "--trace", "1", env={"PYTHONPATH": str(ROOT)})
    assert rc == 0, err[-2000:]
    assert not {"dummy_decodes", "dummy_rejected"} & set(
        last_json(lines)["metrics"])


def test_the_copys_own_suite_passes_with_the_additions(plain, extended):
    rc, n_plain, out = run_suite(plain, *SUITE)
    assert rc == 0 and n_plain > 0, out[-3000:]
    rc, n_extended, out = run_suite(extended, *SUITE)
    assert rc == 0, out[-3000:]
    # the additions are held to the rules: more cases, none failing
    assert n_extended > n_plain


def no_metric_lists_the_cell(root):
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if NEW_CELL not in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def reader_without_example(root):
    path = root / "benchmarks" / "layer_metrics" / "dummy_decodes.py"
    path.write_text(SPAN_READER.replace("EXAMPLE", "_SHOWN"))


def zero_counter_without_its_mark(root):
    path = root / "benchmarks" / "layer_metrics" / "dummy_rejected.py"
    path.write_text(COUNTER_READER.replace("ZERO_IS_A_READING = True\n", ""))


@pytest.mark.parametrize("left_out,file,says", [
    (no_metric_lists_the_cell, "test_manifest.py",
     f"no per_layer metric lists '{NEW_CELL}'"),
    (reader_without_example, "test_layer_metrics.py",
     "layer_metrics/dummy_decodes.py brings no EXAMPLE"),
    (zero_counter_without_its_mark, "test_layer_metrics.py",
     "say ZERO_IS_A_READING = True in benchmarks/layer_metrics/"
     "dummy_rejected.py"),
], ids=["no_metric_lists_the_cell", "reader_without_example",
        "zero_counter_without_its_mark"])
def test_an_addition_with_a_piece_left_out_still_fails(tmp_path, left_out,
                                                      file, says):
    copy_benchmark(tmp_path)
    add_files(tmp_path)
    left_out(tmp_path)
    rc, _, out = run_suite(tmp_path, file)
    assert rc == 1, out[-3000:]
    assert says in " ".join(out.split()), out[-3000:]


def test_without_the_program_there_is_no_result(plain):
    rc, lines, err = rehearse(plain, "--workload", "counter-1k.campaign",
                              "--seed", "1", "--seconds", "1", "--trace",
                              "0", env={"PYTHONPATH": ""})
    assert rc != 0
    assert "NoProgram" in err
    assert not any('"correct"' in x for x in lines)
