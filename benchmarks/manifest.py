"""Finds everything a cell is made of by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; those name a generator,
a plain reference, a control, a loop and a wire. Each is a file of its
own under `benchmarks/`, loaded by path, so adding one never edits a
file that is there.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_manifest(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def bench_dir(root: Path) -> Path:
    return root / "benchmarks"


def load_json(root: Path, kind: str, name: str) -> dict:
    path = bench_dir(root) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    with open(path) as fh:
        return json.load(fh)


def load_module(root: Path, kind: str, name: str):
    path = bench_dir(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    mod_name = f"benchmarks.{kind}.{name.replace('-', '_').replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(root: Path, manifest: dict, name: str, rehearse: bool = False):
    """(workload entry, configuration, traffic mix) of one cell; a
    rehearsal lays each file's `rehearsal` sizes over the real ones."""
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    cfg_entry = [c for c in manifest["configs"]
                 if c["name"] == entry["config"]][0]
    with open(root / cfg_entry["file"]) as fh:
        config = json.load(fh)
    traffic = load_json(root, "traffic", entry["traffic"])
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return entry, config, traffic


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of `end_to_end` or `per_layer` that this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def derive_seed(seed: int, *stream) -> int:
    """An independent seed for one stream of one run."""
    text = ":".join(str(x) for x in (seed,) + stream)
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:8], "big")
