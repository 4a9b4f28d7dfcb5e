"""Shared helpers of the benchmark-harness tests. Nothing here touches
JAX at import."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


#: what a reader's `EXAMPLE` may hold of the program: its counters at
#: the window's two ends, as `run.py`'s `read_counters` takes them
PROGRAM_GROUPS = ("stats", "spans", "fastpath", "tiers")
EXAMPLE_KEYS = {"want", "requests", "acks_ms", "compiles_in_window",
                "trace"} | {f"{g}_{side}" for g in PROGRAM_GROUPS
                            for side in ("before", "after")}


def copy_benchmark(dest) -> None:
    """BENCHMARK.json and everything under its `paths` (`benchmarks/`,
    the harness's own tests) into `dest`, so that a run there shares no
    `benchmarks/cache` with a run in the checkout; the program comes
    from PYTHONPATH."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, dest / path,
                        ignore=shutil.ignore_patterns("cache",
                                                      "__pycache__"))


def example_ctx(example: dict) -> dict:
    """The context `run.py` hands a reader, made from the reader's own
    `EXAMPLE`: a 40 s window of one worker, with the example's
    `stats_*`, `spans_*`, `fastpath_*` and `tiers_*` (`_before` and
    `_after`), and its `acks_ms`, `requests`, `compiles_in_window` and
    `trace`."""
    unknown = sorted(set(example) - EXAMPLE_KEYS)
    if unknown:
        raise ValueError(f"EXAMPLE holds {unknown}; a reader's context "
                         f"is made of {sorted(EXAMPLE_KEYS)}")

    def side(s):
        stats = {"workers": 1, **example.get(f"stats_{s}", {})}
        if f"spans_{s}" in example:
            stats["spans"] = example[f"spans_{s}"]
        return {"stats": stats,
                "fastpath": example.get(f"fastpath_{s}", {}),
                "tiers": example.get(f"tiers_{s}", {})}

    return {"window_s": 40.0, "before": side("before"),
            "after": side("after"),
            "requests": example.get("requests", []),
            "acks_ms": example.get("acks_ms", []),
            "compiles_in_window": example.get("compiles_in_window", 0),
            "trace": example.get("trace")}


def still_ctx(example: dict) -> dict:
    """The example's window with nothing moved in it: the end's counters
    are the start's, no client record, no compile."""
    ctx = example_ctx(example)
    ctx.update(after=ctx["before"], requests=[], acks_ms=[],
               compiles_in_window=0)
    return ctx


def of_the_program(key: str) -> bool:
    return key.rsplit("_", 1)[0] in PROGRAM_GROUPS


def reads_the_program(example: dict) -> bool:
    return any(map(of_the_program, example))


def bare_ctx(example: dict) -> dict:
    """The example's window from a program that serves none of the
    counters and spans (a parent commit's `/stats`): only what the
    harness holds itself is left."""
    return example_ctx({k: v for k, v in example.items()
                        if not of_the_program(k)})


def reader_with_example(name):
    """The reader of per-layer metric `name`, which has to bring its
    `EXAMPLE`."""
    from benchmarks import manifest as mf

    reader = mf.load_module(ROOT, "layer_metrics", name)
    example = getattr(reader, "EXAMPLE", None)
    assert isinstance(example, dict) and "want" in example, (
        f"benchmarks/layer_metrics/{name}.py brings no EXAMPLE: add a "
        "module constant EXAMPLE = {..., 'want': <number>} beside `read` "
        "(tests/benchmark_harness/util_bench.example_ctx lists the keys)")
    return reader


def zero_is_a_reading(reader) -> bool:
    return getattr(reader, "ZERO_IS_A_READING", False) is True


def rehearse(root, *args, env=None, timeout=240):
    """Run `benchmarks/run.py --rehearse` of the checkout at `root` in a
    child on the CPU; returns (return code, stdout lines, stderr)."""
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    p = subprocess.run(
        [sys.executable, str(Path(root) / "benchmarks" / "run.py"),
         "--rehearse", *args],
        capture_output=True, text=True, timeout=timeout, env=e,
        cwd=str(root))
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def last_json(lines):
    return json.loads(lines[-1])
