"""Each per-layer reader on the example it brings in its own file: the
number where the run holds something to read, nothing where it does
not. The test owns no table: a reader added as a new file plus a
`per_layer` entry is held to the same rules by arriving.

`EXAMPLE` (a module constant beside `read`) is a window by its two ends
(`util_bench.example_ctx` says which keys it may hold) and `want`, the
number the reader gives on it. A reader whose healthy reading is 0 (a
count or a time of faults) says `ZERO_IS_A_READING = True` in its file.
"""

import pytest

from benchmarks import manifest as mf
from util_bench import (ROOT, bare_ctx, example_ctx, reader_with_example,
                        reads_the_program, still_ctx, zero_is_a_reading)

MANIFEST = mf.load_manifest(ROOT)


def names(*sources):
    return [m["name"] for m in MANIFEST["per_layer"]
            if not sources or m["source"] in sources]


@pytest.mark.parametrize("name", names())
def test_reader_gives_the_known_number(name):
    reader = reader_with_example(name)
    want = reader.EXAMPLE["want"]
    assert want is not None
    assert reader.read(example_ctx(reader.EXAMPLE)) == pytest.approx(want)


@pytest.mark.parametrize("name", names("device_trace"))
def test_trace_readers_give_nothing_without_device_time(name):
    reader = reader_with_example(name)
    ctx = example_ctx(reader.EXAMPLE)
    assert reader.read(dict(ctx, trace=None)) is None
    idle = {"busy_s": 0.0, "window_s": 8.0, "kernel_rows": 0,
            "device_ops": []}
    assert reader.read(dict(ctx, trace=idle)) is None


@pytest.mark.parametrize("name", names("program_span", "program_counter",
                                       "host_clock"))
def test_reader_gives_nothing_when_nothing_moved(name):
    """A share, a mean or a time for each of something that did not
    happen is nothing, never 0; a count or a time of faults is 0 on a
    healthy window, and its reader's file says so."""
    reader = reader_with_example(name)
    got = reader.read(still_ctx(reader.EXAMPLE))
    if zero_is_a_reading(reader):
        assert got is not None and got == 0.0
    else:
        assert got is None, (
            f"{name} reads {got!r} on a window in which nothing moved: "
            "return None there, or, where 0 is the healthy reading of a "
            "count or a time of faults, say ZERO_IS_A_READING = True in "
            f"benchmarks/layer_metrics/{name}.py")


@pytest.mark.parametrize("name", names("program_span", "program_counter"))
def test_reader_gives_nothing_from_a_program_that_serves_nothing(name):
    """A parent commit's `/stats` may have neither `spans` nor the
    counters: the reader returns None and does not raise, so the result
    line leaves the metric out. A reader whose example holds nothing of
    the program reads what the harness counts itself, and its answer
    does not depend on the program."""
    reader = reader_with_example(name)
    got = reader.read(bare_ctx(reader.EXAMPLE))
    if reads_the_program(reader.EXAMPLE):
        assert got is None
    else:
        assert got == pytest.approx(reader.EXAMPLE["want"])
