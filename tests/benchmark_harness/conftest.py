"""For `test_layer_metrics.py` only: lets the readers that bring their
own example (`EXAMPLE` in the reader's file: span totals before and
after a 40 s window, and the number they give) run through that
module's `test_reader_gives_the_known_number`, which looks every
`per_layer` entry up in its own `WANT` table and reads it off its own
hand-made context. The example's answer joins `WANT`, and the context
carries the example's `stats.spans` and counters, for the one case that
runs. The next `benchmark` issue folds this into the test file (every
reader bringing its example) and deletes this conftest (PERF.md,
section 7)."""

import pytest

from benchmarks import manifest as mf
from util_bench import ROOT


@pytest.fixture(autouse=True)
def readers_bring_their_examples(request, monkeypatch):
    module = request.module
    if module.__name__.rsplit(".", 1)[-1] != "test_layer_metrics":
        return
    name = getattr(getattr(request.node, "callspec", None), "params",
                   {}).get("name")
    if name is None or name in module.WANT:
        return
    example = getattr(mf.load_module(ROOT, "layer_metrics", name),
                      "EXAMPLE", None)
    if example is None:
        return
    monkeypatch.setitem(module.WANT, name, example["want"])
    plain = module.ctx

    def ctx(**kw):
        base = plain(**kw)
        for side in ("before", "after"):
            stats = base[side]["stats"]
            stats["workers"] = 1
            stats["spans"] = example[f"spans_{side}"]
            stats.update(example.get(f"stats_{side}", {}))
        return base

    monkeypatch.setattr(module, "ctx", ctx)
