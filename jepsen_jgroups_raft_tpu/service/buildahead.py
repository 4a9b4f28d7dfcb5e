"""What graftd builds before it reports itself warm (ISSUE 32).

The wavefront's launch shapes are a closed set per KEY
(`checker/schedule.launch_shapes`), and a key is built whole the first
time a launch meets it. That first meeting is a pause of seconds, so a
service builds the keys it can know when it STARTS, on the dispatcher's
side, while its requests wait in the admission queue (acknowledged
after their WAL fsync as ever, answered by the kernels when the build
is done):

  * the keys this host's earlier services met: `launch-keys.json` in
    the plan store's fingerprint directory (`checker/autotune`), kept
    current by `record_keys` after every batch that met a new one.

Nothing is built unasked beyond that record: on the chip a program
built ahead costs a cold start about a second (200 programs of a
default range over the reference's windows took 191.6 s of an empty
compile cache's first start, PERF.md section 6), and a service whose
traffic has other windows would pay for programs it never launches. A
first start builds each key whole the first time a launch meets it —
in a deployment's warm-up — and every later start finds the record.

With the autotuner off (`JGRAFT_AUTOTUNE=0`: no store) nothing is read
or written: keys are built on first sight alone.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Callable, List, Optional

from ..checker import autotune, schedule

LOG = logging.getLogger("jgraft.service")

RECORD_NAME = "launch-keys.json"
RECORD_VERSION = 1

_written = 0   # keys in the record as this process last wrote or read it


def _record_path():
    return autotune.store_root() / autotune.host_fingerprint() / RECORD_NAME


def _service_models() -> dict:
    """Class name -> instance, over the models graftd serves."""
    from .request import service_workloads

    return {cls.__name__: cls()
            for cls, _independent in service_workloads().values()}


def read_record() -> List[dict]:
    if not autotune.autotune_on():
        return []
    try:
        raw = json.loads(_record_path().read_text())
        if raw.get("version") != RECORD_VERSION:
            return []
        return [k for k in raw["keys"]
                if isinstance(k.get("spec"), dict)
                and all(isinstance(k.get(f), int)
                        for f in ("width", "lanes", "rows"))]
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return []


def record_keys() -> None:
    """Write the dense keys built in this process beside the plans, if
    there are more than the record held. Best effort: a read-only store
    costs the next start its build-ahead, nothing else."""
    global _written
    if not autotune.autotune_on():
        return
    built = [k for k in schedule.snapshot_built()
             if k["spec"] is not None and k["rows"]]
    if len(built) <= _written:
        return
    keys = {json.dumps(k["spec"], sort_keys=True) + f"|{k['width']}":
            {"spec": k["spec"], "width": k["width"], "lanes": k["lanes"],
             "rows": max(k["rows"])} for k in built}
    for k in read_record():   # another service of this host may have more
        keys.setdefault(
            json.dumps(k["spec"], sort_keys=True) + f"|{k['width']}", k)
    path = _record_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".tmp{os.getpid()}")
        tmp.write_text(json.dumps({"version": RECORD_VERSION,
                                   "keys": list(keys.values())}, indent=1))
        os.replace(tmp, path)
        _written = len(built)
    except OSError as e:
        LOG.warning("could not write %s (%s: %s)", path,
                    type(e).__name__, e)


def build_at_start(max_rows: int,
                   stop: Optional[Callable[[], bool]] = None) -> dict:
    """Build the keys this service can know (module docstring), each
    whole for launches of up to `max_rows` rows. Returns ``{"source",
    "keys", "programs", "seconds"}`` for `/stats`, `seconds` the wall
    of the three steps, each a span: `start.plans` (the tuner's plans
    read into memory), `start.record` (the record read and a template
    made of each entry), `build.ahead` (the wait for the programs). One
    INFO line a key says what its build cost, by stage."""
    global _written
    from ..history.packing import macro_events_on

    t0 = time.perf_counter()
    with schedule.span("start.plans"):
        autotune.preload_plans()
    with schedule.span("start.record") as reading:
        keys = read_record()
        _written = len(keys)
        if not macro_events_on():
            keys = []   # the records are of the macro stream
        launches = _templates(keys)
        reading.n = len(launches)
    programs = (schedule.build_keys(launches, upto=max_rows, stop=stop,
                                    met="start")
                if launches else 0)
    for k in schedule.snapshot_built():
        if k["met"] == "start":
            LOG.info("built %s at the start: %d programs from %d traces, "
                     "trace %.2f lower %.2f load %.2f compile %.2f s",
                     schedule.key_name(k), k["programs"], k["traced"],
                     k["trace_s"], k["lower_s"], k["load_s"],
                     k["compile_s"])
    return {"source": "record" if launches else "none",
            "keys": len(launches), "programs": programs,
            "seconds": time.perf_counter() - t0}


def _templates(keys: List[dict]) -> list:
    """A launch template for each entry of the record that this process
    serves and would place as recorded."""
    models = _service_models()
    launches = []
    for k in keys:
        model = models.get(k["spec"].get("model"))
        if model is None or \
                repr(model.cache_key()) != k["spec"].get("model_key"):
            continue
        try:
            launch = schedule.key_template(model, k["spec"], k["width"],
                                           k["lanes"], k["rows"])
        except Exception:   # a record must never stop a service
            LOG.warning("launch-keys record entry skipped: %r", k,
                        exc_info=True)
            continue
        if launch is not None:
            launches.append(launch)
    return launches
