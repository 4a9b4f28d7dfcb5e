"""Platform selection shared by every entry point: CPU pinning for
tests and dry runs, the persistent compile cache, the degrade registry,
and the defensive env-knob parsers.

Tests and dry runs run on the host (`pin_cpu`: JAX_PLATFORMS=cpu plus N
virtual devices, set before the CPU backend initializes); everything
else initialises the default backend in its own process, and a backend
that fails to come up fails the run. Used by tests/conftest.py,
cli.py, chip_smoke.py and __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import Optional

_log = logging.getLogger(__name__)

#: Why part of this process's work did NOT run where it was asked to
#: (a distributed seam that could not come up, a graftd batch whose
#: device path died). None when everything ran as intended. Checker
#: results carry this note so a degraded verdict is distinguishable from
#: an intended-CPU one in every artifact.
_DEGRADED_NOTE: Optional[str] = None


def note_degraded(note: str) -> None:
    """Record that the platform degraded (first note wins: the root
    cause, not the retry cascade)."""
    global _DEGRADED_NOTE
    if _DEGRADED_NOTE is None:
        _DEGRADED_NOTE = note


def degraded_note() -> Optional[str]:
    """The degrade reason recorded by `note_degraded`, or None."""
    return _DEGRADED_NOTE


def env_int(name: str, default: int, minimum: Optional[int] = None) -> int:
    """Parse an integer env gate defensively: a non-integer value warns
    and falls back to the default instead of crashing at import time
    with a ValueError. `minimum` clamps with a warning — the gates this
    serves are counts/sizes where a negative or undersized value is
    always operator error, never intent."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = int(raw.strip())
    except ValueError:
        _log.warning("%s=%r is not an integer; using default %d",
                     name, raw, default)
        return default
    if minimum is not None and val < minimum:
        _log.warning("%s=%d below minimum %d; clamping",
                     name, val, minimum)
        return minimum
    return val


def env_float(name: str, default: float,
              minimum: Optional[float] = None) -> float:
    """`env_int`'s float twin (lease TTLs and skew margins are
    sub-second in tests): same defensive stance — garbage warns and
    keeps the default, sub-minimum clamps with a warning."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = float(raw.strip())
    except ValueError:
        _log.warning("%s=%r is not a number; using default %g",
                     name, raw, default)
        return default
    if minimum is not None and val < minimum:
        _log.warning("%s=%g below minimum %g; clamping",
                     name, val, minimum)
        return minimum
    return val


def env_str(name: str, default: str = "") -> str:
    """String twin of `env_int`/`env_float` for path/id knobs
    (JGRAFT_CLUSTER_DIR, JGRAFT_REPLICA_ID, ...): a missing OR
    blank/whitespace value falls back to the default, so
    `JGRAFT_CLUSTER_DIR=""` in a wrapper script means "unset", not "the
    current directory". Registered as a typed knob by the envknobs
    analyzer (lint/flow/envknobs.py), which is why string knobs should
    route through here rather than raw os.environ.get."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    return raw.strip()


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache at a placeable path
    and return the directory in use (None: no cache). Called first
    thing by every entry point (cli.main, chip_smoke.py).

    ``JAX_COMPILATION_CACHE_DIR`` wins — jax reads it itself, nothing
    is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache``, derived from this package's own
    location: the path is part of the cache key, so it must never move
    between runs. A CPU-pinned process (tests, rehearsals) gets none:
    the cache exists to spare chip compiles, and host executables
    loaded back from it only earn XLA's machine-feature warnings."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if _cpu_pinned():
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_COMPILE_COUNTERS_INSTALLED = False

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: the two stages of a build that are Python's, by JAX's event
_PYTHON_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}


def install_compile_counters() -> None:
    """Register, once a process, the `jax.monitoring` listeners that
    feed the registry's compile counters and build stages
    (`checker.schedule`: `programs_built`, `compile_s`,
    `compile_cache_misses`, spans `build.trace|lower|load|compile`, a
    key's seconds in `build_keys`, and the most recent compiles with the
    span each interrupted). graftd calls it when it starts, so an
    operator whose daemon never stops compiling sees that in `/stats`
    as programs, not only as latency.

    The events it listens to, all JAX's own:

    * ``/jax/core/compile/jaxpr_trace_duration`` → `build.trace`
      (Python tracing a program to a jaxpr) and
      ``/jax/core/compile/jaxpr_to_mlir_module_duration`` →
      `build.lower` (the jaxpr to an MLIR module), each as a scalar
      when the stage opens on a thread and as a duration when it ends.
      A `jit` traced inside another's trace (every `jnp` operator is
      one) and a trace inside a lowering fire events of their own whose
      seconds lie INSIDE the outer event's: the scalars count how many
      are open on the thread, and only the outermost is added, so a
      program's trace and lowering are counted once and its four stages
      sum to no more than its wall. What is added is the thread's own
      CPU seconds between the two events (`time.thread_time`), not the
      event's duration: both stages are Python, so with eight build
      threads the duration is mostly the wait for the GIL, counted
      eight times (a CPU rehearsal: 153 s of durations against a wait
      of 23 s).
    * ``/jax/core/compile/backend_compile_duration`` → `programs_built`,
      `compile_s`, and span `build.load` where
      ``/jax/compilation_cache/cache_hits`` fired on that thread since
      the program's last (the persistent cache gave it), `build.compile`
      otherwise (XLA compiled it from source).
    * ``/jax/compilation_cache/cache_misses`` → `compile_cache_misses`
      (a program compiled and written to the persistent cache)."""
    global _COMPILE_COUNTERS_INSTALLED
    if _COMPILE_COUNTERS_INSTALLED:
        return
    _COMPILE_COUNTERS_INSTALLED = True
    from jax import monitoring

    from .checker.schedule import (BUILD_STAGES, note_build_stage,
                                   note_cache_miss, note_compile, note_span)

    for stage in BUILD_STAGES:   # served from the start: a stage that
        note_span("build." + stage, 0.0, 0)   # never ran reads 0.0
    #: `.open`: trace and lower stages open on this thread; `.cpu0`:
    #: the thread's CPU clock where the outermost opened; `.hit`: the
    #: cache gave the program this thread is loading
    here = threading.local()

    def on_scalar(event, value, **kw) -> None:
        if event in _PYTHON_STAGES:
            n_open = getattr(here, "open", 0)
            if not n_open:
                here.cpu0 = time.thread_time()
            here.open = n_open + 1

    def on_duration(event, seconds, **kw) -> None:
        if event == _BACKEND_EVENT:
            loaded, here.hit = getattr(here, "hit", False), False
            note_compile(str(kw.get("fun_name", "?")), seconds, loaded)
        elif event in _PYTHON_STAGES:
            here.open = still_open = max(getattr(here, "open", 1) - 1, 0)
            cpu0 = getattr(here, "cpu0", None)
            if not still_open and cpu0 is not None:
                note_build_stage(_PYTHON_STAGES[event],
                                 time.thread_time() - cpu0)

    def on_event(event, **kw) -> None:
        if event == _CACHE_MISS_EVENT:
            note_cache_miss()
        elif event == _CACHE_HIT_EVENT:
            here.hit = True

    monitoring.register_scalar_listener(on_scalar)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _cpu_pinned() -> bool:
    import jax

    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def pin_cpu(n_devices: int = 8) -> None:
    """Force JAX onto a virtual `n_devices`-device CPU platform.

    Must run before the CPU backend initializes to control the device
    count (afterwards the pin still keeps any accelerator backend from
    initializing, but the existing device count wins). An XLA_FLAGS count
    already present is raised to `n_devices` if smaller.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n_devices}"
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


def is_backend_init_failure(e: BaseException) -> bool:
    """True for the failure flavors of an unusable accelerator backend:
    init refusal (unknown platform) and a runtime that stops answering
    (UNAVAILABLE, DEADLINE_EXCEEDED, setup/compile errors). graftd's
    scheduler uses it to tell a platform-level failure — true of later
    batches too — from a one-off error in one batch."""
    text = f"{type(e).__name__}: {e}"
    return ("Unable to initialize backend" in text
            or "backend setup/compile error" in text
            or "UNAVAILABLE" in text
            or "DEADLINE_EXCEEDED" in text)


def cpu_subprocess_env(base: dict | None = None) -> dict:
    """Environment for a CPU-only child interpreter (soak workers,
    sanitizer runs, `parallel/launch.py`'s cluster children): the parent's
    environment with JAX_PLATFORMS=cpu, so the child can never reach
    for a chip its parent may hold."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env
