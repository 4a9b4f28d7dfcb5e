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


def install_compile_counters() -> None:
    """Register, once a process, the `jax.monitoring` listeners that
    feed the registry's compile counters (`checker.schedule`:
    `programs_built`, `compile_s`, `compile_cache_misses`, and the most
    recent compiles with the span each interrupted). graftd calls it
    when it starts, so an operator whose daemon never stops compiling
    sees that in `/stats` as programs, not only as latency."""
    global _COMPILE_COUNTERS_INSTALLED
    if _COMPILE_COUNTERS_INSTALLED:
        return
    _COMPILE_COUNTERS_INSTALLED = True
    from jax import monitoring

    from .checker.schedule import note_cache_miss, note_compile

    def on_duration(event, seconds, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            note_compile(str(kw.get("fun_name", "?")), seconds)

    def on_event(event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            note_cache_miss()

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
