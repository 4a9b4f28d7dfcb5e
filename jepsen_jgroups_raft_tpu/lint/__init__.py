"""graftlint: project-native static analysis (see ISSUE/doc).

Six analyzers, one per repo-level invariant no generic linter knows —
three pattern-level (PR 1), three CFG/dataflow (the graftcheck tier,
:mod:`.flow`):

* :mod:`.taxonomy` — exception paths that record op outcomes must
  respect the definite/indefinite taxonomy (client/errors.py), or the
  linearizability checker is unsound.
* :mod:`.jit_hygiene` — no host syncs / Python tracer branching /
  recompile hazards inside jitted or Pallas-traced bodies; intentional
  device→host hops in launch functions carry ``# lint: allow(host-sync)``.
* :mod:`.lock_discipline` — ``// GUARDED_BY(mu)`` fields in
  ``native/src`` are only touched under their mutex (or in
  ``// REQUIRES(mu)`` helpers).
* :mod:`.flow.kernel_contract` — the kernels' cap constants and
  chunk-carry accounting held to a VMEM budget, and the
  BlockSpec/grid/out_shape arithmetic of any ``pallas_call`` under
  sampled contract bindings (no such call is in the tree since PR 50).
* :mod:`.flow.heal` — every nemesis fault-injection path heals,
  registers for teardown, or carries ``# lint: allow(unhealed)``.
* :mod:`.flow.resource` — acquire/release balance across exception
  paths in the deploy/runner tiers.

CLI: ``python -m jepsen_jgroups_raft_tpu.lint [paths]`` — with
``--format json`` (SARIF 2.1.0) and a regression baseline
(``--baseline`` / ``--update-baseline``, doc/running.md).
``scripts/lint.sh`` is the one-command gate (ruff → graftlint →
graftcheck → ``make -C native tidy``).
"""

from .base import Finding, SourceFile  # noqa: F401
from .cli import main, run  # noqa: F401
