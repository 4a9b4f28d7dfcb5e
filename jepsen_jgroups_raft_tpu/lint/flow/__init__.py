"""graftcheck: the CFG/dataflow tier of the lint suite (ISSUE 2 tentpole).

PR 1's graftlint analyzers are pattern-level — one AST shape, one
finding. The invariants this package polices are *path* properties that
pattern matching cannot express:

* ``kernel_contract`` — launch shape arithmetic (the kernels' caps and
  chunk carries against the VMEM footprint; BlockSpec, grid and
  out_shape of a ``pallas_call``, of which the tree holds none since
  PR 50) holds for every legal symbol binding, so a kernel
  misconfiguration is a lint error before it is a runtime XLA failure
  on paid TPU time.
* ``heal`` — every nemesis path that injects a fault reaches the
  matching heal/restore (or registers the affliction for teardown) on
  *all* exits including exception edges; deliberate unhealed faults
  carry ``# lint: allow(unhealed)``.
* ``resource`` — acquire/release pairs (connections, popen handles,
  file handles, tempdirs) balance across exception paths in the deploy
  and runner tiers.

``cfg`` builds the statement-level control-flow graph (branches, loops,
try/except/finally, with, early returns, exception edges) that ``heal``
and ``resource`` run their path searches over; ``interp`` is the
restricted AST evaluator ``kernel_contract`` uses to execute shape
arithmetic symbolically over sampled bindings.
"""

from . import cfg, heal, interp, kernel_contract, resource  # noqa: F401
