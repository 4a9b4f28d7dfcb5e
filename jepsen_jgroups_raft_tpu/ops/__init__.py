"""On-device kernels: the TPU execution backend for history verification.

This package is the equivalent of knossos' search engine (the reference's
L0 "compute kernel", SURVEY.md §3.4), re-designed for XLA/TPU. The
kernel families share one step-parts substrate and sit behind one
routing layer (doc/checker-design.md):

* `kernel_ir`   — the shared IR (PR 6): event-row decode, macro latch,
  FORCE dispatch, chunk-carry schema, monolithic + chunked drivers,
  eligibility caps and the chunk-carry contract bindings. Families
  instantiate it with their state lowering.
* `dense_scan`  — dense-bitset frontiers for small enumerable domains
  (register) and order-independent models (counter, mask mode); exact,
  overflow-free.
* `linear_scan` — the general sort-dedup frontier scan (windows ≤127).
"""

from .dense_scan import (  # noqa: F401
    DensePlan,
    dense_plan,
    dense_plans_grouped,
    make_dense_batch_checker,
)
from .linear_scan import (  # noqa: F401
    make_batch_checker,
    make_history_checker,
    DEFAULT_N_CONFIGS,
    MAX_SLOTS,
)
