"""Consistency models.

Equivalent surface: knossos.model (reference L0 dep) plus the two
hand-written models in the reference — CounterModel
(workload/counter.clj:100-127) and LeaderModel (workload/leader.clj:63-75).

A model here is a deterministic state machine over int32 state with a
vectorized JAX step, so the linearizability frontier search can run it
on-device for thousands of configurations at once (SURVEY.md §7.2 step 2).
"""

from .base import Model, NIL  # noqa: F401
from .register import CasRegister  # noqa: F401
from .counter import Counter  # noqa: F401
from .leader import LeaderModel  # noqa: F401
from .setmodel import GSet  # noqa: F401
from .queuemodel import TicketQueue  # noqa: F401
from .listappend import ListAppend  # noqa: F401
from .listappend_txn import ListAppendTxn  # noqa: F401

#: name → constructor, used by workloads and the CLI.
MODELS = {
    "cas-register": CasRegister,
    "counter": Counter,
    "leader": LeaderModel,
    "set": GSet,
    "queue": TicketQueue,
    "list-append": ListAppend,
    "list-append-txn": ListAppendTxn,
}
