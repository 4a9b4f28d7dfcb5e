"""Single-key compare-and-set register, initially unset: read, write,
cas (the reference's register workload, `workload/register.clj:21-34`,
checked there against `knossos.model/cas-register`, `:106-111`).

A write or a cas that completed `ok` took effect; a cas that `fail`ed
found another value and did not happen; one whose completion is unknown
(`info`, or none at all) may have taken effect and may not. A read that
did not complete `ok` constrains nothing.

Departures from Knossos's `cas-register`:
- an acknowledged read of the unset register (value None) is legal only
  while the register is unset. Knossos steps a read whose value is nil
  through any state, because there an invocation's nil stands for "not
  known yet"; here a read's value is taken from its completion, so None
  is what the client saw;
- a cas on a state other than its expected value is not an
  "inconsistent" model but an illegal step of this candidate order: the
  same answer, in `frontier.py`'s terms;
- `fail` and `info` are resolved here, in `encode`, as the frontier
  search asks of a model; Knossos drops failed pairs and keeps crashed
  ones pending while it prepares the history.
"""

INIT = None


def encode(f, value, ctype, cvalue):
    if ctype == "fail":
        return None
    forced = ctype == "ok"
    if f == "read":
        return (("read", cvalue, None), True) if forced else None
    if f == "write":
        return ("write", value, None), forced
    if f == "cas":
        return ("cas", value[0], value[1]), forced
    raise ValueError(f"cas_register: unknown f {f!r}")


def step(state, op):
    f, a, b = op
    if f == "read":
        return state, state == a
    if f == "write":
        return a, True
    return (b, True) if state == a else (state, False)
