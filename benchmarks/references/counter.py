"""Replicated counter, initially 0: read, add, add-and-get (the
reference's counter workload). A completed add-and-get carries
(delta, observed new value); one whose completion is unknown is an
add whose result constrains nothing."""

INIT = 0


def encode(f, value, ctype, cvalue):
    if ctype == "fail":
        return None
    forced = ctype == "ok"
    if f == "read":
        return (("read", cvalue, None), True) if forced else None
    if f == "add":
        return ("add", value, None), forced
    if f == "add-and-get":
        if forced:
            return ("add-and-get", cvalue[0], cvalue[1]), True
        return ("add", value, None), False
    raise ValueError(f"counter: unknown f {f!r}")


def step(state, op):
    f, a, b = op
    if f == "read":
        return state, state == a
    if f == "add":
        return state + a, True
    return state + a, state + a == b
