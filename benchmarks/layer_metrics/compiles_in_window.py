"""Programs the process built, or loaded from the compile cache, between
the window's start and its end. 0 on a run whose warm-up reached every
shape."""

EXAMPLE = {"compiles_in_window": 0, "want": 0}
#: a count of faults: a warm-up that reached every shape reads 0
ZERO_IS_A_READING = True


def read(ctx):
    return ctx["compiles_in_window"]
