"""Programs the process built, or loaded from the compile cache, between
the window's start and its end. 0 on a run whose warm-up reached every
shape."""


def read(ctx):
    return ctx["compiles_in_window"]
