"""Control: the reference with one stated guarantee broken. An op whose
completion is unknown (`info`, or none at all) may have taken effect
and must stay a candidate to the end of the history; this control
treats it as not having happened — the shortcut that keeps every
concurrency window at the number of live processes. A comparison that
cannot tell its verdicts from the reference's proves nothing."""

from . import frontier


def linearizable(rows, model) -> bool:
    class Dropped:
        INIT = model.INIT
        step = staticmethod(model.step)

        @staticmethod
        def encode(f, value, ctype, cvalue):
            return None if ctype == "info" else model.encode(
                f, value, ctype, cvalue)

    return frontier.linearizable(rows, Dropped)
