"""Share of the window's wall time that the host certifier was running
(the program's own clock around `lin_fastpath_pass`)."""

from benchmarks.layer_metrics import delta

EXAMPLE = {"fastpath_before": {"certify_wall_s": 1.0},
           "fastpath_after": {"certify_wall_s": 21.0},
           "want": 50.0}
#: a window in which the certifier never ran reads 0 (ledger, PR 28)
ZERO_IS_A_READING = True


def read(ctx):
    if "certify_wall_s" not in ctx["after"]["fastpath"]:
        return None
    return 100.0 * delta(ctx, "fastpath", "certify_wall_s") / ctx["window_s"]
