"""Share of the window's wall time that the host certifier was running
(the program's own clock around `lin_fastpath_pass`)."""

from benchmarks.layer_metrics import delta


def read(ctx):
    if "certify_wall_s" not in ctx["after"]["fastpath"]:
        return None
    return 100.0 * delta(ctx, "fastpath", "certify_wall_s") / ctx["window_s"]
