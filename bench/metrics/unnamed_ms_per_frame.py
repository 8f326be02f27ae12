"""Device time of every module that is not one of the program's named
phases (`jit_essr_*`) per frame served in the traced window, averaged over
the chips the cell uses. Near 0 when every dispatch of the serving path has
a phase name; it grows when a change adds an unnamed one."""
import phase_trace


def read(ctx):
    red = phase_trace.for_ctx(ctx)
    if red is None or not red["named"] or ctx["frames"] < 1:
        return None
    unnamed = sum(c["unnamed_s"] for c in red["chips"]) / ctx["chips"]
    return 1e3 * unnamed / ctx["frames"]
