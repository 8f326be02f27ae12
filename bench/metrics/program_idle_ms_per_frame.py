"""Device idle time inside the frames' top program spans (`essr.serve`) per
frame served in the traced window, averaged over the chips the cell uses:
the chip waiting on the program's host code, not on the harness."""
import phase_trace


def read(ctx):
    red = phase_trace.program(ctx)
    if red is None:
        return None
    idle = sum(c["program_idle_s"] for c in red["chips"]) / ctx["chips"]
    return 1e3 * idle / ctx["frames"]
