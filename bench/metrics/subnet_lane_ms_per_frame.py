"""Device time of the conv subnet lanes (`essr_c27`, `essr_c54`: the
kernels with the layout copies around them, and each bucket's trim) per
frame served in the traced window, averaged over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx, r"essr_c\d+")
