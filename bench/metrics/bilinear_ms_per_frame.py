"""Device time of the bilinear lane (`essr_bilinear`: the resize of the
routed bucket and its trim) per frame served in the traced window, averaged
over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx, "essr_bilinear")
