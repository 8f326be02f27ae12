"""Device time of the patch extraction phase (`essr_extract`: reflect pad,
reshape and gather of the LR frame into patches) per frame served in the
traced window, averaged over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx, "essr_extract")
