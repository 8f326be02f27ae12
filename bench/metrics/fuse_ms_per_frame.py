"""Device time of the overlap-average fuse (`essr_fuse`: the separable
scatter-add of the HR patches into the 8K frame, and its crop) per frame
served in the traced window, averaged over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx, "essr_fuse")
