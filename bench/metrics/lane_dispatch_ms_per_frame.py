"""Device time of the bucket dispatch phases per frame served in the traced
window: the gather of each routed bucket (`essr_lane_gather`), the zeroed
patch batch and the scatter of each lane's outputs into it
(`essr_lane_scatter`), averaged over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx,
                                          "essr_lane_(gather|scatter)")
