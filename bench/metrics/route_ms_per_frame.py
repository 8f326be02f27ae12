"""Device time of the routing phases per frame served in the traced window:
the frame health verdict (`essr_health`) and the edge score of every patch
(`essr_edge_score`), averaged over the chips the cell uses."""
import phase_trace


def read(ctx):
    return phase_trace.phase_ms_per_frame(ctx, "essr_(health|edge_score)")
