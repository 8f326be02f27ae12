"""Device time of every op that is not a Pallas kernel (patch gather, edge
score, bilinear lane, overlap-average fuse, the frame's other XLA work) per
frame served in the traced window, averaged over the chips the cell uses."""


def read(ctx):
    chips = ctx["trace"]["chips"]
    xla = sum(c["xla_s"] for c in chips) / ctx["chips"]
    if ctx["frames"] < 1 or xla <= 0:
        return None
    return 1e3 * xla / ctx["frames"]
