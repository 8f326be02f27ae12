"""Device time of the Pallas kernels (the C27 and C54 subnets) per frame
served in the traced window, on each chip's own timeline, averaged over the
chips the cell uses."""


def read(ctx):
    chips = ctx["trace"]["chips"]
    pallas = sum(c["pallas_s"] for c in chips) / ctx["chips"]
    if ctx["frames"] < 1 or pallas <= 0:
        return None
    return 1e3 * pallas / ctx["frames"]
