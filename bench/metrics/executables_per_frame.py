"""Executables launched on the device (`XLA Modules` events that start in
the traced window) per frame served in it, averaged over the chips the cell
uses. Each one pays a launch on the host and on the device."""
import phase_trace


def read(ctx):
    red = phase_trace.for_ctx(ctx)
    if red is None or not red["chips"] or ctx["frames"] < 1:
        return None
    runs = sum(c["executables"] for c in red["chips"]) / ctx["chips"]
    return runs / ctx["frames"]
