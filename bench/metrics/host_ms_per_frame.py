"""The host's own work per frame served in the traced window: the length of
the frames' top program spans (`essr.serve`) minus the `essr.wait.*` spans
inside them, in which the host was blocked on the device."""
import phase_trace


def read(ctx):
    red = phase_trace.program(ctx)
    if red is None:
        return None
    prog = red["program"]
    return 1e3 * (prog["frame_s"] - prog["wait_s"]) / ctx["frames"]
