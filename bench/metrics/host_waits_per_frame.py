"""Times the host blocked on the device (`essr.wait.*` spans inside the
frames' top program spans) per frame served in the traced window."""
import phase_trace


def read(ctx):
    red = phase_trace.program(ctx)
    if red is None:
        return None
    return red["program"]["waits"] / ctx["frames"]
