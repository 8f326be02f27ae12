"""The busiest chip's busy seconds over the mean busy seconds of the chips
the cell uses, in the traced window: 1.0 when the chips carry equal loads,
4.0 on four chips when one does all the work. A chip with no operation in
the window counts as idle."""


def read(ctx):
    busy = [c["busy_s"] for c in ctx["trace"]["chips"]]
    if not busy:
        return None
    mean = sum(busy) / max(ctx["chips"], len(busy))
    return max(busy) / mean if mean > 0 else None
