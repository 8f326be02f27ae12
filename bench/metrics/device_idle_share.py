"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses: 100 * (1 - busy / window)."""


def read(ctx):
    chips = ctx["trace"]["chips"]
    window = ctx["trace"]["window_s"]
    if not chips or window <= 0:
        return None
    busy = sum(c["busy_s"] for c in chips) / ctx["chips"]
    return 100.0 * (1.0 - busy / window)
