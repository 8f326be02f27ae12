"""The whole frame's share of the chips' bf16 peak: 2 * the MACs the routed
frames need (bilinear, C27 and C54 lanes, `work.py`) per second of the
traced window, over the chips' peak."""
import work


def read(ctx):
    window = ctx["trace"]["window_s"]
    if not ctx["counts"] or window <= 0 or not ctx["peaks"]:
        return None
    model, patch = ctx["model"], int(ctx["plan"]["patch"])
    macs = sum(work.frame_macs(model, patch, c) for c in ctx["counts"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * 2.0 * macs / window / peak
