"""Share of the subnet kernels' roofline: the least time the chips could
take for the conv subnets' work in the traced frames (the larger of
2 * MACs over the bf16 peak and LR-in plus HR-out fp32 bytes over the HBM
bandwidth, `work.py`), over the kernels' device time. The work is split
over the chips the cell uses, and the kernel time averaged over them."""
import work


def read(ctx):
    chips = ctx["trace"]["chips"]
    kernel_s = sum(c["pallas_s"] for c in chips) / ctx["chips"]
    if not ctx["counts"] or kernel_s <= 0 or not ctx["peaks"]:
        return None
    model, patch = ctx["model"], int(ctx["plan"]["patch"])
    macs = sum(work.subnet_macs(model, patch, c) for c in ctx["counts"])
    nbytes = sum(work.subnet_bytes(model, patch, c) for c in ctx["counts"])
    if macs <= 0:
        return None
    least, _ = work.least_time_s(2.0 * macs / ctx["chips"],
                                 nbytes / ctx["chips"], ctx["peaks"])
    return 100.0 * least / kernel_s
