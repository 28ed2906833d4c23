"""kernel.spmm_busy_share: the SpMM kernels' device time over the
device's busy time in the traced window, over the chips of the cell."""


def read(ctx):
    summ = ctx["summary"]
    if not summ:
        return None
    busy = sum(c["busy_ns"] for c in summ["chips"].values())
    kernels = sum(c["forward_ns"] + c["backward_ns"] for c in summ["chips"].values())
    if busy <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / busy
