"""device.idle_share: 1 − the union of device-operation intervals over
the traced window, averaged over the chips of the cell."""


def read(ctx):
    summ = ctx["summary"]
    if not summ or not summ["chips"] or summ["window_ns"] <= 0:
        return None
    busy = sum(c["busy_ns"] for c in summ["chips"].values()) / len(summ["chips"])
    return 100.0 * (1.0 - busy / summ["window_ns"])
