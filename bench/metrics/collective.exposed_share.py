"""collective.exposed_share: device time in collectives (the ring's
collective-permutes, the loop's all-reduces) during which no compute ran
on that chip, over the traced window, averaged over the chips of the
cell.  A trace with no collective has nothing to read."""


def read(ctx):
    summ = ctx["summary"]
    if not summ or not summ["chips"] or summ["window_ns"] <= 0:
        return None
    chips = summ["chips"].values()
    if not any(c["collective_ns"] > 0 for c in chips):
        return None
    exposed = sum(c["collective_exposed_ns"] for c in chips) / len(chips)
    return 100.0 * exposed / summ["window_ns"]
