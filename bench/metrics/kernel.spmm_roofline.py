"""kernel.spmm_roofline: the SpMM kernels' least time over their time.

The least time of a level's forward and backward work on a chip comes
from ``bench/roofline.py`` (arcs of the chip's share of the graph, the
rows and columns of its block, the batch's s roots).  Under a ring
overlap policy a level calls each kernel once per ring step (R steps on
an R x C mesh), each call on one chunk of the operand, so a call's least
time is the level's over R.  The calls are counted from the kernel events
of the trace and their time is those events' device time, summed over
the chips of the cell.  Every call is memory-bound, where the least time
is linear in the arcs, so an even share of the arcs per chip gives the
same sum as each chip's own.
"""
from bench.roofline import backward_cost, forward_cost, least_seconds


def read(ctx):
    summ = ctx["summary"]
    if not summ or ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    rows_mesh, cols_mesh = cfg["mesh"]
    steps = rows_mesh if cfg["overlap"] != "none" else 1
    arcs = ctx["col"].size / (rows_mesh * cols_mesh)
    rows, cols = ctx["n"] // rows_mesh, ctx["n"] // cols_mesh
    s = ctx["batch_size"]
    fwd, _ = least_seconds(forward_cost(arcs, rows, s, cols), ctx["peaks"])
    bwd, _ = least_seconds(backward_cost(arcs, rows, s, cols), ctx["peaks"])
    least = spent = 0.0
    for chip in summ["chips"].values():
        least += (chip["forward_calls"] * fwd + chip["backward_calls"] * bwd) / steps
        spent += (chip["forward_ns"] + chip["backward_ns"]) / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
