"""driver.turnaround_ms: the host's turnaround between dispatch blocks.

For block k, from the end of its ``bc.driver.drain`` (the device has
finished it and the host has its roots) to the end of block k+1's
``bc.driver.dispatch`` (the next round is queued on the device): the
accumulator's fetch, the stop rule, the next block's sources and the
dispatch itself.  Under the stop rule the device has nothing queued in
that time.  The median over k ≥ 3 (blocks 1 and 2 compile), from the
program's spans of its newest run.
"""
import statistics

from bench import program_spans

FIRST_BLOCK = 3


def read(ctx):
    run = program_spans.newest_run(ctx)
    if not run:
        return None
    drained = {s.attrs["block"]: s.end_ns for s in run if s.name == "bc.driver.drain"}
    dispatched = {s.attrs["block"]: s.end_ns for s in run if s.name == "bc.driver.dispatch"}
    gaps = [dispatched[k + 1] - end for k, end in drained.items()
            if k >= FIRST_BLOCK and k + 1 in dispatched]
    return statistics.median(gaps) / 1e6 if gaps else None
