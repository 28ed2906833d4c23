"""collective.level_mib: MiB one chip sends through the expand and the
fold in one forward level plus one backward level, from the attributes
``exchange_bytes_fwd`` and ``exchange_bytes_bwd`` of the program's span
``bc.setup.partition`` of its newest run (the shapes and dtypes a level
ships).  A program that gives its span no such attributes has nothing to
read."""
from bench import program_spans

KEYS = ("exchange_bytes_fwd", "exchange_bytes_bwd")


def read(ctx):
    run = program_spans.newest_run(ctx)
    for s in run or ():
        if s.name == "bc.setup.partition" and all(k in s.attrs for k in KEYS):
            return sum(s.attrs[k] for k in KEYS) / 2**20
    return None
