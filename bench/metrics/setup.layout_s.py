"""setup.layout_s: host seconds of set-up spent on the schedule, the
2-D partition and the host layout of the graph operands (BCSR tiles),
from the program's spans ``bc.setup.schedule``, ``bc.setup.partition``
and ``bc.setup.layout`` of its newest run."""
from bench import program_spans

SPANS = ("bc.setup.schedule", "bc.setup.partition", "bc.setup.layout")


def read(ctx):
    run = program_spans.newest_run(ctx)
    return program_spans.seconds(run, SPANS) if run else None
