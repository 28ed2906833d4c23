"""setup.transfer_s: seconds from the first host-to-device put of the
graph operands and ω until all are resident on the device, from the
program's span ``bc.setup.transfer`` of its newest run."""
from bench import program_spans


def read(ctx):
    run = program_spans.newest_run(ctx)
    return program_spans.seconds(run, ("bc.setup.transfer",)) if run else None
