"""Host spans: the program's one timing path.

``span(name, **attrs)`` is a context manager that times a stretch of host
code twice over, on one clock:

* it enters :class:`jax.profiler.TraceAnnotation` ``(name, **attrs)``, so
  that under any profiler trace the span lands in the same ``.xplane.pb``
  and on the same timeline as the device operations — an idle gap of
  the device reads under the program's own name;
* it records a :class:`Span` — name, ``start_ns``/``end_ns`` from
  :func:`time.time_ns` (the profiler's base clock: an xplane event's
  offset plus the ``profile_start_time`` stat of its ``Task
  Environment`` plane), the index of its parent in the run, the run id
  and the attributes.

A span opened while no span is open on its thread is a **root**: it
starts a run with a fresh id, and every span opened under it (on that
thread) belongs to that run.  When the root closes the run is finished:
the root holds it as ``finished``, the newest :data:`KEEP_RUNS` finished
runs are kept (a serving process runs forever) and :func:`last_run`
returns the newest.  :func:`attach_run` gives a call's result the run
its root span finished.

Recording is always on; a span costs a few microseconds to enter
and leave.  Open spans at block boundaries of host code only — never
inside a jitted function (its body runs once, while tracing) nor in a
per-root loop.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["KEEP_RUNS", "Span", "span", "last_run", "attach_run"]

#: finished runs kept in memory, newest last
KEEP_RUNS = 4

_local = threading.local()
_lock = threading.Lock()
_finished: collections.deque = collections.deque(maxlen=KEEP_RUNS)
_run_ids = itertools.count(1)


class Span:
    """One timed stretch of host code; a context manager (see :func:`span`).

    ``parent`` is the index of the enclosing span in its run's tuple
    (``None`` for the root); a run lists its spans in the order they
    opened.  A root, once closed, holds its run as ``finished``.
    """

    __slots__ = ("name", "start_ns", "end_ns", "parent", "run", "attrs", "finished",
                 "_index", "_note")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.start_ns = self.end_ns = None
        self.parent = self.run = self.finished = None

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return (self.end_ns - self.start_ns) / 1e9

    def elapsed(self) -> float:
        """Seconds since the span opened (it may still be open)."""
        return (time.time_ns() - self.start_ns) / 1e9

    def __enter__(self) -> Span:
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        if stack:
            top = stack[-1]
            self.parent, self.run = top._index, top.run
        else:
            _local.spans = []
            self.run = next(_run_ids)
        members = _local.spans
        self._index = len(members)
        members.append(self)
        stack.append(self)
        self._note = TraceAnnotation(self.name, **self.attrs)
        self._note.__enter__()
        self.start_ns = time.time_ns()
        return self

    def annotate(self, **attrs) -> None:
        """Add attributes known only once the open span's work is done;
        they reach the trace event too."""
        self.attrs.update(attrs)
        self._note.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        self.end_ns = time.time_ns()
        self._note.__exit__(*exc)
        self._note = None
        stack = _local.stack
        stack.pop()
        if not stack:
            self.finished = tuple(_local.spans)
            with _lock:
                _finished.append(self.finished)
            _local.spans = None


def span(name: str, **attrs) -> Span:
    """``with span("bc.driver.block", block=3) as s: ...`` — see the module
    docstring.  ``s.seconds`` is its duration once closed."""
    return Span(name, attrs)


def last_run() -> tuple[Span, ...] | None:
    """The spans of the newest finished run, root first; ``None`` if none."""
    with _lock:
        return _finished[-1] if _finished else None


def attach_run(root: Span, result) -> None:
    """Give ``result.spans`` the run that ``root`` finished, where ``root``
    opened the run; under another span the run is its root's to give."""
    if root.parent is None:
        result.spans = root.finished
