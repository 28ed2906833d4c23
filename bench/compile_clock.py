"""Host seconds spent compiling, from JAX's own monitoring events."""
from __future__ import annotations

import threading

__all__ = ["COMPILE_EVENTS", "CACHE_HIT_EVENT", "CompileClock", "union_seconds"]

#: JAX's compile-time events: tracing, lowering, and the backend compile
#: (which includes a persistent-cache read on a hit)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def union_seconds(spans) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(spans):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


class CompileClock:
    """Compile spans and persistent-cache hits, as JAX reports them.

    Compile events nest (tracing an outer jit traces the jitted kernel
    wrappers inside it), so the clock keeps the union of their spans
    rather than the sum of their durations.  Register :meth:`on_span` with
    ``jax.monitoring.register_event_time_span_listener`` and
    :meth:`on_event` with ``jax.monitoring.register_event_listener``.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[tuple[float, float]] = []
        self.cache_hits = 0

    def on_span(self, event: str, start: float, end: float, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self._spans.append((start, end))

    def on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def seconds_between(self, t0: float, t1: float = float("inf")) -> float:
        """Union of the compile spans that started in [t0, t1) (epoch s)."""
        with self._lock:
            spans = [s for s in self._spans if t0 <= s[0] < t1]
        return union_seconds(spans)

    def count_between(self, t0: float, t1: float = float("inf")) -> int:
        """Compile spans that started in [t0, t1)."""
        with self._lock:
            return sum(1 for s in self._spans if t0 <= s[0] < t1)
