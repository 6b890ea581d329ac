"""Host phases of the serving loop: profiler spans and self-time counters.

``Phases.phase(name, **args)`` does two things for one phase of
``ServeEngine``'s host loop:

* it opens the profiler span ``engine.<name>``
  (``jax.profiler.TraceAnnotation``, with ``args`` as the span's stats), on
  the host clock of the profiler's trace; while no profiler runs a span
  costs about a microsecond;
* it adds the phase's *self time* to ``metrics[name + "_s"]``: its duration
  on ``time.perf_counter`` less the durations of the phases opened inside
  it, so the counters of one step add up to the step's wall time.

The engine opens a few phases per decode window and none per token, so
the cost stays a few microseconds per window whether or not a profiler
records them.
"""

from __future__ import annotations

import contextlib
import time

import jax

SPAN_PREFIX = "engine."


class Phases:
    """Nested host phases, accumulated into a counter dict."""

    def __init__(self, metrics: dict):
        self.metrics = metrics
        self._inner: list[float] = []   # child time of each open phase

    @contextlib.contextmanager
    def phase(self, name: str, **args):
        self._inner.append(0.0)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args):
                yield
        finally:
            dur = time.perf_counter() - t0
            inner = self._inner.pop()
            key = name + "_s"
            self.metrics[key] = self.metrics.get(key, 0.0) + dur - inner
            if self._inner:
                self._inner[-1] += dur
