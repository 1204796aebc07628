"""Host-side phase tracing: named spans and driver-cache events.

Every ``span()`` opens a ``jax.profiler.TraceAnnotation``, so the name
shows up on the host timeline of a device profile when one is being taken
(and costs about a microsecond when none is). The profiler trace is the one
timeline: it already shares the host clock with the device's events.

Aggregated timings are opt-in. A ``Tracer`` installed with ``use()``
also sums each span's count and seconds for the scope, plus
``executor.cached_driver`` hit/miss events while ``attach()`` is active;
``summary()`` is the compact form a ``RunReport`` stores::

    with obs.trace.use(obs.trace.Tracer()) as tr, tr.attach():
        run()
    tr.summary()

Outside any ``use()`` a span records nothing on the host.
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

from repro.core import executor


class Tracer:
    """Span timings and driver-cache events of one scope, summed by name."""

    def __init__(self, name: str = "repro"):
        self.name = name
        self._spans: dict = {}     # name -> [count, total seconds]
        self._cache = {"hits": 0, "misses": 0, "bypass": 0}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            ent = self._spans.setdefault(name, [0, 0.0])
            ent[0] += 1
            ent[1] += time.perf_counter() - t0

    def _on_cache(self, key, kind: str) -> None:
        self._cache[kind] = self._cache.get(kind, 0) + 1

    @contextlib.contextmanager
    def attach(self):
        """Record driver-cache hit/miss/bypass events while active — a
        removable ``executor.cache_listener``, so nested tracers and
        ``RetraceMonitor``s each count their own events exactly once."""
        with executor.cache_listener(self._on_cache):
            yield self

    def cache_stats(self) -> dict:
        return dict(self._cache)

    def summary(self) -> dict:
        """Span timings aggregated by name (count + total seconds) — the
        compact form a RunReport stores."""
        spans = {name: {"count": count, "total_s": round(total, 6)}
                 for name, (count, total) in self._spans.items()}
        return {"spans": spans, "cache": self.cache_stats()}


_STACK: list = []


def current() -> Tracer | None:
    """The innermost ``use()`` scope's tracer, or None outside any."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def use(tracer: Tracer):
    """Install ``tracer`` as the active tracer within the scope."""
    _STACK.append(tracer)
    try:
        yield tracer
    finally:
        _STACK.remove(tracer)


def span(name: str):
    """``with obs.trace.span("x"): ...`` — a profiler annotation, timed on
    the active tracer when a ``use()`` scope is open."""
    tracer = current()
    return TraceAnnotation(name) if tracer is None else tracer.span(name)
